"""What the registered behavior does with one message, in us per message
(program_counter): ``lens_srv_handler_busy_ns`` / ``lens_srv_handler_ops``:
landing (``decode``), the pool swap's dispatch, the reply's ``d2h`` wait and
its encoding (``device``); the write of the reply (``srv_send``) is taken
out of it."""


def read(run):
    c = run["counters"]
    if not c.get("lens_srv_handler_ops"):
        return None
    return (c.get("lens_srv_handler_busy_ns", 0)
            / c["lens_srv_handler_ops"] / 1e3)
