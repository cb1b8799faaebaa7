"""What the registered behavior does with one message besides decoding it,
in us per message (program_counter): (``lens_srv_handler_busy_ns`` -
``lens_decode_busy_ns``) / ``lens_srv_handler_ops``. The user's function and
its wait for the interpreter: the inside twin of ``bench.pool_put``."""


def read(run):
    c = run["counters"]
    if not c.get("lens_srv_handler_ops"):
        return None
    own = (c.get("lens_srv_handler_busy_ns", 0)
           - c.get("lens_decode_busy_ns", 0))
    return own / c["lens_srv_handler_ops"] / 1e3
