"""What the stream's handler does with one message besides decoding it, in
us per message (program_counter): (``lens_srv_handler_busy_ns`` -
``lens_decode_busy_ns``) / ``lens_srv_handler_ops``.

``srv_handler_self_us.stream``'s formula, and another thing in this cell:
the handler does not consume the message. It is the HAND-OVER: the handler
takes the message's leases (``DeviceRequests.take_leases``) and gives the row
to the batcher (``FanInBatcher.submit``: the wait for the batcher's one
queue lock, an append, a wake-up), with the interpreter waits in between.
The batch's stack and the consumer run on the batcher's thread and are read
by ``batch_stack_us`` and ``batch_run_us``."""


def read(run):
    c = run["counters"]
    if not c.get("lens_srv_handler_ops"):
        return None
    own = (c.get("lens_srv_handler_busy_ns", 0)
           - c.get("lens_decode_busy_ns", 0))
    return own / c["lens_srv_handler_ops"] / 1e3
