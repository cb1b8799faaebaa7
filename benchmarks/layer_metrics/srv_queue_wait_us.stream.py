"""Time a delivered message sat on its call's queue before the handler
thread came for it, in us per message (program_counter):
``native_srv_queue_ns`` / ``native_srv_queue_msgs`` on the C plane,
``lens_srv_queue_busy_ns`` / ``lens_srv_queue_ops`` on the Python plane; a
message crosses one of the two. Over one message time, the handler is the
slower side; near 0, the wire is."""


def read(run):
    c = run["counters"]
    msgs = c.get("native_srv_queue_msgs", 0) + c.get("lens_srv_queue_ops", 0)
    if not msgs:
        return None
    ns = c.get("native_srv_queue_ns", 0) + c.get("lens_srv_queue_busy_ns", 0)
    return ns / msgs / 1e3
