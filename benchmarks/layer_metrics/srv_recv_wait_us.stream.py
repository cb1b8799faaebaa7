"""Time a call's handler thread waited for its next message, in us per wait
(program_counter): ``lens_srv_recv_busy_ns`` / ``lens_srv_recv_ops``. The
wire's side of the hand-over: it RISES when the server stops being the slower
side. One wait a message, plus each call's wait for its first message and for
the end of its stream."""


def read(run):
    c = run["counters"]
    if not c.get("lens_srv_recv_ops"):
        return None
    return c.get("lens_srv_recv_busy_ns", 0) / c["lens_srv_recv_ops"] / 1e3
