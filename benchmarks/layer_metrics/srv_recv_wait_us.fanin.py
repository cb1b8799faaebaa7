"""Time a call's handler thread waited for its next message, in us per wait
(program_counter): ``lens_srv_recv_busy_ns`` / ``lens_srv_recv_ops``.

The ``.stream`` metric's formula under ``fanin4m_c8``. Near 0 here means the
senders are ahead and the server is the pace; it RISES when the batcher and
the landing stop being the slower side."""


def read(run):
    c = run["counters"]
    if not c.get("lens_srv_recv_ops"):
        return None
    return c.get("lens_srv_recv_busy_ns", 0) / c["lens_srv_recv_ops"] / 1e3
