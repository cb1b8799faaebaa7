"""Time the call's handler thread waited for its next message, in us per wait
(program_counter): ``lens_srv_recv_busy_ns`` / ``lens_srv_recv_ops``. With one
message in flight this is the rest of the round trip: the reply's way to the
client, the client's turn (check, let go, stamp, encode) and the next
message's way in. With ``srv_handler_us`` and ``srv_send_us`` it adds up to
one round trip."""


def read(run):
    c = run["counters"]
    if not c.get("lens_srv_recv_ops"):
        return None
    return c.get("lens_srv_recv_busy_ns", 0) / c["lens_srv_recv_ops"] / 1e3
