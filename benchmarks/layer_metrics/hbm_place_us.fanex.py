"""Host time to enqueue one placement, the h2d transfer and the landing
write, in us (program_counter): ``lens_hbm_busy_ns`` / ``lens_hbm_ops``.
Dispatch is asynchronous: this is not device time.

The ``.stream`` metric's formula under ``fanex4m_c8``: the same inbound path
(rendezvous wire, one ``device_put`` a message), with a batcher that answers
behind it."""


def read(run):
    c = run["counters"]
    if not c.get("lens_hbm_ops"):
        return None
    return c.get("lens_hbm_busy_ns", 0) / c["lens_hbm_ops"] / 1e3
