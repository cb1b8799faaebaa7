"""Share of the traced span in which no operation ran on the device, in %:
1 - union of the device's op intervals / traced span (device_trace).

The ``.stream`` metric's formula under ``fanex4m_c8``: the same inbound path
(rendezvous wire, one ``device_put`` a message), with a batcher that answers
behind it."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("window_s") or not trace.get("busy_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
