"""The landing's share of the HBM roofline, in % (device_trace): the least
time the chip needs for the traced span's payload over the time the device
was busy in that span. The work counted is what ANY landing must do, each
payload byte written to HBM once, not what today's three movements move; the
pool write (read n, write n) is always inside the busy time, so the share
cannot pass 50%."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("busy_s") or not trace.get("payload_bytes"):
        return None
    least_s = trace["payload_bytes"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]
