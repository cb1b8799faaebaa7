"""The landing's share of the HBM roofline, in % (device_trace): the least
time the chip needs for the traced span's payload, each payload byte written
to HBM once, over the time the device was busy in that span.

The ``.stream`` metric's formula under ``fanin4m_c8``. The busy time here
also holds the stack (read n, write n) and the consumer's pool write (read n,
write n), so the share cannot pass 20%: it says how much of the device's work
is more than any landing must do, and ``batch_stack_roofline`` says how well
the stack's part of it runs."""


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("busy_s") or not trace.get("payload_bytes"):
        return None
    least_s = trace["payload_bytes"] / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]
