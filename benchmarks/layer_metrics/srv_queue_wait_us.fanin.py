"""Time a delivered message sat on its call's queue before the handler
thread came for it, in us per message (program_counter):
``native_srv_queue_ns`` / ``native_srv_queue_msgs`` on the C plane,
``lens_srv_queue_busy_ns`` / ``lens_srv_queue_ops`` on the Python plane.

The ``.stream`` metric's formula under ``fanin4m_c8``. A message waits here
while its connection's handler thread lands the one before it or waits for
device credit (``hbm_credit_wait_us``), in a landing region it holds all that
while: several batch periods long when the batcher is the pace."""


def read(run):
    c = run["counters"]
    msgs = c.get("native_srv_queue_msgs", 0) + c.get("lens_srv_queue_ops", 0)
    if not msgs:
        return None
    ns = c.get("native_srv_queue_ns", 0) + c.get("lens_srv_queue_busy_ns", 0)
    return ns / msgs / 1e3
