"""Time from one answered batch to the next, in us (program_counter): the
time the server spent in calls (``lens_srv_call_busy_ns``) over the cell's
streams (``connections`` of its traffic mix, read from the mix's file) and
over the batches consumed in the window (``batcher_batches``).
``batch_period_us.fanin``'s quantity under ``fanex4m_c8``, where the
window's calls are the eight ``Swap<c>`` streams, each of which lasts the
window, and the clients' eight ``Report<c>`` calls after it (a few ms each,
left in the sum: under a thousandth of it), so the mean is taken over the
streams and not over ``lens_srv_call_ops``. The streams open up to 0.25 s
before the window does, so it reads 1 to 2% over window / batches. The one
number the stages of a batch are read against. A program whose batcher counts
no batch, or a window in which no call ended, gives nothing to read."""

import json
import os

TRAFFIC = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir,
                       "traffic", "exchange_c8.json")


def read(run):
    c = run["counters"]
    batches = c.get("batcher_batches")
    if not batches or not c.get("lens_srv_call_ops"):
        return None
    with open(TRAFFIC) as f:
        streams = int(json.load(f)["connections"])
    return c.get("lens_srv_call_busy_ns", 0) / streams / batches / 1e3
