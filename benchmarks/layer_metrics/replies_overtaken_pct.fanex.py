"""Share of the deferred replies that were resolved while an earlier reply
of their stream was not, in % (program_counter): ``srv_replies_overtaken`` /
``srv_replies_deferred``. What the ordered writer is for: with four
completion threads two batches can come back out of order, and a stream with
a row in each then has its later reply ready first. A share of replies, not
of a peak: 0 is a true reading, given as long as any reply was deferred."""


def read(run):
    c = run["counters"]
    if not c.get("srv_replies_deferred"):
        return None
    return 100.0 * c.get("srv_replies_overtaken", 0) / c[
        "srv_replies_deferred"]
