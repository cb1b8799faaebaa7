"""A resolved reply's wait for its turn, in us per deferred reply
(program_counter): ``lens_srv_reply_wait_busy_ns`` /
``lens_srv_reply_wait_ops``, hop ``srv_reply_wait`` of
``tpurpc/obs/lens.py``: from the resolution of a reply's future to the start
of its ``srv_send`` (``rpc/server.py`` ``_DeferredReplies``). The wait behind
an earlier reply of its stream that was not resolved or not written yet, and
for the thread that writes. Counters only: no one thread's time. A program
whose streams cannot answer with a future gives nothing to read."""


def read(run):
    c = run["counters"]
    if not c.get("lens_srv_reply_wait_ops"):
        return None
    return (c.get("lens_srv_reply_wait_busy_ns", 0)
            / c["lens_srv_reply_wait_ops"] / 1e3)
