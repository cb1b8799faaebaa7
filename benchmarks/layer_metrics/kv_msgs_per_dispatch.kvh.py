"""Messages a dispatch of the page cache's writing thread (program_counter):
``kv_messages_written`` / (``lens_kv_write_ops`` +
``lens_kv_state_write_ops``), ``tpurpc/serving/pages.py``: a dispatch
takes a message and the same sequence's next ones that it finds queued
behind it (a run of full-chunk page layers, or of state layers), so 1.0 is
a writer that keeps up and more is one behind whose runs share a program
call. A program without the counter gives nothing to read."""


def read(run):
    c = run["counters"]
    ops = c.get("lens_kv_write_ops", 0) + c.get("lens_kv_state_write_ops", 0)
    if not c.get("kv_messages_written") or not ops:
        return None
    return c["kv_messages_written"] / ops
