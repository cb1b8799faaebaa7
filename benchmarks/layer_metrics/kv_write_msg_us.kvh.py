"""The writing thread's dispatch time a message, in us (program_counter):
(``lens_kv_write_busy_ns`` + ``lens_kv_state_write_busy_ns``) /
``kv_messages_written`` / 1e3, ``tpurpc/serving/pages.py``: the wall time
of every page and state dispatch, with its waits for the interpreter, over
the messages they carried, however many a dispatch. Host time, not device
time. A program without the counter gives nothing to read."""


def read(run):
    c = run["counters"]
    if not c.get("kv_messages_written"):
        return None
    busy = (c.get("lens_kv_write_busy_ns", 0)
            + c.get("lens_kv_state_write_busy_ns", 0))
    return busy / c["kv_messages_written"] / 1e3
