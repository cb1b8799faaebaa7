"""One state message's write dispatched, in us (program_counter):
``lens_kv_state_write_busy_ns`` / ``lens_kv_state_write_ops``, hop
``kv_state_write`` of ``tpurpc/obs/lens.py``: the program
``jit_tpurpc_kv_state`` handed to the device on the cache's one writing
thread (``tpurpc/serving/pages.py``), with its waits for the interpreter.
Dispatch is asynchronous: this is host time, not device time. A program
whose cache keeps no state gives nothing to read."""


def read(run):
    c = run["counters"]
    if not c.get("lens_kv_state_write_ops"):
        return None
    return (c.get("lens_kv_state_write_busy_ns", 0)
            / c["lens_kv_state_write_ops"] / 1e3)
