"""Decode's own host time, in us per decoded message (program_counter):
(``lens_decode_busy_ns`` less its children ``hbm_credit``, ``hbm`` and
``hbm_view``) / ``lens_decode_ops``: header parse, unflatten, lease
bookkeeping, and the wait to have the interpreter back from the batcher's
thread and the other seven handlers. The ``.stream`` metric's formula under
``fanin4m_c8``."""


def read(run):
    c = run["counters"]
    if not c.get("lens_decode_ops"):
        return None
    own = c.get("lens_decode_busy_ns", 0) - sum(
        c.get(f"lens_{hop}_busy_ns", 0)
        for hop in ("hbm_credit", "hbm", "hbm_view"))
    return own / c["lens_decode_ops"] / 1e3
