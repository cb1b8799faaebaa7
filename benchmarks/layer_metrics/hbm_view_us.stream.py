"""Host time to enqueue one view of a placed span (slice or window, then
``shaped``), in us (program_counter): ``lens_hbm_view_busy_ns`` /
``lens_hbm_view_ops``."""


def read(run):
    c = run["counters"]
    if not c.get("lens_hbm_view_ops"):
        return None
    return c.get("lens_hbm_view_busy_ns", 0) / c["lens_hbm_view_ops"] / 1e3
