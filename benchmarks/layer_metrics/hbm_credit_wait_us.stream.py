"""Time placements waited for ring credit, in us per placement
(program_counter): ``lens_hbm_credit_busy_ns`` / ``lens_hbm_ops``; 0 where
none blocked."""


def read(run):
    c = run["counters"]
    if not c.get("lens_hbm_ops"):
        return None
    return c.get("lens_hbm_credit_busy_ns", 0) / c["lens_hbm_ops"] / 1e3
