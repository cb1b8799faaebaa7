"""Time placements waited for ring credit, in us per placement
(program_counter): ``lens_hbm_credit_busy_ns`` / ``lens_hbm_ops``; 0 where
none blocked.

The ``.stream`` metric's formula under ``fanex4m_c8``: a row holds its
credit until its batch has been stacked, so a connection whose four rows of
window are all in the batcher waits here. With the batcher's thread held
back by the bound on batches in flight to the host, this is where the
push-back on the senders shows."""


def read(run):
    c = run["counters"]
    if not c.get("lens_hbm_ops"):
        return None
    return c.get("lens_hbm_credit_busy_ns", 0) / c["lens_hbm_ops"] / 1e3
