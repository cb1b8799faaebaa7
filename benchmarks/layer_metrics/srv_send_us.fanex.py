"""Writing one response, in us per response (program_counter):
``lens_srv_send_busy_ns`` / ``lens_srv_send_ops``. In ``fanex4m_c8`` a reply
is written by the thread that resolved it (or the last reply before it): one
of the batcher's completion threads, which serializes the row (a view of the
batch's one host buffer: header and gather list, no copy) and places it
one-sided into the client's landing region, eight a batch one after the
other. The wait for rendezvous credit on the server's side is in it. The
eight ``Report<c>`` answers after the window are among the ops (a few tens
of us each)."""


def read(run):
    c = run["counters"]
    if not c.get("lens_srv_send_ops"):
        return None
    return c.get("lens_srv_send_busy_ns", 0) / c["lens_srv_send_ops"] / 1e3
