"""Writing one response, in us per response (program_counter):
``lens_srv_send_busy_ns`` / ``lens_srv_send_ops``. A ``device=True`` reply is
serialized inside the behavior (``d2h`` and ``device``), so this is the
writer's part: the one-sided rendezvous write into the client's landing
region and its completion record."""


def read(run):
    c = run["counters"]
    if not c.get("lens_srv_send_ops"):
        return None
    return c.get("lens_srv_send_busy_ns", 0) / c["lens_srv_send_ops"] / 1e3
