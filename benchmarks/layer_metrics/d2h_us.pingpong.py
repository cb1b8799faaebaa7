"""The wait for a reply's device-to-host transfers, in us per reply that had a
device leaf (program_counter): ``lens_d2h_busy_ns`` / ``lens_d2h_ops``.
Starting every leaf's transfer and awaiting each; the wait covers what the
device still had to finish for them (the message's own h2d, the swap
program). A program with no ``d2h`` stage gives nothing to read."""


def read(run):
    c = run["counters"]
    if not c.get("lens_d2h_ops"):
        return None
    return c.get("lens_d2h_busy_ns", 0) / c["lens_d2h_ops"] / 1e3
