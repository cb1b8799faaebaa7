"""What giving the interpreter up for a reply's copy costs the thread that did,
in us per placement (program_counter): ``lens_place_return_busy_ns`` /
``lens_place_return_ops``, hop ``place_return`` of ``tpurpc/obs/lens.py``: from
the stamp ``tpr_place`` takes in C when its copy is done until
``place_released`` runs again (``core/rendezvous.py``). One op a placement the
server made by the native call: a reply. A program without the hop gives
nothing to read."""


def read(run):
    c = run["counters"]
    ops = c.get("lens_place_return_ops")
    if not ops:
        return None
    return c.get("lens_place_return_busy_ns", 0) / ops / 1e3
