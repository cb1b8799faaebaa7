"""Host memcpy bytes billed in the CLIENT processes per payload byte
(program_counter): the clients' summed ledger ``host_copy`` over the window /
the payload acknowledged. The clients are where replies land: a reply that
fell back to the framed ring is copied out of it there (and a request sent
framed is copied into the ring there), so this is the part of
``host_copy_b_per_b.fanex`` that the way out can move. 0.0 is a true reading:
nothing was copied on a client."""


def read(run):
    if not run["payload_bytes"]:
        return None
    return run["client_ledger"].get("host_copy", 0) / run["payload_bytes"]
