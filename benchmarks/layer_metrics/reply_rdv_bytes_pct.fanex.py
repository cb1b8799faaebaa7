"""Share of the reply bytes that left the server by rendezvous, in %
(program_counter): ``rdv_bytes_sent`` (message bytes the server's sender
role placed one-sided into the clients' landing regions) over the payload
acknowledged (a reply is as large as its request). Region bytes include the
codec's header, so a run that never falls back reads a few thousandths over
100: a share of traffic, not of a peak. In ``fanex4m_c8`` the server's eight
links send under load for the first time, and wait for credit by the
sender's rule where a client's window is full. A program without the
counter (or whose every reply left framed: the window's counters hold only
what changed) gives nothing to read."""


def read(run):
    c = run["counters"]
    if not run["payload_bytes"] or "rdv_bytes_sent" not in c:
        return None
    return 100.0 * c["rdv_bytes_sent"] / run["payload_bytes"]
