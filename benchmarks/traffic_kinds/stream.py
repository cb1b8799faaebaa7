"""kind ``stream``: one bidi stream, messages back to back for the window.

Sends until ``seconds`` have passed, closes, and waits for the server's one
reply, which comes after ``block_until_ready`` on the connection's pool shard
and carries the number of messages the connection has had in all: that reply
is the acknowledgement of every message of the stream."""

from __future__ import annotations

import time

import numpy as np


def _stream(c, messages) -> int:
    replies = list(c.client.duplex(f"Put{c.conn}", messages, timeout=600))
    if len(replies) != 1:
        raise RuntimeError(f"{len(replies)} replies to one stream")
    return int(np.asarray(replies[0]["n"]).ravel()[0])


def warm(c) -> None:
    n = int(c.traffic["warmup_messages"])
    got = _stream(c, ({"x": c.bank.message(k)} for k in range(n)))
    if got != n:
        raise RuntimeError(f"warm-up: server counts {got} of {n}")
    c.seq = n


def run(c) -> dict:
    end = c.t0 + c.seconds
    first = c.seq
    times = {}

    def messages():
        while time.monotonic() < c.t0:
            time.sleep(0.0005)
        times["first"] = time.monotonic()
        while time.monotonic() < end:
            yield {"x": c.bank.message(c.seq)}
            c.seq += 1

    failed = 0
    try:
        acked = _stream(c, messages()) - first
    except Exception as exc:  # the stream failed: nothing was acknowledged
        acked, failed, times["error"] = 0, c.seq - first, repr(exc)[:300]
    last = time.monotonic()
    attempted = c.seq - first
    return {"attempted": attempted, "acked": acked,
            "failed": failed or attempted - acked,
            "t_first_send": times.get("first", last), "t_last_reply": last,
            "error": times.get("error")}
