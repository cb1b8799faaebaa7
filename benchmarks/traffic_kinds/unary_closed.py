"""kind ``unary_closed``: one call in flight, the next when the reply is in.

RPC callers wait for replies, so this is a closed loop. Every call is timed
from send to reply in exact nanoseconds. After the last call of the window one
``Sync`` call waits for the connection's pool shard (the device side of the
last calls), and the window's end is that reply."""

from __future__ import annotations

import time

import numpy as np


def _call(c) -> bool:
    reply = c.client.call(f"Put{c.conn}", {"x": c.bank.message(c.seq)},
                          timeout=60)
    ok = int(np.asarray(reply["seq"]).ravel()[0]) == c.seq
    c.seq += 1
    return ok


def warm(c) -> None:
    for _ in range(int(c.traffic["warmup_messages"])):
        if not _call(c):
            raise RuntimeError("warm-up: reply out of sequence")
    c.client.call(f"Sync{c.conn}", {"c": np.int32(c.conn)}, timeout=60)


def run(c) -> dict:
    end_ns = int((c.t0 + c.seconds) * 1e9)
    first, failed, latency, error = c.seq, 0, [], None
    while time.monotonic() < c.t0:
        time.sleep(0.0005)
    t_first = time.monotonic()
    now = time.monotonic_ns()
    while now < end_ns:
        try:
            ok = _call(c)
        except Exception as exc:  # counted; a dead connection ends the loop
            failed, error = failed + 1, repr(exc)[:300]
            c.seq += 1
            break
        done = time.monotonic_ns()
        latency.append(done - now)
        failed += not ok
        now = done
    reply = c.client.call(f"Sync{c.conn}", {"c": np.int32(c.conn)},
                          timeout=120)
    last = time.monotonic()
    attempted = c.seq - first
    return {"attempted": attempted, "acked": attempted - failed,
            "failed": failed, "t_first_send": t_first, "t_last_reply": last,
            "server_n": int(np.asarray(reply["n"]).ravel()[0]),
            "latency_ns": latency, "error": error}
