"""kind ``pingpong``: one bidi stream, one message in flight.

Send message ``seq``, wait for its reply, check the reply's shape and its two
stamp words against what the seed says the slot held, let go of the reply,
send ``seq + 1``; for the window. An acknowledgement is a reply received. The
replies whose sequence numbers the reference drew from the seed
(``pingpong_reference.plan_replies``) are kept as copies and compared whole
after the window, in this process, by the reference's own function. Every
wrong stamp and every differing kept reply is a failed message; and one small
``Report<c>`` call after the window hands the server what was seen (every
stamp as received, which replies were kept, their differing bytes), which the
handler gives back in the audit so that the reference's ``check`` holds the
replies *as the client received them* to the seed by name.

The seed is not among what ``client_main`` passes a kind, so it is read where
``client_main`` reads it: the specification in ``sys.argv[1]``.
"""

from __future__ import annotations

import json
import queue
import sys
import time

import numpy as np

from benchmarks.configs import pingpong_reference as reference


class _State:
    """What one connection has seen so far, warm-up included."""

    def __init__(self, c):
        self.seed = int(json.loads(sys.argv[1])["seed"])
        _, self.slots, self.words = reference.geometry(c.config, c.traffic)
        self.shape = tuple(c.config["message"]["shape"])
        self.dtype = np.dtype(c.config["message"]["dtype"])
        self.fresh = reference.init_stamps(self.seed, c.conn, self.slots,
                                           self.words)
        self.plan = set(reference.plan_replies(c.config, c.traffic,
                                               self.seed, c.conn))
        self.stamps: list = []          # (word 0, word 1) of every reply
        self.kept: dict = {}            # seq -> copy of the whole reply
        self.wrong = 0

    def take(self, c, seq: int, reply) -> None:
        y = reply["y"]
        if y.shape != self.shape or y.dtype != self.dtype:
            self.stamps.append((0, 0))
            self.wrong += 1
            return
        w0, w1 = (int(w) for w in y.reshape(-1)[:2].view(np.uint32))
        self.stamps.append((w0, w1))
        want = (tuple(int(w) for w in self.fresh[seq]) if seq < self.slots
                else ((seq - self.slots) & 0xFFFFFFFF, c.conn))
        self.wrong += (w0, w1) != want
        if seq in self.plan:
            self.kept[seq] = np.array(y)


def _exchange(c, more) -> dict:
    """Ping-pong on one stream while ``more()``; the first send waits for
    ``c.t0`` if there is one."""
    st = c.pingpong
    todo: "queue.SimpleQueue" = queue.SimpleQueue()

    def messages():
        while True:
            seq = todo.get()
            if seq is None:
                return
            yield {"x": c.bank.message(seq)}

    first = c.seq
    times = {"first": None, "last": None, "error": None}
    while c.t0 is not None and time.monotonic() < c.t0:
        time.sleep(0.0005)
    try:
        replies = c.client.duplex(f"Swap{c.conn}", messages(), timeout=600)
        times["first"] = time.monotonic()
        todo.put(c.seq)
        for reply in replies:
            times["last"] = time.monotonic()
            st.take(c, c.seq, reply)
            del reply
            c.seq += 1
            todo.put(c.seq if more() else None)
    except Exception as exc:  # the stream failed: what was in flight is lost
        times["error"] = repr(exc)[:300]
        todo.put(None)
    return dict(times, acked=c.seq - first)


def warm(c) -> None:
    c.pingpong = _State(c)
    n = int(c.traffic["warmup_messages"])
    got = _exchange(c, lambda: c.seq < n)
    # a wrong reply here is no reason to stop: it is counted, and the run's
    # result reads not correct (the control plants such faults)
    if got["error"] or got["acked"] != n:
        raise RuntimeError(f"warm-up: {got}")


def run(c) -> dict:
    st = c.pingpong
    end = c.t0 + c.seconds
    got = _exchange(c, lambda: time.monotonic() < end)
    now = time.monotonic()
    bytes_wrong = reference.sampled_bytes_wrong(
        c.config, c.traffic, st.seed, c.conn, st.kept)
    # a message that got no reply was attempted and failed; a reply that is
    # not what the seed says it must be is a failed message too (the
    # warm-up's replies are among those checked)
    attempted = got["acked"] + (got["error"] is not None)
    failed = attempted - got["acked"] + st.wrong + (bytes_wrong > 0)
    error = got["error"]
    if st.wrong or bytes_wrong:
        error = (f"{st.wrong} replies with a wrong shape or stamp, "
                 f"{bytes_wrong} differing bytes in the {len(st.kept)} "
                 f"replies kept whole; {error}")
    try:
        c.client.call(f"Report{c.conn}", {
            "first": np.int64(0),
            "stamps": np.array(st.stamps, np.uint32).reshape(-1, 2),
            "sampled": np.array(sorted(st.kept), np.int64),
            "sample_bytes_wrong": np.int64(bytes_wrong)}, timeout=120)
    except Exception as exc:  # the audit then finds no report: not correct
        error = f"{error}; report: {exc!r}"[:600]
    return {"attempted": attempted, "acked": got["acked"], "failed": failed,
            "t_first_send": got["first"] or now,
            "t_last_reply": got["last"] or now, "error": error,
            "replies_kept": sorted(st.kept)}
