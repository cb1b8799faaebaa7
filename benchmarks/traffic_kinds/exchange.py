"""kind ``exchange``: one bidi stream a connection, ``in_flight`` requests
deep, a tensor back for every tensor sent.

A client sends request ``seq`` as soon as fewer than ``in_flight`` of its
requests are unanswered, with no think time, until the window ends; then it
closes its half and drains the replies still owed. An acknowledgement is a
reply received. Replies come in the order of the requests, so the ``k``-th
reply of the stream answers request ``k``: each is checked on arrival for
shape and dtype, and its first two words are recorded in that order. What a
reply must hold depends on the batch its request rode in, which only the
server's batch log knows, so the comparison with the seed is the
reference's ``check``, made in the parent from that log and from what this
process reports: one small ``Report<c>`` call after the window hands the
server every reply's stamp words as received, which replies were kept
whole (the sequence numbers ``plan_replies`` drew from the seed), their
checksums, and how many of their bytes differ from what their own stamp
words name (``sampled_bytes_wrong``, the reference's own function, run here
because the replies are here). A misshapen reply and a differing kept reply
are failed messages.

The reference is the configuration's (``config["reference"]``); the seed is
not among what ``client_main`` passes a kind, so it is read where
``client_main`` reads it: the specification in ``sys.argv[1]``.
"""

from __future__ import annotations

import importlib
import json
import sys
import threading
import time

import numpy as np

from benchmarks.harness.payloads import checksum_np


class _State:
    """What one connection has seen so far, warm-up included."""

    def __init__(self, c):
        self.seed = int(json.loads(sys.argv[1])["seed"])
        self.reference = importlib.import_module(
            f"benchmarks.configs.{c.config['reference']}")
        self.shape = tuple(c.config["message"]["shape"])
        self.dtype = np.dtype(c.config["message"]["dtype"])
        self.plan = set(self.reference.plan_replies(
            c.config, c.traffic, self.seed, c.conn))
        self.stamps: list = []          # (word 0, word 1) of every reply
        self.kept: dict = {}            # seq -> copy of the whole reply
        self.wrong = 0

    def take(self, seq: int, reply) -> None:
        y = reply["y"]
        if y.shape != self.shape or y.dtype != self.dtype:
            self.stamps.append((0, 0))
            self.wrong += 1
            return
        self.stamps.append(tuple(
            int(w) for w in y.reshape(-1)[:2].view(np.uint32)))
        if seq in self.plan:
            self.kept[seq] = np.array(y)


def _exchange(c, more) -> dict:
    """One pipelined stream while ``more()``; the first send waits for
    ``c.t0`` if there is one. ``c.seq`` counts the replies received."""
    st = c.exchange
    depth = int(c.traffic["in_flight"])
    window = threading.Semaphore(depth)
    first = c.seq
    state = {"sent": c.seq, "first": None, "last": None, "error": None,
             "broken": False}

    def messages():
        while c.t0 is not None and time.monotonic() < c.t0:
            time.sleep(0.0005)
        state["first"] = time.monotonic()
        while True:
            # a slot frees when a reply comes; look up now and then so that
            # a stream that broke cannot park this thread for good
            while not window.acquire(timeout=0.25):
                if state["broken"]:
                    return
            if state["broken"] or not more():
                return
            yield {"x": c.bank.message(state["sent"])}
            state["sent"] += 1

    try:
        for reply in c.client.duplex(f"Swap{c.conn}", messages(),
                                     timeout=600):
            state["last"] = time.monotonic()
            st.take(c.seq, reply)
            del reply
            c.seq += 1
            window.release()
    except Exception as exc:  # the stream failed: what was in flight is lost
        state["error"] = repr(exc)[:300]
    state["broken"] = True
    window.release()
    return dict(state, acked=c.seq - first, attempted=state["sent"] - first)


def warm(c) -> None:
    c.exchange = _State(c)
    n = int(c.traffic["warmup_messages"])
    sent = [0]

    def more():
        sent[0] += 1
        return sent[0] <= n

    got = _exchange(c, more)
    # a wrong reply here is no reason to stop: it is counted, and the run's
    # result reads not correct (the control plants such faults)
    if got["error"] or got["acked"] != n:
        raise RuntimeError(f"warm-up: {got}")


def run(c) -> dict:
    st = c.exchange
    end = c.t0 + c.seconds
    got = _exchange(c, lambda: time.monotonic() < end)
    now = time.monotonic()
    bytes_wrong = st.reference.sampled_bytes_wrong(
        c.config, c.traffic, st.seed, st.kept)
    # a request that got no reply was attempted and failed; a reply that is
    # misshapen, or differs from what its stamps name, is a failed message
    # too (the warm-up's replies are among those checked)
    failed = (got["attempted"] - got["acked"] + st.wrong
              + (bytes_wrong > 0))
    error = got["error"]
    if st.wrong or bytes_wrong:
        error = (f"{st.wrong} misshapen replies, {bytes_wrong} differing "
                 f"bytes in the {len(st.kept)} replies kept whole; {error}")
    kept = sorted(st.kept)
    try:
        c.client.call(f"Report{c.conn}", {
            "first": np.int64(0),
            "stamps": np.array(st.stamps, np.uint32).reshape(-1, 2),
            "sampled": np.array(kept, np.int64),
            "sample_sums": np.array([checksum_np(st.kept[k]) for k in kept],
                                    np.int64),
            "sample_bytes_wrong": np.int64(bytes_wrong)}, timeout=120)
    except Exception as exc:  # the audit then finds no report: not correct
        error = f"{error}; report: {exc!r}"[:600]
    return {"attempted": got["attempted"], "acked": got["acked"],
            "failed": failed, "t_first_send": got["first"] or now,
            "t_last_reply": got["last"] or now, "error": error,
            "replies_kept": kept}
