"""kind ``kv_handoff_hybrid``: a prefill worker handing finished sequences of
a hybrid model to a decode instance, one duplex stream a connection: the
latent KV of its full-attention layers as pages, and the recurrent state of
its linear-attention layers, one state a layer.

Each sequence is one offer ``{"offer": int32[2]}``, its ``(seq,
n_tokens)``, then its messages as layer-wise chunked prefill emits them
(``kvhyb_reference.order``): every chunk but the last as its page layers,
``{"pages": uint16[n, T, W]}`` each, as ``kv_handoff`` sends them; the last
chunk in model layer order, a page layer as its pages and a state layer as
one state message, ``{leaf: words, ...}`` (the state is final only once the
last chunk has run). Pages and states travel as their 16-bit and 32-bit
words: a float never travels (PERF.md 6). The loop is ``kv_handoff``'s: a
client starts a sequence as soon as fewer than ``in_flight`` of its
sequences are unacknowledged, with no think time, until the window ends;
one acknowledgement ``{"seq", "pages"}`` a sequence, in order.

The unit of count is the configuration's ``message.bytes``, of which a
page-layer and a state are whole numbers (``kvhyb_reference.units``):
``attempted`` and ``acked`` are the units of the sequences started and
acknowledged in the window, so ``acked x bytes`` is the KV and state
delivered resident. Lengths from the traffic file's trace seed, bytes from
``--seed`` (read from ``sys.argv[1]``, as ``kv_handoff`` reads it), through
the reference's ``Pages`` and ``States``.
"""

from __future__ import annotations

import json
import sys
import threading
import time

import numpy as np

from benchmarks.configs import kvhyb_reference as ref


class _Gen:
    """One connection's generator: its offers, pages and states."""

    def __init__(self, c):
        self.config = c.config
        _, _, self.tokens, self.width, _ = ref.geometry(c.config)
        seed = int(json.loads(sys.argv[1])["seed"])
        self.offers = ref.Offers(c.config, c.traffic, c.conn)
        self.pages = ref.Pages(c.config, seed, c.conn)
        self.states = ref.States(c.config, seed, c.conn)
        self.names = [name for name, _, _ in ref.state_geometry(c.config)[1]]
        self.wrong = 0

    def page_count(self, ordinal: int) -> int:
        return -(-self.offers.tokens(ordinal) // self.tokens)

    def units(self, ordinal: int) -> int:
        return ref.seq_units(self.config, self.page_count(ordinal))

    def messages_of(self, ordinal: int):
        """Sequence ``ordinal``: its offer, then every message."""
        n_tok = self.offers.tokens(ordinal)
        yield {"offer": np.array([ordinal, n_tok], np.int32)}
        for msg in ref.order(self.config, self.page_count(ordinal)):
            if msg[0] == "s":
                yield dict(zip(self.names,
                               self.states.words_of(ordinal, msg[1])))
                continue
            _, layer, first, n = msg
            out = np.empty((n, self.tokens * self.width), np.uint16)
            self.pages.fill(out, ordinal, layer, first)
            yield {"pages": out.reshape(n, self.tokens, self.width)}


def _handoff(c, more) -> dict:
    """One stream of sequences while ``more()``; the first send waits for
    ``c.t0`` if there is one. ``c.seq`` counts the sequences acknowledged,
    ``c.offered`` those started."""
    gen = c.kvh
    window = threading.Semaphore(int(c.traffic["in_flight"]))
    state = {"first": None, "last": None, "error": None, "broken": False,
             "sent": 0, "acked": 0}

    def messages():
        while c.t0 is not None and time.monotonic() < c.t0:
            time.sleep(0.0005)
        state["first"] = time.monotonic()
        while True:
            # a slot frees when an acknowledgement comes; look up now and
            # then so that a stream that broke cannot park this thread
            while not window.acquire(timeout=0.25):
                if state["broken"]:
                    return
            if state["broken"] or not more():
                return
            ordinal = c.offered
            c.offered += 1
            state["sent"] += gen.units(ordinal)
            yield from gen.messages_of(ordinal)

    try:
        for reply in c.client.duplex(f"KvHandoff{c.conn}", messages(),
                                     timeout=600):
            state["last"] = time.monotonic()
            if (int(np.ravel(reply["seq"])[0]) != c.seq
                    or int(np.ravel(reply["pages"])[0])
                    != gen.page_count(c.seq)):
                gen.wrong += 1
            else:
                state["acked"] += gen.units(c.seq)
            c.seq += 1
            window.release()
    except Exception as exc:  # the stream failed: what was in flight is lost
        state["error"] = repr(exc)[:300]
    state["broken"] = True
    window.release()
    return state


def warm(c) -> None:
    """The warm-up's sequences (``warmup_pages``: a few short ones, each
    with all its states, so that the stream, both wire paths, both write
    programs and the acknowledgement have run)."""
    c.kvh = _Gen(c)
    c.offered = 0
    n = len(c.traffic["warmup_pages"])
    got = _handoff(c, lambda: c.offered < n)
    if got["error"] or c.seq != n or c.kvh.wrong:
        raise RuntimeError(f"warm-up: {got}, {c.seq} of {n} acknowledged")


def run(c) -> dict:
    end = c.t0 + c.seconds
    got = _handoff(c, lambda: time.monotonic() < end)
    now = time.monotonic()
    wrong = c.kvh.wrong
    error = got["error"]
    if wrong:
        error = f"{wrong} acknowledgements out of order; {error}"
    return {"attempted": got["sent"], "acked": got["acked"],
            "failed": got["sent"] - got["acked"] + wrong,
            "t_first_send": got["first"] or now,
            "t_last_reply": got["last"] or now, "error": error,
            "sequences": c.seq}
