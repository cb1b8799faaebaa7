"""``FanInBatcher``'s reply half (ISSUE 36): the read-back is billed once an
output leaf, and a leaf the host cannot address that is larger than
``_D2H_PIECE_BYTES`` comes back in pieces, cut on the device by one program,
each request still getting exactly its rows."""

import numpy as np
import pytest

from tpurpc.jaxshim import FanInBatcher
from tpurpc.jaxshim import service
from tpurpc.tpu import ledger, serialize

ROW = 64                     # floats a row: 256 B


def moved(before):
    after = ledger.snapshot()
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


@pytest.fixture
def away(monkeypatch):
    """Every jax array reads as out of the host's reach, as on a TPU (the
    CPU has no device the host cannot address: ``tests/test_device_reply.py``
    does the same), and a piece is at most three rows."""
    import jax

    monkeypatch.setattr(serialize, "_on_device",
                        lambda x: isinstance(x, jax.Array))
    monkeypatch.setattr(service, "_D2H_PIECE_BYTES", 3 * ROW * 4)


def double(batch):
    import jax.numpy as jnp

    return {"y": jnp.asarray(batch["x"]) * 2}


@pytest.mark.parametrize("rows,lo,hi", [
    (r, lo, hi) for r, cuts in ((8, (0, 3, 6, 8)), (5, (0, 2, 4, 5)))
    for lo in range(r) for hi in range(lo + 1, r + 1)
    if (lo, hi) in ((0, 1), (2, 3), (1, 4), (2, 7), (0, r), (r - 1, r),
                    (3, 6), (4, 5))])
def test_a_cut_leaf_is_indexed_like_the_leaf_it_stands_for(rows, lo, hi):
    whole = np.arange(rows * 4, dtype=np.float32).reshape(rows, 4)
    cuts = (0, 3, 6, 8) if rows == 8 else (0, 2, 4, 5)
    cut = service._Cut([whole[a:b] for a, b in zip(cuts, cuts[1:])], cuts)
    assert cut.nbytes == whole.nbytes
    assert np.array_equal(cut[lo:hi], whole[lo:hi])
    assert np.array_equal(cut[lo], whole[lo]) and cut[lo].shape == (4,)
    inside = any(a <= lo and hi <= b for a, b in zip(cuts, cuts[1:]))
    # rows that lie in one piece are a view of it, not a copy
    assert np.shares_memory(cut[lo:hi], whole) == inside


def test_a_large_result_comes_back_in_pieces_and_is_billed_once(away):
    seen = []
    real = service._cut_program

    def spy(cuts):
        seen.append(cuts)
        return real(cuts)

    service._cut_program, before = spy, ledger.snapshot()
    batcher = FanInBatcher(double, max_batch=8, max_delay_s=0.05,
                           fixed_bucket=True)
    try:
        sizes = [1, 2, 1, 3, 1]       # rows 1-2 in one piece, 4-6 in two
        futures = [batcher.submit({"x": np.full((n, ROW), k, np.float32)})
                   for k, n in enumerate(sizes)]
        for k, (fut, n) in enumerate(zip(futures, sizes)):
            y = fut.result(30)["y"]
            assert isinstance(y, np.ndarray) and y.shape == (n, ROW)
            assert (y == 2 * k).all()
        one = batcher.submit({"x": np.full(ROW, 7, np.float32)},
                             one_row=True).result(30)["y"]
        assert one.shape == (ROW,) and (one == 14).all()
    finally:
        service._cut_program = real
        batcher.close()
    assert seen == [(0, 3, 6, 8)] * 2
    got = moved(before)
    # two batches of 8 rows: each leaf billed once, whole, however many
    # transfers carried it
    assert got["dma_d2h"] == 2 * 8 * ROW * 4 and got["dma_d2h_ops"] == 2
    assert "zero_copy" not in got


def test_a_small_result_and_a_host_result_are_not_cut(away, monkeypatch):
    monkeypatch.setattr(service, "_cut_program", lambda cuts: 1 / 0)
    before = ledger.snapshot()
    batcher = FanInBatcher(double, max_batch=2, max_delay_s=0.01,
                           fixed_bucket=True)
    try:
        y = batcher.submit({"x": np.ones((1, ROW), np.float32)}).result(
            30)["y"]
        assert (y == 2).all()         # 2 rows of 256 B: under a piece
    finally:
        batcher.close()
    assert moved(before)["dma_d2h"] == 2 * ROW * 4
    monkeypatch.undo()                # the host can address every leaf again
    monkeypatch.setattr(service, "_D2H_PIECE_BYTES", ROW * 4)
    monkeypatch.setattr(service, "_cut_program", lambda cuts: 1 / 0)
    before = ledger.snapshot()
    batcher = FanInBatcher(double, max_batch=4, max_delay_s=0.01,
                           fixed_bucket=True)
    try:
        y = batcher.submit({"x": np.ones((3, ROW), np.float32)}).result(
            30)["y"]
        assert y.shape == (3, ROW) and (y == 2).all()
    finally:
        batcher.close()
    got = moved(before)
    assert "dma_d2h" not in got and got["zero_copy"] == 4 * ROW * 4


def test_a_consumer_that_returns_nothing_meets_no_read_back(away):
    before = ledger.snapshot()
    batcher = FanInBatcher(lambda batch: None, max_batch=2, max_delay_s=0.01)
    try:
        assert batcher.submit(
            {"x": np.ones((1, ROW), np.float32)}).result(30) is None
    finally:
        batcher.close()
    assert "dma_d2h" not in moved(before)
