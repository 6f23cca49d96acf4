"""Logical work counts: hand-worked at tiny sizes, and free of padding and
block sizes."""
import inspect

import pytest

from bench import work


def test_l1_rank_by_hand():
    # 2 queries x 3 entities x d=4: each (query, entity) pair is 4 terms of
    # 3 operations plus one comparison (13), the 2 gold scores 12 each;
    # bytes: table 12 floats, queries 8, gold ids 2, 1 filter id, 2 counts
    w = work.l1_rank(2, 3, 4, 1)
    assert w.flops == 2 * 3 * 13 + 2 * 12
    assert w.bytes == 4 * (12 + 8 + 2 + 1 + 2)


def test_l1_topk_by_hand():
    w = work.l1_topk(2, 3, 4, 1, k=2)
    assert w.flops == 2 * 3 * 13
    assert w.bytes == 4 * (12 + 8 + 1 + 2 * 2 * 2)


def test_transe_epoch_by_hand():
    # 250 triples in batches of 100: 3 steps; per step 2B scores and their
    # gradients (2 * 2B * 4d) and the update (2B d); B rows of 4 touched,
    # read and written, and the 2B triples' ids; then one renorm pass
    w = work.transe_epoch(250, 100, 10, 4)
    per_flops = 2 * 100 * 2 * 16 + 2 * 100 * 4
    per_bytes = 4 * (2 * 4 * 100 * 4 + 2 * 3 * 100)
    assert w.flops == 3 * per_flops + 3 * 10 * 4
    assert w.bytes == 3 * per_bytes + 2 * 4 * 10 * 4


def test_counts_take_no_padding_or_block_size():
    # logical shapes only: nothing about buckets, tiles or implementations
    for fn in (work.l1_rank, work.l1_topk, work.ppat, work.align,
               work.transe_epoch, work.hit10):
        params = set(inspect.signature(fn).parameters)
        assert not params & {"block_e", "block_q", "bucket", "padded", "impl"}
    # a batch of 5 real queries costs 5 queries whatever bucket serves it
    assert work.l1_rank(5, 100, 8, 5) == work.l1_rank(5, 100, 8, 5)
    assert work.l1_rank(5, 100, 8, 5).flops < work.l1_rank(8, 100, 8, 5).flops


def test_least_seconds_names_its_bound():
    kind = "TPU v5 lite"
    t, bound = work.least_seconds(work.Work(flops=197e12, bytes=1.0), kind)
    assert bound == "flops" and t == pytest.approx(1.0)
    t, bound = work.least_seconds(work.Work(flops=1.0, bytes=819e9), kind)
    assert bound == "bytes" and t == pytest.approx(1.0)
    # one served request: one read of Dbpedia's table bounds it
    t, bound = work.least_seconds(work.l1_rank(1, 491078, 100, 0), kind)
    assert bound == "bytes" and t == pytest.approx(491078 * 100 * 4 / 819e9, rel=1e-4)
