"""Pages of two kinds under one accounting (serving/kv_cache.py,
scheduler.py): window pages are released as positions slide out, a row
never holds more of them than the window, the chunk in flight and a
block, the invariants hold through admit / slide / rollback / expiry /
free, and a pool of one kind is the pool it was.
"""

import numpy as np
import pytest

from paddle_tpu.serving.kv_cache import (CacheEntry, KVBlockPool, PageKind,
                                         blocks_needed)
from paddle_tpu.serving.scheduler import (GenerationRequest, RequestQueue,
                                          StepScheduler)

ENTRY = CacheEntry((("k", (8,)), ("v", (8,))), "float32")
KINDS = (PageKind("global", [1]), PageKind("window", [0, 2, 3], window=16))


def two_kind_pool(n_global=24, n_window=10, bs=4):
    return KVBlockPool(4, 1, 8, bs, {"global": n_global,
                                     "window": n_window},
                       entry=ENTRY, kinds=KINDS)


def test_arrays_follow_the_kinds():
    pool = two_kind_pool()
    assert [a.shape for a in pool.arrays] == [
        (1, 25, 4, 8), (1, 25, 4, 8), (3, 11, 4, 8), (3, 11, 4, 8)]
    assert pool.kind_totals() == (24, 10)
    assert pool.k.shape == (1, 25, 4, 8)       # the first kind's


def test_kinds_must_partition_the_layers_global_first():
    with pytest.raises(ValueError):
        KVBlockPool(4, 1, 8, 4, [8, 8], entry=ENTRY,
                    kinds=(PageKind("a", [0, 1]), PageKind("b", [3])))
    with pytest.raises(ValueError):
        KVBlockPool(4, 1, 8, 4, [8, 8], entry=ENTRY, kinds=KINDS[::-1])
    with pytest.raises(ValueError):
        KVBlockPool(4, 1, 8, 4, [8], entry=ENTRY, kinds=KINDS)


def test_a_reservation_covers_every_kind_or_none():
    pool = two_kind_pool(n_global=8, n_window=4)
    assert pool.reserve("a", (5, 3))
    assert not pool.reserve("b", (3, 2))       # the window kind is short
    assert pool.stats()["blocks_reserved"] == 5      # nothing of b's
    assert not pool.reserve("b", (4, 1))       # the global kind is short
    assert pool.reserve("b", (3, 1))
    assert not pool.could_hold((9, 1)) and pool.could_hold((8, 4))
    assert pool.check_invariants() == []


def test_release_head_is_the_inverse_of_alloc_from_the_other_end():
    pool = two_kind_pool()
    assert pool.reserve("a", (6, 3))
    got = [pool.alloc_block("a", 1) for _ in range(3)]
    with pytest.raises(RuntimeError):
        pool.alloc_block("a", 1)               # release comes first
    assert pool.release_head("a", 1, 2) == got[:2]
    assert pool.block_table("a", 1) == got[2:]
    assert pool.pages_released("a", 1) == 2
    assert pool.release_head("a", 1, 2) == []  # idempotent
    more = [pool.alloc_block("a", 1) for _ in range(2)]
    assert len(set(got[2:] + more)) == 3
    st = pool.stats()
    assert st["kinds"]["window"] == {
        "blocks_total": 10, "blocks_in_use": 3, "blocks_reserved": 0,
        "blocks_released": 2}
    assert st["window_blocks_released"] == 2
    with pytest.raises(ValueError):
        pool.release_head("a", 0, 1)           # the global kind keeps all
    assert pool.check_invariants() == []
    pool.free_owner("a")
    assert pool.stats()["kinds"]["window"]["blocks_in_use"] == 0
    assert pool.check_invariants() == []


def test_truncate_rolls_back_every_kind():
    pool = two_kind_pool()
    assert pool.reserve("a", (6, 4))
    for _ in range(5):
        pool.alloc_block("a")
    for _ in range(4):
        pool.alloc_block("a", 1)
    pool.release_head("a", 1, 1)               # logical page 0 gone
    pool.alloc_block("a", 1)                   # logical page 4
    assert len(pool.truncate_owner("a", 3)) == 2
    # window pages 1, 2 stay (page 0 was released, 3 and 4 rolled back)
    assert len(pool.block_table("a", 1)) == 2
    assert pool.check_invariants() == []


def test_invariant_audit_sees_a_broken_window_kind():
    pool = two_kind_pool()
    assert pool.reserve("a", (2, 2))
    pool.alloc_block("a", 1)
    pool._extra[0].free.pop()
    assert any("window pages: conservation" in p
               for p in pool.check_invariants())


def test_a_pool_of_one_kind_is_the_pool_it_was():
    pool = KVBlockPool(2, 2, 8, 4, 6)
    assert [k.name for k in pool.kinds] == ["all"]
    assert "kinds" not in pool.stats()
    assert pool.reserve("a", 3) and not pool.reserve("b", 4)
    assert [pool.alloc_block("a") for _ in range(3)] == [1, 2, 3]
    assert pool.truncate_owner("a", 1) == [2, 3]
    assert pool.free_owner("a") == 1
    assert pool.check_invariants() == []


# -- through the scheduler ----------------------------------------------------

def drive(sched, queue, steps, after=None):
    """admit / plan / record / reap, no device: every dispatched row's
    token is recorded at once. Returns the most window-kind pages any
    row held after a plan."""
    most = 0
    for _ in range(steps):
        sched.admit(queue)
        plan, _kind = sched.plan_step()
        for seq in (s for s in sched.slots if s is not None):
            most = max(most, len(sched.pool.block_table(seq, 1)))
        for seq, gen_idx in plan:
            sched.record_token(seq, gen_idx, 1)
        sched.reap()
        assert sched.pool.check_invariants() == []
        if after is not None:
            after(sched)
    return most


@pytest.mark.parametrize("chunk", [4, 8, 12])
def test_window_pages_slide_out_under_the_scheduler(chunk):
    bs, window = 4, 16
    pool = two_kind_pool(n_global=64, n_window=2 * 9, bs=bs)
    sched = StepScheduler(2, pool, max_seq_len=96, prefill_chunk=chunk,
                          prefill_token_budget=chunk)
    cap = blocks_needed(window - 1 + chunk, bs) + 1
    assert sched.kind_max_blocks == [24, cap]
    queue = RequestQueue(8)
    rng = np.random.default_rng(0)
    reqs = [GenerationRequest(rng.integers(0, 9, n).tolist(),
                              max_new_tokens=m)
            for n, m in ((70, 20), (50, 30), (9, 5))]
    for r in reqs:
        queue.submit(r)

    def tables_match(s):
        for slot, seq in enumerate(s.slots):
            if seq is None:
                continue
            head = pool.pages_released(seq, 1)
            live = pool.block_table(seq, 1)
            line = s.kind_tables[1, slot]
            assert list(line[head:head + len(live)]) == live
            assert not line[:head].any()
            # the first live page holds the first position a query at
            # the row's next position still sees
            assert head <= max(seq.pos - window, 0) // bs + 1

    most = drive(sched, queue, 200, after=tables_match)
    assert all(r.finished and r.error is None for r in reqs)
    # never more than window + chunk + a block of tokens of window pages
    assert most <= cap and most * bs <= window + chunk + 2 * bs
    st = pool.stats()
    assert st["window_blocks_released"] > 20
    assert st["blocks_in_use"] == 0
    assert st["kinds"]["window"]["blocks_in_use"] == 0


def test_the_kv_gate_counts_both_kinds():
    """Two rows of long prompts fit the global kind, but the window kind
    holds one row's worth: the second waits for the first."""
    pool = two_kind_pool(n_global=64, n_window=7, bs=4)
    sched = StepScheduler(2, pool, max_seq_len=96, prefill_chunk=8)
    assert sched.kind_max_blocks[1] == 7
    queue = RequestQueue(8)
    for _ in range(2):
        queue.submit(GenerationRequest(list(range(40)), max_new_tokens=4))
    assert len(sched.admit(queue)) == 1
    assert len(queue) == 1
    drive(sched, queue, 40)
    assert len(queue) == 0 and not sched.has_work()


def test_fail_all_frees_both_kinds():
    pool = two_kind_pool(n_global=64, n_window=18, bs=4)
    sched = StepScheduler(2, pool, max_seq_len=96, prefill_chunk=8)
    queue = RequestQueue(8)
    for n in (30, 44):
        queue.submit(GenerationRequest(list(range(n)), max_new_tokens=4))
    sched.admit(queue)
    sched.plan_step()
    assert pool.stats()["kinds"]["window"]["blocks_in_use"] == 4
    sched.fail_all(RuntimeError("boom"))
    assert pool.stats()["kinds"]["window"]["blocks_in_use"] == 0
    assert pool.stats()["blocks_in_use"] == 0
    assert pool.check_invariants() == []
