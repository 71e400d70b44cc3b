"""The second kind of state in the cache manager (kv_cache.RowState): a
small array a batch row beside the K/V pages, carried through every step
with the pool's arrays. Its invariants, its ride through
``KVBlockPool.step_arrays``, and what the engine does with it: builds it
from the model's ``row_state()``, hands it to both steps donated, keeps
what they return, resets nothing at admission."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.serving import (GenerationConfig, GenerationModel,
                                KVBlockPool, PageKind, RowState,
                                ServingEngine)
from paddle_tpu.serving.zaya import ZayaBlock


def toy_model(**block):
    blk = ZayaBlock(n_kv_heads=2, head_dim=128, router_hidden=8,
                    n_routed_experts=4, moe_d_ff=16,
                    weight_dtype="float32", activation_dtype="float32",
                    cache_dtype="float32", **block)
    cfg = GenerationConfig(vocab_size=48, d_model=32, n_heads=4, n_layers=2,
                           d_ff=16, max_seq_len=64, block=blk)
    return GenerationModel.random(cfg, seed=3)


def test_a_row_state_is_zeros_of_the_stated_shape():
    state = RowState(3, (2, 5), "float32")
    assert state.array.shape == (3, 2, 5)
    assert state.array.dtype == jnp.float32
    assert not np.asarray(state.array).any()
    assert state.check_invariants() == []
    assert "RowState(3, (2, 5)" in repr(state)


@pytest.mark.parametrize("wrong,says", [
    (lambda a: a[:2], "where float32 (3, 2, 5) was stated"),
    (lambda a: a.astype(jnp.bfloat16), "bfloat16")])
def test_a_row_state_of_another_shape_or_type_is_a_problem(wrong, says):
    state = RowState(3, (2, 5))
    state.array = wrong(state.array)
    problems = state.check_invariants()
    assert len(problems) == 1 and says in problems[0], problems


def test_a_donated_array_nobody_replaced_is_a_problem():
    state = RowState(2, (4,))
    step = jax.jit(lambda a: a + 1.0, donate_argnums=0)
    out = step(state.array)
    if not state.array.is_deleted():
        pytest.skip("this backend does not donate")
    assert "donated" in state.check_invariants()[0]
    state.array = out
    assert state.check_invariants() == []


def test_the_pool_hands_the_state_over_after_its_arrays():
    plain = KVBlockPool(2, 4, 8, 4, 6)
    assert plain.row_state is None
    assert plain.step_arrays is plain.arrays
    pool = KVBlockPool(2, 4, 8, 4, 6, row_state=RowState(3, (2, 7)))
    k, v, state = pool.step_arrays
    assert (k is pool.k, v is pool.v, state is pool.row_state.array) \
        == (True, True, True)
    pool.step_arrays = (k + 1, v, state + 2)
    assert float(pool.k.max()) == 1.0
    assert float(pool.row_state.array.min()) == 2.0
    assert len(pool.arrays) == 2
    assert pool.check_invariants() == []
    pool.row_state.array = pool.row_state.array[:1]
    assert any("row state" in p for p in pool.check_invariants())


def test_one_named_page_kind_takes_its_count_by_name():
    kinds = (PageKind("global", range(2)),)
    pool = KVBlockPool(2, 4, 8, 4, {"global": 6}, kinds=kinds)
    assert pool.num_blocks == 6 and len(pool.arrays) == 2
    assert "kinds" not in pool.stats()


def test_the_model_states_its_row_state():
    model = toy_model()
    assert model.row_state() == ((2, 2 * 6 * 128 + 128), "float32")
    plain = GenerationModel.random(GenerationConfig(
        vocab_size=16, d_model=8, n_heads=2, n_layers=1, d_ff=8,
        max_seq_len=16), seed=1)
    assert plain.row_state() is None


def test_the_engine_carries_the_state_through_its_steps(monkeypatch):
    monkeypatch.setenv("PTPU_KERNELS", "0")
    monkeypatch.setenv("PTPU_LOCK_CHECK", "1")    # audit at step boundaries
    model = toy_model()
    eng = ServingEngine(model, max_batch=3, max_seq_len=64, block_size=16,
                        prefill_chunk=4)
    try:
        worker = next(iter(eng._workers.values()))
        state = worker.pool.row_state
        assert state.array.shape == (3, 2, 2 * 6 * 128 + 128)
        assert not np.asarray(state.array).any()
        first = eng.submit([1, 2, 3, 4, 5, 6, 7], max_new_tokens=5)
        first.wait(300)
        after = np.asarray(state.array)
        # the one slot used carries its last token's vectors; the slots
        # no request entered were never written
        assert (np.abs(after).reshape(3, -1).max(axis=1) > 0).sum() == 1
        assert worker.pool.check_invariants() == []
        # the same prompt again, into a slot whose carry is stale: the
        # same tokens (the steps read no carry at position 0)
        again = eng.submit([1, 2, 3, 4, 5, 6, 7], max_new_tokens=5)
        assert again.wait(300) == first.tokens
    finally:
        eng.close()


def test_ignoring_the_carry_changes_what_is_served(monkeypatch):
    monkeypatch.setenv("PTPU_KERNELS", "0")
    logits = []
    for ignore in (False, True):
        model = toy_model(ignore_carry=ignore)
        state = RowState(1, *model.row_state())
        pool = KVBlockPool(2, 4, 128, 16, 4, entry=model.cache_entry(),
                           kinds=model.page_kinds(), row_state=state)
        pool.reserve(0, 1)
        tables = np.array([[pool.alloc_block(0)]], np.int32)
        step = model.make_decode_step(1, 1, return_logits=True)
        on, idle = np.ones(1, bool), np.zeros(1, np.int32)
        for pos, tok in enumerate((5, 9)):
            out = step(model.weights, *pool.step_arrays,
                       np.array([tok], np.int32), on, idle,
                       np.array([pos], np.int32), tables, on)
            pool.step_arrays = out[:3]
        logits.append(np.asarray(out[-1]))
        # the carry is WRITTEN either way; the control only reads zeros
        assert np.abs(np.asarray(pool.row_state.array)).max() > 0
    assert np.abs(logits[0] - logits[1]).max() > 1e-3


# -- a row state of named parts, each with its own dtype ---------------------

PARTS = (("scan", (2, 4, 4), "float32"), ("conv", (6,), "bfloat16"))


def test_a_row_state_of_two_parts_is_an_array_a_part():
    state = RowState(3, PARTS)
    assert [a.shape for a in state.arrays] == [(3, 2, 4, 4), (3, 6)]
    assert [str(a.dtype) for a in state.arrays] == ["float32", "bfloat16"]
    assert state.part("conv") is state.arrays[1]
    assert state.nbytes == 3 * (32 * 4 + 6 * 2)
    assert state.check_invariants() == []
    assert "('conv', (6,), 'bfloat16')" in repr(state)
    # no ONE array, shape or dtype to ask for
    for name in ("array", "shape", "dtype"):
        with pytest.raises(AttributeError, match="2 parts"):
            getattr(state, name)
    with pytest.raises(KeyError):
        state.part("carry")
    with pytest.raises(ValueError, match="distinct names"):
        RowState(3, (PARTS[0], PARTS[0]))
    # one part is what the one-array state is, attribute for attribute
    one, named = RowState(3, (2, 5)), RowState(3, (("state", (2, 5),
                                                    "float32"),))
    assert (one.shape, one.dtype, one.array.shape, one.parts) \
        == (named.shape, named.dtype, named.array.shape, named.parts)


@pytest.mark.parametrize("wrong,says", [
    (lambda a: (a[0], a[1][:2]), "part 'conv': bfloat16 (2, 6)"),
    (lambda a: (a[0].astype(jnp.bfloat16), a[1]), "part 'scan': bfloat16"),
    (lambda a: a[:1], "1 arrays for 2 parts")])
def test_a_part_of_another_shape_or_type_is_a_problem(wrong, says):
    state = RowState(3, PARTS)
    state.arrays = tuple(wrong(state.arrays))
    problems = state.check_invariants()
    assert len(problems) == 1 and says in problems[0], problems


def test_the_pool_donates_and_takes_back_every_part():
    pool = KVBlockPool(2, 4, 8, 4, 6, row_state=RowState(3, PARTS))
    k, v, scan, conv = pool.step_arrays
    assert scan is pool.row_state.part("scan")
    step = jax.jit(lambda k, v, s, c: (k + 1, v, s + 2, c + 3),
                   donate_argnums=(0, 1, 2, 3))
    out = step(*pool.step_arrays)
    if scan.is_deleted():
        assert any("part 'scan'" in p and "donated" in p
                   for p in pool.check_invariants())
    pool.step_arrays = out
    assert len(pool.arrays) == 2 and float(pool.k.max()) == 1.0
    assert float(pool.row_state.part("scan").min()) == 2.0
    assert float(pool.row_state.part("conv").min()) == 3.0
    assert pool.check_invariants() == []


def test_stats_report_the_row_states_bytes_beside_the_pages():
    plain = KVBlockPool(2, 4, 8, 4, 6).stats()
    assert plain["page_bytes"] == 2 * 2 * 7 * 4 * 4 * 8 * 4
    assert "row_state_bytes" not in plain
    stats = KVBlockPool(2, 4, 8, 4, 6,
                        row_state=RowState(3, PARTS)).stats()
    assert stats["page_bytes"] == plain["page_bytes"]
    assert stats["row_state_parts"] == {"scan": 3 * 32 * 4, "conv": 3 * 12}
    assert stats["row_state_bytes"] == 3 * (32 * 4 + 12)
    one = KVBlockPool(2, 4, 8, 4, 6, row_state=RowState(3, (2, 7))).stats()
    assert one["row_state_parts"] == {"state": 3 * 14 * 4}
