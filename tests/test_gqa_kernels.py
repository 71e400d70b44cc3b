"""The grouped-query paged attention kernels and the in-place page
write (ops/pallas_kernels.py) against their lax fallbacks, in the Pallas
interpreter: window and global layers, tiles at both ends of a context,
one-token tiles, released table entries, inactive rows.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.ops.kernel_registry import registered_kernels

L, NB, BS, HKV, DH, H = 2, 40, 16, 2, 128, 12
N, CQ, MB = 5, 8, 8


@pytest.fixture(scope="module")
def paged():
    rng = np.random.default_rng(0)
    pools = [jnp.asarray(rng.normal(size=(L, NB + 1, BS, HKV * DH)),
                         jnp.bfloat16) for _ in range(2)]
    tables = rng.permutation(NB)[:N * MB].reshape(N, MB).astype(np.int32) + 1
    q = rng.normal(size=(N, CQ, H, DH)).astype(np.float32)
    # the kernels round q to bfloat16; hand the fallback the same values
    q = np.asarray(jnp.asarray(q, jnp.bfloat16).astype(jnp.float32))
    pos = np.array([0, 5, 40, 100, 77], np.int32)
    lens = np.array([8, 1, 8, 3, 0], np.int32)
    return pools, tables, q, pos, lens


@pytest.mark.parametrize("window", [None, 24, 7])
def test_chunk_kernel_equals_its_fallback(paged, window):
    (kp, vp), tables, q, pos, lens = paged
    got = pk.gqa_paged_chunk_attention(kp, vp, q, tables, pos, lens,
                                       layer=1, window=window,
                                       pages_per_step=2)
    want = pk.gqa_paged_attention_reference(kp, vp, q, tables, pos, lens,
                                            layer=1, window=window)
    assert got.shape == (N, CQ, H, DH)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-2
    assert not np.asarray(got[4]).any()            # the unused tile
    assert not np.asarray(got[1, 1:]).any()        # past a tile's length


@pytest.mark.parametrize("window", [None, 24, 7])
def test_decode_kernel_equals_its_fallback(paged, window):
    (kp, vp), tables, q, pos, _lens = paged
    active = np.array([1, 1, 1, 0, 1], np.int32)
    got = pk.gqa_paged_decode_attention(kp, vp, q[:, 0], tables, pos,
                                        layer=0, window=window,
                                        active=active, pages_per_step=2)
    want = pk.gqa_paged_decode_attention_reference(
        kp, vp, q[:, 0], tables, pos, layer=0, window=window, active=active)
    assert got.shape == (N, H, DH)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-2
    assert not np.asarray(got[3]).any()            # the inactive row


def test_the_window_engages_and_released_entries_are_never_read(paged):
    (kp, vp), tables, q, pos, lens = paged
    run = lambda t, w: pk.gqa_paged_chunk_attention(  # noqa: E731
        kp, vp, q, t, pos, lens, layer=1, window=w, pages_per_step=2)
    full, banded = run(tables, None), run(tables, 7)
    assert float(jnp.max(jnp.abs(full - banded))) > 0.1
    nulled = tables.copy()
    nulled[2, :2] = 0          # position 40, window 7: pages 0, 1 are out
    nulled[3, :5] = 0          # position 100: pages 0..4 are out
    np.testing.assert_array_equal(np.asarray(run(nulled, 7)),
                                  np.asarray(banded))


@pytest.mark.parametrize("n_rows", [BS, 1])
def test_page_write_equals_its_fallback_and_touches_only_its_rows(paged,
                                                                  n_rows):
    (kp, vp), _t, _q, _p, _l = paged
    rng = np.random.default_rng(1)
    U, W = 6, HKV * DH
    kr, vr = (jnp.asarray(rng.normal(size=(U, n_rows, W)), jnp.bfloat16)
              for _ in range(2))
    ids = np.array([3, 9, 0, 17, 0, 22], np.int32)
    lo = np.array([0, 4, 0, 15, 0, 2], np.int32)
    hi = (np.array([16, 9, 0, 16, 0, 2], np.int32) if n_rows > 1
          else lo + (ids > 0))
    got = pk.kv_page_write(kp, vp, kr, vr, ids, lo, hi, layer=1)
    want = pk.kv_page_write_reference(kp, vp, kr, vr, ids, lo, hi, layer=1)
    for g, w, old in zip(got, want, (kp, vp)):
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))
        changed = np.asarray(g != old).any(axis=-1)        # [L, NB+1, BS]
        assert not changed[0].any()
        assert changed[1].sum() == int((hi - lo).sum())


def test_the_three_kernels_are_registered_with_fallbacks():
    specs = registered_kernels()
    for name in ("gqa_decode", "gqa_chunk", "kv_page_write"):
        assert specs[name].fallback is not None
        assert specs[name].qualify(head_dim=128, block_size=64)[0]
        assert not specs[name].qualify(head_dim=64, block_size=64)[0]
        assert not specs[name].qualify(head_dim=128, block_size=8)[0]
