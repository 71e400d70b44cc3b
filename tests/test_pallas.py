"""Pallas kernel tests — interpret mode on the CPU mesh (SURVEY §7:
attention fusion kernels; numeric parity vs the naive XLA reference).

ISSUE 17 grows this into the kernel-library test bed: the
kernel_registry dispatch contract (PTPU_KERNELS modes, per-kernel
disable, qualification warn-once + fallback telemetry), the paged
flash-decode / spec verify-window kernels against their gathered lax
references (block-table edge matrix: null block, partial last block,
post-truncate tables), the fused int8 matmul's bitwise identity with
the unfused quantize->dot->dequantize chain, the serving token-identity
and kernels-off bitwise pins, and the module-text receipt that the
fused emission drops the standalone quantize/dequantize HLOs."""

import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.core import device
from paddle_tpu.ops import kernel_registry as kreg
from paddle_tpu.ops.pallas_kernels import (
    flash_attention, int8_matmul, int8_matmul_reference, paged_attention,
    paged_attention_reference)


def _naive(q, k, v, causal):
    D = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (D ** -0.5)
    if causal:
        T = s.shape[-1]
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("T", [64, 80])  # 80 exercises padding
def test_flash_attention_matches_naive(causal, T):
    rng = np.random.RandomState(0)
    B, H, D = 2, 3, 32
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    got = flash_attention(q, k, v, causal, None, 32, 32)
    want = _naive(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_grads_match_naive():
    rng = np.random.RandomState(1)
    B, H, T, D = 1, 2, 64, 16
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, None, 32, 32) ** 2)

    def loss_naive(q, k, v):
        return jnp.sum(_naive(q, k, v, True) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)

# ---------------------------------------------------------------------------
# kernel registry: dispatch modes, cache key, qualification telemetry
# ---------------------------------------------------------------------------


def test_registry_modes_and_cache_key(monkeypatch):
    monkeypatch.delenv("PTPU_KERNELS", raising=False)
    monkeypatch.delenv("PTPU_KERNELS_DISABLE", raising=False)
    assert kreg.kernels_mode() == "auto"
    assert kreg.cache_key() == "auto"
    monkeypatch.setenv("PTPU_KERNELS", "1")
    assert kreg.kernels_mode() == "force"
    assert kreg.enabled_for("paged_decode")
    assert kreg.enabled_for("int8_matmul")
    monkeypatch.setenv("PTPU_KERNELS", "0")
    assert kreg.kernels_mode() == "off"
    assert not kreg.enabled_for("flash_attention")
    # per-kernel pin beats force mode; sorted names ride the cache key
    monkeypatch.setenv("PTPU_KERNELS", "1")
    monkeypatch.setenv("PTPU_KERNELS_DISABLE", "spec_window,int8_matmul")
    assert not kreg.enabled_for("int8_matmul")
    assert not kreg.enabled_for("spec_window")
    assert kreg.enabled_for("paged_decode")
    assert kreg.cache_key() == "force:-int8_matmul,spec_window"
    # the repo boolean spelling contract: bad values raise by name
    monkeypatch.setenv("PTPU_KERNELS", "maybe")
    with pytest.raises(ValueError):
        kreg.kernels_mode()


def test_registry_auto_policy_is_platform_scoped(monkeypatch):
    """Unset (auto) keeps each kernel's historical policy: flash runs
    everywhere, the serving/quant kernels are TPU-only — so the CPU
    mesh's default numerics are bitwise the pre-kernel paths."""
    monkeypatch.delenv("PTPU_KERNELS", raising=False)
    monkeypatch.delenv("PTPU_KERNELS_DISABLE", raising=False)
    assert kreg.enabled_for("flash_attention")
    on_tpu = device.on_tpu()
    for name in ("paged_decode", "spec_window", "int8_matmul"):
        assert kreg.enabled_for(name) == on_tpu


def test_flash_qualification_fixes_cross_attention_gate():
    """The compat_ops.py:552 latent gate, promoted and fixed: the old
    `q.shape == k.shape` check dropped the tuned path for EVERY
    cross-attention call; the registry predicate admits non-causal
    Tq != Tk (the portable kernel masks by kv length) and names each
    disqualification."""
    spec = kreg.get_kernel("flash_attention")
    assert spec.qualify(T=256, Tk=256, head_dim=64, causal=True)[0]
    # the fix: non-causal cross-attention now qualifies
    assert spec.qualify(T=256, Tk=128, head_dim=64, causal=False)[0]
    ok, reason = spec.qualify(T=256, Tk=128, head_dim=64, causal=True)
    assert not ok and "cross-attention" in reason
    ok, reason = spec.qualify(T=100, Tk=100, head_dim=64, causal=True)
    assert not ok and "128" in reason
    ok, reason = spec.qualify(T=256, Tk=256, head_dim=32, causal=True)
    assert not ok and "head_dim" in reason


def test_disqualified_shape_counts_fallback_and_warns_once(monkeypatch):
    from paddle_tpu.observability import metrics

    monkeypatch.delenv("PTPU_KERNELS", raising=False)
    monkeypatch.delenv("PTPU_KERNELS_DISABLE", raising=False)
    was = metrics.enabled()
    metrics.enable()
    reg = metrics.registry()
    fb0 = reg.counter("kernels/fallbacks").value
    d0 = reg.counter("kernels/dispatches").value
    kreg._WARNED.discard(("flash_attention",
                          "seq len not a multiple of 128"))
    try:
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            assert not kreg.choose("flash_attention", T=100, Tk=100,
                                   head_dim=64, causal=True)
            assert not kreg.choose("flash_attention", T=100, Tk=100,
                                   head_dim=64, causal=True)
        msgs = [w for w in rec
                if "flash_attention" in str(w.message)]
        assert len(msgs) == 1  # DeferredWarns discipline: once per cause
        assert "lax fallback" in str(msgs[0].message)
        assert reg.counter("kernels/fallbacks").value - fb0 == 2
        # a qualifying shape counts a dispatch + the per-kernel counter
        k0 = reg.counter("kernels/kernel:flash_attention").value
        assert kreg.choose("flash_attention", T=256, Tk=256, head_dim=64,
                           causal=True)
        assert reg.counter("kernels/dispatches").value - d0 == 1
        assert reg.counter(
            "kernels/kernel:flash_attention").value - k0 == 1
        # mode off counts a fallback too, silently
        monkeypatch.setenv("PTPU_KERNELS", "0")
        fb1 = reg.counter("kernels/fallbacks").value
        assert not kreg.choose("flash_attention", T=256, Tk=256,
                               head_dim=64, causal=True)
        assert reg.counter("kernels/fallbacks").value - fb1 == 1
    finally:
        if not was:
            metrics.disable()


# ---------------------------------------------------------------------------
# paged attention: decode (C=1) and the spec verify window (C=k+1)
# ---------------------------------------------------------------------------


LAYER = 1   # the layer the paged tests read, of _paged_setup's three


def _paged_setup(seed=0, NB=8, bs=4, H=2, Dh=16, B=2, Mb=4):
    """A whole pool as KVBlockPool stores it, [L, NB + 1, bs, H, Dh]:
    the kernels take it unsliced and find the layer themselves."""
    rng = np.random.RandomState(seed)
    k_pool = jnp.asarray(rng.randn(3, NB + 1, bs, H, Dh).astype(np.float32))
    v_pool = jnp.asarray(rng.randn(3, NB + 1, bs, H, Dh).astype(np.float32))
    return rng, k_pool, v_pool


@pytest.mark.parametrize("table,positions", [
    # full tables, scattered non-monotone physical pages
    ([[5, 2, 7, 3], [1, 4, 6, 8]], [[15], [9]]),
    # partially-filled last block (position mid-page)
    ([[5, 2, 7, 0], [3, 0, 0, 0]], [[9], [2]]),
    # unallocated tail slots hold the null block (id 0) — the kernel
    # gathers page 0 there and the position mask hides every slot
    ([[6, 0, 0, 0], [2, 8, 0, 0]], [[1], [4]]),
])
def test_paged_decode_matches_gathered_reference(table, positions):
    rng, k_pool, v_pool = _paged_setup()
    q = jnp.asarray(rng.randn(2, 1, 2, 16).astype(np.float32))
    tables = jnp.asarray(np.array(table, np.int32))
    pos = jnp.asarray(np.array(positions, np.int32))
    got = paged_attention(k_pool, v_pool, q, tables, pos, layer=LAYER)
    want = paged_attention_reference(k_pool, v_pool, q, tables, pos,
                                     layer=LAYER)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_spec_window_matches_gathered_reference():
    """The verify-window shape: k+1 query positions per row, each
    masked to its OWN causal prefix — exactly the serving chunk
    attention's `t <= pos2d[b, c]` contract."""
    rng, k_pool, v_pool = _paged_setup(seed=3)
    C = 3
    q = jnp.asarray(rng.randn(2, C, 2, 16).astype(np.float32))
    tables = jnp.asarray(np.array([[5, 2, 7, 3], [4, 1, 0, 0]], np.int32))
    pos = jnp.asarray(np.array([[7, 8, 9], [0, 1, 2]], np.int32))
    got = paged_attention(k_pool, v_pool, q, tables, pos, layer=LAYER)
    want = paged_attention_reference(k_pool, v_pool, q, tables, pos,
                                     layer=LAYER)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def _truncated_tables(Mb, **page):
    """One row's block table after the speculative KV rollback
    (KVBlockPool.truncate_owner): three blocks held, two dropped again,
    the padded tail back on the null block."""
    from paddle_tpu.serving.kv_cache import KVBlockPool

    pool = KVBlockPool(n_layers=1, num_blocks=8, **page)
    assert pool.reserve("s", 3)
    for _ in range(3):
        pool.alloc_block("s")
    dropped = pool.truncate_owner("s", 1)
    table_ids = pool.block_table("s")
    assert len(table_ids) == 1 and len(dropped) == 2
    padded = np.full((1, Mb), KVBlockPool.NULL_BLOCK, np.int32)
    padded[0, :len(table_ids)] = table_ids
    return padded


def test_paged_decode_post_truncate_tables():
    """Block tables after the speculative KV rollback: dropped tail
    blocks leave the table, the padded tail reverts to the null block,
    and attention over the kept prefix matches the reference."""
    rng, k_pool, v_pool = _paged_setup(seed=5)
    q = jnp.asarray(rng.randn(1, 1, 2, 16).astype(np.float32))
    pos = jnp.asarray(np.array([[3]], np.int32))  # last kept position
    tables = jnp.asarray(_truncated_tables(
        4, n_heads=2, head_dim=16, block_size=4))
    got = paged_attention(k_pool, v_pool, q, tables, pos, layer=LAYER)
    want = paged_attention_reference(k_pool, v_pool, q, tables, pos,
                                     layer=LAYER)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# paged decode attention: one grid step a row, the row's own pages by DMA
# ---------------------------------------------------------------------------

WALK = dict(bs=16, Mb=128, H=8, Dh=128)     # heads of whole lane tiles


def _walk_tables(rng, positions, NB, Mb=WALK["Mb"], bs=WALK["bs"]):
    """Distinct physical pages up to each row's position, the null page
    past it (what the scheduler hands a step)."""
    free = list(rng.permutation(np.arange(1, NB + 1)))
    tables = np.zeros((len(positions), Mb), np.int32)
    for b, pos in enumerate(positions):
        for j in range(pos // bs + 1):
            tables[b, j] = free.pop()
    return tables


@pytest.mark.parametrize("positions,active,tables_of", [
    # a context of one token; of exactly one page; one short of a page
    # boundary and one past it; eight pages (one run) less one, exact,
    # and one token into the next run
    ([0, 15, 14, 16], None, None),
    ([126, 127, 128], None, None),
    # the whole 2,048-token table beside a short row
    ([2047, 40], None, None),
    # inactive rows beside live ones: first, between, last (their
    # positions and tables are whatever the scheduler left there)
    ([300, 5, 200, 130, 9], [False, True, False, True, False], None),
    ([70, 0, 0, 35], [True, False, False, True], None),
    # tables after truncate_owner: the last kept position
    ([15], None, lambda _positions: _truncated_tables(
        WALK["Mb"], n_heads=WALK["H"], head_dim=WALK["Dh"],
        block_size=WALK["bs"])),
], ids=["page-edges", "run-edges", "full-table", "inactive-ends",
        "inactive-between", "post-truncate"])
@pytest.mark.parametrize("pages_per_step", [3, 8])
def test_paged_decode_walks_each_rows_own_pages(positions, active,
                                                tables_of, pages_per_step):
    """The decode kernel of heads of whole lane tiles: one grid step a
    row, the row's pages copied from the pool in runs (of 8, and of 3 so
    that rows of odd and even run counts hand the next row either
    buffer), every head in one product. Against the gathered reference,
    operands rounded to bfloat16 as a default-precision dot on the chip
    rounds them (within 8 half-ulps of the reference's largest value);
    an inactive row comes out zero and disturbs no live one."""
    from paddle_tpu.ops.pallas_kernels import paged_decode_attention

    g = WALK
    NB = 160
    rng, k_pool, v_pool = _paged_setup(seed=11, NB=NB, bs=g["bs"],
                                       H=g["H"], Dh=g["Dh"])
    B = len(positions)
    tables = (tables_of(positions) if tables_of
              else _walk_tables(rng, positions, NB))
    q = jnp.asarray(rng.randn(B, 1, g["H"], g["Dh"]).astype(np.float32))
    pos = np.array(positions, np.int32)[:, None]
    live = np.ones(B, bool) if active is None else np.array(active)
    got = np.asarray(paged_decode_attention(
        k_pool, v_pool, q, tables, pos, layer=LAYER,
        active=None if active is None else live,
        pages_per_step=pages_per_step))
    want = np.asarray(paged_attention_reference(
        k_pool, v_pool, q, tables, pos, layer=LAYER))
    assert got.shape == want.shape and np.isfinite(got).all()
    np.testing.assert_allclose(
        got[live], want[live],
        atol=8 * 2.0 ** -9 * np.abs(want).max(), rtol=0)
    assert (got[~live] == 0).all()


def test_paged_decode_picks_its_kernel_by_head_width(monkeypatch):
    """`paged_decode` walks a row's own pages only at heads of whole
    128-lane tiles (pages are copied as the pool stores them); a head
    of 64, a served xglm-564M, stays on the BlockSpec kernel (not the
    lax path: `qualify` still holds), fp32 to rounding. So does a
    window of more than one token."""
    from paddle_tpu.ops import pallas_kernels as pk

    calls = []
    real = pk._paged_call
    monkeypatch.setattr(pk, "_paged_call", lambda *a: (
        calls.append(a[2].shape), real(*a))[1])
    spec = kreg.get_kernel("paged_decode")
    assert spec.pallas is pk.paged_decode_attention
    assert spec.qualify(head_dim=64, block_size=16)[0]
    assert spec.qualify(head_dim=128, block_size=16)[0]
    rng, k_pool, v_pool = _paged_setup(seed=2, NB=8, bs=4, H=2, Dh=64)
    q = jnp.asarray(rng.randn(2, 1, 2, 64).astype(np.float32))
    tables = np.array([[5, 2, 7, 3], [1, 4, 0, 0]], np.int32)
    pos = np.array([[15], [6]], np.int32)
    got = spec.pallas(k_pool, v_pool, q, tables, pos, layer=LAYER)
    assert calls == [(2, 1, 2, 64)]
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(spec.fallback(
            k_pool, v_pool, q, tables, pos, layer=LAYER)),
        atol=1e-5, rtol=1e-5)
    # heads of 128: the page walk, and the grid kernel is not called
    rng, k_pool, v_pool = _paged_setup(seed=2, NB=8, bs=4, H=2, Dh=128)
    q = jnp.asarray(rng.randn(2, 1, 2, 128).astype(np.float32))
    spec.pallas(k_pool, v_pool, q, tables, pos, layer=LAYER)
    assert len(calls) == 1
    q3 = jnp.asarray(rng.randn(2, 3, 2, 128).astype(np.float32))
    spec.pallas(k_pool, v_pool, q3, tables, pos + np.arange(3)[None, :]
                - 2, layer=LAYER)
    assert calls[1:] == [(2, 3, 2, 128)]


# ---------------------------------------------------------------------------
# paged chunk attention: the chunk window as query tiles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pages_per_step", [1, 2, 8])
@pytest.mark.parametrize("tile", [4, 8, 32])
def test_paged_chunk_attention_matches_gathered_reference(tile,
                                                          pages_per_step):
    """Tiles of one sequence's consecutive tokens against the paged
    cache: a full tile deep in its context and the tile after it (same
    block-table line), a tile cut short, one-token tiles at both ends
    of a context, an unused tile; runs of 1, 2 and 8 pages, so a tile's
    pages end inside a run, on a run's edge and in a later run. The
    kernel rounds the operands of both products to bfloat16 (a
    default-precision dot on the chip): within 8 half-ulps of the
    reference's largest value, slots past a live tile's length zero.
    The unused tile's slots are not written (the interpreter leaves NaN
    there): no caller reads them."""
    from paddle_tpu.ops.pallas_kernels import (
        paged_chunk_attention, paged_chunk_attention_reference)

    rng, k_pool, v_pool = _paged_setup(seed=7, NB=40, bs=4, H=2, Dh=16)
    Mb, N = 20, 6
    room = Mb * 4
    q = jnp.asarray(rng.randn(N, tile, 2, 16).astype(np.float32))
    tables = rng.permutation(np.arange(1, 41))[:2 * Mb] \
        .reshape(2, Mb).astype(np.int32)
    tables = np.stack([tables[0], tables[0], tables[1], tables[1],
                       tables[0], tables[1]])
    pos = np.array([room - 2 * tile, room - tile, 5, 0, room - 1, 3],
                   np.int32)
    lens = np.array([tile, tile - 1, min(tile, 3), 1, 1, 0], np.int32)
    got = np.asarray(paged_chunk_attention(
        k_pool, v_pool, q, tables, pos, lens, layer=LAYER,
        pages_per_step=pages_per_step))
    want = np.asarray(paged_chunk_attention_reference(
        k_pool, v_pool, q, tables, pos, lens, layer=LAYER))
    used = lens > 0
    assert np.isfinite(got[used]).all()
    np.testing.assert_allclose(
        got[used], want[used], atol=8 * 2.0 ** -9 * np.abs(want).max(),
        rtol=0)
    past = np.arange(tile)[None, :] >= lens[:, None]
    assert (got[past & used[:, None]] == 0).all() and (want[past] == 0).all()
    # and the fallback is the window's own lax attention, slot c of a
    # tile at position pos + c
    slots = np.minimum(np.arange(tile)[None, :], np.maximum(lens - 1, 0)
                       [:, None])
    dense = np.asarray(paged_attention_reference(
        k_pool, v_pool, q, tables, pos[:, None] + slots, layer=LAYER))
    live = ~past
    live[:, 1:] &= (slots[:, 1:] == np.arange(1, tile)[None, :])
    np.testing.assert_allclose(want[live], dense[live], atol=1e-6, rtol=0)


@pytest.mark.parametrize("lens, blocks", [
    ((0, 0, 4, 0, 3, 0), [2, 2, 2, 2, 4, 4]),   # skipped first, between, last
    ((2, 0, 0, 0, 0, 4), [0, 0, 0, 0, 0, 5]),
    ((0, 0, 0, 0, 0, 0), [0] * 6),      # a window of one-token rows alone
    ((0, 0, 0, 0, 0, 1), [5] * 6),
])
def test_paged_chunk_attention_moves_nothing_for_a_skipped_tile(lens,
                                                                blocks):
    """A tile of length 0 (the chunk step's one-token tiles, which the
    decode kernel takes) shares the last live tile's query and output
    block, or the first live tile's where none came before it, so no
    block is copied in or out for it: the live tiles come out as they
    do among live neighbours, whatever stands around them."""
    from paddle_tpu.ops import pallas_kernels as pk

    lens = np.array(lens, np.int32)
    assert np.asarray(pk._tile_blocks(jnp.asarray(lens))).tolist() == blocks
    rng, k_pool, v_pool = _paged_setup(seed=9, NB=40, bs=4, H=2, Dh=16)
    tile, Mb = 4, 10
    q = jnp.asarray(rng.randn(6, tile, 2, 16).astype(np.float32))
    tables = rng.permutation(np.arange(1, 41))[:Mb].astype(np.int32)
    tables = np.tile(tables, (6, 1))
    pos = np.array([0, 7, 12, 20, 30, 33], np.int32)
    got = np.asarray(pk.paged_chunk_attention(
        k_pool, v_pool, q, tables, pos, lens, layer=LAYER))
    want = np.asarray(pk.paged_chunk_attention_reference(
        k_pool, v_pool, q, tables, pos, lens, layer=LAYER))
    used = lens > 0
    np.testing.assert_allclose(
        got[used], want[used], atol=8 * 2.0 ** -9 * max(
            np.abs(want).max(), 1.0), rtol=0)


# ---------------------------------------------------------------------------
# fused int8 matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("M,K,N", [(5, 96, 70), (32, 128, 128), (1, 7, 3)])
def test_int8_matmul_bitwise_vs_unfused_chain(M, K, N):
    """int32 accumulation is exact over any K split and the in-kernel
    quantize is the quantize op's formula verbatim, so fused == unfused
    BITWISE (docs/KERNELS.md numerics policy — stronger than the
    documented int8-vs-fp32 tolerance)."""
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(M, K).astype(np.float32))
    w = jnp.asarray(rng.randint(-128, 128, size=(K, N)).astype(np.int8))
    dq = jnp.asarray((rng.rand(N).astype(np.float32) + 0.1) / 127.0)
    act_scale = float(127.0 / 3.0)
    fused = int8_matmul(x, w, dq, act_scale)
    ref = int8_matmul_reference(x, w, dq, act_scale)
    assert fused.dtype == jnp.float32
    assert bool(jnp.all(fused == ref))


# ---------------------------------------------------------------------------
# serving wiring: token identity with kernels forced on, bitwise
# identity with kernels off
# ---------------------------------------------------------------------------


def _spec_cfg():
    from paddle_tpu.serving import GenerationConfig

    return GenerationConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq_len=64)


def test_serving_decode_kernel_on_token_identical(monkeypatch):
    """The acceptance pin: the paged flash-decode serving leg
    (PTPU_KERNELS=1, interpret mode on CPU) is token-identical to the
    unbatched unpaged numpy reference decoder."""
    from paddle_tpu import serving
    from paddle_tpu.serving import GenerationModel, reference_decode

    monkeypatch.setenv("PTPU_KERNELS", "1")
    model = GenerationModel.random(_spec_cfg(), seed=11, name="pk")
    prompts = [[3, 7, 11, 2], [1, 2, 3], [40, 9, 22, 5, 8]]
    with serving.ServingEngine(model, max_batch=2, max_seq_len=64,
                               block_size=4) as eng:
        reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
        got = [eng.result(r, timeout=120) for r in reqs]
    assert got == [reference_decode(model, p, 8) for p in prompts]


def test_spec_step_kernel_on_token_identical(monkeypatch):
    """The verify-window kernel under the spec step returns the same
    greedy token at EVERY window slot as the lax chunk attention."""
    from paddle_tpu.serving import GenerationModel

    model = GenerationModel.random(_spec_cfg(), seed=13, name="pw")
    bs, mb, W = 4, 4, 3
    nb = 8
    cfg = model.config
    kv_shape = (cfg.n_layers, nb + 1, bs, cfg.n_heads, cfg.head_dim)

    def drive(env):
        if env is None:
            monkeypatch.delenv("PTPU_KERNELS", raising=False)
        else:
            monkeypatch.setenv("PTPU_KERNELS", env)
        step = model.make_spec_step(1, mb, W, return_logits=True)
        kv_k = jnp.zeros(kv_shape, jnp.float32)
        kv_v = jnp.zeros(kv_shape, jnp.float32)
        table = np.array([[5, 2, 7, 3]], np.int32)
        outs = []
        # window 1: prefill 3 prompt tokens; window 2: verify window
        feeds = [(np.array([[9, 33, 2]], np.int32), True, 0),
                 (np.array([[41, 17, 8]], np.int32), False, 3)]
        prev = jnp.zeros((1,), jnp.int32)
        for toks, use_prompt, pos in feeds:
            kv_k, kv_v, nxt, logits = step(
                model.weights, kv_k, kv_v, toks,
                np.array([use_prompt]), prev,
                np.array([pos], np.int32),
                np.array([3], np.int32), table, np.array([True]))
            prev = nxt[:, -1]
            outs.append((np.asarray(nxt).copy(),
                         np.asarray(logits).copy()))
        return outs

    ref = drive(None)      # lax chunk attention (CPU auto)
    onk = drive("1")       # spec_window kernel, interpret mode
    for (nt_ref, lg_ref), (nt_on, lg_on) in zip(ref, onk):
        assert (nt_ref == nt_on).all()
        np.testing.assert_allclose(lg_on, lg_ref, atol=2e-4, rtol=2e-4)


def test_serving_decode_kernels_off_bitwise_identical(monkeypatch):
    """PTPU_KERNELS=0 must reproduce the default CPU decode BITWISE
    (the AMP-off/quant-off identity pattern): on the CPU mesh the
    default (auto) policy already takes the lax paths, so forcing
    fallbacks changes nothing — logits included."""
    from paddle_tpu.serving import GenerationModel

    model = GenerationModel.random(_spec_cfg(), seed=17, name="pz")
    bs, mb = 4, 4
    nb = 8
    cfg = model.config
    kv_shape = (cfg.n_layers, nb + 1, bs, cfg.n_heads, cfg.head_dim)
    table = np.array([[5, 2, 7, 3]], np.int32)
    tokens = [9, 33, 2, 41, 17]

    def drive(env):
        if env is None:
            monkeypatch.delenv("PTPU_KERNELS", raising=False)
        else:
            monkeypatch.setenv("PTPU_KERNELS", env)
        step = model.make_decode_step(1, mb, return_logits=True)
        kv_k = jnp.zeros(kv_shape, jnp.float32)
        kv_v = jnp.zeros(kv_shape, jnp.float32)
        prev = jnp.zeros((1,), jnp.int32)
        logits = []
        for pos, tok in enumerate(tokens):
            kv_k, kv_v, prev, lg = step(
                model.weights, kv_k, kv_v,
                np.array([tok], np.int32), np.array([True]), prev,
                np.array([pos], np.int32), table, np.array([True]))
            logits.append(np.asarray(lg).copy())
        return logits

    ref = drive(None)
    off = drive("0")
    for a, b in zip(ref, off):
        assert (a == b).all()


def test_step_cache_keys_split_by_kernel_mode(monkeypatch):
    """A decode step traced under one PTPU_KERNELS mode must never
    serve another: the mode rides the step-cache key (empty suffix in
    the default state, so pre-kernel keys are unchanged)."""
    from paddle_tpu.serving import GenerationModel

    model = GenerationModel.random(_spec_cfg(), seed=19, name="ck")
    monkeypatch.delenv("PTPU_KERNELS", raising=False)
    model.make_decode_step(1, 4)
    assert (1, 4, False) in model._steps
    monkeypatch.setenv("PTPU_KERNELS", "1")
    model.make_decode_step(1, 4)
    assert (1, 4, False, "kernels:force") in model._steps
    assert len(model._steps) == 2


# ---------------------------------------------------------------------------
# fused int8 emission: module-text receipt (the PR-3 DCE-vanishes
# pattern) + bitwise program numerics
# ---------------------------------------------------------------------------


def _reset_build_state():
    import paddle_tpu as fluid
    from paddle_tpu import initializer, layer_helper, unique_name
    from paddle_tpu.core import scope as scope_mod

    fluid.framework.switch_main_program(fluid.Program())
    fluid.framework.switch_startup_program(fluid.Program())
    unique_name.switch()
    initializer._global_seed_counter[0] = 0
    layer_helper._op_seed_counter[0] = 0
    scope_mod._scope_stack[:] = [scope_mod.Scope()]
    return scope_mod.global_scope()


def _quantized_exe(monkeypatch, env):
    import paddle_tpu as fluid
    from paddle_tpu import layers, quant

    if env is None:
        monkeypatch.delenv("PTPU_KERNELS", raising=False)
    else:
        monkeypatch.setenv("PTPU_KERNELS", env)
    prog, sprog = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, sprog):
        x = layers.data(name="pk_x", shape=[48], dtype="float32")
        h = layers.fc(x, size=56, act="relu")
        out = layers.fc(h, size=24)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(sprog)
    rng = np.random.RandomState(0)
    feeds = [{"pk_x": rng.uniform(-1, 1, (4, 48)).astype(np.float32)}
             for _ in range(3)]
    table = quant.calibrate(prog, feeds)
    infer = prog.clone(for_test=True)
    quant.decorate(infer, mode="full_int8", table=table)
    got, = exe.run(infer, feed=feeds[0], fetch_list=[out])
    (step,) = [s for s in exe._cache.values() if s.fetch_names]
    return exe, step, feeds[0], np.asarray(got)


def test_full_int8_fused_matmul_module_text(monkeypatch):
    """The acceptance receipt: with the fused kernel on, the lowered
    module has NO standalone quantize HLO around the rewritten dense
    layers — pinned by the full-activation int8 tensor shapes
    ('4x48xi8' / '4x56xi8', distinct from the kernel's 32x128 blocks)
    vanishing from the StableHLO text, while the numerics stay bitwise
    the unfused chain's."""
    texts, outs = {}, {}
    for env in (None, "1"):
        scope = _reset_build_state()
        exe, step, feed, got = _quantized_exe(monkeypatch, env)
        mut = {n: scope.get(n) for n in step.mut_names}
        const = {n: scope.get(n) for n in step.const_names}
        texts[env] = step._jitted.lower(
            mut, const, feed, np.uint32(0)).as_text()
        outs[env] = got
        exe.close()
    # unfused: the quantize op materializes each full int8 activation
    assert "4x48xi8" in texts[None] and "4x56xi8" in texts[None]
    # fused: only the kernel's block-shaped int8 tiles remain
    assert "4x48xi8" not in texts["1"] and "4x56xi8" not in texts["1"]
    # and the answer is bit-for-bit the same
    assert (outs[None] == outs["1"]).all()


def test_fused_emission_respects_per_kernel_disable(monkeypatch):
    """PTPU_KERNELS_DISABLE=int8_matmul pins the historical 3-op
    emission even under force mode."""
    from paddle_tpu import quant

    monkeypatch.setenv("PTPU_KERNELS", "1")
    monkeypatch.setenv("PTPU_KERNELS_DISABLE", "int8_matmul")
    assert not quant._kernel_enabled("int8_matmul")
    monkeypatch.delenv("PTPU_KERNELS_DISABLE", raising=False)
    assert quant._kernel_enabled("int8_matmul")
