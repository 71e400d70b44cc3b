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


# ---------------------------------------------------------------------------
# the decode kernel's page pipe: a run sized by its bytes, a row's last
# run starting the next live row's first
# ---------------------------------------------------------------------------

def _decode_both(kp, vp, q, tables, pos, active, window, pages=None,
                 layer=0, kernel_pools=None):
    """(kernel, fallback) over the same rows; `kernel_pools`: what the
    kernel reads where that is not what the fallback reads."""
    kw = {} if pages is None else {"pages_per_step": pages}
    got = pk.gqa_paged_decode_attention(
        *(kernel_pools or (kp, vp)), q, tables, pos, layer=layer,
        window=window, active=active, **kw)
    want = pk.gqa_paged_decode_attention_reference(
        kp, vp, q, tables, pos, layer=layer, window=window, active=active)
    return np.asarray(got), np.asarray(want)


@pytest.mark.parametrize("pos", [
    pytest.param([31, 31, 15, 31, 0], id="exactly_one_run"),
    pytest.param([32, 40, 47, 33, 45], id="one_run_and_a_partial_second"),
    pytest.param([127, 100, 96, 64, 111], id="many_runs"),
    pytest.param([0, 127, 31, 32, 5], id="one_page_to_a_full_table"),
])
@pytest.mark.parametrize("window", [None, 24])
def test_decode_rows_of_one_run_of_a_partial_second_and_of_many(
        paged, pos, window):
    """Runs of 2 pages of 16 tokens: rows that end on a run's last
    token, one token into the next, and eight pages in."""
    (kp, vp), tables, q, _pos, _lens = paged
    got, want = _decode_both(kp, vp, q[:, 0], tables,
                             np.array(pos, np.int32), None, window, pages=2)
    assert np.abs(got - want).max() < 1e-2


@pytest.mark.parametrize("active", [
    pytest.param([0, 1, 0, 1, 0], id="inactive_at_both_ends_and_between"),
    pytest.param([1, 0, 0, 0, 1], id="first_and_last_alone"),
    pytest.param([0, 0, 1, 0, 0], id="one_live_row_opens_and_closes_alone"),
    pytest.param([1, 1, 1, 1, 1], id="every_row_live"),
    pytest.param([0, 0, 0, 0, 0], id="no_row_live"),
    pytest.param([0, 1, 1, 0, 1], id="a_pair_then_a_gap"),
])
@pytest.mark.parametrize("pages", [2, 3])
def test_the_hand_over_skips_inactive_rows(paged, active, pages):
    """A live row's last run starts the NEXT LIVE row's first, whatever
    lies between; an inactive row comes out zero and moves nothing."""
    (kp, vp), tables, q, pos, _lens = paged
    active = np.array(active, np.int32)
    got, want = _decode_both(kp, vp, q[:, 0], tables, pos, active, None,
                             pages=pages)
    assert np.abs(got - want).max() < 1e-2
    assert not got[active == 0].any()
    assert got[active == 1].any() == bool(active.any())


@pytest.mark.parametrize("live,want", [
    ([1, 1, 1], [1, 2, 3]), ([1, 0, 1], [2, 2, 3]), ([0, 0, 1], [2, 2, 3]),
    ([1, 0, 0], [3, 3, 3]), ([0, 0, 0], [3, 3, 3]), ([0, 1, 0], [1, 3, 3]),
])
def test_next_live_names_the_next_live_row_or_the_row_count(live, want):
    np.testing.assert_array_equal(
        np.asarray(pk._next_live(jnp.asarray(live) > 0)), want)


@pytest.mark.parametrize("pages", [1, 2, 8])
def test_a_window_row_is_opened_at_its_own_first_live_page(paged, pages):
    """Window 24 of 16-token pages: every row's walk starts past page 0,
    at a page of its own (`first_page` of the NEXT row is what the row
    before it must start). The table entries before it are released
    (the null page) and the null page is poisoned: never read."""
    (kp, vp), tables, q, _pos, _lens = paged
    pos = np.array([40, 100, 77, 127, 58], np.int32)
    nulled = tables.copy()
    for b, p in enumerate(pos):
        nulled[b, :(p - 24 + 1) // BS] = 0
    assert (nulled[:, 0] == 0).all()
    poisoned = [p.at[:, 0].set(jnp.nan) for p in (kp, vp)]
    got, want = _decode_both(kp, vp, q[:, 0], tables, pos, None, 24,
                             pages=pages, kernel_pools=poisoned)
    released, _ = _decode_both(kp, vp, q[:, 0], nulled, pos, None, 24,
                               pages=pages, kernel_pools=poisoned)
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() < 1e-2
    np.testing.assert_array_equal(released, got)


@pytest.mark.parametrize("width,run", [
    pytest.param(2 * 128, 32, id="pages_of_32KiB"),
    pytest.param(8 * 128, 8, id="pages_of_128KiB")])
def test_a_run_is_sized_by_the_bytes_of_a_page(width, run, monkeypatch):
    """The rule on the served pools' pages, and the kernel called as the
    steps call it (no `pages_per_step`) over such pages: rows of one
    run, of a run and a partial second (of one page: 32 KiB), of less."""
    import jax

    rng = np.random.default_rng(3)
    n_kv, bs, mb, nb = width // 128, 64, 40, 44
    pool = jax.ShapeDtypeStruct((1, nb + 1, bs, width), jnp.bfloat16)
    assert pk.gqa_pages_per_run(pool, 192) == run
    assert pk.gqa_pages_per_run(pool, 5) == 5          # a table's length
    assert pk.gqa_pages_per_run(
        jax.ShapeDtypeStruct((1, 9, 4096, width), jnp.bfloat16), 192) == 1
    kp, vp = (jnp.asarray(rng.normal(size=pool.shape), jnp.bfloat16)
              for _ in range(2))
    tables = (rng.permutation(nb)[:mb] + 1).astype(np.int32)
    tables = np.stack([tables, tables[::-1], np.roll(tables, 7)])
    q = jnp.asarray(rng.normal(size=(3, 4 * n_kv, 128)), jnp.bfloat16) \
        .astype(jnp.float32)
    pos = np.array([run * bs - 1, run * bs, 100], np.int32)
    calls = []
    real = pk._gqa_call

    def spy(*a, **kw):
        calls.append(kw["pages"])
        return real(*a, **kw)

    monkeypatch.setattr(pk, "_gqa_call", spy)
    got, want = _decode_both(kp, vp, q, tables, pos, None, None)
    assert calls == [run]
    assert np.abs(got - want).max() < 1e-2


@pytest.mark.parametrize("window", [None, 24])
def test_the_mixed_steps_call_over_tiles(paged, window):
    """As `_PagedWindow` calls the two kernels in a mixed step: the
    tiles of ONE token go through the decode kernel (`active =
    one_token`, live tiles with a prefilling row's tiles between them,
    which share that row's table line) and the chunk kernel skips them;
    together they are the fallback over every tile."""
    (kp, vp), tables, q, _pos, _lens = paged
    rows = np.array([0, 1, 1, 1, 2, 3, 3, 4])         # a tile's batch row
    t_tables = tables[rows]
    t_pos = np.array([50, 16, 24, 32, 90, 0, 8, 127], np.int32)
    t_len = np.array([1, 8, 8, 1, 1, 8, 3, 1], np.int32)
    rng = np.random.default_rng(5)
    tiles = np.asarray(jnp.asarray(rng.normal(size=(8, CQ, H, DH)),
                                   jnp.bfloat16).astype(jnp.float32))
    one_token = t_len == 1
    ctx = pk.gqa_paged_chunk_attention(
        kp, vp, tiles, t_tables, t_pos, np.where(one_token, 0, t_len),
        layer=1, window=window, pages_per_step=2)
    first = pk.gqa_paged_decode_attention(
        kp, vp, tiles[:, 0], t_tables, t_pos, layer=1, window=window,
        active=one_token, pages_per_step=2)
    assert not np.asarray(first)[~one_token].any()
    ctx = ctx.at[:, 0].set(jnp.where(one_token[:, None, None], first,
                                     ctx[:, 0]))
    want = pk.gqa_paged_attention_reference(
        kp, vp, tiles, t_tables, t_pos, t_len, layer=1, window=window)
    assert float(jnp.max(jnp.abs(ctx - want))) < 1e-2


def test_the_step_log_and_the_kernel_take_the_run_from_one_function(
        monkeypatch):
    """`engine._pages_walked_by_kind` counts a decode step's runs by
    `pk.gqa_pages_per_run`, the function the kernel's call asks: a rule
    that says 3 pages is what both then go by."""
    from types import SimpleNamespace

    import jax

    from paddle_tpu.serving.afmoe import AfmoeBlock
    from paddle_tpu.serving.engine import _ModelWorker
    from paddle_tpu.serving.kv_cache import PageKind
    from paddle_tpu.serving.zaya import ZayaBlock

    asked = []

    def rule(pool, table_len):
        asked.append((tuple(pool.shape), int(table_len)))
        return 3

    monkeypatch.setattr(pk, "gqa_pages_per_run", rule)
    assert AfmoeBlock.decode_pages_per_run is ZayaBlock.decode_pages_per_run
    bs, mb = 16, 8
    pool = jax.ShapeDtypeStruct((2, 41, bs, HKV * DH), jnp.bfloat16)
    positions = np.array([0, 47, 48, 100, 127, 5], np.int64)
    active = np.array([1, 1, 1, 0, 1, 1], bool)
    lens = np.array([1, 1, 1, 1, 1, 9], np.int64)       # row 5 prefills
    worker = SimpleNamespace(
        scheduler=SimpleNamespace(active=active, positions=positions,
                                  chunk_lens=lens, max_blocks_per_seq=mb),
        pool=SimpleNamespace(
            block_size=bs, arrays=(pool, pool),
            kinds=(PageKind("global", [0, 2]),
                   PageKind("window", [1], window=24))),
        model=SimpleNamespace(config=SimpleNamespace(block=AfmoeBlock)))
    rec = _ModelWorker._pages_walked_by_kind(worker)
    assert asked == [((2, 41, bs, HKV * DH), mb)]
    # rows 0, 1, 2, 4 hold one token: 1, 3, 4, 8 pages from page 0 on
    # the two global layers; 1, 2, 2, 2 pages under the window of 24
    assert rec["decode_rows_walked"] == 4 * 3
    assert rec["decode_runs_walked"] == 2 * (1 + 1 + 2 + 3) + (1 + 1 + 1 + 1)
    assert rec["decode_rows_opened_warm"] == 3 * 3
    assert rec["global_pages_walked"] == 2 * (1 + 3 + 4 + 8 + 1)
    # ... and the kernel's own call asks the same function
    rng = np.random.default_rng(0)
    kp = jnp.asarray(rng.normal(size=pool.shape), jnp.bfloat16)
    pk.gqa_paged_decode_attention(
        kp, kp, jnp.ones((2, H, DH)), np.ones((2, mb), np.int32),
        np.array([3, 20], np.int32), layer=0)
    assert asked[1:] == [((2, 41, bs, HKV * DH), mb)]


@pytest.mark.parametrize("active,pos,window,pages", [
    pytest.param([0, 1, 0, 1, 1], [0, 47, 3, 100, 127], None, 2,
                 id="gaps"),
    pytest.param([1, 1, 1, 1, 1], [31, 32, 15, 64, 127], 24, 2,
                 id="window_rows_of_one_and_two_runs"),
    pytest.param([1, 0, 0, 0, 1], [127, 0, 0, 0, 5], None, 3,
                 id="ends_alone"),
])
def test_every_run_is_waited_for_before_it_is_read(paged, monkeypatch,
                                                   active, pos, window,
                                                   pages):
    """Under the TPU interpreter, which simulates the DMAs and their
    semaphores: a copy moves its bytes only when it is WAITED for, so a
    run attended before its wait (or a next row's first run never
    waited for) reads zeros and misses the fallback; and its race
    detector sees a buffer half written while it is read."""
    from jax.experimental.pallas import tpu as pltpu
    try:
        from jax._src.pallas.mosaic.interpret import interpret_pallas_call
    except ImportError:
        pytest.skip("this jax keeps its TPU interpreter elsewhere")
    params = pltpu.InterpretParams(detect_races=True,
                                   dma_execution_mode="on_wait")
    monkeypatch.setattr(pk._device, "pallas_interpret", lambda: params)
    (kp, vp), tables, q, _pos, _lens = paged
    got, want = _decode_both(kp, vp, q[:, 0], tables,
                             np.array(pos, np.int32),
                             np.array(active, np.int32), window,
                             pages=pages)
    assert np.abs(got - want).max() < 1e-2
    assert not interpret_pallas_call.races.races_found
