"""The chunk step over token rows (ISSUE 28, docs/SERVING.md "Chunked
prefill"): everything a token does alone runs over the window's real
tokens, compacted to ``max_tokens`` rows, and the window's attention
runs over query tiles. Pinned here, CPU, toy widths:

  * parity: the step built WITH ``max_tokens`` against the same step
    built without (every slot a row), on one pool and one window, for
    the windows a scheduler can plan: the same greedy tokens, the same
    logits, the same K/V pages for every real token; on the lax path
    tightly, through the ``chunk_window`` kernel (interpreted) within
    the rounding of its bf16 operands;
  * the promise the step rests on: ``plan_step`` never plans more than
    ``max_batch + prefill_token_budget`` tokens, hands the budget to
    the prefilling rows in the order they were admitted, and counts the
    rows it left without a token (``rows_deferred``);
  * the tiles: how many the step is compiled for, and that a window's
    tiles cover each of its tokens once;
  * which kernel a tile goes to (ISSUE 40): at a head of whole lane
    tiles the one-token tiles go to ``paged_decode_attention`` and the
    chunk kernel is told to skip them; a narrower head keeps the single
    call and traces no decode kernel inside the chunk step.
"""

import numpy as np
import pytest

from paddle_tpu.serving import (GenerationConfig, GenerationModel,
                                GenerationRequest, KVBlockPool,
                                RequestQueue, StepScheduler)
from paddle_tpu.serving import model as serving_model
from paddle_tpu.serving.model import CHUNK_TILE, chunk_tile_count

B, C, BS, MB = 4, 8, 4, 16          # rows, chunk, block size, blocks a row
TILE = 4                            # two tiles to a full chunk here
# a head of 128 lanes is the only one the kernel takes
WIDTHS = {"lax": dict(d_model=32, n_heads=2),
          "kernel": dict(d_model=128, n_heads=1)}

# (position, tokens, prefilling?) per row; None: the row is not active
WINDOWS = {
    "full_chunk_among_decode_rows":
        [(9, 1, False), (0, 8, True), (17, 1, False), (5, 1, False)],
    "last_chunk_shorter_than_the_window":
        [(9, 1, False), (8, 3, True), (30, 1, False), (5, 1, False)],
    "short_prefills_up_to_the_budget":
        [(0, 3, True), (4, 2, True), (21, 1, False), (8, 4, True)],
    "a_row_sits_out_and_a_row_is_inactive":
        [(0, 8, True), None, None, (13, 1, False)],
    "chunk_starts_mid_block_and_crosses_blocks":
        [(6, 7, True), (3, 1, False), (11, 5, True), (2, 1, False)],
    "all_decode_rows_dispatched_as_chunk":
        [(9, 1, False), (1, 1, False), (17, 1, False), (40, 1, False)],
    # windows the routing of one-token tiles splits (ISSUE 40): every
    # tile the decode kernel's; a chunk whose tail tile holds one token;
    # a one-token prompt at position 0; idle rows between live ones
    "decode_rows_alone_at_both_ends_of_a_context":
        [(62, 1, False), (1, 1, False), (33, 1, False), (16, 1, False)],
    "chunk_tail_tile_of_one_token":
        [(9, 1, False), (3, 5, True), (17, 1, False), (0, 5, True)],
    "one_token_prompt_at_position_0":
        [(0, 1, True), (12, 1, False), (0, 8, True), (5, 1, False)],
    "idle_rows_between_live_ones":
        [(9, 1, False), None, (4, 5, True), None],
}


_MODELS = {}


def _model(widths):
    """One model a width, so that its compiled steps are shared."""
    if widths not in _MODELS:
        _MODELS[widths] = GenerationModel.random(GenerationConfig(
            vocab_size=64, n_layers=2, d_ff=64, max_seq_len=MB * BS,
            **WIDTHS[widths]), seed=5)
    return _MODELS[widths]


def _feed(rows, cfg, seed=0):
    """The window's arrays and a pool whose pages before each row's
    window hold a random history."""
    rng = np.random.RandomState(seed)
    tables = np.zeros((B, MB), np.int32)
    positions, lengths = np.zeros(B, np.int32), np.zeros(B, np.int32)
    active, use_prompt = np.zeros(B, bool), np.zeros(B, bool)
    pages = list(rng.permutation(np.arange(1, B * MB + 1)))
    for b, row in enumerate(rows):
        if row is None:
            # a row that sits a step out keeps its table and position
            tables[b, :3] = [pages.pop() for _ in range(3)]
            positions[b], lengths[b] = 7, 0
            continue
        pos, n, prefill = row
        need = -(-(pos + n) // BS)
        tables[b, :need] = [pages.pop() for _ in range(need)]
        positions[b], lengths[b] = pos, n
        active[b], use_prompt[b] = True, prefill
    pool = KVBlockPool(cfg.n_layers, cfg.n_heads, cfg.head_dim, BS, B * MB)
    kv = [rng.randn(*pool.k.shape).astype(np.float32) * 0.5
          for _ in range(2)]
    tokens = rng.randint(0, cfg.vocab_size, (B, C)).astype(np.int32)
    prev = rng.randint(0, cfg.vocab_size, (B,)).astype(np.int32)
    return kv, (tokens, use_prompt, prev, positions, lengths, tables,
                active)


def _run(model, max_tokens, kv, feed):
    import jax.numpy as jnp

    step = model.make_prefill_step(B, MB, C, return_logits=True,
                                   max_tokens=max_tokens)
    k, v, nxt, logits = step(model.weights, jnp.asarray(kv[0]),
                             jnp.asarray(kv[1]), *feed)
    # page 0 is the null page: padding rows and invalid slots land there
    return (np.asarray(nxt), np.asarray(logits), np.asarray(k)[:, 1:],
            np.asarray(v)[:, 1:])


@pytest.mark.parametrize("path", ["lax", "kernel", "kernel_every_slot"])
@pytest.mark.parametrize("window", sorted(WINDOWS))
def test_chunk_step_over_token_rows_matches_every_slot(window, path,
                                                       monkeypatch):
    monkeypatch.setattr(serving_model, "CHUNK_TILE", TILE)
    widths = "lax" if path == "lax" else "kernel"
    model = _model(widths)
    rows = WINDOWS[window]
    kv, feed = _feed(rows, model.config)
    held = sum(r[1] for r in rows if r is not None)
    # the promise: exactly what the window holds where that is fewer
    # than its slots, so no padding row hides a dropped token
    max_tokens = None if path == "kernel_every_slot" else held
    monkeypatch.setenv("PTPU_KERNELS", "0")
    want = _run(model, None, kv, feed)
    if path != "lax":
        monkeypatch.setenv("PTPU_KERNELS", "1")
    got = _run(model, max_tokens, kv, feed)
    on = feed[-1]
    # lax: the same arithmetic in another order; kernel: bf16 operands
    tol = 2e-4 if path == "lax" else 16 * 2.0 ** -9 * np.abs(want[1]).max()
    np.testing.assert_allclose(got[1][on], want[1][on], atol=tol, rtol=0)
    if path == "lax":
        assert (got[0] == want[0])[on].all()
    for g, w in zip(got[2:], want[2:]):
        np.testing.assert_allclose(
            g, w, atol=1e-5 if path == "lax" else 16 * 2.0 ** -9 * 2.0,
            rtol=0)
    # the real tokens' pages were written at all: layer 0's K at each
    # token's position is no longer the history's
    k0 = got[2][0]
    for b, row in enumerate(rows):
        if row is None:
            continue
        pos, n, _ = row
        for p in range(pos, pos + n):
            page = feed[5][b, p // BS] - 1
            assert not np.allclose(k0[page, p % BS],
                                   kv[0][0, page + 1, p % BS]), (b, p)


@pytest.mark.parametrize("widths", ["kernel", "lax"])
def test_one_token_tiles_go_to_the_decode_kernel_where_both_qualify(
        widths, monkeypatch):
    """At a head of 128 lanes every layer of the chunk step makes one
    `paged_decode_attention` call over the window's tiles, active on the
    one-token ones, and `paged_chunk_attention` is handed those tiles
    at length 0. At a head of 16 (`_chunk_qualify` false: the decode
    kernel would be the grid over every table slot) the step keeps its
    single call over the lax tiles and traces no decode kernel."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(serving_model, "CHUNK_TILE", TILE)
    monkeypatch.setenv("PTPU_KERNELS", "1")
    calls = {"decode": [], "chunk": [], "lax": []}
    real = {"decode": pk.paged_decode_attention,
            "chunk": pk.paged_chunk_attention,
            "lax": pk.paged_chunk_attention_reference}

    def decode(k, v, q, tables, pos, **kw):
        calls["decode"].append(np.asarray(kw["active"]))
        return real["decode"](k, v, q, tables, pos, **kw)

    def tiles(which):
        def call(k, v, q, tables, pos, lens, **kw):
            calls[which].append(np.asarray(lens))
            return real[which](k, v, q, tables, pos, lens, **kw)
        return call

    monkeypatch.setattr(pk, "paged_decode_attention", decode)
    monkeypatch.setattr(pk, "paged_chunk_attention", tiles("chunk"))
    monkeypatch.setattr(pk, "paged_chunk_attention_reference", tiles("lax"))
    model = GenerationModel.random(GenerationConfig(
        vocab_size=64, n_layers=2, d_ff=64, max_seq_len=MB * BS,
        **WIDTHS[widths]), seed=5)
    rows = WINDOWS["chunk_tail_tile_of_one_token"]
    kv, feed = _feed(rows, model.config)
    with jax.disable_jit():     # the calls see the window's own numbers
        model.make_prefill_step(B, MB, C, max_tokens=B + C).__wrapped__(
            model.weights, jnp.asarray(kv[0]), jnp.asarray(kv[1]),
            *[jnp.asarray(a) for a in feed])
    if widths == "lax":
        assert not calls["decode"] and not calls["chunk"]
        assert [c.tolist() for c in calls["lax"]] == [[1, 4, 1, 1, 4, 1]] * 2
        return
    assert not calls["lax"]
    assert [c.tolist() for c in calls["decode"]] == [
        [True, False, True, True, False, True]] * 2
    assert [c.tolist() for c in calls["chunk"]] == [[0, 4, 0, 0, 4, 0]] * 2


@pytest.mark.parametrize("disable", ["", "paged_decode"],
                         ids=["routed", "decode_kernel_off"])
def test_padding_rows_read_nothing_of_an_unwritten_tile(disable,
                                                        monkeypatch):
    """`paged_chunk_attention` writes nothing for a tile it skips or
    that holds no token (the interpreter leaves NaN there), and a
    padding token row's `back` may point into one: the step takes zero
    for such a row, routed or (`PTPU_KERNELS_DISABLE=paged_decode`: the
    chunk kernel alone) not, so the null page a padding row writes to
    stays finite for a lax step that gathers it, and the real rows
    match the lax path."""
    import jax.numpy as jnp

    monkeypatch.setattr(serving_model, "CHUNK_TILE", TILE)
    model = _model("kernel")
    rows = WINDOWS["chunk_tail_tile_of_one_token"]
    kv, feed = _feed(rows, model.config)
    monkeypatch.setenv("PTPU_KERNELS", "0")
    want = _run(model, None, kv, feed)
    monkeypatch.setenv("PTPU_KERNELS", "1")
    monkeypatch.setenv("PTPU_KERNELS_DISABLE", disable)
    # 20 token rows for the window's 12 tokens, 8 tiles for its 6
    step = model.make_prefill_step(B, MB, C, return_logits=True,
                                   max_tokens=B + 2 * C)
    k, v, _nxt, logits = step(model.weights, jnp.asarray(kv[0]),
                              jnp.asarray(kv[1]), *feed)
    assert np.isfinite(np.asarray(k)).all()
    assert np.isfinite(np.asarray(v)).all()
    on = feed[-1]
    np.testing.assert_allclose(
        np.asarray(logits)[on], want[1][on],
        atol=16 * 2.0 ** -9 * np.abs(want[1]).max(), rtol=0)
    for g, w in zip((k, v), want[2:]):
        np.testing.assert_allclose(np.asarray(g)[:, 1:], w,
                                   atol=16 * 2.0 ** -9 * 2.0, rtol=0)


def test_a_window_holding_fewer_tokens_than_rows_pads(monkeypatch):
    """Padding rows (the promise is larger than what the window holds)
    change nothing: they write to the null page and attend nothing."""
    monkeypatch.setenv("PTPU_KERNELS", "0")
    model = _model("lax")
    kv, feed = _feed(WINDOWS["short_prefills_up_to_the_budget"],
                     model.config)
    want = _run(model, None, kv, feed)
    got = _run(model, B + 2 * C, kv, feed)            # 20 rows, 10 tokens
    np.testing.assert_allclose(got[1], want[1], atol=2e-4, rtol=0)
    assert (got[0] == want[0]).all()
    for g, w in zip(got[2:], want[2:]):
        np.testing.assert_allclose(g, w, atol=1e-5, rtol=0)


@pytest.mark.parametrize("seed", range(4))
def test_plan_step_keeps_the_promise_of_max_tokens(seed):
    """The step computes `max_batch + prefill_token_budget` token rows
    and nothing past them: over random admissions no planned window
    holds more, whatever the prompts, the chunk and the budget."""
    rng = np.random.RandomState(seed)
    max_batch, chunk = int(rng.randint(2, 7)), int(rng.choice([2, 4, 8]))
    budget = int(rng.randint(1, 3 * chunk))
    pool = KVBlockPool(1, 1, 4, 4, num_blocks=256)
    sched = StepScheduler(max_batch, pool, 64, prefill_chunk=chunk,
                          prefill_token_budget=budget)
    queue = RequestQueue(64)
    pending, planned_mixed = 40, 0
    for _ in range(5000):
        while pending and len(queue) < 8 and rng.rand() < 0.5:
            queue.submit(GenerationRequest(
                rng.randint(1, 60, size=rng.randint(1, 40)).tolist(),
                max_new_tokens=int(rng.randint(1, 6))))
            pending -= 1
        sched.admit(queue)
        plan, kind = sched.plan_step()
        if kind == "mixed":
            planned_mixed += 1
            lens = sched.chunk_lens[sched.active]
            assert lens.sum() <= max_batch + budget
            assert sched.chunk_lens[sched.active
                                    & sched.use_prompt].sum() <= budget
            assert (sched.chunk_lens[~sched.active] == 0).all()
            assert lens.max() <= chunk
            tiles = -(-sched.chunk_lens // TILE)
            assert tiles.sum() <= chunk_tile_count(
                max_batch, chunk, max_batch + budget, TILE)
            # the rows that sat out: still mid-prompt, given nothing,
            # counted, and every one admitted after every row fed
            sat_out = [s for s in sched.slots
                       if s is not None and not s.dispatch_done
                       and not sched.active[s.slot]]
            fed = [s for s, _ in plan if sched.use_prompt[s.slot]]
            assert all(s.in_prefill for s in sat_out)
            assert sched.rows_deferred == len(sat_out)
            assert not sat_out or (max(s.admitted for s in fed)
                                   < min(s.admitted for s in sat_out))
        else:
            assert sched.rows_deferred == 0
        for seq, gen_idx in plan:
            sched.record_token(seq, gen_idx, int(rng.randint(1, 60)))
        sched.reap()
        if not pending and not len(queue) and not sched.has_work():
            break
    assert planned_mixed > 10 and not sched.has_work()

@pytest.mark.parametrize("max_batch,window,max_tokens,tile,want", [
    (16, 256, 16 + 1024, 64, 16 + 1024 // 64),      # the benchmark's engine
    # the chosen tile is the benchmark's whole chunk: a tile a row
    (16, 256, 16 + 1024, CHUNK_TILE, 16),
    # ... and since the unstated budget stops at the ridge (one chunk
    # there): fewer token rows, the same tiles
    (16, 256, 16 + 256, CHUNK_TILE, 16),
    (16, 256, 16 + 256, 64, 16 + 256 // 64),
    (16, 2 * CHUNK_TILE, 16 + 4 * 2 * CHUNK_TILE, CHUNK_TILE, 16 + 8),
    (16, 256, None, 64, 16 * 4),                    # every slot's tile
    (16, 256, 16 * 256, 64, 16 * 4),
    (4, 8, 4 + 8, 4, 6),        # rows of 1, 1, 5 and 5 tokens
    (4, 8, 4 + 32, 4, 8),       # no more than every slot's
    (4, 6, 4 + 6, 4, 5),        # a window that is no whole tiles
    (128, 16, 128 + 64, 64, 128),
])
def test_chunk_tile_count(max_batch, window, max_tokens, tile, want):
    assert chunk_tile_count(max_batch, window, max_tokens, tile) == want


@pytest.mark.parametrize("seed", range(3))
def test_tiles_cover_each_token_once(seed):
    """`_chunk_layout`: the tiles' token rows, taken tile by tile up to
    each tile's length, are the window's token rows in order; each tile
    carries its row's table line and its first token's position; `back`
    finds each token row's tile slot; `last` each row's last token."""
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    n_rows, window, tile, bs = 5, 12, 4, 4
    lengths = rng.randint(0, window + 1, n_rows).astype(np.int32)
    lengths[rng.randint(n_rows)] = 1
    active = rng.rand(n_rows) < 0.8
    active[0] = True
    held = int(lengths[active].sum())
    positions = rng.randint(0, 20, n_rows).astype(np.int32)
    tables = rng.randint(1, 99, (n_rows, 16)).astype(np.int32)
    for rows in (held + 3, n_rows * window):
        n_tiles = chunk_tile_count(n_rows, window, rows, tile)
        lay = {k: np.asarray(v) for k, v in serving_model._chunk_layout(
            jnp, jnp.asarray(positions), jnp.asarray(lengths),
            jnp.asarray(active), jnp.asarray(tables), window, rows, tile,
            n_tiles, bs).items()}
        live = np.flatnonzero(lay["live"])
        assert len(live) == held
        # token rows in slot order, a row's tokens together
        want = [(b, c) for b in range(n_rows) if active[b]
                for c in range(lengths[b])]
        assert [(a // window, a % window) for a in lay["at"][live]] == want
        assert lay["pos"][live].tolist() == [positions[b] + c
                                             for b, c in want]
        assert (lay["write_blk"][~lay["live"]] == 0).all()
        assert lay["write_blk"][live].tolist() == [
            tables[b, (positions[b] + c) // bs] for b, c in want]
        covered = []
        for n in range(n_tiles):
            ln = lay["tile_len"][n]
            assert 0 <= ln <= tile
            if not ln:
                continue
            first = lay["tile_rows"][n, 0]
            b, c = want[list(live).index(first)]
            assert c % tile == 0
            assert (lay["tile_tables"][n] == tables[b]).all()
            assert lay["tile_pos"][n] == positions[b] + c
            assert ln == min(tile, lengths[b] - c)
            covered += lay["tile_rows"][n, :ln].tolist()
        assert covered == live.tolist()
        slots = lay["tile_rows"].reshape(-1)
        assert (slots[lay["back"][live]] == live).all()
        for b in range(n_rows):
            if active[b] and lengths[b]:
                assert want[list(live).index(lay["last"][b])] \
                    == (b, lengths[b] - 1)
