"""The grouped-query window/global serving block (serving/afmoe.py)
against its plain reference (perfbench/reference/trinity.py), at toy
widths on the CPU with seeded weights: the served path (chunked prefill,
then decode through both kinds of page, contexts several windows long),
the share of an eight-chip expert layer, rotary on the window layers
only, the refusals, and the step log's fields.
"""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.observability import metrics
from paddle_tpu.serving import (GenerationConfig, GenerationModel,
                                KVBlockPool, ServingEngine, afmoe,
                                latent_moe)
from paddle_tpu.serving.afmoe import AfmoeBlock
from perfbench.reference import trinity as ref
from perfbench.runners import serve_window

SEED = 2147483659      # past 32 signed bits, as the driver's seeds are
TYPES = ["sliding_attention", "sliding_attention", "full_attention",
         "sliding_attention"]


def toy_config(**changes):
    """A configuration file's keys at toy widths (heads of 128 lanes, so
    that the kernels take them), float32 throughout so that the served
    path and the reference agree to rounding."""
    cfg = dict(
        family="trinity",
        vocab_size=96, hidden_size=64, num_attention_heads=4,
        num_key_value_heads=2, head_dim=128, num_hidden_layers=4,
        num_dense_layers=1, layer_types=TYPES, sliding_window=16,
        intermediate_size=96, moe_intermediate_size=32, num_experts=8,
        router_experts=8, num_experts_per_tok=2, num_shared_experts=1,
        rope_theta=10000.0, rms_norm_eps=1e-5, route_scale=2.448,
        mup_enabled=True, init_std=0.3, weight_dtype="float32",
        dtypes={"weights": "float32", "activations": "float32",
                "router": "float32", "cache": "float32"})
    cfg.update(changes)
    return cfg


def served_model(cfg, max_seq_len=96):
    return GenerationModel(
        serve_window.generation_config(cfg, max_seq_len),
        serve_window.seeded_weights(ref, cfg, SEED))


@pytest.fixture
def kernels(request, monkeypatch):
    """PTPU_KERNELS off (the lax paths) or forced (the Pallas kernels in
    the interpreter)."""
    monkeypatch.setenv("PTPU_KERNELS", request.param)
    return request.param == "1"


def reference_logits(cfg, seq, rows):
    params = ref.make_params(SEED, cfg)
    return np.asarray(ref.logits_at(params, jnp.asarray(seq, jnp.int32),
                                    jnp.asarray(rows), cfg))


# -- the served path against the reference's full forward -------------------

def serve_by_hand(model, prompts, n_new, B, Mb, bs, C, max_tokens=None):
    """Chunked prefill, then one-token steps, through the model's own
    compiled steps and a pool of two page kinds, the window kind's pages
    RELEASED as they slide out (their table entries nulled): [(position,
    logits)] a row and the tokens fed."""
    cfg = model.config
    kinds = model.page_kinds()
    if kinds is None:                   # every layer global: one kind
        pool = KVBlockPool(cfg.n_layers, cfg.n_heads, cfg.head_dim, bs,
                           B * Mb, entry=model.cache_entry())
    else:
        pool = KVBlockPool(cfg.n_layers, cfg.n_heads, cfg.head_dim, bs,
                           [B * Mb] * len(kinds), entry=model.cache_entry(),
                           kinds=kinds)
    kinds = pool.kinds
    tables = np.zeros((len(kinds), B, Mb), np.int32)
    for b in range(B):
        assert pool.reserve(b, [Mb] * len(kinds))
    chunk = model.make_prefill_step(B, Mb, C, return_logits=True,
                                    max_tokens=max_tokens)
    decode = model.make_decode_step(B, Mb, return_logits=True)
    arrays = pool.arrays
    lens = [len(p) for p in prompts]
    pos = np.zeros(B, np.int32)
    idle = jnp.zeros(B, jnp.int32)
    seqs = [list(p) for p in prompts]
    got = [[] for _ in range(B)]

    def step_tables():
        """As the engine hands them over: the stack, or the one table."""
        return (tables if len(kinds) > 1 else tables[0]).copy()

    def pages(b, n):
        """What the scheduler does before a step of n tokens at pos[b]."""
        for k, kind in enumerate(kinds):
            if kind.window is not None:
                head = pool.pages_released(b, k)
                gone = pool.release_head(
                    b, k, kind.first_live_page(pos[b], bs))
                tables[k, b, head:head + len(gone)] = 0
        for p in range(pos[b], pos[b] + n):
            if p % bs == 0:
                for k in range(len(kinds)):
                    tables[k, b, p // bs] = pool.alloc_block(b, k)

    while any(pos[b] < lens[b] for b in range(B)):
        feed = np.zeros((B, C), np.int32)
        n = np.array([min(C, lens[b] - pos[b]) for b in range(B)], np.int32)
        if max_tokens is not None:
            turn = int(np.flatnonzero(n)[0])
            n = np.where(np.arange(B) == turn, n, 0).astype(np.int32)
        for b in range(B):
            feed[b, :n[b]] = prompts[b][pos[b]:pos[b] + n[b]]
            pages(b, n[b])
        on = n > 0
        out = chunk(model.weights, *arrays, feed, on, idle, pos.copy(), n,
                    step_tables(), on)
        arrays, logits = out[:len(arrays)], out[-1]
        pos += n
        for b in np.flatnonzero(on):
            got[b].append((pos[b] - 1, np.asarray(logits[b])))
    tok = np.array([int(np.argmax(got[b][-1][1])) for b in range(B)],
                   np.int32)
    on = np.ones(B, bool)
    for _ in range(n_new):
        for b in range(B):
            seqs[b].append(int(tok[b]))
            pages(b, 1)
        out = decode(model.weights, *arrays, tok, on, idle, pos.copy(),
                     step_tables(), on)
        arrays, nxt, logits = out[:len(arrays)], out[len(arrays)], out[-1]
        for b in range(B):
            got[b].append((pos[b], np.asarray(logits[b])))
        pos += 1
        tok = np.asarray(nxt)
    assert pool.check_invariants() == []
    # a row never held more window pages than window + chunk + a block
    return got, seqs, pool


@pytest.mark.parametrize("kernels,max_tokens", [("0", None), ("1", None),
                                                ("1", 12)], indirect=["kernels"])
def test_served_path_equals_the_reference_forward(kernels, max_tokens):
    cfg = toy_config()
    model = served_model(cfg)
    rng = np.random.default_rng(3)
    # contexts that cross the window of 16 four and five times
    prompts = [rng.integers(0, 96, n).tolist() for n in (61, 70)]
    got, seqs, pool = serve_by_hand(model, prompts, n_new=14, B=2, Mb=6,
                                    bs=16, C=12, max_tokens=max_tokens)
    assert pool.stats()["window_blocks_released"] >= 6
    for b in range(2):
        rows = [p for p, _z in got[b]]
        want = reference_logits(cfg, seqs[b], rows)
        have = np.stack([z for _p, z in got[b]])
        scale = np.abs(want).max()
        # bf16 operands inside the kernels; float32 on the lax path
        tol = (2e-2 if kernels else 2e-4) * scale
        assert np.abs(have - want).max() <= tol, (
            b, np.abs(have - want).max(), scale)


def test_the_engine_serves_what_the_reference_decodes(monkeypatch):
    """Through ServingEngine (scheduler, both kinds of page, release):
    greedy tokens equal the reference's, positions past several windows."""
    monkeypatch.setenv("PTPU_KERNELS", "0")
    cfg = toy_config()
    model = served_model(cfg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 96, n).tolist() for n in (50, 9, 33)]
    eng = ServingEngine(model, max_batch=2, max_seq_len=96, block_size=16,
                        prefill_chunk=8, num_blocks={"global": 12,
                                                     "window": 7})
    try:
        reqs = [eng.submit(p, max_new_tokens=20) for p in prompts]
        outs = [r.wait(300) for r in reqs]
        # the worker reaps a finished row on its next tick
        deadline = time.monotonic() + 30
        while True:
            stats = next(iter(eng.stats().values()))
            if not stats["blocks_in_use"] or time.monotonic() > deadline:
                break
            time.sleep(0.02)
    finally:
        eng.close()
    assert stats["window_blocks_released"] > 0
    assert stats["kinds"]["window"]["blocks_in_use"] == 0
    params = ref.make_params(SEED, cfg)
    for prompt, out, req in zip(prompts, outs, reqs):
        seq = list(prompt) + list(out)
        z = np.asarray(ref.logits_at(
            params, jnp.asarray(seq[:-1], jnp.int32),
            jnp.arange(len(prompt) - 1, len(seq) - 1), cfg))
        # each served token is the reference's choice, or within
        # rounding of it
        picked = z[np.arange(len(out)), out]
        assert (z.max(axis=1) - picked).max() <= 1e-3 * np.abs(z).max()
        # and the logit the step handed back beside it is the
        # reference's logit of that token
        assert len(req.top_logits) == len(out)
        np.testing.assert_allclose(req.top_logits, picked, rtol=0,
                                   atol=2e-4 * np.abs(z).max())


# -- the share of an eight-chip layer ----------------------------------------

def test_eight_shares_add_up_to_the_uncut_layer():
    blk = AfmoeBlock(n_kv_heads=2, head_dim=128, layer_types=TYPES,
                     sliding_window=16, n_routed_experts=16,
                     experts_per_token=4, n_shared_experts=1, moe_d_ff=32,
                     routed_scaling_factor=2.448, weight_dtype="float32",
                     activation_dtype="float32")
    rng = np.random.default_rng(0)
    T, D, Fe, E = 24, 64, 32, 16
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    router = jnp.asarray(rng.normal(size=(D, E)) * 0.3, jnp.float32)
    gate, up = (jnp.asarray(rng.normal(size=(E, D, Fe)) * 0.2, jnp.float32)
                for _ in range(2))
    down = jnp.asarray(rng.normal(size=(E, Fe, D)) * 0.2, jnp.float32)
    valid = jnp.ones(T, bool)
    idx, w = latent_moe.route(blk, x, router, jnp.zeros(E))
    whole, c_all = latent_moe.expert_layer(
        blk, x, valid, idx, w, gate, up, down, jnp.float32, False)
    total, pairs = 0.0, 0
    for share in range(8):
        ids = [2 * share, 2 * share + 1]
        at = slice(2 * share, 2 * share + 2)
        part, c = latent_moe.expert_layer(
            blk.replace(experts_held=ids), x, valid, idx, w, gate[at],
            up[at], down[at], jnp.float32, False)
        total, pairs = total + part, pairs + int(c[0])
    assert pairs == int(c_all[0]) == T * 4
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)
    # the reference's held share is the same partial sum
    cfg = toy_config(num_experts=2, router_experts=16,
                     num_experts_per_tok=4, experts_held_from=6)
    wr = {"router": router, "router_bias": jnp.zeros(E),
          "e_gate": gate[6:8], "e_up": up[6:8], "e_down": down[6:8]}
    part, _c = latent_moe.expert_layer(
        blk.replace(experts_held=[6, 7]), x, valid, idx, w, gate[6:8],
        up[6:8], down[6:8], jnp.float32, False)
    np.testing.assert_allclose(np.asarray(ref.experts(x, wr, cfg)),
                               np.asarray(part), rtol=1e-4, atol=1e-5)


# -- rotary -------------------------------------------------------------------

def test_rotary_is_half_split_and_equals_the_references():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(9, 3, 128)), jnp.float32)
    pos = jnp.arange(9)
    mine = afmoe.rope_half_split(x, pos[:, None], 10000.0)
    np.testing.assert_allclose(np.asarray(mine),
                               np.asarray(ref.rotary(x, 10000.0)),
                               rtol=1e-5, atol=1e-5)
    # lane j pairs with lane j + 64, not with its neighbour
    e = jnp.zeros((2, 1, 128)).at[:, 0, 3].set(1.0)
    out = np.asarray(afmoe.rope_half_split(e, jnp.arange(2)[:, None], 1e4))
    assert set(np.flatnonzero(np.abs(out[1, 0]) > 1e-6)) == {3, 67}
    inter = np.asarray(latent_moe.rope_interleaved(
        e, jnp.arange(2)[:, None], 1e4))
    assert set(np.flatnonzero(np.abs(inter[1, 0]) > 1e-6)) == {2, 3}


def test_global_layers_are_not_rotated():
    """A model of global layers alone does not read `rope_theta` at all;
    a model of window layers does. And the served block splits its
    layers over the two page kinds by their type."""
    base = toy_config(num_hidden_layers=2, num_dense_layers=1,
                      layer_types=["full_attention"] * 2)
    seq = list(range(20))
    a = reference_logits(base, seq, [19])
    b = reference_logits(dict(base, rope_theta=77.0), seq, [19])
    np.testing.assert_array_equal(a, b)
    slid = dict(base, layer_types=["sliding_attention"] * 2)
    c = reference_logits(slid, seq, [19])
    d = reference_logits(dict(slid, rope_theta=77.0), seq, [19])
    assert np.abs(c - d).max() > 1e-4
    # the same through the served steps: a global layer's logits are
    # those of the reference whatever theta the block is given
    mixed = dict(base, layer_types=["full_attention", "sliding_attention"])
    model = served_model(mixed)
    assert [k.name for k in model.page_kinds()] == ["global", "window"]
    assert model.page_kinds()[1].layers == (1,)
    only_global = served_model(dict(base, rope_theta=77.0))
    assert only_global.page_kinds() is None          # one kind of page
    got, seqs, _pool = serve_by_hand(only_global, [seq[:12]], n_new=4, B=1,
                                     Mb=2, bs=16, C=8)
    want = reference_logits(base, seqs[0], [p for p, _z in got[0]])
    have = np.stack([z for _p, z in got[0]])
    assert np.abs(have - want).max() <= 2e-4 * np.abs(want).max()


# -- what is refused ----------------------------------------------------------

def test_what_is_not_built_is_refused():
    model = served_model(toy_config())
    for make in (lambda: model.quantized(),
                 lambda: model.make_spec_step(2, 6, 3),
                 lambda: ServingEngine(model, max_batch=2, max_seq_len=96,
                                       block_size=16, prefix_cache=True),
                 lambda: ServingEngine(model, max_batch=2, max_seq_len=96,
                                       block_size=16, spec_k=2)):
        with pytest.raises(NotImplementedError):
            make()


def test_block_description_round_trips():
    blk = serve_window.generation_config(toy_config(), 96).block
    again = GenerationConfig.from_dict(GenerationConfig(
        96, 64, 4, 4, 96, block=blk).to_dict()).block
    assert isinstance(again, AfmoeBlock)
    assert again.to_dict() == blk.to_dict()
    assert blk.replace(sliding_window=99).sliding_window == 99
    assert blk.cache_entry().parts == (("k", (256,)), ("v", (256,)))


# -- the step log -------------------------------------------------------------

def test_step_log_carries_the_page_walks(monkeypatch):
    monkeypatch.setenv("PTPU_KERNELS", "0")
    metrics.reset()
    metrics.enable()
    try:
        model = served_model(toy_config())
        eng = ServingEngine(model, max_batch=2, max_seq_len=96,
                            block_size=16, prefill_chunk=8)
        try:
            eng.submit(list(range(40)), max_new_tokens=12).wait(300)
        finally:
            eng.close()
        recs = metrics.registry().samples("serving/step").records()
    finally:
        metrics.disable()
        metrics.reset()      # leave no record for a later test to read
    assert {r["kind"] for r in recs} == {"mixed", "decode"}
    for r in recs:
        for f in ("global_pages_walked", "window_pages_walked",
                  "window_pages_full", "global_keys_attended",
                  "window_keys_attended", "chunk_pages_walked",
                  "chunk_keys_attended", "cached_tokens", "weight_bytes",
                  "expert_pairs", "experts_touched", "decode_rows_walked",
                  "decode_runs_walked", "decode_rows_opened_warm"):
            assert f in r, (f, r)
        # the decode kernel's pipe over the one-token rows: at least a
        # run a row and layer, and one row a call opens cold
        assert r["decode_rows_walked"] <= r["decode_runs_walked"]
        assert r["decode_rows_walked"] in (0, 4 * (
            r["decode_tokens"] + (r["prefill_tokens"] == 1)))
        assert r["decode_rows_opened_warm"] == max(
            r["decode_rows_walked"] - 4, 0)
        assert r["window_pages_walked"] <= r["window_pages_full"]
        assert r["chunk_keys_attended"] <= (r["global_keys_attended"]
                                            + r["window_keys_attended"])
        assert (r["chunk_keys_attended"] > 0) == (r["prefill_tokens"] > 1)
    last = [r for r in recs if r["kind"] == "decode"][-1]
    # one row at position ~50, window 16, blocks of 16: three window
    # layers walk two pages each where a full walk takes four
    assert last["window_pages_full"] == 3 * 4
    assert last["window_pages_walked"] == 3 * 2
    assert last["global_pages_walked"] == 4
    # pages of 8 KiB and a table of 6: a run takes the whole walk
    assert last["decode_rows_walked"] == last["decode_runs_walked"] == 4
