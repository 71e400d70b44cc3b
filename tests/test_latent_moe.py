"""The latent-attention / routed-expert serving block (serving/latent_moe.py)
against its plain reference (perfbench/reference/kanana.py), at toy widths
on the CPU with seeded weights: the served path (chunked prefill, then
decode through the paged latent cache), the two forms of MLA, the router,
the expert layer and its share of a deployment, the three kernels against
their lax fallbacks in the interpreter, the pool's accounting with a latent
entry, and the step log's new fields.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.observability import metrics
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.serving import (GenerationConfig, GenerationModel,
                                KVBlockPool, ServingEngine, latent_moe)
from paddle_tpu.serving.kv_cache import CacheEntry
from paddle_tpu.serving.latent_moe import LatentMoEBlock
from perfbench import control_block
from perfbench.reference import kanana as ref
from perfbench.runners import serve_latent

SEED = 2147483659      # past 32 signed bits, as the driver's seeds are


def toy_config(**changes):
    """A configuration file's keys at toy widths, float32 throughout so
    that the served path and the reference agree to rounding."""
    cfg = dict(
        vocab_size=96, hidden_size=64, num_attention_heads=4,
        num_hidden_layers=3, intermediate_size=96, moe_intermediate_size=32,
        n_routed_experts=16, num_experts_per_tok=3, n_shared_experts=2,
        kv_lora_rank=128, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, rope_theta=1e6, rms_norm_eps=1e-6,
        first_k_dense_replace=1, routed_scaling_factor=2.448, init_std=0.3,
        weight_dtype="float32",
        dtypes={"weights": "float32", "activations": "float32",
                "router": "float32", "cache": "float32"})
    cfg.update(changes)
    return cfg


def served_model(cfg, max_seq_len=64):
    return GenerationModel(
        serve_latent.generation_config(cfg, max_seq_len),
        serve_latent.seeded_weights(ref, cfg, SEED))


@pytest.fixture
def kernels(request, monkeypatch):
    """PTPU_KERNELS off (the lax paths) or forced (the Pallas kernels in
    the interpreter)."""
    monkeypatch.setenv("PTPU_KERNELS", request.param)
    return request.param == "1"


def block(**kw):
    base = dict(qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                kv_lora_rank=128, n_routed_experts=8, experts_per_token=2,
                n_shared_experts=2, moe_d_ff=32, rope_theta=1e6,
                routed_scaling_factor=2.448, weight_dtype="float32",
                activation_dtype="float32", cache_dtype="float32")
    base.update(kw)
    return LatentMoEBlock(**base)


# -- the served path against the reference's full forward -------------------

def serve_by_hand(model, prompts, n_new, B, Mb, bs, C, max_tokens=None):
    """Chunked prefill, then one-token steps, through the model's own
    compiled steps and a paged latent pool: [(position, logits)] a row,
    the tokens fed, and the last step's counters. With ``max_tokens``
    one row prefills a step and the others sit it out, so a window
    holds at most ``C`` tokens and the step compacts them."""
    cfg = model.config
    pool = KVBlockPool(cfg.n_layers, cfg.n_heads, cfg.head_dim, bs, B * Mb,
                       entry=model.cache_entry())
    tables = np.arange(1, B * Mb + 1, dtype=np.int32).reshape(B, Mb)
    chunk = model.make_prefill_step(B, Mb, C, return_logits=True,
                                    max_tokens=max_tokens)
    decode = model.make_decode_step(B, Mb, return_logits=True)
    latent, = pool.arrays
    lens = [len(p) for p in prompts]
    pos = np.zeros(B, np.int32)
    idle = jnp.zeros(B, jnp.int32)
    seqs = [list(p) for p in prompts]
    got = [[] for _ in range(B)]
    while any(pos[b] < lens[b] for b in range(B)):
        feed = np.zeros((B, C), np.int32)
        n = np.array([min(C, lens[b] - pos[b]) for b in range(B)], np.int32)
        if max_tokens is not None:
            turn = int(np.flatnonzero(n)[0])
            n = np.where(np.arange(B) == turn, n, 0).astype(np.int32)
        for b in range(B):
            feed[b, :n[b]] = prompts[b][pos[b]:pos[b] + n[b]]
        on = n > 0
        latent, _nxt, _cnt, logits = chunk(
            model.weights, latent, feed, on, idle, pos.copy(), n, tables, on)
        pos += n
        for b in np.flatnonzero(on):
            got[b].append((pos[b] - 1, np.asarray(logits[b])))
    tok = np.array([int(np.argmax(got[b][-1][1])) for b in range(B)],
                   np.int32)
    on = np.ones(B, bool)
    for _ in range(n_new):
        for b in range(B):
            seqs[b].append(int(tok[b]))
        latent, nxt, counters, logits = decode(
            model.weights, latent, tok, on, idle, pos.copy(), tables, on)
        for b in range(B):
            got[b].append((pos[b], np.asarray(logits[b])))
        pos += 1
        tok = np.asarray(nxt)
    return got, seqs, np.asarray(counters)


@pytest.mark.parametrize("max_tokens", [None, 6])
@pytest.mark.parametrize("kernels", ["0", "1"], indirect=True)
def test_chunked_prefill_then_paged_decode_equals_the_reference(kernels,
                                                                max_tokens):
    cfg = toy_config()
    model = served_model(cfg)
    params = ref.make_params(SEED, cfg)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
               for n in (13, 7, 10)]
    got, seqs, counters = serve_by_hand(model, prompts, 5, B=3, Mb=8, bs=8,
                                        C=4, max_tokens=max_tokens)
    for b, rows in enumerate(got):
        want = np.asarray(ref.logits_at(
            params, jnp.asarray(seqs[b], jnp.int32),
            jnp.asarray([p for p, _ in rows], jnp.int32), cfg))
        mine = np.stack([z for _, z in rows])
        # logits of the order of 10; float32 both sides
        assert np.abs(mine - want).max() < 2e-4, b
    # three one-token rows, two expert layers, three experts a token
    pairs, touched, rows_max, slots = counters
    assert pairs == 3 * 2 * 3 and slots == 2 * 16
    assert 2 <= touched <= pairs and rows_max >= 2


def test_bfloat16_storage_stays_near_the_reference():
    """The configuration's own precision (bf16 weights, cache and matmul
    operands) against the float32 reference on the same bf16-rounded
    weights: near, not equal."""
    cfg = toy_config(weight_dtype="bfloat16", init_std=0.1, dtypes={
        "weights": "bfloat16", "activations": "bfloat16",
        "router": "float32", "cache": "bfloat16"})
    model = served_model(cfg)
    assert model.weights["l1/we_gate"].dtype == jnp.bfloat16
    assert model.weights["l1/router"].dtype == jnp.float32
    assert model.cache_entry().dtype == "bfloat16"
    params = ref.make_params(SEED, cfg)
    prompts = [np.arange(3, 14, dtype=np.int32)]
    got, seqs, _ = serve_by_hand(model, prompts, 3, B=1, Mb=4, bs=8, C=4)
    want = np.asarray(ref.logits_at(
        params, jnp.asarray(seqs[0], jnp.int32),
        jnp.asarray([p for p, _ in got[0]], jnp.int32), cfg))
    mine = np.stack([z for _, z in got[0]])
    assert np.abs(mine - want).max() < 0.05 * np.abs(want).max()


def test_the_engine_serves_the_block_on_the_normal_path():
    """submit -> scheduler -> pool -> compiled steps: the tokens equal a
    greedy decode by the reference."""
    cfg = toy_config()
    model = served_model(cfg)
    params = ref.make_params(SEED, cfg)
    # one length, so that the reference compiles once
    prompts = [list(range(3 + 7 * i, 14 + 7 * i)) for i in range(5)]
    # a prefill budget of one chunk: a mixed step holds at most 4 + 4 of
    # its 16 slots, so it compacts its tokens
    engine = ServingEngine(model, max_batch=4, max_seq_len=64, block_size=8,
                           prefill_chunk=4, prefill_token_budget=4)
    try:
        outs = [r.wait(300) for r in
                [engine.submit(p, max_new_tokens=6) for p in prompts]]
        assert type(engine._workers["default"].pool.entry) is CacheEntry
    finally:
        engine.close()
    rows = jnp.arange(len(prompts[0]) - 1, len(prompts[0]) + 5)
    for prompt, out in zip(prompts, outs):
        z = np.asarray(ref.logits_at(
            params, jnp.asarray(prompt + out[:-1], jnp.int32), rows, cfg))
        # greedy, float32 both sides: every served token is the
        # reference's first choice given the tokens before it, or tied
        # with it to rounding
        assert (z.max(axis=1) - z[np.arange(6), out] < 1e-3).all()


# -- the two forms of latent attention ---------------------------------------

def test_absorbed_mla_equals_expanded_mla():
    rng = np.random.default_rng(3)
    C, H, dn, dr, dv, r, T = 5, 4, 16, 8, 12, 32, 23
    q_nope, q_pe = rng.normal(size=(C, H, dn)), rng.normal(size=(C, H, dr))
    c_ctx, kpe_ctx = rng.normal(size=(T, r)), rng.normal(size=(T, dr))
    w_uk, w_uv = rng.normal(size=(H, dn, r)), rng.normal(size=(H, r, dv))
    mask = np.arange(T)[None, :] <= (T - C + np.arange(C))[:, None]
    args = [jnp.asarray(a, jnp.float32) for a in
            (q_nope, q_pe, c_ctx, kpe_ctx, w_uk, w_uv)]
    with jax.default_matmul_precision("highest"):
        a = latent_moe.mla_absorbed(*args, jnp.asarray(mask), 0.07)
        e = latent_moe.mla_expanded(*args, jnp.asarray(mask), 0.07)
    assert a.shape == (C, H, dv)
    np.testing.assert_allclose(np.asarray(a), np.asarray(e), rtol=2e-4,
                               atol=2e-4)


def test_rotary_pairs_are_the_adjacent_lanes():
    x = jnp.asarray(np.random.default_rng(0).normal(size=(3, 8)), jnp.float32)
    pos = jnp.asarray([0, 1, 5])
    y = np.asarray(latent_moe.rope_interleaved(x, pos, 100.0))
    np.testing.assert_allclose(y[0], np.asarray(x[0]), atol=1e-6)
    for i in range(4):
        a = 5 * 100.0 ** (-i / 4)
        want = (x[2, 2 * i] * np.cos(a) - x[2, 2 * i + 1] * np.sin(a),
                x[2, 2 * i + 1] * np.cos(a) + x[2, 2 * i] * np.sin(a))
        np.testing.assert_allclose(y[2, 2 * i:2 * i + 2], want, rtol=1e-5)
    # the reference rotates the same pairs
    np.testing.assert_allclose(
        np.asarray(ref.rotary(jnp.stack([x[0]] * 6), 100.0))[5],
        np.asarray(latent_moe.rope_interleaved(x[0], jnp.asarray(5), 100.0)),
        rtol=1e-5, atol=1e-6)


# -- the router ---------------------------------------------------------------

def test_router_sigmoid_bias_choice_renormalisation_and_scale():
    blk = block(n_routed_experts=6, experts_per_token=2,
                routed_scaling_factor=2.448)
    x = jnp.asarray(np.eye(4, dtype=np.float32))
    logits = np.array([[2.0, 1.0, 0.0, -1.0, -2.0, -3.0]] * 4, np.float32)
    bias = np.array([0.0, 0.0, 0.0, 0.0, 0.0, 5.0], np.float32)
    idx, w = latent_moe.route(blk, x, jnp.asarray(logits), jnp.asarray(bias))
    idx, w = np.asarray(idx), np.asarray(w)
    s = 1.0 / (1.0 + np.exp(-logits[0]))
    # the bias lifts expert 5 into the choice; the best raw score stays
    assert sorted(idx[0]) == [0, 5]
    # ... but weighs nothing: the weights are the RAW sigmoid scores,
    # renormalised over the chosen, times the factor
    order = list(idx[0])
    want = s[order] / s[order].sum() * 2.448
    np.testing.assert_allclose(w[0], want, rtol=1e-6)
    np.testing.assert_allclose(w.sum(axis=1), 2.448, rtol=1e-6)
    assert w.dtype == np.float32 and idx.dtype == np.int32


def test_the_reference_reports_how_near_a_tie_each_router_choice_is():
    """``router_margin``: the last expert chosen less the first left out,
    on ``score + bias``; ``logits_and_margin_at`` gives the least of a
    token's margins over the expert layers beside its logits."""
    cfg = toy_config()
    params = ref.make_params(SEED, cfg)
    tokens = np.random.RandomState(3).randint(0, cfg["vocab_size"], 12)
    rows = np.arange(12)
    with jax.default_matmul_precision("highest"):
        z, least = ref.logits_and_margin_at(params, tokens, rows, cfg)
        h = ref.f32(params["top"]["embed"][tokens])
        want = np.full(12, np.inf)
        for i, w in enumerate(params["layers"]):
            if ref.is_expert_layer(cfg, i):
                x = ref.rms_norm(ref.attention(h, w, cfg), w["ffn_norm"],
                                 cfg["rms_norm_eps"])
                s = np.sort(np.asarray(jax.nn.sigmoid(x @ w["router"])
                                       + w["router_bias"]), axis=1)
                k = cfg["num_experts_per_tok"]
                want = np.minimum(want, s[:, -k] - s[:, -k - 1])
            h, _ = ref.layer(h, w, cfg, i)
    np.testing.assert_allclose(np.asarray(least), want, rtol=1e-5, atol=1e-7)
    assert (np.asarray(least) > 0).all()
    np.testing.assert_array_equal(
        np.asarray(z), np.asarray(ref.logits_at(params, tokens, rows, cfg)))
    # the bias takes part: a bias that lifts the first expert left out
    # to the last one chosen closes the margin
    w = {k: v for k, v in params["layers"][1].items()}
    x = jax.random.normal(jax.random.PRNGKey(0), (4, cfg["hidden_size"]))
    order = np.argsort(np.asarray(jax.nn.sigmoid(x @ w["router"]))[0])
    k = cfg["num_experts_per_tok"]
    m0 = float(ref.router_margin(x, w, cfg)[0])
    w["router_bias"] = w["router_bias"].at[order[-k - 1]].add(m0)
    assert abs(float(ref.router_margin(x, w, cfg)[0])) < 1e-6 < m0


def test_router_scores_keep_float32_where_bfloat16_cannot():
    """Two experts whose scores differ below bfloat16's resolution: the
    float32 router tells them apart, the bf16 control does not."""
    blk = block(n_routed_experts=4, experts_per_token=1)
    x = jnp.ones((1, 64), jnp.float32)
    w = np.zeros((64, 4), np.float32)
    w[:, 1] = 0.01
    w[:, 2] = 0.01 * (1 + 2.0 ** -12)
    idx, _ = latent_moe.route(blk, x, jnp.asarray(w), jnp.zeros(4))
    assert int(idx[0, 0]) == 2
    idx16, _ = latent_moe.route(blk.replace(router_dtype="bfloat16"), x,
                                jnp.asarray(w), jnp.zeros(4))
    assert int(idx16[0, 0]) == 1      # a tie in bf16: the lower index
    # operands bf16 holds exactly, logits it cannot tell apart: the
    # narrower router rounds its logits too, and says so to the compiler
    # (the chip's would carry the float32 sum through unrounded)
    w = np.zeros((64, 4), np.float32)
    w[:, 1] = w[:, 2] = 2.0 ** -7
    w[0, 2] += 2.0 ** -14
    route = jax.jit(lambda b, x, w: latent_moe.route(b, x, w, jnp.zeros(4)),
                    static_argnums=0)
    assert int(route(blk, x, jnp.asarray(w))[0][0, 0]) == 2
    blk16 = blk.replace(router_dtype="bfloat16")
    assert int(route(blk16, x, jnp.asarray(w))[0][0, 0]) == 1
    w = jnp.asarray(w)
    assert "reduce_precision" in route.lower(blk16, x, w).as_text()
    assert "reduce_precision" not in route.lower(blk, x, w).as_text()


# -- the expert layer ---------------------------------------------------------

def expert_weights(rng, E, D, F, scale=0.3):
    return [jnp.asarray(rng.normal(size=s) * scale, jnp.float32)
            for s in ((E, D, F), (E, D, F), (E, F, D))]


def dense_experts(x, idx, w, gate, up, down):
    """Every pair computed on its own: the oracle."""
    x = np.asarray(x, np.float64)
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        for k in range(idx.shape[1]):
            e = int(idx[t, k])
            h = x[t] @ np.asarray(gate[e], np.float64)
            h = h / (1 + np.exp(-h)) * (x[t] @ np.asarray(up[e], np.float64))
            out[t] += float(w[t, k]) * (h @ np.asarray(down[e], np.float64))
    return out


@pytest.mark.parametrize("kernels", ["0", "1"], indirect=True)
def test_no_token_is_dropped_when_every_token_goes_to_one_expert(kernels):
    rng = np.random.default_rng(5)
    T, D, F, E = 40, 32, 16, 8
    blk = block(n_routed_experts=E, experts_per_token=2, moe_d_ff=F)
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    idx = np.tile(np.array([[3, 6]], np.int32), (T, 1))   # all to 3 and 6
    w = rng.uniform(0.5, 1.5, size=(T, 2)).astype(np.float32)
    gate, up, down = expert_weights(rng, E, D, F)
    y, counters = latent_moe.expert_layer(
        blk, x, jnp.ones(T, bool), jnp.asarray(idx), jnp.asarray(w), gate,
        up, down, jnp.float32, use_gmm=kernels)
    np.testing.assert_allclose(np.asarray(y),
                               dense_experts(x, idx, w, gate, up, down),
                               rtol=2e-4, atol=2e-4)
    pairs, touched, rows_max, slots = np.asarray(counters)
    assert (pairs, touched, rows_max, slots) == (2 * T, 2, T, E)


def test_rows_that_hold_no_token_route_nowhere():
    rng = np.random.default_rng(6)
    T, D, F, E, k = 24, 32, 16, 8, 2
    blk = block(n_routed_experts=E, experts_per_token=k, moe_d_ff=F)
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    idx = rng.integers(0, E, size=(T, k)).astype(np.int32)
    w = np.ones((T, k), np.float32)
    valid = np.zeros(T, bool)
    valid[[1, 4, 9, 20]] = True
    gate, up, down = expert_weights(rng, E, D, F)
    want = dense_experts(x, idx, w, gate, up, down) * valid[:, None]
    y, counters = latent_moe.expert_layer(
        blk, x, jnp.asarray(valid), jnp.asarray(idx), jnp.asarray(w),
        gate, up, down, jnp.float32, use_gmm=False)
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4, atol=2e-4)
    assert int(counters[0]) == 4 * k


def test_the_shares_of_a_deployment_add_up_to_the_whole_layer():
    """model-configs guide, section 4: the layer told to hold experts
    0-15, 16-31, ... 112-127 in turn; the eight partial results and the
    shared expert counted once equal the reference's whole layer."""
    cfg = toy_config(n_routed_experts=128, num_experts_per_tok=6,
                     hidden_size=32, moe_intermediate_size=16,
                     num_hidden_layers=2)
    words = ref.seed_words(SEED)
    leaves = ref.init_layer(words, cfg, 1)
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(20, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.experts(x, leaves, cfg) + ref.swiglu(
            x, leaves["s_gate"], leaves["s_up"], leaves["s_down"])
    gcfg = serve_latent.generation_config(cfg, 64)
    idx, w = latent_moe.route(gcfg.block, x, leaves["router"],
                              leaves["router_bias"])
    total = np.asarray(latent_moe._swiglu(
        x, leaves["s_gate"], leaves["s_up"], leaves["s_down"], jnp.float32))
    pairs = 0
    for share in range(8):
        held = range(16 * share, 16 * share + 16)
        blk = gcfg.block.replace(experts_held=list(held))
        part, counters = latent_moe.expert_layer(
            blk, x, jnp.ones(20, bool), idx, w,
            leaves["e_gate"][held.start:held.stop],
            leaves["e_up"][held.start:held.stop],
            leaves["e_down"][held.start:held.stop], jnp.float32,
            use_gmm=False)
        total = total + np.asarray(part)
        pairs += int(counters[0])
        assert int(counters[3]) == 16
    assert pairs == 20 * 6                      # every pair on one share
    np.testing.assert_allclose(total, np.asarray(whole), rtol=5e-4,
                               atol=5e-4)


# -- the kernels against their lax fallbacks, interpreted -------------------

@pytest.mark.parametrize("sizes,block_m", [
    ((5, 0, 20, 3), 8), ((0, 0, 1, 0), 16), ((16, 16, 16, 16), 16),
    ((33, 1, 0, 70), 16)])
def test_gmm_equals_ragged_dot(sizes, block_m):
    rng = np.random.default_rng(11)
    E, K, N = len(sizes), 32, 48
    sizes = np.asarray(sizes)
    tiles = -(-sizes // block_m)
    spare = 2
    lhs = np.zeros(((tiles.sum() + spare) * block_m, K), np.float32)
    row = 0
    for g, t in zip(sizes, tiles):
        lhs[row:row + g] = rng.normal(size=(g, K))
        row += t * block_m
    te = np.repeat(np.arange(E), tiles)
    te = np.concatenate([te, np.full(spare, te[-1])]).astype(np.int32)
    rhs = rng.normal(size=(E, K, N)).astype(np.float32)
    args = (jnp.asarray(lhs), jnp.asarray(rhs), jnp.asarray(te),
            int(tiles.sum()))
    got = np.asarray(pk.gmm(*args, block_m=block_m))
    want = np.asarray(pk.gmm_reference(*args, block_m=block_m))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert not got[tiles.sum() * block_m:].any()   # spare tiles: zero


def paged_case(rng, B, Mb, bs, W, C, lens, pos0, dtype=jnp.float32):
    pool = jnp.asarray(rng.normal(size=(2, B * Mb + 1, bs, W)), dtype)
    tables = (rng.permutation(B * Mb).reshape(B, Mb) + 1).astype(np.int32)
    return (pool, tables, jnp.asarray(pos0, jnp.int32),
            jnp.asarray(lens, jnp.int32))


@pytest.mark.parametrize("C,lens,pos0,pages", [
    (1, (1, 1, 1), (0, 17, 46), 2), (4, (4, 1, 3), (0, 17, 30), 2),
    (4, (4, 1, 3), (0, 17, 30), 32), (8, (8, 8, 1), (3, 40, 9), 4),
    # rows that are not live between live ones, before the first and
    # after the last
    (1, (0, 1, 0, 0, 1, 1, 0), (5, 17, 46, 0, 30, 9, 47), 2),
    (4, (0, 4, 0, 1, 0), (20, 3, 9, 41, 0), 2),
    # no row live: nothing copied, all zero
    (1, (0, 0, 0), (3, 17, 46), 2), (4, (0, 0, 0), (3, 17, 30), 2),
    # one live row, alone and among idle ones
    (1, (1,), (45,), 2), (1, (0, 0, 1, 0), (7, 7, 33, 7), 2),
    # rows of one run; of exactly one, two and three whole runs (pages of
    # 8 tokens, 2 a run: positions 15, 31, 47); odd and even numbers of
    # runs side by side, so that the handed half alternates both ways
    (1, (1, 1, 1, 1), (0, 7, 12, 15), 2),
    (1, (1, 1, 1, 1), (15, 31, 47, 31), 2),
    (1, (1, 1, 1, 1, 1, 1), (40, 20, 47, 3, 33, 10), 2),
    (1, (1, 1, 1, 1, 1), (47, 46, 45, 44, 43), 1),
    # a mixed window whose window rows and one-token rows alternate
    (4, (4, 1, 3, 1, 2, 1), (0, 17, 30, 44, 9, 2), 2),
    (8, (1, 8, 1, 5, 1, 8), (39, 3, 9, 20, 0, 40), 2),
    (8, (8, 1, 0, 1, 7, 0), (3, 40, 9, 15, 30, 1), 3)])
def test_latent_attention_kernel_equals_the_gathered_fallback(C, lens, pos0,
                                                              pages):
    rng = np.random.default_rng(12)
    B, Mb, bs, W, Vw, H = len(lens), 6, 8, 256, 128, 4
    pool, tables, pos, lens = paged_case(rng, B, Mb, bs, W, C, lens, pos0)
    q = jnp.asarray(rng.normal(size=(B, C, H, W)) * 0.1, jnp.float32)
    got = pk.latent_paged_attention(pool, q, tables, pos, lens, layer=1,
                                    v_width=Vw, pages_per_step=pages)
    want = pk.latent_paged_attention_reference(pool, q, tables, pos, lens,
                                               layer=1, v_width=Vw)
    for b in range(B):
        n = max(int(lens[b]), 1)
        np.testing.assert_allclose(np.asarray(got)[b, :n],
                                   np.asarray(want)[b, :n], rtol=2e-4,
                                   atol=2e-4)
        if int(lens[b]) <= 1:
            # a row of one token computes its first slot only; a row
            # that is not live nothing: zero in kernel and fallback
            assert not np.asarray(got)[b, int(lens[b]):].any()
            assert not np.asarray(want)[b, int(lens[b]):].any()


@pytest.mark.parametrize("C", [1, 4])
def test_a_row_that_is_not_live_moves_no_page(C):
    """The pages of the rows that are not live hold NaN. Had the kernel
    copied one (as it did while such a row walked ``pos // block_size +
    1`` pages), the buffer's lines past a later, shorter row's last page
    would hold NaN, and a masked NaN is still NaN in ``p @ v``."""
    rng = np.random.default_rng(3)
    lens = np.array([0, C, 0, 1, 0, 1], np.int32)
    B, Mb, bs, W, Vw, H = len(lens), 6, 8, 256, 128, 4
    pool, tables, pos, lens = paged_case(
        rng, B, Mb, bs, W, C, lens, (47, 20, 44, 3, 40, 9))
    idle = np.asarray(lens) == 0
    pool = pool.at[:, np.asarray(tables)[idle].ravel()].set(np.nan)
    q = jnp.asarray(rng.normal(size=(B, C, H, W)) * 0.1, jnp.float32)
    got = np.asarray(pk.latent_paged_attention(
        pool, q, tables, pos, lens, layer=1, v_width=Vw, pages_per_step=2))
    assert np.isfinite(got).all() and not got[idle].any()
    want = np.asarray(pk.latent_paged_attention_reference(
        pool.at[:].set(jnp.nan_to_num(pool)), q, tables, pos, lens, layer=1,
        v_width=Vw))
    np.testing.assert_allclose(got[~idle, 0], want[~idle, 0], rtol=2e-4,
                               atol=2e-4)


@pytest.mark.parametrize("C", [1, 4])
def test_a_rows_context_does_not_depend_on_the_rows_around_it(C):
    """Which row opened a row's pipe, and into which buffer half, is the
    batch's business: the row's result is bitwise the same with the
    batch permuted, with its neighbours switched off, and alone."""
    rng = np.random.default_rng(8)
    lens = np.array([C, 1, 1, max(C - 1, 1), 1, C], np.int32)
    B, Mb, bs, W, Vw, H = len(lens), 6, 8, 256, 128, 4
    pool, tables, pos, lens = paged_case(
        rng, B, Mb, bs, W, C, lens, (40, 3, 30, 12, 47 - C, 21))
    q = jnp.asarray(rng.normal(size=(B, C, H, W)) * 0.1, jnp.float32)

    def run(order, on):
        order = np.asarray(order)
        out = pk.latent_paged_attention(
            pool, q[order], tables[order], pos[order],
            jnp.where(jnp.asarray(on)[order], lens[order], 0), layer=1,
            v_width=Vw, pages_per_step=2)
        back = np.empty_like(order)
        back[order] = np.arange(B)
        return np.asarray(out)[back]

    every = np.ones(B, bool)
    base = run(np.arange(B), every)
    assert base.any(axis=(1, 2, 3)).all()
    for order in (np.arange(B)[::-1], rng.permutation(B), np.roll(
            np.arange(B), 1)):
        np.testing.assert_array_equal(run(order, every), base)
    for on in ([1, 0, 1, 0, 1, 0], [0, 1, 1, 0, 0, 1], [0, 0, 0, 1, 0, 0],
               [1, 1, 0, 0, 0, 0]):
        on = np.array(on, bool)
        got = run(np.arange(B), on)
        np.testing.assert_array_equal(got[on], base[on])
        assert not got[~on].any()


@pytest.mark.parametrize("page,table_len,run", [
    pytest.param((16, 640), 160, 128, id="kanana_pages_of_20KB"),
    pytest.param((64, 640), 640, 32, id="ling_pages_of_80KB"),
    pytest.param((16, 640), 40, 40, id="a_short_table"),
    pytest.param((4096, 640), 160, 1, id="a_page_past_a_run")])
def test_a_latent_run_is_sized_by_the_bytes_of_a_page(page, table_len, run):
    """The rule on the two latent cells' pages (bfloat16): 2.5 MiB a
    buffer half, at least a page, at most a block-table line."""
    pool = jax.ShapeDtypeStruct((1, 9) + page, jnp.bfloat16)
    assert pk.latent_pages_per_run(pool, table_len) == run


@pytest.mark.parametrize("pages", [1, 3, 4, 6, 32, 128, 160])
def test_a_runs_copies_are_waited_for_by_size(pages, monkeypatch):
    """`_run_wait` takes a run's page copies off the semaphore by the
    bits of their count: for every count up to a run the descriptors it
    waits on hold exactly that many pages, in at most `log2(pages) + 1`
    waits a pool. (The interpreter does not block on a semaphore, so a
    wait too many or too few shows only on the chip; this holds the
    arithmetic, `chip_smoke.py`'s `kernels` leg the rest.)"""
    from types import SimpleNamespace

    waited = []

    class Buffer:
        at = property(lambda self: self)

        def __getitem__(self, index):
            return index[1].size            # the descriptor's rows

    monkeypatch.setattr(pk.pl, "when",
                        lambda cond: (lambda f: f() if cond else None))
    monkeypatch.setattr(
        pk.pltpu, "make_async_copy",
        lambda src, dst, sem: SimpleNamespace(
            wait=lambda: waited.append((src, dst, sem))))
    bs = 16
    sems = SimpleNamespace(at={(0, 1): "k", (1, 1): "v"})
    for count in range(1, pages + 1):
        del waited[:]
        pk._run_wait(count, (Buffer(), Buffer()), sems, 1, pages=pages,
                     block_size=bs)
        for sem in "kv":
            rows = [dst for src, dst, s in waited if s == sem]
            assert sum(rows) == count * bs and len(rows) == len(set(rows))
            assert len(rows) <= pages.bit_length()
        assert all(src == dst for src, dst, _ in waited)


@pytest.mark.parametrize("pages", [1, 3, 4, 6])
def test_rows_of_every_count_of_pages_up_to_two_runs(pages):
    rng = np.random.default_rng(21)
    B, Mb, bs, W, Vw, H = 2 * pages, 12, 8, 256, 128, 4
    pos0 = [bs * (n + 1) - 1 - (n % 2) for n in range(B)]   # 1..2P pages
    pool, tables, pos, lens = paged_case(rng, B, Mb, bs, W, 1, [1] * B, pos0)
    q = jnp.asarray(rng.normal(size=(B, 1, H, W)) * 0.1, jnp.float32)
    got = pk.latent_paged_attention(pool, q, tables, pos, lens, layer=0,
                                    v_width=Vw, pages_per_step=pages)
    want = pk.latent_paged_attention_reference(pool, q, tables, pos, lens,
                                               layer=0, v_width=Vw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-4)


def test_the_step_log_and_the_latent_kernel_take_the_run_from_one_function(
        monkeypatch):
    """`engine._decode_pipe_walked` counts a step's runs by
    `pk.latent_pages_per_run`, the function the kernel's call asks: a
    rule that says 3 pages is what both then go by. The twin of the
    grouped-query blocks' test (tests/test_gqa_kernels.py), on a block
    that names no page kind."""
    from types import SimpleNamespace

    from paddle_tpu.serving import engine as serving_engine
    from paddle_tpu.serving.kv_cache import PageKind
    from paddle_tpu.serving.ling import LingBlock

    asked = []

    def rule(pool, table_len):
        asked.append((tuple(pool.shape), int(table_len)))
        return 3

    monkeypatch.setattr(pk, "latent_pages_per_run", rule)
    assert LingBlock.decode_pages_per_run is LatentMoEBlock.decode_pages_per_run
    bs, mb, W = 8, 8, 256
    pool = jax.ShapeDtypeStruct((3, 41, bs, W), jnp.float32)
    sched = SimpleNamespace(
        active=np.array([1, 1, 1, 0, 1, 1], bool),
        positions=np.array([0, 23, 24, 50, 63, 5], np.int64),
        chunk_lens=np.array([1, 1, 1, 1, 1, 4], np.int64),  # row 5 prefills
        max_blocks_per_seq=mb)
    rec = serving_engine._decode_pipe_walked(
        LatentMoEBlock, sched, SimpleNamespace(
            block_size=bs, arrays=(pool,),
            kinds=(PageKind("all", range(3)),)))
    assert asked == [((3, 41, bs, W), mb)]
    # rows 0, 1, 2, 4 hold one token: 1, 3, 4, 8 pages on three layers
    assert rec == {"decode_rows_walked": 4 * 3,
                   "decode_runs_walked": 3 * (1 + 1 + 2 + 3),
                   "decode_rows_opened_warm": 3 * 3}
    # a block whose kernel has no such pipe has no such fields
    assert serving_engine._decode_pipe_walked(object(), sched, None) == {}
    # ... and the kernel's own call asks the same function
    rng = np.random.default_rng(0)
    pk.latent_paged_attention(
        jnp.asarray(rng.normal(size=pool.shape), jnp.float32),
        jnp.ones((2, 1, 4, W)), np.ones((2, mb), np.int32),
        np.array([3, 20], np.int32), np.ones(2, np.int32), layer=0,
        v_width=128)
    assert asked[1:] == [((3, 41, bs, W), mb)]


@pytest.mark.parametrize("C,lens,pos0", [
    (1, (1, 0, 1), (0, 17, 47)), (4, (4, 0, 3), (6, 17, 30)),
    (8, (8, 5, 1), (3, 36, 9))])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_latent_write_kernel_equals_the_scatter(C, lens, pos0, dtype):
    rng = np.random.default_rng(13)
    B, Mb, bs, W = 3, 6, 8, 256
    pool, tables, pos, lens = paged_case(rng, B, Mb, bs, W, C, lens, pos0,
                                         dtype)
    rows = jnp.asarray(rng.normal(size=(B, C, W)), jnp.float32)
    got = np.asarray(pk.latent_write(pool, rows, tables, pos, lens, layer=1)
                     .astype(jnp.float32))
    want = np.asarray(pk.latent_write_reference(
        pool, rows, tables, pos, lens, layer=1).astype(jnp.float32))
    np.testing.assert_array_equal(got, want)
    before = np.asarray(pool.astype(jnp.float32))
    assert (got[0] == before[0]).all()            # the other layer: as it was
    assert (got[1] != before[1]).any(axis=-1).sum() == int(sum(lens))


# -- the pool with a latent entry --------------------------------------------

def test_pool_accounting_is_the_same_with_a_latent_entry():
    entry = block().cache_entry()
    assert entry.parts == (("latent", (256,)),)   # 128 + 8, in whole tiles
    pool = KVBlockPool(3, 4, 16, 8, 10, entry=entry)
    (latent,) = pool.arrays
    assert latent.shape == (3, 11, 8, 256) and latent.dtype == jnp.float32
    with pytest.raises(AttributeError, match="no part 'v'"):
        pool.v
    assert pool.reserve("a", 4) and pool.reserve("b", 6)
    assert not pool.reserve("c", 1)
    blocks = [pool.alloc_block("a") for _ in range(3)]
    assert pool.NULL_BLOCK not in blocks and pool.blocks_in_use == 3
    assert pool.truncate_owner("a", 1) == blocks[1:]
    assert pool.check_invariants() == []
    assert pool.free_owner("a") == 1 and pool.free_owner("b") == 0
    assert pool.blocks_free == 10 and pool.check_invariants() == []
    # the default entry is what it always was
    per_head = KVBlockPool(3, 4, 16, 8, 10)
    assert per_head.k.shape == per_head.v.shape == (3, 11, 8, 4, 16)
    assert per_head.entry.parts == (("k", (4, 16)), ("v", (4, 16)))
    assert KVBlockPool(1, 2, 4, 8, 2, dtype="bfloat16",
                       entry=entry).dtype == jnp.bfloat16


# -- what the block does not build, and what it refuses ---------------------

def test_speculation_and_artifacts_refuse_the_block_by_name(tmp_path):
    from paddle_tpu.serving.model import (ModelDrafter,
                                          load_generation_artifact,
                                          reference_decode,
                                          save_generation_artifact)

    cfg = GenerationConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                           d_ff=64, max_seq_len=64,
                           block=block(qk_nope_head_dim=8, v_head_dim=8))
    model = GenerationModel.random(cfg, seed=7)
    assert GenerationConfig.from_dict(cfg.to_dict()).block.to_dict() \
        == cfg.block.to_dict()
    for make in (lambda: ServingEngine(model, max_batch=2, spec_k=2),
                 lambda: ServingEngine(model, max_batch=2, spec_tree="2x2"),
                 lambda: model.make_spec_step(2, 4, 3),
                 lambda: model.make_draft_step(2, 4, 3),
                 lambda: ModelDrafter(model),
                 lambda: reference_decode(model, [1, 2], 2),
                 lambda: save_generation_artifact(str(tmp_path / "a"), cfg,
                                                  model.weights)):
        with pytest.raises((ValueError, NotImplementedError),
                           match="latent_moe"):
            make()
    assert not os.path.exists(tmp_path / "a")    # nothing written as fp32
    # the XGLM block's configuration dict is what it was: no block key
    plain = GenerationConfig(vocab_size=64, d_model=32, n_heads=2,
                             n_layers=2, d_ff=64)
    assert "block" not in plain.to_dict()
    save_generation_artifact(str(tmp_path / "x"), plain,
                             GenerationModel.random(plain).weights)
    assert load_generation_artifact(str(tmp_path / "x")).config.block is None


def test_the_controls_change_what_is_computed_not_what_is_stored():
    cfg = GenerationConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                           d_ff=64, max_seq_len=64,
                           block=block(qk_nope_head_dim=8, v_head_dim=8,
                                       weight_dtype="bfloat16"))
    model = GenerationModel.random(cfg, seed=7)
    # the block has no int8 store: the program says so, and the
    # benchmark's control file holds the grid (it consumes the model's
    # expert leaves: donated, in place)
    with pytest.raises(NotImplementedError, match="int8 weight store"):
        model.quantized()
    w = np.asarray(model.weights["l1/we_gate"].astype(jnp.float32))
    bf16 = control_block.with_block(model, router_dtype="bfloat16")
    on_grid = control_block.experts_on_int8_grid(model)
    q = np.asarray(on_grid.weights["l1/we_gate"].astype(jnp.float32))
    assert on_grid.weights["l1/we_gate"].dtype == jnp.bfloat16
    assert (w != q).any() and np.abs(w - q).max() < np.abs(w).max() / 100
    assert on_grid.weights["l1/wq"] is model.weights["l1/wq"]
    assert bf16.config.block.router_dtype == "bfloat16"
    assert model.config.block.router_dtype == "float32"
    assert bf16.weights["l1/router"] is model.weights["l1/router"]


# -- the step log -------------------------------------------------------------

def test_the_step_log_carries_the_blocks_counters():
    cfg = toy_config()
    model = served_model(cfg)
    metrics.enable()
    try:
        metrics.registry().metrics().pop("serving/step", None)
        engine = ServingEngine(model, max_batch=4, max_seq_len=64,
                               block_size=8, prefill_chunk=4)
        try:
            for r in [engine.submit(list(range(2, 13 + i)), max_new_tokens=6)
                      for i in range(4)]:
                r.wait(300)
        finally:
            engine.close()
        records = metrics.registry().samples("serving/step").records()
    finally:
        metrics.disable()
    assert {r["kind"] for r in records} == {"decode", "mixed"}
    for r in records:
        tokens = r["prefill_tokens"] + r["decode_tokens"]
        # two expert layers, three experts a token, sixteen held
        assert r["expert_pairs"] == tokens * 2 * 3
        assert r["expert_slots"] == 2 * 16
        assert 1 <= r["experts_touched"] <= min(r["expert_pairs"], 32)
        assert r["expert_rows_max"] >= -(-tokens * 3 // 16)
        assert r["cached_tokens"] >= tokens and "_counters" not in r
    # an XGLM step has no such fields
    plain = GenerationModel.random(GenerationConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq_len=64), seed=7)
    metrics.enable()
    try:
        engine = ServingEngine(plain, max_batch=2, max_seq_len=64,
                               block_size=4)
        try:
            engine.submit([1, 2, 3], max_new_tokens=3).wait(300)
        finally:
            engine.close()
        last = metrics.registry().samples("serving/step").records()[-1]
    finally:
        metrics.disable()
    assert last["model"] == "default" and "expert_pairs" not in last
