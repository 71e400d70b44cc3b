"""The parts of the delta-rule linear-attention / latent-attention /
routed-expert serving block (serving/ling.py; the served path is in
``test_ling.py``): the two scan kernels against ``jax.numpy`` forms and
float64 recurrences (a whole tile at the gate's bound among them), the
quarter-shares of an expert layer against the uncut reference, the
group-limited router, the block's description and what is refused.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.serving import (GenerationConfig, GenerationModel,
                                ServingEngine, latent_moe, ling)
from paddle_tpu.serving.ling import LingBlock
from perfbench.reference import ling as ref
from perfbench.runners import serve_ling
from test_ling import SEED, served_model, toy_config


# -- the two scan kernels against jax.numpy forms ---------------------------

def scan_operands(rng, T, H=8, d=128):
    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    q = unit(rng.standard_normal((T, H, d))) * d ** -0.5
    return [jnp.asarray(a, jnp.float32) for a in (
        q, unit(rng.standard_normal((T, H, d))),
        rng.standard_normal((T, H, d)))]


def recurrence(state, q, k, v, g, beta):
    """The delta rule a token at a time in float64: (state', o)."""
    S = np.asarray(state, np.float64)
    q, k, v, g, beta = (np.asarray(a, np.float64)
                        for a in (q, k, v, g, beta))
    out = []
    for t in range(q.shape[0]):
        S = np.exp(g[t])[..., None] * S
        u = beta[t][:, None] * (v[t] - np.einsum("hk,hkv->hv", k[t], S))
        S = S + k[t][..., None] * u[:, None, :]
        out.append(np.einsum("hk,hkv->hv", q[t], S))
    return S, np.stack(out)


@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16"])
def test_kda_decode_updates_the_active_rows_in_place(state_dtype):
    rng = np.random.default_rng(0)
    B, L, H, d = 4, 3, 8, 128
    state = jnp.asarray(rng.standard_normal((B, L, H, d, d)), state_dtype)
    q, k, v = scan_operands(rng, B)
    alpha = jnp.asarray(np.exp(-5 * rng.uniform(size=(B, H, d))),
                        jnp.float32)
    beta = jnp.asarray(rng.uniform(size=(B, H)), jnp.float32)
    tol = 1e-5 if state_dtype == "float32" else 2e-2
    for on in ([True, False, True, True], [False] * 4,
               [False, False, True, False]):
        on = jnp.asarray(on)
        new, o = pk.kda_decode(state, q, k, v, alpha, beta, on, layer=1)
        want, o_want = pk.kda_decode_reference(state, q, k, v, alpha, beta,
                                               on, layer=1)
        assert new.dtype == state.dtype
        f = np.float32
        np.testing.assert_allclose(np.asarray(new, f), np.asarray(want, f),
                                   atol=tol)
        np.testing.assert_allclose(np.asarray(o), np.asarray(o_want),
                                   atol=tol)
        # the other layers and the idle rows are what they were
        same = np.asarray(new, f) == np.asarray(state, f)
        assert same[:, [0, 2]].all() and same[~np.asarray(on)].all()
        assert not np.asarray(o)[~np.asarray(on)].any()
    if state_dtype == "float32":
        want, o_want = pk.kda_decode_reference(
            state, q, k, v, alpha, beta, jnp.ones(B, bool), layer=1)
        for b in range(B):
            S, o64 = recurrence(
                state[b, 1], q[b:b + 1], k[b:b + 1], v[b:b + 1],
                np.log(np.asarray(alpha[b:b + 1])), beta[b:b + 1])
            np.testing.assert_allclose(np.asarray(o_want)[b], o64[0],
                                       atol=1e-5)
            np.testing.assert_allclose(np.asarray(want)[b, 1], S, atol=1e-5)


def tiles_of(lens, loads, tile=pk.KDA_TILE, n_tiles=8):
    """The tile descriptors of batch rows holding ``lens`` consecutive
    tokens (a row of at most one token gets no tile)."""
    out = [[], [], [], [], []]
    first = 0
    for b, n in enumerate(lens):
        for j in range(-(-n // tile) if n > 1 else 0):
            for lst, x in zip(out, (
                    first + j * tile, min(tile, n - j * tile), b,
                    loads[b] if j == 0 else 0,
                    int((j + 1) * tile >= n))):
                lst.append(x)
        first += n
    return [jnp.asarray(lst + [0] * (n_tiles - len(lst)), jnp.int32)
            for lst in out]


@pytest.mark.parametrize("gate,H", [("drawn", 8), ("bound", 8), ("open", 8),
                                    ("drawn", 16)])
def test_kda_chunk_equals_the_recurrence(gate, H):
    """Rows of 70, 1, 130 and no tokens (two, none, three tiles), from
    their stored state or from zero; the decay drawn, at the gate's
    bound of -5 on every channel of every token (a tile's running sum
    reaches -320: ``exp(+320)`` is no float32, and nothing may form it)
    or absent (0: the state never forgets). Sixteen heads are two
    groups of eight lines."""
    rng = np.random.default_rng(1)
    lens, loads, L, d = [70, 1, 130, 0], [1, 1, 2, 1], 2, 128
    T = sum(lens)
    state = jnp.asarray(rng.standard_normal((4, L, H, d, d)), jnp.float32)
    q, k, v = scan_operands(rng, T, H=H)
    g = {"drawn": -5 * rng.uniform(size=(T, H, d)),
         "bound": np.full((T, H, d), -5.0),
         "open": np.zeros((T, H, d))}[gate]
    g = jnp.asarray(g, jnp.float32)
    beta = jnp.asarray(rng.uniform(size=(T, H)), jnp.float32)
    tiles = tiles_of(lens, loads)
    new, o = pk.kda_chunk(state, q, k, v, g, beta, *tiles, layer=1)
    want, o_want = pk.kda_chunk_reference(state, q, k, v, g, beta, *tiles,
                                          layer=1)
    assert np.isfinite(np.asarray(new)).all()
    first = 0
    for b, n in enumerate(lens):
        rows = slice(first, first + n)
        first += n
        if n <= 1:      # no tile: the state is what it was
            assert (np.asarray(new)[b] == np.asarray(state)[b]).all()
            continue
        assert np.isfinite(np.asarray(o)[rows]).all()
        S0 = state[b, 1] if loads[b] == 1 else jnp.zeros((H, d, d))
        S, o64 = recurrence(S0, q[rows], k[rows], v[rows], g[rows],
                            beta[rows])
        for have in (o, o_want):
            np.testing.assert_allclose(np.asarray(have)[rows], o64,
                                       atol=2e-5)
        for have in (new, want):
            np.testing.assert_allclose(np.asarray(have)[b, 1], S, atol=2e-5)
        assert (np.asarray(new)[b, 0] == np.asarray(state)[b, 0]).all()


def test_scan_kernels_are_chosen_by_head_shape(monkeypatch):
    from paddle_tpu.ops.kernel_registry import choose

    monkeypatch.setenv("PTPU_KERNELS", "1")
    assert choose("kda_decode", head_dim=128, n_heads=32)
    assert choose("kda_chunk", head_dim=128, n_heads=8)
    with pytest.warns(RuntimeWarning, match="multiple of 128"):
        assert not choose("kda_decode", head_dim=64, n_heads=8)
    with pytest.warns(RuntimeWarning, match="multiple of 8"):
        assert not choose("kda_chunk", head_dim=128, n_heads=4)


# -- the expert layer: shares of a deployment, the router's groups -----------

def test_the_quarter_shares_of_an_expert_layer_add_up():
    """Four chips share a layer's 16 experts, four each; every chip
    holds the shared expert. The four partial results, the shared
    expert counted once, are the uncut layer's result: in the reference,
    and in the program against it."""
    whole = toy_config(num_experts=16, router_experts=16)
    w = ref.init_layer(ref.seed_words(SEED), whole, 2)   # a KDA expert layer
    x = jnp.asarray(np.random.default_rng(2).standard_normal((11, 64)),
                    jnp.float32)
    shared = ref.swiglu(x, w["s_gate"], w["s_up"], w["s_down"])
    uncut = ref.ffn(x, w, whole, 2)[0]
    parts, served = [], []
    for share in range(4):
        cfg = toy_config(num_experts=4, experts_held_from=4 * share)
        ids = ref.held(cfg)
        mine = dict(w, **{n: w[n][np.asarray(ids)]
                          for n in ("e_gate", "e_up", "e_down")})
        parts.append(ref.ffn(x, mine, cfg, 2)[0] - shared)
        blk = serve_ling.generation_config(cfg, 64).block
        assert blk.experts_held == tuple(ids)
        idx, wt = latent_moe.route(blk, x, w["router"], w["router_bias"])
        y, counters = latent_moe.expert_layer(
            blk, x, jnp.ones(11, bool), idx, wt, mine["e_gate"],
            mine["e_up"], mine["e_down"], jnp.float32, False)
        served.append(y)
        np.testing.assert_allclose(np.asarray(y), np.asarray(parts[-1]),
                                   atol=2e-5)
    assert sum(int(np.asarray(c)) for c in [counters[3]]) == 4
    for shares in (parts, served):
        np.testing.assert_allclose(np.asarray(sum(shares) + shared),
                                   np.asarray(uncut), atol=5e-5)


def test_the_references_scan_stops_where_it_is_told():
    """``kda_scan``'s state after ``stop`` tokens of a sequence padded
    beyond them is the float64 recurrence's over those tokens alone,
    whatever the padding holds; with no ``stop`` it is the state after
    the last token."""
    rng = np.random.default_rng(11)
    q, k, v = scan_operands(rng, 24, H=2, d=16)
    g = jnp.asarray(-5.0 * rng.uniform(0.0, 1.0, (24, 2, 16)), jnp.float32)
    beta = jnp.asarray(rng.uniform(0.0, 1.0, (24, 2)), jnp.float32)
    zero = np.zeros((2, 16, 16))
    for stop in (None, 24, 17, 1):
        o, S = ref.kda_scan(q, k, v, g, beta, stop)
        n = 24 if stop is None else stop
        want, o_want = recurrence(zero, q[:n], k[:n], v[:n], g[:n],
                                  beta[:n])
        np.testing.assert_allclose(np.asarray(S), want, atol=1e-5)
        np.testing.assert_allclose(np.asarray(o)[:n], o_want, atol=1e-5)


def test_the_router_chooses_inside_the_best_groups():
    cfg = toy_config()
    blk = serve_ling.generation_config(cfg, 64).block
    w = ref.init_layer(ref.seed_words(SEED), cfg, 1)
    w["router_bias"] = jnp.asarray(
        np.random.default_rng(3).standard_normal(16) * 0.1, jnp.float32)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((64, 64)),
                    jnp.float32)
    idx, wt = latent_moe.route(blk, x, w["router"], w["router_bias"])
    want = np.asarray(ref.router_weights(x, w, cfg))
    got = np.zeros_like(want)
    np.put_along_axis(got, np.asarray(idx), np.asarray(wt), axis=1)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # by hand: a group's score is the sum of its two largest s + b
    s = np.asarray(ref.router_scores(x, w))
    choice = (s + np.asarray(w["router_bias"])).reshape(64, 4, 4)
    group = np.sort(choice, axis=-1)[..., -2:].sum(-1)
    kept = np.argsort(-group, axis=-1)[:, :2]
    for t in range(64):
        assert set(np.asarray(idx)[t] // 4) <= set(kept[t])
    # the limit changes the choice of some token, so it is measured
    free, _ = latent_moe.route(blk.replace(n_group=1, topk_group=1), x,
                               w["router"], w["router_bias"])
    assert (np.sort(np.asarray(free)) != np.sort(np.asarray(idx))).any()
    np.testing.assert_allclose(wt.sum(axis=1), 2.5, rtol=1e-5)


def test_one_group_routes_as_it_did_before():
    """``n_group`` 1 (every block but this one): the top k of score +
    bias over all experts, weights renormalised and scaled."""
    blk = latent_moe.LatentMoEBlock(
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        kv_lora_rank=128, n_routed_experts=16, experts_per_token=4,
        n_shared_experts=1, moe_d_ff=32, routed_scaling_factor=2.5)
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((33, 64)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((64, 16)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(16) * 0.1, jnp.float32)
    idx, wt = latent_moe.route(blk, x, router, bias)
    with jax.default_matmul_precision("highest"):
        s = np.asarray(jax.nn.sigmoid(x @ router))
    want = np.argsort(-(s + np.asarray(bias)), axis=1)[:, :4]
    assert (np.asarray(idx) == want).all()
    picked = np.take_along_axis(s, want, axis=1)
    np.testing.assert_allclose(
        np.asarray(wt), picked / picked.sum(1, keepdims=True) * 2.5,
        rtol=1e-5)


# -- the description ----------------------------------------------------------

def test_block_description_round_trips_and_states_its_state():
    cfg = serve_ling.generation_config(toy_config(), 96)
    again = GenerationConfig.from_dict(cfg.to_dict())
    assert isinstance(again.block, LingBlock)
    assert again.block.to_dict() == cfg.block.to_dict()
    blk = cfg.block
    assert blk.layer_types == ("kda", "mla", "kda")
    assert blk.cache_entry().parts == (("latent", (256,)),)
    kind, = blk.page_kinds(cfg)
    assert (kind.name, kind.window, kind.layers) == ("global", None, (1,))
    (scan, conv), = blk.row_state(cfg)
    assert scan == ("scan", (2, 8, 128, 128), "float32")
    assert conv == ("conv", (2 * 3 * 3 * 8 * 128,), "float32")
    assert blk.step_counters[-3:] == ("state_rows", "scan_tokens",
                                      "scan_fresh_rows")
    with pytest.raises(NotImplementedError, match="no page"):
        blk.replace(layer_types=["kda"] * 3)
    with pytest.raises(ValueError, match="n_group"):
        blk.replace(n_group=3)
    with pytest.raises(ValueError, match="layer_types names"):
        cfg.block = blk.replace(layer_types=["kda", "mla"])
        cfg.block.page_kinds(cfg)
    cfg.block = blk
    shapes = ling.leaf_shapes(cfg)
    assert shapes["l0/conv_w"] == ((4, 3 * 8 * 128), "float32")
    assert "l1/conv_w" not in shapes and "l1/w_uk" in shapes
    assert "l0/w_gate" in shapes and "l1/router" in shapes
    assert "l2/conv_w" in shapes and "l2/we_gate" in shapes
    rand = GenerationModel.random(cfg, seed=1)
    assert float(rand.weights["l0/o_norm"][0]) == 1.0
    assert float(jnp.abs(rand.weights["l0/alpha_bias"]).max()) == 0.0


@pytest.mark.parametrize("more,why", [
    (dict(prefix_cache=True), "row state"), (dict(spec_k=2), "row state"),
    (dict(spec_tree="2x2"), "row state")])
def test_engine_refuses_what_the_scan_state_cannot_follow(more, why):
    model = served_model(toy_config(), max_seq_len=96)
    with pytest.raises(NotImplementedError, match=why) as err:
        ServingEngine(model, max_batch=2, max_seq_len=96, block_size=16,
                      **more)
    assert "R7" in str(err.value)


def test_other_steps_and_stores_are_refused():
    model = served_model(toy_config(), max_seq_len=96)
    with pytest.raises(NotImplementedError, match="ling"):
        model.make_spec_step(2, 3, 2)
    with pytest.raises(NotImplementedError, match="ling"):
        model.quantized()
