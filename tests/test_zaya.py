"""The compressed-convolutional-attention / top-1 routed serving block
(serving/zaya.py) against its plain reference
(perfbench/reference/zaya.py), at toy widths on the CPU with seeded
weights whose every gain, bias, temperature and mixing vector is drawn
off its 1 or 0 (``init_gain_noise``): the served path through the pages
AND the row state (prompts fed as chunks of 1, 2, 3 and 7 tokens and
then decoded, so that every carry boundary is crossed), rows of
different phase in one compacted chunk, a slot whose carry is stale, the
router's state passing from layer to layer, the engine, its refusals
and the step log's fields.
"""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.observability import metrics
from paddle_tpu.serving import (GenerationConfig, GenerationModel,
                                KVBlockPool, RowState, ServingEngine, zaya)
from paddle_tpu.serving.zaya import ZayaBlock
from perfbench.reference import zaya as ref
from perfbench.runners import serve_zaya

SEED = 2147483659      # past 32 signed bits, as the driver's seeds are


def toy_config(**changes):
    """A configuration file's keys at toy widths (heads of 128 lanes, so
    that the kernels take them), float32 throughout so that the served
    path and the reference agree to rounding."""
    cfg = dict(
        family="zaya", vocab_size=96, hidden_size=64,
        num_attention_heads=4, num_key_value_heads=2, head_dim=128,
        num_hidden_layers=3, cca_time0=2, cca_time1=2,
        partial_rotary_factor=0.5,
        rope_parameters={"hybrid": {"rope_theta": 5000000}},
        router_hidden_size=16, num_experts=4, num_experts_per_tok=1,
        moe_intermediate_size=32, rms_norm_eps=1e-5, init_std=0.3,
        init_gain_noise=0.2, weight_dtype="float32",
        dtypes={"weights": "float32", "activations": "float32",
                "router": "float32", "cache": "float32"})
    cfg.update(changes)
    return cfg


def served_model(cfg, max_seq_len=96, **block):
    config = serve_zaya.generation_config(cfg, max_seq_len)
    if block:
        config.block = config.block.replace(**block)
    return GenerationModel(config,
                           serve_zaya.seeded_weights(ref, cfg, SEED))


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


class ByHand:
    """The model's own compiled steps over a pool and a row state, fed
    as the engine feeds them."""

    def __init__(self, model, B, Mb, bs, C, max_tokens=None):
        cfg = model.config
        self.model, self.B, self.bs, self.C = model, B, bs, C
        self.pool = KVBlockPool(
            cfg.n_layers, cfg.n_heads, cfg.head_dim, bs, B * Mb,
            entry=model.cache_entry(), kinds=model.page_kinds(),
            row_state=RowState(B, *model.row_state()))
        self.tables = np.zeros((B, Mb), np.int32)
        self.chunk = model.make_prefill_step(B, Mb, C, return_logits=True,
                                             max_tokens=max_tokens)
        self.decode = model.make_decode_step(B, Mb, return_logits=True)
        self.pos = np.zeros(B, np.int32)
        self.idle = jnp.zeros(B, jnp.int32)
        self.counters = []

    def admit(self, b, n_blocks):
        assert self.pool.reserve(b, n_blocks)

    def retire(self, b):
        """As the engine does: the pages go back, the carry stays as
        the row's last step left it."""
        self.pool.free_owner(b)
        self.tables[b] = 0
        self.pos[b] = 0

    def _pages(self, b, n):
        for p in range(self.pos[b], self.pos[b] + n):
            if p % self.bs == 0:
                self.tables[b, p // self.bs] = self.pool.alloc_block(b)

    def _take(self, out):
        arrays = self.pool.step_arrays
        self.pool.step_arrays = out[:len(arrays)]
        self.counters.append(np.asarray(out[len(arrays) + 1]))
        return np.asarray(out[len(arrays)]), np.asarray(out[-1])

    def feed(self, tokens):
        """One chunk step: ``tokens[b]`` the (possibly empty) list row
        ``b`` is fed. ``{b: logits at its last token}``."""
        n = np.array([len(t) for t in tokens], np.int32)
        feed = np.zeros((self.B, self.C), np.int32)
        for b, t in enumerate(tokens):
            feed[b, :n[b]] = t
            self._pages(b, n[b])
        on = n > 0
        _nxt, logits = self._take(self.chunk(
            self.model.weights, *self.pool.step_arrays, feed, on,
            self.idle, self.pos.copy(), n, self.tables.copy(), on))
        self.pos += n
        return {int(b): logits[b] for b in np.flatnonzero(on)}

    def step(self, tok, on=None):
        """One decode step of the rows ``on`` (all): logits ``[B, V]``."""
        on = np.ones(self.B, bool) if on is None else np.asarray(on)
        for b in np.flatnonzero(on):
            self._pages(b, 1)
        _nxt, logits = self._take(self.decode(
            self.model.weights, *self.pool.step_arrays,
            np.asarray(tok, np.int32), on, self.idle, self.pos.copy(),
            self.tables.copy(), on))
        self.pos += on
        return logits


def reference_margins(cfg, seq, rows):
    """The least router margin the reference met at each position."""
    params = ref.make_params(SEED, cfg)
    return np.asarray(ref.logits_and_margin_at(
        params, jnp.asarray(seq, jnp.int32), jnp.asarray(rows), cfg)[1])


def close_to(have, want, kernels, margins=None):
    """float32 on the lax path: what is left is the order of float32
    sums (a chunk's matmuls and the reference's whole-sequence ones tile
    differently). bf16 operands inside the kernels: there a token whose
    top-1 choice is a near-tie in the reference (``margins``) may take
    the other expert, a different function and no error, so it is left
    out, as ``correct`` leaves it out on the chip; few are."""
    scale = np.abs(want).max()
    tol = (2e-2 if kernels else 2e-4) * scale
    worst = np.abs(have - want).reshape(len(want), -1).max(axis=-1)
    if kernels and margins is not None:
        decided = margins > 2e-3
        assert decided.mean() >= 0.8, margins
        worst = worst[decided]
    assert worst.max() <= tol, (worst, scale)


# -- the served path against the reference's full forward -------------------

@pytest.mark.parametrize("kernels,chunk,max_tokens", [
    ("0", 1, None), ("0", 2, None), ("0", 3, None), ("0", 7, None),
    ("1", 3, None), ("0", 7, 9), ("1", 7, 9)], indirect=["kernels"])
def test_served_path_equals_the_reference_forward(kernels, chunk,
                                                  max_tokens):
    """Prompts fed ``chunk`` tokens a step (lengths no multiple of it,
    so a last chunk is shorter), then decoded: every token but a
    prompt's first takes its predecessor from the row before it or from
    the carry, and both must give the whole-sequence convolution."""
    cfg = toy_config()
    model = served_model(cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 96, n).tolist() for n in (23, 17)]
    hand = ByHand(model, B=2, Mb=3, bs=16, C=chunk, max_tokens=max_tokens)
    got = [[], []]
    for b in range(2):
        hand.admit(b, 3)
    while any(hand.pos[b] < len(prompts[b]) for b in range(2)):
        fed = [prompts[b][hand.pos[b]:hand.pos[b] + chunk]
               for b in range(2)]
        if max_tokens is not None:      # a budget: one row a step
            turn = next(b for b in range(2) if fed[b])
            fed = [t if b == turn else [] for b, t in enumerate(fed)]
        for b, z in hand.feed(fed).items():
            got[b].append((hand.pos[b] - 1, z))
    seqs = [list(p) for p in prompts]
    tok = [int(np.argmax(got[b][-1][1])) for b in range(2)]
    for _ in range(9):
        for b in range(2):
            seqs[b].append(tok[b])
        at = hand.pos.copy()
        logits = hand.step(tok)
        for b in range(2):
            got[b].append((at[b], logits[b]))
        tok = [int(np.argmax(logits[b])) for b in range(2)]
    assert hand.pool.check_invariants() == []
    for b in range(2):
        rows = [p for p, _z in got[b]]
        close_to(np.stack([z for _p, z in got[b]]),
                 reference_logits(cfg, seqs[b], rows), kernels,
                 reference_margins(cfg, seqs[b], rows))
    # decode rows read the carry; so does a chunk's first token past
    # position 0
    steps = np.stack(hand.counters)
    assert (steps[-9:, -1] == 2).all()
    if chunk > 1 and max_tokens is None:
        assert (steps[1:-9, -1] <= 2).all() and steps[0, -1] == 0


@pytest.mark.parametrize("kernels", ["0", "1"], indirect=True)
def test_rows_of_different_phase_share_a_compacted_chunk(kernels):
    """One mixed step holds a row's first chunk (position 0: no
    predecessor), another's later chunk (its first token reads the
    carry) and a decode row (one token, the carry); in the compacted
    token rows each one's predecessor is the row before ONLY where that
    is its own."""
    cfg = toy_config()
    model = served_model(cfg)
    rng = np.random.default_rng(11)
    a, b, c = (rng.integers(0, 96, n).tolist() for n in (5, 9, 6))
    hand = ByHand(model, B=3, Mb=2, bs=16, C=4, max_tokens=10)
    for row in range(3):
        hand.admit(row, 2)
    hand.feed([[], b[:4], c[:4]])
    hand.feed([[], b[4:8], c[4:6]])          # c's prompt ends
    z = hand.feed([[], b[8:9], []])
    tok_b = int(np.argmax(z[1]))
    # the mixed step: a starts, b decodes, c stays idle this step
    z = hand.feed([a[:4], [tok_b], []])
    want_a = reference_logits(cfg, a[:4], [3])[0]
    want_b = reference_logits(cfg, b + [tok_b], [9])[0]
    close_to(z[0][None], want_a[None], kernels)
    close_to(z[1][None], want_b[None], kernels)
    # c resumes by a decode step after two steps away: its carry is
    # what ITS last step left, untouched by the steps it sat out
    z_c = reference_logits(cfg, c, [5])[0]
    tok_c = int(np.argmax(z_c))
    logits = hand.step([0, 0, tok_c], on=[False, False, True])
    close_to(logits[2][None], reference_logits(cfg, c + [tok_c], [6]),
             kernels)


def test_a_readmitted_slot_ignores_its_stale_carry(monkeypatch):
    """A sequence leaves a slot, another enters it: the steps get no
    reset, and the newcomer's position 0 must not read what the old one
    left (nor must its later tokens, through the convolution)."""
    monkeypatch.setenv("PTPU_KERNELS", "0")
    cfg = toy_config()
    model = served_model(cfg)
    rng = np.random.default_rng(17)
    old, new = (rng.integers(0, 96, n).tolist() for n in (12, 10))
    hand = ByHand(model, B=1, Mb=2, bs=16, C=3)
    hand.admit(0, 2)
    for i in range(0, 12, 3):
        hand.feed([old[i:i + 3]])
    stale = np.asarray(hand.pool.row_state.array)
    assert np.abs(stale).max() > 0.1
    hand.retire(0)
    hand.admit(0, 2)
    got = []
    for i in range(0, 10, 3):
        got.append(hand.feed([new[i:i + 3]])[0])
    want = reference_logits(cfg, new, [2, 5, 8, 9])
    close_to(np.stack(got), want, False)
    # and a ONE-token first step too (a decode-shaped start)
    hand.retire(0)
    hand.admit(0, 2)
    z = hand.feed([new[:1]])[0]
    close_to(z[None], reference_logits(cfg, new[:1], [0]), False)


def test_ignoring_the_carry_is_seen(monkeypatch):
    """The benchmark's control: with every carry read as zero the
    chunk boundaries and every decode token lose their predecessor, and
    the logits leave the reference by far more than rounding."""
    monkeypatch.setenv("PTPU_KERNELS", "0")
    cfg = toy_config()
    model = served_model(cfg, ignore_carry=True)
    seq = np.random.default_rng(5).integers(0, 96, 9).tolist()
    hand = ByHand(model, B=1, Mb=1, bs=16, C=3)
    hand.admit(0, 1)
    got = [hand.feed([seq[i:i + 3]])[0] for i in range(0, 9, 3)]
    want = reference_logits(cfg, seq, [2, 5, 8])
    scale = np.abs(want).max()
    assert np.abs(got[0] - want[0]).max() <= 2e-4 * scale   # no boundary
    assert np.abs(got[1] - want[1]).max() > 1e-2 * scale
    assert np.abs(got[2] - want[2]).max() > 1e-2 * scale


# -- the router -------------------------------------------------------------

def test_router_state_passes_from_layer_to_layer():
    """Three layers: each layer's choice depends on the router states of
    the layers before it through ``router_mix``; the served ``route``
    and the reference's agree on state, weights and choice, and zeroing
    the mix of the LAST layer alone changes its probabilities."""
    cfg = toy_config()
    params = ref.make_params(SEED, cfg)
    model = served_model(cfg)
    blk = model.config.block
    x = np.random.default_rng(2).standard_normal((3, 11, 64)) \
        .astype(np.float32)
    r_ref = r_got = None
    probs = []
    for i, w in enumerate(params["layers"]):
        r_ref = ref.router_state(jnp.asarray(x[i]), w, r_ref)
        p = ref.router_probs(r_ref, w, cfg)
        weights, _margin = ref.router_choice(p, w)
        idx, wt, r_got = zaya.route(blk, jnp.asarray(x[i]), model.weights,
                                    "l%d/" % i, r_got)
        np.testing.assert_allclose(np.asarray(r_got), np.asarray(r_ref),
                                   rtol=1e-5, atol=1e-6)
        assert (np.asarray(idx)[:, 0]
                == np.argmax(np.asarray(weights), axis=1)).all()
        np.testing.assert_allclose(np.asarray(wt)[:, 0],
                                   np.asarray(weights).max(axis=1),
                                   rtol=1e-5)
        probs.append(np.asarray(p))
    last = dict(params["layers"][2], router_mix=jnp.zeros(16))
    alone = ref.router_probs(ref.router_state(jnp.asarray(x[2]), last, 1.0),
                             last, cfg)
    assert np.abs(np.asarray(alone) - probs[2]).max() > 1e-3
    # one expert a token, weighed by its own probability
    assert blk.experts_per_token == 1
    assert ((np.asarray(weights) > 0).sum(axis=1) == 1).all()


def test_a_narrower_router_is_rounded_to_its_type():
    cfg = toy_config()
    model = served_model(cfg, router_dtype="bfloat16")
    x = jnp.asarray(np.random.default_rng(4).standard_normal((9, 64)),
                    jnp.float32)
    _idx, w, r = zaya.route(model.config.block, x, model.weights, "l0/",
                            None)
    for a in (w, r):
        a = np.asarray(a)
        assert (a == np.asarray(jnp.asarray(a, jnp.bfloat16)
                                .astype(jnp.float32))).all()


# -- the description ----------------------------------------------------------

def test_block_description_round_trips_and_states_its_state():
    cfg = serve_zaya.generation_config(toy_config(), 96)
    again = GenerationConfig.from_dict(cfg.to_dict())
    assert isinstance(again.block, ZayaBlock)
    assert again.block.to_dict() == cfg.block.to_dict()
    blk = cfg.block
    assert blk.cache_entry().parts == (("k", (256,)), ("v", (256,)))
    kind, = blk.page_kinds(cfg)
    assert (kind.name, kind.window, kind.layers) == ("global", None,
                                                     (0, 1, 2))
    # z and c of four query and two key heads, and the shifted value
    assert blk.row_state(cfg) == ((3, 2 * 6 * 128 + 128), "float32")
    assert blk.step_counters[-1] == "carry_rows"
    with pytest.raises(ValueError, match="two cache heads"):
        blk.replace(n_kv_heads=4)
    with pytest.raises(NotImplementedError, match="one position"):
        blk.replace(conv_taps=[3, 2])
    shapes = zaya.leaf_shapes(cfg)
    assert "lm_head" not in shapes          # the head is the embedding
    assert shapes["l1/conv1_w"] == ((2, 6, 128, 128), "float32")
    rand = GenerationModel.random(cfg, seed=1)
    assert float(rand.weights["l0/k_temp"][0]) == 1.0
    assert float(jnp.abs(rand.weights["l0/ffn_out_bias"]).max()) == 0.0


# -- through the engine -------------------------------------------------------

def test_the_engine_serves_what_the_reference_decodes(monkeypatch):
    """Through ServingEngine (scheduler, pool, row state, slots reused
    by later requests): greedy tokens equal the reference's, and the
    step log carries the block's fields."""
    monkeypatch.setenv("PTPU_KERNELS", "0")
    cfg = toy_config()
    model = served_model(cfg)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, 96, n).tolist() for n in (30, 9, 21, 14)]
    metrics.reset()
    metrics.enable()
    try:
        eng = ServingEngine(model, max_batch=2, max_seq_len=96,
                            block_size=16, prefill_chunk=8, num_blocks=12)
        try:
            reqs = [eng.submit(p, max_new_tokens=12) for p in prompts]
            outs = [r.wait(300) for r in reqs]
            deadline = time.monotonic() + 30
            while next(iter(eng.stats().values()))["blocks_in_use"] \
                    and time.monotonic() < deadline:
                time.sleep(0.02)
        finally:
            eng.close()
        recs = metrics.registry().samples("serving/step").records()
    finally:
        metrics.disable()
        metrics.reset()      # leave no record for a later test to read
    params = ref.make_params(SEED, cfg)
    for prompt, out, req in zip(prompts, outs, reqs):
        seq = list(prompt) + list(out)
        z = np.asarray(ref.logits_at(
            params, jnp.asarray(seq[:-1], jnp.int32),
            jnp.arange(len(prompt) - 1, len(seq) - 1), cfg))
        assert (np.argmax(z, axis=-1) == np.asarray(out)).all()
        # each served token's own logit came back beside it
        np.testing.assert_allclose(np.asarray(req.top_logits),
                                   z.max(axis=-1), rtol=2e-4, atol=2e-4)
    assert recs
    for r in recs:
        for f in ("expert_pairs", "experts_touched", "expert_rows_max",
                  "expert_slots", "carry_rows", "global_pages_walked",
                  "global_keys_attended", "window_keys_attended",
                  "chunk_pages_walked", "chunk_keys_attended"):
            assert f in r, (f, r)
        assert r["window_keys_attended"] == 0
        assert "pages_walked" not in r
        # one expert a token and layer, none dropped
        assert r["expert_pairs"] == 3 * r["slots_used"]
        assert r["expert_slots"] == 3 * 4
        if r["kind"] == "decode":
            assert r["carry_rows"] == r["slots_used"]
        else:
            assert r["carry_rows"] <= r["rows"]


@pytest.mark.parametrize("more,why", [
    (dict(prefix_cache=True), "row state"), (dict(spec_k=2), "row state"),
    (dict(spec_tree="2x2"), "row state")])
def test_engine_refuses_what_the_row_state_cannot_follow(more, why):
    model = served_model(toy_config())
    with pytest.raises(NotImplementedError, match=why) as err:
        ServingEngine(model, max_batch=2, max_seq_len=96, block_size=16,
                      **more)
    assert "R7" in str(err.value)


def test_other_steps_and_stores_are_refused():
    model = served_model(toy_config())
    with pytest.raises(NotImplementedError, match="zaya"):
        model.make_spec_step(2, 3, 2)
    with pytest.raises(NotImplementedError, match="zaya"):
        model.quantized()
