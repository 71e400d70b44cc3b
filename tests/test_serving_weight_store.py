"""What the XGLM weight store holds and why (docs/SERVING.md): a leaf
that is only ever the weight operand of a default-precision dot is
stored in the dtype that dot rounds it to, decided in ONE function
(``serving.model.dot_operand_dtype``: bfloat16 on the TPU, float32 on
the CPU these tests run on). The tests patch that one function to
bfloat16 and show that the store changes where the rounding happens and
nothing else. CPU, toy widths.
"""

import jax
import numpy as np
import pytest

from paddle_tpu.core import device
from paddle_tpu.core.scope import Scope
from paddle_tpu.observability import metrics
from paddle_tpu.serving import (GenerationConfig, GenerationModel,
                                KVBlockPool, ServingEngine,
                                load_generation_artifact, reference_decode,
                                save_generation_artifact)
from paddle_tpu.serving import model as model_mod
from paddle_tpu.serving.model import random_weights

CFG = GenerationConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                       d_ff=64, max_seq_len=64)
OPERANDS = ("lm_head", "l0/wqkv", "l1/wproj", "l0/wff1", "l1/wff2")
OTHERS = ("embedding", "final_ln_scale", "final_ln_bias", "l0/ln1_scale",
          "l1/ln2_bias", "l0/bqkv", "l1/bproj", "l0/bff1", "l1/bff2")
B, BS, MB, C = 4, 4, 16, 8     # rows, block size, blocks a row, window


def rne_bf16(x):
    """float32 -> the nearest bfloat16 (ties to even), as float32: the
    bit arithmetic, independent of ml_dtypes and of XLA."""
    u = np.ascontiguousarray(x, np.float32).view(np.uint32)
    r = (u + np.uint32(0x7FFF) + ((u >> 16) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return r.view(np.float32)


def source_weights(seed=3):
    """Seeded float32 leaves with every bias and gain off its default,
    and exact ties in each dot operand (1 + 2**-8 lies halfway between
    two bfloat16 values and goes to the even one, 1.0; 1 + 3 * 2**-8
    goes up)."""
    rng = np.random.RandomState(seed)
    w = random_weights(CFG, seed)
    for k, v in w.items():
        if v.ndim == 1:
            w[k] = (v + rng.randn(*v.shape) * 0.05).astype(np.float32)
        else:
            v[0, :4] = [1 + 2.0 ** -8, 1 + 3 * 2.0 ** -8,
                        -(1 + 2.0 ** -8), 1 + 2.0 ** -9]
    return w


def pre_rounded(weights):
    operands = model_mod.dot_operand_names(CFG)
    return {k: rne_bf16(v) if k in operands else v
            for k, v in weights.items()}


def store_bf16(monkeypatch):
    """Patch the one function: models built from here on keep their
    dot operands in bfloat16."""
    monkeypatch.setattr(model_mod, "dot_operand_dtype",
                        lambda: "bfloat16")


@pytest.fixture
def bf16_store(monkeypatch):
    store_bf16(monkeypatch)


# -- (a) what the store holds -----------------------------------------------

@pytest.mark.parametrize("leaf", OPERANDS)
def test_dot_operand_is_its_source_rounded_to_nearest_even(bf16_store,
                                                           leaf):
    src = source_weights()
    held = GenerationModel(CFG, src).weights[leaf]
    assert str(held.dtype) == "bfloat16"
    np.testing.assert_array_equal(np.asarray(held, np.float32),
                                  rne_bf16(src[leaf]))
    assert rne_bf16(src[leaf])[0, 0] == 1.0      # the tie went to even


@pytest.mark.parametrize("leaf", OTHERS)
def test_every_other_leaf_is_its_source_bit_for_bit(bf16_store, leaf):
    src = source_weights()
    held = GenerationModel(CFG, src).weights[leaf]
    assert str(held.dtype) == "float32"
    assert np.asarray(held).tobytes() == src[leaf].tobytes()


def test_a_device_leaf_of_the_stored_dtype_is_taken_as_it_is(bf16_store):
    first = GenerationModel(CFG, source_weights())
    again = GenerationModel(CFG, first.weights)
    assert all(again.weights[k] is v for k, v in first.weights.items())


# -- (f) the one function ---------------------------------------------------

TPU = device.DeviceIdentity("tpu", "TPU v5 lite", 1)
CPU = device.DeviceIdentity("cpu", "cpu", 1)


@pytest.mark.parametrize("ident, precision, want", [
    (CPU, None, "float32"), (TPU, None, "bfloat16"),
    (TPU, "default", "bfloat16"), (TPU, "bfloat16", "bfloat16"),
    (TPU, "high", "float32"), (TPU, "highest", "float32"),
    (TPU, "float32", "float32"), (TPU, "BF16_BF16_F32_X3", "float32"),
    (CPU, "highest", "float32"),
], ids=lambda v: str(getattr(v, "platform", v)))
def test_dot_operand_dtype_follows_device_and_asked_precision(
        ident, precision, want):
    with device.compiling_for(ident), \
            jax.default_matmul_precision(precision):
        assert model_mod.dot_operand_dtype() == want
        held = {d for _s, d in model_mod.leaf_shapes(CFG).values()}
    assert held == {"float32", want}


def test_unpatched_on_the_cpu_the_store_is_float32_as_before():
    src = source_weights()
    m = GenerationModel(CFG, src)
    assert {str(v.dtype) for v in m.weights.values()} == {"float32"}
    for k, v in m.weights.items():
        assert np.asarray(v).tobytes() == src[k].tobytes(), k
    assert m.dot_operand_bytes == 4 * m.dot_operand_params


# -- (b) the same arithmetic ------------------------------------------------

def step_logits(model, kind):
    """(tokens, logits) of one compiled step over a seeded cache."""
    rng = np.random.RandomState(11)
    pool = KVBlockPool(CFG.n_layers, CFG.n_heads, CFG.head_dim, BS,
                       B * MB)
    kv = [np.asarray(rng.randn(*pool.k.shape), np.float32) * 0.3
          for _ in range(2)]
    width = 1 if kind == "decode" else C
    pos = rng.randint(1, MB * BS - width, size=B).astype(np.int32)
    tables = rng.permutation(np.arange(1, B * MB + 1)) \
        .reshape(B, MB).astype(np.int32)
    toks = rng.randint(0, CFG.vocab_size, (B, width)).astype(np.int32)
    on, zeros = np.ones(B, bool), np.zeros(B, np.int32)
    if kind == "decode":
        out = model.make_decode_step(B, MB, return_logits=True)(
            model.weights, kv[0], kv[1], toks[:, 0], on, zeros, pos,
            tables, on)
    else:
        make = (model.make_prefill_step if kind == "chunk"
                else model.make_spec_step)
        out = make(B, MB, C, return_logits=True)(
            model.weights, kv[0], kv[1], toks, on, zeros, pos,
            np.full(B, C, np.int32), tables, on)
    return np.asarray(out[2]), np.asarray(out[3])


@pytest.mark.parametrize("kind", ["decode", "chunk", "spec"])
def test_bf16_store_computes_what_a_pre_rounded_float32_store_does(
        monkeypatch, kind):
    """The store changes where the rounding happens and nothing else:
    a float32 store whose dot operands were rounded beforehand gives
    the same tokens and (to float32 tolerance) the same logits."""
    src = source_weights()
    wide = GenerationModel(CFG, pre_rounded(src))
    unrounded = GenerationModel(CFG, src)
    assert str(wide.weights["lm_head"].dtype) == "float32"
    store_bf16(monkeypatch)
    narrow = GenerationModel(CFG, src)
    assert str(narrow.weights["lm_head"].dtype) == "bfloat16"
    tok_n, logit_n = step_logits(narrow, kind)
    tok_w, logit_w = step_logits(wide, kind)
    np.testing.assert_array_equal(tok_n, tok_w)
    np.testing.assert_allclose(logit_n, logit_w, rtol=1e-5, atol=1e-5)
    # the comparison can tell: on the CPU, whose dot multiplies float32
    # as it is, the unrounded float32 store computes something else
    _tok, logit_src = step_logits(unrounded, kind)
    assert np.abs(logit_src - logit_n).max() > 1e-4


def test_served_tokens_are_the_reference_decode_of_the_stored_values(
        bf16_store):
    m = GenerationModel(CFG, source_weights())
    prompt = [3, 9, 27, 5, 1, 8]
    with ServingEngine(m, max_batch=4, max_seq_len=64, block_size=4,
                       prefill_chunk=4) as eng:
        got = eng.generate(prompt, max_new_tokens=10, timeout=300)
        store = eng.stats()["default"]["weight_store"]
    assert got == reference_decode(m, prompt, 10)
    assert store["by_dtype"]["bfloat16"] == m.dot_operand_bytes \
        == 2 * m.dot_operand_params
    assert store["bytes"] == sum(store["by_dtype"].values())


# -- (c) the int8 store, chosen by role -------------------------------------

def test_quantized_picks_the_same_leaves_from_either_store(monkeypatch):
    src = source_weights()
    from_wide = GenerationModel(CFG, pre_rounded(src)).quantized()
    store_bf16(monkeypatch)
    from_narrow = GenerationModel(CFG, src).quantized()
    assert from_narrow.weight_only_int8
    int8 = {k for k, v in from_narrow.weights.items()
            if str(v.dtype) == "int8"}
    assert int8 == {"embedding"} | set(
        model_mod.dot_operand_names(CFG))
    assert set(from_wide.weights) == set(from_narrow.weights)
    for k, v in from_narrow.weights.items():
        assert v.dtype == from_wide.weights[k].dtype, k
        np.testing.assert_array_equal(np.asarray(v),
                                      np.asarray(from_wide.weights[k]), k)


def test_quantized_from_a_bf16_store_decodes_as_its_dequantized_weights(
        bf16_store):
    q = GenerationModel(CFG, source_weights()).quantized()
    deq = q.dequantized_weights()
    assert {v.dtype for v in deq.values()} == {np.dtype(np.float32)}
    prompt = [7, 2, 40, 11]
    with ServingEngine(q, max_batch=2, max_seq_len=64,
                       block_size=4) as eng:
        got = eng.generate(prompt, max_new_tokens=8, timeout=300)
    assert got == reference_decode(q, prompt, 8)
    assert got == reference_decode(GenerationModel(CFG, deq), prompt, 8)


# -- (d) hot swap onto a bf16-store worker ----------------------------------

@pytest.mark.parametrize("kind", ["dict", "scope", "model", "artifact"])
def test_swap_installs_each_leaf_in_its_served_dtype(monkeypatch,
                                                     tmp_path, kind):
    new = source_weights(seed=9)
    wide = GenerationModel(CFG, new)         # a float32-store model
    assert str(wide.weights["lm_head"].dtype) == "float32"
    store_bf16(monkeypatch)
    served = GenerationModel(CFG, source_weights(seed=3))
    want = reference_decode(GenerationModel(CFG, new), [5, 6, 7], 8)
    if kind == "dict":
        source = new
    elif kind == "scope":
        source = Scope()
        for k, v in new.items():
            source.set(k, v)
    elif kind == "model":
        source = wide
    else:
        source = str(tmp_path / "art")
        save_generation_artifact(source, CFG, new)
    with ServingEngine(served, max_batch=2, max_seq_len=64, block_size=4,
                       prefill_chunk=4) as eng:
        before = eng.generate([5, 6, 7], max_new_tokens=8, timeout=300)
        worker = eng._workers["default"]
        dtypes = {n: worker.scope.get(n).dtype
                  for n in worker._weight_names}
        traces = served.trace_count
        eng.swap_weights(source)
        assert {n: worker.scope.get(n).dtype
                for n in worker._weight_names} == dtypes
        after = eng.generate([5, 6, 7], max_new_tokens=8, timeout=300)
        assert served.trace_count == traces      # no retrace, no compile
    assert after == want and before != want
    np.testing.assert_array_equal(
        np.asarray(worker.scope.get("l0/wqkv"), np.float32),
        rne_bf16(new["l0/wqkv"]))


# -- (e) artifacts ----------------------------------------------------------

def test_artifact_round_trip_from_a_bf16_store(bf16_store, tmp_path):
    m = GenerationModel(CFG, source_weights())
    d = str(tmp_path / "art")
    save_generation_artifact(d, CFG, m.weights)
    with np.load(d + "/__generation__.npz") as z:
        assert {z[k].dtype for k in z.files} == {np.dtype(np.float32)}
    back = load_generation_artifact(d)
    assert set(back.weights) == set(m.weights)
    for k, v in m.weights.items():
        assert back.weights[k].dtype == v.dtype, k
        np.testing.assert_array_equal(np.asarray(back.weights[k]),
                                      np.asarray(v), k)
    assert load_generation_artifact(d, quantize="weight_only") \
        .weight_only_int8


# -- (g) the step log says what a step streams ------------------------------

def latent_model():
    from paddle_tpu.serving.latent_moe import LatentMoEBlock

    return GenerationModel.random(GenerationConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq_len=64, block=LatentMoEBlock(
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            kv_lora_rank=128, n_routed_experts=8, experts_per_token=2,
            n_shared_experts=2, moe_d_ff=32)), seed=7)


def xglm_model():
    return GenerationModel(CFG, source_weights())


@pytest.mark.parametrize("make_model", [xglm_model, latent_model],
                         ids=["xglm", "latent"])
def test_step_records_carry_the_weight_stream(bf16_store, make_model):
    model = make_model()
    metrics.reset()
    metrics.enable()
    try:
        with ServingEngine(model, max_batch=4, max_seq_len=64,
                           block_size=4, prefill_chunk=4) as eng:
            for r in [eng.submit(list(range(1, n + 1)), max_new_tokens=6)
                      for n in (5, 11)]:
                r.wait(300)
        recs = metrics.registry().samples("serving/step").records()
    finally:
        metrics.disable()
        metrics.reset()
    assert {r["kind"] for r in recs} == {"decode", "mixed"}
    operands = [model.weights[n]
                for n in model_mod.dot_operand_names(model.config)]
    params = sum(int(v.size) for v in operands)
    held = sum(int(v.size) * v.dtype.itemsize for v in operands)
    for r in recs:
        assert (r["weight_params"], r["weight_bytes"]) == (params, held)
    wide = [v for v in operands if str(v.dtype) == "float32"]
    if model.config.block is None:
        assert not wide and held == 2 * params
    else:
        # bf16 matrices, and the float32 routers of the expert layers
        assert all(v.shape[-1] == 8 for v in wide)
        assert held == 2 * params + 2 * sum(int(v.size) for v in wide)
        assert "embedding" not in model_mod.dot_operand_names(
            model.config)
