"""chip_smoke.py: does the system start, compile and answer on the chip?

    python chip_smoke.py                    # the real run; needs a TPU
    python chip_smoke.py --rehearse-on-cpu  # toy-size rehearsal, CPU only

One process, the normal entry points, full width of the flagship (depth
as shipped, random weights from a seed). Legs, in order:

  device     what jax found (must be a TPU) + one small op check
  train      transformer_fluid.build through fluid.Executor(TPUPlace())
  serve      GenerationModel behind ServingEngine, PTPU_KERNELS unset
  kernels    every registered Pallas kernel, compiled, vs its fallback
  rec        host-table DeepFM through train_from_dataset (callbacks)
  multichip  the train program data-parallel, when 4 chips are visible

Each leg prints one JSON line (leg, ok, seconds, compile_seconds, ...),
then comes a summary line that ends with "claim": null. The last line of
stdout is the result the driver reads, with exactly these keys and the
device as jax reports it:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

A leg that fails raises: the traceback goes to stderr, the summary and
the result say ok=false and the exit code is non-zero. Without a TPU no
result is printed at all. The seconds printed are set-up times, not a
metric.

The rehearsal exists so the control flow can be debugged without chip
time (on-chip-measurement guide). It says that it is a rehearsal, names
the CPU in its device field, forces the Pallas kernels through the
interpreter, and is never the default.
"""

import argparse
import collections
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

# fails here, before anything is printed, in a directory that holds this
# script and nothing else of the repo
import paddle_tpu as fluid  # noqa: E402
from paddle_tpu.async_engine import persistent_cache_dir  # noqa: E402

Sizes = collections.namedtuple("Sizes", [
    "model",        # vocab_size, d_model, n_heads, n_layers, d_ff
    "seq", "batch", "ref_batch", "train_steps", "lr",
    "serve_ctx", "serve_batch", "block_size", "prefill_chunk",
    "prompt_lens", "new_tokens", "spec_k", "spec_tree",
    "int8_mkn", "latent", "latent_batch", "latent_chunk", "afmoe",
    "afmoe_batch", "afmoe_chunk", "zaya", "zaya_batch", "zaya_chunk",
    "scan", "rec"])

# the flagship at full width (bench_transformer_fluid's operating point)
FULL = Sizes(
    model=dict(vocab_size=32000, d_model=512, n_heads=8, n_layers=6,
               d_ff=2048),
    seq=512, batch=160, ref_batch=2, train_steps=8, lr=1.0,
    serve_ctx=1024, serve_batch=8, block_size=16, prefill_chunk=64,
    prompt_lens=(32, 48, 64, 96, 128, 160, 200, 256), new_tokens=32,
    spec_k=4, spec_tree=(2, 3),
    int8_mkn=(160, 512, 2048),
    # the latent-attention / routed-expert block at its published
    # widths (kakaocorp/kanana-2-30b-a3b), one dense and one expert layer
    latent=dict(vocab_size=128256, d_model=2048, n_heads=32, n_layers=2,
                d_ff=6144, block=dict(
                    qk_nope_head_dim=128, qk_rope_head_dim=64,
                    v_head_dim=128, kv_lora_rank=512, rope_theta=1e6,
                    n_routed_experts=128, experts_per_token=6,
                    n_shared_experts=2, moe_d_ff=768,
                    routed_scaling_factor=2.448)),
    latent_batch=32, latent_chunk=16,
    # the grouped-query window/global block at its published widths
    # (arcee-ai/Trinity-Large-Preview): a dense window layer, then a
    # global and a window expert layer holding 8 of 256 experts
    afmoe=dict(vocab_size=25024, d_model=3072, n_heads=48, n_layers=3,
               d_ff=12288, block=dict(
                   kind="afmoe", n_kv_heads=8, head_dim=128,
                   layer_types=["sliding_attention", "full_attention",
                                "sliding_attention"],
                   sliding_window=128, n_dense_layers=1,
                   n_routed_experts=256, experts_per_token=4,
                   n_shared_experts=1, moe_d_ff=3072,
                   routed_scaling_factor=2.448,
                   experts_held=list(range(8)))),
    afmoe_batch=8, afmoe_chunk=256,
    # the compressed-convolutional-attention / top-1 routed block at its
    # published widths (Zyphra/ZAYA1-8B): two layers, all 16 experts of
    # each, the whole tied vocabulary
    zaya=dict(vocab_size=262272, d_model=2048, n_heads=8, n_layers=2,
              d_ff=2048, block=dict(
                  kind="zaya", n_kv_heads=2, head_dim=128,
                  router_hidden=256, n_routed_experts=16, moe_d_ff=2048,
                  rope_theta=5e6)),
    zaya_batch=8, zaya_chunk=256,
    # the delta-rule scan's two kernels at the published head shape
    # (inclusionAI/Ling-3.0-flash: 32 heads of 128 x 128 float32 a row
    # a layer): batch rows, layers (the second used), the prefill
    # tokens of the chunk kernel's rows
    scan=dict(n_heads=32, head_dim=128, layers=2, batch=8,
              chunk_rows=(150, 64, 2, 1000)),
    # bench.py --rec-only sizes
    rec=dict(n_shards=4, records_per_shard=320, batch_size=32, vocab=512,
             fields=6, embed_dim=16, cache_rows=128))

# toy sizes for the CPU rehearsal: same code, same leg structure
TOY = Sizes(
    model=dict(vocab_size=512, d_model=128, n_heads=2, n_layers=2,
               d_ff=256),
    seq=128, batch=8, ref_batch=2, train_steps=8, lr=1.0,
    serve_ctx=128, serve_batch=4, block_size=16, prefill_chunk=16,
    prompt_lens=(8, 12, 20, 28, 33, 40), new_tokens=8,
    spec_k=3, spec_tree=(2, 2),
    int8_mkn=(32, 128, 256),
    latent=dict(vocab_size=512, d_model=128, n_heads=4, n_layers=2,
                d_ff=256, block=dict(
                    qk_nope_head_dim=32, qk_rope_head_dim=16,
                    v_head_dim=32, kv_lora_rank=128, rope_theta=1e6,
                    n_routed_experts=8, experts_per_token=2,
                    n_shared_experts=2, moe_d_ff=64,
                    routed_scaling_factor=2.448)),
    latent_batch=4, latent_chunk=8,
    afmoe=dict(vocab_size=512, d_model=64, n_heads=4, n_layers=3,
               d_ff=128, block=dict(
                   kind="afmoe", n_kv_heads=2, head_dim=128,
                   layer_types=["sliding_attention", "full_attention",
                                "sliding_attention"],
                   sliding_window=16, n_dense_layers=1,
                   n_routed_experts=16, experts_per_token=2,
                   n_shared_experts=1, moe_d_ff=64,
                   routed_scaling_factor=2.448,
                   experts_held=list(range(4)))),
    afmoe_batch=4, afmoe_chunk=32,
    zaya=dict(vocab_size=512, d_model=64, n_heads=4, n_layers=2,
              d_ff=64, block=dict(
                  kind="zaya", n_kv_heads=2, head_dim=128,
                  router_hidden=16, n_routed_experts=4, moe_d_ff=64,
                  rope_theta=5e6)),
    zaya_batch=4, zaya_chunk=32,
    scan=dict(n_heads=8, head_dim=128, layers=2, batch=4,
              chunk_rows=(70, 3)),
    rec=dict(n_shards=2, records_per_shard=64, batch_size=16, vocab=128,
             fields=4, embed_dim=8, cache_rows=32))

# tolerances, stated once. bf16 keeps 8 significant bits: half an ulp is
# 2**-9 of the value. On the chip fp32 matmuls run as bf16 passes at
# jax's default precision, inside the kernels and outside them alike.
BF16_HALF_ULP = 2.0 ** -9
KERNEL_REL_BOUND = 8 * BF16_HALF_ULP      # of the fallback's largest value
INT8_REL_BOUND = 1e-6                      # int32 accumulation is exact
LOSS_TOL = 5e-3                            # TPU vs CPU / 1 vs 4 chips, abs
LOGITS_REL_BOUND = 16 * BF16_HALF_ULP      # six layers deep, of max |logit|


# ---------------------------------------------------------------------------
# bookkeeping: per-leg wall time, compile time and cache events from jax
# ---------------------------------------------------------------------------

class Clock:
    """Sums jax's own lowering + backend-compile durations and counts its
    persistent-cache events, so each leg can say how much of its time was
    compilation and whether the cache answered."""

    COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                      "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.compile_s = 0.0
        self.hits = 0
        self.writes = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_kw):
        if event in self.COMPILE_EVENTS:
            self.compile_s += duration

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.writes += 1

    def snapshot(self):
        return time.perf_counter(), self.compile_s, self.hits, self.writes


def run_leg(name, fn, clock, results):
    """Run one leg and print its line. A failure is printed and recorded,
    then re-raised: nothing downstream runs on a broken system."""
    t0, c0, h0, w0 = clock.snapshot()
    line = {"leg": name, "ok": False}
    try:
        detail = fn() or {}
        line["ok"] = True
        line.update(detail)
    finally:
        t1, c1, h1, w1 = clock.snapshot()
        line.update(seconds=round(t1 - t0, 2),
                    compile_seconds=round(c1 - c0, 2),
                    cache_hits=h1 - h0, cache_writes=w1 - w0)
        results[name] = line
        print(json.dumps(line), flush=True)


def place(rehearsal):
    return fluid.CPUPlace() if rehearsal else fluid.TPUPlace()


def counter(name):
    from paddle_tpu.observability import metrics

    return metrics.registry().counter(name).value


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def leg_device(rehearsal):
    import importlib.metadata

    import jax
    import jaxlib

    from paddle_tpu.core import device

    ident = device.identity()  # main() already refused a non-TPU
    # the old tests/test_tpu_backend_parity.py check, with the backend
    # asserted: fc / softmax / reduce on the device against numpy
    rng = np.random.RandomState(7)
    x = rng.rand(4, 16).astype(np.float32)
    w = rng.rand(16, 8).astype(np.float32)
    prog, sprog = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, sprog):
        xin = fluid.layers.data(name="x", shape=[16], dtype="float32")
        h = fluid.layers.fc(
            input=xin, size=8, bias_attr=False,
            param_attr=fluid.ParamAttr(
                name="smoke_w",
                initializer=fluid.initializer.NumpyArrayInitializer(w)))
        sm = fluid.layers.softmax(h)
        red = fluid.layers.reduce_sum(fluid.layers.tanh(h), dim=[1])
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(place(rehearsal))
        exe.run(sprog)
        o1, o2 = exe.run(prog, feed={"x": x}, fetch_list=[sm, red],
                         return_numpy=False)
        assert {d.platform for d in o1.devices()} == {ident.platform}, \
            o1.devices()
        exe.close()
    hw = x @ w
    e = np.exp(hw - hw.max(axis=1, keepdims=True))
    # fp32 matmul on the chip runs as bf16 passes: ~1e-3 from numpy
    np.testing.assert_allclose(np.asarray(o1), e / e.sum(1, keepdims=True),
                               rtol=5e-3, atol=5e-4)
    np.testing.assert_allclose(np.asarray(o2).ravel(), np.tanh(hw).sum(1),
                               rtol=5e-3, atol=5e-3)
    return {"platform": ident.platform, "device_kind": ident.kind,
            "device_count": ident.count,
            "jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": importlib.metadata.version("libtpu"),
            "cache_dir": persistent_cache_dir()}


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def build_trainer(sz):
    """The flagship program at the bench_transformer_fluid headline
    precision: bf16-stored parameters and residual stream, the contrib
    mixed-precision decorator, SGD. The learning rate is not the
    headline's 0.01: a bf16 parameter only moves when its update exceeds
    half an ulp of its value, and at 0.01 none does within eight steps,
    so the loss would sit still and say nothing about the update path."""
    from paddle_tpu.models import transformer_fluid

    prog, sprog = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, sprog):
        _t, _l, loss = transformer_fluid.build(
            seq_len=sz.seq, remat=False, dtype="bfloat16", **sz.model)
        opt = fluid.contrib.mixed_precision.decorate(
            fluid.optimizer.SGD(sz.lr), init_loss_scaling=1.0,
            use_dynamic_loss_scaling=False)
        opt.minimize(loss)
    return prog, sprog, loss


def train_batch(sz, batch):
    rng = np.random.RandomState(0)
    toks = rng.randint(0, sz.model["vocab_size"],
                       (batch, sz.seq)).astype(np.int32)
    return {"tokens": toks, "labels": np.roll(toks, -1, axis=1)}


def run_steps(exe, prog, sprog, loss, feed, steps):
    exe.run(sprog)
    return [float(np.asarray(exe.run(prog, feed=feed,
                                     fetch_list=[loss])[0]).ravel()[0])
            for _ in range(steps)]


def leg_train(sz, rehearsal, shared):
    from paddle_tpu.core import device

    # one program for every run below and for the multichip leg: the
    # initializer seeds are drawn when it is built, so re-running its
    # startup program gives every executor the same parameters
    prog, sprog, loss = shared["trainer"] = build_trainer(sz)
    flash0 = counter("kernels/kernel:flash_attention")
    platform = device.identity().platform

    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(place(rehearsal))
        losses = run_steps(exe, prog, sprog, loss,
                           train_batch(sz, sz.batch), sz.train_steps)
        params = [p.name for p in prog.all_parameters()]
        where = {d.platform for n in params
                 for d in fluid.global_scope().get(n).devices()}
        exe.close()
    assert all(np.isfinite(losses)), losses
    # falling: lower at every step, by more than rounding in total (at
    # full width the fixed batch moves the loss by ~0.006 a step)
    assert all(b < a for a, b in zip(losses, losses[1:])) \
        and losses[0] - losses[-1] >= 0.02, ("loss is not falling", losses)
    assert where == {platform}, ("parameters live on", where)
    flash = counter("kernels/kernel:flash_attention") - flash0
    assert flash >= 1, "the tuned flash path did not run"
    shared["train_first_loss"] = losses[0]

    # small batch, two steps, against Executor(CPUPlace()) on the lax
    # path (PTPU_KERNELS=0: a reference that shares no kernel code)
    feed = train_batch(sz, sz.ref_batch)
    with fluid.scope_guard(fluid.Scope()):
        exe = fluid.Executor(place(rehearsal))
        got = run_steps(exe, prog, sprog, loss, feed, 2)
        exe.close()
    os.environ["PTPU_KERNELS"] = "0"
    try:
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            want = run_steps(exe, prog, sprog, loss, feed, 2)
            exe.close()
    finally:
        del os.environ["PTPU_KERNELS"]
    diff = max(abs(a - b) for a, b in zip(got, want))
    assert diff <= LOSS_TOL, (got, want)
    return {"steps": sz.train_steps, "batch": sz.batch, "seq": sz.seq,
            "losses": [round(v, 4) for v in losses],
            "flash_attention_dispatches": flash,
            "params_on": sorted(where),
            "ref_losses_device": [round(v, 4) for v in got],
            "ref_losses_cpu_lax": [round(v, 4) for v in want],
            "ref_max_abs_diff": round(diff, 5), "ref_tolerance": LOSS_TOL}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def serving_model(sz):
    from paddle_tpu.serving import GenerationConfig, GenerationModel

    # heads of 128 lanes, as the served configurations have them: the
    # chunk step's kernel copies pages as the pool stores them and
    # takes no narrower head (the `kernels` leg runs the other paged
    # kernels at the trainer's heads of 64 too)
    cfg = GenerationConfig(max_seq_len=sz.serve_ctx, **dict(
        sz.model, n_heads=max(1, sz.model["d_model"] // 128)))
    return GenerationModel.random(cfg, seed=7)


def prompts(sz):
    """Periodic prompts: the default n-gram drafter finds its suffix in
    the history, so every speculative engine really drafts."""
    rng = np.random.RandomState(3)
    out = []
    for n in sz.prompt_lens:
        period = rng.randint(0, sz.model["vocab_size"], 5 + len(out))
        out.append([int(t) for t in np.resize(period, n)])
    return out


def serve_all(model, sz, **engine_kw):
    from paddle_tpu.serving import ServingEngine

    eng = ServingEngine(model, max_batch=sz.serve_batch,
                        max_seq_len=sz.serve_ctx,
                        block_size=sz.block_size,
                        prefill_chunk=sz.prefill_chunk, **engine_kw)
    try:
        reqs = [eng.submit(p, max_new_tokens=sz.new_tokens)
                for p in prompts(sz)]
        outs = [r.wait(600) for r in reqs]
    finally:
        eng.close()
    assert all(len(o) == sz.new_tokens for o in outs), \
        [len(o) for o in outs]
    return outs


def agreement(a, b):
    pairs = [(x, y) for s, t in zip(a, b) for x, y in zip(s, t)]
    return round(sum(x == y for x, y in pairs) / len(pairs), 4)


def step_logits(model, sz, kind):
    """Logits of one compiled serving step on a random cache: the model's
    own step builders with return_logits=True, under whatever kernel
    policy is in force."""
    import jax.numpy as jnp

    from paddle_tpu.serving import KVBlockPool

    cfg = model.config
    B, bs = sz.serve_batch, sz.block_size
    Mb = sz.serve_ctx // bs
    C = {"decode": 1, "chunk": sz.prefill_chunk, "spec": sz.spec_k + 1,
         "tree": 1 + sz.spec_tree[0] * sz.spec_tree[1]}[kind]
    rng = np.random.RandomState(11)
    pool = KVBlockPool(cfg.n_layers, cfg.n_heads, cfg.head_dim, bs, B * Mb)
    kv = [jnp.asarray(rng.randn(*pool.k.shape).astype(np.float32) * 0.3)
          for _ in range(2)]
    tables, pos = paged_layout(rng, B, Mb, bs, C)
    toks = rng.randint(0, cfg.vocab_size, (B, C)).astype(np.int32)
    on = np.ones(B, bool)
    if kind == "decode":
        step = model.make_decode_step(B, Mb, return_logits=True)
        out = step(model.weights, kv[0], kv[1], toks[:, 0], on,
                   np.zeros(B, np.int32), pos[:, 0], tables, on)
    else:
        if kind == "tree":
            step = model.make_spec_tree_step(B, Mb, *sz.spec_tree,
                                             return_logits=True)
        else:
            make = (model.make_prefill_step if kind == "chunk"
                    else model.make_spec_step)
            step = make(B, Mb, C, return_logits=True)
        out = step(model.weights, kv[0], kv[1], toks, on,
                   np.zeros(B, np.int32), pos[:, 0],
                   np.full(B, C, np.int32), tables, on)
    return np.asarray(out[3])


def store_parity(sz):
    """One decode step and one chunk step from both weight stores: the
    one this device gets (`serving.model.dot_operand_dtype`: on the
    chip the dot operands in bfloat16, rounded once when the model is
    built) and the float32 one, built the way the rule itself offers
    (under a raised default matmul precision the store keeps float32)
    and stepped at the default precision like the other, so that its
    dots round the same operands on every step. Same arithmetic: the
    tokens must be identical, and the logits apart by accumulation
    order at most."""
    import jax

    from paddle_tpu.serving import GenerationModel

    served = serving_model(sz)
    with jax.default_matmul_precision("highest"):
        wide = GenerationModel.random(served.config, seed=7)
    out = {"served_dot_operand_dtype": str(served.weights["lm_head"].dtype),
           "float32_store_dtype": str(wide.weights["lm_head"].dtype)}
    assert out["float32_store_dtype"] == "float32", out
    for kind in ("decode", "chunk"):
        got = step_logits(served, sz, kind)
        want = step_logits(wide, sz, kind)
        assert got.shape == want.shape and np.isfinite(got).all(), kind
        same = bool((got.argmax(-1) == want.argmax(-1)).all())
        out[kind] = {"tokens_identical": same,
                     "max_abs_dlogit": float(np.abs(got - want).max()),
                     "logit_std": float(want.std()),
                     "logit_max_abs": float(np.abs(want).max())}
        assert same, (kind, out)
    return out


def leg_serve(sz, rehearsal):
    assert "PTPU_KERNELS" not in os.environ
    if rehearsal:
        # off-TPU the kernels are off by default; the rehearsal forces
        # them through the interpreter so the same counters move
        os.environ["PTPU_KERNELS"] = "1"
    names = ("paged_decode", "chunk_window", "spec_window",
             "spec_window_tree")
    k0 = {n: counter("kernels/kernel:" + n) for n in names}
    fall0 = counter("kernels/fallbacks")
    chunk0 = counter("serving/prefill_chunk_steps")
    model = serving_model(sz)
    try:
        plain = serve_all(model, sz)
        linear = serve_all(model, sz, spec_k=sz.spec_k)
        tree = serve_all(model, sz, spec_tree=sz.spec_tree)
        kernel_logits = {k: step_logits(model, sz, k)
                         for k in ("decode", "spec", "tree")}
        parity = store_parity(sz)
        dispatched = {n: counter("kernels/kernel:" + n) - k0[n]
                      for n in names}
        fallbacks = counter("kernels/fallbacks") - fall0
    finally:
        os.environ.pop("PTPU_KERNELS", None)
    chunk_steps = counter("serving/prefill_chunk_steps") - chunk0
    assert all(v >= 1 for v in dispatched.values()), dispatched
    assert fallbacks == 0, fallbacks
    assert chunk_steps >= 1, "no request went through chunked prefill"

    # the same engine and the same steps on the lax path
    os.environ["PTPU_KERNELS"] = "0"
    try:
        plain_lax = serve_all(model, sz)
        lax_logits = {k: step_logits(model, sz, k) for k in kernel_logits}
    finally:
        del os.environ["PTPU_KERNELS"]
    logit_err = {}
    for k, want in lax_logits.items():
        got = kernel_logits[k]
        assert got.shape == want.shape and np.isfinite(got).all(), k
        err = float(np.abs(got - want).max() / np.abs(want).max())
        assert err <= LOGITS_REL_BOUND, (k, err)
        logit_err[k] = round(err, 6)
    return {"requests": 3 * len(plain), "new_tokens": sz.new_tokens,
            "prompt_lens": list(sz.prompt_lens),
            "kernel_dispatches": dispatched, "kernel_fallbacks": fallbacks,
            "prefill_chunk_steps": chunk_steps,
            "token_agreement_vs_lax_path": agreement(plain, plain_lax),
            "token_agreement_linear_spec_vs_plain": agreement(linear, plain),
            "token_agreement_tree_spec_vs_plain": agreement(tree, plain),
            "logits_rel_err_vs_lax": logit_err,
            "logits_rel_bound": LOGITS_REL_BOUND,
            "weight_store_parity": parity}


# ---------------------------------------------------------------------------
# latent: the latent-attention / routed-expert block at published widths,
# through the engine and step against step (kernels vs the lax path)
# ---------------------------------------------------------------------------

LATENT_KERNELS = ("gmm", "latent_decode", "latent_window", "latent_write")


def latent_step_logits(model, sz, chunked):
    """Logits of one decode or one chunk step of the block on a random
    latent cache, under whatever kernel policy is in force."""
    import jax.numpy as jnp

    from paddle_tpu.serving import KVBlockPool

    cfg = model.config
    B, bs = sz.latent_batch, sz.block_size
    Mb = sz.serve_ctx // bs
    C = sz.latent_chunk if chunked else 1
    rng = np.random.RandomState(13)
    pool = KVBlockPool(cfg.n_layers, cfg.n_heads, cfg.head_dim, bs, B * Mb,
                       entry=model.cache_entry())
    latent = jnp.asarray(rng.randn(*pool.arrays[0].shape)
                         .astype(np.float32) * 0.3, pool.dtype)
    tables, pos = paged_layout(rng, B, Mb, bs, C)
    toks = rng.randint(0, cfg.vocab_size, (B, C)).astype(np.int32)
    on = np.ones(B, bool)
    if chunked:
        # full windows beside one-token rows, as a mixed step holds them
        lens = np.where(np.arange(B) % 2, 1, C).astype(np.int32)
        out = model.make_prefill_step(B, Mb, C, return_logits=True)(
            model.weights, latent, toks, lens > 1, np.zeros(B, np.int32),
            pos[:, 0], lens, tables, on)
    else:
        out = model.make_decode_step(B, Mb, return_logits=True)(
            model.weights, latent, toks[:, 0], on, np.zeros(B, np.int32),
            pos[:, 0], tables, on)
    return np.asarray(out[3]), np.asarray(out[2])


def block_leg(sz, rehearsal, config_kw, batch, chunk, kernels,
              step_logits, seed, counters_agree=False, after_serving=None):
    """A serving block's leg: serve the prompts through the engine with
    the block's kernels on, then one decode and one chunk step on a
    random cache, kernel path against lax path. ``after_serving(stats)``
    adds to the report from the engine's statistics."""
    from paddle_tpu.serving import (GenerationConfig, GenerationModel,
                                    ServingEngine)

    assert "PTPU_KERNELS" not in os.environ
    if rehearsal:
        os.environ["PTPU_KERNELS"] = "1"
    k0 = {n: counter("kernels/kernel:" + n) for n in kernels}
    fall0 = counter("kernels/fallbacks")
    model = GenerationModel.random(
        GenerationConfig(max_seq_len=sz.serve_ctx, **config_kw), seed=seed)
    report = {}
    try:
        eng = ServingEngine(model, max_batch=batch,
                            max_seq_len=sz.serve_ctx,
                            block_size=sz.block_size, prefill_chunk=chunk)
        try:
            outs = [r.wait(900) for r in
                    [eng.submit(p, max_new_tokens=sz.new_tokens)
                     for p in prompts(sz)]]
            if after_serving is not None:
                report.update(after_serving(
                    next(iter(eng.stats().values()))))
        finally:
            eng.close()
        assert all(len(o) == sz.new_tokens for o in outs)
        kernel = {k: step_logits(model, sz, k == "chunk")
                  for k in ("decode", "chunk")}
        dispatched = {n: counter("kernels/kernel:" + n) - k0[n]
                      for n in kernels}
        fallbacks = counter("kernels/fallbacks") - fall0
    finally:
        os.environ.pop("PTPU_KERNELS", None)
    assert all(v >= 1 for v in dispatched.values()), dispatched
    assert fallbacks == 0, fallbacks
    os.environ["PTPU_KERNELS"] = "0"
    try:
        lax = {k: step_logits(model, sz, k == "chunk") for k in kernel}
    finally:
        del os.environ["PTPU_KERNELS"]
    steps = {}
    for k, (got, counters) in kernel.items():
        want = lax[k][0]
        assert got.shape == want.shape and np.isfinite(got).all(), k
        # a row's error as a share of the largest logit. The two paths
        # round differently, so a token at a near-tie of the router may
        # take another expert on one of them and its row then differs by
        # the logits' own size: the median row is held to the bound, the
        # worst row reported
        rows = np.abs(got - want).max(axis=1) / np.abs(want).max()
        assert np.median(rows) <= LOGITS_REL_BOUND, (k, np.median(rows))
        if counters_agree:
            assert (counters == lax[k][1]).all() or k == "chunk", k
        steps[k] = {"median_row_err": float("%.3g" % np.median(rows)),
                    "worst_row_err": float("%.3g" % rows.max()),
                    "counters": [int(c) for c in counters]}
    return dict(report, requests=len(outs), kernel_dispatches=dispatched,
                kernel_fallbacks=fallbacks, steps=steps,
                logits_rel_bound=LOGITS_REL_BOUND)


def leg_latent(sz, rehearsal):
    return block_leg(sz, rehearsal, sz.latent, sz.latent_batch,
                     sz.latent_chunk, LATENT_KERNELS, latent_step_logits,
                     seed=7, counters_agree=True)


# ---------------------------------------------------------------------------
# afmoe: the grouped-query window/global block, its decode and chunk steps
# over two kinds of page, kernel path against lax path
# ---------------------------------------------------------------------------

AFMOE_KERNELS = ("gmm", "gqa_decode", "gqa_chunk", "kv_page_write")


def paged_step_logits(model, batch, chunk, sz):
    """Logits of one decode (``chunk`` 1) or one chunk step of a
    grouped-query block over random pages of every kind it keeps
    (contexts longer than a window, so that a walk starts past page 0)
    and, where it has one, a random row state, under whatever kernel
    policy is in force."""
    import jax.numpy as jnp

    from paddle_tpu.serving import KVBlockPool

    cfg = model.config
    B, bs, C = batch, sz.block_size, chunk
    Mb = sz.serve_ctx // bs
    rng = np.random.RandomState(17)
    kinds = model.page_kinds()
    pool = KVBlockPool(cfg.n_layers, cfg.n_heads, cfg.head_dim, bs,
                       [B * Mb] * len(kinds) if len(kinds) > 1 else B * Mb,
                       entry=model.cache_entry(), kinds=kinds)
    arrays = [jnp.asarray(rng.randn(*a.shape).astype(np.float32) * 0.3,
                          pool.dtype) for a in pool.arrays]
    tables = np.stack([rng.permutation(np.arange(1, B * Mb + 1))
                       .reshape(B, Mb).astype(np.int32) for _ in kinds])
    if len(kinds) == 1:
        tables = tables[0]
    state = model.row_state()
    if state is not None:
        arrays.append(jnp.asarray(
            rng.randn(B, *state[0]).astype(np.float32) * 0.3, state[1]))
    pos = rng.randint(Mb * bs // 2, Mb * bs - C, B).astype(np.int32)
    toks = rng.randint(0, cfg.vocab_size, (B, C)).astype(np.int32)
    on = np.ones(B, bool)
    if C > 1:
        lens = np.where(np.arange(B) % 2, 1, C).astype(np.int32)
        out = model.make_prefill_step(B, Mb, C, return_logits=True)(
            model.weights, *arrays, toks, lens > 1, np.zeros(B, np.int32),
            pos, lens, tables, on)
    else:
        out = model.make_decode_step(B, Mb, return_logits=True)(
            model.weights, *arrays, toks[:, 0], on, np.zeros(B, np.int32),
            pos, tables, on)
    # (*pools', [row state',] tokens, counters, top_logit, logits)
    return np.asarray(out[-1]), np.asarray(out[-3])


def afmoe_step_logits(model, sz, chunked):
    return paged_step_logits(model, sz.afmoe_batch,
                             sz.afmoe_chunk if chunked else 1, sz)


def leg_afmoe(sz, rehearsal):
    def released(stats):
        # prompts longer than the window: pages slid out and came back
        assert stats["window_blocks_released"] > 0, stats
        return {"window_blocks_released": stats["window_blocks_released"]}

    return block_leg(sz, rehearsal, sz.afmoe, sz.afmoe_batch,
                     sz.afmoe_chunk, AFMOE_KERNELS, afmoe_step_logits,
                     seed=11, after_serving=released)


def zaya_step_logits(model, sz, chunked):
    return paged_step_logits(model, sz.zaya_batch,
                             sz.zaya_chunk if chunked else 1, sz)


def leg_zaya(sz, rehearsal):
    """The fourth block: the same kernels over one kind of page, a row
    state carried through the engine's steps, a tied head."""
    return block_leg(sz, rehearsal, sz.zaya, sz.zaya_batch, sz.zaya_chunk,
                     AFMOE_KERNELS, zaya_step_logits, seed=13)


# ---------------------------------------------------------------------------
# kernels: the shapes the legs above use (tests/test_kernels_lower_tpu.py
# lowers the same cases for the TPU from the sandbox)
# ---------------------------------------------------------------------------

def paged_layout(rng, B, Mb, bs, C):
    """Block tables + window positions as the scheduler lays them out:
    distinct physical pages up to each row's last position, the null
    page (0) past it; rows at both ends of the context."""
    pos0 = rng.randint(1, Mb * bs - C, size=B).astype(np.int32)
    pos0[0], pos0[-1] = 1, Mb * bs - C
    pos = pos0[:, None] + np.arange(C, dtype=np.int32)[None, :]
    tables = rng.permutation(np.arange(1, B * Mb + 1)) \
        .reshape(B, Mb).astype(np.int32)
    for b in range(B):
        tables[b, pos[b, -1] // bs + 1:] = 0
    return tables, pos


def kernel_cases(sz):
    """{kernel: (specs, kwargs, qualify_kwargs, fill)}: specs are the
    operand (shape, dtype) pairs, fill(rng) the arrays to run with."""
    H = sz.model["n_heads"]
    Dh = sz.model["d_model"] // H
    B, bs = sz.serve_batch, sz.block_size
    Mb = sz.serve_ctx // bs
    NB = B * Mb + 1
    f32, i32 = np.float32, np.int32
    cases = {}

    def paged(name, C, anc=None, H=H, Dh=Dh):
        # the pool goes in whole, as KVBlockPool stores it; two layers,
        # the second one read, so the kernel's layer index is exercised
        pool = (2, NB, bs, H, Dh)
        specs = [(pool, f32), (pool, f32),
                 ((B, C, H, Dh), f32), ((B, Mb), i32), ((B, C), i32)]
        if anc is not None:
            specs.append(((C, C), f32))

        def fill(rng):
            tables, pos = paged_layout(rng, B, Mb, bs, C)
            out = [rng.randn(*s).astype(f32) for s, _ in specs[:3]]
            return out + [tables, pos] + ([anc] if anc is not None else [])

        cases[name] = (specs, {"layer": 1},
                       dict(head_dim=Dh, block_size=bs, window=C), fill)

    from paddle_tpu.serving.model import tree_topology

    # the decode step at heads of 128 lanes, as the served
    # configurations have them: one grid step a row, the row's own
    # pages by DMA. A narrower head takes the verify window's kernel,
    # run below at the trainer's heads of 64.
    paged("paged_decode", 1, H=max(1, H * Dh // 128), Dh=128)
    paged("spec_window", sz.spec_k + 1)
    paged("spec_window_tree", 1 + sz.spec_tree[0] * sz.spec_tree[1],
          tree_topology(*sz.spec_tree)[2].astype(f32))

    # the chunk window as query tiles: a full tile deep in its row's
    # context, the tile after it (a chunk cut short), one-token tiles
    # (decode rows) at both ends of the context, and an unused tile
    from paddle_tpu.serving.model import CHUNK_TILE

    # (heads of 128 lanes, the only ones the kernel takes: it copies
    # pages as the pool stores them)
    Cq = min(CHUNK_TILE, sz.serve_ctx // 2)
    Hc, Dc = max(1, H * Dh // 128), 128
    pool = (2, NB, bs, Hc, Dc)
    chunk_specs = [(pool, f32), (pool, f32), ((B, Cq, Hc, Dc), f32),
                   ((B, Mb), i32), ((B,), i32), ((B,), i32)]

    def fill_chunk(rng):
        tables = rng.permutation(np.arange(1, B * Mb + 1)) \
            .reshape(B, Mb).astype(i32)
        room = Mb * bs
        pos = rng.randint(0, room - 1, B).astype(i32)
        lens = np.ones(B, i32)
        pos[0], lens[0] = room - 2 * Cq, Cq
        tables[1], pos[1], lens[1] = tables[0], room - Cq, Cq - 3
        pos[2], pos[3] = 0, room - 1
        lens[4:5] = 0                  # (the toy's four tiles have none)
        return [rng.randn(*s).astype(f32) for s, _ in chunk_specs[:3]] \
            + [tables, pos, lens]

    cases["chunk_window"] = (
        chunk_specs, {"layer": 1},
        dict(head_dim=Dc, block_size=bs, window=Cq), fill_chunk)

    import jax.numpy as jnp

    qkv = [((sz.batch, H, sz.seq, Dh), jnp.bfloat16)] * 3
    cases["flash_attention"] = (
        qkv, {"causal": True},
        dict(T=sz.seq, head_dim=Dh, causal=True),
        lambda rng: [jnp.asarray(rng.randn(*s).astype(f32), d)
                     for s, d in qkv])

    # the latent block's three kernels at its widths: the latent pool
    # whole (two layers, the second used), rows at both ends of the
    # context, one-token rows beside full windows, a row that is not
    # live between two that hand the page pipe over it
    lb = sz.latent["block"]
    Hl, Bl = sz.latent["n_heads"], sz.latent_batch
    r = lb["kv_lora_rank"]
    W = -(-(r + lb["qk_rope_head_dim"]) // 128) * 128
    lpool = ((2, Bl * Mb + 1, bs, W), jnp.bfloat16)

    def latent(name, C):
        def layout(rng):
            tables, pos = paged_layout(rng, Bl, Mb, bs, C)
            lens = np.where(np.arange(Bl) % 2, 1, C).astype(i32)
            return tables, pos[:, 0].copy(), lens

        def fill_attn(rng):
            tables, pos0, lens = layout(rng)
            lens[1] = 0           # not live, between two rows that are
            return [jnp.asarray(rng.randn(*lpool[0]).astype(f32), lpool[1]),
                    (rng.randn(Bl, C, Hl, W) * 0.1).astype(f32),
                    tables, pos0, lens]

        cases[name] = (
            [lpool, ((Bl, C, Hl, W), f32), ((Bl, Mb), i32), ((Bl,), i32),
             ((Bl,), i32)], {"layer": 1, "v_width": r},
            dict(width=W, v_width=r, block_size=bs, window=C), fill_attn)
        if C > 1:
            def fill_write(rng):
                tables, pos0, lens = layout(rng)
                lens[0] = 0                       # a row that sits out
                return [jnp.asarray(rng.randn(*lpool[0]).astype(f32),
                                    lpool[1]),
                        rng.randn(Bl, C, W).astype(f32), tables, pos0, lens]

            cases["latent_write"] = (
                [lpool, ((Bl, C, W), f32), ((Bl, Mb), i32), ((Bl,), i32),
                 ((Bl,), i32)], {"layer": 1},
                dict(width=W, block_size=bs, window=C), fill_write)

    latent("latent_decode", 1)
    latent("latent_window", sz.latent_chunk)

    # gmm at the expert layer's shapes: a decode step's pairs over all
    # experts, uneven groups, two experts without a row, spare tiles
    from paddle_tpu.ops.pallas_kernels import GMM_BLOCK_M as bm

    E, Dm, Fe = lb["n_routed_experts"], sz.latent["d_model"], lb["moe_d_ff"]
    pairs = Bl * lb["experts_per_token"]
    n_tiles = -(-(pairs + E * (bm - 1)) // bm)

    def fill_gmm(rng):
        sizes = rng.multinomial(pairs, np.ones(E) / E)
        sizes[:2] = 0
        tiles = -(-sizes // bm)
        lhs = np.zeros((n_tiles * bm, Dm), f32)
        row = 0
        for g, t in zip(sizes, tiles):
            lhs[row:row + g] = rng.randn(g, Dm)
            row += t * bm
        te = np.repeat(np.arange(E), tiles)
        te = np.concatenate([te, np.full(n_tiles - len(te), te[-1])])
        return [jnp.asarray(lhs, jnp.bfloat16),
                jnp.asarray(rng.randn(E, Dm, Fe).astype(f32) * 0.05,
                            jnp.bfloat16),
                te.astype(i32), np.array([tiles.sum()], i32)]

    cases["gmm"] = (
        [((n_tiles * bm, Dm), jnp.bfloat16), ((E, Dm, Fe), jnp.bfloat16),
         ((n_tiles,), i32), ((1,), i32)], {},
        dict(rows=n_tiles * bm, k=Dm, n=Fe), fill_gmm)

    # the grouped-query block's three kernels at its widths: packed bf16
    # pools of [bs, Hkv * Dh] pages (two layers, the second used), tiles
    # deep in a context under a window, one-token tiles, an unused tile
    ab = sz.afmoe["block"]
    Ha, Hkv, Da = sz.afmoe["n_heads"], ab["n_kv_heads"], ab["head_dim"]
    Ba, Ca = sz.afmoe_batch, min(128, sz.afmoe_chunk)
    gpool = ((2, Ba * Mb + 1, bs, Hkv * Da), jnp.bfloat16)
    win = ab["sliding_window"]

    def gqa_layout(rng):
        tables = rng.permutation(np.arange(1, Ba * Mb + 1)) \
            .reshape(Ba, Mb).astype(i32)
        room = Mb * bs
        pos = rng.randint(win, room - Ca, Ba).astype(i32)
        lens = np.where(np.arange(Ba) % 2, 1, Ca).astype(i32)
        pos[0], lens[-1] = 0, 0
        # entries the window has slid past may point anywhere
        for b in range(1, Ba):
            tables[b, :max(pos[b] - win + 1, 0) // bs] = 0
        return tables, pos, lens

    def gqa_pools(rng):
        return [jnp.asarray(rng.randn(*gpool[0]).astype(f32), gpool[1])
                for _ in range(2)]

    def fill_gqa_chunk(rng):
        tables, pos, lens = gqa_layout(rng)
        return gqa_pools(rng) + [rng.randn(Ba, Ca, Ha, Da).astype(f32),
                                 tables, pos, lens]

    def fill_gqa_decode(rng):
        tables, pos, _lens = gqa_layout(rng)
        return gqa_pools(rng) + [rng.randn(Ba, Ha, Da).astype(f32),
                                 tables, pos]

    gq = dict(head_dim=Da, block_size=bs)
    cases["gqa_chunk"] = (
        [gpool, gpool, ((Ba, Ca, Ha, Da), f32), ((Ba, Mb), i32),
         ((Ba,), i32), ((Ba,), i32)], {"layer": 1, "window": win}, gq,
        fill_gqa_chunk)
    cases["gqa_decode"] = (
        [gpool, gpool, ((Ba, Ha, Da), f32), ((Ba, Mb), i32), ((Ba,), i32)],
        {"layer": 1, "window": win}, gq, fill_gqa_decode)
    Ua = 2 * Ba

    def fill_page_write(rng):
        ids = rng.permutation(np.arange(1, Ba * Mb + 1))[:Ua].astype(i32)
        lo = rng.randint(0, bs, Ua).astype(i32)
        hi = np.minimum(lo + rng.randint(0, bs + 1, Ua), bs).astype(i32)
        ids[0], hi[0] = 0, lo[0]                   # an unused unit
        rows = [jnp.asarray(rng.randn(Ua, bs, Hkv * Da).astype(f32),
                            jnp.bfloat16) for _ in range(2)]
        return gqa_pools(rng) + rows + [ids, lo, hi]

    rows_spec = ((Ua, bs, Hkv * Da), jnp.bfloat16)
    cases["kv_page_write"] = (
        [gpool, gpool, rows_spec, rows_spec, ((Ua,), i32), ((Ua,), i32),
         ((Ua,), i32)], {"layer": 1}, gq, fill_page_write)

    # the scan's two kernels: states of every row and layer in one
    # array (the second layer used); the one-token step with idle rows
    # at both ends and one that starts its sequence (decay 0); the
    # chunked one over rows of several tiles, one tile and two tokens,
    # from their stored state and from zero, decays down to the gate's
    # bound of -5
    from paddle_tpu.ops.pallas_kernels import KDA_TILE

    sc = sz.scan
    Hs, ds, Bs = sc["n_heads"], sc["head_dim"], sc["batch"]
    state_spec = ((Bs, sc["layers"], Hs, ds, ds), f32)

    def unit(x):
        return (x / np.linalg.norm(x, axis=-1, keepdims=True)).astype(f32)

    def fill_scan(rng, T):
        return [rng.randn(*state_spec[0]).astype(f32),
                unit(rng.randn(T, Hs, ds)) * f32(ds ** -0.5),
                unit(rng.randn(T, Hs, ds)), rng.randn(T, Hs, ds).astype(f32)]

    def fill_kda_decode(rng):
        alpha = np.exp(-5.0 * rng.rand(Bs, Hs, ds)).astype(f32)
        alpha[1] = 0.0
        on = np.ones(Bs, bool)
        on[0] = on[-1] = False
        return fill_scan(rng, Bs) + [alpha, rng.rand(Bs, Hs).astype(f32),
                                     on]

    cases["kda_decode"] = (
        [state_spec] + [((Bs, Hs, ds), f32)] * 4
        + [((Bs, Hs), f32), ((Bs,), np.bool_)], {"layer": 1},
        dict(head_dim=ds, n_heads=Hs), fill_kda_decode)
    rows = sc["chunk_rows"]
    Ts = sum(rows)
    tiles = [(sum(rows[:b]) + j, min(KDA_TILE, n - j), b,
              (1 + b % 2) if j == 0 else 0, int(j + KDA_TILE >= n))
             for b, n in enumerate(rows) for j in range(0, n, KDA_TILE)]
    tiles.insert(1, (0, 0, 0, 0, 0))                # a tile that sits out

    def fill_kda_chunk(rng):
        g = (-5.0 * rng.rand(Ts, Hs, ds)).astype(f32)
        g[:KDA_TILE] = -5.0
        return fill_scan(rng, Ts) + [g, rng.rand(Ts, Hs).astype(f32)] \
            + [np.array(col, i32) for col in zip(*tiles)]

    cases["kda_chunk"] = (
        [state_spec] + [((Ts, Hs, ds), f32)] * 4 + [((Ts, Hs), f32)]
        + [((len(tiles),), i32)] * 5, {"layer": 1},
        dict(head_dim=ds, n_heads=Hs), fill_kda_chunk)

    M, K, N = sz.int8_mkn
    cases["int8_matmul"] = (
        [((M, K), f32), ((K, N), np.int8), ((N,), f32)],
        {"act_scale": 40.0}, dict(),
        lambda rng: [rng.randn(M, K).astype(f32),
                     rng.randint(-127, 128, (K, N)).astype(np.int8),
                     (rng.rand(N).astype(f32) + 0.1) * 1e-3])
    return cases


def leg_kernels(sz, rehearsal):
    import jax

    from paddle_tpu.core import device
    from paddle_tpu.ops.kernel_registry import registered_kernels

    # compiled, never interpreted, on the chip; the rehearsal interprets
    assert device.pallas_interpret() == rehearsal
    cases = kernel_cases(sz)
    specs = registered_kernels()
    assert set(cases) == set(specs), (sorted(cases), sorted(specs))
    rng = np.random.RandomState(5)
    out = {}
    for name, (_shapes, kwargs, qualify, fill) in sorted(cases.items()):
        spec = specs[name]
        ok, why = spec.qualify(**qualify) if qualify else (True, None)
        assert ok, (name, why)
        args = fill(rng)
        got = jax.jit(lambda *a: spec.pallas(*a, **kwargs))(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda *a: spec.fallback(*a, **kwargs))(*args)
        if name.startswith("kda_"):
            # (states, read-outs): two results of different shapes
            got, want = (np.concatenate([np.asarray(a, np.float32).ravel()
                                         for a in pair])
                         for pair in (got, want))
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        if name == "chunk_window":
            # an unused tile's slots are not written (the chunk step
            # reads none of them): the tiles that hold a token compare
            used = np.asarray(args[5]) > 0
            got, want = got[used], want[used]
        scale = float(np.abs(want).max())
        err = float(np.abs(got - want).max())
        bound = (INT8_REL_BOUND if name == "int8_matmul"
                 else KERNEL_REL_BOUND) * scale
        assert got.shape == want.shape and np.isfinite(got).all(), name
        assert err <= bound, (name, err, bound)
        out[name] = {"max_abs_err": float("%.3g" % err),
                     "bound": float("%.3g" % bound)}
    return {"interpret": rehearsal, "kernels": out}


# ---------------------------------------------------------------------------
# rec: host embedding table, prefetch + hot-row cache, train_from_dataset
# ---------------------------------------------------------------------------

def leg_rec(sz, rehearsal):
    import shutil
    import tempfile

    from paddle_tpu import framework, initializer
    from paddle_tpu.models import deepfm
    from paddle_tpu.parallel.host_embedding import HostEmbeddingTable

    r = sz.rec
    tmp = tempfile.mkdtemp(prefix="ptpu_smoke_rec_")
    knobs = {"PTPU_EMBED_PREFETCH": "1",
             "PTPU_EMBED_CACHE_ROWS": str(r["cache_rows"]),
             "PTPU_EMBED_CACHE_ADMIT": "2"}
    Var = collections.namedtuple("Var", "name")
    seed_base = initializer._global_seed_counter[0]

    def records(seed):
        rng = np.random.RandomState(seed)
        for _ in range(r["records_per_shard"]):
            # half the lookups land in a small hot set, so frequency
            # admission has a signal
            hot = rng.rand(r["fields"]) < 0.5
            ids = np.where(hot, rng.randint(0, 16, r["fields"]),
                           rng.randint(0, r["vocab"], r["fields"]))
            yield (ids.astype(np.int64),
                   np.array([rng.randint(0, 2)], np.float32))

    def run(env):
        os.environ.update(env)
        HostEmbeddingTable.reset_registry()
        ds = fluid.DatasetFactory().create_dataset("QueueDataset")
        ds.set_batch_size(r["batch_size"])
        ds.set_filelist(paths)
        prog, sprog = framework.Program(), framework.Program()
        # both builds draw the same initializer seeds (bench.py's way)
        initializer._global_seed_counter[0] = seed_base
        try:
            with framework.program_guard(prog, sprog), \
                    fluid.unique_name.guard(), \
                    fluid.scope_guard(fluid.Scope()):
                np.random.seed(42)
                _feeds, _pred, cost = deepfm.build_distributed(
                    vocab_size=r["vocab"], num_fields=r["fields"],
                    embed_dim=r["embed_dim"], mlp_dims=(32, 16),
                    num_shards=2, learning_rate=0.05)
                fluid.optimizer.SGD(learning_rate=0.05).minimize(cost)
                ds.set_use_var([Var("ids"), Var("label")])
                exe = fluid.Executor(place(rehearsal))
                exe.run(sprog)
                losses = [np.asarray(exe.train_from_dataset(
                    program=prog, dataset=ds, fetch_list=[cost])[0]).copy()
                    for _ in range(2)]
                exe.close()
        finally:
            for k in env:
                del os.environ[k]
        return np.concatenate([v.ravel() for v in losses])

    try:
        paths = []
        for s in range(r["n_shards"]):
            paths.append("%s/ctr%02d.rec" % (tmp, s))
            fluid.convert_reader_to_recordio_file(
                paths[-1], lambda s=s: records(7000 + s))
        c0 = {m: counter("embed/" + m)
              for m in ("cache_hits", "prefetch_hits", "pull_rows")}
        fast = run(knobs)
        moved = {m: counter("embed/" + m) - c0[m] for m in c0}
        sync = run({})
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
        HostEmbeddingTable.reset_registry()
    assert np.isfinite(fast).all() and np.isfinite(sync).all()
    assert moved["prefetch_hits"] > 0 and moved["cache_hits"] > 0, moved
    # the fast path may only move work, never change numerics
    np.testing.assert_allclose(fast, sync, rtol=1e-5, atol=1e-6)
    return {"steps_per_epoch": (r["n_shards"] * r["records_per_shard"]
                                // r["batch_size"]), "epochs": 2,
            "last_loss": round(float(fast[-1]), 5),
            "bitwise_equal_to_sync_path": bool((fast == sync).all()),
            "embed_counters": moved}


# ---------------------------------------------------------------------------
# multichip
# ---------------------------------------------------------------------------

def leg_multichip(sz, rehearsal, shared):
    import jax

    prog, sprog, loss = shared["trainer"]
    feed = train_batch(sz, sz.batch)
    out = {}
    for tag, tp in (("dp4", 1), ("dp2_tp2", 2)):
        bs = fluid.BuildStrategy()
        bs.tensor_parallel_degree = tp
        with fluid.scope_guard(fluid.Scope()):
            exe = fluid.Executor(place(rehearsal))
            exe.run(sprog)
            compiled = fluid.CompiledProgram(prog).with_data_parallel(
                loss_name=loss.name, build_strategy=bs)
            lv, = exe.run(compiled, feed=feed, fetch_list=[loss])
            first = float(np.asarray(lv).mean())
            step = next(iter(compiled._compiled_steps.values()))
            batch_devs = step.feed_sharding(
                "tokens", feed["tokens"]).device_set
            param_devs = set()
            for p in prog.all_parameters():
                param_devs |= set(fluid.global_scope().get(p.name).devices())
            exe.close()
        assert len(batch_devs) == 4 and len(param_devs) == 4, \
            (batch_devs, param_devs)
        diff = abs(first - shared["train_first_loss"])
        assert diff <= LOSS_TOL, (tag, first, shared["train_first_loss"])
        out[tag] = {"mesh": dict(compiled._get_mesh().shape),
                    "first_loss": round(first, 4),
                    "abs_diff_vs_one_chip": round(diff, 5),
                    "param_devices": len(param_devs),
                    "batch_devices": len(batch_devs)}
    out["tolerance"] = LOSS_TOL
    out["devices"] = len(jax.devices())
    return out


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-on-cpu", action="store_true",
                    help="toy-size rehearsal on a virtual 4-device CPU "
                         "mesh: debugs this script, proves nothing about "
                         "the chip")
    args = ap.parse_args(argv)
    rehearsal = args.rehearse_on_cpu
    if rehearsal:
        from xla_env import use_host_mesh

        use_host_mesh(4)  # before the first device query: never the chip
    import jax

    from paddle_tpu.core import device
    from paddle_tpu.observability import metrics

    ident = device.identity()
    if ident.platform != "tpu" and not rehearsal:
        # no accelerator: no result line, a non-zero exit
        sys.exit("chip_smoke: needs a TPU; jax found platform %r (%s, %d "
                 "device(s)). `--rehearse-on-cpu` runs the toy rehearsal."
                 % tuple(ident))
    sz = TOY if rehearsal else FULL
    metrics.enable()
    clock = Clock()
    results, shared = {}, {}
    t0 = time.perf_counter()
    multichip = "not reached"
    try:
        run_leg("device", lambda: leg_device(rehearsal), clock, results)
        run_leg("train", lambda: leg_train(sz, rehearsal, shared), clock,
                results)
        run_leg("serve", lambda: leg_serve(sz, rehearsal), clock, results)
        run_leg("latent", lambda: leg_latent(sz, rehearsal), clock,
                results)
        run_leg("afmoe", lambda: leg_afmoe(sz, rehearsal), clock,
                results)
        run_leg("zaya", lambda: leg_zaya(sz, rehearsal), clock, results)
        run_leg("kernels", lambda: leg_kernels(sz, rehearsal), clock,
                results)
        run_leg("rec", lambda: leg_rec(sz, rehearsal), clock, results)
        if len(jax.devices()) >= 4:
            multichip = "run"
            run_leg("multichip",
                    lambda: leg_multichip(sz, rehearsal, shared), clock,
                    results)
        else:
            multichip = "not run (%d device)" % len(jax.devices())
            print("multichip: " + multichip, flush=True)
    except Exception:
        traceback.print_exc()
    ok = (all(r["ok"] for r in results.values())
          and set(results) >= {"device", "train", "serve", "latent", "afmoe",
                               "zaya", "kernels", "rec"})
    summary = {
        "ok": ok,
        "device": ident._asdict(),
        "legs": {k: {"ok": r["ok"], "seconds": r["seconds"],
                     "compile_seconds": r["compile_seconds"]}
                 for k, r in results.items()},
        "multichip": multichip,
        "cache_dir": persistent_cache_dir(),
        "cache_hits": clock.hits, "cache_writes": clock.writes,
        "seconds": round(time.perf_counter() - t0, 1),
        "claim": None,
    }
    if rehearsal:
        summary["rehearsal"] = ("toy sizes on the CPU, kernels interpreted:"
                                " says nothing about the chip")
    print(json.dumps(summary), flush=True)
    # the result line: these keys and no others, the device as jax has it
    dev = jax.devices()[0]
    print(json.dumps({"ok": ok,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
