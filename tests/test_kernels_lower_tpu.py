"""Kernel pre-flight: every registered Pallas kernel must lower for the
TPU, checked from the CPU sandbox in seconds.

Two strengths of the same question, at the chip_smoke.py shapes (the
flagship at full width) and at a toy shape each kernel's `qualify`
accepts:

  cross-lowering   jax.jit(f).trace(...).lower(lowering_platforms=("tpu",))
                   runs the Pallas -> Mosaic lowering. This is where the
                   (1, bs, 1, Dh) paged BlockSpecs of PR 17 were refused
                   ("last two dimensions of your block shape ...").
  topology compile the installed libtpu compiles for a `v5e:2x2`
                   topology description with no chip attached: the real
                   TPU compiler, Mosaic included. This is where the
                   tree window's [1, 1] -> [C, bs] broadcast was refused.

Whether a kernel compiles is settled here. Numerics, memory and time are
not: those are chip_smoke.py's `kernels` leg, on the chip.

The kernels decide compiled-vs-interpreted in one place
(core.device.pallas_interpret); `device.compiling_for` tells that place
the trace is for a TPU this process does not hold.
"""

import os

import jax
import jax.numpy as jnp
import pytest

import chip_smoke
from paddle_tpu.core import device
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.ops.kernel_registry import registered_kernels
from paddle_tpu.serving.scheduler import default_prefill_token_budget

V5E = device.DeviceIdentity("tpu", "TPU v5 lite", 1)
SIZES = {"smoke": chip_smoke.FULL, "toy": chip_smoke.TOY}
KERNELS = sorted(registered_kernels())


def _case(name, sizes):
    specs, kwargs, qualify, _fill = chip_smoke.kernel_cases(SIZES[sizes])[name]
    spec = registered_kernels()[name]
    ok, why = spec.qualify(**qualify) if qualify else (True, None)
    assert ok, "%s disqualifies its own %s shape: %s" % (name, sizes, why)
    # a fresh function per lowering: the interpret decision is baked in
    # at trace time and must not be served from a CPU trace's cache
    return (lambda *a: spec.pallas(*a, **kwargs)), specs


def test_cases_cover_every_registered_kernel():
    assert sorted(chip_smoke.kernel_cases(chip_smoke.FULL)) == KERNELS


@pytest.mark.parametrize("sizes", sorted(SIZES))
@pytest.mark.parametrize("name", KERNELS)
def test_kernel_cross_lowers_for_tpu(name, sizes):
    fn, specs = _case(name, sizes)
    with device.compiling_for(V5E):
        lowered = jax.jit(fn).trace(
            *[jax.ShapeDtypeStruct(s, d) for s, d in specs]).lower(
                lowering_platforms=("tpu",))
    # compiled, not interpreted: the kernel is a Mosaic custom call
    assert "tpu_custom_call" in lowered.as_text()


@pytest.fixture(scope="module")
def v5e_chips():
    """The four devices of a v5e:2x2 topology description: compile
    targets, not chips. Compile-only use takes no device, so several
    processes may load libtpu at once."""
    from jax.experimental import topologies

    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # libtpu could not start in this sandbox
        pytest.skip("no TPU compiler here: %r" % (e,))
    return topo.devices


@pytest.fixture
def v5e_chip(v5e_chips):
    return v5e_chips[0]


def _compile(fn, specs, chip):
    sharding = jax.sharding.SingleDeviceSharding(chip)
    with device.compiling_for(chip):
        return jax.jit(fn).lower(
            *[jax.ShapeDtypeStruct(s, d, sharding=sharding)
              for s, d in specs]).compile()


@pytest.mark.parametrize("sizes", sorted(SIZES))
@pytest.mark.parametrize("name", KERNELS)
def test_kernel_compiles_for_v5e(name, sizes, v5e_chip):
    assert v5e_chip.device_kind == V5E.kind
    _compile(*_case(name, sizes), v5e_chip)


def test_flash_backward_compiles_for_v5e(v5e_chip):
    """The trainer differentiates through the library flash kernel."""
    specs = chip_smoke.kernel_cases(chip_smoke.FULL)["flash_attention"][0]

    def loss(q, k, v):
        return pk.flash_attention(q, k, v, causal=True) \
            .astype(jnp.float32).sum()

    _compile(jax.grad(loss, argnums=(0, 1, 2)), specs, v5e_chip)


@pytest.mark.parametrize("shape,axes", [((4,), ("dp",)),
                                        ((2, 2), ("dp", "tp"))])
def test_flash_in_a_gspmd_step_compiles_for_four_chips(shape, axes,
                                                       v5e_chips):
    """GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned"): the data-parallel trainer's flash call
    must go through the shard_map in compat_ops._flash_on_mesh. Compiled
    for all four devices of the topology, forward and backward."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from paddle_tpu.ops import compat_ops

    mesh = Mesh(np.array(v5e_chips).reshape(shape), axes)
    batch_sharded = NamedSharding(mesh, PartitionSpec("dp"))
    qkv = [jax.ShapeDtypeStruct(s, d, sharding=batch_sharded) for s, d in
           chip_smoke.kernel_cases(chip_smoke.FULL)["flash_attention"][0]]

    def loss(q, k, v):
        return compat_ops._flash_on_mesh(q, k, v, True, None, mesh) \
            .astype(jnp.float32).sum()

    with device.compiling_for(v5e_chips[0], count=4):
        jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*qkv).compile()


def test_one_row_page_is_disqualified_with_a_reason(v5e_chip):
    """A one-row page was the one paged geometry Mosaic refused while a
    block was a page with its heads folded into the lanes. The stored
    `[bs, H, Dh]` page compiles at block_size 1 as well; `qualify`
    still keeps it on the lax path (it has never run on the chip), with
    a reason, so the dispatch warns and never reaches the compiler."""
    B, H, Dh, bs, Mb, C = 4, 8, 64, 1, 16, 5
    spec = registered_kernels()["spec_window_tree"]
    ok, why = spec.qualify(head_dim=Dh, block_size=bs, window=C)
    assert not ok and "block_size" in why
    f32, i32 = jnp.float32, jnp.int32
    NB = B * Mb + 1
    specs = [((1, NB, bs, H, Dh), f32), ((1, NB, bs, H, Dh), f32),
             ((B, C, H, Dh), f32), ((B, Mb), i32), ((B, C), i32),
             ((C, C), f32)]
    _compile(lambda *a: spec.pallas(*a, layer=0), specs, v5e_chip)


def test_decode_page_walk_compiles_at_the_served_geometry(v5e_chip):
    """The decode step's attention call as `xglm-1.7b-serve` makes it:
    24 layers of `engine.num_blocks` blocks (1,760 since PR 36) of 16
    tokens, 16 heads of 128, 16 rows of 128 blocks, the layer a traced scalar. At that head width
    `paged_decode` is the kernel that walks each row's own pages."""
    import json

    with open(os.path.join(os.path.dirname(chip_smoke.__file__),
                           "perfbench/configs/xglm-1.7b-serve.json")) as f:
        c = json.load(f)
    e = c["engine"]
    H, L, bs, B = (c["attention_heads"], c["num_layers"], e["block_size"],
                   e["max_batch"])
    Dh, Mb = c["d_model"] // H, e["max_seq_len"] // e["block_size"]
    assert (H, Dh, L, bs, B, Mb) == (16, 128, 24, 16, 16, 128)
    spec = registered_kernels()["paged_decode"]
    assert spec.qualify(head_dim=Dh, block_size=bs)[0]
    f32, i32 = jnp.float32, jnp.int32
    pool = ((L, e["num_blocks"] + 1, bs, H, Dh), f32)
    specs = [pool, pool, ((B, 1, H, Dh), f32), ((B, Mb), i32),
             ((B, 1), i32), ((), i32), ((B,), jnp.bool_)]
    compiled = _compile(
        lambda k, v, q, tables, pos, layer, active: spec.pallas(
            k, v, q, tables, pos, layer=layer, active=active),
        specs, v5e_chip)
    hlo = compiled.as_text()
    assert "paged_decode_attention" in hlo
    assert 'kernel_name = "paged_attention"' not in hlo
    # nothing of the pool's size is made for the call
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20


# ---------------------------------------------------------------------------
# the serving steps read the KV pool in place (PR 25)
# ---------------------------------------------------------------------------

POOL_STEP = dict(n_layers=2, n_heads=16, head_dim=128, block_size=16,
                 num_blocks=640, batch=4, blocks_per_seq=8, chunk=16,
                 spec_window=5, tree=(2, 3))


def _zero_model():
    """A two-layer model at the paged kernels' flagship widths (16 heads
    of 128), zero weights: only its compiled steps are looked at."""
    import numpy as np

    from paddle_tpu.serving import GenerationConfig, GenerationModel
    from paddle_tpu.serving.model import weight_names

    g = POOL_STEP
    D = g["n_heads"] * g["head_dim"]
    cfg = GenerationConfig(64, D, g["n_heads"], g["n_layers"], 128,
                           max_seq_len=g["blocks_per_seq"] * g["block_size"])
    shapes = {"embedding": (64, D), "lm_head": (D, 64), "wqkv": (D, 3 * D),
              "bqkv": (3 * D,), "wproj": (D, D), "wff1": (D, 128),
              "bff1": (128,), "wff2": (128, D)}
    return GenerationModel(cfg, {
        n: np.zeros(shapes.get(n.split("/")[-1], (D,)), np.float32)
        for n in weight_names(cfg)})


@pytest.fixture(scope="module")
def pool_model():
    return _zero_model()


def _pool_step(model, kind, chip):
    """One serving step at a geometry the paged kernels qualify for,
    compiled for the v5e: (compiled, elements of one layer's pages,
    layers). The pool is sized so that a layer's pages (84 MB) dwarf
    everything a step has reason to allocate and every weight it may
    prefetch (`wqkv`, 50 MB); only its shape is made."""
    from paddle_tpu.serving import KVBlockPool

    g = POOL_STEP
    B, Mb, L = g["batch"], g["blocks_per_seq"], g["n_layers"]
    if kind == "chunk_tokens":
        # a context of 384 positions, so that the score tensor a dense
        # window would make, [B, C, H, T], is the size of nothing else
        Mb *= 3
    sharding = jax.sharding.SingleDeviceSharding(chip)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def like(a):
        return arg(a.shape, a.dtype)

    pool = like(jax.eval_shape(lambda: KVBlockPool(
        L, g["n_heads"], g["head_dim"], g["block_size"],
        g["num_blocks"]).k))
    row, on, tables = arg((B,)), arg((B,), jnp.bool_), arg((B, Mb))
    if kind == "decode":
        step = model.make_decode_step(B, Mb)
        # prompt_feed, use_prompt, prev, positions, tables, active
        feed = (row, on, row, row, tables, on)
    else:
        width, depth = g["tree"]
        C, step = {
            "chunk": (g["chunk"],
                      model.make_prefill_step(B, Mb, g["chunk"])),
            # the engine's promise: a token a row and one chunk of budget
            "chunk_tokens": (g["chunk"], model.make_prefill_step(
                B, Mb, g["chunk"], max_tokens=B + g["chunk"])),
            "spec": (g["spec_window"],
                     model.make_spec_step(B, Mb, g["spec_window"])),
            "tree": (1 + width * depth,
                     model.make_spec_tree_step(B, Mb, width, depth)),
        }[kind]
        # tokens, use_prompt, prev, positions, lengths, tables, active
        feed = (arg((B, C)), on, row, row, row, tables, on)
    with device.compiling_for(chip):
        compiled = step.lower(
            jax.tree_util.tree_map(like, model.weights), pool, pool,
            *feed).compile()
    return compiled, pool.size // L, L


def _large_results(hlo, at_least):
    """(opcode, dtype, elements) of every array an instruction of the
    ENTRY computation produces with `at_least` elements or more.
    Parameters, tuples and bitcasts move nothing and are left out."""
    import re

    found = []
    entry = hlo[hlo.index("\nENTRY "):]
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(", line)
        if not m or m.group(2) in ("parameter", "tuple", "bitcast",
                                   "get-tuple-element"):
            continue
        for dtype, dims in re.findall(r"\b([a-z]+\d+)\[([\d,]+)\]",
                                      m.group(1)):
            n = 1
            for d in dims.split(","):
                n *= int(d)
            if n >= at_least:
                found.append((m.group(2), dtype, n))
    return found


@pytest.mark.parametrize("kind", ["decode", "chunk", "chunk_tokens", "spec",
                                  "tree"])
def test_serving_step_reads_the_kv_pool_in_place(kind, pool_model,
                                                 v5e_chip):
    """No step that runs the paged kernel may copy a layer's pages out
    of the pool. Before PR 25 each of them did, twice for K and twice
    for V in every layer (`kv_k[i]` in front of a custom call: a
    `slice`, then a `reshape` to the kernel's block), 35 ms of a 56 ms
    decode step at the benchmark's size; the kernel now takes the pool
    whole. So the optimised HLO holds nothing of a layer's size but the
    in-place write of the new rows, and the step's temporaries stay
    under one layer's pages (the parent needed over three).

    The chunk step is a kernel step since PR 28. Until then its
    attention was the lax path over the `[B, C]` window: a fused
    slice-and-convert of the layer's pages for K and for V and a
    `[B, C, H, T]` score tensor in every layer, 537 MB of it at the
    benchmark's size, in a window 5 % full. Its attention now runs over
    query tiles in `paged_chunk_attention`, which leaves the pool in
    HBM and copies a tile's own pages itself. `chunk` is the step built
    without a promise (every slot a token row), `chunk_tokens` the
    engine's (`max_tokens`: the per-token work compacted to that many
    rows), which is also shown to make nothing of `B x C x H x T`
    elements."""
    compiled, layer, n_layers = _pool_step(pool_model, kind, v5e_chip)
    hlo = compiled.as_text()
    large = _large_results(hlo, layer)
    writes = [r for r in large if r[2] == layer * n_layers]
    others = [r for r in large if r[2] != layer * n_layers]
    assert writes and all(op in ("fusion", "scatter")
                          for op, _, _ in writes), large
    assert "tpu_custom_call" in hlo
    assert not others, others
    assert compiled.memory_analysis().temp_size_in_bytes < layer * 4
    if kind.startswith("chunk"):
        assert "paged_chunk_attention" in hlo
    if kind == "decode":
        # heads of 128: the kernel that walks each row's own pages
        assert "paged_decode_attention" in hlo
    if kind == "chunk_tokens":
        g = POOL_STEP
        scores = (g["batch"] * g["chunk"] * g["n_heads"]
                  * 3 * g["blocks_per_seq"] * g["block_size"])
        assert scores not in [n for _, _, n in _large_results(hlo, scores)]


def _dot_operand_dtypes(hlo):
    """{(lhs dtype, rhs dtype)} over a compiled module's convolutions
    and dots, as XLA reads their operands."""
    import re

    types = dict(re.findall(r"(%[\w.\-]+) = (\w+)\[", hlo))
    return {(types.get(a), types.get(b)) for a, b in re.findall(
        r" (?:convolution|dot)\((%[\w.\-]+), (%[\w.\-]+)\)", hlo)}


@pytest.mark.parametrize("kind", ["decode", "chunk_tokens"])
@pytest.mark.parametrize("built_for", ["v5e", "cpu"])
def test_xglm_step_multiplies_the_store_as_it_holds_it(kind, built_for,
                                                       v5e_chip):
    """PR 35. A model built FOR the v5e keeps its dot operands in
    bfloat16 (`serving.model.dot_operand_dtype`), and its steps hand
    the MXU that leaf beside activations rounded the same way: every
    product of the compiled step is bf16 x bf16 (with float32
    accumulation), none reads a float32 weight and none is the mixed
    f32 x bf16 dot that cost a chunk step 3 ms. A float32 store
    (built where the rule says float32, here the CPU) compiled for the
    same chip keeps its f32 x f32 dots: the steps adapt to what they
    are given (XLA may round their activations in a producer fusion, a
    float32 weight it reads as float32)."""
    if built_for == "v5e":
        with device.compiling_for(v5e_chip):
            model = _zero_model()
        want = "bf16"
    else:
        model = _zero_model()
        want = "f32"
    assert {str(model.weights[k].dtype) for k in ("lm_head", "l0/wqkv",
                                                  "l1/wff2")} \
        == {{"bf16": "bfloat16", "f32": "float32"}[want]}
    assert str(model.weights["embedding"].dtype) == "float32"
    compiled, layer, _n = _pool_step(model, kind, v5e_chip)
    dots = _dot_operand_dtypes(compiled.as_text())
    if want == "bf16":
        assert dots == {("bf16", "bf16")}
    else:
        assert dots and all("f32" in pair for pair in dots), dots
    assert compiled.memory_analysis().temp_size_in_bytes < layer * 4


def test_routed_chunk_step_compiles_at_the_served_geometry(v5e_chip):
    """ISSUE 40. The engine's ONE chunk program of `xglm-1.7b-serve` as
    it is built on the chip (16 rows of 128 blocks, a chunk of 256, 272
    token rows, 24 layers of 16 heads of 128, the pool of
    `engine.num_blocks`, the bf16 store; only shapes are made): every
    layer calls `paged_chunk_attention` over the 16 query tiles (a tile
    a row: no more than every slot's) AND `paged_decode_attention` over
    the same 16 for the one-token ones,
    the BlockSpec grid `paged_attention` is nowhere in it, it copies no
    layer's pages out of the pool, and its workspace stays under one
    layer's pages."""
    import json

    from paddle_tpu.serving import (GenerationConfig, GenerationModel,
                                    KVBlockPool)
    from paddle_tpu.serving.model import chunk_tile_count, leaf_shapes

    with open(os.path.join(os.path.dirname(chip_smoke.__file__),
                           "perfbench/configs/xglm-1.7b-serve.json")) as f:
        c = json.load(f)
    e = c["engine"]
    B, bs, C = e["max_batch"], e["block_size"], e["prefill_chunk"]
    Mb = e["max_seq_len"] // bs
    rows = B + default_prefill_token_budget(C)
    assert (B, Mb, C, rows, chunk_tile_count(B, C, rows)) \
        == (16, 128, 256, 272, 16)
    cfg = GenerationConfig(
        vocab_size=c["vocab_size"], d_model=c["d_model"],
        n_heads=c["attention_heads"], n_layers=c["num_layers"],
        d_ff=c["ffn_dim"], max_seq_len=e["max_seq_len"])
    sharding = jax.sharding.SingleDeviceSharding(v5e_chip)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)

    # a model of shapes (`GenerationModel.__init__` wants arrays), its
    # leaves as the store keeps them on the v5e
    model = GenerationModel.__new__(GenerationModel)
    model.config, model.name, model._steps = cfg, "shapes", {}
    model.weight_only_int8, model.trace_count = False, 0
    with device.compiling_for(v5e_chip):
        model.weights = {k: arg(s, d)
                         for k, (s, d) in leaf_shapes(cfg).items()}
    pool = jax.eval_shape(lambda: KVBlockPool(
        cfg.n_layers, cfg.n_heads, cfg.head_dim, bs, e["num_blocks"]).k)
    pool = arg(pool.shape, pool.dtype)
    row, on = arg((B,)), arg((B,), jnp.bool_)
    step = model.make_prefill_step(B, Mb, C, max_tokens=rows)
    with device.compiling_for(v5e_chip):
        compiled = step.lower(model.weights, pool, pool, arg((B, C)), on,
                              row, row, row, arg((B, Mb)), on).compile()
    hlo = compiled.as_text()
    import re

    for kernel in ("paged_chunk_attention", "paged_decode_attention"):
        assert len(re.findall(r"%%%s[.\d]* = " % kernel, hlo)) \
            == cfg.n_layers
    assert not re.findall(r"%paged_attention[.\d]* = ", hlo)
    layer = pool.size // cfg.n_layers
    large = _large_results(hlo, layer)
    assert all(n == pool.size for _, _, n in large), large
    assert compiled.memory_analysis().temp_size_in_bytes < layer * 4


# ---------------------------------------------------------------------------
# the latent block's steps update and read the bf16 latent pool in place
# (PR 27)
# ---------------------------------------------------------------------------

LATENT_STEP = dict(batch=8, blocks_per_seq=16, block_size=16, num_blocks=4096,
                   chunk=16)


@pytest.mark.parametrize("kind", ["decode", "chunk"])
def test_latent_step_updates_the_bf16_pool_in_place(kind, v5e_chip):
    """The latent pool is bfloat16, two tokens to a sublane, and XLA's
    own scatter into it re-laid the WHOLE pool out to update a row and
    copied it back for the attention kernel (18.25 GB asked of a 15.75
    GB chip at the benchmark's size); a pool whose rows are 576 lanes
    wide is laid out by the runtime with the blocks minor-most, and every
    step copied it into the kernels' layout and back. So the rows are
    written by `latent_write` (whole pages, in place), the entry is
    stated in whole 128-lane tiles, and the compiled steps hold nothing
    of the pool's size but the kernels' own results."""
    from paddle_tpu.serving import (GenerationConfig, GenerationModel,
                                    KVBlockPool, latent_moe)

    g = LATENT_STEP
    block = dict(chip_smoke.FULL.latent["block"], n_routed_experts=8)
    cfg = GenerationConfig(
        **dict(chip_smoke.FULL.latent, vocab_size=1024, block=block),
        max_seq_len=g["blocks_per_seq"] * g["block_size"])
    assert cfg.block.cache_width == 576 and cfg.block.cache_row == 640
    model = GenerationModel.__new__(GenerationModel)
    model.config, model.trace_count = cfg, 0
    sharding = jax.sharding.SingleDeviceSharding(v5e_chip)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=sharding)

    weights = {k: arg(s, d) for k, (s, d) in
               latent_moe.leaf_shapes(cfg).items()}
    B, Mb = g["batch"], g["blocks_per_seq"]
    (latent,) = jax.eval_shape(lambda: KVBlockPool(
        cfg.n_layers, cfg.n_heads, cfg.head_dim, g["block_size"],
        g["num_blocks"], entry=cfg.block.cache_entry()).arrays)
    assert latent.dtype == jnp.bfloat16 and latent.shape[-1] == 640
    pool = arg(latent.shape, latent.dtype)
    row, on, tables = arg((B,)), arg((B,), jnp.bool_), arg((B, Mb))
    with device.compiling_for(v5e_chip):
        if kind == "decode":
            compiled = latent_moe.make_decode_step(model).lower(
                weights, pool, row, on, row, row, tables, on).compile()
        else:
            compiled = latent_moe.make_window_step(
                model, g["chunk"],
                max_tokens=B + default_prefill_token_budget(
                    g["chunk"])).lower(
                weights, pool, arg((B, g["chunk"])), on, row, row, row,
                tables, on).compile()
    hlo = compiled.as_text()
    for name in ("gmm", "latent_paged_attention", "latent_write"):
        assert name in hlo, name
    large = _large_results(hlo, latent.size)
    assert large and all(op == "custom-call" for op, _, _ in large), large
    # workspace: far under the pool (84 MB here), whatever the step holds
    assert compiled.memory_analysis().temp_size_in_bytes < latent.size, \
        compiled.memory_analysis()


KANANA_POOL, LING_POOL = (8, 20481, 16, 640), (1, 26001, 64, 640)


@pytest.mark.parametrize("rows,window,pool,table_len", [
    (128, 1, KANANA_POOL, 160), (128, 16, KANANA_POOL, 160),
    (256, 1, LING_POOL, 640), (320, 16, LING_POOL, 640)],
    ids=["kanana-decode", "kanana-window", "ling-decode", "ling-tiles"])
def test_latent_attention_hands_its_pipe_over_at_the_cells_shapes(
        rows, window, pool, table_len, v5e_chip):
    """`latent_paged_attention` as the two latent cells' steps call it
    (kanana: 128 rows of one token or a window of 16 over pages of
    `[16, 640]`; ling: 256 rows of one token or 320 query tiles of 16
    over pages of `[64, 640]`; 32 heads, the value 512 wide), with what
    the hand-over adds: the fifth prefetched scalar (each row's next
    live row) and the SMEM words that hand the buffer half over."""
    bf16, i32 = jnp.bfloat16, jnp.int32
    specs = [(pool, bf16), ((rows, window, 32, 640), bf16),
             ((rows, table_len), i32), ((rows,), i32), ((rows,), i32)]

    def call(*args):
        return pk.latent_paged_attention(*args, layer=0, v_width=512)

    with device.compiling_for(v5e_chip):
        lowered = jax.jit(call).trace(
            *[jax.ShapeDtypeStruct(s, d) for s, d in specs]).lower(
                lowering_platforms=("tpu",)).as_text()
    # the next live rows are made in front of the kernel, and go in
    # with the tables, positions, lengths and the layer
    assert "tpu_custom_call" in lowered and "cummin" in lowered
    hlo = _compile(call, specs, v5e_chip).as_text()
    assert "latent_paged_attention" in hlo
    # a run is 2.5 MiB: 128 of kanana's pages of 20 KB, 32 of ling's
    assert pk.latent_pages_per_run(
        jax.ShapeDtypeStruct(pool, bf16), table_len) == (
            128 if pool is KANANA_POOL else 32)


# ---------------------------------------------------------------------------
# the grouped-query window/global block (PR 37): its kernels at the served
# geometry, and its steps over two kinds of packed bf16 page
# ---------------------------------------------------------------------------

def _trinity():
    import json

    with open(os.path.join(
            os.path.dirname(chip_smoke.__file__),
            "perfbench/configs/trinity-large-preview-serve.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("kernel", ["gqa_decode", "gqa_chunk",
                                    "kv_page_write"])
def test_gqa_kernels_compile_at_the_served_geometry(kernel, v5e_chip):
    """The three kernels as `trinity-large-preview-serve` calls them on
    its window pool: 4 layers of 3,888 pages `[64, 1024]` bf16 (8 cache
    heads of 128 side by side), 48 query heads, 48 rows of 544 table
    slots, a mixed step's 56 tiles of 128 tokens and 112 page units; the
    layer and the window traced scalars."""
    c = _trinity()
    e = c["engine"]
    H, Hkv, Dh = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    bs, B = e["block_size"], e["max_batch"]
    Mb = e["max_seq_len"] // bs
    assert (H, Hkv, Dh, bs, B, Mb) == (48, 8, 128, 64, 48, 544)
    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    pool = ((4, e["window_blocks"] + 1, bs, Hkv * Dh), bf)
    spec = registered_kernels()[kernel]
    assert spec.qualify(head_dim=Dh, block_size=bs)[0]
    if kernel == "gqa_decode":
        specs = [pool, pool, ((B, H, Dh), f32), ((B, Mb), i32), ((B,), i32),
                 ((), i32), ((), i32), ((B,), jnp.bool_)]
        fn = lambda k, v, q, t, p, layer, w, on: spec.pallas(  # noqa: E731
            k, v, q, t, p, layer=layer, window=w, active=on)
    elif kernel == "gqa_chunk":
        N = 56
        specs = [pool, pool, ((N, 128, H, Dh), f32), ((N, Mb), i32),
                 ((N,), i32), ((N,), i32), ((), i32), ((), i32)]
        fn = lambda k, v, q, t, p, n, layer, w: spec.pallas(  # noqa: E731
            k, v, q, t, p, n, layer=layer, window=w)
    else:
        U = 112
        rows = ((U, bs, Hkv * Dh), bf)
        specs = [pool, pool, rows, rows, ((U,), i32), ((U,), i32),
                 ((U,), i32), ((), i32)]
        fn = lambda k, v, kr, vr, ids, lo, hi, layer: spec.pallas(  # noqa: E731,E501
            k, v, kr, vr, ids, lo, hi, layer=layer)
    compiled = _compile(fn, specs, v5e_chip)
    assert spec.pallas.__name__ in compiled.as_text()
    # the pools stay where they are: nothing of a pool's size is made
    pool_bytes = 4 * (e["window_blocks"] + 1) * bs * Hkv * Dh * 2
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes // 8


# pools larger than the chip's 128 MiB of VMEM: the compiler prefetches
# an argument that fits there, and that copy is not the one looked for
AFMOE_STEP = dict(batch=8, blocks_per_seq=16, block_size=64, chunk=256,
                  global_blocks=2048, window_blocks=1024)


@pytest.mark.parametrize("kind", ["decode", "chunk"])
def test_afmoe_step_updates_both_kinds_of_page_in_place(kind, v5e_chip):
    """The third block's steps at its published widths (a dense window
    layer, a global and a window expert layer, 8 experts held): each
    kind's K and V pools are packed bf16 and are rewritten by
    `kv_page_write` in place and read by the grouped-query kernels from
    HBM; the compiled steps hold nothing of a pool's size but the
    kernels' own (aliased) results."""
    from paddle_tpu.serving import (GenerationConfig, GenerationModel,
                                    KVBlockPool)

    g = AFMOE_STEP
    cfg = GenerationConfig(
        **dict(chip_smoke.FULL.afmoe, vocab_size=1024),
        max_seq_len=g["blocks_per_seq"] * g["block_size"])
    model = GenerationModel.__new__(GenerationModel)
    model.config, model.trace_count = cfg, 0
    kinds = model.page_kinds()
    assert [(k.name, k.layers) for k in kinds] == [("global", (1,)),
                                                   ("window", (0, 2))]
    sharding = jax.sharding.SingleDeviceSharding(v5e_chip)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=sharding)

    weights = {k: arg(s, d) for k, (s, d) in
               cfg.block.leaf_shapes(cfg).items()}
    B, Mb = g["batch"], g["blocks_per_seq"]
    arrays = jax.eval_shape(lambda: KVBlockPool(
        cfg.n_layers, cfg.n_heads, cfg.head_dim, g["block_size"],
        [g["global_blocks"], g["window_blocks"]],
        entry=cfg.block.cache_entry(), kinds=kinds).arrays)
    assert [a.shape for a in arrays] == [(1, 2049, 64, 1024)] * 2 \
        + [(2, 1025, 64, 1024)] * 2
    assert all(a.dtype == jnp.bfloat16 for a in arrays)
    pools = tuple(arg(a.shape, a.dtype) for a in arrays)
    row, on = arg((B,)), arg((B,), jnp.bool_)
    tables = arg((2, B, Mb))
    with device.compiling_for(v5e_chip):
        if kind == "decode":
            compiled = cfg.block.make_decode_step(model).lower(
                weights, *pools, row, on, row, row, tables, on).compile()
        else:
            compiled = cfg.block.make_window_step(
                model, g["chunk"], max_tokens=B + g["chunk"]).lower(
                weights, *pools, arg((B, g["chunk"])), on, row, row, row,
                tables, on).compile()
    hlo = compiled.as_text()
    names = ["gmm", "kv_page_write", "gqa_paged_decode_attention"
             if kind == "decode" else "gqa_paged_chunk_attention"]
    for name in names:
        assert name in hlo, name
    smallest = min(a.size for a in arrays)
    large = _large_results(hlo, smallest)
    assert large and all(op == "custom-call" for op, _, _ in large), large
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * smallest, \
        compiled.memory_analysis()


# the fourth block: one kind of page and a row state beside it
ZAYA_STEP = dict(batch=8, blocks_per_seq=16, block_size=64, chunk=256,
                 blocks=2048)


@pytest.mark.parametrize("kind", ["decode", "chunk"])
def test_zaya_step_updates_its_pages_and_its_carry_in_place(kind, v5e_chip):
    """The fourth block's steps at its published widths (two layers, all
    16 experts of each, heads of 128 on 2 cache heads: pages 256 lanes
    wide, 4 query heads a group): the grouped-query kernels and
    `kv_page_write` qualify as they are, K, V and the row state are
    donated and rewritten in place, and the compiled steps hold nothing
    of a pool's size but the kernels' own (aliased) results."""
    from paddle_tpu.serving import (GenerationConfig, GenerationModel,
                                    KVBlockPool)

    g = ZAYA_STEP
    cfg = GenerationConfig(
        **dict(chip_smoke.FULL.zaya, vocab_size=1024),
        max_seq_len=g["blocks_per_seq"] * g["block_size"])
    model = GenerationModel.__new__(GenerationModel)
    model.config, model.trace_count = cfg, 0
    kinds = model.page_kinds()
    assert [(k.name, k.window, k.layers) for k in kinds] \
        == [("global", None, (0, 1))]
    sharding = jax.sharding.SingleDeviceSharding(v5e_chip)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=sharding)

    weights = {k: arg(s, d) for k, (s, d) in
               cfg.block.leaf_shapes(cfg).items()}
    B, Mb = g["batch"], g["blocks_per_seq"]
    arrays = jax.eval_shape(lambda: KVBlockPool(
        cfg.n_layers, cfg.n_heads, cfg.head_dim, g["block_size"],
        g["blocks"], entry=cfg.block.cache_entry(), kinds=kinds).arrays)
    assert [a.shape for a in arrays] == [(2, 2049, 64, 256)] * 2
    assert all(a.dtype == jnp.bfloat16 for a in arrays)
    shape, dtype = model.row_state()
    assert shape == (2, 2 * 1280 + 128) and dtype == "float32"
    state = arg((B,) + shape, dtype)
    pools = tuple(arg(a.shape, a.dtype) for a in arrays) + (state,)
    row, on = arg((B,)), arg((B,), jnp.bool_)
    tables = arg((B, Mb))
    with device.compiling_for(v5e_chip):
        if kind == "decode":
            compiled = cfg.block.make_decode_step(model).lower(
                weights, *pools, row, on, row, row, tables, on).compile()
        else:
            compiled = cfg.block.make_window_step(
                model, g["chunk"], max_tokens=B + g["chunk"]).lower(
                weights, *pools, arg((B, g["chunk"])), on, row, row, row,
                tables, on).compile()
    hlo = compiled.as_text()
    names = ["gmm", "kv_page_write", "gqa_paged_decode_attention"
             if kind == "decode" else "gqa_paged_chunk_attention"]
    for name in names:
        assert name in hlo, name
    smallest = min(a.size for a in arrays)
    large = _large_results(hlo, smallest)
    assert large and all(op == "custom-call" for op, _, _ in large), large
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < 2 * smallest, m
    # the pools and the carry come back in the arguments' own buffers
    donated = sum(a.size * 2 for a in arrays) + B * shape[0] * shape[1] * 4
    assert m.alias_size_in_bytes >= donated, m


# the fifth block: the MLA layers' latent pages and a row state of two
# parts beside them; a KDA dense layer, an MLA and a KDA expert layer
LING_STEP = dict(batch=8, blocks_per_seq=32, block_size=64, chunk=256,
                 blocks=2048)
LING = dict(vocab_size=1024, d_model=2560, n_heads=32, n_layers=3,
            d_ff=6144, block=dict(
                kind="ling", head_dim=128,
                layer_types=["kda", "mla", "kda"], conv_kernel=4,
                kda_lower_bound=-5.0, qk_nope_head_dim=128,
                qk_rope_head_dim=64, v_head_dim=128, kv_lora_rank=512,
                rope_theta=6e6, first_k_dense=1, n_routed_experts=512,
                experts_per_token=8, n_shared_experts=1, moe_d_ff=768,
                routed_scaling_factor=2.5, n_group=8, topk_group=4,
                experts_held=list(range(8))))


@pytest.mark.parametrize("kind", ["decode", "chunk"])
def test_ling_step_updates_its_pages_and_its_scan_state_in_place(
        kind, v5e_chip):
    """The fifth block's steps at its published widths (32 scan heads of
    128 x 128 float32, a latent row of 576 values, 8 of 512 experts in 8
    groups held): the two scan kernels and the latent block's three
    qualify as they are; the latent pool and both parts of the row state
    are donated and rewritten in place, and the compiled steps hold
    nothing of the pool's or the scan state's size but the kernels' own
    (aliased) results."""
    from paddle_tpu.serving import (GenerationConfig, GenerationModel,
                                    KVBlockPool, RowState)

    g = LING_STEP
    cfg = GenerationConfig(
        **LING, max_seq_len=g["blocks_per_seq"] * g["block_size"])
    model = GenerationModel.__new__(GenerationModel)
    model.config, model.trace_count = cfg, 0
    kinds = model.page_kinds()
    assert [(k.name, k.window, k.layers) for k in kinds] \
        == [("global", None, (1,))]
    sharding = jax.sharding.SingleDeviceSharding(v5e_chip)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=sharding)

    weights = {k: arg(s, d) for k, (s, d) in
               cfg.block.leaf_shapes(cfg).items()}
    B, Mb = g["batch"], g["blocks_per_seq"]
    arrays = jax.eval_shape(lambda: KVBlockPool(
        cfg.n_layers, cfg.n_heads, cfg.head_dim, g["block_size"],
        g["blocks"], entry=cfg.block.cache_entry(), kinds=kinds,
        row_state=RowState(B, *model.row_state())).step_arrays)
    assert [(a.shape, str(a.dtype)) for a in arrays] == [
        ((1, 2049, 64, 640), "bfloat16"),
        ((B, 2, 32, 128, 128), "float32"),
        ((B, 2 * 3 * 3 * 32 * 128), "bfloat16")]
    pools = tuple(arg(a.shape, a.dtype) for a in arrays)
    row, on = arg((B,)), arg((B,), jnp.bool_)
    tables = arg((B, Mb))
    with device.compiling_for(v5e_chip):
        if kind == "decode":
            compiled = cfg.block.make_decode_step(model).lower(
                weights, *pools, row, on, row, row, tables, on).compile()
        else:
            compiled = cfg.block.make_window_step(
                model, g["chunk"], max_tokens=B + g["chunk"]).lower(
                weights, *pools, arg((B, g["chunk"])), on, row, row, row,
                tables, on).compile()
    hlo = compiled.as_text()
    names = ["gmm", "latent_write", "latent_paged_attention", "kda_decode"]
    for name in names + ["kda_chunk"] * (kind == "chunk"):
        assert name in hlo, name
    # (a weight is as large as these few rows' scan state, and the
    # compiler stages weights through VMEM: only results of exactly the
    # pool's or the scan state's size are looked at)
    sizes = {a.size for a in arrays[:2]}
    large = [r for r in _large_results(hlo, min(sizes)) if r[2] in sizes]
    assert large and all(op == "custom-call" for op, _, _ in large), large
    m = compiled.memory_analysis()
    # the pool and the row state come back in the arguments' own buffers
    donated = sum(a.size * a.dtype.itemsize for a in arrays)
    assert m.alias_size_in_bytes >= donated, m
