"""Did a refactor change a compiled serving step? Answered without a chip.

    python tools/lowered_serving_steps.py write <checkout> <out-dir> [--compile]
                                          [--only=<configuration> ...]
    python tools/lowered_serving_steps.py compare <out-dir-a> <out-dir-b>

``write`` imports ``paddle_tpu`` from ``<checkout>`` (this tree or a
``git archive`` of another commit), builds the decode and chunk steps of
the serving configurations of the benchmark (XGLM, kanana and, where the
checkout has them, trinity with its two kinds of page, zaya with its
row state and ling with its latent pages beside a row state of two
parts) at their engines' geometry
(``perfbench/configs/*-serve.json``; only shapes are made, no weights),
lowers them for a described ``v5e:2x2`` device and writes the StableHLO
text with debug locations stripped, and prints each step's dots counted
by operand dtypes (``dots``: XGLM's read ``bf16 x bf16``, activations
rounded as the dot rounds them against a weight the store keeps in
bfloat16 for the v5e, with no convert between the step's weight
argument and the dot). ``--compile``
(``--only=ling``: that configuration's two steps alone, which is how
an engine is sized) also compiles each for the v5e and prints its
argument, output and workspace bytes and the same count over the compiled program's
convolutions (``compiled_dots``: the dtypes XLA really reads; a bf16
weight there and a workspace smaller than a weight say no float32 copy
of a weight is written to HBM).

``compare`` says whether two such directories hold the same programs. A
Mosaic kernel's body rides in its custom call as base64 MLIR bytecode,
the Python call stack of its trace included, so each body is parsed and
reprinted without locations before the lines are compared, first in
order and then sorted (set-up lines ahead of the layer loop may move).
Of two steps that differ it names the functions they do not share (by
name and body, the counters of private names taken off), so that "only
the decode kernel's call changed" can be read off.

Keep JAX_PLATFORMS=cpu set: nothing here runs, and nothing it prints is
a device number.
"""

import base64
import collections
import glob
import hashlib
import json
import os
import re
import sys


def lowered_dots(text):
    """{"lhs x rhs": count} over a StableHLO module's dot_generals, by
    the operands' element types."""
    return dict(collections.Counter(
        "%s x %s" % pair for pair in re.findall(
            r"stablehlo\.dot_general .*: \(tensor<(?:\d+x)*(\w+)>, "
            r"tensor<(?:\d+x)*(\w+)>\)", text)))


def compiled_dots(text):
    """The same count over a compiled module's convolutions and dots,
    by the element types of the operands as XLA reads them."""
    types = dict(re.findall(r"(%[\w.\-]+) = (\w+)\[", text))
    dots = collections.Counter()
    for a, b in re.findall(
            r" (?:convolution|dot)\((%[\w.\-]+), (%[\w.\-]+)\)", text):
        dots["%s x %s" % (types.get(a, "?"), types.get(b, "?"))] += 1
    return dict(dots)


def write(root, out, compile_too, only=None):
    root = os.path.realpath(root)
    sys.path.insert(0, root)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    os.makedirs(out, exist_ok=True)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    import paddle_tpu
    assert os.path.realpath(paddle_tpu.__file__).startswith(root), \
        "paddle_tpu came from %s, not %s" % (paddle_tpu.__file__, root)
    from paddle_tpu.core import device
    from paddle_tpu.serving import (GenerationConfig, GenerationModel,
                                    KVBlockPool, latent_moe)
    from paddle_tpu.serving.model import weight_names
    from perfbench.runners import serve_latent
    try:
        from paddle_tpu.serving.scheduler import \
            default_prefill_token_budget
    except ImportError:   # a checkout whose unstated budget is 4 chunks
        def default_prefill_token_budget(chunk):
            return 4 * chunk

    chip = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0]
    sharding = jax.sharding.SingleDeviceSharding(chip)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)

    def model_of(cfg, shapes):
        # a model of shapes: `GenerationModel.__init__` wants arrays
        model = GenerationModel.__new__(GenerationModel)
        model.config, model.name, model._steps = cfg, "shapes", {}
        model.weights = {k: arg(s, d) for k, (s, d) in shapes.items()}
        model.weight_only_int8, model.trace_count = False, 0
        return model

    def emit(name, step, args):
        with device.compiling_for(chip):
            lowered = step.lower(*args)
            text = re.sub(r"\s*loc\(.*?\)$", "", lowered.as_text(),
                          flags=re.M)
            text = "\n".join(line for line in text.splitlines()
                             if not line.lstrip().startswith("#loc"))
            with open(os.path.join(out, name + ".mlir"), "w") as f:
                f.write(text + "\n")
            info = {"step": name, "lines": text.count("\n") + 1,
                    "dots": lowered_dots(text)}
            if compile_too:
                compiled = lowered.compile()
                m = compiled.memory_analysis()
                info.update(argument_bytes=m.argument_size_in_bytes,
                            output_bytes=m.output_size_in_bytes,
                            workspace_bytes=m.temp_size_in_bytes,
                            bytes_accessed=(compiled.cost_analysis()
                                            or {}).get("bytes accessed"),
                            compiled_dots=compiled_dots(
                                compiled.as_text()))
        print(json.dumps(info), flush=True)

    def both_steps(name, model, e, kinds=None, num_blocks=None):
        if only and name not in only:
            return
        cfg = model.config
        B, bs, C = e["max_batch"], e["block_size"], e["prefill_chunk"]
        Mb = -(-e["max_seq_len"] // bs)
        more = {"kinds": kinds} if kinds else {}
        pool = tuple(arg(a.shape, a.dtype) for a in jax.eval_shape(
            lambda: KVBlockPool(cfg.n_layers, cfg.n_heads, cfg.head_dim,
                                bs, num_blocks or e["num_blocks"],
                                entry=model.cache_entry(), **more).arrays))
        # a block with a row state hands it over after the pool's arrays
        # (one array, or one a named part)
        state = getattr(model, "row_state", lambda: None)()
        if state is not None:
            parts = state[0] if not isinstance(state[0][0], int) \
                else (("state",) + tuple(state),)
            pool += tuple(arg((B,) + tuple(s), d) for _n, s, d in parts)
        row, on = arg((B,)), arg((B,), jnp.bool_)
        # one block table, or the stack of them, a table a page kind
        tables = arg((len(kinds), B, Mb) if kinds and len(kinds) > 1
                     else (B, Mb))
        budget = e.get("prefill_token_budget",
                       default_prefill_token_budget(C))
        # the engine's calls: prompt_feed, use_prompt, prev_tokens,
        # positions, (lengths,) block_tables, active; its promise of
        # max_batch + the scheduler's prefill budget of token rows
        emit(name + "_decode_step", model.make_decode_step(B, Mb),
             (model.weights,) + pool + (row, on, row, row, tables, on))
        emit(name + "_chunk_step",
             model.make_prefill_step(B, Mb, C, max_tokens=B + budget),
             (model.weights,) + pool
             + (arg((B, C)), on, row, row, row, tables, on))

    def config(name):
        with open(os.path.join(root, "perfbench/configs", name)) as f:
            return json.load(f)

    c = config("xglm-1.7b-serve.json")
    cfg = GenerationConfig(
        vocab_size=c["vocab_size"], d_model=c["d_model"],
        n_heads=c["attention_heads"], n_layers=c["num_layers"],
        d_ff=c["ffn_dim"], max_seq_len=c["engine"]["max_seq_len"])
    try:
        # the leaves as the store keeps them on the v5e
        from paddle_tpu.serving.model import leaf_shapes

        with device.compiling_for(chip):
            shapes = leaf_shapes(cfg)
    except ImportError:   # a checkout from before the store stated them
        D, F, V = cfg.d_model, cfg.d_ff, cfg.vocab_size
        by_leaf = {"embedding": (V, D), "lm_head": (D, V),
                   "wqkv": (D, 3 * D), "bqkv": (3 * D,), "wproj": (D, D),
                   "wff1": (D, F), "bff1": (F,), "wff2": (F, D)}
        shapes = {n: (by_leaf.get(n.split("/")[-1], (D,)), jnp.float32)
                  for n in weight_names(cfg)}
    both_steps("xglm", model_of(cfg, shapes), c["engine"])

    c = config("kanana-2-30b-a3b-serve.json")
    cfg = serve_latent.generation_config(c, c["engine"]["max_seq_len"])
    both_steps("kanana", model_of(cfg, latent_moe.leaf_shapes(cfg)),
               c["engine"])

    if not os.path.exists(os.path.join(
            root, "perfbench/configs/trinity-large-preview-serve.json")):
        return             # a checkout from before the third block
    from perfbench.runners import serve_window

    c = config("trinity-large-preview-serve.json")
    cfg = serve_window.generation_config(c, c["engine"]["max_seq_len"])
    model = model_of(cfg, cfg.block.leaf_shapes(cfg))
    both_steps("trinity", model, c["engine"], kinds=model.page_kinds(),
               num_blocks={"global": c["engine"]["global_blocks"],
                           "window": c["engine"]["window_blocks"]})

    if not os.path.exists(os.path.join(
            root, "perfbench/configs/zaya1-8b-serve.json")):
        return             # a checkout from before the fourth block
    from perfbench.runners import serve_zaya

    c = config("zaya1-8b-serve.json")
    cfg = serve_zaya.generation_config(c, c["engine"]["max_seq_len"])
    model = model_of(cfg, cfg.block.leaf_shapes(cfg))
    both_steps("zaya", model, c["engine"], kinds=model.page_kinds())

    if not os.path.exists(os.path.join(
            root, "perfbench/configs/ling-3.0-flash-serve.json")):
        return             # a checkout from before the fifth block
    from perfbench.runners import serve_ling

    c = config("ling-3.0-flash-serve.json")
    cfg = serve_ling.generation_config(c, c["engine"]["max_seq_len"])
    model = model_of(cfg, cfg.block.leaf_shapes(cfg))
    both_steps("ling", model, c["engine"], kinds=model.page_kinds(),
               num_blocks=c["engine"]["latent_blocks"])


def compare(dir_a, dir_b):
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    bodies = {}

    def kernel(match):
        b64 = match.group(1)
        if b64 not in bodies:
            ctx = ir.Context()
            ctx.allow_unregistered_dialects = True   # `stable_mosaic`
            tpu.register_dialect(ctx)
            with ctx:
                text = ir.Module.parse(base64.b64decode(b64)) \
                    .operation.get_asm(enable_debug_info=False)
            bodies[b64] = "<kernel %s, %d lines>" % (
                hashlib.sha256(text.encode()).hexdigest()[:16],
                text.count("\n"))
        return '\\22body\\22: \\22%s\\22' % bodies[b64]

    def lines(path):
        with open(path) as f:
            return [re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22',
                           kernel, line) for line in f]

    names = sorted(os.path.basename(p)
                   for p in glob.glob(os.path.join(dir_a, "*.mlir")))
    different = not names
    for name in names:
        a = lines(os.path.join(dir_a, name))
        b = lines(os.path.join(dir_b, name))
        verdict = ("identical" if a == b else
                   "identical once sorted" if sorted(a) == sorted(b)
                   else "DIFFERENT")
        print("%-24s %6d / %6d lines: %s" % (name[:-5], len(a), len(b),
                                             verdict))
        different |= verdict == "DIFFERENT"
        if verdict == "DIFFERENT":
            in_a, in_b = functions(a), functions(b)
            for side, fns in (("a", in_a - in_b), ("b", in_b - in_a)):
                print("    only in %s: %s" % (side, ", ".join(
                    "%s[%s] x%d (%d lines)" % (
                        fn[0], " ".join(re.findall(
                            r'kernel_name = "(\w+)"', fn[1])),
                        n, fn[1].count("\n"))
                    for fn, n in sorted(fns.items())) or "nothing"))
    return 1 if different else 0


def functions(lines):
    """A lowered module's functions as a multiset of (name, body), the
    counters jax appends to private names (``@_where_228``) taken off
    both: a function added anywhere renumbers every later one, and what
    a reader wants to know is WHICH functions two programs do not
    share."""
    text = re.sub(r"@([A-Za-z_][\w.]*?)_\d+\b", r"@\1", "".join(lines))
    found = collections.Counter()
    for body in re.split(r"\n(?=  func\.func )", text):
        name = re.match(r"\s*func\.func (?:\w+ )?@([\w.]+)", body)
        if name:
            found[(name.group(1), body)] += 1
    return found


if __name__ == "__main__":
    if len(sys.argv) >= 4 and sys.argv[1] == "write":
        write(sys.argv[2], sys.argv[3], "--compile" in sys.argv[4:],
              [a[7:] for a in sys.argv[4:] if a.startswith("--only=")])
    elif len(sys.argv) == 4 and sys.argv[1] == "compare":
        sys.exit(compare(sys.argv[2], sys.argv[3]))
    else:
        sys.exit(__doc__)
