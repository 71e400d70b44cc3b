"""Runner ``train``: a configuration trained through the normal path.

``transformer_fluid.build`` -> ``contrib.mixed_precision.decorate(Adam)``
-> ``Executor.run``, fed by a background reader. Set-up builds ONE
object (the program with its state in a scope), gives it the seeded
weights, drives it through its first three steps by the same call and
feed as the window, and hands that same object to the window.

``correct`` (builder's contract, training): the plain reference follows
the first three steps on the same batches, after the program's state is
freed. Compared, each with its own limit from the configuration file:

* each step's loss (gap to the reference's, absolute);
* the first gradient as the optimizer got it, per leaf: Adam's first
  moment after one step is ``(1 - beta1) * g``;
* each leaf's change after the three steps;

both norms by the worst leaf: the gap between the program's norm and
the reference's, over the reference's norm of that leaf or of the
median leaf, whichever is larger. In the window the losses are finite
and nothing compiles.
"""

import gc
import queue
import threading
import time

import numpy as np

from perfbench import loadgen, spec
from perfbench.runners import check, counter_value, memory_peak_bytes
from perfbench.trace_reduce import span

CHECK_STEPS = 3


def build_program(fluid, config, t):
    from paddle_tpu.models import transformer_fluid

    prog, sprog = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, sprog):
        _tok, _lab, loss = transformer_fluid.build(
            vocab_size=config["vocab_size"], d_model=config["d_model"],
            n_heads=config["attention_heads"],
            n_layers=config["num_layers"], d_ff=config["ffn_dim"],
            seq_len=t["seq_len"], remat=t["remat"],
            dtype=t["param_dtype"],
            head_chunk=t["head_chunk"])
        opt = fluid.contrib.mixed_precision.decorate(
            fluid.optimizer.Adam(t["lr"], beta1=t["beta1"],
                                 beta2=t["beta2"], epsilon=t["epsilon"]),
            init_loss_scaling=1.0, use_dynamic_loss_scaling=False,
            use_bf16=True)
        opt.minimize(loss)
    return prog, sprog, loss


def leaf_map(prog, ref, config):
    """[(program parameter name, reference leaf, layer or None)] in the
    program's own order: embedding, then per layer LN, q/k/v/out, LN,
    the two feed-forward matrices, then the final LN and the head."""
    per_layer = ref.LAYER_LEAVES
    want = [("embed", None)]
    for i in range(config["num_layers"]):
        want += [(k, i) for k in per_layer]
    want += [("lnf_g", None), ("lnf_b", None), ("head", None)]
    params = list(prog.all_parameters())
    if len(params) != len(want):
        raise RuntimeError("the program has %d parameters, the %s block "
                           "has %d" % (len(params), config["family"],
                                       len(want)))
    shapes = ref.leaf_shapes(config)
    out = []
    for p, (leaf, layer) in zip(params, want):
        shape = shapes[leaf][1:] if layer is not None else shapes[leaf]
        if int(np.prod(p.shape)) != int(np.prod(shape)):
            raise RuntimeError("parameter %s %r does not hold leaf %s %r"
                               % (p.name, tuple(p.shape), leaf, shape))
        out.append((p.name, leaf, layer, tuple(int(d) for d in p.shape)))
    return out


def seeded_weights(ref, config, seed, leaves, dtype):
    """{program parameter: array} from the reference's own
    ``init_params``, in the program's shapes: one jitted call."""
    import jax

    def make(words):
        params = ref.init_params(words, config)
        return {name: (params[leaf] if layer is None
                       else params[leaf][layer]).reshape(shape)
                .astype(dtype)
                for name, leaf, layer, shape in leaves}

    return jax.jit(make)(ref.seed_words(seed))


def first_moments(prog):
    """{parameter: name of its Adam first moment}, from the program's
    own ``adam`` ops."""
    return {op.inputs["Param"][0].name: op.inputs["Moment1"][0].name
            for op in prog.global_block().ops if op.type == "adam"}


def program_norms(scope, leaves, names, scale=1.0):
    """{(leaf, layer): norm} of the scope's arrays ``names[parameter]``,
    computed on the device."""
    import jax
    import jax.numpy as jnp

    arrays = {name: scope.get(names[name]) for name, *_ in leaves}
    norms = jax.jit(lambda t: {
        k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
        for k, v in t.items()})(arrays)
    return {(leaf, layer): float(norms[name]) * scale
            for name, leaf, layer, _ in leaves}


def program_change_norms(scope, ref, config, seed, leaves):
    """{(leaf, layer): ||p - p0||}: each leaf's seeded start is made
    again on the device, one reference leaf at a time."""
    import jax
    import jax.numpy as jnp

    shapes = ref.leaf_shapes(config)
    by_leaf = {}
    for name, leaf, layer, _ in leaves:
        by_leaf.setdefault(leaf, []).append((layer, name))
    out = {}
    for leaf, members in by_leaf.items():
        shape = shapes[leaf]

        def change(arrays, words, shape=shape, leaf=leaf,
                   stacked=members[0][0] is not None):
            p0 = ref.init_leaf(words, leaf, shape)
            if stacked:
                p = jnp.stack([a.reshape(shape[1:]) for a in arrays])
                axes = tuple(range(1, len(shape)))
            else:
                p = arrays[0].reshape(shape)[None]
                p0, axes = p0[None], tuple(range(1, len(shape) + 1))
            d = p.astype(jnp.float32) - p0
            return jnp.sqrt(jnp.sum(d * d, axis=axes))

        norms = np.asarray(jax.jit(change)(
            [scope.get(name) for _, name in members],
            ref.seed_words(seed)))
        for (layer, _), n in zip(members, norms):
            out[(leaf, layer)] = float(n)
    return out


def reference_steps(ref, config, seed, batches, note):
    """The plain reference through the same steps: losses, the first
    gradient's leaf norms, the leaves' change after the last step.
    Adam's moments wait on the host between steps, so the device holds
    parameters, one gradient and a row's activations."""
    import jax
    import jax.numpy as jnp

    t = config["train"]
    params = ref.make_params(seed, config)
    moments = {}
    losses, grad_norms = [], None
    for step, (tokens, labels) in enumerate(batches, 1):
        t0 = time.perf_counter()
        loss, grads = ref.loss_and_grad(params, jnp.asarray(tokens),
                                        jnp.asarray(labels), config)
        losses.append(float(loss))
        if step == 1:
            grad_norms = {k: np.asarray(v) for k, v in
                          ref.leaf_norms(grads).items()}
        last = step == len(batches)
        for k in list(params):
            if k in moments:
                m, v = (jnp.asarray(a) for a in moments.pop(k))
            else:
                m = jnp.zeros_like(params[k])
                v = jnp.zeros_like(params[k])
            params[k], m, v = ref.adam_leaf(
                params[k], m, v, grads.pop(k), float(step), t["lr"],
                t["beta1"], t["beta2"], t["epsilon"])
            if not last:
                moments[k] = (np.asarray(m), np.asarray(v))
            del m, v
        note(phase="reference_step", step=step, loss=losses[-1],
             seconds=time.perf_counter() - t0)
    change = {}
    shapes = ref.leaf_shapes(config)
    for k in list(params):
        d = jax.jit(lambda p, w, k=k: ref.leaf_norms(
            {k: p - ref.init_leaf(w, k, shapes[k])})[k])(
                params.pop(k), ref.seed_words(seed))
        change[k] = np.asarray(d)

    def by_leaf(tree):
        return {(k, None if k in ref.TOP_LEAVES else i): float(x)
                for k, v in tree.items() for i, x in enumerate(v)}

    return losses, by_leaf(grad_norms), by_leaf(change)


def worst_leaf_gap(got, want, skip=()):
    """max over leaves of |got - want| / max(want, median want)."""
    keys = [k for k in want if k not in skip]
    median = float(np.median([want[k] for k in keys]))
    worst, at = 0.0, None
    for k in keys:
        gap = abs(got[k] - want[k]) / max(want[k], median, 1e-30)
        if gap > worst:
            worst, at = gap, k
    return worst, at


def make_checks(config, prog_side, ref_side, window):
    """The numbers compared, each beside its limit."""
    lim = config["correct"]
    losses, grad, change = prog_side
    r_losses, r_grad, r_change = ref_side
    loss_gap = max(abs(a - b) for a, b in zip(losses, r_losses))
    # a leaf whose gradient is zero by the mathematics (the key bias:
    # softmax ignores a shift of every score of a row) moves by Adam's
    # lr-sized steps along rounding noise; its change says nothing
    g_median = float(np.median(list(r_grad.values())))
    noise = [k for k, g in r_grad.items()
             if g < lim["zero_gradient_share"] * g_median]
    grad_gap, grad_at = worst_leaf_gap(grad, r_grad)
    change_gap, change_at = worst_leaf_gap(change, r_change, skip=noise)

    return [
        check("loss_gap_max", loss_gap, lim["loss_gap_max"],
              program=losses, reference=r_losses),
        check("grad_norm_gap_worst_leaf", grad_gap,
              lim["grad_norm_gap_worst_leaf"], leaf=str(grad_at)),
        check("param_change_gap_worst_leaf", change_gap,
              lim["param_change_gap_worst_leaf"], leaf=str(change_at),
              leaves_left_out=len(noise)),
        check("window_nonfinite_losses", window["nonfinite"], 0),
        check("window_compilations", window["compilations"], 0),
    ] + ([check("kernel_fallbacks", window["kernel_fallbacks"], 0)]
         if window["kernel_fallbacks"] is not None else [])


class Reader(threading.Thread):
    """The input pipeline: batches made from the seed on a background
    thread, a few ahead of the step that consumes them."""

    def __init__(self, seed, first, batch, seq_len, vocab, depth):
        super().__init__(name="perfbench-reader", daemon=True)
        self.q = queue.Queue(maxsize=depth)
        self.args = (seed, batch, seq_len, vocab)
        self.next = first
        self.stopping = threading.Event()

    def run(self):
        seed, batch, seq_len, vocab = self.args
        while not self.stopping.is_set():
            item = loadgen.token_batch(seed, self.next, batch, seq_len,
                                       vocab)
            self.next += 1
            while not self.stopping.is_set():
                try:
                    self.q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    pass

    def stop(self):
        self.stopping.set()
        self.join(10)


def run(ctx, break_step=None, program_train=None):
    """``break_step`` is for the harness's own tests: a callable that
    replaces the step's effect, to show ``correct`` come out false.
    ``program_train`` is for the controls: keys of the configuration's
    ``train`` that the program alone is built with, while the reference
    keeps the configuration's (``param_dtype``: the program's own path
    with parameters and residual stream stored in that type; ``lr``: an
    optimizer step of the wrong size)."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.observability import metrics

    config, mix, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    note, tracer = ctx["note"], ctx["tracer"]
    t, ref = config["train"], spec.family(config, "reference")
    batch, seq_len, vocab = t["batch"], t["seq_len"], config["vocab_size"]
    if mix["kind"] != "token_stream":
        raise spec.SpecError("runner train needs token_stream traffic")
    if tracer:
        metrics.enable()   # kernel dispatch counters; off when timing

    t_prog = dict(t, **(program_train or {}))
    param_dtype = t_prog["param_dtype"]
    prog, sprog, loss_var = build_program(fluid, config, t_prog)
    leaves = leaf_map(prog, ref, config)
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.TPUPlace() if ctx["require_chip"]
                             else fluid.CPUPlace())
        note(phase="program_built", seconds=time.perf_counter()
             - ctx["t_start"])
        exe.run(sprog)
        note(phase="startup_ran", seconds=time.perf_counter()
             - ctx["t_start"])
        for name, *_ in leaves:
            scope.erase(name)
        for name, arr in seeded_weights(ref, config, seed, leaves,
                                        param_dtype).items():
            scope.set(name, arr)
        note(phase="state_ready", seconds=time.perf_counter()
             - ctx["t_start"], parameters=ref.n_params(config))

        def step(tokens, labels):
            with span("exe.run"):
                out = exe.run(prog, feed={"tokens": tokens,
                                          "labels": labels},
                              fetch_list=[loss_var], return_numpy=False)[0]
                out.block_until_ready()
            return out

        if break_step is not None:
            step = break_step(step, scope, leaves)

        # set-up: the first steps, through the window's own call
        first = [loadgen.token_batch(seed, i, batch, seq_len, vocab)
                 for i in range(CHECK_STEPS)]
        losses, grad = [], None
        for i, (tokens, labels) in enumerate(first):
            losses.append(float(np.asarray(step(tokens, labels)).ravel()[0]))
            note(phase="setup_step", step=i + 1, seconds=time.perf_counter()
                 - ctx["t_start"])
            if i == 0:
                grad = program_norms(scope, leaves, first_moments(prog),
                                     1.0 / (1.0 - t["beta1"]))
        change = program_change_norms(scope, ref, config, seed, leaves)
        fallbacks0 = counter_value("kernels/fallbacks") if tracer else None
        dispatches = counter_value("kernels/dispatches") if tracer else None

        reader = Reader(seed, CHECK_STEPS, batch, seq_len, vocab,
                        mix["prefetch"])
        reader.start()
        for _ in range(mix["warm_steps"]):
            step(*reader.q.get())

        # the window
        compiles0 = ctx["compiles"].count
        seconds, trace_s = ctx["seconds"], mix["trace_seconds"]
        stamps, outs = [], []
        t0 = time.perf_counter()
        note(phase="window_open", setup_s=t0 - ctx["t_start"])
        while True:
            now = time.perf_counter()
            if now - t0 >= seconds:
                break
            if (tracer and not tracer.started
                    and now - t0 >= seconds - trace_s):
                tracer.start()
            with span("reader.get"):
                tokens, labels = reader.q.get()
            outs.append(step(tokens, labels))
            stamps.append(time.perf_counter())
        t1 = stamps[-1]
        if tracer and tracer.started:
            tracer.stop()
        reader.stop()
        window_losses = [float(np.asarray(o).ravel()[0]) for o in outs]
        window = {
            "nonfinite": int(sum(not np.isfinite(x) for x in window_losses)),
            "compilations": ctx["compiles"].count - compiles0,
            "kernel_fallbacks": (counter_value("kernels/fallbacks")
                                 - fallbacks0) if tracer else None}
        memory_peak = memory_peak_bytes(ctx["devices"][:1])
        steps = len(stamps)
        tokens_per_s = steps * batch * seq_len / (t1 - t0)
        step_s = np.diff([t0] + stamps)
        note(phase="window_closed", steps=steps, window_s=t1 - t0,
             first_loss=window_losses[0], last_loss=window_losses[-1],
             kernel_dispatches=dispatches,
             compile_seconds_total=ctx["compiles"].seconds)
        exe.close()
    # free the program's state before the reference takes the chip
    del exe, outs, step
    for name in list(scope.local_var_names()):
        scope.erase(name)
    del scope
    gc.collect()

    reduced = tracer.reduce() if tracer else None
    t_ref = time.perf_counter()
    ref_side = reference_steps(ref, config, seed, first, note)
    note(phase="reference_done", seconds=time.perf_counter() - t_ref)
    checks = make_checks(config, (losses, grad, change), ref_side, window)
    return {
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": t0 - ctx["t_start"]},
        "observations": {"step_s": step_s.tolist(),
                         "train_tokens_per_s": tokens_per_s,
                         "seq_len": seq_len, "trace": reduced},
        "attempted": steps, "failed": window["nonfinite"],
        "checks": checks, "memory_peak_bytes": memory_peak,
    }
