"""Sharded-optimizer data parallelism (parity: the reference's Reduce mode —
`ReduceSSAGraphBuilder` multi_devices_graph_pass.h:164 /
details/reduce_op_handle.cc, SURVEY §2.3 P2: "each param's grad reduced to
one owner device, updated there, then broadcast — ZeRO-1-like ancestor"),
grown into the full ZeRO ladder with comm/compute overlap (docs/ZERO.md;
Rajbhandari et al. SC 2020, Li et al. VLDB 2020).

Sharding levels (`zero_stage` / $PTPU_ZERO_STAGE):

  1  optimizer-state sharding (the historical default): each gradient is
     reduce-scattered along the dp axis, Adam's m/v live only as
     rank-local shards, and updated parameter slices all-gather back to
     the full (replicated) parameters — per-leaf collectives, or a few
     large flattened buckets with `bucket_mb` set (Megatron DDP parity,
     PR 5).
  2  + gradient sharding: bucketing is mandatory and each bucket's
     gradients exist only as dp-sharded bucket shards past the
     reduce-scatter boundary — the full-gradient buffer is a transient
     the backward segment frees, never part of step state. Update math
     is identical to the bucketed stage-1 path (fp32 legs are bitwise
     equal — tests/test_zero.py pins it).
  3  + parameter sharding: parameters are STORED dp-sharded (flat fp32
     bucket shards, 1/n of the model per device instead of a full
     replica), all-gathered per bucket at the start-of-step first use,
     and the update writes shards directly — the all-gather back that
     stages 1/2 pay never happens, and full-parameter HBM is freed
     between steps. `shard_params`/`gather_params` convert to/from the
     pytree form.

Comm/compute overlap (`overlap` / $PTPU_ZERO_OVERLAP, docs/ZERO.md):
buckets are planned in BACKWARD order (amp.plan_buckets order="backward":
segment 0 holds the leaves whose grads the backward pass produces first),
each bucket's parameters pass through a `custom_vjp` segment marker whose
backward rule is an `optimization_barrier` — splitting the backward into
per-bucket segments XLA cannot fuse across — and the per-bucket
`psum_scatter`s are chained with optimization_barrier ordering so
collective k is issued as soon as segment k's grads exist and XLA's
latency-hiding scheduler can run it concurrently with backward segment
k+1. Every marker/barrier is semantically identity: overlap on/off is
bitwise identical (pinned), only the schedule changes.

Host-offloaded optimizer state (`offload` / $PTPU_ZERO_OFFLOAD): m/v are
pinned in host RAM between steps (fp32 state larger than HBM stops being
a capacity wall). The step splits into a backward/scatter jit and an
update jit; while the backward executes, the PR-2 transfer machinery
(async_engine.HostStateStager riding the FeedPrefetcher worker) stages
m/v host->device, and the updated shards copy back out after the update
— the H2D leg overlaps backward, the D2H copy is the step's optimizer
sync point. Bytes both ways land in zero/offload_bytes.

The legacy surface is unchanged: defaults (stage 1, overlap/offload off)
run byte-for-byte the pre-overlap paths, so the existing ZeRO-1
trajectory is bitwise identical.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import flags as _flags
from ..observability import metrics as _metrics

__all__ = ["ShardedAdam", "ZeroLayoutError"]


class ZeroLayoutError(RuntimeError):
    """The optimizer's planned state layout and the configuration seen at
    make_step time disagree (init_state never called, or a knob changed
    after it ran) — re-plan with init_state instead of silently latching
    a stale layout."""


# the one boolean-spelling parser for PTPU_* switches now lives in the
# central flags registry; kept under the established local name
_env_flag = _flags.env_flag


def _env_stage():
    try:
        return _flags.env("PTPU_ZERO_STAGE")
    except ValueError as exc:
        raise ValueError("PTPU_ZERO_STAGE is not an integer: %s" % (exc,))


def _pad_leading(x, n):
    pad = (-x.shape[0]) % n
    if pad:
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    return x


# ---------------------------------------------------------------------------
# backward segment boundary
# ---------------------------------------------------------------------------
# Identity in the forward; the backward rule pins the segment's cotangents
# behind an optimization_barrier, so XLA cannot fuse gradient production
# across bucket boundaries — the "split the backward into per-bucket
# segments" half of the overlap contract (the issue-order chain in the
# step builders is the other half). The raw jax.lax primitive is safe
# here even on pre-0.5 jax (where it lacks an AD rule): the barrier in
# the bwd rule is traced, not differentiated — training steps are not
# themselves differentiated through.


@jax.custom_vjp
def _grad_segment(leaves):
    return leaves


def _grad_segment_fwd(leaves):
    return leaves, None


def _grad_segment_bwd(_, cotangents):
    with jax.named_scope("zero_backward_segment"):
        return (jax.lax.optimization_barrier(cotangents),)


_grad_segment.defvjp(_grad_segment_fwd, _grad_segment_bwd)


def _mark_segments(flat_p, layout):
    """flat_p with each bucket's leaves routed through its own
    _grad_segment boundary (values unchanged)."""
    marked = list(flat_p)
    for b in layout:
        outs = _grad_segment(tuple(flat_p[i] for i in b.indices))
        for i, o in zip(b.indices, outs):
            marked[i] = o
    return marked


def _segmented(loss_fn, layout):
    """loss_fn with every parameter leaf routed through its bucket's
    _grad_segment boundary INSIDE the differentiated function — the
    cotangents then cross the boundary's optimization_barrier on their
    way out, which is what splits the backward into per-bucket
    segments."""

    def marked_loss(params, *batch):
        flat, tdef = jax.tree.flatten(params)
        return loss_fn(tdef.unflatten(_mark_segments(flat, layout)),
                       *batch)

    return marked_loss


def _ordered(buf, token):
    """Order `buf`'s consumer (the bucket's collective) after `token`
    (the previous bucket's collective output): the issue chain that keeps
    collectives in backward-production order so each one can overlap the
    NEXT segment's compute instead of all bursting at the end."""
    buf, token = jax.lax.optimization_barrier((buf, token))
    return buf, token


class ShardedAdam:
    """Adam with dp-sharded state (the ZeRO ladder — module docstring /
    docs/ZERO.md).

    bucket_mb: flatten gradients into same-dtype buckets of this many
    MiB for the reduce-scatter (None = read $PTPU_AMP_BUCKET_MB; 0 or an
    unset environment = the legacy one-collective-per-leaf path).
    grad_dtype: dtype the gradients are cast to BEFORE the collective
    (e.g. jnp.bfloat16 under AMP — half the bytes on the wire); None
    keeps each gradient's own dtype.
    zero_stage: 1 (optimizer-state sharding, default), 2 (+ gradient
    sharding), 3 (+ parameter sharding). None reads $PTPU_ZERO_STAGE.
    overlap: issue per-bucket collectives in backward order under
    optimization_barrier segment boundaries (None reads
    $PTPU_ZERO_OVERLAP; bitwise identical to overlap=False).
    offload: keep m/v in host RAM between steps, staged through the
    async-engine transfer machinery (None reads $PTPU_ZERO_OFFLOAD).

    Stages 2/3, overlap and offload all require bucketing. init_state
    latches the planned layout; calling make_step with a configuration
    that no longer matches the plan raises ZeroLayoutError."""

    def __init__(self, learning_rate=1e-3, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, axis_name="dp", grad_dtype=None,
                 bucket_mb=None, zero_stage=None, overlap=None,
                 offload=None):
        self.lr = learning_rate
        self.b1, self.b2, self.eps = beta1, beta2, epsilon
        self.axis = axis_name
        self.grad_dtype = grad_dtype
        self.bucket_mb = bucket_mb
        self.zero_stage = zero_stage
        self.overlap = overlap
        self.offload = offload
        self._plan = None    # resolved config latched by init_state
        self._layout = None  # bucket plan latched by init_state
        self._p_treedef = None   # ZeRO-3: params pytree structure
        self._p_template = None  # ZeRO-3: per-leaf ShapeDtypeStruct

    # ------------------------------------------------------------------
    # configuration
    # ------------------------------------------------------------------
    def _bucket_bytes(self):
        from .. import amp

        if self.bucket_mb is not None:
            return amp.mb_to_bucket_bytes(self.bucket_mb)
        return amp.bucket_bytes_from_env(default_mb=None)

    def _resolve_config(self):
        """The effective (validated) configuration right now — ctor
        arguments win over the environment."""
        env_stage = _env_stage()
        stage = self.zero_stage if self.zero_stage is not None \
            else (env_stage if env_stage is not None else 1)
        if stage not in (1, 2, 3):
            raise ValueError("zero_stage must be 1, 2 or 3, got %r"
                             % (stage,))
        overlap = self.overlap if self.overlap is not None \
            else bool(_env_flag("PTPU_ZERO_OVERLAP"))
        offload = self.offload if self.offload is not None \
            else bool(_env_flag("PTPU_ZERO_OFFLOAD"))
        bb = self._bucket_bytes()
        needs = [k for k, on in (("zero_stage>=2", stage >= 2),
                                 ("overlap", overlap),
                                 ("offload", offload)) if on]
        if needs and not bb:
            raise ValueError(
                "%s requires gradient bucketing: set bucket_mb (or "
                "$PTPU_AMP_BUCKET_MB) to a positive MiB size"
                % " + ".join(needs))
        return {"bucket_bytes": bb, "stage": stage,
                "overlap": bool(overlap), "offload": bool(offload),
                "grad_dtype": str(self.grad_dtype)}

    def _check_plan(self, what):
        """make_step-time guard: the layout planned by init_state must
        match the configuration in force NOW (a changed bucket_mb /
        $PTPU_AMP_BUCKET_MB / stage / overlap / offload between the two
        calls would silently pair a stale state layout with a different
        step function)."""
        cfg = self._resolve_config()
        if self._plan is None:
            if cfg["bucket_bytes"] or cfg["stage"] >= 2 or cfg["offload"]:
                raise ZeroLayoutError(
                    "%s: call init_state(params, mesh) before make_step — "
                    "this configuration (%r) needs a planned state layout"
                    % (what, cfg))
            return cfg
        if cfg != self._plan:
            raise ZeroLayoutError(
                "%s: configuration changed after init_state (planned %r, "
                "now %r) — call init_state(params, mesh) again to re-plan "
                "the state layout" % (what, self._plan, cfg))
        return cfg

    # ------------------------------------------------------------------
    # state
    # ------------------------------------------------------------------
    def init_state(self, params, mesh):
        """m/v pytrees sharded over dp: per-leaf leading-dim shards in
        the legacy path, flat per-BUCKET shards in bucketed mode (host
        numpy buffers under offload). The resolved configuration is
        LATCHED here — make_step verifies it still holds, so a knob
        changed in between raises instead of silently pairing a stale
        layout with a different step function."""
        cfg = self._resolve_config()
        self._plan = cfg
        n = mesh.shape[self.axis]
        if not cfg["bucket_bytes"]:
            self._layout = None

            def zeros_sharded(p):
                shape = ((p.shape[0] + (-p.shape[0]) % n),) + p.shape[1:]
                z = jnp.zeros(shape, jnp.float32)
                return jax.device_put(
                    z, jax.sharding.NamedSharding(mesh, P(self.axis)))

            return {"m": jax.tree.map(zeros_sharded, params),
                    "v": jax.tree.map(zeros_sharded, params),
                    "step": jnp.zeros((), jnp.int32)}

        from .. import amp

        flat, treedef = jax.tree.flatten(params)
        gdt = self.grad_dtype if self.grad_dtype is not None \
            else jnp.float32
        self._layout = amp.plan_buckets(
            flat, cfg["bucket_bytes"], pad_multiple=n, dtype=gdt,
            order="backward" if cfg["overlap"] else "forward")
        self._p_treedef = treedef
        self._p_template = [
            jax.ShapeDtypeStruct(
                np.shape(p), getattr(p, "dtype", None)
                or np.asarray(p).dtype)
            for p in flat]
        if cfg["offload"]:
            return {"m": [np.zeros((b.padded,), np.float32)
                          for b in self._layout],
                    "v": [np.zeros((b.padded,), np.float32)
                          for b in self._layout],
                    "step": np.zeros((), np.int32)}
        sh = NamedSharding(mesh, P(self.axis))

        def zeros_flat(b):
            return jax.device_put(jnp.zeros((b.padded,), jnp.float32), sh)

        return {"m": [zeros_flat(b) for b in self._layout],
                "v": [zeros_flat(b) for b in self._layout],
                "step": jnp.zeros((), jnp.int32)}

    # ------------------------------------------------------------------
    # ZeRO-3 parameter layout
    # ------------------------------------------------------------------
    def shard_params(self, params, mesh):
        """params pytree -> list of flat fp32 dp-sharded bucket buffers
        (the ZeRO-3 stored form: each device holds 1/n of the model).
        Requires init_state (the bucket layout doubles as the parameter
        layout so gradient shards and parameter shards stay aligned)."""
        from .. import amp

        if self._layout is None:
            raise ZeroLayoutError(
                "shard_params: call init_state(params, mesh) first — the "
                "parameter shards follow the planned bucket layout")
        flat, treedef = jax.tree.flatten(params)
        if treedef != self._p_treedef:
            raise ValueError("params structure does not match the tree "
                             "init_state planned for")
        sh = NamedSharding(mesh, P(self.axis))
        return [jax.device_put(
                    amp.flatten_bucket(b, flat, dtype=jnp.float32), sh)
                for b in self._layout]

    def gather_params(self, pshards):
        """The pytree form of ZeRO-3 sharded parameters (host-side
        assembly — jax reads the global view of each sharded buffer;
        leaves come back in their original dtypes)."""
        from .. import amp

        if self._layout is None or self._p_treedef is None:
            raise ZeroLayoutError("gather_params: no planned layout — "
                                  "call init_state first")
        if len(pshards) != len(self._layout):
            raise ZeroLayoutError(
                "gather_params: %d shard buffers for a %d-bucket layout "
                "(sharded under a different bucket plan?)"
                % (len(pshards), len(self._layout)))
        flat = [None] * self._p_treedef.num_leaves
        for b, buf in zip(self._layout, pshards):
            for i, seg in amp.unflatten_bucket(b, buf,
                                               self._p_template).items():
                flat[i] = seg
        return jax.tree.unflatten(self._p_treedef, flat)

    # ------------------------------------------------------------------
    # update math (shared by every path — the ladder changes data
    # movement, never the arithmetic)
    # ------------------------------------------------------------------
    def _local_update(self, g_shard, p_shard, m, v, t):
        m = self.b1 * m + (1 - self.b1) * g_shard
        v = self.b2 * v + (1 - self.b2) * jnp.square(g_shard)
        mhat = m / (1 - self.b1 ** t)
        vhat = v / (1 - self.b2 ** t)
        p_new = p_shard - self.lr * mhat / (jnp.sqrt(vhat) + self.eps)
        return p_new, m, v

    # ------------------------------------------------------------------
    def make_step(self, mesh, loss_fn):
        """jit-compiled (params, state, *batch) -> (params, state, loss)
        with grads reduce-scattered and updates computed on local shards.
        Under zero_stage=3 the params position holds the sharded form
        (`shard_params` output) and stays sharded. Under offload the
        callable is a host-side wrapper around a backward/scatter jit and
        an update jit (module docstring)."""
        cfg = self._check_plan("make_step")
        if cfg["overlap"]:
            # structural overlap receipt: with B buckets, the first B-1
            # collectives each have at least one backward segment still
            # outstanding to overlap with. Only overlap-enabled steps
            # write the gauge — it reads as "the headroom of the most
            # recent overlap-enabled step", and a later non-overlap
            # optimizer in the same process does not clobber it.
            nb = len(self._layout)
            _metrics.gauge("zero/overlap_ratio").set(
                (nb - 1) / nb if nb else 0.0)
        if cfg["offload"]:
            return self._make_step_offloaded(mesh, loss_fn, cfg)
        if cfg["stage"] == 3:
            return self._make_step_zero3(mesh, loss_fn, cfg)
        if cfg["bucket_bytes"]:
            return self._make_step_bucketed(mesh, loss_fn, cfg)
        return self._make_step_per_leaf(mesh, loss_fn)

    # -- stage 1, per-leaf collectives (the legacy default path) -------
    def _make_step_per_leaf(self, mesh, loss_fn):
        axis = self.axis
        n = mesh.shape[axis]

        def step(params, state, *batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, *batch)
            t = state["step"] + 1

            def upd(p, g, m, v):
                # grad_dtype applies BEFORE the collective in this path
                # too (halved wire bytes); the fp32 cast moves to the
                # local shard, after the reduce-scatter
                gdt = self.grad_dtype if self.grad_dtype is not None \
                    else jnp.float32
                gp = _pad_leading(g.astype(gdt), n)
                pp = _pad_leading(p.astype(jnp.float32), n)

                def inner(gp, pp, m, v):
                    # mean-reduce + scatter the grad to its owner rank
                    gs = jax.lax.psum_scatter(
                        gp, axis, scatter_dimension=0, tiled=True) / n
                    p_new, m, v = self._local_update(
                        gs.astype(jnp.float32), pp, m, v,
                        t.astype(jnp.float32))
                    # broadcast updated slices back (BCastParamsToDevices
                    # parity, parallel_executor.cc:434)
                    p_full = jax.lax.all_gather(p_new, axis, axis=0,
                                                tiled=True)
                    return p_full, m, v

                spec_full = P()
                spec_shard = P(axis)
                p_full, m, v = shard_map(
                    inner, mesh=mesh,
                    in_specs=(spec_full, spec_shard, spec_shard, spec_shard),
                    out_specs=(spec_full, spec_shard, spec_shard),
                    check_vma=False)(gp, pp, m, v)
                return p_full[: p.shape[0]].astype(p.dtype), m, v

            flat_p, tdef = jax.tree.flatten(params)
            flat_g = tdef.flatten_up_to(grads)
            flat_m = tdef.flatten_up_to(state["m"])
            flat_v = tdef.flatten_up_to(state["v"])
            out = [upd(p, g, m, v)
                   for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
            new_p = tdef.unflatten([o[0] for o in out])
            new_state = {"m": tdef.unflatten([o[1] for o in out]),
                         "v": tdef.unflatten([o[2] for o in out]),
                         "step": t}
            return new_p, new_state, loss

        return jax.jit(step, donate_argnums=(0, 1))

    # -- shared bucket plumbing ----------------------------------------
    def _scatter_update(self, mesh, gbuf, pbuf, m, v, t, gather_back):
        """ONE large low-precision reduce-scatter for a bucket, the fp32
        update on the local shard, and (stages 1/2) the all-gather of the
        updated slices back to the full buffer."""
        axis = self.axis
        n = mesh.shape[axis]
        spec_full, spec_shard = P(), P(axis)

        def inner(gb, pb, m, v):
            gs = jax.lax.psum_scatter(
                gb, axis, scatter_dimension=0, tiled=True) / n
            p_new, m, v = self._local_update(
                gs.astype(jnp.float32), pb, m, v, t.astype(jnp.float32))
            if gather_back:
                p_new = jax.lax.all_gather(p_new, axis, axis=0, tiled=True)
            return p_new, m, v

        return shard_map(
            inner, mesh=mesh,
            in_specs=(spec_full, spec_shard, spec_shard, spec_shard),
            out_specs=(spec_full if gather_back else spec_shard,
                       spec_shard, spec_shard),
            check_vma=False)(gbuf, pbuf, m, v)

    # -- stages 1/2, bucketed collectives ------------------------------
    def _make_step_bucketed(self, mesh, loss_fn, cfg):
        """Same update math as per-leaf, but the reduce-scatter moves a
        few large flattened buckets (in grad_dtype) instead of one
        collective per leaf; overlap=True issues them in backward order
        behind segment boundaries. Stage 2 is this path with bucketing
        mandatory: gradients never exist as step state beyond their
        dp-sharded bucket shards."""
        from .. import amp

        layout = self._layout
        overlap = cfg["overlap"]

        fn = _segmented(loss_fn, layout) if overlap else loss_fn

        def step(params, state, *batch):
            flat_p, tdef = jax.tree.flatten(params)
            loss, grads = jax.value_and_grad(fn)(params, *batch)
            t = state["step"] + 1
            flat_g = tdef.flatten_up_to(grads)
            new_flat = list(flat_p)
            new_m, new_v = [], []
            token = loss
            for k, b in enumerate(layout):
                gbuf = amp.flatten_bucket(b, flat_g)
                if overlap:
                    gbuf, token = _ordered(gbuf, token)
                # params flatten in fp32 REGARDLESS of the collective
                # dtype — rounding the master copy through bf16 would
                # destroy the mixed-precision contract
                pbuf = amp.flatten_bucket(b, flat_p, dtype=jnp.float32)
                p_full, mb, vb = self._scatter_update(
                    mesh, gbuf, pbuf, state["m"][k], state["v"][k], t,
                    gather_back=True)
                if overlap:
                    token = mb
                for i, seg in amp.unflatten_bucket(b, p_full,
                                                   flat_p).items():
                    new_flat[i] = seg
                new_m.append(mb)
                new_v.append(vb)
            return (tdef.unflatten(new_flat),
                    {"m": new_m, "v": new_v, "step": t}, loss)

        return jax.jit(step, donate_argnums=(0, 1))

    # -- stage 3, parameter sharding -----------------------------------
    def _gathered_leaves(self, mesh, pshards):
        """Full-precision full-parameter leaves all-gathered per bucket
        from the sharded stored form — traced inside the step, so each
        bucket's gather is consumed exactly where its leaves are first
        used and XLA can overlap it with earlier compute."""
        from .. import amp

        if len(pshards) != len(self._layout):
            raise ZeroLayoutError(
                "%d parameter shard buffers for a %d-bucket layout — "
                "pass shard_params output from THIS optimizer's plan"
                % (len(pshards), len(self._layout)))
        axis = self.axis
        spec_shard = P(axis)

        def gather(buf):
            return shard_map(
                lambda s: jax.lax.all_gather(s, axis, axis=0, tiled=True),
                mesh=mesh, in_specs=(spec_shard,), out_specs=P(),
                check_vma=False)(buf)

        flat = [None] * self._p_treedef.num_leaves
        for b, buf in zip(self._layout, pshards):
            with jax.named_scope("zero3_param_gather"):
                full = gather(buf)
            for i, seg in amp.unflatten_bucket(b, full,
                                               self._p_template).items():
                flat[i] = seg
        return flat

    def _make_step_zero3(self, mesh, loss_fn, cfg):
        """(pshards, state, *batch) -> (pshards, state, loss): parameters
        live dp-sharded (shard_params), are gathered per bucket for the
        forward, and the update writes the fp32 shards in place — no
        gather-back, no replicated parameter storage."""
        from .. import amp

        layout = self._layout
        overlap = cfg["overlap"]
        tdef = self._p_treedef
        _metrics.gauge("zero/gather_bytes").set(sum(
            b.padded * 4 for b in layout))

        fn = _segmented(loss_fn, layout) if overlap else loss_fn

        def step(pshards, state, *batch):
            flat_full = self._gathered_leaves(mesh, pshards)
            params_in = jax.tree.unflatten(tdef, flat_full)
            loss, grads = jax.value_and_grad(fn)(params_in, *batch)
            t = state["step"] + 1
            flat_g = tdef.flatten_up_to(grads)
            new_shards, new_m, new_v = [], [], []
            token = loss
            for k, b in enumerate(layout):
                gbuf = amp.flatten_bucket(b, flat_g)
                if overlap:
                    gbuf, token = _ordered(gbuf, token)
                ps, mb, vb = self._scatter_update(
                    mesh, gbuf, pshards[k], state["m"][k], state["v"][k],
                    t, gather_back=False)
                if overlap:
                    token = mb
                new_shards.append(ps)
                new_m.append(mb)
                new_v.append(vb)
            return (new_shards,
                    {"m": new_m, "v": new_v, "step": t}, loss)

        return jax.jit(step, donate_argnums=(0, 1))

    # -- host-offloaded optimizer state --------------------------------
    def _make_step_offloaded(self, mesh, loss_fn, cfg):
        """Two-phase step with m/v living in host RAM between steps:

          phase 1 (backward jit): forward + segmented backward + the
                  per-bucket reduce-scatters -> dp-sharded grad shards.
                  Dispatched first; WHILE it executes, the HostStateStager
                  worker places m/v host->device with their shard
                  sharding.
          phase 2 (update jit): the same _local_update on (grad shard,
                  param fp32, m, v) per bucket; new m/v copy back to host
                  (the D2H sync), parameters return like the on-device
                  paths (full for stages 1/2, shards for stage 3).

        Splitting at the reduce-scatter boundary keeps the arithmetic
        identical to the fused step — offload on/off is bitwise equal on
        fp32 legs (pinned)."""
        from .. import amp
        from ..async_engine import HostStateStager

        layout = self._layout
        overlap = cfg["overlap"]
        stage3 = cfg["stage"] == 3
        tdef = self._p_treedef
        sh = NamedSharding(mesh, P(self.axis))
        # each returned step OWNS its stager (a re-made step must not
        # break callables handed out earlier); the worker thread is
        # daemonic and lazily started, and `step.close()` releases it
        # eagerly for callers that cycle many steps in one process
        stager = HostStateStager(place_fn=lambda v: jax.device_put(v, sh))
        if stage3:
            _metrics.gauge("zero/gather_bytes").set(sum(
                b.padded * 4 for b in layout))

        fn = _segmented(loss_fn, layout) if overlap else loss_fn

        def backward(pstate, *batch):
            if stage3:
                flat_full = self._gathered_leaves(mesh, pstate)
            else:
                flat_full, _ = jax.tree.flatten(pstate)
            params_in = jax.tree.unflatten(tdef, flat_full)
            loss, grads = jax.value_and_grad(fn)(params_in, *batch)
            flat_g = tdef.flatten_up_to(grads)
            axis, n = self.axis, mesh.shape[self.axis]

            def scatter(gb):
                return shard_map(
                    lambda g: jax.lax.psum_scatter(
                        g, axis, scatter_dimension=0, tiled=True) / n,
                    mesh=mesh, in_specs=(P(),), out_specs=P(axis),
                    check_vma=False)(gb)

            gshards = []
            token = loss
            for b in layout:
                gbuf = amp.flatten_bucket(b, flat_g)
                if overlap:
                    gbuf, token = _ordered(gbuf, token)
                gs = scatter(gbuf)
                if overlap:
                    token = gs
                gshards.append(gs)
            return loss, gshards

        def update(pstate, gshards, ms, vs, step_count):
            t = step_count + 1
            spec_shard = P(self.axis)
            flat_p = None if stage3 else jax.tree.flatten(pstate)[0]
            new_p, new_m, new_v = [], [], []
            for k, b in enumerate(layout):
                pbuf = pstate[k] if stage3 else amp.flatten_bucket(
                    b, flat_p, dtype=jnp.float32)

                def inner(gs, pb, m, v):
                    p_new, m, v = self._local_update(
                        gs.astype(jnp.float32), pb, m, v,
                        t.astype(jnp.float32))
                    if not stage3:
                        p_new = jax.lax.all_gather(p_new, self.axis,
                                                   axis=0, tiled=True)
                    return p_new, m, v

                pn, mb, vb = shard_map(
                    inner, mesh=mesh,
                    in_specs=(spec_shard, spec_shard, spec_shard,
                              spec_shard),
                    out_specs=(spec_shard if stage3 else P(),
                               spec_shard, spec_shard),
                    check_vma=False)(gshards[k], pbuf, ms[k], vs[k])
                new_p.append(pn)
                new_m.append(mb)
                new_v.append(vb)
            if stage3:
                out_p = new_p
            else:
                flat_new = list(flat_p)
                for b, full in zip(layout, new_p):
                    for i, seg in amp.unflatten_bucket(b, full,
                                                       flat_p).items():
                        flat_new[i] = seg
                out_p = jax.tree.unflatten(tdef, flat_new)
            return out_p, new_m, new_v, t

        backward_jit = jax.jit(backward)
        update_jit = jax.jit(update, donate_argnums=(0, 1, 2, 3))

        def step(pstate, state, *batch):
            # H2D of m/v overlaps the backward's async execution. A
            # failing backward (trace error, transient XLA fault the
            # PR-4 trainer retries) must not wedge the stager: abort
            # drops the staged batch so the retry starts clean.
            stager.stage_in_begin(list(state["m"]) + list(state["v"]))
            try:
                loss, gshards = backward_jit(pstate, *batch)
                staged = stager.stage_in_end()
            except BaseException:
                stager.abort()
                raise
            ms, vs = staged[:len(layout)], staged[len(layout):]
            new_p, new_m, new_v, t = update_jit(
                pstate, gshards, ms, vs, jnp.asarray(state["step"]))
            host_m = stager.stage_out(new_m)
            host_v = stager.stage_out(new_v)
            return new_p, {"m": host_m, "v": host_v,
                           "step": np.asarray(t)}, loss

        step.close = stager.close
        return step
