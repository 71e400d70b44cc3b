"""Executor (parity: python/paddle/fluid/executor.py:292 `Executor`, :550
`run`, :671 `_run` with program cache; C++ framework/executor.cc).

TPU-native execution model: `run()` lowers the whole program (forward + grad
+ optimizer ops) into ONE pure function
    step(state, feeds, step_counter) -> (fetches, new_state)
jit-compiled by XLA with the state pytree donated, so parameter updates are
in-place buffer aliases in HBM and the host loop does nothing but feed and
fetch. Compiled executables are cached on (program fingerprint, feed
signature, fetch names) — the analogue of Fluid's `_get_strong_program_cache_key`
(executor.py:250), but a cache hit here skips XLA retracing entirely.

The hot loop is asynchronous end-to-end (docs/ASYNC_EXECUTION.md):
`return_numpy=False` (or a non-boundary `fetch_every_n` step) returns the
fetches as unmaterialized device futures, a bounded in-flight window
(`async_steps`, default $PTPU_ASYNC_STEPS or 12) backpressures dispatch,
feed batches can be staged host->device in the background
(`Executor.prefetch` / `train_from_dataset`'s built-in lookahead), and
jax's on-disk compilation cache persists compiled executables across
processes (at $JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache).
"""

import time

import numpy as np

import jax

from . import framework
from .flags import env as flags_env
from . import observability as _observability
from .observability import metrics as _metrics
from .observability import tracing as _tracing
from .async_engine import (DeferredWarns, FeedPrefetcher, InflightWindow,
                           LazyFetchList, prefetch_iter,
                           setup_persistent_cache)
from .async_engine import _nbytes  # shared feed/fetch byte accounting
from .async_engine import as_numpy  # noqa: F401  (re-export: sync point)
from .core.lowering import (LoweringContext, execute_block,
                            pack_nan_reports, pack_warn_reports,
                            raise_if_nonfinite)
from .core.place import CPUPlace, TPUPlace, default_place
from .core.scope import Scope, global_scope, scope_guard  # noqa: F401
from .framework import Program, dtype_to_np

__all__ = ["Executor", "global_scope", "scope_guard", "as_numpy"]


def _exe_phase(name):
    """A phase of a step on the profiler's clock (``ptpu/exe.<name>`` on
    the host plane of a `jax.profiler` capture, beside the device rows)
    while metrics are on; the shared null span otherwise."""
    if not _metrics.enabled():
        return _tracing.NULL_SPAN
    return _tracing.annotation("ptpu/exe." + name)


def _feed_signature(feed):
    # duck-typed dtype: np.asarray on a device-resident jax.Array would
    # round-trip the whole buffer over the host link EVERY run() call
    def _dt(v):
        dt = getattr(v, "dtype", None)
        return str(dt) if dt is not None else str(np.asarray(v).dtype)

    return tuple(
        sorted((k, tuple(np.shape(v)), _dt(v)) for k, v in feed.items())
    )


_INT64_DTYPES = (np.dtype(np.int64), np.dtype(np.uint64))


def check_feed_int64(name, value):
    """JAX canonicalizes int64 device inputs to int32; an id above 2^31
    would truncate SILENTLY. Fail loudly instead — raw feature hashes
    belong on the host side (DataFeedDesc slot hash_mod /
    HostEmbeddingTable(hash_ids=True)).

    Checked on the ORIGINAL feed value, BEFORE the host/device branch:
    a device-resident jax.Array keeps an int64 dtype only under
    jax_enable_x64, and exactly then this guard still sees it (with x64
    off the truncation already happened inside the user's device_put,
    which no run()-time check can undo). Only int64/uint64 feeds pay the
    range reduction; every other dtype is one dtype compare."""
    dt = getattr(value, "dtype", None)
    if dt is None or np.dtype(dt) not in _INT64_DTYPES:
        return
    if not getattr(value, "size", 0):
        return
    # host-side reduction even for device arrays: a jnp.max on an int64
    # operand under x64-off canonicalizes the REDUCTION to int32 and
    # reports the truncated value — the very bug being guarded against.
    # The transfer only taxes the rare (and discouraged) int64 feed path.
    arr = np.asarray(value)
    mx, mn = int(arr.max()), int(arr.min())
    if mx > np.iinfo(np.int32).max or mn < np.iinfo(np.int32).min:
        raise ValueError(
            "feed %r holds int64 ids above int32 range; JAX would "
            "silently truncate them on device. Hash them on the "
            "host first (DataFeedDesc.set_hash_mod, or "
            "HostEmbeddingTable(hash_ids=True) for direct "
            "pull/push)" % name)


# byte-scale buckets for module-size histograms (1KiB .. 1GiB)
_BYTE_BUCKETS = tuple(float(1 << s) for s in range(10, 31, 2))


class _CompiledStep:
    """One lowered+jitted step for a (program, feed signature, fetches)."""

    def __init__(self, program, feed_names, fetch_names, scope, mesh_ctx=None):
        from . import ir_passes
        from .compiler import classify_persistable_state

        self.program = program
        self.feed_names = list(feed_names)
        self.fetch_names = list(fetch_names)
        block = program.global_block()

        # pserver-mode RPC ops (transpiled trainer program) run host-side
        # after the jitted step: send needs the step's grad values fetched
        self._rpc_ops = [op for op in block.ops if op.type in
                         ("send", "recv", "send_barrier", "fetch_barrier")]
        self._rpc_client = None
        self._rpc_endpoints = []
        for op in self._rpc_ops:
            for ep in [op.attrs.get("endpoint")] + list(
                    op.attrs.get("endpoints", [])):
                if ep and ep not in self._rpc_endpoints:
                    self._rpc_endpoints.append(ep)
        rpc_fetches = []
        for op in self._rpc_ops:
            if op.type == "send":
                for v in op.inputs.get("X", []):
                    if v.name not in rpc_fetches \
                            and v.name not in self.fetch_names:
                        rpc_fetches.append(v.name)
        self._all_fetch_names = self.fetch_names + rpc_fetches

        # persistable read/write classification (shared with the
        # data-parallel step): mut is donated — param/accumulator updates
        # alias in-place in HBM; const is read-only (e.g. learning rate)
        inplace = (ir_passes.InplaceInfo(scope=scope)
                   if ir_passes.pipeline_enabled() else None)
        self._inplace = inplace
        self.mut_names, self.const_names, self.state_out = \
            classify_persistable_state(block, self._all_fetch_names,
                                       inplace=inplace)
        seed = program.random_seed or 0
        self._seed = seed

        from .flags import flag

        self._check_nan_inf = bool(flag("check_nan_inf"))
        self._nan_labels = []
        self._warn_labels = []
        self._warned = set()
        self._deferred_warns = DeferredWarns()

        def step(mut_state, const_state, feeds, step_counter):
            base_key = jax.random.fold_in(
                jax.random.PRNGKey(self._seed), step_counter
            )
            ctx = LoweringContext(base_key=base_key,
                                  check_nan_inf=self._check_nan_inf)
            env = {}
            env.update(const_state)
            env.update(mut_state)
            env.update(feeds)
            execute_block(block, env, ctx)
            fetches = [env[n] for n in self._all_fetch_names]
            new_state = {n: env[n] for n in self.state_out if n in env}
            # FLAGS_check_nan_inf parity: one fused bool per op output;
            # labels are trace-static, flags come back as a packed array
            self._nan_labels, finite = pack_nan_reports(ctx)
            self._warn_labels, warns = pack_warn_reports(ctx)
            return fetches, new_state, finite, warns

        # under the debug flag, keep state undonated so a nan raise can
        # leave the scope at its pre-step values (catch-and-continue safe)
        donate = () if self._check_nan_inf else (0,)
        self._jitted = jax.jit(step, donate_argnums=donate)
        # AOT-compiled executable, built on FIRST run when telemetry is on
        # so compile time and module size are measured separately from
        # execute time (the plain jit dispatch hides both in call #1).
        # Once a step has executed via the jit path its executable is
        # already cached — AOT-compiling then would duplicate the whole
        # XLA compile just to measure it, so _ran_jit pins the jit path.
        self._aot = None
        self._ran_jit = False

    def _read_state(self, scope, names):
        from . import ir_passes

        state = {}
        for name in names:
            val = scope.get(name)
            if val is None:
                # compile-time artifacts (baked folded constants,
                # donation-promoted dead inputs) self-heal into whatever
                # scope this cached step runs against
                val = ir_passes.state_fallback(self.program,
                                               self._inplace, name)
                if val is not None:
                    scope.set(name, val)
            if val is None:
                raise RuntimeError(
                    "persistable var %r is not initialized — run the startup "
                    "program first (exe.run(fluid.default_startup_program()))"
                    % name
                )
            state[name] = val
        return state

    def _stage(self, scope, feed):
        """The step's arguments: state read from the scope, feeds cast
        to the program's dtypes."""
        mut = self._read_state(scope, self.mut_names)
        const = self._read_state(scope, self.const_names)
        feeds = {}
        block = self.program.global_block()
        for name in self.feed_names:
            v = block._find_var_recursive(name)
            arr = feed[name]
            # range-check the ORIGINAL value: after the device branch a
            # jax.Array has already been canonicalized, after the astype
            # a numpy int64 has already been narrowed
            check_feed_int64(name, arr)
            # device-resident arrays (PyReader double-buffer, user
            # device_put) pass through untouched — np.asarray here would
            # round-trip them over the host link every step
            if not isinstance(arr, jax.Array):
                arr = np.asarray(arr)
            if v is not None and v.shape is not None:
                want = dtype_to_np(v.dtype)
                if arr.dtype != want:
                    arr = arr.astype(want)
            feeds[name] = arr
        step_counter = np.uint32(scope.get("__step_counter__", 0) or 0)
        return mut, const, feeds, step_counter

    def run(self, scope, feed):
        with _exe_phase("prepare"):
            mut, const, feeds, step_counter = self._stage(scope, feed)
        fn = self._aot
        if fn is None:
            # tracing alone also takes the AOT path: without it the first
            # "execute" span would swallow the whole trace+compile and
            # point a Perfetto reader at the device for host-side cost
            if ((_metrics.enabled() or _tracing.enabled())
                    and not self._ran_jit):
                fn = self._compile_instrumented(mut, const, feeds,
                                                step_counter)
            else:
                fn = self._jitted
                self._ran_jit = True
        with _exe_phase("dispatch"), _tracing.span("execute"):
            fetches, new_state, finite, warns = fn(
                mut, const, feeds, step_counter)
        # deferred: the all-false common case must not sync the device
        # every step — flags accumulate and materialize every few steps
        # (and at Executor.sync/close)
        self._deferred_warns.add(self._warn_labels, warns, self._warned)
        if self._check_nan_inf and finite.size:
            # state was NOT donated under the debug flag: raising here leaves
            # the scope at its pre-step values, so the poisoned update is
            # discarded and training can resume after catching
            raise_if_nonfinite(self._nan_labels, finite)
        for name, val in new_state.items():
            scope.set(name, val)
        scope.set("__step_counter__", int(step_counter) + 1)
        if self._rpc_ops:
            self._run_rpc_plan(scope, dict(zip(self._all_fetch_names,
                                               fetches)))
        return fetches[: len(self.fetch_names)]

    def _compile_instrumented(self, mut, const, feeds, step_counter):
        """Trace+lower+compile ahead of time (jax AOT), recording the
        compile-vs-execute split and the StableHLO module size. The
        compiled executable replaces the jit dispatch for this step's
        remaining runs, so the telemetry shows compile cost exactly once
        per cache entry instead of folded into the first step."""
        with _tracing.span("compile", step=self.fetch_names[:4]):
            t0 = time.perf_counter()
            lowered = self._jitted.lower(mut, const, feeds, step_counter)
            t1 = time.perf_counter()
            compiled = lowered.compile()
            t2 = time.perf_counter()
        _metrics.histogram("compile_cache/trace_time").observe(t1 - t0)
        _metrics.histogram("compile_cache/compile_time").observe(t2 - t1)
        if _metrics.enabled():
            # per-step FLOPs/bytes from XLA's own cost model — the MFU
            # receipts bench.py reports (docs/OBSERVABILITY.md)
            from .observability import cost as _cost

            _cost.publish(compiled)
        if _metrics.enabled():  # serialization is real work, not a no-op
            try:
                # bytecode serialization, NOT as_text(): the pretty text
                # of a large step runs to tens of MB just to be len()'d
                import io

                buf = io.BytesIO()
                lowered.compiler_ir("stablehlo").operation.write_bytecode(
                    buf)
                _metrics.histogram("compile_cache/stablehlo_module_bytes",
                                   buckets=_BYTE_BUCKETS).observe(
                    buf.tell())
            except Exception:
                pass
        self._aot = compiled
        return compiled

    def _run_rpc_plan(self, scope, fetched):
        """Host-side pserver round (grpc_client.h parity): send grads,
        barrier on the server's optimizer pass, pull fresh params into the
        scope for the next step."""
        from .distributed_runtime import ParameterServerClient

        if self._rpc_client is None:
            tid = next((op.attrs.get("trainer_id", 0)
                        for op in self._rpc_ops), 0)
            self._rpc_client = ParameterServerClient(trainer_id=tid or 0)
        c = self._rpc_client
        for op in self._rpc_ops:
            a = op.attrs
            if op.type == "send":
                for v in op.inputs.get("X", []):
                    c.send_var(a["endpoint"], v.name,
                               np.asarray(fetched[v.name]))
            elif op.type == "send_barrier":
                for ep in a.get("endpoints", []):
                    c.send_barrier(ep)
            elif op.type == "recv":
                for v in op.outputs.get("Out", []):
                    scope.set(v.name, c.get_var(a["endpoint"], v.name))
            elif op.type == "fetch_barrier":
                for ep in a.get("endpoints", []):
                    c.fetch_barrier(ep)


class Executor:
    """Drop-in parity with fluid.Executor (executor.py:292).

    `async_steps` bounds how many dispatched-but-unsynced steps the
    async return paths (`return_numpy=False`, `fetch_every_n`) keep in
    flight before backpressuring on the oldest (default: $PTPU_ASYNC_STEPS
    or 12 — deep enough to amortize the host round trip of a drain; the
    depth has not been re-measured on today's chip, PERF.md)."""

    def __init__(self, place=None, async_steps=None):
        self.place = place if place is not None else default_place()
        self._cache = {}
        if async_steps is None:
            async_steps = flags_env("PTPU_ASYNC_STEPS")
        self._window = InflightWindow(async_steps)
        self._fetch_tick = 0
        self._prefetcher = None
        self._feed_sharding_fn = None
        # compiled steps owned by CompiledPrograms run through this
        # executor — sync() must reach their deferred warnings too
        self._warn_sources = []
        setup_persistent_cache()

    # -- async pipeline ----------------------------------------------------
    def sync(self):
        """Explicit sync point: block until every in-flight step has
        materialized and flush deferred runtime warnings."""
        self._window.drain()
        for compiled in list(self._cache.values()) + self._warn_sources:
            warns = getattr(compiled, "_deferred_warns", None)
            if warns is not None:
                warns.drain(compiled._warned)

    def _feed_sharding(self, name, value):
        """Target placement for a prefetched feed value: the compiled
        sharded step's decision once one exists (compiler.py
        feed_sharding), this executor's device until then."""
        fn = self._feed_sharding_fn
        if fn is not None:
            return fn(name, value)
        return self.place.jax_device()

    def prefetch(self, feed):
        """Stage `feed`'s host values to device on a background thread,
        overlapping the H2D transfer with the device's current step. A
        subsequent `run(feed=feed)` with the SAME value objects picks up
        the staged copies transparently; staged batches are consumed in
        prefetch order."""
        if self._prefetcher is None:
            self._prefetcher = FeedPrefetcher(
                sharding_fn=self._feed_sharding)
        self._prefetcher.put(feed)

    def _finish_run(self, fetches, return_numpy, fetch_every_n):
        """Shared async/sync return path (Executor.run and
        CompiledProgram._run): materialize at the sync points, otherwise
        admit the step to the in-flight window and hand back lazy fetch
        handles."""
        n = int(fetch_every_n or 0)
        if n > 1:
            self._fetch_tick += 1
            if self._fetch_tick % n:
                self._window.admit(fetches)
                return LazyFetchList(fetches)
        if return_numpy:
            out = [np.asarray(f) for f in fetches]
            # the newest step is now host-complete; device execution is
            # in-order, so every older in-flight step is too
            self._window.reset()
            return out
        self._window.admit(fetches)
        return LazyFetchList(fetches)

    def close(self):
        """Notify pservers this trainer is done (executor.py:453 parity —
        the server exits once every trainer completed), then drop caches,
        flushing deferred warnings and the in-flight window."""
        self.sync()
        if self._prefetcher is not None:
            self._prefetcher.close()
            self._prefetcher = None
        for compiled in self._cache.values():
            client = getattr(compiled, "_rpc_client", None)
            if client is not None:
                for ep in getattr(compiled, "_rpc_endpoints", ()):
                    client.complete(ep)
                client.close()
        self._cache.clear()

    def run(
        self,
        program=None,
        feed=None,
        fetch_list=None,
        feed_var_name="feed",
        fetch_var_name="fetch",
        scope=None,
        return_numpy=True,
        use_program_cache=True,
        fetch_every_n=None,
    ):
        """`fetch_every_n=N` keeps the loop asynchronous between sync
        points: only every Nth call materializes fetches (per
        `return_numpy`); the steps in between return LazyFetchList
        handles without touching the host link, bounded by the
        executor's in-flight window."""
        from .compiler import CompiledProgram

        if program is None:
            program = framework.default_main_program()
        if isinstance(program, CompiledProgram):
            return program._run(self, feed, fetch_list, scope, return_numpy,
                                fetch_every_n)
        rec = _metrics.enabled()
        t_run = time.perf_counter() if rec else 0.0
        feed = dict(feed or {})
        fetch_list = list(fetch_list or [])
        scope = scope if scope is not None else global_scope()

        # a transpiled pserver program: block serving (the reference's
        # ListenAndServOp::RunImpl never returns until shutdown)
        lsv = next((op for op in program.global_block().ops
                    if op.type == "listen_and_serv"), None)
        if lsv is not None:
            from .distributed_runtime import run_pserver

            run_pserver(program, scope, lsv.attrs["endpoint"])
            return []

        fetch_names = [
            v.name if isinstance(v, framework.Variable) else str(v)
            for v in fetch_list
        ]
        from . import ir_passes
        from .flags import flag

        with _exe_phase("prepare"):
            key = (
                id(program),
                program.version,
                _feed_signature(feed),
                tuple(fetch_names),
                bool(flag("check_nan_inf")),
                # the compile-time pass pipeline is part of the step identity:
                # toggling PTPU_NO_PROGRAM_OPT (or the program flipping
                # between train/inference shape) must not hit a stale entry.
                # The scope is NOT in the key: scope-bound compile artifacts
                # (baked constants, promoted dead inputs) self-heal through
                # ir_passes.state_fallback at state-read time
                ir_passes.pipeline_key(None, program),
            )
            compiled = (self._cache.get(key) if use_program_cache
                        else None)
        # substitute staged device copies only AFTER the cache key is
        # computed from the ORIGINAL feed: device_put canonicalizes some
        # dtypes, and a signature drift here would force a spurious
        # recompile of the identical program
        if self._prefetcher is not None:
            staged = self._prefetcher.take_if_match(feed)
            if staged is not None:
                feed = staged
        with _observability.step_scope():
            if compiled is None:
                # fault-injection hook (docs/RESILIENCE.md): the
                # `transient_compile` site raises a retryable error here
                # so the rollback-and-retry path is testable without a
                # real allocator failure
                from .resilience import maybe_inject_compile_fault

                maybe_inject_compile_fault()
                if rec:
                    _metrics.counter("compile_cache/miss").inc()
                # compile-time pass pipeline (docs/COMPILER_PASSES.md):
                # DCE/CSE/constant folding on a clone of the program;
                # PTPU_NO_PROGRAM_OPT=1 restores the unoptimized path
                run_program = program
                if ir_passes.pipeline_enabled():
                    with _tracing.span("optimize"):
                        run_program = ir_passes.optimize_for_execution(
                            program, fetch_names, scope)
                else:
                    # PTPU_NO_PROGRAM_OPT=1 skips the pipeline (and its
                    # per-pass verification) — PTPU_VERIFY_PASSES=1 must
                    # still check the program once per compile
                    from .analysis import maybe_verify

                    maybe_verify(program, tuple(fetch_names))
                with _tracing.span("lower"):
                    compiled = _CompiledStep(run_program, feed.keys(),
                                             fetch_names, scope)
                if use_program_cache:
                    self._cache[key] = compiled
                else:
                    # sync()/close() can never reach an uncached step, so
                    # its warnings must not defer past this run
                    compiled._deferred_warns.drain_every = 1
            elif rec:
                _metrics.counter("compile_cache/hit").inc()

            with jax.default_device(self.place.jax_device()):
                fetches = compiled.run(scope, feed)
        if rec:
            _metrics.counter("executor/feed_bytes").inc(
                _nbytes(feed.values()))
            _metrics.counter("executor/fetch_bytes").inc(_nbytes(fetches))
        t_finish = time.perf_counter() if rec else 0.0
        out = self._finish_run(fetches, return_numpy, fetch_every_n)
        if rec:
            # the host's share of the step: what is left of `run` is
            # where it blocks on the device (`_finish_run`: materialised
            # fetches, a full in-flight window; the sync point's warning
            # flush), so the clock stops before it
            _metrics.samples("executor/run_host_ms").add(
                (t_finish - t_run) * 1e3)
        if not isinstance(out, LazyFetchList):
            # a materializing run is already a sync point: flush pending
            # runtime warnings so the per-step-sync loop warns promptly
            compiled._deferred_warns.drain(compiled._warned)
        return out

    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           cursor=None, epochs=None):
        """Drive a whole Dataset through the program (parity: executor.py:851
        → C++ MultiTrainer/HogwildWorker trainer.h:71/C15). The reference's
        thread-per-core Hogwild becomes a reader thread pool over file
        shards (thread= here or dataset.set_thread) parsing on the host
        while the single jitted step owns the device;
        FLAGS_cpu_deterministic serializes emission to filelist order.

        `cursor` (a `data_plane.DatasetCursor`) switches to the
        checkpoint-resumable stream (docs/DATA_PLANE.md): batches start
        at the cursor's position, and the cursor — mirrored into the
        run scope's ``__data_cursor__`` as each batch is consumed — is
        what a later restore resumes the byte-identical stream from.
        `epochs` is the ABSOLUTE epoch bound of that stream (the
        `resumable_batches` contract); default = one pass from the
        cursor's current epoch, so a restored epoch-k cursor trains the
        rest of epoch k rather than silently yielding nothing.
        No cursor = the exact legacy path."""
        if dataset is None:
            raise ValueError("train_from_dataset needs a dataset")
        if thread:
            dataset.set_thread(thread)
        program = program or framework.default_main_program()
        fetch_list = list(fetch_list or [])
        fetch_names = [v.name if isinstance(v, framework.Variable) else str(v)
                       for v in fetch_list]
        step = 0
        last = None
        cursor_states = None
        if cursor is not None:
            from collections import deque

            from .core.scope import global_scope

            cursor_scope = scope if scope is not None else global_scope()
            if epochs is None:
                epochs = cursor.epoch + 1
            pair_stream = dataset._resumable_stream(cursor, epochs, None)
            cursor_states = deque()

            def _feeds():
                for feed, state in pair_stream:
                    cursor_states.append(state)
                    yield feed

            batches = _feeds()
        elif epochs is not None:
            raise ValueError("epochs= only applies to the cursor path; "
                             "re-run train_from_dataset per epoch on "
                             "the legacy stream")
        else:
            batches = (dataset._batches_prefetched()
                       if getattr(dataset, "_thread", 1) > 1
                       else dataset._batches())
        # sparse-embedding fast path (docs/RECOMMENDER.md): with
        # PTPU_EMBED_PREFETCH=1 and host-embedding lookups in the
        # program, batch t+1's ids are announced to a background gather
        # worker as the lookahead pulls them, and each step receives the
        # staged row buffer as ordinary feeds instead of paying the
        # in-step pure_callback pull. None = the exact legacy path.
        from .parallel.embedding_pipeline import maybe_pipeline

        embed_pipeline = maybe_pipeline(program)
        if embed_pipeline is not None:
            batches = embed_pipeline.announce_iter(batches)
        # H2D lookahead: while the device runs batch k, a background
        # thread device_puts batch k+1 (same contract as PyReader's
        # double buffer, here for the Dataset path)
        device_feeder = FeedPrefetcher(sharding_fn=self._feed_sharding)
        try:
            for feed in prefetch_iter(batches, device_feeder):
                if embed_pipeline is not None:
                    # coherence point: barrier on the prior steps'
                    # pushes, repair dirtied rows, merge staged arrays
                    feed = embed_pipeline.finalize_into(feed)
                if cursor_states is not None:
                    # consumption point: the lookahead above has already
                    # PULLED batch k+1, but the mirrored cursor may only
                    # advance as batch k is taken for its step — else a
                    # checkpoint would name a position one batch ahead
                    cursor.advance_to(*cursor_states.popleft())
                    cursor.write_to(cursor_scope)
                last = self.run(program, feed=feed, fetch_list=fetch_list,
                                scope=scope)
                step += 1
                if debug and fetch_names and step % print_period == 0:
                    info = fetch_info or fetch_names
                    print("step %d: %s" % (step, {
                        k: np.asarray(v).ravel()[:4]
                        for k, v in zip(info, last)}))
        finally:
            device_feeder.close()
            if embed_pipeline is not None:
                # detaches the program decoration too, so a later direct
                # exe.run compiles the legacy synchronous lookup again
                embed_pipeline.close()
        return last

    infer_from_dataset = train_from_dataset
