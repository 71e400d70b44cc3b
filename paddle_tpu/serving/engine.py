"""`ServingEngine` — the multi-model continuous-batching generation
service front end.

One process serves N models: each model gets an isolated
:class:`~paddle_tpu.core.scope.Scope` holding its weights, its own
blocked KV pool, scheduler, bounded request queue, and a worker thread
driving the fixed-shape decode step. ``submit()`` is thread-safe and
non-blocking (admission control raises :class:`AdmissionError` when the
queue is full); ``result()``/``request.wait()`` is the pull side and
``stream=`` callbacks are the push side.

Decode steps ride the PR-2 async machinery: the step's input token
vector chains on *device* from the previous step's output, so the worker
dispatches step ``k+1`` without materializing step ``k`` — an
``InflightWindow`` (``async_depth``, default ``$PTPU_SERVE_ASYNC_STEPS``
or 2: ONE step queued behind the running one) bounds the lag, and EOS
detection/streaming callbacks process the materialized tokens
``async_depth - 1`` steps behind dispatch. The queued steps hide the
host's tick from the token gap: a tick shorter than ``async_depth - 1``
steps never leaves the device idle (the step log's ``ran_dry`` says
where one did). They cost a new request's first step its place: it
stands behind the ``async_depth - 1`` steps dispatched before it (the
request log's ``ahead_ms``). Deterministic finishes
(``max_new_tokens``, the sequence-length cap) are known at dispatch
time, so the other cost of the lag is ``async_depth - 1`` discarded
speculative steps after an EOS (one, by default) — whose tokens are
never emitted (``record_token`` drops post-EOS outputs) and whose KV
writes land in blocks the retiring sequence still owns until ``reap``;
``swap_weights`` waits for as many to drain. With
speculative DECODING on (``spec_k`` below) the window collapses to one
step: every verify window is materialized before the next is planned,
so nothing is ever dispatched for a finished sequence, and rejected
draft positions are rolled back — the contract
``test_spec_no_post_eos_emission_and_kv_rolled_back`` pins.

Prefill is chunked (docs/SERVING.md): a tick with a row mid-prompt
dispatches the second compiled step shape — a ``[max_batch, chunk]``
window (``prefill_chunk`` / ``$PTPU_SERVE_PREFILL_CHUNK``) where prefill
rows consume whole prompt spans while decode rows ride along as 1-token
windows — with ``prefill_token_budget`` bounding the prompt tokens per
mixed step so decode latency stays bounded (where none is stated: four
chunks up to the 256 rows at which the weights' matmuls turn
compute-bound, never under one chunk,
``scheduler.default_prefill_token_budget``; handed to the prefilling
rows in admission order) and with it the token rows the ONE chunk
program is compiled for, ``max_batch`` + the budget; any other tick
dispatches the decode step, every row a window of one.
**Radix prefix caching** (opt-in: ``prefix_cache`` /
``$PTPU_SERVE_PREFIX_CACHE``) content-addresses the KV pool so requests
sharing a prompt prefix skip its prefill compute and block allocations.
Prefix reuse assumes the weights that computed the cached KV state:
weight hot-swaps go through :meth:`ServingEngine.swap_weights`, the ONE
atomic entry point — the worker pauses admission, drains its active
batch to a clean step boundary, then installs the new weights and
flushes the prefix cache in the same critical section under the worker
cv, so stale-prefix tokens can never leak across a swap and every
request's tokens come from exactly one weight version
(docs/SERVING.md "Online updates").

The other opt-in leg is **speculative decoding** (``spec_k`` /
``$PTPU_SERVE_SPEC_K``, 0 = off): when every row is
past its prompt, the engine dispatches a VERIFY window — each row's
last committed token plus up to ``spec_k`` tokens proposed by the
``drafter`` (n-gram prompt lookup by default; any object with
``propose(history, k)``, e.g. ``ModelDrafter``) — and the target's
argmax at all ``k+1`` positions decides per-row acceptance in ONE
step. Every window emits the accepted run plus a correction token
(never fewer tokens per step than plain decoding); rejected positions roll
back through ``KVBlockPool.truncate_owner``. Spec windows run
synchronously (the acceptance result feeds the next window's drafts),
trading the async-depth pipelining for multi-token steps.

Telemetry (the autoscaling surface, docs/OBSERVABILITY.md):
``serving/{queue_depth,batch_occupancy,peak_batch_occupancy,
kv_blocks_in_use,tokens_per_sec,request_latency(_p50/_p99),
ttft(_p50/_p99),steps,prefill_tokens,decode_tokens,prefill_chunk_steps,
prefix_blocks_reused,prefix_tokens_skipped,spec_steps,spec_proposed,
spec_accepted,spec_rejected,spec_accept_rate,prefill_rows_deferred,
mixed_one_token_rows,requests_submitted,requests_completed,requests_rejected,
requests_failed}``, and the two logs
the worker writes where the work happens (:class:`_TickLog`):
``serving/step``, one record per dispatched step, and
``serving/request``, one record per request that leaves the engine,
whose time to first token is the sum of its queue, plan, prefill,
in-flight and delivery phases (``_ModelWorker._request_record``).
"""

import threading
import time

import numpy as np

from .. import resilience as _resil
from ..analysis import concurrency as _conc
from ..core.scope import Scope
from ..observability import flight_recorder as _blackbox
from ..observability import metrics as _metrics
from ..observability import tracing as _tracing
from ..quant import weight_store_bytes as _weight_store_bytes
from .kv_cache import KVBlockPool, RowState, blocks_needed
from .model import GenerationModel, load_generation_artifact, store_leaf
from .scheduler import (AdmissionError, GenerationRequest, RequestQueue,
                        StepScheduler)

__all__ = ["ServingEngine"]


# the fields of a `serving/step` record that the registry dump and
# /metrics summarise
STEP_LOG_FIELDS = ("device_ms", "host_ms", "wait_ms", "rows_deferred")
# and of a `serving/request` record
REQUEST_LOG_FIELDS = ("ttft_ms", "queue_ms", "plan_ms", "prefill_ms",
                      "inflight_ms", "ahead_ms", "deliver_ms", "latency_ms")


def _ms(t0, t1):
    """Milliseconds from one stamp to another; None where either is."""
    return None if t0 is None or t1 is None else (t1 - t0) * 1e3


class _TickLog:
    """The host stamps of one scheduler tick, taken only while metrics
    or tracing are on (docs/OBSERVABILITY.md, "The serving step log").
    A tick dispatches at most one step (``opened``: its record, which
    gets this tick's host time) and consumes the result of at most one,
    dispatched ``async_depth - 1`` ticks earlier (``done``; ``waited``
    is the seconds this tick spent blocked on it): the tick before, by
    default. ``follows_dispatch``: the tick before this one (``before``,
    its log: None after an idle stretch, or where it did not record)
    dispatched a step too, so a device with nothing to run at this
    tick's dispatch ran dry under a working host (the record's
    ``ran_dry``)."""

    __slots__ = ("t_tick", "t_planned", "waited", "opened", "done",
                 "follows_dispatch")

    def __init__(self, before):
        self.t_tick = self.t_planned = time.perf_counter()
        self.waited = 0.0
        self.opened = None
        self.done = []
        self.follows_dispatch = (before is not None
                                 and before.opened is not None)


def _phase(tick, name):
    """The tick's phase on the profiler's clock (``ptpu/engine.<name>``
    on the host plane of a `jax.profiler` capture, beside the device
    rows) while the step log records; the shared null span otherwise."""
    if tick is None:
        return _tracing.NULL_SPAN
    return _tracing.annotation("ptpu/engine." + name)


def _decode_pipe_walked(block, sched, pool):
    """What the step just planned makes the page pipe of ``block``'s
    decode attention kernel do on its ONE-TOKEN rows, where that kernel
    takes a row's pages in runs it sizes itself and hands the pipe from
    row to row (``block.decode_pages_per_run``: the grouped-query
    blocks' ``gqa_paged_decode_attention``, the latent blocks'
    ``latent_paged_attention``; nothing for any other block), over
    every page kind's layers: ``decode_rows_walked`` (rows x layers: the
    kernel's live grid steps on such rows), ``decode_runs_walked``
    (their runs, ``ceil(pages / run)`` by the kernel's own rule) and
    ``decode_rows_opened_warm`` (those whose first run a one-token row
    before them started: every such row of a call but its first. A
    chunk's tail tile of one token, which the kernels also take, is not
    counted; nor is it that in the latent kernel, whose window rows
    share the grid, a window row opens the one-token row after it: a
    floor by at most one row a layer of a mixed step)."""
    run_rule = getattr(block, "decode_pages_per_run", None)
    if run_rule is None:
        return {}
    bs = pool.block_size
    on = sched.active & (sched.chunk_lens <= 1)
    pos = sched.positions[on].astype(np.int64)
    rows = int(on.sum())
    # every kind's pool has the entry's page: one rule a step
    per_run = run_rule(pool.arrays[0], sched.max_blocks_per_seq)
    layers = runs = 0
    for kind in pool.kinds:
        first = (0 if kind.window is None
                 else np.maximum(pos - kind.window + 1, 0) // bs)
        pages = pos // bs + 1 - first
        layers += len(kind.layers)
        runs += len(kind.layers) * int((-(-pages // per_run)).sum())
    return {"decode_rows_walked": layers * rows,
            "decode_runs_walked": runs,
            "decode_rows_opened_warm": layers * max(rows - 1, 0)}


class _ModelWorker:
    """Per-model serving state: isolated scope + pool + scheduler +
    decode loop thread."""

    def __init__(self, name, model, max_batch, max_seq_len, block_size,
                 num_blocks, max_queue, async_depth, engine,
                 prefill_chunk=None, prefix_cache=False,
                 prefill_token_budget=None, spec_k=0, drafter=None,
                 spec_tree=None, transient_tolerance=2):
        from .model import NGramDrafter, parse_tree_shape

        self.name = name
        self.model = model
        self.engine = engine
        cfg = model.config
        max_seq_len = min(int(max_seq_len), cfg.max_seq_len)
        kinds = model.page_kinds()
        row_state = model.row_state()
        if (prefix_cache or spec_k or spec_tree or drafter is not None):
            if row_state is not None:
                raise NotImplementedError(
                    "model %r carries a row state beside its pages: the "
                    "prefix cache and speculative, tree and draft "
                    "windows are not built with it (the carry at an "
                    "adopted page boundary, and a roll-back of it, do "
                    "not exist yet: ROADMAP R7)" % name)
            if kinds:
                raise NotImplementedError(
                    "model %r keeps pages of %d kinds (%s): the prefix "
                    "cache and speculative, tree and draft windows are "
                    "not built over released window pages (ROADMAP "
                    "Queue 2a)"
                    % (name, len(kinds), ", ".join(k.name for k in kinds)))
        if not isinstance(num_blocks, dict):
            # default: enough cache for every slot to run a full-length
            # sequence concurrently (no admission stalls from the pool)
            full = max_batch * blocks_needed(max_seq_len, block_size)
            first = full if num_blocks is None else num_blocks
            # an int (or nothing) sizes the kind that keeps every
            # position; a window kind then never gates admission
            num_blocks = {k.name: first if k.window is None else full
                          for k in kinds} if kinds else first
        # the model says what one token's cache entry is, and what a
        # batch row carries beside its pages; the pool's accounting is
        # the same for every entry
        self.pool = KVBlockPool(
            cfg.n_layers, cfg.n_heads, cfg.head_dim, block_size,
            num_blocks, entry=model.cache_entry(), kinds=kinds,
            row_state=row_state and RowState(max_batch, *row_state))
        # a model that NAMES its page kinds, be it one, gets the step
        # log's fields by kind (`_pages_walked_by_kind`)
        self._kinds_named = bool(kinds)
        self.prefix_cache = bool(prefix_cache)
        # speculative decoding: the verify window is a compiled shape,
        # clamped so a full window always fits the context. A tree
        # shape (PTPU_SERVE_SPEC_TREE) implies speculation — its depth
        # plays spec_k's role and the verify window becomes the
        # level-order token tree
        self.spec_tree = parse_tree_shape(spec_tree)
        if self.spec_tree:
            width, depth = self.spec_tree
            depth = max(1, min(depth, max_seq_len - 1))
            self.spec_tree = (width, depth)
            self.spec_k = depth
        else:
            self.spec_k = max(0, min(int(spec_k or 0), max_seq_len - 1))
        if self.spec_k and drafter is None:
            drafter = NGramDrafter()
        if drafter is not None and not callable(
                getattr(drafter, "propose", None)):
            raise TypeError(
                "drafter %r has no propose(history, k) method"
                % (type(drafter).__name__,))
        self.drafter = drafter if self.spec_k else None
        if self.drafter is not None and hasattr(self.drafter, "bind"):
            # jitted ModelDrafter: size its draft-side KV pool/batch
            # geometry once, up front
            self.drafter.bind(max_batch, self.spec_k)
        # the scheduler settles the chunk's size and the prefill budget
        # of a mixed step (scheduler.DEFAULT_PREFILL_CHUNK,
        # default_prefill_token_budget: four chunks up to the ridge)
        self.scheduler = StepScheduler(
            max_batch, self.pool, max_seq_len,
            prefill_chunk=prefill_chunk,
            prefix_cache=self.prefix_cache,
            prefill_token_budget=prefill_token_budget,
            cache_namespace=name, spec_k=self.spec_k,
            drafter=self.drafter, spec_tree=self.spec_tree)
        self.prefill_chunk = self.scheduler.prefill_chunk
        self.queue = RequestQueue(max_queue)
        self.max_batch = int(max_batch)
        # bounded in-flight step lag (the PR-2 InflightWindow contract,
        # with the per-step scheduling plan riding each admitted handle
        # so lagged processing can fold tokens back into sequences)
        self.async_depth = max(1, int(async_depth))
        # [(next_tokens_handle, plan, step-log record or None)], FIFO
        self._inflight = []
        # this tick's _TickLog while recording (between ticks the last
        # tick's; None again once the worker idles)
        self._tick_log = None
        # (step, t_ready, t_ready is its completion) of the last record
        self._last_consumed = None

        # isolated per-model scope: the weights the step consumes are
        # read from here each dispatch, so hot-swapping an entry (or
        # inspecting one) goes through the same surface training uses
        self.scope = Scope()
        for wname, val in model.weights.items():
            self.scope.set(wname, val)
        self._weight_names = list(model.weights)

        # the two compiled shapes: the decode step (every row a window
        # of one) and the mixed prefill/decode window. jit is lazy, so
        # an engine that never sees a prompt mid-flight traces one.
        self._step = model.make_decode_step(
            self.max_batch, self.scheduler.max_blocks_per_seq)
        # a mixed step holds at most one token a row plus the
        # scheduler's prefill budget: a block that computes the window's
        # real tokens only runs that many rows
        max_tokens = self.max_batch + self.scheduler.prefill_token_budget
        self._chunk_step = model.make_prefill_step(
            self.max_batch, self.scheduler.max_blocks_per_seq,
            self.prefill_chunk, max_tokens=max_tokens)
        # the token rows a mixed step computes (the step log's
        # `rows_computed`): the promise, or the window's slots where
        # those are fewer
        self._chunk_rows = min(self.max_batch * self.prefill_chunk,
                               max_tokens)
        # the speculative verify window (third compiled shape; jit is
        # lazy, so geometry that never speculates still traces nothing).
        # Tree mode swaps in the tree verify window plus the tiny
        # post-acceptance KV compaction step.
        if self.spec_tree:
            width, depth = self.spec_tree
            self._spec_step = model.make_spec_tree_step(
                self.max_batch, self.scheduler.max_blocks_per_seq,
                width, depth)
            self._tree_commit = model.make_tree_commit_step(
                self.max_batch, self.scheduler.max_blocks_per_seq,
                1 + width * depth)
        else:
            self._spec_step = (
                model.make_spec_step(self.max_batch,
                                     self.scheduler.max_blocks_per_seq,
                                     self.spec_k + 1)
                if self.spec_k else None)
            self._tree_commit = None
        self.spec_tree_commits = 0  # host-side (live with metrics off)
        import jax.numpy as jnp

        self._prev_tokens = jnp.zeros((self.max_batch,), jnp.int32)
        # the decode step's prompt operands (prompt_feed, use_prompt)
        # stay all-false: a prompt token always goes through the window
        self._no_prompt = (jnp.zeros((self.max_batch,), jnp.int32),
                           jnp.zeros((self.max_batch,), bool))

        # named lock site (docs/STATIC_ANALYSIS.md): tracked under
        # PTPU_LOCK_CHECK=1, a plain Condition otherwise; the same flag
        # turns on the pool/engine invariant audit at step boundaries
        self._cv = _conc.make_condition("serving.engine.cv")
        self._lock_check = _conc.tracking_enabled()
        self._closing = False
        self.error = None
        # online-update surface (docs/SERVING.md "Online updates"): a
        # pending swap pauses admission; the worker applies it at the
        # first step boundary with no active or in-flight sequences,
        # so no request's tokens ever span two weight versions
        self.weight_version = 0
        self._pending_swap = None  # [weights, version, event, result]
        # failover surface (docs/SERVING.md "Fleet & failover"): abort()
        # injects a fatal error at the next step boundary (or into an
        # injected stall) so a router-declared-dead replica drains its
        # pool through the normal death path; the transient counters
        # feed the router's health state machine
        self._abort_error = None
        self.transient_tolerance = max(0, int(transient_tolerance))
        self._consec_transient = 0
        self._transient_retries = 0  # host-side (live with metrics off)
        # flipped by the first deadline-carrying submit: the deadline
        # scan never runs on a deadline-free engine
        self._track_deadlines = False
        self._tick_retryable = False
        self._gen_tokens = 0
        self._steps_dispatched = 0  # host-side (live with metrics off)
        self._t_first_step = None
        self._t_last_step = None
        self._thread = threading.Thread(
            target=self._run, name="ptpu-serve-%s" % name, daemon=True)
        self._thread.start()

    # -- submission side -----------------------------------------------
    def submit(self, request):
        # the scheduler's own admission budget (incl. the tree-window
        # overhang) — delegating keeps the two checks mirrored, so a
        # submittable request can never deadlock the head of the queue
        worst = self.scheduler._budget_for(request)
        if not self.pool.could_hold(worst):
            raise AdmissionError(
                "request needs %s KV blocks but the pool holds %s — "
                "shorten the request or grow num_blocks"
                % (worst, self.pool.kind_totals()))
        # the liveness checks and the enqueue are one atomic region
        # under the worker's condition lock: the worker only exits (or
        # drains the queue on death) while holding the same lock, so a
        # request can never land in a queue nobody will ever pop
        with self._cv:
            if self._closing:
                raise RuntimeError("ServingEngine is closed")
            if self.error is not None:
                raise RuntimeError("serving worker %r died: %r"
                                   % (self.name, self.error))
            if request.deadline is not None:
                self._track_deadlines = True
            self.queue.submit(request)
            self._cv.notify()
        _metrics.counter("serving/requests_submitted").inc()
        _metrics.gauge("serving/queue_depth").set(len(self.queue))
        return request

    # -- failover surface ----------------------------------------------
    def abort(self, error):
        """Inject a fatal error into the worker: it raises at the next
        step boundary (or out of an injected stall) and dies through
        the normal drain path — fail_all + queue drain, KV pool left
        fully drained. The router's watchdog uses this to put down a
        stalled replica; idempotent once dead or already aborted."""
        with self._cv:
            if self.error is None and self._abort_error is None:
                self._abort_error = error
            self._cv.notify_all()

    # -- decode loop ----------------------------------------------------
    def _run(self):
        try:
            while True:
                with self._cv:
                    while (self._abort_error is None
                           and not self._closing
                           and self._pending_swap is None
                           and not len(self.queue)
                           and not self.scheduler.has_work()
                           and not self._inflight):
                        # an idle stretch: the next step follows no tick
                        self._tick_log = None
                        self._cv.wait(timeout=0.1)
                    abort = self._abort_error
                    if (abort is None and self._closing
                            and not len(self.queue)
                            and not self.scheduler.has_work()
                            and not self._inflight):
                        self._fail_pending_swap(RuntimeError(
                            "ServingEngine closed with a weight swap "
                            "pending"))
                        return
                if abort is not None:
                    raise abort
                if (self._pending_swap is not None
                        and not self.scheduler.has_work()
                        and not self._inflight):
                    # clean step boundary, batch drained: install the
                    # new weights and flush the prefix cache in ONE
                    # critical section, then resume admission
                    self._apply_swap()
                    continue
                try:
                    self._tick()
                    self._consec_transient = 0
                except Exception as e:
                    # a transient failure raised BEFORE any
                    # scheduler/pool mutation (the injection/admission
                    # window — the step boundary is still consistent)
                    # is retried in place, a bounded number of
                    # consecutive times; anything else — non-transient,
                    # mid-dispatch, or tolerance spent — is replica
                    # death and the router's failover problem
                    if (self._tick_retryable
                            and _resil.is_transient_error(e)
                            and self._consec_transient
                            < self.transient_tolerance):
                        self._consec_transient += 1
                        self._transient_retries += 1
                        _metrics.counter(
                            "serving/step_transient_retries").inc()
                        _blackbox.record_event(
                            "step_transient_retry", model=self.name,
                            step=self._steps_dispatched, error=repr(e))
                        continue
                    raise
        except BaseException as e:  # deliver, don't vanish: EVERYTHING
            # escaping the loop — a tick, the wait/liveness block, an
            # abort — latches the error and drains, so submit() can
            # never feed a queue nobody will pop
            self._die(e)

    def _fail_pending_swap(self, error):
        """Deliver a never-applied swap's failure to its waiter (cv
        held by the caller): death and close must not strand a
        swap_weights() caller on its event forever."""
        if self._pending_swap is None:
            return
        swap = self._pending_swap
        self._pending_swap = None
        swap[3]["error"] = error
        swap[2].set()

    def _apply_swap(self):
        """Install a pending weight swap at a clean step boundary (no
        active or in-flight sequences — _run checked): new weights and
        the prefix-cache flush land in ONE cv critical section, so no
        step can read swapped weights against a stale prefix index and
        no token is ever computed by a half-installed weight set."""
        with self._cv:
            if self._pending_swap is None:
                return
            weights, version, done, result = self._pending_swap
            self._pending_swap = None
            # each leaf in the dtype it is SERVED in (a float32 source
            # onto a bfloat16 leaf is rounded as the store rounds):
            # the compiled steps are keyed on their arguments' dtypes,
            # so any other would retrace and compile in the serving path
            for wname in self._weight_names:
                self.scope.set(wname, store_leaf(
                    weights[wname], self.scope.get(wname).dtype))
            flushed = self.pool.flush_prefix_cache()
            self.weight_version = version
            result["applied"] = True
            result["flushed"] = flushed
            done.set()
        _metrics.counter("online/swaps").inc()
        _blackbox.record_event("weight_swap", model=self.name,
                               version=version, flushed=flushed,
                               step=self._steps_dispatched)

    def _die(self, e):
        """Replica death: error latch + fail_all + queue drain run under
        the cv lock so they are atomic with submit()'s liveness check
        (no request can slip into the queue between the drain and the
        latch)."""
        with self._cv:
            self.error = e
            self._fail_pending_swap(e)
            self.scheduler.fail_all(e)
            while True:
                req = self.queue.pop()
                if req is None:
                    break
                req._finish(e)
                _metrics.counter("serving/requests_failed").inc()
                self.scheduler._note_departed(req, None, "failed")
            self._close_requests()
        # black box: the uncaught-worker-death dump trigger — recorded
        # AFTER the cv region (dump does file I/O; the ring lock is the
        # only lock it takes)
        _blackbox.record_event("worker_dead", model=self.name,
                               error=repr(e),
                               steps=self._steps_dispatched)
        _blackbox.dump("worker_dead")

    def _stall(self):
        """Injected step stall (`serve_stall_at_step`): stop making
        progress WITHOUT raising — the wedged-replica failure mode an
        exception cannot model — until the router's watchdog aborts
        this replica or the engine closes, then die through the normal
        drain path."""
        while self._abort_error is None and not self._closing:
            time.sleep(0.005)
        raise (self._abort_error
               or RuntimeError("stalled serving worker %r closed while "
                               "wedged" % self.name))

    def _tick(self):
        """One scheduler round: admit at the boundary, dispatch one
        fixed-shape step (the speculative verify window when every row
        is past its prompt, else the mixed chunk shape whenever a row
        is mid-prompt and the decode shape otherwise), lag-process
        materialized tokens, retire."""
        # everything up to step planning leaves the scheduler/pool state
        # consistent, so a transient failure in this window is retried
        # in place by _run (the fault-injection sites fire here — BEFORE
        # any mutation — for exactly that reason)
        tick = self._tick_log = (
            _TickLog(self._tick_log)
            if _metrics.enabled() or _tracing.enabled() else None)
        sched = self.scheduler
        plan = kind = None
        with _phase(tick, "plan"):
            self._tick_retryable = True
            fault = _resil.maybe_inject_serve_fault(self._steps_dispatched)
            if fault == "stall":
                self._stall()
            if self._track_deadlines:
                sched.expire_deadlines(self.queue)
            if self._pending_swap is None:
                # a pending swap pauses admission so the active batch
                # drains to the clean boundary the swap needs; queued
                # requests wait and are served wholly on the new weights
                sched.admit(self.queue)
            _metrics.gauge("serving/queue_depth").set(len(self.queue))
            self._tick_retryable = False
            spec_plan = sched.plan_spec() if self.spec_k else None
            if not spec_plan:
                plan, kind = sched.plan_step()
        if tick is not None:
            tick.t_planned = time.perf_counter()
        if spec_plan:
            # verify window: dispatched AND materialized in one round
            # (acceptance feeds the next window's drafts)
            self._dispatch_spec(spec_plan)
        elif plan:
            self._dispatch(plan, kind)
            if self.spec_k:
                # spec mode is synchronous everywhere: the next
                # plan (a verify window) reads committed history
                while self._inflight:
                    self._process_oldest()
            elif len(self._inflight) > self.async_depth - 1:
                self._process_oldest()
        elif self._inflight:
            # nothing left to dispatch — drain the pipeline
            self._process_oldest()
        sched.reap()
        _metrics.gauge("serving/kv_blocks_in_use").set(
            self.pool.blocks_in_use)
        if self._lock_check:
            self._check_invariants()
        if tick is not None:
            self._close_tick(tick)
        if sched.departed:
            self._close_requests()

    def _check_invariants(self):
        """Step-boundary runtime audit (PTPU_LOCK_CHECK=1 only): the
        pool's conservation/refcount/index invariants plus the engine's
        own queue/liveness bounds, reported as structured concurrency
        violations (docs/STATIC_ANALYSIS.md) so the CI `race` stage can
        gate `concurrency/violations == 0`."""
        import re as _re

        pool_dirty = False
        for msg in self.pool.check_invariants():
            # detail = the digit-stripped problem class per model, so
            # two DIFFERENT corruption kinds on one pool both report
            # while a recurring one (counts evolving per tick) doesn't
            # spam a violation per step
            pool_dirty = True
            _conc.record_violation(
                "pool-invariant", "KVBlockPool[%s]: %s" % (self.name, msg),
                locks=("serving.kv_pool",),
                detail=(self.name, _re.sub(r"\d+", "N", msg)))
            _blackbox.record_event("pool_invariant_violation",
                                   model=self.name, message=msg)
        if pool_dirty:
            _blackbox.dump("invariant_violation")
        if len(self._inflight) > self.async_depth:
            _conc.record_violation(
                "engine-invariant",
                "model %r: %d in-flight steps exceed async_depth %d"
                % (self.name, len(self._inflight), self.async_depth),
                locks=("serving.engine.cv",),
                detail=(self.name, "inflight"))
        if self.spec_k and self._inflight:
            # the spec contract: every window materializes before the
            # next plan — a step left in flight would let a post-EOS
            # window dispatch (docs/SERVING.md)
            _conc.record_violation(
                "engine-invariant",
                "model %r: %d steps in flight with spec_k=%d (spec "
                "windows must run synchronously)"
                % (self.name, len(self._inflight), self.spec_k),
                locks=("serving.engine.cv",),
                detail=(self.name, "spec-inflight"))
        if len(self.queue) > self.queue.max_queue:
            _conc.record_violation(
                "engine-invariant",
                "model %r: queue depth %d exceeds bound %d"
                % (self.name, len(self.queue), self.queue.max_queue),
                locks=("serving.request_queue",),
                detail=(self.name, "queue-depth"))
        occupied = self.scheduler.num_occupied
        if occupied > self.max_batch:
            _conc.record_violation(
                "engine-invariant",
                "model %r: %d occupied slots exceed max_batch %d"
                % (self.name, occupied, self.max_batch),
                locks=("serving.engine.cv",),
                detail=(self.name, "occupancy"))
        _conc.publish_metrics()

    # -- the step log ---------------------------------------------------
    def _open_record(self, tick, kind, rows, prefill_tokens, decode_tokens,
                     slots_used, slots_total, traces0):
        """The record of the step just dispatched: its dispatch side.
        `_consume_record` adds the other side when the step's result is
        taken, `_close_tick` this tick's host time."""
        tick.opened = rec = {
            "model": self.name, "step": self._steps_dispatched,
            "kind": kind, "rows": rows,
            "prefill_tokens": prefill_tokens,
            "decode_tokens": decode_tokens,
            "slots_used": slots_used, "slots_total": slots_total,
            # steps dispatched before it whose results the host had not
            # consumed yet: what may still be ahead of it on the device
            "queued": len(self._inflight),
            # the device had nothing left to run although the host was
            # working: the tick before dispatched too, and the newest
            # step in flight was done already (the device runs them in
            # order, so that one says it for all), or none was in flight
            "ran_dry": tick.follows_dispatch and (
                not self._inflight or self._inflight[-1][0].is_ready()),
            # the dispatch call traced or compiled (the first step of
            # each shape): its times are not a warm step's
            "cold": self.model.trace_count != traces0,
            "t_tick": tick.t_tick, "t_planned": tick.t_planned,
            "t_dispatched": time.perf_counter()}
        return rec

    def _materialize(self, tick, handle):
        """A step's result on the host, and while the log records the
        wait's stamps: (array, (was_ready, t_wait, t_ready) or None).
        `was_ready`: the result was there before the host asked, so
        `t_ready` is not the step's completion."""
        if tick is None:
            return np.asarray(handle), None
        was_ready = handle.is_ready()
        with _phase(tick, "wait"):
            t_wait = time.perf_counter()
            out = np.asarray(handle)
            t_ready = time.perf_counter()
        return out, (was_ready, t_wait, t_ready)

    def _consume_record(self, tick, rec, waited):
        """The consuming side, stamped once the step's tokens are
        recorded and its stream callbacks have returned. The device
        works through the steps in dispatch order, so while the host
        was blocked on this result (`was_ready` false) `t_ready` is the
        step's completion, and the step began when the one before it
        completed, or when it was dispatched if nothing was queued
        ahead of it: with steps queued and the device never idle,
        `device_ms` is the device's own time for the step. It is None
        where either end is not a completion the host saw."""
        was_ready, t_wait, t_ready = waited
        rec["t_wait"], rec["t_ready"] = t_wait, t_ready
        if "_counters" in rec:
            # the block's device counters: the step that made them has
            # completed, so this is a few bytes over the host link
            rec.update(zip(self.model.step_counters,
                           (int(c) for c in np.asarray(
                               rec.pop("_counters")))))
        rec["t_done"] = time.perf_counter()
        rec["wait_ms"] = (t_ready - t_wait) * 1e3
        prev = self._last_consumed
        if not rec["queued"]:
            began = rec["t_dispatched"]
        elif prev is not None and prev[0] == rec["step"] - 1 and prev[2]:
            began = prev[1]
        else:
            began = None
        rec["device_ms"] = ((t_ready - began) * 1e3
                            if began is not None and not was_ready
                            else None)
        self._last_consumed = (rec["step"], t_ready, not was_ready)
        tick.waited += t_ready - t_wait
        tick.done.append(rec)

    def _close_tick(self, tick):
        """The tick's host time goes to the step it dispatched: the
        tick's wall time less its waits, whatever the host did in it
        (planning and dispatching that step, an earlier step's token
        and stream loop, reaping). A tick that only drains dispatched
        nothing, and its host time is in no record. Then the records
        whose results this tick consumed are complete (their own tick
        closed earlier, or just now) and are written."""
        t_end = time.perf_counter()
        rec = tick.opened
        if rec is not None:
            rec["t_end"] = t_end
            rec["tick_ms"] = (t_end - tick.t_tick) * 1e3
            rec["host_ms"] = (t_end - tick.t_tick - tick.waited) * 1e3
            # the step whose result this tick took, if it took one: the
            # wait and the stream loop inside `tick_ms` are that record's
            rec["consumed"] = tick.done[0]["step"] if tick.done else None
        if not tick.done:
            return
        log = _metrics.samples("serving/step", fields=STEP_LOG_FIELDS)
        traced = _tracing.enabled()
        for rec in tick.done:
            log.add(rec)
            if traced:
                fields = {k: v for k, v in rec.items()
                          if not k.startswith("t_")}
                _tracing.complete(
                    "serving_spec_step" if rec["kind"] == "spec"
                    else "serving_step", int(rec["t_tick"] * 1e9),
                    int(rec["t_dispatched"] * 1e9), **fields)
                _tracing.complete(
                    "serving_wait", int(rec["t_wait"] * 1e9),
                    int(rec["t_ready"] * 1e9), model=self.name,
                    step=rec["step"])

    # -- the request log ------------------------------------------------
    def _close_requests(self):
        """Write one `serving/request` record for each request that left
        since the last call (the scheduler's `departed`). Called once
        the tick's step records are complete: the step that gave a
        request's first token was consumed in this tick or an earlier
        one, so its `t_ready` and `device_ms` are there to read."""
        departed = self.scheduler.departed
        log = _metrics.samples("serving/request", fields=REQUEST_LOG_FIELDS)
        for request, noted, outcome in departed:
            log.add(self._request_record(request, noted, outcome))
        del departed[:]

    def _request_record(self, request, noted, outcome):
        """One request's record (docs/OBSERVABILITY.md, "The serving
        request log"). Every stamp is one that was taken where the thing
        happened: the request's own (`submit_time`, `start_time` in
        `StepScheduler.admit`, `first_token_time` in `record_token`,
        `finish_time`) and the `t_dispatched` / `t_ready` of the step
        records that carried it (`noted`, its `_RequestLog`: an empty
        one where it never reached a slot). The five phases are
        differences of consecutive stamps and `ttft_ms` is their sum."""
        first, token = noted.first_rec, noted.token_rec
        t_submit, t_admit = request.submit_time, request.start_time
        t_first = first and first["t_dispatched"]
        t_last = token and token["t_dispatched"]
        # None until the step's result has been taken
        t_ready = token and token.get("t_ready")
        t_token, t_finish = request.first_token_time, request.finish_time
        phases = (_ms(t_submit, t_admit), _ms(t_admit, t_first),
                  _ms(t_first, t_last), _ms(t_last, t_ready),
                  _ms(t_ready, t_token))
        queue_ms, plan_ms, prefill_ms, inflight_ms, deliver_ms = phases
        device_ms = token and token.get("device_ms")
        return {
            "request": request.id, "model": self.name,
            "trace_id": request.trace_id, "outcome": outcome,
            "prompt_tokens": len(request.prompt),
            "output_tokens": len(request.tokens),
            # a step that carried it traced or compiled: its times are
            # not a warm request's
            "cold": noted.cold,
            "t_submit": t_submit, "t_admit": t_admit,
            "t_first_dispatch": t_first,
            "t_last_prefill_dispatch": t_last, "t_first_ready": t_ready,
            "t_first_token": t_token, "t_finish": t_finish,
            "first_step": first and first["step"],
            "first_token_step": token and token["step"],
            "last_step": noted.last_step,
            "prefill_steps": noted.prefill_steps,
            "deferred_steps": noted.deferred_steps,
            "queued_at_first_token": token and token["queued"],
            "gaps": noted.gaps, "gaps_mixed": noted.gaps_mixed,
            "gap_max_ms": noted.gap_max_ms,
            "gap_max_kind": noted.gap_max_kind,
            "queue_ms": queue_ms, "plan_ms": plan_ms,
            "prefill_ms": prefill_ms, "inflight_ms": inflight_ms,
            # what the first token's step stood behind steps queued
            # ahead of it: its time in flight less the device's own
            "ahead_ms": (None if inflight_ms is None or device_ms is None
                         else inflight_ms - device_ms),
            "deliver_ms": deliver_ms,
            # added in this order, left to right (`sum()` compensates
            # its rounding and can differ from it by an ulp)
            "ttft_ms": (None if None in phases else queue_ms + plan_ms
                        + prefill_ms + inflight_ms + deliver_ms),
            "latency_ms": _ms(t_submit, t_finish)}

    def _dispatch(self, plan, kind):
        mixed = kind == "mixed"
        sched = self.scheduler
        occupancy = int(sched.active.sum())
        tick = self._tick_log
        traces0 = self.model.trace_count
        with _phase(tick, "dispatch"):
            weights = {n: self.scope.get(n) for n in self._weight_names}
            # a step takes the pool's arrays (K and V, or one latent
            # array; then the row state, of a block that has one) and
            # returns them updated, then its tokens, then whatever
            # counters its block reduces on the device
            arrays = self.pool.step_arrays
            # one block table, or where the pool keeps pages of several
            # kinds the stack of them, a table a kind
            tables = (sched.block_tables if len(self.pool.kinds) == 1
                      else sched.kind_tables).copy()
            if mixed:
                out = self._chunk_step(
                    weights, *arrays,
                    sched.chunk_feed.copy(), sched.use_prompt.copy(),
                    self._prev_tokens, sched.positions.copy(),
                    sched.chunk_lens.copy(), tables, sched.active.copy())
            else:
                out = self._step(
                    weights, *arrays, *self._no_prompt,
                    self._prev_tokens, sched.positions.copy(), tables,
                    sched.active.copy())
            self.pool.step_arrays = out[:len(arrays)]
            next_tokens = out[len(arrays)]
            # then what the block's steps hand back beside their
            # tokens: its device counters, each token's own logit
            extras = list(out[len(arrays) + 1:])
            counters = extras.pop(0) if self.model.step_counters else None
            top_logits = (extras.pop(0) if self.model.returns_top_logit
                          else None)
        self._steps_dispatched += 1
        rec = None
        if tick is not None:
            n_prefill = int(sched.chunk_lens[sched.use_prompt].sum())
            n_decode = len(plan) - int(sched.use_prompt.sum())
            rec = self._open_record(
                tick, kind, occupancy, n_prefill, n_decode,
                n_prefill + n_decode,
                self.max_batch * (self.prefill_chunk if mixed else 1),
                traces0)
            # the context the step attends: each active row's position
            # after it (the sum of their lengths)
            rec["cached_tokens"] = int(
                ((sched.positions + sched.chunk_lens)
                 * sched.active).sum())
            # the step's weight stream as stored: its dot operands'
            # bytes and element count (the model's, known at build)
            rec["weight_bytes"] = self.model.dot_operand_bytes
            rec["weight_params"] = self.model.dot_operand_params
            if mixed:
                rec["rows_computed"] = self._chunk_rows
                # prefilling rows the budget left without a token
                rec["rows_deferred"] = sched.rows_deferred
                # rows holding ONE token (every decode row, a prompt's
                # last token): the tiles a decode kernel takes
                rec["one_token_rows"] = int(
                    (sched.chunk_lens[sched.active] == 1).sum())
            if self._kinds_named:
                rec.update(self._pages_walked_by_kind())
            else:
                # named kinds or not: what a decode kernel whose page
                # pipe hands over from row to row does on this step
                rec.update(_decode_pipe_walked(
                    self.model.config.block, sched, self.pool))
                if not mixed:
                    # the pages a decode kernel that walks each row's
                    # own pages copies, beside the grid steps of one
                    # that visits every table slot of every row
                    rec["pages_walked"] = int(
                        (sched.positions[sched.active]
                         // self.pool.block_size + 1).sum())
                    rec["pages_grid"] = (self.max_batch
                                         * sched.max_blocks_per_seq)
            if counters is not None:
                rec["_counters"] = counters      # read when consumed
            # request-scoped view of the same step, from the record's
            # own stamps into two sinks: each row's request log notes
            # the record, and a traced request gets one window event,
            # so its trace shows ITS prefill/decode activity, not just
            # engine steps
            traced = _tracing.enabled()
            if traced:
                t0 = int(rec["t_planned"] * 1e9)
                t1 = int(rec["t_dispatched"] * 1e9)
            for seq, gen_idx in plan:
                prefill = bool(sched.use_prompt[seq.slot])
                if seq.log is not None:
                    seq.log.dispatched(rec, prefill, gen_idx)
                tid = seq.request.trace_id
                if traced and tid is not None:
                    _tracing.complete(
                        "prefill_chunk" if prefill else "decode_window",
                        t0, t1, trace_id=tid, request=seq.request.id,
                        model=self.name)
        self._prev_tokens = next_tokens
        self._inflight.append((next_tokens, plan, rec, top_logits))
        _metrics.gauge("serving/inflight_steps").set(len(self._inflight))
        now = time.perf_counter()
        if self._t_first_step is None:
            self._t_first_step = now
        self._t_last_step = now
        if rec is not None and _metrics.enabled():
            reg = _metrics.registry()
            reg.counter("serving/steps").inc()
            reg.gauge("serving/batch_occupancy").set(occupancy)
            peak = reg.gauge("serving/peak_batch_occupancy")
            if occupancy > peak.value:
                peak.set(occupancy)
            if mixed:
                reg.counter("serving/prefill_chunk_steps").inc()
                reg.counter("serving/prefill_rows_deferred").inc(
                    rec["rows_deferred"])
                reg.counter("serving/mixed_one_token_rows").inc(
                    rec["one_token_rows"])
            reg.counter("serving/prefill_tokens").inc(
                rec["prefill_tokens"])
            reg.counter("serving/decode_tokens").inc(rec["decode_tokens"])

    def _pages_walked_by_kind(self):
        """What the step just planned makes the attention kernels walk,
        by page kind (the step log's ``<kind>_pages_walked``, summed
        over rows and the kind's layers): each active row's pages from the
        first its earliest query still sees to the page of its last
        token. ``window_pages_full`` is what the window kinds' walk
        would be from position 0 (it and ``window_keys_attended`` are 0
        where no kind has a window). ``<kind>_keys_attended``: the keys
        the rows' queries see between them, again over the kind's
        layers, which is the attention's arithmetic. ``chunk_pages_walked``
        and ``chunk_keys_attended``: the same two over every kind, of the
        rows that hold more than one token alone (a mixed step's chunk
        kernel; its one-token rows go through the decode kernel). And
        the decode kernel's page pipe (:func:`_decode_pipe_walked`)."""
        sched, bs = self.scheduler, self.pool.block_size
        on = sched.active
        pos0 = sched.positions[on].astype(np.int64)
        n = np.maximum(sched.chunk_lens[on].astype(np.int64), 1)
        last_page = (pos0 + n - 1) // bs
        full_keys = n * pos0 + n * (n + 1) // 2
        out = {"window_pages_full": 0, "window_keys_attended": 0,
               "chunk_pages_walked": 0, "chunk_keys_attended": 0}
        chunk = n > 1
        for kind in self.pool.kinds:
            layers = len(kind.layers)
            if kind.window is None:
                first, keys = 0, full_keys
            else:
                w = kind.window
                first = np.maximum(pos0 - w + 1, 0) // bs
                # query c sees min(pos0 + c + 1, w) keys
                ramp = np.clip(w - pos0, 0, n)   # queries that see all
                keys = (ramp * pos0 + ramp * (ramp + 1) // 2
                        + (n - ramp) * w)
                out["window_pages_full"] += layers * int(
                    (last_page + 1).sum())
            pages = last_page - first + 1
            out[kind.name + "_pages_walked"] = layers * int(pages.sum())
            out[kind.name + "_keys_attended"] = layers * int(keys.sum())
            out["chunk_pages_walked"] += layers * int(pages[chunk].sum())
            out["chunk_keys_attended"] += layers * int(keys[chunk].sum())
        out.update(_decode_pipe_walked(self.model.config.block, sched,
                                       self.pool))
        return out

    def _dispatch_spec(self, plan):
        """Dispatch one speculative verify window and fold it back
        immediately: per-row acceptance (and the next window's drafts)
        depend on the materialized tokens, so spec steps run
        synchronously — the tokens-per-step win replaces the
        async-depth pipelining (docs/SERVING.md)."""
        sched = self.scheduler
        occupancy = int(sched.active.sum())
        tick = self._tick_log
        traces0 = self.model.trace_count
        with _phase(tick, "dispatch"):
            weights = {n: self.scope.get(n) for n in self._weight_names}
            self.pool.k, self.pool.v, out = self._spec_step(
                weights, self.pool.k, self.pool.v,
                sched.spec_feed.copy(), sched.use_prompt.copy(),
                self._prev_tokens, sched.positions.copy(),
                sched.spec_lens.copy(), sched.block_tables.copy(),
                sched.active.copy())
        self._steps_dispatched += 1
        rec = None
        if tick is not None:
            rec = self._open_record(
                tick, "spec", occupancy, 0, occupancy,
                int(sched.spec_lens[sched.active].sum()),
                self.max_batch * sched.spec_feed.shape[1], traces0)
        # materialize NOW (the sync contract)
        outs, waited = self._materialize(tick, out)
        if rec is not None:
            # the same two sinks as `_dispatch`, a window's extent
            # reaching to its synchronous result
            traced = _tracing.enabled()
            t0, t1 = int(rec["t_planned"] * 1e9), int(waited[2] * 1e9)
            for seq, window in plan:
                if seq.log is not None:
                    seq.log.dispatched(rec, False, None)
                tid = seq.request.trace_id
                if traced and tid is not None:
                    _tracing.complete(
                        "spec_window", t0, t1, trace_id=tid,
                        request=seq.request.id, model=self.name,
                        window=len(window))
        now = time.perf_counter()
        if self._t_first_step is None:
            self._t_first_step = now
        self._t_last_step = now
        with _phase(tick, "stream"):
            n_emitted = self._fold_spec(plan, outs, rec)
        self._gen_tokens += n_emitted
        if (self._t_first_step is not None
                and self._t_last_step > self._t_first_step):
            _metrics.gauge("serving/tokens_per_sec").set(
                self._gen_tokens
                / (self._t_last_step - self._t_first_step))
        if rec is not None:
            # a window's decode tokens are what it emitted (known only
            # now): the accepted run plus the correction token
            rec["decode_tokens"] = n_emitted
            self._consume_record(tick, rec, waited)
        if _metrics.enabled():
            reg = _metrics.registry()
            reg.counter("serving/steps").inc()
            reg.gauge("serving/batch_occupancy").set(occupancy)
            peak = reg.gauge("serving/peak_batch_occupancy")
            if occupancy > peak.value:
                peak.set(occupancy)
            reg.counter("serving/decode_tokens").inc(n_emitted)
            reg.gauge("serving/spec_accept_rate").set(
                sched.spec_accepted / max(1, sched.spec_proposed))

    def _fold_spec(self, plan, outs, rec):
        """Fold a materialized verify window back into its sequences
        (acceptance, rollback, stream callbacks); returns the tokens
        emitted. `rec`: the window's step record while the log records,
        which each row's request log notes its tokens against."""
        import jax.numpy as jnp

        sched = self.scheduler
        n_emitted = 0
        # decode rows that later ride a mixed prefill step chain their
        # input from prev_tokens — re-point each spec row's entry at
        # its last COMMITTED token (the [B, W] window output replaced
        # the [B] chain this vector used to carry)
        prev = np.asarray(self._prev_tokens).copy()
        if self.spec_tree:
            from .scheduler import spec_tree_acceptance

            width = self.spec_tree[0]
            # host acceptance walk first; the accepted paths' KV must
            # be compacted into the committed slot layout BEFORE
            # record_spec_tree's truncate re-points the tail blocks
            # (the sources live in blocks the rollback may drop)
            acc = []
            commit_rows = []
            for seq, window in plan:
                path, emitted = spec_tree_acceptance(
                    window, outs[seq.slot], width)
                acc.append((seq, window, path, emitted))
                if path and any(s != j + 1 for j, s in enumerate(path)):
                    commit_rows.append((seq.slot, path))
            if commit_rows:
                C = sched.spec_feed.shape[1]
                src = np.zeros((self.max_batch, C), np.int32)
                n_commit = np.zeros(self.max_batch, np.int32)
                commit_active = np.zeros(self.max_batch, bool)
                for slot, path in commit_rows:
                    src[slot, 1:1 + len(path)] = path  # [0, path...]
                    n_commit[slot] = 1 + len(path)
                    commit_active[slot] = True
                self.pool.k, self.pool.v = self._tree_commit(
                    self.pool.k, self.pool.v,
                    jnp.asarray(sched.positions.copy()),
                    jnp.asarray(src), jnp.asarray(n_commit),
                    jnp.asarray(sched.block_tables.copy()),
                    jnp.asarray(commit_active))
                self.spec_tree_commits += 1
                _metrics.counter("serving/spec_tree_commits").inc()
            record = sched.record_spec_tree
            rows = [(seq, rest) for seq, *rest in acc]
        else:
            record = sched.record_spec
            rows = [(seq, (window, outs[seq.slot])) for seq, window in plan]
        for seq, args in rows:
            was_done = seq.request.finished
            n = record(seq, *args)
            n_emitted += n
            if n and seq.log is not None and rec is not None:
                seq.log.emitted(rec, time.perf_counter(), n)
            if seq.request.tokens:
                prev[seq.slot] = seq.request.tokens[-1]
            if seq.request.finished and not was_done:
                self._note_completion(seq.request)
        self._prev_tokens = jnp.asarray(prev)
        return n_emitted

    def _process_oldest(self):
        handle, plan, rec, top_logits = self._inflight.pop(0)
        _metrics.gauge("serving/inflight_steps").set(len(self._inflight))
        tick = self._tick_log if rec is not None else None
        tokens, waited = self._materialize(tick, handle)
        if top_logits is not None:
            # the same step made them: there once its tokens are
            top_logits = np.asarray(top_logits)
        with _phase(tick, "stream"):
            for seq, gen_idx in plan:
                request = seq.request
                was_done = request.finished
                n_before = len(request.tokens)
                self.scheduler.record_token(
                    seq, gen_idx, tokens[seq.slot],
                    None if top_logits is None else top_logits[seq.slot])
                if len(request.tokens) != n_before:
                    if not n_before:
                        self._note_first_token(request)
                    if seq.log is not None and rec is not None:
                        # the first token's stamp is the request's own
                        seq.log.emitted(
                            rec, time.perf_counter() if n_before
                            else request.first_token_time)
                if request.finished and not was_done:
                    self._note_completion(request)
        if tick is not None:
            self._consume_record(tick, rec, waited)
        if gen_tokens := sum(1 for _, g in plan if g is not None):
            self._gen_tokens += gen_tokens
            if (self._t_first_step is not None
                    and self._t_last_step > self._t_first_step):
                _metrics.gauge("serving/tokens_per_sec").set(
                    self._gen_tokens
                    / (self._t_last_step - self._t_first_step))

    def _note_first_token(self, request):
        """TTFT telemetry: submit-to-first-generated-token. The
        end-to-end request_latency can't see the prefill stall the
        chunked/prefix fast paths remove — this row can. Percentiles
        come from the histogram's own bucket-interpolated quantile()
        (one shared implementation; the old per-engine deque(1024)
        windows are retired), so the gauges cover the request's whole
        lifetime distribution."""
        ttft = request.ttft
        if ttft is None or not _metrics.enabled():
            return
        reg = _metrics.registry()
        h = reg.histogram("serving/ttft")
        h.observe(ttft)
        reg.gauge("serving/ttft_p50").set(h.quantile(0.50))
        reg.gauge("serving/ttft_p99").set(h.quantile(0.99))

    def _note_completion(self, request):
        _metrics.counter("serving/requests_completed").inc()
        lat = request.latency
        if lat is None:
            return
        if _metrics.enabled():
            reg = _metrics.registry()
            h = reg.histogram("serving/request_latency")
            h.observe(lat)
            reg.gauge("serving/request_latency_p50").set(h.quantile(0.50))
            reg.gauge("serving/request_latency_p99").set(h.quantile(0.99))

    # -- shutdown -------------------------------------------------------
    def close(self, timeout=30.0):
        with self._cv:
            self._closing = True
            self._cv.notify_all()
        self._thread.join(timeout)


def _resolve_swap_weights(source, worker):
    """Coerce a swap source (GenerationModel | Scope | dict | artifact
    dir) into the worker's weight layout, validated name-by-name
    against the served geometry — the compiled steps are weight-shape-
    keyed, so a swap can never change geometry, only values. Artifact
    dirs are digest-verified on load (a torn export never serves); an
    fp32 source is re-quantized when the worker serves the int8
    store. The values' dtypes are the source's: `_apply_swap` casts
    each to the dtype its leaf is served in as it installs it."""
    if isinstance(source, str):
        source = load_generation_artifact(source, name=worker.name)
    if isinstance(source, GenerationModel):
        if worker.model.weight_only_int8 and not source.weight_only_int8:
            source = source.quantized()
        weights = dict(source.weights)
    elif isinstance(source, Scope):
        weights = {n: source.get(n) for n in worker._weight_names}
    elif isinstance(source, dict):
        weights = source
    else:
        raise TypeError(
            "swap_weights wants a GenerationModel, Scope, weight dict "
            "or artifact directory, got %r" % (type(source).__name__,))
    out = {}
    for n in worker._weight_names:
        val = weights.get(n)
        if val is None:
            raise ValueError(
                "swap_weights: source has no weight %r for model %r "
                "(same-architecture weights required)"
                % (n, worker.name))
        cur = worker.scope.get(n)
        if cur is not None and np.shape(val) != np.shape(cur):
            raise ValueError(
                "swap_weights: weight %r shape %s != served shape %s "
                "for model %r — the compiled steps are weight-shape-"
                "keyed, so a swap cannot change geometry"
                % (n, np.shape(val), np.shape(cur), worker.name))
        out[n] = val
    return out


class ServingEngine:
    """Multi-model generation service (see module docstring).

    ``models`` is a single :class:`GenerationModel`, an artifact
    directory (written by ``inference.export_generation_model``), or a
    ``{name: model-or-artifact-dir}`` dict for multi-model serving.
    """

    def __init__(self, models, max_batch=8, max_seq_len=256,
                 block_size=16, num_blocks=None, max_queue=64,
                 async_depth=None, prefill_chunk=None, prefix_cache=None,
                 prefill_token_budget=None, spec_k=None, drafter=None,
                 spec_tree=None, deadline_s=None, transient_tolerance=2):
        from ..flags import env as _env

        if async_depth is None:
            async_depth = _env("PTPU_SERVE_ASYNC_STEPS")
        if prefill_chunk is None:
            prefill_chunk = _env("PTPU_SERVE_PREFILL_CHUNK")
        if prefix_cache is None:
            prefix_cache = bool(_env("PTPU_SERVE_PREFIX_CACHE"))
        if spec_k is None:
            spec_k = _env("PTPU_SERVE_SPEC_K")
        if spec_tree is None:
            spec_tree = _env("PTPU_SERVE_SPEC_TREE")
        draft_model = _env("PTPU_SERVE_DRAFT_MODEL")
        if deadline_s is None:
            deadline_s = _env("PTPU_SERVE_DEADLINE_S")
        self._deadline_s = deadline_s
        if not isinstance(models, dict):
            models = {"default": models}
        if not models:
            raise ValueError("ServingEngine needs at least one model")
        self._workers = {}
        for name, model in models.items():
            if isinstance(model, str):
                model = load_generation_artifact(model, name=name)
            if not isinstance(model, GenerationModel):
                raise TypeError(
                    "model %r must be a GenerationModel or an artifact "
                    "dir, got %r" % (name, type(model).__name__))
            worker_drafter = drafter
            if worker_drafter is None and draft_model:
                # env-configured jitted draft model: one ModelDrafter
                # per worker (drafter state — draft KV pool, per-seq
                # slots — must never be shared across worker threads)
                from .model import ModelDrafter

                worker_drafter = ModelDrafter(load_generation_artifact(
                    draft_model, name=name + ".draft"))
            self._workers[name] = _ModelWorker(
                name, model, max_batch=max_batch,
                max_seq_len=max_seq_len, block_size=block_size,
                num_blocks=num_blocks, max_queue=max_queue,
                async_depth=async_depth, engine=self,
                prefill_chunk=prefill_chunk, prefix_cache=prefix_cache,
                prefill_token_budget=prefill_token_budget,
                spec_k=spec_k, drafter=worker_drafter,
                spec_tree=spec_tree,
                transient_tolerance=transient_tolerance)
        self._default = next(iter(self._workers))
        self._closed = False
        # /healthz surface: registered only while the endpoint is
        # enabled, so a flag-off engine never lands in the provider dict
        # (and is never pinned live by it)
        self._health_key = None
        from ..observability import endpoint as _endpoint

        if _endpoint.enabled():
            self._health_key = "engine-%x" % id(self)
            _endpoint.register_health_provider(self._health_key,
                                               self._health_json)

    # -- public API -----------------------------------------------------
    @property
    def model_names(self):
        return list(self._workers)

    def model_scope(self, model=None):
        """The named model's isolated weight scope."""
        return self._workers[model or self._default].scope

    def row_state(self, model=None):
        """The named model's :class:`~paddle_tpu.serving.kv_cache.
        RowState` (None where its block carries none): what each batch
        row holds beside its pages. A row keeps its last occupant's
        entry (``GenerationRequest.slot`` names the row) until another
        sequence is admitted to it; the arrays are donated to every
        step, so read them only while no step is in flight (after
        :meth:`close`)."""
        return self._workers[model or self._default].pool.row_state

    def weight_version(self, model=None):
        """The named model's current weight version: 0 for the weights
        the engine was built with, bumped by every applied
        :meth:`swap_weights` (or set to that call's explicit
        ``version``). The version a request's tokens are attributable
        to (docs/SERVING.md \"Online updates\")."""
        return self._workers[model or self._default].weight_version

    def export_weights(self, model=None):
        """Host-side copy of the named model's CURRENTLY-served weights,
        keyed by canonical weight name — what an
        :class:`~paddle_tpu.serving.online.OnlineUpdater` captures as
        the incumbent source so a canary rollback has something
        concrete to swap back to. Taken under the worker cv so it can
        never observe a half-applied swap."""
        w = self._workers[model or self._default]
        with w._cv:
            return {n: np.asarray(w.scope.get(n)) for n in w._weight_names}

    def swap_weights(self, scope_or_artifact, model=None, version=None,
                     timeout=30.0):
        """Atomically hot-swap the named model's served weights — the
        ONE entry point replacing the old "hot-swap then call
        flush_prefix_cache()" comment contract with enforced behavior.

        ``scope_or_artifact`` is a :class:`GenerationModel`, a weight
        :class:`~paddle_tpu.core.scope.Scope`, a ``{name: array}``
        dict, or an exported artifact directory (digest-verified on
        load — a torn export raises
        :class:`~paddle_tpu.serving.GenerationArtifactError` and is
        never served). The worker pauses admission, drains its active
        batch to a clean step boundary, then installs the weights AND
        flushes the prefix cache in one critical section under the
        worker cv: stale-prefix tokens can never leak across the swap,
        and no request's tokens span two weight versions (queued
        requests wait and are served wholly on the new weights).

        Returns the new weight version (``version`` or the old
        version + 1). Raises ``TimeoutError`` if the batch does not
        drain within ``timeout`` seconds (the swap is cancelled), and
        ``RuntimeError`` if the worker dies first."""
        if self._closed:
            raise RuntimeError("ServingEngine is closed")
        name = model or self._default
        if name not in self._workers:
            raise KeyError("unknown model %r (have %r)"
                           % (name, list(self._workers)))
        w = self._workers[name]
        weights = _resolve_swap_weights(scope_or_artifact, w)
        done = threading.Event()
        result = {"applied": False, "error": None, "flushed": 0}
        with w._cv:
            if w.error is not None:
                raise RuntimeError("serving worker %r died: %r"
                                   % (name, w.error))
            if w._pending_swap is not None:
                raise RuntimeError(
                    "model %r already has a weight swap pending" % name)
            if version is None:
                version = w.weight_version + 1
            entry = [weights, int(version), done, result]
            w._pending_swap = entry
            w._cv.notify_all()
        if not done.wait(timeout):
            with w._cv:
                if w._pending_swap is entry:
                    w._pending_swap = None
                    raise TimeoutError(
                        "swap_weights for model %r not applied within "
                        "%.1fs (active batch still draining) — swap "
                        "cancelled" % (name, timeout))
            # lost the race: the worker picked it up while we timed
            # out — the event lands momentarily on either outcome
            done.wait(timeout)
        if not result["applied"]:
            raise RuntimeError(
                "serving worker %r failed before applying the swap: %r"
                % (name, result["error"]))
        return int(version)

    def submit(self, prompt, max_new_tokens=32, eos_id=None, stream=None,
               model=None, deadline_s=None):
        """Enqueue one generation request; returns the
        :class:`GenerationRequest` handle. Raises
        :class:`AdmissionError` when the model's queue is full.
        ``deadline_s`` (default: the engine's ``deadline_s`` /
        ``$PTPU_SERVE_DEADLINE_S``, unset = wait forever) fails the
        request with :class:`DeadlineExceededError` at the next step
        boundary once the wall-clock budget is spent."""
        if deadline_s is None:
            deadline_s = self._deadline_s
        # request identity is minted HERE (or by RouterRequest, which
        # passes one id through every failover attempt); with tracing
        # off the field stays None and no span carries it
        trace_id = _tracing.new_trace_id() if _tracing.enabled() else None
        request = GenerationRequest(prompt, max_new_tokens=max_new_tokens,
                                    eos_id=eos_id, stream=stream,
                                    model=model or self._default,
                                    deadline_s=deadline_s,
                                    trace_id=trace_id)
        # model-name validation lives in submit_request (one copy)
        return self.submit_request(request)

    def submit_request(self, request):
        """Enqueue a pre-built :class:`GenerationRequest` (the router's
        re-admission path builds the request first, so its stream and
        ``on_finish`` callbacks are attached before any token can
        flow). ``request.model`` picks the worker (None = default)."""
        if self._closed:
            raise RuntimeError("ServingEngine is closed")
        name = request.model or self._default
        if name not in self._workers:
            raise KeyError("unknown model %r (have %r)"
                           % (name, list(self._workers)))
        try:
            return self._workers[name].submit(request)
        except AdmissionError:
            _metrics.counter("serving/requests_rejected").inc()
            raise

    def result(self, request, timeout=None):
        """Block until `request` completed; returns its token list."""
        return request.wait(timeout)

    # -- fleet surface (docs/SERVING.md "Fleet & failover") -------------
    def load(self):
        """Instantaneous load for least-loaded routing: queued plus
        in-batch requests across models — the same quantity the
        ``serving/queue_depth`` + ``serving/batch_occupancy`` gauges
        record, read per engine."""
        return sum(len(w.queue) + w.scheduler.num_occupied
                   for w in self._workers.values())

    def health(self):
        """Per-model liveness/progress snapshot for an external
        watchdog (the :class:`~paddle_tpu.serving.router.ServingRouter`
        health state machine polls this): worker thread liveness, the
        latched death error, the dispatched-step counter (the stall
        watchdog's progress signal), whether work is pending, and the
        consecutive-transient-failure count."""
        out = {}
        for name, w in self._workers.items():
            out[name] = {
                "alive": w.error is None and w._thread.is_alive(),
                "error": w.error,
                "steps": w._steps_dispatched,
                "busy": bool(len(w.queue) or w.scheduler.has_work()
                             or w._inflight),
                "consecutive_transient_errors": w._consec_transient,
                "transient_retries": w._transient_retries,
            }
        return out

    def _health_json(self):
        """`health()` with the latched error stringified — the /healthz
        JSON body (exception objects don't serialize)."""
        models = {}
        for name, snap in self.health().items():
            snap = dict(snap)
            snap["error"] = (repr(snap["error"])
                             if snap["error"] is not None else None)
            models[name] = snap
        return {"models": models, "load": self.load()}

    def kill(self, error=None):
        """Put the whole engine down as a dead replica would go down:
        every worker aborts at its next step boundary (or out of an
        injected stall), failing in-flight and queued requests with
        ``error`` and draining its KV pool through ``fail_all``. New
        submits are refused. The failover path's teardown half — the
        router calls this when its watchdog declares a replica dead."""
        if error is None:
            error = RuntimeError("ServingEngine killed")
        self._closed = True
        for w in self._workers.values():
            w.abort(error)
        return error

    def generate(self, prompt, max_new_tokens=32, eos_id=None,
                 model=None, timeout=None):
        """Synchronous convenience: submit + wait."""
        return self.result(
            self.submit(prompt, max_new_tokens=max_new_tokens,
                        eos_id=eos_id, model=model), timeout)

    def stats(self):
        out = {}
        for name, w in self._workers.items():
            sched = w.scheduler
            out[name] = {
                "queue_depth": len(w.queue),
                "batch_occupancy": sched.num_occupied,
                "generated_tokens": w._gen_tokens,
                "steps": w._steps_dispatched,
                "prefill_chunk": w.prefill_chunk,
                "prefix_cache": w.prefix_cache,
                "prefix_blocks_reused": sched.prefix_blocks_reused,
                "prefix_tokens_skipped": sched.prefix_tokens_skipped,
                "spec_k": w.spec_k,
                "spec_tree": ("%dx%d" % w.spec_tree
                              if w.spec_tree else None),
                "spec_steps": sched.spec_steps,
                "spec_proposed": sched.spec_proposed,
                "spec_accepted": sched.spec_accepted,
                "spec_emitted": sched.spec_emitted,
                "spec_blocks_rolled_back":
                    sched.spec_blocks_rolled_back,
                "spec_tree_slots": sched.spec_tree_slots,
                "spec_tree_commits": w.spec_tree_commits,
                "spec_accept_rate": (sched.spec_accepted
                                     / max(1, sched.spec_proposed)),
                "spec_draft_steps": getattr(w.drafter, "draft_steps",
                                            0) if w.drafter else 0,
                "weight_version": w.weight_version,
                "weight_only_int8": w.model.weight_only_int8,
                "weight_store": _weight_store_bytes(w.model.weights),
                "deadline_expired": sched.deadline_expired,
                "transient_retries": w._transient_retries,
                **w.pool.stats(),
            }
        return out

    def close(self, timeout=30.0):
        """Drain outstanding requests and stop the worker threads."""
        if self._closed:
            return
        self._closed = True
        if self._health_key is not None:
            from ..observability import endpoint as _endpoint

            _endpoint.unregister_health_provider(self._health_key)
            self._health_key = None
        for w in self._workers.values():
            w.close(timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
