"""Asynchronous execution pipeline (the Fluid lineage's "dispatch step,
fetch results" loop, made real on TPU).

XLA dispatch is asynchronous: a jitted step returns device arrays that are
futures, and the host only stalls when something forces a host copy. The
seed executor threw that away by `np.asarray`-ing every fetch every step.
This module holds the pieces that keep N steps in flight end-to-end:

  LazyFetchList    — what `Executor.run(return_numpy=False)` (and every
                     `fetch_every_n` skipped step) returns: the fetches as
                     unmaterialized device futures. `as_numpy` (or
                     np.asarray on an element) is the ONE sync point.
  InflightWindow   — bounded count of dispatched-but-unsynced steps.
                     Admitting a step past the limit first materializes the
                     oldest step's fetches (host-transfer sync), so
                     device buffers can't grow without bound.
  FeedPrefetcher   — background thread that `jax.device_put`s the NEXT
                     batch (with its target sharding) while the current
                     step executes; preserves batch order; feeds the
                     `feed/h2d_bytes` / `feed/prefetch_depth` telemetry.
  DeferredWarns    — host-side accumulator for the packed runtime-warning
                     flags each step returns; materializes every few steps
                     instead of syncing the device every step.
  persistent cache — jax's on-disk compilation cache, on for every
                     compiling entry: at `JAX_COMPILATION_CACHE_DIR` when
                     that is set, else at `<checkout>/.jax_cache`;
                     `compile_cache/persistent_hit|miss` count jax's own
                     cache-read and cache-write events.

Sync-point contract (docs/ASYNC_EXECUTION.md): fetch values, scope state
and runtime warnings are only guaranteed observed after a sync — a
materialized fetch (`as_numpy`), a `fetch_every_n` boundary step, a
`return_numpy=True` run, `Executor.sync()`, or window backpressure.
Donated state buffers never alias a held fetch: XLA's copy insertion
gives every entry-computation output its own buffer, so a fetch handle
from step t stays valid (and keeps its step-t value) after step t+1
donates and overwrites the state — tests/test_async_exec.py pins this.
"""

import os
import queue as _queue
import threading

import numpy as np

from .observability import metrics as _metrics
from .observability import tracing as _tracing

__all__ = ["LazyFetchList", "InflightWindow", "FeedPrefetcher",
           "DeferredWarns", "HostStateStager", "as_numpy", "prefetch_iter",
           "setup_persistent_cache", "persistent_cache_dir"]


def as_numpy(value):
    """THE sync point: materialize device fetch values as numpy. Accepts a
    single value, a list/tuple of values, or a LazyFetchList."""
    if isinstance(value, (list, tuple)):
        return [np.asarray(v) for v in value]
    return np.asarray(value)


class LazyFetchList(list):
    """Fetch results that have NOT been synced to host. Elements are the
    raw device arrays — futures under XLA async dispatch — so any numpy
    coercion (np.asarray, float(...)) is the materialization point."""

    def as_numpy(self):
        return [np.asarray(v) for v in self]


_concurrency = None


def _note_blocking(kind, site):
    """Concurrency-analysis hook (docs/STATIC_ANALYSIS.md): declare a
    blocking operation so PTPU_LOCK_CHECK=1 can flag a tracked lock held
    across it. Resolved lazily (this module imports during package
    bootstrap, before `paddle_tpu.analysis` exists); a no-op dict hit
    when tracking is off."""
    global _concurrency
    if _concurrency is None:
        from .analysis import concurrency as _c

        _concurrency = _c
    _concurrency.check_blocking(kind, site)


def _materialize(token):
    """Force one admitted step's fetches to host: the host copy is the
    sync, and the caller wants the values on the host anyway."""
    _note_blocking("device-sync", "async_engine._materialize")
    if isinstance(token, (list, tuple)):
        for v in token:
            np.asarray(v)
    else:
        np.asarray(token)


class InflightWindow:
    """Bounded window of dispatched-but-unsynced steps (backpressure).

    `admit` registers one async step's fetch handles; when the window is
    full it first blocks on the OLDEST step, so at most `limit` steps of
    fetch/state buffers are ever pending on device. The
    `exec/inflight_steps` gauge records the window depth at each dispatch
    (it is deliberately not zeroed on sync — it reads as "how deep was
    the pipeline when a step was last dispatched")."""

    def __init__(self, limit=12):
        self.limit = max(1, int(limit))
        self._pending = []

    @property
    def depth(self):
        return len(self._pending)

    def admit(self, token):
        if token is None or (isinstance(token, (list, tuple))
                             and not token):
            return
        while len(self._pending) >= self.limit:
            _materialize(self._pending.pop(0))
        self._pending.append(token)
        _metrics.gauge("exec/inflight_steps").set(len(self._pending))

    def drain(self):
        """Block until every admitted step has materialized — the sync
        point behind Executor.sync(), resilience's preemption drain, and
        pre-checkpoint quiesce (docs/RESILIENCE.md)."""
        if not self._pending:
            return
        _metrics.counter("exec/window_drains").inc()
        with _tracing.span("window_drain", depth=len(self._pending)):
            while self._pending:
                _materialize(self._pending.pop(0))

    def reset(self):
        """Forget admitted steps without blocking — for callers that just
        synced the NEWEST step (device execution is in-order, so older
        steps are complete by then)."""
        del self._pending[:]


class DeferredWarns:
    """Deferred materialization for the per-step packed warning flags.

    The all-false common case must not cost a device sync per step, so
    each step's bool vector is merely kept (a device future); every
    `drain_every` steps — and at executor close/sync — the pending
    vectors are OR-reduced host-side and any newly-flagged label warns
    once. Labels are trace-static per compiled step, so every pending
    vector is congruent."""

    __slots__ = ("drain_every", "_labels", "_pending")

    def __init__(self, drain_every=8):
        self.drain_every = max(1, int(drain_every))
        self._labels = ()
        self._pending = []

    def add(self, labels, flags, warned):
        if not labels or not getattr(flags, "size", 0):
            return
        if all(label in warned for label in labels):
            return  # every label already fired: nothing left to observe
        self._labels = labels
        self._pending.append(flags)
        if len(self._pending) >= self.drain_every:
            self.drain(warned)

    def drain(self, warned):
        if not self._pending:
            return
        import warnings

        flagged = np.logical_or.reduce(
            [np.asarray(f) for f in self._pending])
        del self._pending[:]
        for label, hit in zip(self._labels, flagged):
            if hit and label not in warned:
                warned.add(label)
                warnings.warn(label, RuntimeWarning)


# ---------------------------------------------------------------------------
# feed prefetch
# ---------------------------------------------------------------------------


def _nbytes(vals):
    """Total buffer bytes across feed/fetch values without touching device
    memory (jax.Array.nbytes is shape metadata, not a transfer). The one
    byte-accounting helper behind executor/feed_bytes, executor/
    fetch_bytes and feed/h2d_bytes."""
    total = 0
    for v in vals:
        nb = getattr(v, "nbytes", None)
        if nb is not None:
            total += int(nb)
    return total


class FeedPrefetcher:
    """Background host->device double buffer for feed dicts.

    `put(feed)` hands a host batch to the worker thread, which
    `jax.device_put`s every value — with the target sharding from
    `sharding_fn(name, value)` when given (the compiled step's batch/seq
    sharding decision) — while the device executes the current step.
    `get()` returns staged batches strictly in put() order. At most
    `depth` batches are staged ahead (put() blocks past that — the same
    bounded-buffer contract as the in-flight window).

    `take_if_match(feed)` serves the raw feed-dict path: it returns the
    head staged batch only when it was built from exactly these value
    objects (identity match), so `Executor.prefetch(feed)` followed by
    `Executor.run(feed=feed)` transparently picks up the staged copy."""

    _CLOSE = object()

    def __init__(self, sharding_fn=None, depth=2, stage_fn=None):
        self._sharding_fn = sharding_fn
        self._stage_fn = stage_fn
        # unbounded queues + a slot semaphore: the WORKER never blocks
        # (so close() always reaches it), producers block in put() once
        # `depth` batches are staged ahead
        self._in = _queue.Queue()
        self._out = _queue.Queue()
        self._keys = _queue.Queue()
        self._slots = threading.Semaphore(max(1, int(depth)))
        self._thread = None
        self._closed = False

    # -- worker --------------------------------------------------------
    def _ensure_thread(self):
        if self._thread is None:
            t = threading.Thread(target=self._worker,
                                 name="ptpu-feed-prefetch", daemon=True)
            t.start()
            self._thread = t

    def _stage_one(self, name, value):
        if self._stage_fn is not None:
            return self._stage_fn(name, value)
        import jax

        if isinstance(value, jax.Array):
            return value  # already device-resident
        from .executor import check_feed_int64

        check_feed_int64(name, value)
        dt = getattr(value, "dtype", None)
        if dt is not None and np.dtype(dt) in (np.dtype(np.int64),
                                               np.dtype(np.uint64)):
            # keep 64-bit int slots host-side: device_put would
            # canonicalize them to int32 BEFORE the executor's declared-
            # dtype cast (and warn per batch); the step dispatch stages
            # them exactly as the unprefetched path does
            return value
        sharding = (self._sharding_fn(name, value)
                    if self._sharding_fn is not None else None)
        try:
            if sharding is not None:
                return jax.device_put(value, sharding)
            return jax.device_put(value)
        except (TypeError, ValueError):
            return value  # non-array feed entries pass through host-side

    def _worker(self):
        while True:
            item = self._in.get()
            if item is self._CLOSE:
                return
            try:
                staged = {k: self._stage_one(k, v) for k, v in item.items()}
                if _metrics.enabled():
                    _metrics.counter("feed/h2d_bytes").inc(
                        _nbytes(staged.values()))
                result = ("ok", staged)
            except BaseException as e:  # re-raised on the consumer side
                result = ("error", e)
            self._out.put(result)
            if _metrics.enabled():
                _metrics.gauge("feed/prefetch_depth").set(
                    self._out.qsize())

    # -- producer/consumer API -----------------------------------------
    def put(self, feed):
        """Queue one host feed dict for background staging. Blocks when
        `depth` batches are already staged ahead."""
        if self._closed:
            raise RuntimeError("FeedPrefetcher is closed")
        self._ensure_thread()
        _note_blocking("Semaphore.acquire", "feed_prefetcher.slots")
        self._slots.acquire()
        # strong refs to the SOURCE objects: identity matching via bare
        # id() would misfire when CPython reuses a freed array's address
        self._keys.put(dict(feed))
        self._in.put(dict(feed))

    def get(self):
        """Next staged device feed, in put() order."""
        _note_blocking("queue.get", "feed_prefetcher.out")
        self._keys.get()
        kind, payload = self._out.get()
        self._slots.release()
        if kind == "error":
            raise payload
        return payload

    def take_if_match(self, feed):
        """The head staged batch if it was built from exactly `feed`'s
        value objects; None otherwise (the staged queue is untouched)."""
        try:
            key = self._keys.queue[0]  # deque peek; GIL-atomic
        except IndexError:
            return None
        if len(key) != len(feed) or any(
                key.get(k) is not v for k, v in feed.items()):
            return None
        return self.get()

    def close(self):
        if self._closed:
            return
        self._closed = True
        if self._thread is not None:
            self._in.put(self._CLOSE)
            self._thread.join(timeout=5.0)


class HostStateStager:
    """Host<->device staging for host-offloaded optimizer state
    (docs/ZERO.md): m/v live in host RAM between steps; each step stages
    them to device for the sharded update and copies the updated shards
    back out.

    The H2D leg rides the FeedPrefetcher worker thread: `stage_in_begin`
    hands the host leaves to the worker (which `place_fn`s each one onto
    its target sharding) and returns immediately, so the transfer runs
    WHILE the backward/scatter jit — dispatched right after — executes;
    `stage_in_end` collects the staged device arrays at the point the
    update phase needs them. The D2H leg (`stage_out`) is a forced host
    copy (np.array — the same everywhere-reliable sync the in-flight
    window uses), which is also the step's optimizer-state sync point.
    Both directions count into the `counter` metric (zero/offload_bytes);
    the worker's own feed/h2d_bytes accounting sees the H2D leg too, as
    it is real host->device traffic."""

    def __init__(self, place_fn, counter="zero/offload_bytes"):
        self._prefetcher = FeedPrefetcher(
            stage_fn=lambda _name, value: place_fn(value))
        self._counter = counter
        self._pending_n = None

    def stage_in_begin(self, leaves):
        """Queue `leaves` (host arrays) for background placement."""
        if self._pending_n is not None:
            raise RuntimeError("stage_in_begin before the previous "
                               "stage_in_end was collected")
        self._pending_n = len(leaves)
        self._prefetcher.put({str(i): v for i, v in enumerate(leaves)})

    def stage_in_end(self):
        """The staged device arrays, in stage_in_begin order."""
        if self._pending_n is None:
            raise RuntimeError("stage_in_end without stage_in_begin")
        n, self._pending_n = self._pending_n, None
        staged = self._prefetcher.get()
        vals = [staged[str(i)] for i in range(n)]
        _metrics.counter(self._counter).inc(_nbytes(vals))
        return vals

    def abort(self):
        """Drop a begun-but-uncollected stage — error recovery for a
        caller whose compute phase failed between begin and end. The
        staged batch is collected and discarded so the worker slot frees
        and the next stage_in_begin starts clean. No-op when nothing is
        pending."""
        if self._pending_n is None:
            return
        self._pending_n = None
        try:
            self._prefetcher.get()
        except Exception:
            pass  # a staging error dies with the aborted step

    def stage_out(self, leaves):
        """Forced host copies of `leaves` (device arrays) — the D2H side.
        Blocks until the producing computation delivers."""
        out = [np.array(v) for v in leaves]
        _metrics.counter(self._counter).inc(_nbytes(out))
        return out

    def close(self):
        self._prefetcher.close()


def prefetch_iter(batches, prefetcher):
    """Drive `batches` (an iterable of host feed dicts) through a
    FeedPrefetcher with one-batch lookahead: while the consumer runs the
    step for batch k, the worker stages batch k+1's H2D transfer. Yields
    staged feeds in source order."""
    in_flight = 0
    for feed in batches:
        prefetcher.put(feed)
        in_flight += 1
        if in_flight >= 2:
            yield prefetcher.get()
            in_flight -= 1
    while in_flight:
        yield prefetcher.get()
        in_flight -= 1


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------

# the one fixed place the cache lives when nobody placed it from outside:
# <checkout>/.jax_cache (git-ignored). Never a temp name, pid or time —
# the path is part of jax's cache key, so a directory that moves never hits.
_DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")
_cache_ready = False


def _on_jax_event(event, **_kw):
    # jax's own receipts: a hit is an executable deserialized from disk,
    # a miss is a new entry written (compiles under jax's size/time
    # thresholds are neither)
    if event == "/jax/compilation_cache/cache_hits":
        _metrics.counter("compile_cache/persistent_hit").inc()
    elif event == "/jax/compilation_cache/cache_misses":
        _metrics.counter("compile_cache/persistent_miss").inc()


def setup_persistent_cache():
    """Make sure jax's on-disk compilation cache is on, once per process.
    Every compiling entry calls this (Executor, CompiledProgram, the
    serving step builders, SPMDTrainer); all calls after the first are a
    flag check.

    Where the cache lives is decided from outside: when
    ``JAX_COMPILATION_CACHE_DIR`` is set (or the application configured
    ``jax_compilation_cache_dir`` itself) jax already has its directory
    and this sets none. Only when nothing placed it does the cache go to
    the fixed ``<checkout>/.jax_cache``. jax's own thresholds decide
    what is worth writing (compiles of a second or more), so a test
    suite of toy compiles writes almost nothing. Returns the active
    directory."""
    global _cache_ready
    if not _cache_ready:
        _cache_ready = True
        import jax

        if jax.config.jax_compilation_cache_dir is None:
            jax.config.update("jax_compilation_cache_dir",
                              _DEFAULT_CACHE_DIR)
        jax.monitoring.register_event_listener(_on_jax_event)
    return persistent_cache_dir()


def persistent_cache_dir():
    """The directory jax's persistent cache is using (None = off)."""
    import jax

    return jax.config.jax_compilation_cache_dir
