"""Async execution pipeline semantics (docs/ASYNC_EXECUTION.md):
deferred fetches vs donated state, the bounded in-flight window,
background feed prefetch ordering, fetch_every_n sync points, deferred
runtime warnings, the int64 device-feed guard, and the persistent
compilation cache across a process-sim (fresh Executor + cleared jax
caches)."""

import os
import subprocess
import sys

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import async_engine
from paddle_tpu.async_engine import (DeferredWarns, FeedPrefetcher,
                                     InflightWindow, LazyFetchList,
                                     as_numpy, prefetch_iter)
from paddle_tpu.core import scope as scope_mod
from paddle_tpu.observability import metrics as obs_metrics


def _sgd_program(lr=0.1):
    x = fluid.layers.data(name="x", shape=[4])
    loss = fluid.layers.mean(fluid.layers.fc(input=x, size=2))
    fluid.optimizer.SGD(lr).minimize(loss)
    return loss


def _feed(seed=0):
    rng = np.random.RandomState(seed)
    return {"x": rng.rand(2, 4).astype(np.float32)}


# ---------------------------------------------------------------------------
# deferred fetches vs donation
# ---------------------------------------------------------------------------


def test_deferred_fetch_survives_donating_steps():
    """A held fetch handle from step t must still materialize the step-t
    value after K further (state-donating) steps — donated buffers never
    alias a lazily-held fetch (XLA copy insertion gives every entry
    output its own buffer)."""
    loss = _sgd_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = _feed()

    # reference trajectory, fully synced every step
    sync_vals = []
    for _ in range(6):
        (lv,) = exe.run(feed=feed, fetch_list=[loss])
        sync_vals.append(float(lv.reshape(-1)[0]))

    # reset state and replay async, materializing only at the END
    scope_mod._scope_stack[:] = [scope_mod.Scope()]
    exe2 = fluid.Executor(fluid.CPUPlace())
    exe2.run(fluid.default_startup_program())
    handles = []
    for _ in range(6):
        res = exe2.run(feed=feed, fetch_list=[loss], return_numpy=False)
        assert isinstance(res, LazyFetchList)
        handles.append(res[0])
    async_vals = [float(np.asarray(h).reshape(-1)[0]) for h in handles]
    np.testing.assert_allclose(async_vals, sync_vals, rtol=1e-6)


def test_fetched_param_survives_donation():
    """Fetching a PERSISTABLE that the step also donates/overwrites is the
    sharpest aliasing case: the held handle must keep the step-t value."""
    _sgd_program()
    prog = fluid.default_main_program()
    w = next(iter(prog.global_block().all_parameters()))
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = _feed()

    (w_t,) = exe.run(prog, feed=feed, fetch_list=[w.name],
                     return_numpy=False)
    for _ in range(3):  # further steps donate and overwrite the param
        exe.run(prog, feed=feed, fetch_list=[w.name], return_numpy=False)
    (w_now,) = exe.run(prog, feed=feed, fetch_list=[w.name])
    held = np.asarray(w_t)
    assert held.shape == w_now.shape
    # SGD moved the param each step; the held handle must NOT see that
    assert not np.allclose(held, w_now)


# ---------------------------------------------------------------------------
# in-flight window
# ---------------------------------------------------------------------------


class _Token:
    """Materialization-recording stand-in for a fetch handle."""

    def __init__(self, log, i):
        self._log = log
        self._i = i

    def __array__(self, dtype=None):
        self._log.append(self._i)
        return np.zeros(1, dtype or np.float32)


def test_inflight_window_blocks_at_limit():
    log = []
    win = InflightWindow(limit=3)
    for i in range(5):
        win.admit([_Token(log, i)])
        assert win.depth <= 3
    # admits 3 and 4 had to materialize the two oldest steps, in order
    assert log == [0, 1]
    win.drain()
    assert log == [0, 1, 2, 3, 4]
    assert win.depth == 0


def test_inflight_window_gauge():
    obs_metrics.enable()
    try:
        win = InflightWindow(limit=4)
        for i in range(3):
            win.admit([_Token([], i)])
        assert obs_metrics.registry().gauge(
            "exec/inflight_steps").value == 3
    finally:
        obs_metrics.disable()


def test_executor_sync_drains_window():
    loss = _sgd_program()
    exe = fluid.Executor(fluid.CPUPlace(), async_steps=4)
    exe.run(fluid.default_startup_program())
    feed = _feed()
    for _ in range(3):
        exe.run(feed=feed, fetch_list=[loss], return_numpy=False)
    assert exe._window.depth == 3
    exe.sync()
    assert exe._window.depth == 0


# ---------------------------------------------------------------------------
# fetch_every_n
# ---------------------------------------------------------------------------


def test_fetch_every_n_sync_points():
    loss = _sgd_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = _feed()
    kinds, vals = [], []
    for _ in range(6):
        (lv,) = exe.run(feed=feed, fetch_list=[loss], fetch_every_n=3)
        kinds.append(isinstance(lv, np.ndarray))
        vals.append(float(np.asarray(lv).reshape(-1)[0]))
    # every 3rd call materializes; the others return device futures
    assert kinds == [False, False, True, False, False, True]
    # values are per-step correct regardless of the sync cadence
    assert len(set(round(v, 6) for v in vals)) == 6


# ---------------------------------------------------------------------------
# feed prefetch
# ---------------------------------------------------------------------------


def test_prefetch_preserves_batch_order():
    pf = FeedPrefetcher(depth=2)
    try:
        feeds = [{"x": np.full((2, 2), i, np.float32)} for i in range(8)]
        out = []
        for staged in prefetch_iter(iter(feeds), pf):
            assert isinstance(staged["x"], jax.Array)
            out.append(int(np.asarray(staged["x"])[0, 0]))
        assert out == list(range(8))
    finally:
        pf.close()


def test_prefetch_identity_path():
    loss = _sgd_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    feed = _feed()
    (ref,) = exe.run(feed=feed, fetch_list=[loss])

    exe.prefetch(feed)
    (lv,) = exe.run(feed=feed, fetch_list=[loss])
    # staged run continues the same trajectory (feed values identical)
    assert lv.shape == ref.shape
    # the staged entry was consumed
    assert exe._prefetcher.take_if_match(feed) is None
    # a mismatching feed leaves the staged queue untouched
    exe.prefetch(feed)
    assert exe._prefetcher.take_if_match({"x": _feed(1)["x"]}) is None
    assert exe._prefetcher.take_if_match(feed) is not None
    exe.close()


def test_prefetch_error_propagates():
    def boom(name, value):
        raise RuntimeError("stage failed")

    pf = FeedPrefetcher(stage_fn=boom)
    try:
        pf.put({"x": np.zeros(2)})
        with pytest.raises(RuntimeError, match="stage failed"):
            pf.get()
    finally:
        pf.close()


# ---------------------------------------------------------------------------
# deferred warnings
# ---------------------------------------------------------------------------


def test_deferred_warns_all_false_stays_silent(recwarn):
    dw = DeferredWarns(drain_every=3)
    warned = set()
    flags = np.zeros(2, bool)
    for _ in range(7):
        dw.add(["warn-a", "warn-b"], flags, warned)
    dw.drain(warned)
    assert not warned
    assert not [w for w in recwarn.list if "warn-a" in str(w.message)]


def test_deferred_warns_fire_after_drain_interval():
    dw = DeferredWarns(drain_every=3)
    warned = set()
    labels = ["warn-a", "warn-b"]
    with pytest.warns(RuntimeWarning, match="warn-b"):
        for i in range(3):  # drains (and warns) on the 3rd add
            dw.add(labels, np.array([False, i == 0]), warned)
    assert warned == {"warn-b"}
    # already-warned labels short-circuit: nothing accumulates
    dw.add(["warn-b"], np.array([True]), warned)
    assert not dw._pending


# ---------------------------------------------------------------------------
# int64 feed guard (device arrays included)
# ---------------------------------------------------------------------------


def test_int64_guard_catches_device_arrays():
    loss = _sgd_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    with jax.enable_x64():
        bad = jax.device_put(np.full((2, 4), 2 ** 40, np.int64))
    assert bad.dtype == np.int64
    with pytest.raises(ValueError, match="int64 ids above int32 range"):
        exe.run(feed={"x": bad}, fetch_list=[loss])


def test_int64_guard_host_arrays_still_checked():
    loss = _sgd_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    with pytest.raises(ValueError, match="int64 ids above int32 range"):
        exe.run(feed={"x": np.full((2, 4), 2 ** 40, np.int64)},
                fetch_list=[loss])
    # in-range int64 feeds still pass (cast to the var dtype)
    (lv,) = exe.run(feed={"x": np.ones((2, 4), np.int64)},
                    fetch_list=[loss])
    assert np.isfinite(lv).all()


# ---------------------------------------------------------------------------
# persistent compilation cache
# ---------------------------------------------------------------------------


_CACHE_KNOBS = ("jax_compilation_cache_dir",
                "jax_persistent_cache_min_entry_size_bytes",
                "jax_persistent_cache_min_compile_time_secs",
                "jax_enable_compilation_cache")  # off in conftest


@pytest.fixture
def fresh_cache(tmp_path):
    """Place jax's persistent cache in a temp dir the way an application
    would (its own jax.config.update), with the write thresholds off so
    a toy compile is worth an entry; restore on exit."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = [getattr(jax.config, k) for k in _CACHE_KNOBS]
    cache = str(tmp_path / "cache")
    for k, v in zip(_CACHE_KNOBS, (cache, -1, 0.0, True)):
        jax.config.update(k, v)
    cc.reset_cache()  # drop the singleton bound to the previous dir
    yield cache
    for k, v in zip(_CACHE_KNOBS, prev):
        jax.config.update(k, v)
    cc.reset_cache()


def test_persistent_cache_process_sim(fresh_cache):
    """New cache dir -> miss; same dir from a 'fresh process' (new
    Executor, jax in-memory caches cleared) -> hit, and the on-disk dir
    actually holds compiled artifacts."""
    obs_metrics.enable()
    try:
        reg = obs_metrics.registry()

        def count(name):
            return reg.counter(name).value

        # shapes unique to THIS test: an identical program compiled by an
        # earlier test (before the cache dir was active) would be served
        # from jax's in-memory cache and never touch the disk cache
        x = fluid.layers.data(name="x", shape=[6])
        loss = fluid.layers.mean(fluid.layers.fc(input=x, size=5))
        fluid.optimizer.SGD(0.05).minimize(loss)
        feed = {"x": np.random.RandomState(0).rand(3, 6).astype(np.float32)}
        miss0, hit0 = (count("compile_cache/persistent_miss"),
                       count("compile_cache/persistent_hit"))
        exe = fluid.Executor(fluid.CPUPlace())
        # a directory placed from outside is left alone
        assert async_engine.persistent_cache_dir() == fresh_cache
        exe.run(fluid.default_startup_program())
        (ref,) = exe.run(feed=feed, fetch_list=[loss])
        assert count("compile_cache/persistent_miss") > miss0
        assert count("compile_cache/persistent_hit") == hit0
        assert any(f.endswith("-cache")
                   for f in os.listdir(fresh_cache)), "no XLA cache files"

        # process-sim: drop every in-memory compile cache, fresh Executor
        jax.clear_caches()
        scope_mod._scope_stack[:] = [scope_mod.Scope()]
        exe2 = fluid.Executor(fluid.CPUPlace())
        exe2.run(fluid.default_startup_program())
        (lv,) = exe2.run(feed=feed, fetch_list=[loss])
        assert count("compile_cache/persistent_hit") > hit0
        np.testing.assert_allclose(lv, ref, rtol=1e-6)
    finally:
        obs_metrics.disable()


def test_persistent_cache_default_placement():
    """Constructing an Executor turned the cache on: where the
    environment placed it, else at the one fixed path in the checkout —
    with jax's own write thresholds either way."""
    fluid.Executor(fluid.CPUPlace())
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(repo, ".jax_cache"))
    assert async_engine.persistent_cache_dir() == want
    assert jax.config.jax_persistent_cache_min_compile_time_secs == float(
        os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", 1.0))


def test_persistent_cache_env_dir_is_not_overridden(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, jax has its directory before
    the framework looks and no jax.config.update of the dir runs."""
    script = (
        "import jax\n"
        "calls = []\n"
        "orig = jax.config.update\n"
        "jax.config.update = lambda k, v: (calls.append(k), orig(k, v))\n"
        "import paddle_tpu as fluid\n"
        "fluid.Executor(fluid.CPUPlace())\n"
        "from paddle_tpu import async_engine\n"
        "assert 'jax_compilation_cache_dir' not in calls, calls\n"
        "print(async_engine.persistent_cache_dir())\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo,
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "outside"))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == str(tmp_path / "outside")


# ---------------------------------------------------------------------------
# misc surface
# ---------------------------------------------------------------------------


def test_as_numpy_sync_point():
    lst = LazyFetchList([jax.numpy.arange(3.0)])
    out = lst.as_numpy()
    assert isinstance(out[0], np.ndarray)
    assert isinstance(as_numpy(lst)[0], np.ndarray)
    assert isinstance(as_numpy(jax.numpy.ones(2)), np.ndarray)


def test_ptpu_stats_assertions(tmp_path, capsys):
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tools"))
    try:
        from ptpu_stats import main as stats_main
    finally:
        sys.path.pop(0)

    reg = obs_metrics.MetricsRegistry()
    reg.gauge("exec/inflight_steps").set(5)
    reg.counter("feed/h2d_bytes").inc(100)
    dump = str(tmp_path / "m.json")
    reg.dump_json(dump)
    assert stats_main([dump, "--assert-has", "feed/h2d_bytes",
                       "--assert-min", "exec/inflight_steps=2"]) == 0
    assert stats_main([dump, "--assert-has", "nope/metric"]) == 1
    assert stats_main([dump, "--assert-min",
                       "exec/inflight_steps=9"]) == 1
    assert stats_main([dump, "--assert-max",
                       "exec/inflight_steps=9"]) == 0
    assert stats_main([dump, "--assert-max",
                       "exec/inflight_steps=2"]) == 1
    assert stats_main([dump, "--assert-max", "malformed"]) == 1
