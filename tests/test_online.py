"""Online learning (docs/SERVING.md "Online updates"): atomic
generation-artifact publish with digest verification, the
ServingEngine.swap_weights hot-swap contract, router drain/undrain,
canary pinning, and the OnlineUpdater chaos matrix (torn export,
replica killed mid-drain, canary anomaly -> structured rollback).

Shares one GenerationModel pair across the engine/router tests (the
jitted step caches per geometry) and one Fluid program across the
updater tests — the test_serving_fleet budget pattern.
"""

import os
import threading
import time
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import checkpoint, inference, resilience, serving
from paddle_tpu.serving import (CanaryGate, GenerationArtifactError,
                                GenerationConfig, GenerationModel,
                                OnlineUpdater, ServingRouter,
                                load_generation_artifact, reference_decode,
                                save_generation_artifact,
                                verify_generation_artifact)

CFG = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
           max_seq_len=64)

_MODELS = {}


def model_pair():
    """Two same-geometry models (v0/v1 stand-ins), decode step warmed."""
    if not _MODELS:
        _MODELS["a"] = GenerationModel.random(GenerationConfig(**CFG),
                                              seed=0, name="online-a")
        _MODELS["b"] = GenerationModel.random(GenerationConfig(**CFG),
                                              seed=1, name="online-b")
        with serving.ServingEngine(_MODELS["a"], max_batch=2,
                                   max_seq_len=64, block_size=4) as warm:
            warm.generate([1, 2], max_new_tokens=2, timeout=300)
    return _MODELS["a"], _MODELS["b"]


def _router(model, **kw):
    kw.setdefault("replicas", 2)
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("health_interval_s", 0.02)
    kw.setdefault("backoff_base", 0.0)
    return ServingRouter(model, **kw)


class _inject:
    """Arm the process-global FaultInjector for one with-block."""

    def __init__(self, spec):
        self.spec = spec

    def __enter__(self):
        self._prev = resilience.set_global_injector(
            resilience.FaultInjector(self.spec))
        self._warns = warnings.catch_warnings()
        self._warns.__enter__()
        warnings.simplefilter("ignore", RuntimeWarning)
        return self

    def __exit__(self, *exc):
        self._warns.__exit__(*exc)
        resilience.set_global_injector(self._prev)
        return False


# ---------------------------------------------------------------------------
# atomic artifact publish + digest verification (satellite 1)
# ---------------------------------------------------------------------------


def test_artifact_manifest_verify_roundtrip(tmp_path):
    m, _ = model_pair()
    d = str(tmp_path / "art")
    save_generation_artifact(d, m.config, m.weights)
    assert verify_generation_artifact(d) is True
    # republish over the EXISTING directory (the per-file-replace path)
    save_generation_artifact(d, m.config, m.weights)
    assert verify_generation_artifact(d) is True
    loaded = load_generation_artifact(d)
    assert sorted(loaded.weights) == sorted(m.weights)


def test_artifact_corruption_raises_structured_error(tmp_path):
    m, _ = model_pair()
    d = str(tmp_path / "art")
    save_generation_artifact(d, m.config, m.weights)
    npz = os.path.join(d, "__generation__.npz")
    blob = bytearray(open(npz, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(npz, "wb") as f:
        f.write(bytes(blob))
    with pytest.raises(GenerationArtifactError) as e:
        verify_generation_artifact(d)
    # the error NAMES the artifact (the loader's structured contract)
    assert e.value.dirname == d and d in str(e.value)
    with pytest.raises(GenerationArtifactError):
        load_generation_artifact(d)


def test_artifact_without_manifest_is_legacy_not_error(tmp_path):
    m, _ = model_pair()
    d = str(tmp_path / "art")
    save_generation_artifact(d, m.config, m.weights)
    os.remove(os.path.join(d, "__generation_manifest__.json"))
    assert verify_generation_artifact(d) is False   # legacy: unverifiable
    load_generation_artifact(d)                     # ...but loadable


def test_torn_export_injection_is_detected(tmp_path):
    m, _ = model_pair()
    d = str(tmp_path / "art")
    with _inject("ckpt_torn_export:1"):
        save_generation_artifact(d, m.config, m.weights)
    with pytest.raises(GenerationArtifactError):
        verify_generation_artifact(d)
    with pytest.raises(GenerationArtifactError):
        load_generation_artifact(d)   # a torn export is NEVER served


# ---------------------------------------------------------------------------
# ServingEngine.swap_weights (satellite 2)
# ---------------------------------------------------------------------------


def test_swap_weights_per_version_token_consistency():
    """The headline attribution pin: a request mid-generation when the
    swap lands finishes WHOLLY on its version; requests admitted after
    serve wholly on the new one — no token list spans two versions."""
    m0, m1 = model_pair()
    prompt = [3, 4, 5]
    ref0 = reference_decode(m0, prompt, 24)
    ref1 = reference_decode(m1, prompt, 8)
    with serving.ServingEngine(m0, max_batch=2, max_seq_len=64,
                               block_size=4) as eng:
        assert eng.weight_version() == 0
        seen = threading.Event()

        def cb(req, tok, final):
            if len(req.tokens) >= 3:
                seen.set()
        inflight = eng.submit(prompt, max_new_tokens=24, stream=cb)
        assert seen.wait(120)          # genuinely mid-batch
        v = eng.swap_weights(m1)       # blocks until the batch drains
        assert v == 1 and eng.weight_version() == 1
        assert inflight.wait(0.1) == ref0   # finished BEFORE the swap
        assert eng.generate(prompt, max_new_tokens=8, timeout=120) == ref1
        assert eng.stats()["default"]["weight_version"] == 1


def test_swap_weights_flushes_prefix_cache():
    """Pinned: stale-prefix tokens never leak across a swap. With the
    radix cache warm for a prompt, post-swap decode of that prompt must
    match the NEW weights' reference (cached KV from the old weights
    would poison it)."""
    m0, m1 = model_pair()
    shared = list(range(1, 17))     # 4 full shareable blocks
    prompt = shared + [7, 9]
    ref1 = reference_decode(m1, prompt, 8)
    with serving.ServingEngine(m0, max_batch=2, max_seq_len=64,
                               block_size=4, prefill_chunk=4,
                               prefix_cache=True) as eng:
        eng.generate(prompt, max_new_tokens=4, timeout=300)  # warm cache
        eng.swap_weights(m1)
        assert eng.generate(prompt, max_new_tokens=8,
                            timeout=300) == ref1
        st = eng.stats()["default"]
        assert st["prefix_blocks_reused"] >= 0  # cache still functional


def test_swap_weights_sources_and_errors(tmp_path):
    m0, m1 = model_pair()
    d = str(tmp_path / "art")
    save_generation_artifact(d, m1.config, m1.weights)
    ref1 = reference_decode(m1, [5, 6], 6)
    with serving.ServingEngine(m0, max_batch=2, max_seq_len=64,
                               block_size=4) as eng:
        # artifact-directory source (digest-verified on load)
        assert eng.swap_weights(d, version=7) == 7
        assert eng.weight_version() == 7
        assert eng.generate([5, 6], max_new_tokens=6, timeout=120) == ref1
        # dict source
        eng.swap_weights(dict(m0.weights))
        # wrong weight set / shape are rejected before anything swaps
        with pytest.raises(ValueError):
            eng.swap_weights({"bogus": np.zeros(2)})
        bad = dict(m1.weights)
        k = next(iter(bad))
        bad[k] = np.zeros((1, 1), np.float32)
        with pytest.raises(ValueError):
            eng.swap_weights(bad)
        with pytest.raises(TypeError):
            eng.swap_weights(42)
        with pytest.raises(KeyError):
            eng.swap_weights(m1, model="nope")
    with pytest.raises(RuntimeError):
        eng.swap_weights(m1)   # closed engine


# ---------------------------------------------------------------------------
# router drain / undrain (satellite 3)
# ---------------------------------------------------------------------------


def test_drain_excludes_dispatch_watchdog_stands_down():
    m0, _ = model_pair()
    with _router(m0, stall_timeout_s=0.3) as router:
        steps0 = router.stats()["replicas"][1]["model:default"]["steps"]
        assert router.drain(1)
        assert router.replica_states() == ["healthy", "draining"]
        assert router.wait_drained(1, timeout=5) is True   # it was idle
        # traffic flows; replica 1 gets NONE of it, and sitting idle
        # well past stall_timeout_s must not read as a stall
        for _ in range(3):
            router.generate([1, 2], max_new_tokens=4, timeout=120)
        time.sleep(0.5)
        st = router.stats()
        assert st["replicas"][1]["model:default"]["steps"] == steps0
        assert st["replicas_draining"] == 1
        assert router.replica_states()[1] == "draining"    # not dead
        assert router.undrain(1)
        assert router.undrain(1) is False                  # idempotence
        assert router.stats()["replicas_draining"] == 0
        # re-admitted to dispatch: CONCURRENT traffic (least-loaded
        # ties break toward replica 0, so serial submits never prove
        # anything) reaches it again
        reqs = [router.submit([1, 2], max_new_tokens=8)
                for _ in range(6)]
        for r in reqs:
            r.wait(120)
        st = router.stats()
        assert st["replicas"][1]["model:default"]["steps"] > steps0


def test_drain_kill_undrain_never_double_spends_budget():
    """A replica killed MID-DRAIN: its in-flight request re-admits
    through the normal failover path spending exactly one retry, and
    undrain refuses to resurrect the corpse."""
    m0, _ = model_pair()
    prompt = [2, 3, 4]
    ref = reference_decode(m0, prompt, 20)
    with _router(m0) as router:
        # the stream callback runs on the engine worker thread, so
        # blocking it holds the request mid-flight deterministically —
        # a first-token poll alone races completion on a fast box
        gate, seen = threading.Event(), threading.Event()

        def cb(rreq, token, final):
            seen.set()
            gate.wait(30)

        req = router.submit(prompt, max_new_tokens=20, stream=cb)
        assert seen.wait(30)
        victim = req._replica.idx
        assert router.drain(victim)
        router.replica_engine(victim).kill(
            resilience.InjectedReplicaDeathError("killed mid-drain"))
        gate.set()   # release the worker into its death boundary
        assert req.wait(300) == ref          # token-identical failover
        assert req.retries == 1              # one spend, not two
        assert router.wait_drained(victim, timeout=5) is False  # died
        assert router.undrain(victim) is False
        assert router.replica_states()[victim] == "dead"
        st = router.stats()
        assert st["retries"] == 1
        assert st["requests_submitted"] == \
            st["requests_completed"] + st["requests_failed"]
    assert router.drain(victim) is False     # dead replicas don't drain


# ---------------------------------------------------------------------------
# the CanaryGate signals (unit)
# ---------------------------------------------------------------------------


class _FakeEngine:
    def __init__(self, rows):
        self._rows = rows

    def stats(self):
        return self._rows


class _FakeRouter:
    num_replicas = 2

    def __init__(self, ledger, stats=None):
        self._ledger = ledger
        self._stats = stats or [{}, {}]

    def version_ledger(self):
        return self._ledger

    def replica_states(self):
        return ["healthy", "healthy"]

    def replica_engine(self, idx):
        return _FakeEngine(self._stats[idx])


def test_canary_gate_failure_and_latency_signals():
    gate = CanaryGate(min_requests=4, failure_delta=0.25,
                      latency_factor=3.0)
    # insufficient cohort: no verdict either way
    assert gate.evaluate(_FakeRouter({1: (2, 0, 0.2), 0: (9, 0, 0.9)}),
                         0, 1, 0) is None
    # failure-rate regression
    v = gate.evaluate(_FakeRouter({1: (2, 3, 0.2), 0: (10, 0, 1.0)}),
                      0, 1, 0)
    assert v and v["signal"] == "failure_rate"
    # latency regression
    v = gate.evaluate(_FakeRouter({1: (5, 0, 5.0), 0: (10, 0, 1.0)}),
                      0, 1, 0)
    assert v and v["signal"] == "latency"
    # healthy candidate: promote
    assert gate.evaluate(_FakeRouter({1: (5, 0, 0.5), 0: (10, 0, 1.0)}),
                         0, 1, 0) is None


def test_canary_gate_nonfinite_and_injected_signals():
    gate = CanaryGate()
    r = _FakeRouter({})
    assert gate.evaluate(r, 0, 1, 0, nonfinite=True)["signal"] == \
        "nonfinite_weights"
    with _inject("canary_anomaly_at_version:3"):
        assert gate.evaluate(r, 0, 3, 2)["signal"] == "injected"
        assert gate.evaluate(r, 0, 3, 2) is None   # one-shot


def test_canary_gate_accept_rate_signal():
    gate = CanaryGate(min_requests=4, accept_delta=0.2)
    ledger = {1: (5, 0, 0.5), 0: (10, 0, 1.0)}
    stats = [{"default": {"spec_proposed": 40, "spec_accepted": 8}},
             {"default": {"spec_proposed": 40, "spec_accepted": 36}}]
    v = gate.evaluate(_FakeRouter(ledger, stats), 0, 1, 0)
    assert v and v["signal"] == "accept_rate"


# ---------------------------------------------------------------------------
# the OnlineUpdater chaos matrix (tentpole, satellite 4)
# ---------------------------------------------------------------------------


_FLUID = {}


def fluid_program():
    """One tiny training program + startup scope per pytest process."""
    if not _FLUID:
        from paddle_tpu.models import transformer_fluid
        prog, sprog = fluid.Program(), fluid.Program()
        with fluid.program_guard(prog, sprog):
            transformer_fluid.build(vocab_size=64, d_model=16, n_heads=2,
                                    n_layers=1, d_ff=32, seq_len=8,
                                    remat=False)
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(sprog, scope=scope)
        _FLUID["prog"], _FLUID["scope"] = prog, scope
    return _FLUID["prog"], _FLUID["scope"]


def _scope_state(scope, seed):
    """A checkpoint-shaped state: the scope's weights, perturbed."""
    rng = np.random.RandomState(seed)
    state = {}
    for name, value in scope.items():
        v = np.asarray(value)
        if np.issubdtype(v.dtype, np.floating):
            v = v + rng.normal(0, 0.02, v.shape).astype(v.dtype)
        state[name] = v
    return state


def test_online_updater_chaos_matrix(tmp_path):
    """One fleet, the full rollout state machine: (A) happy-path
    publish -> canary -> promote with per-version token identity,
    (B) torn export detected + skipped with NO rollout, then
    republished next interval, (C) injected canary anomaly ->
    structured rollback to the incumbent with zero dropped requests,
    (D) replica killed mid-drain: survivors serve, the rollout
    resumes and completes on what's left of the fleet."""
    prog, scope = fluid_program()
    ckpt_dir = str(tmp_path / "ckpts")
    pub_dir = str(tmp_path / "pub")
    v0_dir = str(tmp_path / "v0")
    os.makedirs(ckpt_dir)
    inference.export_generation_model(v0_dir, prog, scope, max_seq_len=32)

    router = ServingRouter(v0_dir, replicas=2, max_batch=2,
                           max_seq_len=32, block_size=4,
                           health_interval_s=0.02, backoff_base=0.0)
    try:
        upd = OnlineUpdater(router, ckpt_dir, pub_dir, prog,
                            max_seq_len=32, canary_pct=50.0,
                            canary_window_s=0.4)
        assert upd.poll_once() is None    # nothing published yet

        # -- A: happy path ---------------------------------------------
        checkpoint.save_checkpoint(ckpt_dir, _scope_state(scope, 1), 1)
        out = upd.poll_once()
        assert out["published"] and out["promoted"] and \
            out["version"] == 1, out
        assert [router.replica_engine(i).weight_version()
                for i in range(2)] == [1, 1]
        m1 = load_generation_artifact(os.path.join(pub_dir, "v1"))
        assert router.submit([3, 4, 5], max_new_tokens=6).wait(120) == \
            reference_decode(m1, [3, 4, 5], 6)
        assert upd.poll_once() is None    # consumed

        # -- B: torn export --------------------------------------------
        with _inject("ckpt_torn_export:1"):
            checkpoint.save_checkpoint(ckpt_dir, _scope_state(scope, 2),
                                       2)
            out = upd.poll_once()
        assert not out["published"] and out["reason"] == "torn_export"
        assert upd.torn_exports == 1
        assert [router.replica_engine(i).weight_version()
                for i in range(2)] == [1, 1]   # no rollout happened
        checkpoint.save_checkpoint(ckpt_dir, _scope_state(scope, 3), 3)
        out = upd.poll_once()
        assert out["published"] and out["version"] == 2, out
        assert [router.replica_engine(i).weight_version()
                for i in range(2)] == [2, 2]

        # -- C: canary anomaly -> structured rollback ------------------
        with _inject("canary_anomaly_at_version:3"):
            checkpoint.save_checkpoint(ckpt_dir, _scope_state(scope, 4),
                                       4)
            stop, errs = threading.Event(), []

            def pump():     # live traffic THROUGH the rollback
                while not stop.is_set():
                    try:
                        router.submit([1, 2], max_new_tokens=4).wait(120)
                    except Exception as e:      # pragma: no cover
                        errs.append(e)
                    time.sleep(0.005)
            t = threading.Thread(target=pump)
            t.start()
            try:
                out = upd.poll_once()
            finally:
                stop.set()
                t.join()
        assert out["published"] and not out["promoted"], out
        assert upd.rollbacks == 1
        assert errs == []                      # zero dropped requests
        assert [router.replica_engine(i).weight_version()
                for i in range(2)] == [2, 2]   # fleet on the incumbent
        m2 = load_generation_artifact(os.path.join(pub_dir, "v2"))
        assert router.submit([9, 1], max_new_tokens=5).wait(120) == \
            reference_decode(m2, [9, 1], 5)
        st = router.stats()
        assert st["requests_submitted"] == \
            st["requests_completed"] + st["requests_failed"]
        assert st["canary_requests"] >= 0

        # -- D: replica killed mid-drain -------------------------------
        with _inject("swap_die_mid_drain:1"):
            checkpoint.save_checkpoint(ckpt_dir, _scope_state(scope, 5),
                                       5)
            out = upd.poll_once()
        assert out["published"] and out["promoted"], out
        states = router.replica_states()
        assert states.count("dead") == 1, states
        live = next(i for i, s in enumerate(states) if s != "dead")
        assert router.replica_engine(live).weight_version() == 4
        m4 = load_generation_artifact(os.path.join(pub_dir, "v4"))
        assert router.submit([2, 7], max_new_tokens=5).wait(120) == \
            reference_decode(m4, [2, 7], 5)
        st = router.stats()
        assert st["requests_submitted"] == \
            st["requests_completed"] + st["requests_failed"]
        assert upd.stats()["incumbent_version"] == 4
    finally:
        router.close()


def test_online_updater_skips_corrupt_checkpoint(tmp_path):
    """A checkpoint torn on disk (`ckpt_torn_write`) costs one update
    interval, never a rollout of garbage weights."""
    prog, scope = fluid_program()
    ckpt_dir = str(tmp_path / "ckpts")
    v0_dir = str(tmp_path / "v0")
    inference.export_generation_model(v0_dir, prog, scope, max_seq_len=32)
    with _inject("ckpt_torn_write:1"):
        checkpoint.save_checkpoint(ckpt_dir, _scope_state(scope, 1), 1)
    with ServingRouter(v0_dir, replicas=1, max_batch=2, max_seq_len=32,
                       block_size=4, health_interval_s=0.02,
                       backoff_base=0.0) as router:
        upd = OnlineUpdater(router, ckpt_dir, str(tmp_path / "pub"),
                            prog, max_seq_len=32, canary_pct=None)
        # a size-torn step never makes the intact candidate list (poll
        # sees nothing); a content-torn one fails digest verification
        # (poll reports corrupt_checkpoint) — EITHER way: no rollout
        out = upd.poll_once()
        assert out is None or (out["published"] is False and
                               out["reason"] == "corrupt_checkpoint")
        assert router.replica_engine(0).weight_version() == 0
        assert upd.versions_published == 0
        # the next intact checkpoint recovers the stream
        checkpoint.save_checkpoint(ckpt_dir, _scope_state(scope, 2), 2)
        out = upd.poll_once()
        assert out["published"] and out["promoted"], out
        assert router.replica_engine(0).weight_version() == 1


# ---------------------------------------------------------------------------
# defaults-off identity (the AMP-off pattern)
# ---------------------------------------------------------------------------


def test_online_off_defaults_do_no_online_work(monkeypatch):
    """No OnlineUpdater attached and $PTPU_SERVE_CANARY_PCT unset: no
    canary pin, no version ledger accrual, every replica stays on
    version 0, and the tokens are reference_decode's."""
    monkeypatch.delenv("PTPU_SERVE_CANARY_PCT", raising=False)
    m0, _ = model_pair()
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9]]
    refs = [reference_decode(m0, p, 6) for p in prompts]
    with _router(m0) as router:
        assert router._canary is None
        reqs = [router.submit(p, max_new_tokens=6) for p in prompts]
        assert [r.wait(120) for r in reqs] == refs
        assert router.version_ledger() == {}
        st = router.stats()
    assert st["canary_requests"] == 0
    assert st["version_restarts"] == 0
    assert st["replicas_draining"] == 0
    assert all(r["weight_version"] == 0 for r in st["replicas"])
    from paddle_tpu.flags import env
    assert env("PTPU_SERVE_CANARY_PCT") is None


# ---------------------------------------------------------------------------
# train-while-serving (slow: the CI `online` stage shape in-process)
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_online_train_while_serving_slow(tmp_path):
    """A live ResilientTrainer checkpointing while the fleet serves and
    the OnlineUpdater polls in the background: >=2 weight versions roll
    out, the ledger balances (zero dropped), and every response is
    token-identical to its version's artifact reference."""
    from paddle_tpu.models import transformer_fluid
    prog, sprog = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, sprog):
        _toks, _labs, loss = transformer_fluid.build(
            vocab_size=64, d_model=16, n_heads=2, n_layers=1, d_ff=32,
            seq_len=8, remat=False)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(sprog, scope=scope)

    ckpt_dir = str(tmp_path / "ckpts")
    pub_dir = str(tmp_path / "pub")
    v0_dir = str(tmp_path / "v0")
    inference.export_generation_model(v0_dir, prog, scope, max_seq_len=32)

    rng = np.random.RandomState(0)

    def feeds(n):
        for _ in range(n):
            toks = rng.randint(0, 64, (1, 8)).astype(np.int32)
            yield {"tokens": toks,
                   "labels": np.roll(toks, -1, 1).astype(np.int32)}

    router = ServingRouter(v0_dir, replicas=2, max_batch=2,
                           max_seq_len=32, block_size=4,
                           health_interval_s=0.02, backoff_base=0.0)
    upd = OnlineUpdater(router, ckpt_dir, pub_dir, prog, max_seq_len=32,
                        canary_pct=50.0, canary_window_s=0.2,
                        poll_s=0.05)
    outputs = []
    try:
        upd.start()
        stop, errs = threading.Event(), []

        def pump():
            while not stop.is_set():
                try:
                    req = router.submit([1, 2, 3], max_new_tokens=5)
                    outputs.append((req.wait(300), req.weight_version))
                except Exception as e:      # pragma: no cover
                    errs.append(e)
                time.sleep(0.01)
        t = threading.Thread(target=pump)
        t.start()
        try:
            trainer = fluid.ResilientTrainer(
                exe, prog, fetch_list=[loss], scope=scope,
                checkpoint_dir=ckpt_dir, checkpoint_every=4,
                guard_every=4, backoff_base=0.0)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                trainer.run(feeds(16))
                deadline = time.time() + 60
                while upd.swaps < 2 and time.time() < deadline:
                    time.sleep(0.05)
                # second training run: a SECOND version must flow
                # through the same live pipeline (the updater's newest-
                # supersedes scan may collapse one run's checkpoint
                # backlog into a single publish, so >= 2 published
                # versions needs >= 2 runs' worth of checkpoints)
                trainer.run(feeds(16))
            deadline = time.time() + 60
            while upd.versions_published < 2 and time.time() < deadline:
                time.sleep(0.05)
        finally:
            stop.set()
            t.join()
        assert errs == []
        assert upd.swaps >= 2, upd.stats()
        assert upd.versions_published >= 2, upd.stats()
        st = router.stats()
        assert st["requests_submitted"] == \
            st["requests_completed"] + st["requests_failed"]
    finally:
        upd.stop()
        router.close()
    # per-version token attribution: every output matches ITS version's
    # reference exactly (version 0 = the pre-rollout export)
    refs = {0: reference_decode(load_generation_artifact(v0_dir),
                                [1, 2, 3], 5)}
    for toks, ver in outputs:
        if ver not in refs:
            refs[ver] = reference_decode(
                load_generation_artifact(
                    os.path.join(pub_dir, "v%d" % ver)), [1, 2, 3], 5)
        assert toks == refs[ver], (ver, toks, refs[ver])
