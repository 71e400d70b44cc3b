"""Fault-tolerant serving fleet (docs/SERVING.md "Fleet & failover"):
ServingRouter least-loaded dispatch, the healthy -> suspect -> dead
health state machine (consecutive step failures + the stall watchdog),
re-admission of in-flight requests with already-emitted prefixes, load
shedding, per-request deadlines, and the serve_* fault-injection sites.

The module shares ONE GenerationModel across tests (the jitted step
caches per geometry on the model, so each compiled shape is paid once
per pytest process — the test_serving_spec budget pattern). Every test
that arms the global FaultInjector restores the previous one.
"""

import threading
import time
import warnings

import numpy as np
import pytest

from paddle_tpu import resilience, serving
from paddle_tpu.serving import (DeadlineExceededError, GenerationConfig,
                                GenerationModel, ServingRouter,
                                reference_decode)

_MODEL = None


def shared_model():
    global _MODEL
    if _MODEL is None:
        _MODEL = GenerationModel.random(
            GenerationConfig(vocab_size=64, d_model=32, n_heads=2,
                             n_layers=2, d_ff=64, max_seq_len=64),
            seed=0, name="fleet")
        # warm the standard-geometry decode step once: the tight stall
        # budgets below are for INJECTED stalls, and the watchdog
        # contract is stall_timeout_s > worst-case step time including
        # first-step XLA compile — a cold solo run must not read the
        # compile as a stall
        with serving.ServingEngine(_MODEL, max_batch=2, max_seq_len=64,
                                   block_size=4) as warm:
            warm.generate([1, 2], max_new_tokens=2, timeout=300)
    return _MODEL


def _prompts(n, vocab=64, seed=7, lo=3, hi=8):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, size=rng.randint(lo, hi)).tolist()
            for _ in range(n)]


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


def _assert_drained(engine):
    """Every pool of `engine` fully drained and invariant-clean (the
    replica-death drain contract)."""
    for w in engine._workers.values():
        problems = w.pool.check_invariants()
        assert problems == [], problems
        st = w.pool.stats()
        assert st["blocks_in_use"] == 0, st
        assert st["blocks_reserved"] == 0, st


# ---------------------------------------------------------------------------
# the injector satellites
# ---------------------------------------------------------------------------


def test_injector_serving_sites_parse():
    inj = resilience.FaultInjector(
        "serve_die_at_step:3,serve_transient_at_step:5,"
        "serve_stall_at_step:7")
    assert inj.active()
    with pytest.raises(ValueError):
        resilience.FaultInjector("serve_explode_at_step:1")


def test_injector_one_shot_firing_is_atomic():
    """The match-and-consume satellite: N threads racing one armed step
    (or one armed occurrence) produce EXACTLY one firing."""
    for kind in ("step", "occurrence"):
        if kind == "step":
            inj = resilience.FaultInjector("serve_die_at_step:5")
        else:
            inj = resilience.FaultInjector("transient_compile:8")
        fired = []
        start = threading.Barrier(8)

        def hammer():
            start.wait()
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", RuntimeWarning)
                for _ in range(4):
                    if kind == "step":
                        hit = inj.fire_at_step("serve_die_at_step", 5)
                    else:
                        hit = inj.fire_occurrence("transient_compile")
                    if hit:
                        fired.append(threading.get_ident())
        threads = [threading.Thread(target=hammer, daemon=True)
                   for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(fired) == 1, (kind, fired)


def test_maybe_inject_serve_fault_sites():
    with _inject("serve_die_at_step:2,serve_transient_at_step:3,"
                 "serve_stall_at_step:4"):
        assert resilience.maybe_inject_serve_fault(0) is None
        with pytest.raises(resilience.InjectedReplicaDeathError):
            resilience.maybe_inject_serve_fault(2)
        with pytest.raises(resilience.InjectedTransientError) as e:
            resilience.maybe_inject_serve_fault(3)
        assert resilience.is_transient_error(e.value)
        assert resilience.maybe_inject_serve_fault(4) == "stall"
        # every site is one-shot
        assert resilience.maybe_inject_serve_fault(2) is None
        assert resilience.maybe_inject_serve_fault(4) is None


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------


def test_router_basic_identity_and_spread():
    model = shared_model()
    prompts = _prompts(6)
    refs = [reference_decode(model, p, 6) for p in prompts]
    with _router(model) as router:
        reqs = [router.submit(p, max_new_tokens=6) for p in prompts]
        assert [r.wait(120) for r in reqs] == refs
        st = router.stats()
    assert st["replicas_healthy"] == 2
    assert st["failovers"] == 0 and st["shed_requests"] == 0
    assert st["requests_completed"] == 6
    # least-loaded dispatch actually spread work over both replicas
    steps = [r["model:default"]["steps"] for r in st["replicas"]]
    assert all(s > 0 for s in steps), steps


def test_clean_close_is_not_a_failover():
    """A worker exiting cleanly during close() must not read as replica
    death: no phantom failovers on a healthy multi-replica shutdown."""
    model = shared_model()
    router = _router(model)
    try:
        assert router.generate([1, 2, 3], max_new_tokens=4,
                               timeout=120) == reference_decode(
                                   model, [1, 2, 3], 4)
    finally:
        router.close()
    assert router._failovers == 0
    assert all(s != "dead" for s in router.replica_states()), \
        router.replica_states()


def test_multi_model_stall_not_masked_by_sibling():
    """Per-worker watchdog progress: one wedged model worker inside a
    replica fails over even while a sibling model keeps serving."""
    model_a = shared_model()
    model_b = GenerationModel.random(model_a.config, seed=21,
                                     name="fleet-b")
    ref = reference_decode(model_b, [4, 5, 6], 6)
    # warm BOTH models' jitted steps BEFORE arming the injector and the
    # tight stall budget: the watchdog contract is stall_timeout_s >
    # worst-case step time INCLUDING first-step XLA compile
    with serving.ServingEngine({"a": model_a, "b": model_b}, max_batch=2,
                               max_seq_len=64, block_size=4) as warm:
        warm.generate([1, 2], max_new_tokens=2, model="a", timeout=300)
        warm.generate([1, 2], max_new_tokens=2, model="b", timeout=300)
    with _inject("serve_stall_at_step:2"):
        with ServingRouter({"a": model_a, "b": model_b}, replicas=2,
                           max_batch=2, max_seq_len=64, block_size=4,
                           stall_timeout_s=0.4, backoff_base=0.0,
                           health_interval_s=0.02) as router:
            # keep model "a" busy on both replicas while "b" wedges on
            # whichever replica serves it first
            bg = [router.submit([1, 2, 3], max_new_tokens=24, model="a")
                  for _ in range(4)]
            out = router.generate([4, 5, 6], max_new_tokens=6,
                                  model="b", timeout=300)
            for r in bg:
                r.wait(300)
            st = router.stats()
    assert out == ref
    assert st["failovers"] >= 1, st


def test_router_load_shedding_is_structured_and_metered(monkeypatch):
    model = shared_model()
    with _router(model) as router:
        for rep in router._replicas:
            def full(request, _rep=rep):
                raise serving.AdmissionError("queue full (test)")
            monkeypatch.setattr(rep.engine, "submit_request", full)
        with pytest.raises(serving.AdmissionError) as e:
            router.submit([1, 2, 3], max_new_tokens=4)
        assert "saturated" in str(e.value)
        st = router.stats()
    assert st["shed_requests"] == 1
    assert st["inflight"] == 0  # the shed request left the table


def test_env_flags_configure_router(monkeypatch):
    model = shared_model()
    monkeypatch.setenv("PTPU_SERVE_REPLICAS", "2")
    monkeypatch.setenv("PTPU_SERVE_RETRY_BUDGET", "5")
    monkeypatch.setenv("PTPU_SERVE_DEADLINE_S", "123.0")
    with ServingRouter(model, max_batch=2, max_seq_len=64,
                       block_size=4) as router:
        assert router.num_replicas == 2
        assert router._retry_budget == 5
        req = router.submit([1, 2, 3], max_new_tokens=2)
        assert req.deadline is not None
        assert req.wait(120) == reference_decode(model, [1, 2, 3], 2)


# ---------------------------------------------------------------------------
# failover: death, transient, stall
# ---------------------------------------------------------------------------


def test_replica_death_failover_token_identity():
    """The headline pin: a replica dies mid-stream, its in-flight
    requests are re-admitted on the survivor with their emitted prefix,
    and every streamed output — including the re-admitted ones — is
    token-identical to the unfailed reference run."""
    model = shared_model()
    prompts = _prompts(8, seed=11)
    refs = [reference_decode(model, p, 12) for p in prompts]
    streamed = {i: [] for i in range(len(prompts))}
    with _inject("serve_die_at_step:6"):
        with _router(model) as router:
            reqs = []
            for i, p in enumerate(prompts):
                def cb(req, tok, final, _i=i):
                    streamed[_i].append(int(tok))
                reqs.append(router.submit(p, max_new_tokens=12,
                                          stream=cb))
            outs = [r.wait(300) for r in reqs]
            st = router.stats()
            dead = [r for r in router._replicas if r.state == "dead"]
            assert len(dead) == 1, st["replicas"]
            _assert_drained(dead[0].engine)
    assert outs == refs
    # the user stream saw each token exactly once, in order, across
    # the failover (no re-streaming of the committed prefix)
    assert {i: streamed[i] for i in streamed} == dict(enumerate(refs))
    assert st["failovers"] == 1
    assert st["readmitted"] >= 1 and st["retries"] >= 1
    assert st["replicas_healthy"] == 1
    assert st["requests_completed"] == len(prompts)
    # the per-request re-admission ledger mirrors the router counter
    assert sum(r.readmissions for r in reqs) == st["readmitted"]


def test_transient_step_failure_retried_in_place():
    model = shared_model()
    prompts = _prompts(4, seed=3)
    refs = [reference_decode(model, p, 8) for p in prompts]
    with _inject("serve_transient_at_step:4"):
        with _router(model) as router:
            outs = [router.generate(p, max_new_tokens=8, timeout=300)
                    for p in prompts]
            st = router.stats()
    assert outs == refs
    assert st["failovers"] == 0  # nobody died: retried at the boundary
    retried = sum(r["model:default"]["transient_retries"]
                  for r in st["replicas"])
    assert retried >= 1
    assert st["replicas_healthy"] == 2


def test_stall_watchdog_failover():
    """The watchdog satellite of the health machine: a replica that
    stops dispatching WITHOUT raising is declared dead on step-progress
    (not exceptions) and its work fails over."""
    model = shared_model()
    prompts = _prompts(6, seed=5)
    refs = [reference_decode(model, p, 10) for p in prompts]
    # The watchdog's contract (ServingRouter): the budget must exceed
    # the worst single step, a first step's compile included. So the
    # step is warmed before the router exists, and the budget is seconds,
    # not the 0.4 s a loaded machine (six test workers) can hold a
    # HEALTHY replica's millisecond step for: the wedged replica never
    # steps again, so it is still the only one the watchdog can catch.
    with _router(model, replicas=1) as primer:
        primer.submit(prompts[0], max_new_tokens=2).wait(300)
    with _inject("serve_stall_at_step:5"):
        with _router(model, stall_timeout_s=3.0) as router:
            reqs = [router.submit(p, max_new_tokens=10) for p in prompts]
            outs = [r.wait(300) for r in reqs]
            st = router.stats()
            dead = [r for r in router._replicas if r.state == "dead"]
            assert len(dead) == 1
            assert "stalled" in str(dead[0].error)
            _assert_drained(dead[0].engine)
    assert outs == refs
    assert st["failovers"] == 1


def test_failover_readmission_rides_prefix_cache():
    """The re-admission contract's fast half: prompt + emitted tokens
    resubmitted on a survivor whose radix prefix cache holds the span
    skips the recomputed prefill (prefix_blocks_reused advances)."""
    model = shared_model()
    bs = 4
    shared = list(range(1, 1 + 4 * bs))       # 4 full shareable blocks
    prompt = shared + [7, 9]
    ref = reference_decode(model, prompt, 10)
    with _router(model, prefill_chunk=4, prefix_cache=True,
                 max_seq_len=64) as router:
        # warm BOTH replicas with the shared prefix (two concurrent
        # submits: least-loaded sends the second to the idle replica)
        warms = [router.submit(shared + [3], max_new_tokens=2),
                 router.submit(shared + [5], max_new_tokens=2)]
        for w in warms:
            w.wait(300)
        st0 = router.stats()
        assert all(r["model:default"]["steps"] > 0
                   for r in st0["replicas"]), st0["replicas"]
        reused0 = {r["idx"]: r["model:default"]["prefix_blocks_reused"]
                   for r in st0["replicas"]}
        # kill whichever replica picks up the next request, a few steps
        # into its generation
        steps_now = max(r["model:default"]["steps"]
                        for r in st0["replicas"])
        with _inject("serve_die_at_step:%d" % (steps_now + 3)):
            req = router.submit(prompt, max_new_tokens=10)
            assert req.wait(300) == ref
            st1 = router.stats()
        dead = [r for r in router._replicas if r.state == "dead"]
        assert len(dead) == 1
        survivor = [r for r in st1["replicas"]
                    if r["state"] != "dead"][0]
    assert st1["readmitted"] >= 1
    # the survivor adopted cached prefix blocks for the re-admission
    assert (survivor["model:default"]["prefix_blocks_reused"]
            > reused0[survivor["idx"]])


def test_retry_budget_exhausted_is_the_pr4_shape():
    model = shared_model()
    with _inject("serve_die_at_step:2"):
        with _router(model, replicas=1, retry_budget=0) as router:
            req = router.submit(list(range(1, 6)), max_new_tokens=10)
            with pytest.raises(resilience.RetryBudgetExceededError):
                req.wait(300)
            st = router.stats()
    assert st["requests_failed"] >= 1
    assert st["retries"] == 0  # budget 0: nothing was spent


def test_no_surviving_replica_fails_loudly():
    model = shared_model()
    with _inject("serve_die_at_step:2"):
        with _router(model, replicas=1, retry_budget=2) as router:
            req = router.submit(list(range(1, 6)), max_new_tokens=10)
            with pytest.raises(RuntimeError) as e:
                req.wait(300)
    assert "no surviving replica" in str(e.value)


# ---------------------------------------------------------------------------
# deadlines (the ServingEngine.submit satellite)
# ---------------------------------------------------------------------------


def test_deadline_validation():
    model = shared_model()
    with serving.ServingEngine(model, max_batch=2, max_seq_len=64,
                               block_size=4) as eng:
        with pytest.raises(ValueError):
            eng.submit([1, 2], max_new_tokens=2, deadline_s=0)
        with pytest.raises(ValueError):
            eng.submit([1, 2], max_new_tokens=2, deadline_s=-1.0)
    # the router's submit surface enforces the SAME rule set (shared
    # check_request_args — the two paths cannot drift)
    with _router(model) as router:
        with pytest.raises(ValueError):
            router.submit([1, 2], max_new_tokens=2, deadline_s=-1.0)
        with pytest.raises(ValueError):
            router.submit([], max_new_tokens=2)
        with pytest.raises(ValueError):
            router.submit([1, 2], max_new_tokens=0)


def test_engine_deadline_expires_queued_request():
    model = shared_model()
    with serving.ServingEngine(model, max_batch=1, max_seq_len=64,
                               block_size=4) as eng:
        blocker = eng.submit(list(range(1, 6)), max_new_tokens=40)
        doomed = eng.submit(list(range(1, 6)), max_new_tokens=40,
                            deadline_s=0.02)
        with pytest.raises(DeadlineExceededError):
            doomed.wait(120)
        blocker.wait(120)  # the blocking request is untouched
        st = eng.stats()["default"]
    assert st["deadline_expired"] == 1
    assert doomed.error is not None and doomed.finished


def test_engine_deadline_expires_mid_batch_and_pool_drains():
    model = shared_model()
    with serving.ServingEngine(model, max_batch=1, max_seq_len=64,
                               block_size=4) as eng:
        req = eng.submit(list(range(1, 6)), max_new_tokens=50,
                         deadline_s=60.0)
        # force the deadline into the past once the request is running:
        # the next step boundary must fail it (deterministic on any box)
        req.deadline = time.perf_counter() - 1.0
        with pytest.raises(DeadlineExceededError):
            req.wait(120)
        w = eng._workers["default"]
        deadline = time.time() + 30
        while w.pool.stats()["blocks_in_use"] and time.time() < deadline:
            time.sleep(0.005)
        _assert_drained(eng)
        st = eng.stats()["default"]
    assert st["deadline_expired"] == 1
    assert len(req.tokens) < 50  # it was cut off mid-generation


def test_router_deadline_backstop_on_wedged_replica():
    """A wedged worker has no step boundaries, so the engine-side check
    can never run — the router's monitor fails the request itself."""
    model = shared_model()
    with _inject("serve_stall_at_step:2"):
        with _router(model, replicas=1, retry_budget=0,
                     stall_timeout_s=60.0) as router:
            req = router.submit(list(range(1, 6)), max_new_tokens=30,
                                deadline_s=0.25)
            with pytest.raises(DeadlineExceededError):
                req.wait(120)
            st = router.stats()
    assert st["deadline_expired"] == 1


# ---------------------------------------------------------------------------
# drain-path satellites: killed mid-prefill / mid-spec-window
# ---------------------------------------------------------------------------


def test_replica_killed_mid_prefill_drains_pool():
    model = shared_model()
    prompt = list(range(1, 33))  # 8 mixed steps at 4 tokens a chunk
    with _inject("serve_die_at_step:5"):
        with serving.ServingEngine(model, max_batch=2, max_seq_len=64,
                                   block_size=4, prefill_chunk=4) as eng:
            req = eng.submit(prompt, max_new_tokens=8)
            with pytest.raises(resilience.InjectedReplicaDeathError):
                req.wait(120)
            w = eng._workers["default"]
            assert w.error is not None
            # died mid-prefill: nothing was ever generated
            assert req.tokens == []
            _assert_drained(eng)


def test_replica_killed_mid_spec_window_drains_pool():
    model = shared_model()
    pattern = [3, 5, 7, 9]
    prompt = pattern * 3  # repetitive: spec windows will accept
    die_at = 1 + 2  # past the one-chunk prefill, inside the spec phase
    with _inject("serve_die_at_step:%d" % die_at):
        with serving.ServingEngine(model, max_batch=2, max_seq_len=64,
                                   block_size=4, spec_k=3) as eng:
            req = eng.submit(prompt, max_new_tokens=24)
            with pytest.raises(resilience.InjectedReplicaDeathError):
                req.wait(120)
            w = eng._workers["default"]
            assert w.scheduler.spec_steps >= 1  # death landed mid-spec
            _assert_drained(eng)


# ---------------------------------------------------------------------------
# defaults-off identity (the AMP-off pattern)
# ---------------------------------------------------------------------------


def test_fleet_off_defaults_do_no_fleet_work(monkeypatch):
    """No router in play and the fleet flags unset: no deadline scan,
    no injector work, the engine's two compiled shapes and no other,
    and reference_decode's tokens."""
    for name in ("PTPU_SERVE_REPLICAS", "PTPU_SERVE_DEADLINE_S",
                 "PTPU_SERVE_RETRY_BUDGET", "PTPU_FAULT_INJECT"):
        monkeypatch.delenv(name, raising=False)
    model = GenerationModel.random(
        GenerationConfig(vocab_size=64, d_model=32, n_heads=2,
                         n_layers=2, d_ff=64, max_seq_len=64),
        seed=9, name="fleet-off")
    prompts = _prompts(4, seed=13)
    refs = [reference_decode(model, p, 6) for p in prompts]
    prev = resilience.set_global_injector(resilience.FaultInjector(""))
    try:
        with serving.ServingEngine(model, max_batch=2, max_seq_len=64,
                                   block_size=4) as eng:
            w = eng._workers["default"]
            reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
            assert all(r.deadline is None for r in reqs)
            assert [r.wait(120) for r in reqs] == refs
            assert w._track_deadlines is False
            assert w._transient_retries == 0
            st = eng.stats()["default"]
    finally:
        resilience.set_global_injector(prev)
    assert model.trace_count == 2  # the decode and chunk shapes, no other
    assert len(model._steps) == 2
    assert st["deadline_expired"] == 0 and st["transient_retries"] == 0
    # the default router width is one replica (flag default)
    from paddle_tpu.flags import env
    assert env("PTPU_SERVE_REPLICAS") == 1
    assert env("PTPU_SERVE_DEADLINE_S") is None
