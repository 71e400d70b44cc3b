"""The request log: one ``serving/request`` record per request that
leaves the engine, written by the worker beside the step log, its time
to first token the sum of five phases stamped where each happens
(docs/OBSERVABILITY.md, "The serving request log"). CPU, toy widths.
"""

import threading
import time

import numpy as np
import pytest

from paddle_tpu.observability import metrics, tracing
from paddle_tpu.serving import (GenerationConfig, GenerationModel,
                                ServingEngine)
from paddle_tpu.serving.scheduler import DeadlineExceededError

PHASES = ("queue_ms", "plan_ms", "prefill_ms", "inflight_ms", "deliver_ms")
STAMPS = ("t_submit", "t_admit", "t_first_dispatch",
          "t_last_prefill_dispatch", "t_first_ready", "t_first_token",
          "t_finish")
FIELDS = {
    "request", "model", "trace_id", "outcome", "prompt_tokens",
    "output_tokens", "cold", *STAMPS, "first_step", "first_token_step",
    "last_step", "prefill_steps", "deferred_steps",
    "queued_at_first_token", "gaps", "gaps_mixed", "gap_max_ms",
    "gap_max_kind", *PHASES, "ahead_ms", "ttft_ms", "latency_ms"}

PROMPT_LENS = (5, 11, 17, 3, 9, 20)
NEW_TOKENS = 12
CHUNK = 8


def toy_model():
    return GenerationModel.random(
        GenerationConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                         d_ff=64, max_seq_len=64), seed=7)


def engine_of(model, **engine_kw):
    kw = dict(max_batch=4, max_seq_len=64, block_size=4,
              prefill_chunk=CHUNK)
    kw.update(engine_kw)
    return ServingEngine(model, **kw)


def prompts(lens, seed=3):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 64, size=n).tolist() for n in lens]


def submit_together(engine, lens, **submit_kw):
    """Every prompt queued before the worker's next admission: it
    sleeps on the condition whose lock this thread holds."""
    with engine._workers["default"]._cv:
        return [engine.submit(p, max_new_tokens=NEW_TOKENS, **submit_kw)
                for p in prompts(lens)]


def logs():
    reg = metrics.registry()
    return (reg.samples("serving/request").records(),
            reg.samples("serving/step").records())


@pytest.fixture
def metrics_on():
    metrics.reset()
    metrics.enable()
    try:
        yield metrics.registry()
    finally:
        metrics.disable()
        metrics.reset()


@pytest.fixture(scope="module")
def logged_run():
    """Six prompts over four rows with one chunk of prefill budget a
    step, every token's arrival stamped by a stream callback, metrics
    on; and the same requests with metrics off, the log slot of every
    live sequence looked at from the stream callback."""
    metrics.reset()
    assert not metrics.enabled()
    slots_seen = []
    with engine_of(toy_model(), prefill_token_budget=CHUNK) as engine:
        sched = engine._workers["default"].scheduler

        def look(_request, _token, _final):
            slots_seen.extend(s.log for s in sched.slots if s is not None)

        off = submit_together(engine, PROMPT_LENS, stream=look)
        off_tokens = [r.wait(300) for r in off]
        off_departed = list(sched.departed)
    off_series = metrics.registry().metrics().get("serving/request")
    arrivals = {}

    def stamp(request, _token, _final):
        arrivals.setdefault(request.id, []).append(time.perf_counter())

    metrics.enable()
    try:
        with engine_of(toy_model(), prefill_token_budget=CHUNK) as engine:
            reqs = submit_together(engine, PROMPT_LENS, stream=stamp)
            tokens = [r.wait(300) for r in reqs]
        requests, steps = logs()
    finally:
        metrics.disable()
        metrics.reset()
    return dict(requests=requests, steps=steps, reqs=reqs, tokens=tokens,
                arrivals=arrivals, off_tokens=off_tokens,
                off_series=off_series, off_departed=off_departed,
                slots_seen=slots_seen)


def check_one_record_per_request(run):
    recs = run["requests"]
    assert sorted(r["request"] for r in recs) \
        == sorted(q.id for q in run["reqs"])
    by_id = {q.id: q for q in run["reqs"]}
    for r in recs:
        q = by_id[r["request"]]
        assert set(r) == FIELDS
        assert r["model"] == "default" and r["outcome"] == "finished"
        assert r["trace_id"] is None          # tracing is off
        assert r["prompt_tokens"] == len(q.prompt)
        assert r["output_tokens"] == len(q.tokens) == NEW_TOKENS
        assert r["gaps"] == NEW_TOKENS - 1


def check_phases_sum_to_ttft(run):
    for r in run["requests"]:
        # to the float: ttft_ms IS the sum, taken in this order
        assert r["queue_ms"] + r["plan_ms"] + r["prefill_ms"] \
            + r["inflight_ms"] + r["deliver_ms"] == r["ttft_ms"]
        assert all(r[p] >= 0 for p in PHASES)
        assert r["ttft_ms"] == pytest.approx(
            (r["t_first_token"] - r["t_submit"]) * 1e3, rel=1e-9)
        assert r["latency_ms"] == pytest.approx(
            (r["t_finish"] - r["t_submit"]) * 1e3)
        assert r["ttft_ms"] <= r["latency_ms"]


def check_stamps_are_the_requests_and_ordered(run):
    by_id = {q.id: q for q in run["reqs"]}
    for r in run["requests"]:
        q = by_id[r["request"]]
        assert [r[k] for k in STAMPS] == sorted(r[k] for k in STAMPS)
        assert (r["t_submit"], r["t_admit"], r["t_first_token"],
                r["t_finish"]) == (q.submit_time, q.start_time,
                                   q.first_token_time, q.finish_time)


def check_steps_join_the_step_log(run):
    by_step = {s["step"]: s for s in run["steps"]}
    for r in run["requests"]:
        first = by_step[r["first_step"]]
        token = by_step[r["first_token_step"]]
        assert first["kind"] == token["kind"] == "mixed"
        assert r["t_first_dispatch"] == first["t_dispatched"]
        assert r["t_last_prefill_dispatch"] == token["t_dispatched"]
        assert r["t_first_ready"] == token["t_ready"]
        assert r["queued_at_first_token"] == token["queued"]
        # the first token was recorded while that step's result was
        # being taken
        assert token["t_ready"] <= r["t_first_token"] <= token["t_done"]
        assert r["first_step"] <= r["first_token_step"] < r["last_step"]
        assert by_step[r["last_step"]]["t_ready"] <= r["t_finish"] \
            <= by_step[r["last_step"]]["t_done"]
        # ceil(prompt / chunk) steps carried its prompt
        assert r["prefill_steps"] == -(-r["prompt_tokens"] // CHUNK)
        assert (r["prefill_ms"] == 0) == (r["prefill_steps"] == 1)


def check_ahead_is_inflight_less_the_steps_own_time(run):
    by_step = {s["step"]: s for s in run["steps"]}
    for r in run["requests"]:
        device_ms = by_step[r["first_token_step"]]["device_ms"]
        if device_ms is None:
            assert r["ahead_ms"] is None
        else:
            assert r["ahead_ms"] == r["inflight_ms"] - device_ms
            assert r["ahead_ms"] >= -1e-6
            if not r["queued_at_first_token"]:
                # nothing queued ahead: the step began when dispatched
                assert r["ahead_ms"] == pytest.approx(0, abs=1e-6)


def check_deferred_steps_are_the_rows_deferred(run):
    mixed = [s for s in run["steps"] if s["kind"] == "mixed"]
    assert sum(r["deferred_steps"] for r in run["requests"]) \
        == sum(s["rows_deferred"] for s in mixed) > 0
    # one chunk a step in admission order: a request sits out every
    # mixed step between its admission and its first chunk but those
    # it is fed in
    for r in run["requests"]:
        planned = [s for s in mixed
                   if r["t_admit"] <= s["t_planned"]
                   and s["step"] <= r["first_token_step"]]
        assert r["deferred_steps"] == len(planned) - r["prefill_steps"]


def check_cold_is_a_cold_step_that_carried_it(run):
    cold = sorted(s["step"] for s in run["steps"] if s["cold"])
    assert len(cold) == 2                 # one a step shape
    recs = run["requests"]
    for r in recs:
        assert r["cold"] == any(r["first_step"] <= c <= r["last_step"]
                                for c in cold)
    assert any(r["cold"] for r in recs) and not all(r["cold"] for r in recs)


def check_gaps_mixed_counts_tokens_of_mixed_steps(run):
    for r in run["requests"]:
        times = run["arrivals"][r["request"]]
        assert len(times) == NEW_TOKENS
        # the step whose result was being taken when each token arrived
        kinds = [next(s["kind"] for s in run["steps"]
                      if s["t_ready"] <= t <= s["t_done"]) for t in times]
        assert kinds[0] == "mixed"        # not a gap: the prompt's end
        assert r["gaps_mixed"] == kinds[1:].count("mixed")
        # the worker's stamps bracket the callback's: the longest gap
        # it noted is the longest the callback saw, give or take a
        # preemption between the two clock readings
        seen = np.diff(times) * 1e3
        assert r["gap_max_ms"] == pytest.approx(seen.max(), abs=25.0)
        assert r["gap_max_kind"] in ("mixed", "decode")
    assert 0 < sum(r["gaps_mixed"] for r in run["requests"]) \
        < sum(r["gaps"] for r in run["requests"])


def check_metrics_off_keeps_no_log(run):
    assert run["off_series"] is None
    assert run["off_tokens"] == run["tokens"]
    assert run["slots_seen"] and all(s is None for s in run["slots_seen"])
    assert run["off_departed"] == []


@pytest.mark.parametrize("check", [
    check_one_record_per_request, check_phases_sum_to_ttft,
    check_stamps_are_the_requests_and_ordered,
    check_steps_join_the_step_log,
    check_ahead_is_inflight_less_the_steps_own_time,
    check_deferred_steps_are_the_rows_deferred,
    check_cold_is_a_cold_step_that_carried_it,
    check_gaps_mixed_counts_tokens_of_mixed_steps,
    check_metrics_off_keeps_no_log,
], ids=lambda f: f.__name__[len("check_"):])
def test_engine_request_log(logged_run, check):
    check(logged_run)


@pytest.mark.parametrize("budget,waits", [(CHUNK, True), (None, False)],
                         ids=["one_chunk_a_step", "default_budget"])
def test_the_second_prompt_sits_out_the_firsts_chunks(metrics_on, budget,
                                                      waits):
    """Two prompts of three chunks admitted together. With one chunk of
    budget a step the second is granted nothing while the first is fed:
    its `deferred_steps` is the first's `prefill_steps`, and over the
    requests they sum to the steps' `rows_deferred`. With the default
    budget (four chunks) both are fed from the first step on."""
    with engine_of(toy_model(), prefill_token_budget=budget) as engine:
        reqs = submit_together(engine, (3 * CHUNK, 3 * CHUNK))
        for r in reqs:
            r.wait(300)
    requests, steps = logs()
    first, second = sorted(requests, key=lambda r: r["request"])
    assert first["t_admit"] < second["t_admit"]
    assert first["prefill_steps"] == second["prefill_steps"] == 3
    assert first["deferred_steps"] == 0
    assert second["deferred_steps"] == (first["prefill_steps"] if waits
                                        else 0)
    assert first["deferred_steps"] + second["deferred_steps"] \
        == sum(s.get("rows_deferred", 0) for s in steps)
    if waits:
        # it was admitted with the first and waited for its first chunk
        # where the first did not
        assert second["first_step"] == first["first_token_step"] + 1
        assert second["plan_ms"] > first["plan_ms"] + first["prefill_ms"]
    else:
        assert second["first_step"] == first["first_step"]


def test_a_prompt_the_engine_refuses_leaves_a_failed_record(metrics_on):
    with engine_of(toy_model()) as engine:
        bad = engine.submit([1] * 64, max_new_tokens=4)   # >= max_seq_len
        with pytest.raises(ValueError):
            bad.wait(60)
    (rec,), steps = logs()
    assert steps == []
    assert set(rec) == FIELDS
    assert rec["request"] == bad.id and rec["outcome"] == "failed"
    assert (rec["prompt_tokens"], rec["output_tokens"]) == (64, 0)
    # it never reached a slot
    assert rec["t_submit"] == bad.submit_time
    assert rec["t_finish"] == bad.finish_time
    for k in STAMPS[1:-1] + PHASES + ("ttft_ms", "ahead_ms", "first_step",
                                      "first_token_step", "last_step"):
        assert rec[k] is None, k
    assert rec["prefill_steps"] == rec["gaps"] == 0 and not rec["cold"]
    assert rec["latency_ms"] > 0


def test_a_killed_engine_leaves_failed_records(metrics_on):
    """One request mid-generation and one still queued when the engine
    is killed: each leaves one record, outcome `failed`, with the
    stamps it got as far as."""
    reached, go = threading.Event(), threading.Event()

    def hold(request, _token, _final):
        if len(request.tokens) == 3:
            reached.set()
            go.wait(60)

    engine = engine_of(toy_model(), max_batch=1)
    try:
        running = engine.submit(prompts((9,))[0], max_new_tokens=40,
                                stream=hold)
        assert reached.wait(300)
        queued = engine.submit(prompts((5,))[0], max_new_tokens=4)
        engine.kill(RuntimeError("killed by the test"))
        go.set()
        for r in (running, queued):
            with pytest.raises(RuntimeError, match="killed by the test"):
                r.wait(60)
    finally:
        go.set()
        engine.close()
    requests, _steps = logs()
    by_id = {r["request"]: r for r in requests}
    assert set(by_id) == {running.id, queued.id} and len(requests) == 2
    assert {r["outcome"] for r in requests} == {"failed"}
    mid, never = by_id[running.id], by_id[queued.id]
    assert 3 <= mid["output_tokens"] == len(running.tokens) < 40
    assert mid["ttft_ms"] == mid["queue_ms"] + mid["plan_ms"] \
        + mid["prefill_ms"] + mid["inflight_ms"] + mid["deliver_ms"]
    assert mid["gaps"] == mid["output_tokens"] - 1
    assert never["t_admit"] is None and never["ttft_ms"] is None
    assert never["latency_ms"] == pytest.approx(
        (queued.finish_time - queued.submit_time) * 1e3)


def test_a_request_that_expires_in_the_queue_leaves_an_expired_record(
        metrics_on):
    """One row: the second request's deadline passes while the first
    holds it (the first steps of a fresh model compile for longer)."""
    with engine_of(toy_model(), max_batch=1) as engine:
        first = engine.submit(prompts((9,))[0], max_new_tokens=NEW_TOKENS)
        late = engine.submit(prompts((5,))[0], max_new_tokens=4,
                             deadline_s=1e-3)
        first.wait(300)
        with pytest.raises(DeadlineExceededError):
            late.wait(60)
    requests, _steps = logs()
    by_id = {r["request"]: r for r in requests}
    assert len(requests) == 2
    assert by_id[first.id]["outcome"] == "finished"
    rec = by_id[late.id]
    assert rec["outcome"] == "expired"
    assert rec["t_admit"] is None and rec["queue_ms"] is None
    assert rec["t_first_dispatch"] is None and rec["ttft_ms"] is None
    assert rec["latency_ms"] >= 1.0 and rec["output_tokens"] == 0


@pytest.mark.parametrize("engine_kw", [dict(spec_k=3),
                                       dict(spec_tree="2x2")],
                         ids=["spec_k", "spec_tree"])
def test_a_speculative_engine_fills_the_same_fields(metrics_on, engine_kw):
    # periodic prompts, so the n-gram drafter has something to propose
    lens = (12, 18, 7)
    with engine_of(toy_model(), **engine_kw) as engine:
        reqs = [engine.submit((list(range(4)) * 8)[:n],
                              max_new_tokens=NEW_TOKENS) for n in lens]
        for r in reqs:
            r.wait(300)
    requests, steps = logs()
    by_step = {s["step"]: s for s in steps}
    assert len(requests) == len(lens)
    assert {s["kind"] for s in steps} >= {"mixed", "spec"}
    for r in requests:
        assert set(r) == FIELDS and r["outcome"] == "finished"
        assert r["queue_ms"] + r["plan_ms"] + r["prefill_ms"] \
            + r["inflight_ms"] + r["deliver_ms"] == r["ttft_ms"]
        token = by_step[r["first_token_step"]]
        # a prompt goes through mixed steps here too, each taken as
        # soon as it is dispatched: nothing is ever queued ahead
        assert token["kind"] == "mixed" and token["queued"] == 0
        assert r["t_first_ready"] == token["t_ready"]
        assert r["queued_at_first_token"] == 0
        assert r["output_tokens"] == NEW_TOKENS == r["gaps"] + 1
        assert by_step[r["last_step"]]["kind"] in ("spec", "mixed")
        assert r["gap_max_kind"] in ("spec", "mixed", "decode")
    # the windows gave tokens, several at a time where drafts held
    assert any(by_step[r["last_step"]]["kind"] == "spec"
               for r in requests)
    assert sum(r["gaps"] for r in requests) \
        > sum(1 for s in steps if s["kind"] == "spec")


def test_trace_events_and_the_record_share_their_stamps(metrics_on):
    """One set of stamps, two sinks: a traced request's `queue_wait`
    ends at the record's `t_admit`, its first `prefill_chunk` at
    `t_first_dispatch`, and the record carries its `trace_id`."""
    tracing.reset()
    tracing.enable()
    try:
        with engine_of(toy_model()) as engine:
            req = engine.submit(prompts((2 * CHUNK + 3,))[0],
                                max_new_tokens=4)
            req.wait(300)
        events = [e for e in tracing.events()
                  if e.get("args", {}).get("trace_id") == req.trace_id]
    finally:
        tracing.disable()
        tracing.reset()
    (rec,), _steps = logs()
    assert rec["trace_id"] == req.trace_id is not None

    def us(t):
        return int(t * 1e9) // 1000

    (wait,) = [e for e in events if e["name"] == "queue_wait"]
    assert wait["ts"] == us(rec["t_submit"])
    assert wait["ts"] + wait["dur"] in (us(rec["t_admit"]),
                                        us(rec["t_admit"]) - 1)
    chunks = [e for e in events if e["name"] == "prefill_chunk"]
    assert len(chunks) == rec["prefill_steps"] == 3
    ends = [e["ts"] + e["dur"] for e in chunks]
    assert abs(ends[0] - us(rec["t_first_dispatch"])) <= 1
    assert abs(ends[-1] - us(rec["t_last_prefill_dispatch"])) <= 1
    windows = [e for e in events if e["name"] == "decode_window"]
    assert len(windows) == rec["gaps"]


def test_the_dump_summarises_the_phases(metrics_on):
    with engine_of(toy_model()) as engine:
        for r in [engine.submit(p, max_new_tokens=4)
                  for p in prompts((5, 9))]:
            r.wait(300)
    doc = metrics_on.to_dict()["samples"]["serving/request"]
    assert doc["added"] == 2
    assert {"ttft_ms", "queue_ms", "inflight_ms", "ahead_ms",
            "latency_ms"} <= set(doc["fields"])
    assert doc["fields"]["ttft_ms"]["count"] == 2
    assert "ptpu_serving_request_ttft_ms_count 2" \
        in metrics_on.to_prometheus()


def test_ttft_is_the_phases_added_left_to_right():
    """`sum()` compensates its rounding (Python 3.12) and differs from
    the plain left-to-right sum by an ulp where the phases differ by
    orders of magnitude (2 of 1,785 records in the first chip runs, both
    of warm-up requests that waited out a compile): the record's
    `ttft_ms` is the plain sum, so the identity holds to the float for
    stamps on which the two differ."""
    import types

    from paddle_tpu.serving.engine import _ModelWorker, _ms
    from paddle_tpu.serving.scheduler import GenerationRequest, _RequestLog

    rng = np.random.RandomState(39)
    for _ in range(100000):
        stamps = (250.0   # a young clock: more bits below the second
                  + np.cumsum(10 ** rng.uniform(-5, 1, size=6))).tolist()
        phases = [_ms(a, b) for a, b in zip(stamps, stamps[1:])]
        q, p, pr, i, d = phases
        if sum(phases) != q + p + pr + i + d:
            break
    else:
        pytest.fail("no stamps on which sum() and the plain sum differ")
    t_submit, t_admit, t_first, t_last, t_ready, t_token = stamps
    request = GenerationRequest([1, 2, 3], max_new_tokens=2)
    request.submit_time, request.start_time = t_submit, t_admit
    request.first_token_time = request.finish_time = t_token
    request.tokens.append(5)
    noted = _RequestLog()
    step = dict(step=1, kind="mixed", cold=False, queued=0, device_ms=None)
    noted.dispatched(dict(step, t_dispatched=t_first), True, None)
    noted.dispatched(dict(step, step=2, t_dispatched=t_last,
                          t_ready=t_ready), True, 0)
    rec = _ModelWorker._request_record(
        types.SimpleNamespace(name="default"), request, noted, "finished")
    assert rec["ttft_ms"] == rec["queue_ms"] + rec["plan_ms"] \
        + rec["prefill_ms"] + rec["inflight_ms"] + rec["deliver_ms"]
    assert rec["ttft_ms"] != sum(rec[k] for k in PHASES)
    assert (rec["first_step"], rec["first_token_step"],
            rec["prefill_steps"]) == (1, 2, 2)
