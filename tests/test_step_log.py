"""The step log: raw samples in the registry, one record per serving
step written by the worker, one host time per ``Executor.run``, the
names the steps and kernels carry into a device trace, and the native
library's build under a lock (docs/OBSERVABILITY.md). CPU, toy widths.
"""

import importlib.util
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from paddle_tpu import flags
from paddle_tpu.observability import metrics, tracing
from paddle_tpu.serving import (GenerationConfig, GenerationModel,
                                ServingEngine)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
import ptpu_stats  # noqa: E402

STAMPS = ("t_tick", "t_planned", "t_dispatched", "t_wait", "t_ready",
          "t_done")


@pytest.fixture
def metrics_on():
    metrics.reset()
    metrics.enable()
    try:
        yield metrics.registry()
    finally:
        metrics.disable()
        metrics.reset()


# -- the raw-sample kind ------------------------------------------------

@pytest.mark.parametrize("q", [0.0, 0.5, 0.9, 0.95, 0.99, 1.0])
def test_samples_quantiles_are_exact(q):
    rng = np.random.RandomState(11)
    values = rng.lognormal(size=257).tolist()
    reg = metrics.MetricsRegistry()
    plain = reg.samples("t/plain")
    records = reg.samples("t/records", fields=("ms",))
    for i, v in enumerate(values):
        plain.add(v)
        # a record without the field, or with None, is not a sample of it
        records.add({"ms": v, "i": i})
        records.add({"ms": None} if i % 2 else {"i": i})
    want = float(np.percentile(values, 100 * q))
    assert plain.quantile(q) == pytest.approx(want, rel=1e-12)
    assert records.quantile(q, "ms") == pytest.approx(want, rel=1e-12)
    assert plain.max() == records.max("ms") == max(values)
    with pytest.raises(ValueError):
        plain.quantile(1.5)


def test_samples_ring_is_bounded_and_counts_evictions():
    reg = metrics.MetricsRegistry()
    s = reg.samples("t/ring", maxlen=8)
    assert s.quantile(0.5) is None and s.max() is None
    for i in range(20):
        s.add(float(i))
    assert s.records() == [float(i) for i in range(12, 20)]  # newest win
    assert (s.added, s.evicted, s.maxlen) == (20, 12, 8)
    d = s.to_dict()
    assert d["added"] == 20 and d["evicted"] == 12 and d["count"] == 8
    assert d["max"] == 19.0 and d["p50"] == 15.5
    assert reg.samples("t/ring") is s
    with pytest.raises(TypeError):
        reg.counter("t/ring")            # one name, one kind


def test_samples_are_the_null_metric_and_no_ring_when_off():
    metrics.reset()
    assert not metrics.enabled()
    s = metrics.samples("t/off")
    assert s is metrics.NULL_METRIC
    s.add(1.0)
    assert "t/off" not in metrics.registry().metrics()


def test_samples_round_trip_through_ptpu_stats(tmp_path, capsys):
    reg = metrics.MetricsRegistry()
    reg.counter("t/c").inc(2)
    plain = reg.samples("t/run_ms")
    steps = reg.samples("t/step", fields=("host_ms", "wait_ms"))
    reg.samples("t/empty")
    for i in range(40):
        plain.add(0.5 + i)
        steps.add({"host_ms": 0.1 * i, "wait_ms": None if i % 4 else 3.0,
                   "kind": "decode"})
    text = reg.to_prometheus()
    assert "# TYPE ptpu_t_run_ms summary" in text
    assert 'ptpu_t_run_ms{quantile="0.95"} %r' % plain.quantile(0.95) \
        in text
    assert "ptpu_t_run_ms_count 40" in text
    assert "# TYPE ptpu_t_step_host_ms summary" in text
    assert "ptpu_t_step_wait_ms_count 10" in text
    assert "ptpu_t_empty_count 0" in text
    doc = json.loads(json.dumps(reg.to_dict()))
    assert doc["samples"]["t/step"]["fields"]["host_ms"]["p50"] \
        == steps.quantile(0.5, "host_ms")
    assert ptpu_stats._to_prometheus(doc) == text
    path = reg.dump_json(str(tmp_path / "m.json"))
    assert ptpu_stats.main([path, "--assert-min", "t/step=40"]) == 0
    out = capsys.readouterr().out
    assert "t/step.host_ms" in out and "t/run_ms" in out


# -- the serving step log -----------------------------------------------

def toy_model():
    return GenerationModel.random(
        GenerationConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                         d_ff=64, max_seq_len=64), seed=7)


PROMPT_LENS = (5, 11, 17, 3, 9, 20)
NEW_TOKENS = 12


def serve(model, stream=None, lens=PROMPT_LENS, eos_id=None, **engine_kw):
    """One engine, every request to its end, the engine closed: the
    worker has written its last record. Returns (tokens, Δsteps)."""
    kw = dict(max_batch=4, max_seq_len=64, block_size=4, prefill_chunk=8)
    kw.update(engine_kw)
    rng = np.random.RandomState(3)
    with ServingEngine(model, **kw) as engine:
        steps0 = engine.stats()["default"]["steps"]
        reqs = [engine.submit(rng.randint(0, 64, size=n).tolist(),
                              max_new_tokens=NEW_TOKENS, eos_id=eos_id,
                              stream=stream)
                for n in lens]
        tokens = [r.wait(300) for r in reqs]
        steps = engine.stats()["default"]["steps"] - steps0
    return tokens, steps


@pytest.fixture(scope="module")
def logged_run():
    """A chunked run with metrics on and no `async_depth` stated: its
    records, its step count, the registry's counters, and the same
    requests served with metrics off by a model of the same weights."""
    with pytest.MonkeyPatch.context() as mp:
        mp.delenv("PTPU_SERVE_ASYNC_STEPS", raising=False)
        return _logged_run()


def _logged_run():
    metrics.reset()
    assert not metrics.enabled()
    off_model = toy_model()
    raw = off_model.make_decode_step(4, 16)
    off_tokens, off_steps = serve(off_model)
    off_records = metrics.registry().metrics().get("serving/step")
    metrics.enable()
    try:
        tokens, steps = serve(toy_model())
        reg = metrics.registry()
        out = dict(
            records=reg.samples("serving/step").records(), steps=steps,
            requests=reg.samples("serving/request").records(),
            async_depth=flags.env("PTPU_SERVE_ASYNC_STEPS"),
            tokens=tokens, off_tokens=off_tokens, off_steps=off_steps,
            off_records=off_records, raw_step=raw,
            counters={k: reg.counter("serving/" + k).value
                      for k in ("steps", "prefill_chunk_steps",
                                "prefill_tokens", "decode_tokens",
                                "prefill_rows_deferred",
                                "mixed_one_token_rows")})
    finally:
        metrics.disable()
        metrics.reset()
    return out


def check_one_record_per_step(run):
    recs = run["records"]
    assert len(recs) == run["steps"] == run["counters"]["steps"]
    assert [r["step"] for r in recs] == list(range(1, len(recs) + 1))
    assert {r["kind"] for r in recs} == {"mixed", "decode"}
    assert all(r["model"] == "default" for r in recs)


def check_mixed_steps_are_the_chunk_steps(run):
    mixed = [r for r in run["records"] if r["kind"] == "mixed"]
    assert len(mixed) == run["counters"]["prefill_chunk_steps"] > 0
    assert all(r["slots_total"] == 4 * 8 for r in mixed)
    assert all(r["slots_total"] == 4 for r in run["records"]
               if r["kind"] == "decode")
    assert all(0 < r["slots_used"] <= r["slots_total"]
               and r["rows"] <= 4 for r in run["records"])


def check_mixed_records_carry_rows_computed(run):
    # the toy engine's window (4 x 8 slots) is not larger than its
    # promise (4 rows + a budget of 4 chunks): every slot is a row
    for r in run["records"]:
        if r["kind"] == "mixed":
            assert r["rows_computed"] == r["slots_total"] == 4 * 8
            assert r["slots_used"] <= r["rows_computed"]
        else:
            assert "rows_computed" not in r


def check_mixed_records_carry_rows_deferred(run):
    # four rows of one chunk at most against a budget of four chunks:
    # the budget never binds here, and the field says so on every mixed
    # record and on no other
    for r in run["records"]:
        if r["kind"] == "mixed":
            assert r["rows_deferred"] == 0
        else:
            assert "rows_deferred" not in r
    assert run["counters"]["prefill_rows_deferred"] == 0


def check_mixed_records_carry_one_token_rows(run):
    # the rows of a mixed step that hold ONE token, which a decode
    # kernel takes (ISSUE 40): every decode row riding in it, and a
    # prompt's last chunk where that is one token (the prompts of 9 and
    # 17 tokens against a chunk of 8); on no decode record; the counter
    # is the records' sum
    mixed = [r for r in run["records"] if r["kind"] == "mixed"]
    for r in mixed:
        assert r["decode_tokens"] <= r["one_token_rows"] <= r["rows"]
    assert all("one_token_rows" not in r for r in run["records"]
               if r["kind"] != "mixed")
    assert sum(r["one_token_rows"] - r["decode_tokens"]
               for r in mixed) == 2
    assert sum(r["decode_tokens"] for r in mixed) > 0
    assert run["counters"]["mixed_one_token_rows"] == sum(
        r["one_token_rows"] for r in mixed)


def check_slots_used_is_what_the_scheduler_planned(run):
    recs = run["records"]
    # every prompt token is prefilled once; a request's first token
    # comes out of its last prompt chunk, each later one is a decode row
    prompt = sum(PROMPT_LENS)
    decode = len(PROMPT_LENS) * (NEW_TOKENS - 1)
    assert sum(r["prefill_tokens"] for r in recs) == prompt \
        == run["counters"]["prefill_tokens"]
    assert sum(r["decode_tokens"] for r in recs) == decode \
        == run["counters"]["decode_tokens"]
    assert sum(r["slots_used"] for r in recs) == prompt + decode


def check_stamps_are_ordered(run):
    for r in run["records"]:
        stamps = [r[k] for k in STAMPS]
        assert stamps == sorted(stamps), r
        assert r["t_dispatched"] <= r["t_end"]
        assert r["wait_ms"] == pytest.approx(
            (r["t_ready"] - r["t_wait"]) * 1e3)
        assert r["device_ms"] is None or 0 < r["device_ms"] \
            <= (r["t_ready"] - r["t_dispatched"]) * 1e3 + 1e-6


def check_host_and_wait_fit_in_the_tick(run):
    by_step = {r["step"]: r for r in run["records"]}
    consumed = set()
    for r in run["records"]:
        assert r["tick_ms"] == pytest.approx(
            (r["t_end"] - r["t_tick"]) * 1e3)
        # the wait inside a tick is the wait for the step it consumed
        waited = by_step[r["consumed"]]["wait_ms"] \
            if r["consumed"] is not None else 0.0
        assert 0 <= r["host_ms"] == pytest.approx(r["tick_ms"] - waited)
        if r["consumed"] is not None:
            taken = by_step[r["consumed"]]
            assert r["t_dispatched"] <= taken["t_wait"] \
                and taken["t_done"] <= r["t_end"]
            consumed.add(r["consumed"])
    # steps queue async_depth deep, so a result is taken
    # async_depth - 1 ticks on
    assert consumed and all(by_step[s]["queued"] <= run["async_depth"] - 1
                            for s in consumed)


def check_one_step_is_queued_where_no_depth_is_stated(run):
    # the program's default (ISSUE 43): one step behind the running one,
    # so a new request's first-token step stands behind one step at most
    assert run["async_depth"] == 2
    warm = [r for r in run["records"] if not r["cold"]]
    assert warm and all(r["queued"] <= 1 for r in warm)
    assert any(r["queued"] == 1 for r in warm)
    assert len(run["requests"]) == len(PROMPT_LENS)
    assert all(q["queued_at_first_token"] <= 1 for q in run["requests"])


def check_ran_dry_is_a_bool_and_false_on_the_first_step(run):
    recs = run["records"]
    assert all(type(r["ran_dry"]) is bool for r in recs)
    # the first step of the run follows no tick that dispatched
    assert recs[0]["ran_dry"] is False and recs[0]["queued"] == 0
    # a step with another still running ahead of it is not dry
    assert all(r["queued"] for r in recs if r["ran_dry"])


def check_cold_is_the_first_step_of_each_shape(run):
    seen, recs = set(), run["records"]
    for r in recs:
        assert r["cold"] == (r["kind"] not in seen), r
        seen.add(r["kind"])
    assert sum(r["cold"] for r in recs) == 2


def check_metrics_off_writes_nothing_and_changes_no_token(run):
    assert run["off_records"] is None
    assert run["off_tokens"] == run["tokens"]      # bitwise: same ints
    assert run["off_steps"] > 0
    # off, _instrument_step hands back the raw jitted function
    assert hasattr(run["raw_step"], "lower") \
        and run["raw_step"].__name__ == "decode_step"


@pytest.mark.parametrize("check", [
    check_one_record_per_step, check_mixed_steps_are_the_chunk_steps,
    check_mixed_records_carry_rows_computed,
    check_mixed_records_carry_rows_deferred,
    check_mixed_records_carry_one_token_rows,
    check_slots_used_is_what_the_scheduler_planned,
    check_stamps_are_ordered, check_host_and_wait_fit_in_the_tick,
    check_one_step_is_queued_where_no_depth_is_stated,
    check_ran_dry_is_a_bool_and_false_on_the_first_step,
    check_cold_is_the_first_step_of_each_shape,
    check_metrics_off_writes_nothing_and_changes_no_token,
], ids=lambda f: f.__name__[len("check_"):])
def test_engine_step_log(logged_run, check):
    check(logged_run)


def sleepy_stream(seconds):
    """A stream callback that sleeps once, at a request's fifth token,
    and the list that gets the moment it fell asleep."""
    slept = []

    def stream(request, _token, _final):
        if len(request.tokens) == 5 and not slept:
            slept.append(time.perf_counter())
            time.sleep(seconds)

    return stream, slept


@pytest.mark.parametrize("async_depth", [1, 4])
def test_a_slow_stream_callback_is_host_time_not_wait(metrics_on,
                                                     async_depth):
    stream, slept = sleepy_stream(0.05)
    serve(toy_model(), stream=stream, lens=(6,),
          async_depth=async_depth)
    recs = metrics_on.samples("serving/step").records()
    (t_cb,) = slept
    # the step whose token was streamed: the sleep is after its wait
    taken = next(r for r in recs if r["t_ready"] <= t_cb <= r["t_done"])
    assert (taken["t_done"] - taken["t_ready"]) * 1e3 >= 50
    # the tick it ran in belongs to the step that tick dispatched
    tick = next(r for r in recs if r["t_tick"] <= t_cb <= r["t_end"])
    assert tick["consumed"] == taken["step"]
    assert (tick is taken) == (async_depth == 1)
    assert tick["host_ms"] >= 50
    assert all(r["wait_ms"] < 50 for r in recs if not r["cold"])
    assert all(r["host_ms"] < 50 for r in recs
               if r is not tick and not r["cold"])


@pytest.mark.parametrize("eos", [False, True], ids=["to_length", "eos"])
@pytest.mark.parametrize("async_depth", [1, 2, 4])
def test_served_tokens_do_not_depend_on_the_depth(logged_run, async_depth,
                                                  eos):
    """The depth orders the same steps: their inputs chain on the
    device, so every request's tokens are those of the run with no
    depth stated, to the int. With an EOS the `async_depth - 1` steps
    dispatched for a finished row are discarded at every depth."""
    want = logged_run["off_tokens"]
    eos_id = None
    if eos:
        # a token some request emits mid-way: it stops there, the
        # others (which never emit it, or later) run on
        eos_id = want[0][4]
        want = [t[:t.index(eos_id) + 1] if eos_id in t else t
                for t in want]
        assert len(want[0]) < NEW_TOKENS
    tokens, _steps = serve(toy_model(), eos_id=eos_id,
                           async_depth=async_depth)
    assert tokens == want


@pytest.mark.parametrize("async_depth", [1, 2])
def test_an_arrival_into_an_idle_engine_is_not_a_dry_queue(metrics_on,
                                                           async_depth):
    """`ran_dry` counts a device left idle by a WORKING host. A request
    that finds the worker idle finds the device idle too, and its first
    step reads false: at depth 1, where the tick before the idle
    stretch dispatched the last step of the request before it, as at
    depth 2, where that tick only drained."""
    kw = dict(max_batch=4, max_seq_len=64, block_size=4, prefill_chunk=8,
              async_depth=async_depth)
    with ServingEngine(toy_model(), **kw) as engine:
        for _ in range(3):
            engine.submit([5, 9, 2, 7, 1], max_new_tokens=6).wait(300)
            time.sleep(0.25)          # the worker waits on its condition
    recs = metrics_on.samples("serving/step").records()
    reqs = metrics_on.samples("serving/request").records()
    assert len(reqs) == 3
    assert all(type(r["ran_dry"]) is bool for r in recs)
    by_step = {r["step"]: r for r in recs}
    for q in reqs:
        first = by_step[q["first_step"]]
        assert first["queued"] == 0 and first["ran_dry"] is False
    if async_depth == 1:
        # synchronous: the device waits out every tick of a busy stretch
        firsts = {q["first_step"] for q in reqs}
        assert all(r["ran_dry"] for r in recs if r["step"] not in firsts)


@pytest.mark.parametrize("async_depth", [2, 4])
def test_a_tick_longer_than_the_queued_steps_runs_the_queue_dry(
        metrics_on, async_depth):
    """A stream callback that sleeps longer than the queued steps run:
    they are done when the next tick dispatches, and that step's record
    says the device had nothing left (`ran_dry`)."""
    stream, slept = sleepy_stream(0.1)
    serve(toy_model(), stream=stream, lens=(6,), async_depth=async_depth)
    recs = metrics_on.samples("serving/step").records()
    (t_cb,) = slept
    tick = next(r for r in recs if r["t_tick"] <= t_cb <= r["t_end"])
    after = next(r for r in recs if r["step"] == tick["step"] + 1)
    assert tick["host_ms"] >= 100
    assert after["queued"] == async_depth - 1 and after["ran_dry"] is True


def latent_toy_model():
    from paddle_tpu.serving.latent_moe import LatentMoEBlock

    return GenerationModel.random(GenerationConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq_len=64, block=LatentMoEBlock(
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            kv_lora_rank=128, n_routed_experts=8, experts_per_token=2,
            n_shared_experts=2, moe_d_ff=32)), seed=7)


@pytest.mark.parametrize("make_model", [toy_model, latent_toy_model],
                         ids=["xglm", "latent"])
def test_decode_records_count_the_pages_their_rows_hold(metrics_on,
                                                        make_model):
    """Every record carries `cached_tokens`; a decode record of either
    block also the pages a kernel that walks each row's own pages
    copies (`pages_walked`) beside the grid steps of one that visits
    every table slot (`pages_grid`): host integers from the scheduler's
    arrays, the bytes and the ceiling a decode kernel is judged by."""
    lens = (5, 11, 17)
    serve(make_model(), lens=lens)
    recs = metrics_on.samples("serving/step").records()
    decode = [r for r in recs if r["kind"] == "decode"]
    assert decode and len(decode) < len(recs)
    for r in recs:
        # each live row holds at least its step's token, and no more
        # than a whole request
        assert r["slots_used"] <= r["cached_tokens"] \
            <= r["rows"] * (max(lens) + NEW_TOKENS)
        assert ("pages_walked" in r) == ("pages_grid" in r) \
            == (r["kind"] == "decode")
    for r in decode:
        # max_batch 4 x 16 blocks of 4 tokens a row
        assert r["pages_grid"] == 4 * 16
        # a row at position p holds p // 4 + 1 pages: at least
        # tokens / 4 over the rows, under one more a row
        assert r["cached_tokens"] / 4 <= r["pages_walked"] \
            <= r["cached_tokens"] / 4 + r["rows"]
        assert r["rows"] <= r["pages_walked"] <= r["pages_grid"]
    # one request alone: the pages of ITS position, step by step
    serve(make_model(), lens=(9,))
    alone = [r for r in
             metrics_on.samples("serving/step").records()[len(recs):]
             if r["kind"] == "decode"]
    # the prompt's last chunk made the first token, so the row of
    # decode step k sits at position 9 + k
    assert [r["pages_walked"] for r in alone] \
        == [(9 + k) // 4 + 1 for k in range(len(alone))]
    assert [r["cached_tokens"] for r in alone] \
        == [9 + k + 1 for k in range(len(alone))]


@pytest.mark.parametrize("engine_kw,rows", [
    # the default budget of four chunks: max_batch + budget token rows
    (dict(max_batch=8), 8 + 4 * 8),
    (dict(prefill_token_budget=8), 4 + 8),
    # a window no larger than the promise: every slot
    (dict(prefill_token_budget=64), 4 * 8),
], ids=["default_budget", "small_budget", "window_within_promise"])
def test_rows_computed_is_the_promise_or_the_window(metrics_on, engine_kw,
                                                    rows):
    """`rows_computed` of a mixed record: the token rows the compiled
    chunk step runs its per-token work on, which is what the engine
    promised `make_prefill_step` (`max_batch` + the prefill budget)
    where that is fewer than the window's slots."""
    tokens, _steps = serve(toy_model(), **engine_kw)
    assert all(len(t) == NEW_TOKENS for t in tokens)
    mixed = [r for r in metrics_on.samples("serving/step").records()
             if r["kind"] == "mixed"]
    assert mixed
    for r in mixed:
        assert r["rows_computed"] == rows <= r["slots_total"]
        assert r["slots_used"] <= r["rows_computed"]


@pytest.mark.parametrize("engine_kw,binds", [
    (dict(prefill_token_budget=8), True),     # one chunk a step
    (dict(prefill_token_budget=12), True),    # a chunk and a half
    (dict(), False),                          # the rule's: four chunks
], ids=["one_chunk", "chunk_and_a_half", "default_budget"])
def test_rows_deferred_counts_what_sat_out(metrics_on, engine_kw, binds):
    """`rows_deferred` of a mixed record: the prefilling rows that took
    no token in the step because the rows admitted before them had the
    budget. Six prompts over four rows: with a chunk of budget a step
    some row waits in most mixed steps, with the default never; the
    counter is the records' sum and the registry summarises the field."""
    tokens, _steps = serve(toy_model(), **engine_kw)
    assert all(len(t) == NEW_TOKENS for t in tokens)
    mixed = [r for r in metrics_on.samples("serving/step").records()
             if r["kind"] == "mixed"]
    budget = engine_kw.get("prefill_token_budget", 4 * 8)
    for r in mixed:
        assert 0 <= r["rows_deferred"] <= r["rows"] + r["rows_deferred"] \
            <= 4
        assert r["prefill_tokens"] <= budget
        # a row sits out only where the budget was spent to the last
        # token on the rows ahead of it
        assert not r["rows_deferred"] or r["prefill_tokens"] == budget
    total = sum(r["rows_deferred"] for r in mixed)
    assert (total > 0) == binds
    assert metrics_on.counter("serving/prefill_rows_deferred").value \
        == total
    summary = metrics_on.to_dict()["samples"]["serving/step"]["fields"]
    assert summary["rows_deferred"]["count"] == len(mixed)
    assert summary["rows_deferred"]["max"] == max(
        r["rows_deferred"] for r in mixed)


def test_a_speculative_window_is_logged_as_spec(metrics_on):
    _tokens, steps = serve(toy_model(), lens=(6, 9), spec_k=3)
    recs = metrics_on.samples("serving/step").records()
    assert len(recs) == steps
    spec = [r for r in recs if r["kind"] == "spec"]
    # the prompts go through mixed windows, everything after them
    # through verify windows
    assert spec and {r["kind"] for r in recs} == {"mixed", "spec"}
    for r in spec:
        # dispatched and taken in one tick, nothing queued ahead of it
        assert r["consumed"] == r["step"] and r["queued"] == 0
        assert r["slots_total"] == 4 * (3 + 1)
        assert r["rows"] <= r["slots_used"] <= r["slots_total"]
        assert 1 <= r["decode_tokens"] <= r["slots_used"]
        assert [r[k] for k in STAMPS] == sorted(r[k] for k in STAMPS)


def test_tracing_emits_the_spans_from_the_same_record():
    metrics.reset()
    tracing.reset()
    tracing.enable()
    try:
        serve(toy_model(), lens=(6, 9))
        events = tracing.events()
    finally:
        tracing.disable()
        tracing.reset()
    # tracing alone keeps the log's stamps but no ring in the registry
    assert "serving/step" not in metrics.registry().metrics()
    steps = [e for e in events if e["name"] == "serving_step"]
    waits = [e for e in events if e["name"] == "serving_wait"]
    assert steps and len(steps) == len(waits)
    for e in steps:
        args = e["args"]
        assert args["kind"] in ("mixed", "decode")
        assert {"rows", "slots_used", "host_ms", "wait_ms", "device_ms",
                "cold"} <= set(args)
        assert not any(k.startswith("t_") for k in args)
    by_step = {e["args"]["step"]: e for e in waits}
    for e in steps:
        wait = by_step[e["args"]["step"]]
        assert e["ts"] + e["dur"] <= wait["ts"] + 1   # integer µs
        assert wait["dur"] == pytest.approx(e["args"]["wait_ms"] * 1e3,
                                            abs=1.5)
    names = {e["name"] for e in events}
    assert {"prefill_chunk", "decode_window", "queue_wait"} <= names


# -- the executor's host time -------------------------------------------

def test_executor_run_host_ms_is_one_sample_per_run(metrics_on):
    import paddle_tpu as fluid
    from paddle_tpu import layers

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="sl_x", shape=[8], dtype="float32")
        loss = layers.reduce_mean(layers.fc(x, 4))
        fluid.optimizer.SGD(0.1).minimize(loss)
    feed = {"sl_x": np.ones((2, 8), np.float32)}
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup)
        t0 = time.perf_counter()
        for _ in range(5):
            exe.run(main, feed=feed, fetch_list=[loss])
        wall_ms = (time.perf_counter() - t0) * 1e3
        s = metrics_on.samples("executor/run_host_ms")
        assert s.added == 6 == metrics_on.counter("executor/steps").value
        assert 0 < sum(s.records()[1:]) <= wall_ms
        metrics.disable()
        exe.run(main, feed=feed, fetch_list=[loss])
        assert s.added == 6


# -- names in the device trace ------------------------------------------

def _lowered_text(step, *shapes):
    import jax

    return step.lower(*[jax.ShapeDtypeStruct(s, d) if s is not None else d
                        for s, d in shapes]).as_text(debug_info=True)


def test_step_names_and_scopes_reach_the_lowered_hlo():
    """Beside test_profiler's test_named_scopes_reach_lowered_hlo: the
    serving steps' programs are told apart by name (`XLA Modules` in a
    device trace) and their layers' parts by scope."""
    model = toy_model()
    assert not metrics.enabled()
    i32, f32 = np.int32, np.float32
    B, Mb, bs, C = 4, 16, 4, 8
    kv = ((2, B * Mb + 1, bs, 2, 16), f32)
    weights = (None, {k: np.asarray(v) for k, v in model.weights.items()})
    row, flag = ((B,), i32), ((B,), np.bool_)
    decode = model.make_decode_step(B, Mb)
    text = _lowered_text(decode, weights, kv, kv, row, flag, row, row,
                         ((B, Mb), i32), flag)
    assert "jit_decode_step" in text
    for scope in ("kv_write", "kv_read", "attention", "ffn", "head"):
        assert "decode_step)/%s" % scope in text or "/%s/" % scope in text
    window = [weights, kv, kv, None, flag, row, row, row, ((B, Mb), i32),
              flag]
    for name, step, width in (
            ("chunk_step", model.make_prefill_step(B, Mb, C), C),
            ("spec_step", model.make_spec_step(B, Mb, 4), 4)):
        window[3] = ((B, width), i32)
        text = _lowered_text(step, *window)
        assert "jit_" + name in text and "kv_read" in text
    draft = model.make_draft_step(B, Mb, 3)
    text = _lowered_text(draft, weights, kv, kv, row, row, ((B, Mb), i32),
                         flag)
    assert "jit_draft_step" in text
    assert model.trace_count == 4


@pytest.mark.parametrize("kernel,name", [
    ("paged_decode", "paged_decode_attention"),
    ("spec_window", "paged_attention"),
    ("spec_window_tree", "paged_attention_tree"),
    ("chunk_window", "paged_chunk_attention"),
    ("flash_attention", "flash_attention"),
    ("int8_matmul", "int8_matmul")])
def test_kernel_names_reach_the_lowered_hlo(kernel, name):
    """Each `pl.pallas_call` site names its kernel: the name is the
    kernel's row in a device trace (unnamed, the paged kernel's row was
    called after the jitted function around it)."""
    import jax

    import chip_smoke
    from paddle_tpu.core import device
    from paddle_tpu.ops import pallas_kernels
    from paddle_tpu.ops.kernel_registry import registered_kernels

    specs, kwargs, _qualify, _fill = \
        chip_smoke.kernel_cases(chip_smoke.TOY)[kernel]
    fn = registered_kernels()[kernel].pallas
    if kernel == "flash_attention":
        # on a TPU self-attention goes to the jax library's kernel; the
        # in-repo call site is the portable one (cross-attention shapes)
        fn = pallas_kernels.flash_attention_portable
        kwargs = {}
    with device.compiling_for(
            device.DeviceIdentity("tpu", "TPU v5 lite", 1)):
        text = jax.jit(lambda *a: fn(*a, **kwargs)).trace(
            *[jax.ShapeDtypeStruct(s, d) for s, d in specs]).lower(
                lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    assert 'kernel_name = "%s"' % name in text


# -- the native library's build -----------------------------------------

LOAD_AND_ROUND_TRIP = """
import importlib.util, os, sys
spec = importlib.util.spec_from_file_location("native_copy", sys.argv[1])
native = importlib.util.module_from_spec(spec)
spec.loader.exec_module(native)
native._NATIVE_DIR = sys.argv[2]
assert native.loaded() is None
lib = native.lib()
assert lib is not None and native.loaded() is lib
path = os.path.join(sys.argv[2], "rt_%d.recordio" % os.getpid())
records = [b"record-%d" % i * (i % 7 + 1) for i in range(300)]
w = native.RecordIOWriter(path, max_chunk_records=64)
for r in records:
    w.write(r)
w.close()
assert list(native.RecordIOScanner(path)) == records
print("round-trip ok")
"""


def test_four_fresh_processes_build_the_library_once_and_all_load_it(
        tmp_path):
    """A fresh checkout has no .so and several test workers ask for it
    at once: the build runs under a lock, to a temporary name."""
    import shutil

    if shutil.which("make") is None or shutil.which("g++") is None:
        pytest.skip("no toolchain")
    src = os.path.join(REPO, "native")
    copy = tmp_path / "native"
    copy.mkdir()
    for name in os.listdir(src):
        if name.endswith((".cc", ".h")) or name == "Makefile":
            shutil.copy(os.path.join(src, name), copy / name)
    native_py = os.path.join(REPO, "paddle_tpu", "core", "native.py")
    procs = [subprocess.Popen(
        [sys.executable, "-c", LOAD_AND_ROUND_TRIP, native_py, str(copy)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for _ in range(4)]
    outs = [p.communicate(timeout=240)[0] for p in procs]
    assert [p.returncode for p in procs] == [0] * 4, outs
    assert all("round-trip ok" in o for o in outs)
    left = sorted(os.listdir(copy))
    assert "libpaddle_tpu_native.so" in left
    assert not [n for n in left if n.endswith(".tmp")]


def test_a_span_never_builds_the_library(monkeypatch):
    """tracing used to call native.lib() from every span's exit: the
    first traced step could run make inside the serving worker."""
    spec = importlib.util.spec_from_file_location(
        "native_unloaded", os.path.join(REPO, "paddle_tpu", "core",
                                        "native.py"))
    fresh = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fresh)

    def no_make(*_a, **_k):
        raise AssertionError("a span asked for a build")

    monkeypatch.setattr(fresh.subprocess, "run", no_make)
    monkeypatch.setattr(tracing, "_native", fresh)
    tracing.reset()
    tracing.enable()
    try:
        with tracing.span("quiet"):
            pass
        tracing.complete("quiet_too", 0, 1000)
        assert [e["name"] for e in tracing.events()] \
            == ["quiet", "quiet_too"]
    finally:
        tracing.disable()
        tracing.reset()
    assert fresh.loaded() is None
