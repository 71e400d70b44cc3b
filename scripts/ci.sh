#!/usr/bin/env bash
# CI driver (parity: paddle/scripts/paddle_build.sh — cmake_gen/build :55/:290,
# run_test :320, API-diff check). Stages:
#   build      - compile the C++ runtime spine + its gtest binary
#   test       - native tests, then the python suite on the 8-dev CPU mesh
#   api_check  - enforce the frozen public API surface (API.spec)
#   bench      - headline benchmark (single JSON line; needs a TPU and
#                fails without one)
#   stress     - 5x back-to-back run of the rendezvous-heaviest file
#   obs        - observability smoke: metrics dump + stats CLI render
#   bench-smoke- tiny-model bench.py --metrics-out run asserting the async
#                pipeline telemetry (in-flight window, prefetch H2D) lands
#                in the dump
#   chaos      - fault-injected fit-a-line train (NaN step + torn
#                checkpoint, docs/RESILIENCE.md): gates on
#                resilience/rollbacks >= 1, corrupt-checkpoint fallback,
#                and final-loss sanity via ptpu_stats --assert-max
#   data-chaos - fault-tolerant data-plane receipt (docs/DATA_PLANE.md):
#                train_from_dataset through an injected corrupt shard,
#                a shuffle-peer death mid-exchange, and a kill-then-
#                resume leg, all under PTPU_LOCK_CHECK=1 — gating
#                data/records_corrupt >= 1, data/peer_failovers >= 1,
#                finite decreasing loss, the resumed record stream
#                bitwise vs the unfailed oracle, and
#                concurrency/violations == 0
#   amp        - mixed-precision receipt (docs/MIXED_PRECISION.md): the
#                tiny bench fp32-vs-AMP leg pair, gating on the bf16
#                rewrite firing (amp/casts_inserted >= 1), finite loss,
#                and the AMP leg not regressing vs fp32
#   serve      - continuous-batching serving receipt (docs/SERVING.md):
#                the same Poisson request stream through a batched vs a
#                serial engine, gating on occupancy > 1, token-identical
#                outputs, finite request latencies, and batched >= 2x
#                serial aggregate tokens/s
#   lint       - repo-invariant linter (docs/STATIC_ANALYSIS.md):
#                tools/ptpu_lint.py over paddle_tpu/, zero findings
#   race       - concurrency-analysis receipt (docs/STATIC_ANALYSIS.md
#                "Concurrency analysis"): the serving fast path
#                (chunked prefill + prefix cache, concurrent
#                submitters) and the resilience chaos leg replayed
#                under PTPU_LOCK_CHECK=1 with sys.setswitchinterval
#                (1e-5) jitter to flush interleavings, gating
#                concurrency/violations == 0 with order_edges >= 1 and
#                locks_tracked >= 6 (the tracker demonstrably saw the
#                real runtime, not a stub)
#   verify     - Program IR verifier receipt: fit-a-line (default
#                pipeline + PTPU_NO_PROGRAM_OPT=1) and the tiny
#                transformer bench with AMP on, all under
#                PTPU_VERIFY_PASSES=1, gating verify/violations == 0
#   quant      - int8 quantized-inference receipt (docs/QUANTIZATION.md):
#                a tiny calibrate -> quant_rewrite -> predict run under
#                PTPU_VERIFY_PASSES=1 gating quant/ops_rewritten >= 1,
#                verify/violations == 0 and the numerics bound, then the
#                bench quant legs gating top-1 agreement, the >= 40%
#                weight-store shrink, token-identical int8 serving, and
#                the int8-vs-fp32 serving throughput floor (retried like
#                serve's ratio; functional gates hold every attempt)
#   fleet      - fault-tolerant serving-fleet receipt (docs/SERVING.md
#                "Fleet & failover"): a 2-replica ServingRouter under
#                PTPU_LOCK_CHECK=1 survives (a) an injected replica
#                death and (b) a transient step failure plus an
#                injected stall — gating zero token divergence vs the
#                unfailed reference (incl. requests re-admitted
#                mid-generation), router/failovers >= 1,
#                router/readmitted >= 1, clean KV-pool invariants on
#                the dead replica, and concurrency/violations == 0 —
#                then the 1->2 replica throughput-scaling bench
#                (core-aware floor, retried like serve's ratios)
#   online     - online-learning hot-swap receipt (docs/SERVING.md
#                "Online updates"): a 2-replica fleet under
#                PTPU_LOCK_CHECK=1 with live traffic survives the full
#                chaos matrix — happy-path publish + rollout, a torn
#                export (detected, never served, republished), an
#                injected canary anomaly (structured rollback to the
#                incumbent) and a replica killed mid-drain (rollout
#                completes on the survivor) — gating per-version token
#                identity vs reference_decode, the zero-lost-requests
#                ledger, online/rollbacks >= 1, online/torn_exports
#                >= 1 and concurrency/violations == 0; then the slow
#                train-while-serving pytest leg and the bench
#                steady-vs-rollout throughput pair (ratio floor
#                retried like serve's; functional gates every attempt)
#   rec        - recommender fast-path receipt (docs/RECOMMENDER.md):
#                a host-table DeepFM CTR run twice — legacy sync
#                lookups vs async prefetch + hot-row device cache —
#                under PTPU_VERIFY_PASSES=1 + PTPU_LOCK_CHECK=1 with
#                switch-interval jitter, gating bitwise-identical
#                losses and table state across modes,
#                embed/prefetch_hits >= 1, embed/cache_hits >= 1,
#                verify/violations == 0 and concurrency/violations
#                == 0; then the bench three-leg receipt (sync /
#                overlap / overlap+cache) gating
#                bench/rec_bitwise_identical == 1 every attempt and
#                the overlapped-vs-sync throughput floor retried like
#                serve's ratios (shared-box timing)
#   zero       - ZeRO ladder + comm/compute overlap receipt
#                (docs/ZERO.md): one tiny MLP through ZeRO-1 per-leaf /
#                bucketed-no-overlap (the PR-5 path) / ZeRO-2 overlap /
#                ZeRO-3 / host-offloaded m/v on the 8-device CPU mesh,
#                gating numerics per rung, losses decreasing, offload
#                bytes moved, and the step-time overlap receipt
#                (overlapped <= non-overlapped)
# Usage: scripts/ci.sh [build|test|api_check|bench|bench-smoke|stress|obs|chaos|data-chaos|amp|serve|lint|race|verify|quant|rec|zero|fleet|online|all]
set -euo pipefail
cd "$(dirname "$0")/.."

stage="${1:-all}"

do_build() {
  make -C native -s
  make -C native -s native_test
}

# Collective-dense suites (1F1B pipeline scans, ring attention, 8-way
# SPMD) on the oversubscribed virtual CPU mesh can hit XLA:CPU's
# collective-rendezvous terminate timer under host load, which SIGABRTs
# the whole pytest process (rc=134) even though every test is correct —
# observed ~50% at file level on a loaded 1-core box (round-4 review,
# weak #1). Isolation contract (paddle_build.sh:637 reliable
# parallel_test parity): each such file runs in its OWN pytest process,
# and a rendezvous abort (134 = SIGABRT, 139 = SIGSEGV in teardown after
# an abort) retries up to twice; real test failures (rc=1) never retry.
HEAVY_FILES=(
  tests/test_pipeline_program.py
  tests/test_pipeline_1f1b.py
  tests/test_sequence_parallel.py
  tests/test_switch_moe.py
  tests/test_spmd_transformer.py
  tests/test_parallel_executor.py
)

run_isolated() {
  local f="$1" rc attempt
  for attempt in 1 2 3; do
    set +e
    XLA_FLAGS="--xla_force_host_platform_device_count=8" JAX_PLATFORMS=cpu \
      python -m pytest "$f" -q
    rc=$?
    set -e
    [ "$rc" -eq 0 ] && return 0
    if [ "$rc" -ne 134 ] && [ "$rc" -ne 139 ]; then
      return "$rc"
    fi
    echo "collective-rendezvous abort (rc=$rc) in $f — retry $attempt/2" >&2
  done
  return "$rc"
}

do_test() {
  make -C native -s test
  # Shard the python suite across workers (paddle_build.sh:637
  # parallel_test parity) — pytest-xdist over spare cores (capped at 4),
  # file granularity so per-file compile caches stay together. A 1-core
  # box runs serial: concurrent 8-device CPU meshes there only add
  # collective rendezvous pressure, not wall-clock.
  local n extra="" f
  local ignores=()
  n=$(python -c 'import os; print(max(1, min(4, (os.cpu_count() or 1) - 1)))')
  if ! python -c 'import xdist' 2>/dev/null; then
    n=1  # pytest-xdist not installed: run serial
  fi
  [ "$n" -gt 1 ] && extra="-n $n --dist loadfile"
  for f in "${HEAVY_FILES[@]}"; do
    ignores+=("--ignore=$f")
  done
  XLA_FLAGS="--xla_force_host_platform_device_count=8" JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q $extra "${ignores[@]}"
  for f in "${HEAVY_FILES[@]}"; do
    run_isolated "$f"
  done
  do_obs_smoke
}

do_obs_smoke() {
  # observability receipt (docs/OBSERVABILITY.md): a 3-step toy program
  # under PTPU_METRICS=1 must produce a metrics dump at exit that the
  # stats CLI renders — step_time count, compile-cache hit/miss, trace
  local dump=/tmp/ptpu_ci_metrics.json
  rm -f "$dump"
  JAX_PLATFORMS=cpu PTPU_METRICS=1 PTPU_METRICS_OUT="$dump" \
    python - <<'PYEOF'
import numpy as np
import paddle_tpu as fluid

x = fluid.layers.data(name="x", shape=[4])
loss = fluid.layers.mean(fluid.layers.fc(input=x, size=2))
fluid.optimizer.SGD(0.1).minimize(loss)
exe = fluid.Executor(fluid.CPUPlace())
exe.run(fluid.default_startup_program())
for _ in range(3):
    exe.run(feed={"x": np.ones((2, 4), np.float32)}, fetch_list=[loss])
PYEOF
  python tools/ptpu_stats.py --selftest
  python tools/ptpu_stats.py "$dump"
  python - "$dump" <<'PYEOF'
import json, sys
doc = json.load(open(sys.argv[1]))
assert doc["histograms"]["executor/step_time"]["count"] >= 3, doc
assert doc["counters"]["compile_cache/hit"] >= 1, doc
assert doc["counters"]["compile_cache/miss"] >= 1, doc
print("observability smoke ok")
PYEOF
  # live-endpoint receipt: a /metrics scrape must be byte-identical to
  # registry().to_prometheus(), /varz must round-trip through the stats
  # CLI with exact metric names, and /healthz must flip 200 -> 503 when
  # a provider degrades (docs/OBSERVABILITY.md "Live endpoint")
  JAX_PLATFORMS=cpu python - <<'PYEOF'
import json
import urllib.request

from paddle_tpu.observability import endpoint, metrics

metrics.enable()
reg = metrics.registry()
reg.counter("ci/obs_probe").inc(3)
reg.gauge("ci/obs_gauge").set(1.5)
reg.histogram("ci/obs_hist").observe(0.25)
endpoint.start(0)
try:
    scrape = urllib.request.urlopen(endpoint.url("/metrics")).read().decode()
    assert scrape == reg.to_prometheus(), "scrape != registry export"
    varz = json.loads(urllib.request.urlopen(endpoint.url("/varz")).read())
    assert varz["counters"]["ci/obs_probe"] == 3, varz
    hz = urllib.request.urlopen(endpoint.url("/healthz"))
    assert hz.status == 200, hz.status
    assert json.loads(hz.read())["status"] == "ok"
    endpoint.register_health_provider(
        "ci-degraded", lambda: (_ for _ in ()).throw(RuntimeError("down")))
    try:
        urllib.request.urlopen(endpoint.url("/healthz"))
    except urllib.error.HTTPError as e:
        assert e.code == 503, e.code
        assert json.loads(e.read())["status"] == "degraded"
    else:
        raise AssertionError("degraded /healthz did not return 503")
finally:
    endpoint.stop()
print("endpoint scrape parity ok")
PYEOF
}

do_stress() {
  # determinism receipt for the rendezvous-heavy path: the historically
  # flakiest file must come back green 5x back-to-back through the
  # isolation wrapper (round-4 review, weak #1 'done' criterion)
  local i
  for i in 1 2 3 4 5; do
    echo "== stress iteration $i/5 =="
    run_isolated tests/test_pipeline_program.py
  done
}

do_api_check() {
  python tools/diff_api.py
}

do_bench() {
  python bench.py
}

do_bench_smoke() {
  # async-pipeline receipt (docs/ASYNC_EXECUTION.md): a tiny-model bench
  # run with executor telemetry on must record >1 step in flight, H2D
  # bytes through the background prefetcher, and both steady-state step
  # times in the metrics dump the stats CLI gates on
  local dump=/tmp/ptpu_bench_smoke.json
  rm -f "$dump"
  JAX_PLATFORMS=cpu PTPU_METRICS=1 \
    python bench.py --tiny --metrics-out "$dump"
  # compiler/ops_removed + ops_fused: the compile-time pass pipeline
  # (docs/COMPILER_PASSES.md) fired on the bench program's receipt ops
  # bench/step_time_guarded|unguarded: the resilience-overhead leg ran
  # and recorded the guard's measured cost (docs/RESILIENCE.md)
  python tools/ptpu_stats.py "$dump" \
    --assert-has feed/h2d_bytes bench/step_time_async \
                 bench/step_time_sync executor/step_time \
                 compiler/ops_removed bench/compile_time_s_noopt \
                 bench/step_time_guarded bench/step_time_unguarded \
                 bench/guard_overhead_pct \
    --assert-min exec/inflight_steps=2 compiler/ops_removed=1 \
                 compiler/ops_fused=1
}

do_chaos() {
  # resilience receipt (docs/RESILIENCE.md): a short fit-a-line train
  # survives an injected NaN step AND a torn newest checkpoint. The
  # trainer must roll back and retry (resilience/rollbacks), restore must
  # detect the torn step and fall back to the intact one
  # (resilience/ckpt_corrupt_detected), and the final loss must match a
  # healthy run (--assert-max chaos/final_loss).
  local dump=/tmp/ptpu_chaos_metrics.json ckdir=/tmp/ptpu_chaos_ckpt
  rm -rf "$dump" "$ckdir"
  # nan_at_step:12 poisons one mid-training batch; ckpt_torn_write:2
  # tears the SECOND save — with checkpoint_every=60 over 120 steps the
  # saves land at the step-65 boundary (occurrence 1, intact) and the
  # final step-121 blocking save (occurrence 2, torn), so restore must
  # fall back across the newest step
  JAX_PLATFORMS=cpu PTPU_METRICS=1 PTPU_METRICS_OUT="$dump" \
    PTPU_ANOMALY_POLICY=rollback PTPU_RETRY_BACKOFF=0 \
    PTPU_FAULT_INJECT="nan_at_step:12,ckpt_torn_write:2" \
    python - "$ckdir" <<'PYEOF'
import sys
import warnings

import numpy as np
import paddle_tpu as fluid
from paddle_tpu import checkpoint
from paddle_tpu.observability import metrics as obs

ckdir = sys.argv[1]
x = fluid.layers.data(name="x", shape=[13], dtype="float32")
y = fluid.layers.data(name="y", shape=[1], dtype="float32")
pred = fluid.layers.fc(input=x, size=1)
loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
fluid.optimizer.SGD(0.05).minimize(loss)
exe = fluid.Executor(fluid.CPUPlace())
exe.run(fluid.default_startup_program())

rng = np.random.RandomState(0)
xs = rng.uniform(-1, 1, (256, 13)).astype(np.float32)
w = rng.uniform(-2, 2, (13, 1)).astype(np.float32)
ys = (xs @ w + 0.5).astype(np.float32)


def batches(epochs=30, batch=64):
    for _ in range(epochs):
        for i in range(0, len(xs), batch):
            yield {"x": xs[i:i + batch], "y": ys[i:i + batch]}


trainer = fluid.ResilientTrainer(
    exe, fluid.default_main_program(), fetch_list=[loss],
    guard_every=8, checkpoint_dir=ckdir, checkpoint_every=60)
with warnings.catch_warnings():
    warnings.simplefilter("ignore")
    result = trainer.run(batches())
print("chaos train:", result, "final loss", result.losses[-1])
assert result.rollbacks >= 1, result
assert not result.preempted, result

# newest checkpoint is torn: restore must detect it and fall back
scope2 = fluid.Scope()
exe2 = fluid.Executor(fluid.CPUPlace())
exe2.run(fluid.default_startup_program(), scope=scope2)
trainer2 = fluid.ResilientTrainer(
    exe2, fluid.default_main_program(), fetch_list=[loss],
    scope=scope2, checkpoint_dir=ckdir)
with warnings.catch_warnings():
    warnings.simplefilter("ignore")
    step = trainer2.restore()
print("restored from step", step, "of", checkpoint.all_checkpoints(ckdir))
assert step is not None and step < result.step, (step, result.step)

reg = obs.registry()
reg.gauge("chaos/final_loss").set(result.losses[-1])
reg.gauge("chaos/restored_step").set(step)
PYEOF
  python tools/ptpu_stats.py "$dump" \
    --assert-has resilience/anomalies resilience/snapshot_bytes \
                 chaos/restored_step \
    --assert-min resilience/rollbacks=1 resilience/retries=1 \
                 resilience/ckpt_corrupt_detected=1 \
                 resilience/ckpt_saves=2 resilience/faults_injected=2 \
    --assert-max chaos/final_loss=0.1
}

do_data_chaos() {
  # streaming data-plane receipt (docs/DATA_PLANE.md). One process,
  # three legs, all under PTPU_LOCK_CHECK=1 + 10us switch jitter:
  #   A) train_from_dataset straight THROUGH an injected corrupt shard
  #      (data_corrupt_shard:1 -> skip_record containment) — loss must
  #      stay finite and decrease vs the first epoch,
  #   B) a global-shuffle sample exchange where peer rank 1 dies at the
  #      exchange top (data_peer_die_at_exchange:1) — the survivor
  #      re-partitions and keeps every record it loaded,
  #   C) kill-then-resume: SIGTERM mid-epoch -> emergency checkpoint
  #      (the DatasetCursor rides the scope manifest) -> fresh trainer
  #      restores and resumes; the concatenated loss stream must be
  #      BITWISE the unfailed oracle's (data_chaos/resume_stream_match).
  local dump=/tmp/ptpu_data_chaos_metrics.json
  rm -f "$dump"
  JAX_PLATFORMS=cpu PTPU_METRICS=1 PTPU_METRICS_OUT="$dump" \
    PTPU_LOCK_CHECK=1 PTPU_RETRY_BACKOFF=0 \
    PTPU_DATA_PEER_TIMEOUT=0.4 PTPU_DATA_RETRY_BUDGET=1 \
    PTPU_FAULT_INJECT="data_corrupt_shard:1" \
    python - <<'PYEOF'
import sys
import tempfile
import threading
import warnings

sys.setswitchinterval(1e-5)
import numpy as np

import paddle_tpu as fluid
from paddle_tpu import data_plane, resilience
from paddle_tpu.analysis import concurrency
from paddle_tpu.distributed_runtime import exchange_samples
from paddle_tpu.observability import metrics as obs

tmp = tempfile.mkdtemp(prefix="ptpu_data_chaos_")
rng = np.random.RandomState(0)
w_true = rng.uniform(-2, 2, (13, 1)).astype(np.float32)
paths = []
for i in range(4):
    p = "%s/s%d.rec" % (tmp, i)

    def gen(i=i):
        r = np.random.RandomState(100 + i)
        for _ in range(64):
            x = r.uniform(-1, 1, (13,)).astype(np.float32)
            yield (x, (x @ w_true + 0.5).astype(np.float32))

    fluid.convert_reader_to_recordio_file(p, gen)
    paths.append(p)

x = fluid.layers.data(name="x", shape=[13], dtype="float32")
y = fluid.layers.data(name="y", shape=[1], dtype="float32")
pred = fluid.layers.fc(input=x, size=1)
loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
fluid.optimizer.SGD(0.05).minimize(loss)
main, startup = fluid.default_main_program(), \
    fluid.default_startup_program()


def make_ds():
    ds = fluid.DatasetFactory().create_dataset("QueueDataset")
    ds.set_filelist(paths)
    ds.set_batch_size(32)
    ds.set_use_var([x, y])
    ds.set_thread(2)
    return ds


# ---- leg A: train straight through the injected corrupt shard -------
exe = fluid.Executor(fluid.CPUPlace())
exe.run(startup)
first = last = None
with warnings.catch_warnings():
    warnings.simplefilter("ignore")
    for epoch in range(14):
        out = exe.train_from_dataset(main, make_ds(), fetch_list=[loss])
        if first is None:
            first = float(np.asarray(out[0]).ravel()[0])
        last = float(np.asarray(out[0]).ravel()[0])
exe.close()
assert np.isfinite(last), last
assert last < first, (first, last)
corrupt = obs.registry().counter("data/records_corrupt").value
assert corrupt >= 1, corrupt
print("leg A ok: first %.4f -> last %.4f, %d corrupt records contained"
      % (first, last, corrupt))

# ---- leg B: peer death mid-shuffle ---------------------------------
resilience.set_global_injector(
    resilience.FaultInjector("data_peer_die_at_exchange:1"))


def free_port():
    # hardcoded ports fail the stage spuriously under concurrent CI
    # runs or an unrelated listener; let the kernel pick
    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


eps = ["127.0.0.1:%d" % free_port(), "127.0.0.1:%d" % free_port()]
outgoing = {r: [[b"r%d.d%d.i%d" % (r, d, i) for i in range(4)]
                for d in range(2)] for r in range(2)}
res, errs = {}, {}


def worker(r):
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            # short exchange deadline: the dead peer never binds its
            # listener, and never-connected peers are only confirmed
            # dead at the full deadline (the startup-skew tolerance)
            res[r] = exchange_samples(eps, r, outgoing[r], timeout=6.0)
    except resilience.InjectedPeerDeathError as e:
        errs[r] = e


ts = [threading.Thread(target=worker, args=(r,), daemon=True)
      for r in range(2)]
for t in ts:
    t.start()
for t in ts:
    t.join(60)
assert 1 in errs, (res, errs)
assert sorted(res[0]) == sorted(b for d in range(2)
                                for b in outgoing[0][d]), res
print("leg B ok: survivor kept %d records after peer death"
      % len(res[0]))

# ---- leg C: kill-then-resume, record stream bitwise vs unfailed -----
def fresh():
    sc = fluid.Scope()
    e = fluid.Executor(fluid.CPUPlace())
    e.run(startup, scope=sc)
    return sc, e


resilience.set_global_injector(resilience.FaultInjector(""))
sc, e = fresh()
tr = fluid.ResilientTrainer(e, main, fetch_list=[loss], scope=sc,
                            guard_every=4)
cur = data_plane.DatasetCursor(seed=5)
with warnings.catch_warnings():
    warnings.simplefilter("ignore")
    oracle = list(tr.run(make_ds().resumable_batches(
        cur, epochs=2, scope=sc)).losses)

ckdir = tmp + "/ck"
resilience.set_global_injector(
    resilience.FaultInjector("sigterm_at_step:6"))
sc2, e2 = fresh()
tr2 = fluid.ResilientTrainer(e2, main, fetch_list=[loss], scope=sc2,
                             guard_every=4, checkpoint_dir=ckdir,
                             fault_injector=resilience.global_injector())
with warnings.catch_warnings():
    warnings.simplefilter("ignore")
    res2 = tr2.run(make_ds().resumable_batches(
        data_plane.DatasetCursor(seed=5), epochs=2, scope=sc2))
assert res2.preempted, res2
pre = list(res2.losses)

resilience.set_global_injector(resilience.FaultInjector(""))
sc3, e3 = fresh()
tr3 = fluid.ResilientTrainer(e3, main, fetch_list=[loss], scope=sc3,
                             guard_every=4, checkpoint_dir=ckdir)
step = tr3.restore()
cur3 = data_plane.DatasetCursor.from_scope(sc3)
assert step is not None and cur3 is not None, (step, cur3)
with warnings.catch_warnings():
    warnings.simplefilter("ignore")
    res3 = tr3.run(make_ds().resumable_batches(cur3, epochs=2,
                                               scope=sc3))
total = pre + list(res3.losses)
match = (len(total) == len(oracle)
         and bool(np.array_equal(np.asarray(total), np.asarray(oracle))))
assert match, (len(pre), len(res3.losses), len(oracle))
print("leg C ok: %d pre + %d resumed steps bitwise == %d-step oracle"
      % (len(pre), len(res3.losses), len(oracle)))

concurrency.assert_clean()
concurrency.publish_metrics()
reg = obs.registry()
reg.gauge("data_chaos/final_loss").set(last)
reg.gauge("data_chaos/loss_decreasing").set(1.0 if last < first else 0.0)
reg.gauge("data_chaos/resume_stream_match").set(1.0 if match else 0.0)
print("data-chaos ok:", concurrency.stats())
PYEOF
  python tools/ptpu_stats.py "$dump" \
    --assert-has data_chaos/final_loss \
    --assert-min data/records_corrupt=1 data/records_skipped=1 \
                 data/peer_failovers=1 data/peer_retries=1 \
                 data_chaos/loss_decreasing=1 \
                 data_chaos/resume_stream_match=1 \
                 resilience/preemptions=1 \
                 concurrency/locks_tracked=1 \
    --assert-max concurrency/violations=0 data_chaos/final_loss=0.2
}

do_amp() {
  # mixed-precision receipt (docs/MIXED_PRECISION.md): the tiny
  # transformer trained plain-fp32 and through paddle_tpu.amp.decorate
  # in one bench run. Gates: the amp_rewrite pass actually fired
  # (amp/casts_inserted, amp/ops_rewritten), both legs' losses are
  # finite and sane (--assert-max; the tiny config starts near
  # ln(vocab)≈6.2 so 20 catches NaN/divergence without pinning
  # numerics), and the AMP leg is non-regressing vs fp32 — the floor is
  # 0.5 because CPU CI emulates bf16 (no MXU win, measured ~0.9x);
  # on an attached TPU the same gauge records the real speedup.
  local dump=/tmp/ptpu_amp_metrics.json legs=/tmp/ptpu_amp_legs.json
  rm -f "$dump" "$legs"
  JAX_PLATFORMS=cpu PTPU_METRICS=1 \
    python bench.py --tiny --amp-only --metrics-out "$dump" \
    --legs-out "$legs"
  python tools/ptpu_stats.py "$dump" \
    --assert-has bench/tokens_per_sec_fp32 bench/tokens_per_sec_amp \
                 bench/amp_speedup_vs_fp32 amp/ops_rewritten \
    --assert-min amp/casts_inserted=1 bench/amp_speedup_vs_fp32=0.5 \
    --assert-max bench/amp_last_loss=20 bench/fp32_last_loss=20
  python - "$legs" <<'PYEOF'
import json, sys
legs = {e["leg"]: e for e in json.load(open(sys.argv[1]))}
assert "fp32" in legs and "amp" in legs, legs
print("amp stage ok:", {k: v["tokens_per_sec"] for k, v in legs.items()})
PYEOF
}

do_serve() {
  # serving receipt (docs/SERVING.md): one deterministic Poisson stream
  # served through a 16-slot continuously-batched engine and replayed
  # serially through a 1-slot engine. Gates: the batch actually filled
  # (peak occupancy > 1), every request completed with finite latency
  # (p99 bound), batching never changed any request's tokens
  # (serving_outputs_match — greedy decode is deterministic), and
  # continuous batching bought >= 2x aggregate tokens/s over serial
  # decoding (measured ~3-4x on the 2-core CI box, ISSUE 6 acceptance).
  # The throughput/TTFT ratios are measurements on a shared box, so a
  # run that misses those bars retries up to twice; the functional
  # gates (occupancy/identity/latency/prefix-reuse) must hold on every
  # attempt. The fast-path leg (ISSUE 11) serves a shared-system-prompt
  # stream through chunked prefill + radix prefix caching:
  # token-identical to reference_decode and >= 1 prefix block actually
  # reused. The speculative leg
  # (ISSUE 13) serves the repetitive-generation set with spec_k on and
  # off: both legs token-identical, accept_rate > 0 and emitted
  # tokens-per-compiled-step > 1 on every attempt (legacy is exactly
  # 1/step per sequence), and the tokens-per-step speedup ratio
  # retried like the TTFT gate. Wall-clock tokens/s for the spec pair
  # is recorded but not gated: the CPU box pays the verify window's
  # full FLOPs, while on TPU the decode step is memory-bandwidth-bound
  # and the step-count ratio is the real win (docs/SERVING.md). The
  # compounded legs (ISSUE 18): the tree + jitted-drafter leg must be
  # token-identical with draft_steps > 0 and tokens-per-target-step >=
  # the linear-k leg on EVERY attempt (ratio > 1.1 retried like TTFT);
  # the int8-compounded leg token-identical to its dequantized
  # reference; the engine's serving/spec_accept_rate gauge finite
  # (NaN fails both bounds).
  local dump=/tmp/ptpu_serve_metrics.json legs=/tmp/ptpu_serve_legs.json
  local attempt rc=1
  for attempt in 1 2 3; do
    rm -f "$dump" "$legs"
    JAX_PLATFORMS=cpu PTPU_METRICS=1 \
      python bench.py --tiny --serving-only --metrics-out "$dump" \
      --legs-out "$legs"
    python tools/ptpu_stats.py "$dump" \
      --assert-has serving/request_latency serving/tokens_per_sec \
                   serving/queue_depth serving/batch_occupancy \
                   serving/ttft_p50 serving/ttft_p99 \
                   bench/serving_tokens_per_sec_batched \
                   bench/serving_tokens_per_sec_serial \
                   bench/serving_ttft_chunked_s \
                   bench/serving_spec_tokens_per_step \
                   bench/serving_spec_speedup \
                   bench/serving_spec_tree_tokens_per_step \
                   bench/serving_spec_tree_speedup \
                   serving/spec_accept_rate \
      --assert-min serving/peak_batch_occupancy=2 \
                   serving/requests_completed=1 \
                   serving/prefix_blocks_reused=1 \
                   serving/prefill_chunk_steps=1 \
                   serving/spec_steps=1 \
                   serving/spec_accept_rate=0 \
                   bench/serving_outputs_match=1 \
                   bench/serving_fastpath_outputs_match=1 \
                   bench/serving_prefix_hit_rate=0.1 \
                   bench/serving_spec_outputs_match=1 \
                   bench/serving_spec_int8_outputs_match=1 \
                   bench/serving_spec_accept_rate=0.01 \
                   bench/serving_spec_tokens_per_step=1.05 \
                   bench/serving_spec_tree_speedup=1 \
      --assert-max serving/request_latency_p99=120 \
                   bench/serving_p99_latency_s=120 \
                   serving/spec_accept_rate=1
    set +e
    python tools/ptpu_stats.py "$dump" \
      --assert-min bench/serving_speedup_vs_serial=2 \
                   bench/serving_spec_speedup=1.1 \
                   bench/serving_spec_tree_speedup=1.1
    rc=$?
    set -e
    [ "$rc" -eq 0 ] && break
    echo "serving speedup/TTFT ratio below bar (loaded box?) —" \
         "retry $attempt/2" >&2
  done
  [ "$rc" -eq 0 ]
  python - "$legs" <<'PYEOF'
import json, sys
legs = {e["leg"]: e for e in json.load(open(sys.argv[1]))}
assert "serving_batched" in legs and "serving_serial" in legs, legs
assert legs["serving_batched"]["outputs_match"], legs
assert "serving_fastpath" in legs, legs
assert legs["serving_fastpath"]["outputs_match"], legs
assert legs["serving_fastpath"]["prefix_hit_rate"] > 0, legs
assert "serving_spec" in legs and "serving_spec_baseline" in legs, legs
assert legs["serving_spec"]["outputs_match"], legs
assert legs["serving_spec"]["accept_rate"] > 0, legs
assert legs["serving_spec"]["tokens_per_step"] > 1, legs
assert "serving_spec_tree" in legs and "serving_spec_int8" in legs, legs
assert legs["serving_spec_tree"]["outputs_match"], legs
assert legs["serving_spec_int8"]["outputs_match"], legs
assert legs["serving_spec_tree"]["draft_steps"] > 0, legs
assert (legs["serving_spec_tree"]["tokens_per_step"]
        >= legs["serving_spec"]["tokens_per_step"]), legs
print("serve stage ok:",
      {k: v["tokens_per_sec"] for k, v in legs.items()},
      "ttft chunked:", legs["serving_fastpath"]["ttft_p50_s"],
      "spec tokens/step:",
      (legs["serving_spec"]["tokens_per_step"],
       legs["serving_spec_baseline"]["tokens_per_step"]))
PYEOF
}

do_lint() {
  # source-invariant gate (docs/STATIC_ANALYSIS.md): PTPU_* env reads
  # through the flags registry, no bare excepts, no build-time jnp in
  # op builders, metric names documented. Zero findings or fail.
  python tools/ptpu_lint.py paddle_tpu/
  python -c "import paddle_tpu; print(paddle_tpu.flags.describe())" \
    > /dev/null
}

do_race() {
  # concurrency-analysis receipt (docs/STATIC_ANALYSIS.md). Leg 1: the
  # serving fast path — chunked prefill + radix prefix caching with 4
  # concurrent submitter threads, then the same traffic through a
  # SPECULATIVE engine (spec_k + chunk + prefix cache, ISSUE 13: the
  # verify-window/rollback path exercises truncate_owner and the new
  # pool rollback invariants at every step boundary) — under
  # PTPU_LOCK_CHECK=1 and a 10us
  # thread switch interval so the GIL hands off mid-critical-section.
  # Every tracked acquisition feeds the lock-order graph; the gates
  # prove the tracker saw the real runtime (locks_tracked >= 6,
  # order_edges >= 1) and that no potential deadlock / blocking-while-
  # holding / invariant violation surfaced (violations == 0). Outputs
  # stay pinned token-identical to reference_decode — the tracked
  # wrappers may not change behavior.
  local dump=/tmp/ptpu_race_metrics.json
  rm -f "$dump"
  JAX_PLATFORMS=cpu PTPU_METRICS=1 PTPU_METRICS_OUT="$dump" \
    PTPU_LOCK_CHECK=1 \
    python - <<'PYEOF'
import sys
import threading

sys.setswitchinterval(1e-5)
import numpy as np

from paddle_tpu import serving
from paddle_tpu.analysis import concurrency
from paddle_tpu.serving import (GenerationConfig, GenerationModel,
                                reference_decode)

model = GenerationModel.random(
    GenerationConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                     d_ff=64, max_seq_len=64), seed=0, name="race")
rng = np.random.RandomState(7)
shared = rng.randint(0, 64, size=8).tolist()  # shared prefix -> radix path
prompts = [shared + rng.randint(0, 64, size=rng.randint(2, 8)).tolist()
           for _ in range(12)]
results = {}
with serving.ServingEngine(model, max_batch=4, max_seq_len=64,
                           block_size=4, prefill_chunk=4,
                           prefix_cache=True) as eng:
    def client(lo, hi):
        for i in range(lo, hi):
            results[i] = eng.generate(prompts[i], max_new_tokens=8,
                                      timeout=300)
    threads = [threading.Thread(target=client, args=(i * 3, i * 3 + 3),
                                name="race-client-%d" % i)
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    pools = [w.pool for w in eng._workers.values()]
for i, p in enumerate(prompts):
    assert results[i] == reference_decode(model, p, 8), (i, results[i])
for pool in pools:
    assert pool.check_invariants() == [], pool.check_invariants()
# the same traffic through the SPECULATIVE engine (ISSUE 13): verify
# windows, KV rollback and the truncate invariants under the tracker
results = {}
with serving.ServingEngine(model, max_batch=4, max_seq_len=64,
                           block_size=4, prefill_chunk=4,
                           prefix_cache=True, spec_k=4) as eng:
    def client(lo, hi):
        for i in range(lo, hi):
            results[i] = eng.generate(prompts[i], max_new_tokens=8,
                                      timeout=300)
    threads = [threading.Thread(target=client, args=(i * 3, i * 3 + 3),
                                name="race-spec-client-%d" % i)
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    spec_steps = eng.stats()["default"]["spec_steps"]
    pools = [w.pool for w in eng._workers.values()]
for i, p in enumerate(prompts):
    assert results[i] == reference_decode(model, p, 8), (i, results[i])
for pool in pools:
    assert pool.check_invariants() == [], pool.check_invariants()
assert spec_steps > 0, "spec engine never dispatched a verify window"
# the compounded leg (ISSUE 18): TREE verify windows on int8 weight
# stores for drafter AND target — the tree acceptance/commit/rollback
# path and the drafter's own KV pool under the same tracker/jitter
results = {}
qmodel = model.quantized()
with serving.ServingEngine(qmodel, max_batch=4, max_seq_len=64,
                           block_size=4, prefill_chunk=4,
                           prefix_cache=True, spec_tree="2x2",
                           drafter=serving.ModelDrafter(qmodel)) as eng:
    def client(lo, hi):
        for i in range(lo, hi):
            results[i] = eng.generate(prompts[i], max_new_tokens=8,
                                      timeout=300)
    threads = [threading.Thread(target=client, args=(i * 3, i * 3 + 3),
                                name="race-tree-client-%d" % i)
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tree_stats = eng.stats()["default"]
    pools = [w.pool for w in eng._workers.values()]
    dpool = eng._workers["default"].drafter._pool
for i, p in enumerate(prompts):
    assert results[i] == reference_decode(qmodel, p, 8), (i, results[i])
for pool in pools:
    assert pool.check_invariants() == [], pool.check_invariants()
assert dpool.check_invariants() == [], dpool.check_invariants()
assert tree_stats["spec_tree_slots"] > 0, tree_stats
assert tree_stats["weight_only_int8"], tree_stats
concurrency.assert_clean()
concurrency.publish_metrics()
print("race serve leg ok:", concurrency.stats())
PYEOF
  python tools/ptpu_stats.py "$dump" \
    --assert-min concurrency/locks_tracked=6 concurrency/order_edges=1 \
                 concurrency/acquisitions=1 \
                 serving/prefill_chunk_steps=1 \
                 serving/prefix_blocks_reused=1 \
                 serving/spec_steps=1 \
                 serving/spec_tree_slots=1 \
    --assert-max concurrency/violations=0
  # Leg 2: the async-executor chaos leg — ResilientTrainer with an
  # injected NaN step, rollback + async checkpointing (the background
  # writer thread + the PR-2 in-flight window + prefetcher), same
  # switch-interval jitter. The tracked checkpoint-manager lock and the
  # runtime's queue blocking regions must come through violation-free.
  rm -f "$dump"
  JAX_PLATFORMS=cpu PTPU_METRICS=1 PTPU_METRICS_OUT="$dump" \
    PTPU_LOCK_CHECK=1 PTPU_ANOMALY_POLICY=rollback PTPU_RETRY_BACKOFF=0 \
    PTPU_FAULT_INJECT="nan_at_step:12" \
    python - <<'PYEOF'
import sys
import tempfile
import warnings

sys.setswitchinterval(1e-5)
import numpy as np

import paddle_tpu as fluid
from paddle_tpu.analysis import concurrency

x = fluid.layers.data(name="x", shape=[13], dtype="float32")
y = fluid.layers.data(name="y", shape=[1], dtype="float32")
pred = fluid.layers.fc(input=x, size=1)
loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
fluid.optimizer.SGD(0.05).minimize(loss)
exe = fluid.Executor(fluid.CPUPlace())
exe.run(fluid.default_startup_program())

rng = np.random.RandomState(0)
xs = rng.uniform(-1, 1, (256, 13)).astype(np.float32)
w = rng.uniform(-2, 2, (13, 1)).astype(np.float32)
ys = (xs @ w + 0.5).astype(np.float32)


def batches(epochs=10, batch=64):
    for _ in range(epochs):
        for i in range(0, len(xs), batch):
            yield {"x": xs[i:i + batch], "y": ys[i:i + batch]}


with tempfile.TemporaryDirectory() as ckdir:
    trainer = fluid.ResilientTrainer(
        exe, fluid.default_main_program(), fetch_list=[loss],
        guard_every=8, checkpoint_dir=ckdir, checkpoint_every=20)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        result = trainer.run(batches())
assert result.rollbacks >= 1, result
assert np.isfinite(result.losses[-1]), result
concurrency.assert_clean()
concurrency.publish_metrics()
print("race chaos leg ok:", concurrency.stats(),
      "rollbacks", result.rollbacks)
PYEOF
  python tools/ptpu_stats.py "$dump" \
    --assert-min concurrency/locks_tracked=1 concurrency/acquisitions=1 \
                 resilience/rollbacks=1 \
    --assert-max concurrency/violations=0
}

do_verify() {
  # Program IR verifier receipt (docs/STATIC_ANALYSIS.md): training and
  # inference compile paths run clean under PTPU_VERIFY_PASSES=1 — the
  # verifier checked >= 1 program and found 0 violations — on the
  # default pipeline, under PTPU_NO_PROGRAM_OPT=1 (the no-opt compile
  # hook), and on the tiny transformer bench with AMP on.
  local dump=/tmp/ptpu_verify_metrics.json
  local noopt
  for noopt in "" "1"; do
    rm -f "$dump"
    JAX_PLATFORMS=cpu PTPU_METRICS=1 PTPU_METRICS_OUT="$dump" \
      PTPU_VERIFY_PASSES=1 PTPU_NO_PROGRAM_OPT="$noopt" \
      python - <<'PYEOF'
import numpy as np
import paddle_tpu as fluid

x = fluid.layers.data(name="x", shape=[13], dtype="float32")
y = fluid.layers.data(name="y", shape=[1], dtype="float32")
pred = fluid.layers.fc(input=x, size=1)
loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
fluid.optimizer.SGD(0.05).minimize(loss)
exe = fluid.Executor(fluid.CPUPlace())
exe.run(fluid.default_startup_program())
rng = np.random.RandomState(0)
for _ in range(10):
    out, = exe.run(feed={"x": rng.uniform(-1, 1, (16, 13)).astype("float32"),
                         "y": rng.uniform(-1, 1, (16, 1)).astype("float32")},
                   fetch_list=[loss])
assert np.isfinite(np.asarray(out)).all(), out
print("verify fit-a-line ok, loss", np.asarray(out))
PYEOF
    python tools/ptpu_stats.py "$dump" \
      --assert-min verify/programs_checked=1 \
      --assert-max verify/violations=0
  done
  # transformer bench config, AMP on, verifier live for every compile
  rm -f "$dump"
  JAX_PLATFORMS=cpu PTPU_METRICS=1 PTPU_VERIFY_PASSES=1 \
    python bench.py --tiny --amp-only --metrics-out "$dump"
  python tools/ptpu_stats.py "$dump" \
    --assert-min verify/programs_checked=1 amp/casts_inserted=1 \
    --assert-max verify/violations=0
}

do_quant() {
  # int8 quantized-inference receipt (docs/QUANTIZATION.md).
  # (a) the full workflow — calibrate on sample feeds, full_int8
  # quant_rewrite through the compile pipeline, predict — under the IR
  # verifier: the pass must actually fire (quant/ops_rewritten >= 1,
  # quant/calib_tensors >= 1), every program must verify clean
  # (verify/violations == 0), and the int8 logits must sit inside the
  # documented numerics bound vs the same predictor's fp32 run
  # (quant/predict_max_abs_err via ptpu_stats --assert-max).
  local dump=/tmp/ptpu_quant_metrics.json legs=/tmp/ptpu_quant_legs.json
  rm -f "$dump"
  JAX_PLATFORMS=cpu PTPU_METRICS=1 PTPU_METRICS_OUT="$dump" \
    PTPU_VERIFY_PASSES=1 \
    python - <<'PYEOF'
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import quant
from paddle_tpu.observability import metrics as obs

prog, sprog = fluid.Program(), fluid.Program()
with fluid.program_guard(prog, sprog):
    x = fluid.layers.data(name="cx", shape=[32], dtype="float32")
    h = fluid.layers.fc(input=x, size=64, act="relu")
    out = fluid.layers.fc(input=h, size=10)
exe = fluid.Executor(fluid.CPUPlace())
exe.run(sprog)
rng = np.random.RandomState(0)
feeds = [{"cx": rng.uniform(-1, 1, (16, 32)).astype(np.float32)}
         for _ in range(6)]
ref, = exe.run(prog, feed=feeds[0], fetch_list=[out])
table = quant.calibrate(prog, feeds)
infer = prog.clone(for_test=True)
quant.decorate(infer, mode="full_int8", table=table)
got, = exe.run(infer, feed=feeds[0], fetch_list=[out])
err = float(np.abs(np.asarray(ref) - np.asarray(got)).max())
exe.close()
obs.registry().gauge("quant/predict_max_abs_err").set(err)
print("quant ci: calibrate->rewrite->predict ok, max-abs-err", err)
PYEOF
  python tools/ptpu_stats.py "$dump" \
    --assert-min quant/ops_rewritten=1 quant/calib_tensors=1 \
                 quant/weights_quantized=1 verify/programs_checked=1 \
    --assert-max verify/violations=0 quant/predict_max_abs_err=0.1
  # (b) the bench quant legs. Functional gates hold on EVERY attempt:
  # predictor numerics (max-abs-err bound + top-1 agreement vs fp32),
  # the >= 40% weight-store shrink (ISSUE 10 acceptance), and the
  # serving int8 leg token-identical to its fp32 reference. The
  # batched-serving int8-vs-fp32 throughput floor is a timing
  # measurement on a shared box, so it retries up to twice (the serve
  # stage's ratio pattern); the floor is 0.5 because CPU XLA pays the
  # dequantize without an int8 MXU to win it back — on TPU the same
  # gauge records the real memory-bandwidth win.
  local attempt rc=1
  for attempt in 1 2 3; do
    rm -f "$dump" "$legs"
    JAX_PLATFORMS=cpu PTPU_METRICS=1 \
      python bench.py --tiny --quant-only --metrics-out "$dump" \
      --legs-out "$legs"
    python tools/ptpu_stats.py "$dump" \
      --assert-has bench/quant_examples_per_sec_fp32 \
                   bench/quant_examples_per_sec_int8 \
                   bench/serving_tokens_per_sec_int8 \
                   bench/serving_tokens_per_sec_fp32_ref \
                   quant/weight_bytes_saved \
      --assert-min bench/quant_top1_agreement=0.9 \
                   bench/quant_weight_bytes_saved_ratio=0.4 \
                   bench/serving_int8_outputs_match=1 \
                   bench/serving_int8_token_agreement=0.5 \
      --assert-max bench/quant_max_abs_err=0.1
    set +e
    python tools/ptpu_stats.py "$dump" \
      --assert-min bench/serving_int8_speedup_vs_fp32=0.5
    rc=$?
    set -e
    [ "$rc" -eq 0 ] && break
    echo "int8 serving throughput below floor (loaded box?) — retry $attempt/2" >&2
  done
  [ "$rc" -eq 0 ]
  python - "$legs" <<'PYEOF'
import json, sys
legs = {e["leg"]: e for e in json.load(open(sys.argv[1]))}
for need in ("quant_fp32_predictor", "quant_int8_predictor",
             "serving_int8", "serving_fp32_ref"):
    assert need in legs, (need, sorted(legs))
assert legs["serving_int8"]["outputs_match"], legs
print("quant stage ok:",
      {k: legs[k]["tokens_per_sec"] for k in sorted(legs)})
PYEOF
}

do_rec() {
  # Recommender fast-path receipt (docs/RECOMMENDER.md). (a) the
  # cached/prefetched CTR run must be BITWISE the legacy synchronous
  # run — same per-step losses, same final table shards + optimizer
  # accumulators — while the IR verifier checks every rewritten
  # program and the lock tracker (plus switch-interval jitter) watches
  # the gather worker, the push queue and the coherence barrier race
  # against the training loop. Gates: identity asserts in-leg,
  # embed/prefetch_hits >= 1, embed/cache_hits >= 1,
  # verify/violations == 0, concurrency/violations == 0.
  local dump=/tmp/ptpu_rec_metrics.json legs=/tmp/ptpu_rec_legs.json
  rm -f "$dump"
  JAX_PLATFORMS=cpu PTPU_METRICS=1 PTPU_METRICS_OUT="$dump" \
    PTPU_VERIFY_PASSES=1 PTPU_LOCK_CHECK=1 \
    python - <<'PYEOF'
import os
import sys

sys.setswitchinterval(1e-5)  # flush thread interleavings
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import framework, initializer, unique_name
from paddle_tpu.core import scope as scope_mod
from paddle_tpu.models import deepfm
from paddle_tpu.parallel import host_embedding
from paddle_tpu.parallel.host_embedding import HostEmbeddingTable
from paddle_tpu.recordio_writer import convert_reader_to_recordio_file

paths = []
for s in range(2):
    p = "/tmp/ptpu_rec_ci_%d.rec" % s
    rng = np.random.RandomState(100 + s)

    def gen(rng=rng):
        for _ in range(96):
            hot = rng.rand(4) < 0.5
            ids = np.where(hot, rng.randint(0, 16, 4),
                           rng.randint(0, 256, 4))
            yield (ids.astype(np.int64),
                   np.array([rng.randint(0, 2)], np.float32))

    convert_reader_to_recordio_file(p, gen)
    paths.append(p)


class V:
    def __init__(self, name):
        self.name = name


def run_leg(env):
    for k in ("PTPU_EMBED_PREFETCH", "PTPU_EMBED_CACHE_ROWS"):
        os.environ.pop(k, None)
    os.environ.update(env)
    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    scope_mod._scope_stack[:] = [scope_mod.Scope()]
    HostEmbeddingTable.reset_registry()
    initializer._global_seed_counter[0] = 0
    np.random.seed(42)
    ds = fluid.DatasetFactory().create_dataset("QueueDataset")
    ds.set_batch_size(16)
    ds.set_filelist(paths)
    main_p, startup = framework.Program(), framework.Program()
    with framework.program_guard(main_p, startup):
        _feeds, _pred, avg_cost = deepfm.build_distributed(
            vocab_size=256, num_fields=4, embed_dim=8, mlp_dims=(16,),
            num_shards=2, learning_rate=0.05)
        fluid.optimizer.SGD(learning_rate=0.05).minimize(avg_cost)
    ds.set_use_var([V("ids"), V("label")])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    losses = []
    for _epoch in range(2):
        out = exe.train_from_dataset(program=main_p, dataset=ds,
                                     fetch_list=[avg_cost])
        losses.append(np.asarray(out[0]).copy())
    return losses, host_embedding.tables_state_dict()


sync_l, sync_s = run_leg({})
fast_l, fast_s = run_leg({"PTPU_EMBED_PREFETCH": "1",
                          "PTPU_EMBED_CACHE_ROWS": "64"})
for a, b in zip(sync_l, fast_l):
    assert a.tobytes() == b.tobytes(), ("loss diverged", a, b)
for tab in sync_s:
    for key in sync_s[tab]:
        assert (np.asarray(sync_s[tab][key]).tobytes()
                == np.asarray(fast_s[tab][key]).tobytes()), \
            ("table state diverged", tab, key)
print("rec ci: cached+prefetched run bitwise-identical to sync, "
      "final loss", float(sync_l[-1].ravel()[0]))
PYEOF
  python tools/ptpu_stats.py "$dump" \
    --assert-min embed/prefetch_hits=1 embed/cache_hits=1 \
                 embed/pull_rows=1 embed/push_rows=1 \
                 verify/programs_checked=1 concurrency/locks_tracked=1 \
    --assert-max verify/violations=0 concurrency/violations=0
  # (b) the bench three-leg receipt. Bitwise identity and a nonzero
  # cache hit rate are functional gates that hold on EVERY attempt;
  # the overlapped-vs-sync examples/s floor is a timing measurement on
  # a shared box, so it retries up to twice (the serve stage's ratio
  # pattern). The floor is 0.8: on CPU the host gather is nearly free
  # so overlap can only tie — the gauge records the real win on TPU,
  # the gate only proves the fast path never collapses throughput.
  local attempt rc=1
  for attempt in 1 2 3; do
    rm -f "$dump" "$legs"
    JAX_PLATFORMS=cpu PTPU_METRICS=1 \
      python bench.py --tiny --rec-only --metrics-out "$dump" \
      --legs-out "$legs"
    python tools/ptpu_stats.py "$dump" \
      --assert-has bench/rec_examples_per_sec_sync \
                   bench/rec_examples_per_sec_overlap \
                   bench/rec_examples_per_sec_cache \
                   bench/rec_cache_hit_rate \
      --assert-min bench/rec_bitwise_identical=1 \
                   embed/cache_hits=1 embed/prefetch_hits=1
    set +e
    python tools/ptpu_stats.py "$dump" \
      --assert-min bench/rec_overlap_speedup=0.8
    rc=$?
    set -e
    [ "$rc" -eq 0 ] && break
    echo "rec overlap throughput below floor (loaded box?) — retry $attempt/2" >&2
  done
  [ "$rc" -eq 0 ]
  python - "$legs" <<'PYEOF'
import json, sys
legs = {e["leg"]: e for e in json.load(open(sys.argv[1]))}
for need in ("rec_sync", "rec_overlap", "rec_overlap_cache"):
    assert need in legs, (need, sorted(legs))
assert legs["rec_overlap_cache"]["bitwise_identical"], legs
print("rec stage ok:",
      {k: legs[k]["examples_per_sec"] for k in sorted(legs)})
PYEOF
}

do_kernels() {
  # Pallas kernel dispatch receipt (docs/KERNELS.md). (a) under
  # PTPU_KERNELS=1 the registry actually dispatches on the CPU
  # interpreter legs (kernels/dispatches >= 1), and a full-int8
  # program routed through the fused int8 matmul — one
  # fused_int8_matmul op, no standalone quantize/dequantize ops —
  # verifies clean under PTPU_VERIFY_PASSES=1 (verify/violations == 0)
  # while matching the unfused chain bitwise. (b) the per-kernel bench
  # receipts publish the three speedup gauges. CPU floor gates only:
  # the kernels run in interpret mode off-TPU, so the gauges are
  # parity-checked and positive, not > 1 — the real margins are TPU
  # receipts (the amp/int8 CPU-floor precedent).
  local dump=/tmp/ptpu_kernels_metrics.json
  local legs=/tmp/ptpu_kernels_legs.json
  rm -f "$dump"
  JAX_PLATFORMS=cpu PTPU_METRICS=1 PTPU_METRICS_OUT="$dump" \
    PTPU_VERIFY_PASSES=1 PTPU_KERNELS=1 \
    python - <<'PYEOF'
import os

import numpy as np
import paddle_tpu as fluid
from paddle_tpu import quant

prog, sprog = fluid.Program(), fluid.Program()
with fluid.program_guard(prog, sprog):
    x = fluid.layers.data(name="kx", shape=[48], dtype="float32")
    h = fluid.layers.fc(input=x, size=56, act="relu")
    out = fluid.layers.fc(input=h, size=24)
exe = fluid.Executor(fluid.CPUPlace())
exe.run(sprog)
rng = np.random.RandomState(0)
feeds = [{"kx": rng.uniform(-1, 1, (8, 48)).astype(np.float32)}
         for _ in range(6)]
table = quant.calibrate(prog, feeds)

infer = prog.clone(for_test=True)
quant.decorate(infer, mode="full_int8", table=table)
# compile-pipeline rewrite emits ONE fused_int8_matmul per fc (the
# kernels/kernel:int8_matmul counter asserted below is the dispatch
# receipt; the no-standalone-quantize-HLO module-text pin is tier-1)
fused, = exe.run(infer, feed=feeds[0], fetch_list=[out])

# same decorated program with kernels pinned off: the unfused
# quantize -> int8 dot -> dequantize chain, its own compile-cache key
os.environ["PTPU_KERNELS"] = "0"
unfused, = exe.run(infer, feed=feeds[0], fetch_list=[out])
os.environ["PTPU_KERNELS"] = "1"
exe.close()

assert np.array_equal(np.asarray(fused), np.asarray(unfused)), (
    float(np.abs(np.asarray(fused) - np.asarray(unfused)).max()))
print("kernels ci: fused int8 matmul bitwise == unfused chain")
PYEOF
  python tools/ptpu_stats.py "$dump" \
    --assert-min kernels/dispatches=1 "kernels/kernel:int8_matmul=1" \
                 quant/ops_rewritten=1 verify/programs_checked=1 \
    --assert-max verify/violations=0
  # per-kernel parity receipts: kernel vs fallback inside the documented
  # bound per leg. On the CPU the kernels run in the interpreter, so the
  # run reports no time (a kernel's time comes from the chip only).
  rm -f "$dump" "$legs"
  JAX_PLATFORMS=cpu PTPU_METRICS=1 \
    python bench.py --tiny --kernels-only --metrics-out "$dump" \
    --legs-out "$legs"
  python tools/ptpu_stats.py "$dump" \
    --assert-has bench/kernel_paged_decode_max_err \
                 bench/kernel_int8_matmul_max_err \
                 bench/kernel_spec_window_max_err
  python - "$legs" <<'PYEOF'
import json, sys
legs = {e["leg"]: e for e in json.load(open(sys.argv[1]))}
for need in ("kernel_paged_decode", "kernel_spec_window",
             "kernel_int8_matmul"):
    assert need in legs, (need, sorted(legs))
    assert legs[need]["max_err"] < 1e-4, legs[need]
    assert "pallas_s" not in legs[need], legs[need]
assert legs["kernel_int8_matmul"]["max_err"] == 0.0, legs
print("kernels stage ok:", {k: v["max_err"] for k, v in legs.items()})
PYEOF
}

do_fleet() {
  # fault-tolerant serving-fleet receipt (docs/SERVING.md "Fleet &
  # failover"). Leg A — replica death: a 2-replica router serving a
  # shared-prefix stream loses one replica mid-stream
  # (serve_die_at_step); every output, including requests re-admitted
  # with their already-emitted prefix, must be token-identical to the
  # unfailed reference (greedy decode is history-deterministic), the
  # dead replica's KV pool must come out invariant-clean and fully
  # drained, and the whole path runs under PTPU_LOCK_CHECK=1 with
  # switch-interval jitter gating concurrency/violations == 0.
  local dump=/tmp/ptpu_fleet_metrics.json legs=/tmp/ptpu_fleet_legs.json
  local blackbox=/tmp/ptpu_fleet_blackbox
  rm -f "$dump"
  rm -rf "$blackbox" && mkdir -p "$blackbox"
  JAX_PLATFORMS=cpu PTPU_METRICS=1 PTPU_METRICS_OUT="$dump" \
    PTPU_LOCK_CHECK=1 PTPU_RETRY_BACKOFF=0 \
    PTPU_TRACE=1 PTPU_BLACKBOX_DIR="$blackbox" \
    PTPU_FAULT_INJECT="serve_die_at_step:6" \
    python - <<'PYEOF'
import sys
import threading
import warnings

sys.setswitchinterval(1e-5)
import numpy as np

from paddle_tpu import serving
from paddle_tpu.analysis import concurrency
from paddle_tpu.serving import (GenerationConfig, GenerationModel,
                                reference_decode)

warnings.simplefilter("ignore", RuntimeWarning)
model = GenerationModel.random(
    GenerationConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                     d_ff=64, max_seq_len=64), seed=0, name="fleet")
rng = np.random.RandomState(7)
shared = rng.randint(0, 64, size=8).tolist()  # shared prefix -> radix reuse
prompts = [shared + rng.randint(0, 64, size=rng.randint(2, 6)).tolist()
           for _ in range(12)]
refs = [reference_decode(model, p, 10) for p in prompts]
results = {}
with serving.ServingRouter(model, replicas=2, max_batch=2, max_seq_len=64,
                           block_size=4, prefill_chunk=4,
                           prefix_cache=True, backoff_base=0.0,
                           health_interval_s=0.02) as router:
    def client(lo, hi):
        for i in range(lo, hi):
            results[i] = router.generate(prompts[i], max_new_tokens=10,
                                         timeout=300)
    threads = [threading.Thread(target=client, args=(i * 3, i * 3 + 3),
                                name="fleet-client-%d" % i, daemon=True)
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    st = router.stats()
    dead = [r for r in router._replicas if r.state == "dead"]
    assert len(dead) == 1, st["replicas"]
    for w in dead[0].engine._workers.values():
        assert w.pool.check_invariants() == [], w.pool.check_invariants()
        assert w.pool.stats()["blocks_in_use"] == 0, w.pool.stats()
for i, p in enumerate(prompts):
    assert results[i] == refs[i], (i, results[i], refs[i])
assert st["failovers"] >= 1 and st["readmitted"] >= 1, st
concurrency.assert_clean()
concurrency.publish_metrics()
# fleet-tracing receipt: a re-admitted request's whole life — spans on
# the replica that died AND spans after re-admission — must share ONE
# trace_id, with the readmit marker in between (docs/OBSERVABILITY.md
# "Per-request trace ids")
from paddle_tpu.observability import tracing
evs = tracing.events()
readmits = [e for e in evs if e["name"] == "readmit"
            and "trace_id" in e.get("args", {})]
assert readmits, "no readmit trace event recorded"
ok = False
for rm in readmits:
    tid = rm["args"]["trace_id"]
    mine = [e for e in evs if e.get("args", {}).get("trace_id") == tid]
    pre = [e for e in mine
           if e["name"] in ("admit", "prefill_chunk", "decode_window")
           and e["ts"] < rm["ts"]]
    post = [e for e in mine
            if e["name"] in ("admit", "prefill_chunk", "decode_window")
            and e["ts"] > rm["ts"]]
    if pre and post:
        ok = True
        break
assert ok, "no single-trace_id span set straddles a readmit"
print("fleet kill leg ok:", {k: st[k] for k in
      ("failovers", "readmitted", "retries", "replicas_healthy")},
      concurrency.stats(), "traced requests straddling failover:",
      sum(1 for _ in readmits))
PYEOF
  # flight-recorder receipt: the run must have left at least one
  # atomically-renamed dump whose event list holds BOTH the replica
  # death and a subsequent re-admission (the atexit "exit" dump always
  # qualifies), and no torn tmp files
  python - "$blackbox" <<'PYEOF'
import glob, json, os, sys
bdir = sys.argv[1]
tmps = glob.glob(os.path.join(bdir, ".ptpu_tmp_*"))
assert not tmps, "torn flight-recorder tmp files: %r" % tmps
dumps = sorted(glob.glob(os.path.join(bdir, "ptpu_blackbox_*.json")))
assert dumps, "no flight-recorder dumps in %s" % bdir
ok = None
for path in dumps:
    doc = json.load(open(path))
    types = [e["type"] for e in doc["events"]]
    if "replica_dead" in types and "readmit" in types:
        ok = (path, doc["reason"])
        break
assert ok, "no dump holds both replica_dead and readmit: %r" % dumps
print("flight recorder ok: %d dump(s), %s (reason=%s)"
      % (len(dumps), os.path.basename(ok[0]), ok[1]))
PYEOF
  python tools/ptpu_stats.py "$dump" \
    --assert-min router/failovers=1 router/readmitted=1 \
                 router/retries=1 resilience/faults_injected=1 \
                 concurrency/locks_tracked=6 concurrency/acquisitions=1 \
                 serving/prefix_blocks_reused=1 \
    --assert-max concurrency/violations=0
  # Leg B — transient + stall: one retryable step failure (retried in
  # place at the boundary, nobody dies) and one injected stall (no
  # exception ever raised — the router's step-progress watchdog must
  # declare the replica dead and fail its work over), same identity and
  # violation gates.
  rm -f "$dump"
  JAX_PLATFORMS=cpu PTPU_METRICS=1 PTPU_METRICS_OUT="$dump" \
    PTPU_LOCK_CHECK=1 PTPU_RETRY_BACKOFF=0 \
    python - <<'PYEOF'
import sys
import warnings

sys.setswitchinterval(1e-5)
import numpy as np

from paddle_tpu import resilience, serving
from paddle_tpu.analysis import concurrency
from paddle_tpu.serving import (GenerationConfig, GenerationModel,
                                reference_decode)

warnings.simplefilter("ignore", RuntimeWarning)
model = GenerationModel.random(
    GenerationConfig(vocab_size=64, d_model=32, n_heads=2, n_layers=2,
                     d_ff=64, max_seq_len=64), seed=0, name="fleet")
rng = np.random.RandomState(11)
prompts = [rng.randint(0, 64, size=rng.randint(3, 8)).tolist()
           for _ in range(8)]
refs = [reference_decode(model, p, 10) for p in prompts]
# warm the (replica-shared) jitted step through a throwaway engine
# BEFORE arming the injector: the tight 0.5s stall budget below is
# meant for the injected stall, not for first-step XLA compile (the
# watchdog contract: stall_timeout_s must exceed worst-case step time)
with serving.ServingEngine(model, max_batch=2, max_seq_len=64,
                           block_size=4) as warm:
    warm.generate([1, 2], max_new_tokens=2, timeout=300)
resilience.set_global_injector(resilience.FaultInjector(
    "serve_transient_at_step:3,serve_stall_at_step:8"))
with serving.ServingRouter(model, replicas=2, max_batch=2, max_seq_len=64,
                           block_size=4, backoff_base=0.0,
                           stall_timeout_s=0.5,
                           health_interval_s=0.02) as router:
    reqs = [router.submit(p, max_new_tokens=10) for p in prompts]
    outs = [r.wait(300) for r in reqs]
    st = router.stats()
    dead = [r for r in router._replicas if r.state == "dead"]
    assert len(dead) == 1, st["replicas"]
    assert "stalled" in str(dead[0].error), dead[0].error
    for w in dead[0].engine._workers.values():
        assert w.pool.check_invariants() == [], w.pool.check_invariants()
assert outs == refs, [i for i, (o, r) in enumerate(zip(outs, refs))
                      if o != r]
assert st["failovers"] >= 1, st
retried = sum(r["model:default"]["transient_retries"]
              for r in st["replicas"])
assert retried >= 1, st
concurrency.assert_clean()
concurrency.publish_metrics()
print("fleet stall leg ok: watchdog failover after in-place transient "
      "retry", {k: st[k] for k in ("failovers", "readmitted")})
PYEOF
  python tools/ptpu_stats.py "$dump" \
    --assert-min router/failovers=1 serving/step_transient_retries=1 \
                 resilience/faults_injected=2 \
    --assert-max concurrency/violations=0
  # Leg C — throughput scaling 1 -> 2 replicas. The functional gates
  # (routed outputs token-identical on both legs, both replicas
  # actually used) hold on every attempt; the scaling ratio is a
  # timing measurement retried like serve's ratios. The floor is
  # core-aware: with >= 2 cores the two engine threads run their XLA
  # steps concurrently (GIL released) and must clear 1.5x; a 1-core
  # box serializes the step streams, so parity (0.85 with jitter
  # margin) is the honest expectation — on real TPU pods each replica
  # owns its chip and the scaling is the product number.
  local floor=1.5 attempt rc=1
  if [ "$(nproc)" -lt 2 ]; then floor=0.85; fi
  for attempt in 1 2 3; do
    rm -f "$dump" "$legs"
    JAX_PLATFORMS=cpu PTPU_METRICS=1 \
      python bench.py --tiny --fleet-only --metrics-out "$dump" \
      --legs-out "$legs"
    python tools/ptpu_stats.py "$dump" \
      --assert-has bench/serving_fleet_tokens_per_sec_1r \
                   bench/serving_fleet_tokens_per_sec_2r \
      --assert-min bench/serving_fleet_outputs_match=1 \
                   bench/serving_fleet_replicas_used=2
    set +e
    python tools/ptpu_stats.py "$dump" \
      --assert-min bench/serving_fleet_scaling="$floor"
    rc=$?
    set -e
    [ "$rc" -eq 0 ] && break
    echo "fleet scaling below ${floor}x (loaded box?) — retry $attempt/2" >&2
  done
  [ "$rc" -eq 0 ]
  python - "$legs" <<'PYEOF'
import json, sys
legs = {e["leg"]: e for e in json.load(open(sys.argv[1]))}
assert "serving_fleet_1r" in legs and "serving_fleet_2r" in legs, legs
assert legs["serving_fleet_1r"]["outputs_match"], legs
assert legs["serving_fleet_2r"]["outputs_match"], legs
assert legs["serving_fleet_2r"]["replicas_used"] == 2, legs
print("fleet stage ok:",
      {k: v["tokens_per_sec"] for k, v in legs.items()},
      "scaling:", legs["serving_fleet_2r"]["fleet_scaling"])
PYEOF
}

do_online() {
  # online-learning hot-swap receipt (docs/SERVING.md "Online
  # updates"). Leg A — the chaos matrix under live traffic: a
  # 2-replica fleet serves a continuous request pump while an
  # OnlineUpdater walks four chained scenarios — (1) happy-path
  # publish + canary-gated rollout, (2) an injected torn export
  # (detected by the digest manifest, never rolled out, version
  # republished next interval), (3) an injected canary anomaly
  # (structured rollback drains the canary back onto the incumbent
  # weights, zero client errors), (4) a replica killed mid-drain (the
  # rollout completes on the survivor). Every output must be
  # token-identical to reference_decode under the weight version that
  # served it, the router's request ledger must balance (nothing
  # dropped), and the whole path runs under PTPU_LOCK_CHECK=1 with
  # switch-interval jitter gating concurrency/violations == 0.
  local dump=/tmp/ptpu_online_metrics.json
  rm -f "$dump"
  JAX_PLATFORMS=cpu PTPU_METRICS=1 PTPU_METRICS_OUT="$dump" \
    PTPU_LOCK_CHECK=1 PTPU_RETRY_BACKOFF=0 \
    python - <<'PYEOF'
import os
import sys
import threading
import time
import warnings

sys.setswitchinterval(1e-5)
import numpy as np

import paddle_tpu as fluid
from paddle_tpu import checkpoint as ckpt
from paddle_tpu import inference, resilience, serving
from paddle_tpu.analysis import concurrency
from paddle_tpu.serving import reference_decode

warnings.simplefilter("ignore", RuntimeWarning)
base = "/tmp/ptpu_online_stage"
import shutil
shutil.rmtree(base, ignore_errors=True)
ckpt_dir, pub_dir = os.path.join(base, "ckpts"), os.path.join(base, "pub")
v0_dir = os.path.join(base, "v0")
os.makedirs(ckpt_dir)

from paddle_tpu.models import transformer_fluid
prog, sprog = fluid.Program(), fluid.Program()
with fluid.program_guard(prog, sprog):
    transformer_fluid.build(vocab_size=64, d_model=16, n_heads=2,
                            n_layers=1, d_ff=32, seq_len=8, remat=False)
scope = fluid.Scope()
fluid.Executor(fluid.CPUPlace()).run(sprog, scope=scope)
inference.export_generation_model(v0_dir, prog, scope, max_seq_len=32)


def scope_state(seed):
    rng = np.random.RandomState(seed)
    state = {}
    for name, value in scope.items():
        v = np.asarray(value)
        if np.issubdtype(v.dtype, np.floating):
            v = v + rng.normal(0, 0.02, v.shape).astype(v.dtype)
        state[name] = v
    return state


def vers():
    return [router.replica_engine(i).weight_version()
            for i in range(2) if router.replica_states()[i] != "dead"]


router = serving.ServingRouter(v0_dir, replicas=2, max_batch=2,
                               max_seq_len=32, block_size=4,
                               health_interval_s=0.02,
                               backoff_base=0.0, stall_timeout_s=30.0)
try:
    # latency_factor widened: the switch-interval jitter makes every
    # request slow in bursts, and the happy-path canary (leg 1) must
    # promote on real health, not flake on scheduler noise — the
    # anomaly legs below inject their signal explicitly
    upd = serving.OnlineUpdater(router, ckpt_dir, pub_dir, prog,
                                max_seq_len=32, canary_pct=50.0,
                                canary_window_s=0.4,
                                gate=serving.CanaryGate(latency_factor=6.0))
    # warm the jitted step on both replicas before the pump starts
    for p in [router.submit([1, 2], max_new_tokens=2) for _ in range(2)]:
        p.wait(300)
    stop, errs = threading.Event(), []

    def pump():
        while not stop.is_set():
            try:
                router.submit([1, 2], max_new_tokens=4).wait(60)
            except Exception as e:
                errs.append(e)
            time.sleep(0.005)

    t = threading.Thread(target=pump, name="online-pump", daemon=True)
    t.start()
    try:
        # (1) happy path: publish v1, canary window, promote fleet-wide
        ckpt.save_checkpoint(ckpt_dir, scope_state(1), 1)
        out = upd.poll_once()
        assert out and out["published"] and out["promoted"], out
        assert vers() == [1, 1], vers()
        # (2) torn export: detected, never served, republished as v2
        resilience.set_global_injector(
            resilience.FaultInjector("ckpt_torn_export:1"))
        ckpt.save_checkpoint(ckpt_dir, scope_state(2), 2)
        out = upd.poll_once()
        assert out and not out["published"] \
            and out["reason"] == "torn_export", out
        assert vers() == [1, 1], vers()  # no rollout of the torn dir
        ckpt.save_checkpoint(ckpt_dir, scope_state(3), 3)
        out = upd.poll_once()
        assert out and out["published"] and out["version"] == 2, out
        assert vers() == [2, 2], vers()
        # (3) canary anomaly: structured rollback, fleet on incumbent
        resilience.set_global_injector(
            resilience.FaultInjector("canary_anomaly_at_version:3"))
        ckpt.save_checkpoint(ckpt_dir, scope_state(4), 4)
        out = upd.poll_once()
        assert out and out["published"] and not out["promoted"], out
        assert upd.rollbacks == 1, upd.stats()
        assert vers() == [2, 2], vers()
    finally:
        stop.set()
        t.join()
    # single-fault rollouts (swap, torn export, rollback) never
    # surfaced a client error — the pump stops before leg 4 because a
    # replica CRASHING while its peer drains is a double fault: for
    # one health-poll interval the fleet genuinely has nowhere to
    # dispatch, and clients see the same error a crash-only outage
    # would produce
    assert not errs, errs[:3]
    try:
        # (4) replica killed mid-drain: rollout completes on survivor
        resilience.set_global_injector(
            resilience.FaultInjector("swap_die_mid_drain:1"))
        ckpt.save_checkpoint(ckpt_dir, scope_state(5), 5)
        out = upd.poll_once()
        assert out and out["published"] and out["promoted"], out
        assert router.replica_states().count("dead") == 1, \
            router.replica_states()
        assert vers() == [4], vers()
    finally:
        resilience.set_global_injector(None)
    # per-version token identity: the promoted artifact is what serves
    m4 = inference.load_generation_model(os.path.join(pub_dir, "v4"))
    got = router.submit([9, 3], max_new_tokens=5).wait(60)
    assert got == reference_decode(m4, [9, 3], 5), got
    st = router.stats()
    assert st["requests_submitted"] == \
        st["requests_completed"] + st["requests_failed"], st
finally:
    router.close()
concurrency.assert_clean()
concurrency.publish_metrics()
print("online chaos matrix ok:", upd.stats(),
      {k: st[k] for k in ("requests_submitted", "requests_completed",
                          "requests_failed", "canary_requests")},
      concurrency.stats())
PYEOF
  python tools/ptpu_stats.py "$dump" \
    --assert-min online/versions_published=3 online/swaps=5 \
                 online/rollbacks=1 online/torn_exports=1 \
                 serving/prefix_cache_flushes=1 \
                 resilience/faults_injected=3 \
                 concurrency/locks_tracked=6 concurrency/acquisitions=1 \
    --assert-max concurrency/violations=0
  # Leg B — the real thing end to end: a live ResilientTrainer
  # streaming checkpoints while the fleet serves under load, >= 2
  # versions published and rolled out, every output attributed to the
  # exact weight version that produced it (the slow pytest leg, also
  # under the lock checker)
  JAX_PLATFORMS=cpu PTPU_RETRY_BACKOFF=0 PTPU_LOCK_CHECK=1 \
    python -m pytest tests/test_online.py -q -m slow \
    -p no:cacheprovider -p no:xdist -p no:randomly
  # Leg C — steady-state vs mid-rollout serving throughput. Functional
  # gates (token identity per version, zero requests lost, both
  # replicas promoted) hold on every attempt; the rollout throughput
  # ratio is a timing measurement retried like serve's ratios — the
  # floor says a live weight push may not stall the fleet, not that
  # it is free (each replica drains in turn).
  local legs=/tmp/ptpu_online_legs.json attempt rc=1
  for attempt in 1 2 3; do
    rm -f "$dump" "$legs"
    JAX_PLATFORMS=cpu PTPU_METRICS=1 \
      python bench.py --tiny --online-only --metrics-out "$dump" \
      --legs-out "$legs"
    python tools/ptpu_stats.py "$dump" \
      --assert-has bench/online_tokens_per_sec_steady \
                   bench/online_tokens_per_sec_rollout \
      --assert-min bench/online_outputs_match=1 \
                   bench/online_versions_published=1 \
                   bench/online_swaps=2 \
      --assert-max bench/online_requests_lost=0
    set +e
    python tools/ptpu_stats.py "$dump" \
      --assert-min bench/online_rollout_throughput_ratio=0.3
    rc=$?
    set -e
    [ "$rc" -eq 0 ] && break
    echo "online rollout ratio below 0.3x (loaded box?) — retry $attempt/2" >&2
  done
  [ "$rc" -eq 0 ]
  python - "$legs" <<'PYEOF'
import json, sys
legs = {e["leg"]: e for e in json.load(open(sys.argv[1]))}
assert "online_steady" in legs and "online_rollout" in legs, legs
assert legs["online_steady"]["outputs_match"], legs
assert legs["online_rollout"]["outputs_match"], legs
assert legs["online_rollout"]["requests_lost"] == 0, legs
assert legs["online_rollout"]["final_versions"] == [1, 1], legs
print("online stage ok:",
      {k: v["tokens_per_sec"] for k, v in legs.items()},
      "ratio:", legs["online_rollout"]["online_rollout_throughput_ratio"])
PYEOF
}

do_zero() {
  # ZeRO/overlap receipt (docs/ZERO.md). Functional gates hold on every
  # attempt: every rung's trained params close to the bucketed anchor
  # (bench/zero{2,3,_offload}_close), every leg's loss finite AND
  # decreasing (a NaN loss fails the decreasing gauge — NaN compares
  # false), the structural overlap ratio recorded, and real bytes moved
  # through the host-offload stager. The step-time overlap receipt
  # (overlapped bucketed step <= the non-overlapped PR-5 path, i.e.
  # speedup >= 1) is a timing measurement on a shared box, so like
  # serve's throughput ratio it retries up to twice; on real TPU meshes
  # the async collectives make the margin, on CPU the collectives run
  # synchronously and parity-or-better is the expectation.
  local dump=/tmp/ptpu_zero_metrics.json legs=/tmp/ptpu_zero_legs.json
  local mc=/tmp/ptpu_zero_multichip.json
  local attempt rc=1
  for attempt in 1 2 3; do
    rm -f "$dump" "$legs"
    JAX_PLATFORMS=cpu PTPU_METRICS=1 \
      python bench.py --zero-only --metrics-out "$dump" \
      --legs-out "$legs"
    python tools/ptpu_stats.py "$dump" \
      --assert-has bench/zero_step_time_overlap \
                   bench/zero_step_time_no_overlap \
                   bench/zero_step_time_per_leaf \
                   bench/zero_step_time_zero3 \
                   bench/zero_step_time_offload zero/gather_bytes \
      --assert-min bench/zero2_close=1 bench/zero3_close=1 \
                   bench/zero_offload_close=1 \
                   bench/zero_losses_decreasing=1 \
                   zero/overlap_ratio=0.5 zero/offload_bytes=1 \
      --assert-max bench/zero1_per_leaf_last_loss=10 \
                   bench/zero2_overlap_last_loss=10 \
                   bench/zero3_last_loss=10 \
                   bench/zero_offload_last_loss=10
    set +e
    python tools/ptpu_stats.py "$dump" \
      --assert-min bench/zero_overlap_speedup=1
    rc=$?
    set -e
    [ "$rc" -eq 0 ] && break
    echo "zero overlap speedup below 1x (loaded box?) — retry $attempt/2" >&2
  done
  [ "$rc" -eq 0 ]
  # emit the per-leg numbers as one JSON record for this axis
  python - "$legs" "$mc" <<'PYEOF'
import json, sys
legs = json.load(open(sys.argv[1]))
by = {e["leg"]: e for e in legs}
tail = ("zero ladder ok: " + " ".join(
    "%s=%.2fms/loss=%.4f" % (e["leg"], e["step_time_s"] * 1e3,
                             e["last_loss"]) for e in legs)
    + " overlap_speedup=%.4f" % by["zero2_overlap"]["overlap_speedup"])
json.dump({"n_devices": 8, "rc": 0, "ok": True, "skipped": False,
           "tail": tail, "zero_legs": legs},
          open(sys.argv[2], "w"), indent=2)
print(tail)
PYEOF
}

case "$stage" in
  build) do_build ;;
  test) do_build; do_test ;;
  api_check) do_api_check ;;
  bench) do_bench ;;
  bench-smoke) do_bench_smoke ;;
  stress) do_stress ;;
  obs) do_obs_smoke ;;
  chaos) do_chaos ;;
  data-chaos) do_data_chaos ;;
  amp) do_amp ;;
  serve) do_serve ;;
  lint) do_lint ;;
  race) do_race ;;
  verify) do_verify ;;
  quant) do_quant ;;
  rec) do_rec ;;
  kernels) do_kernels ;;
  zero) do_zero ;;
  fleet) do_fleet ;;
  online) do_online ;;
  all) do_build; do_lint; do_test; do_api_check; do_bench_smoke; do_chaos; do_data_chaos; do_amp; do_serve; do_fleet; do_online; do_race; do_verify; do_quant; do_rec; do_kernels; do_zero; do_bench ;;
  *) echo "unknown stage: $stage" >&2; exit 2 ;;
esac
