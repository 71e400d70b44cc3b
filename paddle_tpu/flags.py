"""Runtime flags facade (parity: gflags + the env-var bootstrap of
python/paddle/fluid/__init__.py:104-165 `__bootstrap__` — a curated
FLAGS_* allowlist is read from the environment at import; programmatic
set_flags/get_flags mirror the later fluid API).

Supported flags:
  check_nan_inf       : after every op kernel, verify all floating outputs
                        are finite; raise naming the op/var (reference
                        FLAGS_check_nan_inf, framework/operator.cc:950).
                        The check compiles into the jitted step as
                        isfinite-all reductions, so it costs one fused
                        reduction per op output when on and nothing when off.
  cpu_deterministic   : deterministic reductions (XLA is deterministic by
                        default on TPU; kept for API parity).
  eager_delete_tensor_gb : accepted for parity; XLA buffer liveness already
                        frees intermediates (donation in executor).

This module is also the ONE registry for the framework's own `PTPU_*`
environment switches (docs/STATIC_ANALYSIS.md): every in-tree read goes
through `env("PTPU_...")` against a declared (type, default, docstring)
entry — `tools/ptpu_lint.py` rejects direct `os.environ` reads of
`PTPU_*` names and `env()` calls naming an undeclared flag, so a typo'd
flag name fails CI instead of silently reading a default. `describe()`
prints the registry as the reference table. This module must stay
dependency-free (stdlib only) so anything in the package can import it.
"""

import os

__all__ = ["set_flags", "get_flags", "flag", "env", "env_flag",
           "declared_flags", "describe", "EnvFlag"]

_FLAGS = {
    "check_nan_inf": False,
    "cpu_deterministic": True,
    "eager_delete_tensor_gb": 0.0,
    # pserver RPC robustness (grpc_client.h:181-199 parity):
    #   rpc_deadline     — seconds one RPC (incl. reconnect attempts) may
    #                      take before failing loudly (FLAGS_rpc_deadline
    #                      is ms in the reference; seconds here)
    #   rpc_retry_times  — reconnect+resend attempts per RPC
    #                      (FLAGS_rpc_retry_times)
    #   rpc_barrier_grace — how long the server waits on stragglers at a
    #                      sync barrier before erring the round
    "rpc_deadline": 120.0,
    "rpc_retry_times": 3,
    "rpc_barrier_grace": 300.0,
}

_ENV_ALLOWLIST = {
    "FLAGS_check_nan_inf": ("check_nan_inf", lambda s: s not in
                            ("0", "false", "False", "")),
    "FLAGS_cpu_deterministic": ("cpu_deterministic", lambda s: s not in
                                ("0", "false", "False", "")),
    "FLAGS_eager_delete_tensor_gb": ("eager_delete_tensor_gb", float),
    "FLAGS_rpc_deadline": ("rpc_deadline", float),
    "FLAGS_rpc_retry_times": ("rpc_retry_times", int),
    "FLAGS_rpc_barrier_grace": ("rpc_barrier_grace", float),
}


def _bootstrap():
    for env, (name, conv) in _ENV_ALLOWLIST.items():
        if env in os.environ:
            try:
                _FLAGS[name] = conv(os.environ[env])
            except ValueError:
                pass


_bootstrap()


def set_flags(flags):
    for k, v in flags.items():
        key = k[6:] if k.startswith("FLAGS_") else k
        if key not in _FLAGS:
            raise KeyError("unknown flag %r" % k)
        _FLAGS[key] = v


def get_flags(keys):
    if isinstance(keys, str):
        keys = [keys]
    out = {}
    for k in keys:
        key = k[6:] if k.startswith("FLAGS_") else k
        out[k] = _FLAGS[key]
    return out


def flag(name):
    return _FLAGS[name]


# ---------------------------------------------------------------------------
# PTPU_* environment-switch registry
# ---------------------------------------------------------------------------


def env_flag(name, raw=None):
    """Boolean env parsing shared by every PTPU_* switch (the spelling
    semantics parallel/zero.py established): unset/empty -> None,
    1/true/on/yes -> True, 0/false/off/no -> False (case-insensitive),
    anything else raises naming the flag."""
    raw = os.environ.get(name, "") if raw is None else raw
    if raw == "":
        return None
    low = raw.strip().lower()
    if low in ("1", "true", "on", "yes"):
        return True
    if low in ("0", "false", "off", "no"):
        return False
    raise ValueError("%s=%r is not a boolean flag (use 0/1)" % (name, raw))


class EnvFlag:
    """One declared PTPU_* environment switch: name, type ('bool', 'int',
    'float', 'str', 'path'), default (returned when unset/empty),
    docstring. 'path' accepts the boolean OFF spellings as unset —
    `PTPU_TRACE_DIR=0` disables tracing rather than naming a directory
    literally '0', the semantics the pre-registry `_env_on` gate had."""

    __slots__ = ("name", "type", "default", "doc")

    def __init__(self, name, type, default, doc):
        self.name = name
        self.type = type
        self.default = default
        self.doc = doc

    def parse(self, raw):
        if raw == "":
            return self.default
        if self.type == "bool":
            val = env_flag(self.name, raw)
            return self.default if val is None else val
        if self.type in ("int", "float"):
            conv = int if self.type == "int" else float
            try:
                return conv(raw)
            except ValueError:
                raise ValueError("%s=%r is not %s %s"
                                 % (self.name, raw,
                                    "an" if self.type == "int" else "a",
                                    self.type))
        if self.type == "path" and raw.strip().lower() in (
                "0", "false", "off", "no"):
            return self.default
        return raw


_ENV_REGISTRY = {}


def _declare(name, type, default, doc):
    _ENV_REGISTRY[name] = EnvFlag(name, type, default, doc)


# -- observability (docs/OBSERVABILITY.md) ----------------------------------
_declare("PTPU_METRICS", "bool", False,
         "enable the instrumented metrics hot paths")
_declare("PTPU_METRICS_OUT", "path", None,
         "dump the metrics registry as JSON to this path at process exit")
_declare("PTPU_TRACE", "bool", False,
         "enable tracing-span recording")
_declare("PTPU_TRACE_DIR", "path", None,
         "enable spans and write <dir>/ptpu_trace.json at process exit")
_declare("PTPU_METRICS_PORT", "int", None,
         "serve live /metrics, /healthz and /varz on this loopback port "
         "(0 = pick an ephemeral port; unset = no endpoint thread)")
_declare("PTPU_BLACKBOX_DIR", "path", None,
         "enable the flight recorder and write its crash dumps "
         "(ptpu_blackbox_*.json) into this directory")
_declare("PTPU_BLACKBOX_EVENTS", "int", None,
         "flight-recorder ring capacity in events (default 4096)")
# -- executor / async engine (docs/ASYNC_EXECUTION.md) ----------------------
_declare("PTPU_ASYNC_STEPS", "int", 12,
         "async in-flight window depth before dispatch backpressures")
# -- compiler pipeline (docs/COMPILER_PASSES.md, docs/STATIC_ANALYSIS.md) ---
_declare("PTPU_NO_PROGRAM_OPT", "bool", False,
         "disable the compile-time pass pipeline (exact unoptimized path)")
_declare("PTPU_VERIFY_PASSES", "bool", False,
         "run the Program IR verifier before the pass pipeline and after "
         "each pass, blaming the pass that introduced a violation")
# -- mixed precision (docs/MIXED_PRECISION.md) ------------------------------
_declare("PTPU_AMP", "bool", False,
         "activate the AMP dtype rewrite process-wide")
_declare("PTPU_AMP_LEVEL", "str", "O1",
         "AMP level when activated via PTPU_AMP (O1 or O2)")
_declare("PTPU_AMP_DTYPE", "str", "bfloat16",
         "AMP compute dtype when activated via PTPU_AMP")
_declare("PTPU_AMP_BUCKET_MB", "float", None,
         "gradient-bucket size in MiB for coalesced collectives "
         "(0/unset = per-leaf collectives)")
# -- quantized inference (docs/QUANTIZATION.md) -----------------------------
_declare("PTPU_QUANT", "bool", False,
         "activate the int8 quant_rewrite pass process-wide")
_declare("PTPU_QUANT_MODE", "str", "weight_only",
         "quantization mode when activated via PTPU_QUANT "
         "(weight_only or full_int8)")
_declare("PTPU_QUANT_TABLE", "path", None,
         "calibration-table JSON (quant.CalibrationTable.save) supplying "
         "activation ranges for full_int8")
_declare("PTPU_QUANT_BLACKLIST", "str", None,
         "comma-separated var names whose ops are pinned fp32 by the "
         "quant_rewrite pass")
# -- ZeRO (docs/ZERO.md) ----------------------------------------------------
_declare("PTPU_ZERO_STAGE", "int", None,
         "ZeRO sharding stage for ShardedAdam (1, 2 or 3)")
_declare("PTPU_ZERO_OVERLAP", "bool", False,
         "issue per-bucket collectives in backward order (comm/compute "
         "overlap)")
_declare("PTPU_ZERO_OFFLOAD", "bool", False,
         "keep optimizer state in host RAM between steps")
# -- resilience (docs/RESILIENCE.md) ----------------------------------------
_declare("PTPU_ANOMALY_POLICY", "str", None,
         "ResilientTrainer anomaly policy (warn|skip_batch|rollback|abort; "
         "unset = rollback)")
_declare("PTPU_SPIKE_FACTOR", "float", None,
         "loss-spike threshold as a multiple of the running EMA "
         "(unset = spike detection off)")
_declare("PTPU_FAULT_INJECT", "str", None,
         "deterministic fault-injection spec, e.g. "
         "'nan_at_step:12,ckpt_torn_write:2'")
_declare("PTPU_RETRY_BUDGET", "int", 8,
         "rollback-and-retry attempts per training run")
_declare("PTPU_RETRY_BACKOFF", "float", 0.05,
         "base seconds of exponential backoff between transient retries")
# -- streaming data plane (docs/DATA_PLANE.md) ------------------------------
_declare("PTPU_DATA_ANOMALY_POLICY", "str", None,
         "corrupt-input containment policy for recordio shard readers "
         "(abort|skip_record|quarantine_shard; unset = skip_record)")
_declare("PTPU_DATA_STRICT", "bool", False,
         "abort the sample exchange on a confirmed-dead shuffle peer "
         "instead of re-partitioning across the survivors")
_declare("PTPU_DATA_RETRY_BUDGET", "int", 2,
         "frame retries per CONNECTED shuffle peer (wedged before ack, "
         "torn frame) before it is confirmed dead; never-connected "
         "peers are governed by PTPU_DATA_EXCHANGE_TIMEOUT instead")
_declare("PTPU_DATA_PEER_TIMEOUT", "float", 10.0,
         "seconds one shuffle-peer connection attempt / frame "
         "send+ack may take; also sizes the bounded straggler grace "
         "for SEND-CONFIRMED-DEAD peers' frames (acked-but-silent "
         "peers get the full PTPU_DATA_EXCHANGE_TIMEOUT — a slow "
         "loader holding our bucket is not a dead one)")
_declare("PTPU_DATA_EXCHANGE_TIMEOUT", "float", 300.0,
         "full sample-exchange deadline; a never-connected peer "
         "(listener not up — startup skew or a crashed machine) is "
         "only confirmed dead at this deadline, the legacy tolerance")
# -- serving (docs/SERVING.md) ----------------------------------------------
_declare("PTPU_SERVE_ASYNC_STEPS", "int", 2,
         "serving steps the worker keeps dispatched ahead of the one "
         "whose result it takes (depth): a tick of the host longer than "
         "depth - 1 steps leaves the device idle, and a new request's "
         "first step stands behind depth - 1 steps of other rows "
         "(1 = synchronous)")
_declare("PTPU_SERVE_PREFILL_CHUNK", "int", 0,
         "prompt tokens a prefill row consumes per mixed serving step "
         "(0 = the server's default, 256, clamped to the context)")
_declare("PTPU_SERVE_PREFIX_CACHE", "bool", False,
         "content-addressed KV block sharing: requests whose prompt "
         "prefix is cached skip its prefill compute and block "
         "allocations (radix prefix caching)")
_declare("PTPU_SERVE_SPEC_K", "int", 0,
         "speculative decoding: draft tokens proposed per serving "
         "decode step and verified in one batched target step "
         "(0 = legacy one-token decode)")
_declare("PTPU_SERVE_SPEC_TREE", "str", None,
         "tree speculation shape 'WxD' (width x depth, e.g. '2x3'): "
         "verify a W-branch token tree of depth D per compiled step "
         "via the in-window tree attention mask; unset/0/off = the "
         "linear PTPU_SERVE_SPEC_K window, bitwise PR-12 behavior")
_declare("PTPU_SERVE_DRAFT_MODEL", "path", None,
         "generation-artifact directory holding the draft model for "
         "speculative decoding: loads a jitted on-device ModelDrafter "
         "per engine model (unset = n-gram prompt-lookup drafting)")
_declare("PTPU_SERVE_DRAFT_CHUNK", "int", 16,
         "prompt tokens per draft-side catch-up prefill chunk when the "
         "jitted ModelDrafter brings a row's draft KV level with its "
         "committed history")
_declare("PTPU_SERVE_REPLICAS", "int", 1,
         "ServingRouter engine-replica count (least-loaded dispatch "
         "with health-checked failover across them)")
_declare("PTPU_SERVE_DEADLINE_S", "float", None,
         "per-request serving deadline in seconds: requests past it "
         "fail with DeadlineExceededError at the next step boundary "
         "(unset = wait forever, the legacy behavior)")
_declare("PTPU_SERVE_RETRY_BUDGET", "int", 3,
         "re-admission attempts the ServingRouter may spend per "
         "request when its replica fails over (exponential backoff; "
         "RetryBudgetExceededError when spent)")
_declare("PTPU_SERVE_CANARY_PCT", "float", None,
         "percentage of new requests the ServingRouter pins to the "
         "canary replica while an OnlineUpdater rollout is in its "
         "canary phase (docs/SERVING.md \"Online updates\"; unset = "
         "no canary gate, router/engine stay bitwise-legacy)")
_declare("PTPU_ONLINE_POLL_S", "float", 0.25,
         "OnlineUpdater checkpoint-directory poll interval in seconds "
         "(the cadence at which a live trainer's newly landed intact "
         "checkpoints are discovered and exported)")
# -- concurrency analysis (docs/STATIC_ANALYSIS.md) -------------------------
_declare("PTPU_LOCK_CHECK", "bool", False,
         "route the runtime's named lock sites through tracked "
         "wrappers: lock-order/deadlock detection, "
         "blocking-while-holding checks and the pool/engine invariant "
         "hooks (unset = plain threading primitives, zero overhead)")
_declare("PTPU_LOCK_HOLD_MS", "float", None,
         "with PTPU_LOCK_CHECK=1, report a long-hold violation when a "
         "tracked lock is held longer than this many milliseconds "
         "(unset = off)")
# -- Pallas kernel dispatch (docs/KERNELS.md) -------------------------------
_declare("PTPU_KERNELS", "bool", None,
         "Pallas kernel dispatch mode: 1 forces every registered kernel "
         "on (interpret mode off-TPU — the CI/test spelling), 0 forces "
         "the lax fallbacks bitwise, unset keeps each kernel's default "
         "platform policy")
_declare("PTPU_KERNELS_DISABLE", "str", None,
         "comma-separated kernel names pinned to their lax fallback "
         "regardless of PTPU_KERNELS (names: docs/KERNELS.md "
         "qualification table)")
# -- recommender embedding fast path (docs/RECOMMENDER.md) ------------------
_declare("PTPU_EMBED_PREFETCH", "bool", False,
         "stage host-embedding rows one step ahead: train_from_dataset "
         "announces batch t+1's ids to a background gather worker and "
         "the compiled step reads the deduped row buffer as an ordinary "
         "device feed instead of a blocking in-step pure_callback pull "
         "(unset = the exact legacy synchronous lookup)")
_declare("PTPU_EMBED_CACHE_ROWS", "int", 0,
         "with PTPU_EMBED_PREFETCH=1, keep this many hot embedding rows "
         "resident in a device-side cache with frequency admission + LRU "
         "eviction; 0 = no cache (prefetch buffer only)")
_declare("PTPU_EMBED_CACHE_ADMIT", "int", 2,
         "admission threshold for the hot-row cache: a row enters the "
         "cache once it has been touched by this many distinct batches")
_declare("PTPU_EMBED_PUSH_QUEUE", "int", 64,
         "Communicator async-push queue bound per table; a full queue "
         "blocks the enqueueing (training) thread until the drain "
         "thread catches up (backpressure, embed/push_queue_depth "
         "gauge)")


def env(name):
    """Read one declared PTPU_* environment switch: the parsed value, or
    the declared default when unset/empty. Reads the environment at CALL
    time (no import-time latch). Unknown names raise — declare the flag
    here first (the linter enforces the same rule statically)."""
    spec = _ENV_REGISTRY.get(name)
    if spec is None:
        raise KeyError(
            "undeclared environment flag %r — add it to the "
            "paddle_tpu.flags registry (see docs/STATIC_ANALYSIS.md)"
            % (name,))
    return spec.parse(os.environ.get(name, ""))


def declared_flags():
    """{name: EnvFlag} snapshot of the PTPU_* registry (the linter's and
    describe()'s source of truth)."""
    return dict(_ENV_REGISTRY)


def describe():
    """The PTPU_* registry as an aligned text table (name, type, default,
    description) — the contract surface docs and the linter check
    against."""
    rows = [("Flag", "Type", "Default", "Description")]
    for name in sorted(_ENV_REGISTRY):
        spec = _ENV_REGISTRY[name]
        rows.append((name, spec.type,
                     "-" if spec.default is None else repr(spec.default),
                     spec.doc))
    w0 = max(len(r[0]) for r in rows)
    w1 = max(len(r[1]) for r in rows)
    w2 = max(len(r[2]) for r in rows)
    return "\n".join("%-*s  %-*s  %-*s  %s" % (w0, r[0], w1, r[1],
                                               w2, r[2], r[3])
                     for r in rows)
