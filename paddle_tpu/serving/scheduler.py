"""Request queue and iteration-level (continuous-batching) scheduler
(Orca OSDI '22 mapped onto a fixed-shape XLA decode step).

The unit of scheduling is one *step*: every active batch slot advances
by one generated token per step (or by a chunk of its prompt), and
sequences join/retire only at step boundaries. The compiled steps'
shapes never change — admission
fills a free slot's row in the (fixed ``[max_batch]``) input arrays and
flips its ``active`` flag, retirement flips it back — so XLA never
retraces no matter how traffic arrives.

Admission control is two-gated:

  * queue gate — ``RequestQueue`` bounds how many requests may wait;
    past ``max_queue`` a submit fails fast with ``AdmissionError``
    (callers see backpressure instead of unbounded memory growth).
  * KV gate — a queued request joins the batch only when the block pool
    can reserve its worst-case block count (``blocks_needed(prompt +
    max_new)``), so decode can never deadlock on cache exhaustion.
    Head-of-line order is preserved: if the head request doesn't fit,
    nothing behind it jumps the queue (no starvation of big requests).

Prefill rides the same steps (Orca's iteration-level scheduling, in
Sarathi-Serve's chunks): whenever a row is mid-prompt ``plan_step``
plans a MIXED step, in which prefill rows consume up to
``prefill_chunk`` prompt tokens (all of whose blocks are allocated at
the boundary, still drawn from the admission reservation) and decode
rows ride along as 1-token windows; when no row is mid-prompt it plans
a DECODE step, every row a window of one whose input token chains
on-device from the previous step's output. ``prefill_token_budget``
caps the TOTAL prompt tokens per mixed step, so decode rows' per-step
latency stays bounded no matter how many prompts arrive at once. Where
nobody states it, it is four chunks but no more than the rows at which
a weight matmul turns compute-bound, and never under one chunk
(:func:`default_prefill_token_budget`, :data:`PREFILL_BUDGET_RIDGE`):
past the ridge every further row adds its full time to the step of
every decode row in it and buys no prefill throughput. The budget goes
to the prefilling rows in the order they were ADMITTED, first admitted
first fed: a row past the budget sits the step out (``rows_deferred``
counts them) and waits for the prompts ahead of it, never for a later
arrival that landed in a lower slot.

**Radix prefix caching** (``prefix_cache=True``, off by default):
admission runs a longest-prefix-match of the prompt's chain keys
(:func:`~paddle_tpu.serving.kv_cache.prefix_chain_keys`) against the
pool's content index; matched blocks are adopted refcounted into the
block table and ``pos`` starts past the shared span — the request
skips both the prefill compute and the block allocations for it. As a
sequence's own prefill crosses each full-prompt-block boundary the
block is sealed into the index for later requests.

**Speculative decoding** (``spec_k=K`` / ``$PTPU_SERVE_SPEC_K``,
docs/SERVING.md, off by default) changes what a decode step emits:
when every occupied row is past its prompt, ``plan_spec`` plans a
VERIFY window — each row feeds its last committed token plus up to
``K`` continuations proposed by a ``drafter`` (n-gram prompt lookup by
default) — and ``record_spec`` folds the materialized window back:
per-row acceptance is the longest prefix where draft == the target's
argmax, the accepted run plus the target's correction token are
emitted (>= 1 token per window, so speculation never takes more steps
than plain decoding), and the KV blocks past the rewound position are
returned through ``KVBlockPool.truncate_owner`` (rollback).
"""

import itertools
import threading
import time
from collections import deque

from ..observability import flight_recorder as _blackbox
from ..observability import metrics as _metrics
from ..observability import tracing as _tracing
from .kv_cache import blocks_needed, prefix_chain_keys

__all__ = ["AdmissionError", "DeadlineExceededError", "GenerationRequest",
           "RequestQueue", "StepScheduler", "check_request_args",
           "default_prefill_token_budget", "spec_tree_acceptance"]

_req_ids = itertools.count()

# The prompt tokens a prefill row consumes in one mixed step where the
# deployment names no size (``prefill_chunk`` / $PTPU_SERVE_PREFILL_CHUNK
# unset, None or 0), clamped to the context. 256 is what both XGLM cells
# of the benchmark run and one query tile of the chunk step's attention
# (``model.CHUNK_TILE``, settled on the chip in PR 28), so a default
# engine compiles the shape that was measured.
DEFAULT_PREFILL_CHUNK = 256

# The token rows at which a weight matmul on the served chip turns
# compute-bound. A ``[rows, d] x [d, n]`` dot over bf16 weights does
# ``rows`` FLOP a weight byte, and the v5e does 197 TFLOP/s over
# 819 GB/s = 240 FLOP a byte (Google Cloud documentation, "TPU v5e";
# observability/cost.py has the first): up to 240 rows ride on the
# weights' stream, every row past it adds its full time to the step.
# 240 rounded up to whole query tiles (``model.CHUNK_TILE``). PERF.md §5
# has the mixed step measured on either side of it.
PREFILL_BUDGET_RIDGE = 256


def default_prefill_token_budget(prefill_chunk):
    """The prompt tokens a mixed step may hold where the deployment
    names no ``prefill_token_budget``: four chunks, but no more than
    :data:`PREFILL_BUDGET_RIDGE` and never under one chunk. A budget
    past the ridge buys no prefill throughput a token, only a longer
    step for every decode row in it; what a wider step does amortise is
    the step's fixed part, and only when it is full. A caller whose
    prompts arrive in bursts that fill a wider window, or whose chunk
    step is not bound by its token rows' matmuls, states the budget."""
    return max(prefill_chunk, min(4 * prefill_chunk, PREFILL_BUDGET_RIDGE))


def spec_tree_acceptance(window, outs, width):
    """The pure host acceptance walk over ONE materialized tree verify
    window (docs/SERVING.md tree speculation). ``window`` is the
    level-order token window ``[root, level-1 slots..., ...]`` the
    scheduler planned (``width`` chains per level); ``outs[j]`` is the
    target's greedy token after window slot ``j``'s root path.

    Each chain is walked independently: level ``l``'s slot is accepted
    iff its token equals the target argmax after the previously
    accepted slot (the root for ``l == 1``). The DEEPEST accepted root
    path wins; ties resolve to the lowest chain index (at width 1 this
    is bitwise the linear prefix walk — duplicate sibling tokens
    produce identical argmax contexts, so the tie-break can never
    change the emitted tokens). Returns ``(path_slots, emitted)``:
    the winning path's window slots and its tokens plus the correction
    token (the argmax at the accepted frontier) — every window emits
    at least one sequential-greedy-identical token."""
    width = int(width)
    L = len(window)
    if L <= 1:
        return [], [int(outs[0])]
    levels = (L - 1) // width
    best_path = None
    for c in range(width):
        cur = 0
        path = []
        for lev in range(levels):
            s = 1 + lev * width + c
            if s >= L or int(window[s]) != int(outs[cur]):
                break
            path.append(s)
            cur = s
        if best_path is None or len(path) > len(best_path):
            best_path = path
    frontier = best_path[-1] if best_path else 0
    emitted = ([int(window[s]) for s in best_path]
               + [int(outs[frontier])])
    return best_path, emitted


class AdmissionError(RuntimeError):
    """Raised by submit() when the request queue is at capacity."""


class DeadlineExceededError(TimeoutError):
    """Delivered into a request whose ``deadline_s`` passed before it
    completed (docs/SERVING.md "Fleet & failover"): the scheduler fails
    the request at the next step boundary — queued or mid-batch —
    instead of letting it wait forever on a wedged stream. Counted in
    ``serving/requests_failed`` and ``serving/deadline_expired``."""


def check_request_args(prompt, max_new_tokens, deadline_s=None):
    """Shared request validation (``GenerationRequest`` and the
    router's ``RouterRequest`` — one rule set, so the two submit
    surfaces can never drift): returns the int-coerced prompt."""
    prompt = [int(t) for t in prompt]
    if not prompt:
        raise ValueError("prompt must hold at least one token")
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    if deadline_s is not None and float(deadline_s) <= 0:
        raise ValueError("deadline_s must be > 0 (got %r)"
                         % (deadline_s,))
    return prompt


class GenerationRequest:
    """One generation request plus its completion surface.

    ``stream`` (optional) is called as ``stream(request, token_id,
    finished)`` from the engine thread for every generated token, in
    order. ``wait()``/``result`` is the pull side.
    """

    def __init__(self, prompt, max_new_tokens=32, eos_id=None,
                 stream=None, model=None, deadline_s=None,
                 on_finish=None, trace_id=None):
        prompt = check_request_args(prompt, max_new_tokens, deadline_s)
        self.id = next(_req_ids)
        self.model = model
        # request-scoped tracing identity (docs/OBSERVABILITY.md):
        # minted at the submit surface when tracing is on, None
        # otherwise — the router passes ONE id through every failover
        # attempt so a re-admitted request renders as a single trace
        self.trace_id = trace_id
        self.prompt = prompt
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        self.stream = stream
        # completion hook (the router's re-admission surface): called
        # once from _finish, success or error, possibly from an engine
        # thread — it must not call back into engine locks
        self.on_finish = on_finish
        self.submit_time = time.perf_counter()
        # absolute perf_counter deadline; None = wait forever (legacy)
        self.deadline = (self.submit_time + float(deadline_s)
                         if deadline_s is not None else None)
        self.start_time = None      # admitted to the batch
        self.slot = None            # the batch row it was admitted to
        self.first_token_time = None  # first generated token materialized
        self.finish_time = None
        self.tokens = []            # generated ids (truncated at EOS)
        # each generated token's own logit as the step computed it, one
        # a token, where the model's steps hand it back
        # (`GenerationModel.returns_top_logit`); empty otherwise
        self.top_logits = []
        self.error = None
        self._done = threading.Event()

    # -- completion surface --------------------------------------------
    @property
    def finished(self):
        return self._done.is_set()

    def wait(self, timeout=None):
        """Block until the request completed; returns the generated
        token list. Raises the engine-side error, if any."""
        if not self._done.wait(timeout):
            raise TimeoutError("request %d not finished" % self.id)
        if self.error is not None:
            raise self.error
        return list(self.tokens)

    @property
    def latency(self):
        if self.finish_time is None:
            return None
        return self.finish_time - self.submit_time

    @property
    def ttft(self):
        """Time-to-first-token: submit until the first generated token
        materialized (None until then) — the latency the prefill fast
        path optimizes; ``latency`` can't see the prefill stall."""
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.submit_time

    def _finish(self, error=None):
        self.error = error
        self.finish_time = time.perf_counter()
        self._done.set()
        if self.on_finish is not None:
            try:
                self.on_finish(self)
            except Exception:
                pass  # a completion consumer must not kill the engine


class RequestQueue:
    """Bounded FIFO with fail-fast admission (the queue gate)."""

    def __init__(self, max_queue=64):
        from ..analysis.concurrency import make_lock

        self.max_queue = int(max_queue)
        self._q = deque()
        self._lock = make_lock("serving.request_queue")

    def __len__(self):
        return len(self._q)

    def submit(self, request):
        with self._lock:
            if len(self._q) >= self.max_queue:
                raise AdmissionError(
                    "request queue full (%d waiting); retry later or "
                    "raise max_queue" % len(self._q))
            self._q.append(request)
        return request

    def peek(self):
        with self._lock:
            return self._q[0] if self._q else None

    def pop(self):
        with self._lock:
            return self._q.popleft() if self._q else None

    def pop_expired(self, now):
        """Remove and return every queued request whose deadline passed
        (head-of-line order of the survivors is preserved)."""
        with self._lock:
            expired = [r for r in self._q
                       if r.deadline is not None and now >= r.deadline]
            if expired:
                dead = set(id(r) for r in expired)
                self._q = deque(r for r in self._q
                                if id(r) not in dead)
        return expired


class _RequestLog:
    """What the worker notes of ONE request while the request log
    records (`serving/request`, docs/OBSERVABILITY.md "The serving
    request log"): which step records carried it, and its counts. It
    hangs off the request's `_Sequence` and holds no clock reading of
    its own but the last emission's: every other stamp of the record is
    the request's (`submit_time`, `start_time`, `first_token_time`,
    `finish_time`) or a step record's (`t_dispatched`, `t_ready`)."""

    __slots__ = ("first_rec", "token_rec", "last_step", "prefill_steps",
                 "deferred_steps", "cold", "gaps", "gaps_mixed",
                 "gap_max_ms", "gap_max_kind", "t_emitted")

    def __init__(self):
        self.first_rec = None    # record of the first step with its prompt
        self.token_rec = None    # ... of the step that ends its prompt
        self.last_step = None    # `step` of the one that gave its last token
        self.prefill_steps = self.deferred_steps = 0
        self.cold = False
        self.gaps = self.gaps_mixed = 0
        self.gap_max_ms = self.gap_max_kind = None
        self.t_emitted = None

    def dispatched(self, rec, prefill, gen_idx):
        """The step of `rec` carries a row of this request: `prefill`,
        prompt tokens of it; `gen_idx == 0`, its last one."""
        if rec["cold"]:
            self.cold = True
        if prefill:
            self.prefill_steps += 1
            if self.first_rec is None:
                self.first_rec = rec
            if gen_idx == 0:
                self.token_rec = rec

    def emitted(self, rec, now, n=1):
        """The step of `rec` gave `n` tokens of this request (a verify
        window may give several), recorded at `now`."""
        if self.t_emitted is None:
            n -= 1                       # the first token ends no gap
        else:
            gap_ms = (now - self.t_emitted) * 1e3
            if self.gap_max_ms is None or gap_ms > self.gap_max_ms:
                self.gap_max_ms, self.gap_max_kind = gap_ms, rec["kind"]
        self.gaps += n
        if rec["kind"] == "mixed":
            self.gaps_mixed += n
        self.t_emitted = now
        self.last_step = rec["step"]


class _Sequence:
    """Scheduler-internal per-slot decode state."""

    __slots__ = ("request", "slot", "admitted", "pos", "n_dispatched",
                 "pending", "finished", "dispatch_done", "prefix_keys",
                 "sealed_upto", "log")

    def __init__(self, request, slot, admitted):
        self.request = request
        self.slot = slot
        self.admitted = admitted  # the scheduler's admission ordinal
        self.pos = 0             # position of the NEXT token to process
        self.n_dispatched = 0    # generated tokens dispatched so far
        self.pending = 0         # dispatched steps not yet processed
        self.finished = False    # result delivered (EOS/max/seq-cap)
        self.dispatch_done = False  # no more steps will be dispatched
        self.prefix_keys = ()    # content keys of the prompt's full blocks
        self.sealed_upto = 0     # prompt blocks already in the pool index
        self.log = None          # its _RequestLog while the log records

    @property
    def in_prefill(self):
        return self.pos < len(self.request.prompt)


class StepScheduler:
    """Joins/retires sequences at step boundaries over fixed slots.

    The engine drives it:  ``admit()`` → ``plan_step()`` → dispatch →
    (lagged) ``record_token()`` per decode output → ``reap()``.
    """

    def __init__(self, max_batch, pool, max_seq_len, prefill_chunk=None,
                 prefix_cache=False, prefill_token_budget=None,
                 cache_namespace="", spec_k=0, drafter=None,
                 spec_tree=None):
        import numpy as np

        self.max_batch = int(max_batch)
        self.pool = pool
        self.max_seq_len = int(max_seq_len)
        self.slots = [None] * self.max_batch
        # persistent step-input arrays (host side, fixed shapes)
        self._np = np
        mb = blocks_needed(self.max_seq_len, pool.block_size)
        self.max_blocks_per_seq = mb
        # one block table a page KIND (kv_cache.PageKind), each indexed
        # by the logical page; `block_tables` is the first kind's (the
        # only one of most models), a view of the stack
        self.kind_tables = np.zeros(
            (len(pool.kinds), self.max_batch, mb), np.int32)
        self.block_tables = self.kind_tables[0]
        self.use_prompt = np.zeros(self.max_batch, bool)
        self.positions = np.zeros(self.max_batch, np.int32)
        self.active = np.zeros(self.max_batch, bool)
        # the chunk is a compiled shape, so it is clamped to the
        # context; the per-step token budget (stated, or the rule's:
        # four chunks up to the matmuls' ridge) bounds how much prefill
        # compute a MIXED step carries alongside decode rows: the
        # decode-latency bound, and the token rows the engine compiles
        # the chunk step for
        self.prefill_chunk = min(
            max(0, int(prefill_chunk or 0)) or DEFAULT_PREFILL_CHUNK,
            self.max_seq_len)
        self.prefill_token_budget = max(1, int(
            default_prefill_token_budget(self.prefill_chunk)
            if prefill_token_budget is None else prefill_token_budget))
        self._admissions = itertools.count()
        # prefilling rows the last planned step gave no token for want
        # of budget (the step log's `rows_deferred`)
        self.rows_deferred = 0
        # (request, its _RequestLog, outcome) of the requests that left
        # since the worker last wrote the request log; filled only
        # while that log records (`_note_departed`)
        self.departed = []
        self.chunk_feed = np.zeros(
            (self.max_batch, self.prefill_chunk), np.int32)
        self.chunk_lens = np.zeros(self.max_batch, np.int32)
        # a window kind's pages are released as a row's positions slide
        # out, so a row never holds more of them than the window, the
        # chunk in flight and one block's slack
        self.kind_max_blocks = [
            mb if k.window is None else min(mb, blocks_needed(
                k.window - 1 + self.prefill_chunk, pool.block_size) + 1)
            for k in pool.kinds]
        self.prefix_cache = bool(prefix_cache)
        self.cache_namespace = str(cache_namespace)
        # host-side reuse telemetry (live even with metrics disabled —
        # engine.stats()/bench read these)
        self.prefix_blocks_reused = 0
        self.prefix_tokens_skipped = 0
        # -- speculative decoding (docs/SERVING.md; off by default)
        from .model import parse_tree_shape

        self.spec_tree = parse_tree_shape(spec_tree)
        self.spec_k = max(0, int(spec_k or 0))
        if self.spec_tree and not self.spec_k:
            # tree shape implies speculation: depth plays spec_k's role
            # in every `if self.spec_k` gate
            self.spec_k = self.spec_tree[1]
        self.drafter = drafter
        # host-side spec telemetry (live even with metrics disabled —
        # engine.stats()/bench read these). In tree mode spec_proposed/
        # spec_accepted count PATH DEPTH (deepest branch fed / accepted
        # path length) so accept_rate keeps its per-chain meaning;
        # spec_tree_slots counts every draft slot verified.
        self.spec_steps = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_emitted = 0
        self.spec_blocks_rolled_back = 0
        self.spec_tree_slots = 0
        # host-side deadline telemetry (live even with metrics disabled)
        self.deadline_expired = 0
        if self.spec_k:
            width = (1 + self.spec_tree[0] * self.spec_tree[1]
                     if self.spec_tree else self.spec_k + 1)
            self.spec_feed = np.zeros((self.max_batch, width), np.int32)
            self.spec_lens = np.zeros(self.max_batch, np.int32)

    # -- occupancy ------------------------------------------------------
    @property
    def num_active(self):
        return sum(1 for s in self.slots
                   if s is not None and not s.dispatch_done)

    @property
    def num_occupied(self):
        return sum(1 for s in self.slots if s is not None)

    def has_work(self):
        return any(s is not None for s in self.slots)

    # -- admission (step boundary) -------------------------------------
    def _budget_for(self, request):
        total = min(len(request.prompt) + request.max_new_tokens,
                    self.max_seq_len)
        if self.spec_tree:
            # tree windows write KV up to C - 1 = W*D slots past the
            # committed end (rejected sibling branches at higher window
            # offsets than the linear clamp ever reaches), so the
            # admission reservation carries that overhang — a
            # mid-flight window can then never exhaust the pool. The
            # per-row depth clamp keeps every write < max_seq_len, so
            # the cap here matches it.
            total = min(total + self.spec_tree[0] * self.spec_tree[1],
                        self.max_seq_len)
        n = blocks_needed(total, self.pool.block_size)
        if len(self.pool.kinds) == 1:
            return n
        return tuple(min(n, cap) for cap in self.kind_max_blocks)

    def admit(self, queue):
        """Move queued requests into free slots while the KV pool can
        cover their reservations (head-of-line order). Returns the list
        of admitted sequences."""
        admitted = []
        for slot in range(self.max_batch):
            if self.slots[slot] is not None:
                continue
            request = queue.peek()
            if request is None:
                break
            if len(request.prompt) >= self.max_seq_len:
                queue.pop()
                request._finish(ValueError(
                    "prompt length %d >= engine max_seq_len %d"
                    % (len(request.prompt), self.max_seq_len)))
                _metrics.counter("serving/requests_failed").inc()
                self._note_departed(request, None, "failed")
                continue
            seq = _Sequence(request, slot, next(self._admissions))
            keys = ()
            if self.prefix_cache:
                # longest-prefix-match candidates: every full prompt
                # block EXCEPT one covering the final prompt token — at
                # least one prompt token must still be processed so the
                # first generated token has logits to come from
                bs = self.pool.block_size
                shareable = ((len(request.prompt) - 1) // bs) * bs
                keys = prefix_chain_keys(request.prompt[:shareable], bs,
                                         namespace=self.cache_namespace)
            if not self.pool.reserve(seq, self._budget_for(request),
                                     prefix_keys=keys or None):
                break  # KV gate: head doesn't fit — keep queue order
            queue.pop()
            request.start_time = time.perf_counter()
            request.slot = slot
            if _metrics.enabled():
                seq.log = _RequestLog()
            if request.trace_id is not None and _tracing.enabled():
                # retroactive queue_wait span (submit -> admission: the
                # request log's `t_submit`, `t_admit`) plus an admit
                # marker carrying the slot the request landed in
                _tracing.complete(
                    "queue_wait", int(request.submit_time * 1e9),
                    int(request.start_time * 1e9),
                    trace_id=request.trace_id, request=request.id)
                _tracing.instant("admit", trace_id=request.trace_id,
                                 request=request.id, slot=slot)
            self.slots[slot] = seq
            self.kind_tables[:, slot, :] = self.pool.NULL_BLOCK
            seq.prefix_keys = tuple(keys)
            matched = self.pool.block_table(seq)
            if matched:
                # adopted shared blocks: skip their prefill compute and
                # allocations — decoding starts past the shared span
                self.block_tables[slot, :len(matched)] = matched
                seq.pos = len(matched) * self.pool.block_size
                seq.sealed_upto = len(matched)
                self.prefix_blocks_reused += len(matched)
                self.prefix_tokens_skipped += (len(matched)
                                               * self.pool.block_size)
                _metrics.counter("serving/prefix_blocks_reused").inc(
                    len(matched))
                _metrics.counter("serving/prefix_tokens_skipped").inc(
                    len(matched) * self.pool.block_size)
            self.positions[slot] = seq.pos
            self.active[slot] = True
            admitted.append(seq)
        return admitted

    def _seal_ready(self, slot, seq):
        """Seal every fully-written full-prompt block (its content is
        now fixed: prefill has advanced past it) into the pool's
        content index so later admissions can adopt it."""
        bs = self.pool.block_size
        done = min(seq.pos, len(seq.request.prompt)) // bs
        limit = min(done, len(seq.prefix_keys))
        while seq.sealed_upto < limit:
            i = seq.sealed_upto
            self.pool.seal_block(int(self.block_tables[slot, i]),
                                 seq.prefix_keys[i])
            seq.sealed_upto += 1

    # -- step planning --------------------------------------------------
    def plan_step(self):
        """Fill the fixed window arrays for the next step and return
        ``(plan, kind)``. The plan is a list of
        ``(seq, generated_index | None)`` rows, one per dispatching
        slot (``None`` while the slot is still consuming its prompt);
        ``kind`` says which compiled program the window is for:
        ``"mixed"`` whenever an active row is mid-prompt, ``"decode"``
        otherwise (every row a window of one, no prompt token fed).

        Prefill rows consume up to ``prefill_chunk`` prompt tokens
        (bounded further by ``prefill_token_budget`` across rows, in
        the order the rows were admitted; rows past the budget sit the
        step out and ``rows_deferred`` counts them), decode rows one
        token chained on the device."""
        prefilling = sorted(
            (s for s in self.slots
             if s is not None and not s.dispatch_done and s.in_prefill),
            key=lambda s: s.admitted)
        kind = "mixed" if prefilling else "decode"
        bs = self.pool.block_size
        # a prefill row takes what the step's budget still holds once
        # the rows admitted before it have taken theirs; where that is
        # nothing it sits the step out, so that decode rows' latency
        # stays bounded, and resumes once the prompts ahead of it are
        # through
        budget = self.prefill_token_budget
        granted = {}
        for seq in prefilling:
            n = min(self.prefill_chunk,
                    len(seq.request.prompt) - seq.pos, budget)
            granted[seq.slot] = n
            budget -= n
            if not n and seq.log is not None:
                seq.log.deferred_steps += 1
        self.rows_deferred = sum(not n for n in granted.values())
        plan = []
        for slot, seq in enumerate(self.slots):
            n = 0
            if seq is not None and not seq.dispatch_done:
                prefill = seq.in_prefill
                pos, prompt = seq.pos, seq.request.prompt
                n = granted[slot] if prefill else 1
            if not n:
                self.active[slot] = False
                self.use_prompt[slot] = False
                self.chunk_lens[slot] = 0
                continue
            if prefill:
                self.chunk_feed[slot, :n] = prompt[pos:pos + n]
                # the window consuming the LAST prompt token emits the
                # first generated token
                gen_idx = 0 if pos + n == len(prompt) else None
            else:
                gen_idx = seq.n_dispatched
            self.use_prompt[slot] = prefill
            if len(self.pool.kinds) > 1:
                self._release_slid_out(slot, seq, pos)
            # lazy block allocation for EVERY boundary the window
            # crosses (drawn from the admission-time reservation, so it
            # cannot fail), a page of every kind
            for p in range(pos, pos + n):
                if p % bs == 0:
                    for k, table in enumerate(self.kind_tables):
                        table[slot, p // bs] = self.pool.alloc_block(
                            seq, k)
            self.positions[slot] = pos
            self.chunk_lens[slot] = n
            self.active[slot] = True
            if gen_idx is not None:
                seq.n_dispatched = gen_idx + 1
            seq.pos = pos + n
            seq.pending += 1
            plan.append((seq, gen_idx))
            if (seq.n_dispatched >= seq.request.max_new_tokens
                    or seq.pos >= self.max_seq_len):
                seq.dispatch_done = True
            if seq.prefix_keys:
                self._seal_ready(slot, seq)
        return plan, kind

    def _release_slid_out(self, slot, seq, pos):
        """Before a step whose earliest query of this row is at ``pos``:
        hand back the row's window-kind pages no query at or past
        ``pos`` reads, and point their table entries at the null block.
        Steps already dispatched carry the tables they were planned
        with, and the device runs steps in order, so a page released
        here is rewritten only after its last reader."""
        bs = self.pool.block_size
        for kind, page_kind in enumerate(self.pool.kinds):
            if page_kind.window is None:
                continue
            head = self.pool.pages_released(seq, kind)
            live = page_kind.first_live_page(pos, bs)
            if live <= head:
                continue
            with _tracing.annotation("ptpu/scheduler.release_window_pages"):
                n = len(self.pool.release_head(seq, kind, live))
                self.kind_tables[kind, slot, head:head + n] = \
                    self.pool.NULL_BLOCK

    def plan_spec(self):
        """Speculative verify-window planning (docs/SERVING.md).

        Applies only when every occupied row is past its prompt with no
        step still in flight — the engine materializes every window
        before planning the next, because both acceptance and the next
        window's drafts read the committed token history — and returns
        ``None`` otherwise so the engine falls back to the
        prefill/decode plan. When it applies, fills the
        ``spec_feed``/``spec_lens`` window arrays: each dispatching row
        feeds its last committed token plus up to ``spec_k`` drafted
        continuations (clamped so no window can overshoot
        ``max_new_tokens`` or the sequence cap — the admission-time
        reservation therefore always covers the window's block
        allocations) and returns the spec plan, a list of
        ``(seq, window_tokens)`` rows."""
        if not self.spec_k:
            return None
        for seq in self.slots:
            if seq is None:
                continue
            if seq.pending or (not seq.dispatch_done and seq.in_prefill):
                return None
        if self.spec_tree:
            return self._plan_spec_tree()
        bs = self.pool.block_size
        # batched drafting: a drafter with propose_batch (the jitted
        # ModelDrafter) drafts every row in a constant number of device
        # steps before the per-row window assembly below
        batch_drafts = None
        if (self.drafter is not None
                and hasattr(self.drafter, "propose_batch")):
            rows = []
            for seq in self.slots:
                if seq is None or seq.dispatch_done:
                    continue
                request = seq.request
                limit = min(self.spec_k + 1,
                            request.max_new_tokens - len(request.tokens),
                            self.max_seq_len - seq.pos)
                if limit > 1:
                    rows.append((request.id,
                                 request.prompt + request.tokens))
            if rows:
                batch_drafts = self.drafter.propose_batch(
                    rows, self.spec_k)
        plan = []
        for slot, seq in enumerate(self.slots):
            if seq is None or seq.dispatch_done:
                self.active[slot] = False
                self.use_prompt[slot] = False
                self.spec_lens[slot] = 0
                continue
            request = seq.request
            pos = seq.pos
            history = request.prompt + request.tokens
            if pos != len(history) - 1:
                raise RuntimeError(
                    "spec window planned at pos %d but the committed "
                    "history holds %d tokens — a step result was lost"
                    % (pos, len(history)))
            # every emitted token consumes one max_new slot and one
            # sequence position; >= 1 here (else dispatch_done already)
            limit = min(self.spec_k + 1,
                        request.max_new_tokens - len(request.tokens),
                        self.max_seq_len - pos)
            drafts = []
            if limit > 1 and self.drafter is not None:
                if batch_drafts is not None:
                    drafts = batch_drafts.get(request.id, [])
                elif hasattr(self.drafter, "propose_for"):
                    # memoized n-gram path: identical tokens, O(k) host
                    # cost per window via the per-sequence suffix index
                    drafts = self.drafter.propose_for(
                        request.id, history, limit - 1)
                else:
                    drafts = self.drafter.propose(history, limit - 1)
                drafts = [int(t) for t in drafts][:limit - 1]
            window = [history[-1]] + drafts
            # lazy block allocation for EVERY boundary the window
            # crosses (drawn from the admission-time reservation; the
            # window clamp above keeps it within the worst case)
            for p in range(pos, pos + len(window)):
                if p % bs == 0:
                    bid = self.pool.alloc_block(seq)
                    self.block_tables[slot, p // bs] = bid
            self.spec_feed[slot, :len(window)] = window
            self.spec_lens[slot] = len(window)
            self.positions[slot] = pos
            self.use_prompt[slot] = True
            self.active[slot] = True
            seq.pending += 1
            plan.append((seq, window))
        if plan:
            self.spec_steps += 1
            _metrics.counter("serving/spec_steps").inc()
        return plan

    def _plan_spec_tree(self):
        """Tree verify-window planning (docs/SERVING.md tree
        speculation): each dispatching row feeds a LEVEL-ORDER token
        tree ``[root, level-1 slots..., level-2 slots...]`` of up to
        ``width`` chains and a per-row depth clamped so the emitted
        path can never overshoot ``max_new_tokens`` and no window slot
        can ever write at or past the sequence cap. Chains shorter than
        the row's depth pad their missing slots with token 0 — sound
        under verify-based acceptance (a pad is just a draft that will
        not match the target argmax). Rows whose drafter proposes
        nothing (or whose clamp hits 0) ride as 1-slot windows — plain
        decode through the tree step, so tree mode never takes more
        steps than plain decoding. Returns the spec plan
        ``[(seq, window_tokens), ...]``."""
        bs = self.pool.block_size
        W, D = self.spec_tree
        rows = []
        for slot, seq in enumerate(self.slots):
            if seq is None or seq.dispatch_done:
                self.active[slot] = False
                self.use_prompt[slot] = False
                self.spec_lens[slot] = 0
                continue
            request = seq.request
            history = request.prompt + request.tokens
            if seq.pos != len(history) - 1:
                raise RuntimeError(
                    "spec window planned at pos %d but the committed "
                    "history holds %d tokens — a step result was lost"
                    % (seq.pos, len(history)))
            # depth clamp: path emission (depth + correction) within
            # the max_new budget, every window slot (pos + 1 .. pos +
            # W*d) strictly below the sequence cap
            d = min(D, request.max_new_tokens - len(request.tokens) - 1,
                    (self.max_seq_len - seq.pos - 1) // W)
            rows.append((slot, seq, history, max(d, 0)))
        # draft pass — batched when the drafter supports it (the jitted
        # ModelDrafter), per-row tree/linear proposals otherwise
        chains_by_slot = {}
        drafter = self.drafter
        need = [r for r in rows if r[3] > 0] if drafter is not None \
            else []
        if need and hasattr(drafter, "propose_tree_batch"):
            got = drafter.propose_tree_batch(
                [(seq.request.id, h, d) for _s, seq, h, d in need], W)
            for slot, seq, _h, _d in need:
                chains_by_slot[slot] = got.get(seq.request.id, [])
        elif need and hasattr(drafter, "propose_tree"):
            for slot, seq, h, d in need:
                chains_by_slot[slot] = drafter.propose_tree(
                    h, W, d, seq_id=seq.request.id)
        elif need:
            for slot, seq, h, d in need:
                chains_by_slot[slot] = [list(drafter.propose(h, d))]
        plan = []
        for slot, seq, history, d in rows:
            chains = [[int(t) for t in ch][:d]
                      for ch in chains_by_slot.get(slot, [])][:W]
            chains = [ch for ch in chains if ch]
            d_used = max((len(ch) for ch in chains), default=0)
            window = [history[-1]]
            for lev in range(d_used):
                for c in range(W):
                    ch = chains[c] if c < len(chains) else []
                    window.append(ch[lev] if lev < len(ch) else 0)
            pos = seq.pos
            # lazy block allocation for EVERY boundary the window
            # crosses (drawn from the admission-time reservation — the
            # _budget_for tree overhang covers the worst case)
            for p in range(pos, pos + len(window)):
                if p % bs == 0:
                    bid = self.pool.alloc_block(seq)
                    self.block_tables[slot, p // bs] = bid
            self.spec_feed[slot, :len(window)] = window
            self.spec_lens[slot] = len(window)
            self.positions[slot] = pos
            self.use_prompt[slot] = True
            self.active[slot] = True
            seq.pending += 1
            plan.append((seq, window))
        if plan:
            self.spec_steps += 1
            _metrics.counter("serving/spec_steps").inc()
        return plan

    def record_spec(self, seq, window, outs):
        """Fold one materialized verify window back into its sequence:
        acceptance is the longest prefix where draft == the target's
        argmax at the previous slot; the accepted run plus the target's
        correction token are emitted in order (>= 1 token per window,
        truncated at EOS / ``max_new_tokens`` / the sequence cap — no
        post-EOS token is ever emitted), then the sequence rewinds to
        its first unverified position and the over-allocated KV blocks
        go back through ``KVBlockPool.truncate_owner`` (rollback).
        Returns the number of tokens emitted."""
        seq.pending -= 1
        request = seq.request
        if seq.finished:
            return 0
        drafts = [int(t) for t in window[1:]]
        m = 0
        while m < len(drafts) and drafts[m] == int(outs[m]):
            m += 1
        emitted = drafts[:m] + [int(outs[m])]
        self.spec_proposed += len(drafts)
        self.spec_accepted += m
        _metrics.counter("serving/spec_proposed").inc(len(drafts))
        _metrics.counter("serving/spec_accepted").inc(m)
        _metrics.counter("serving/spec_rejected").inc(len(drafts) - m)
        return self._emit_spec(seq, emitted)

    def record_spec_tree(self, seq, window, path_slots, emitted):
        """Fold one materialized TREE verify window back into its
        sequence: the engine has already run the host acceptance walk
        (:func:`spec_tree_acceptance` -> ``path_slots``, ``emitted``)
        and compacted the accepted path's KV into the committed slot
        layout, so this is the bookkeeping half — emission with the
        same EOS/``max_new``/sequence-cap finality as ``record_spec``,
        position advance, and reservation-restoring KV rollback of
        every rejected branch. ``spec_proposed``/``spec_accepted``
        count path DEPTH (deepest branch fed / accepted path length) so
        the accept-rate gauge keeps its per-chain meaning;
        ``spec_tree_slots`` counts every draft slot verified. Returns
        the number of tokens emitted."""
        seq.pending -= 1
        if seq.finished:
            return 0
        W = self.spec_tree[0]
        n_slots = len(window) - 1
        depth_fed = n_slots // W            # full levels by construction
        m = len(path_slots)
        self.spec_proposed += depth_fed
        self.spec_accepted += m
        self.spec_tree_slots += n_slots
        _metrics.counter("serving/spec_proposed").inc(depth_fed)
        _metrics.counter("serving/spec_accepted").inc(m)
        _metrics.counter("serving/spec_rejected").inc(depth_fed - m)
        _metrics.counter("serving/spec_tree_slots").inc(n_slots)
        return self._emit_spec(seq, emitted)

    def _emit_spec(self, seq, emitted):
        """The shared emission half of ``record_spec`` /
        ``record_spec_tree``: emit the accepted run + correction token
        in order (>= 1 token per window, truncated at EOS /
        ``max_new_tokens`` / the sequence cap — no post-EOS token is
        ever emitted), advance the sequence to its first unverified
        position, and return the over-allocated KV blocks through
        ``KVBlockPool.truncate_owner`` (rollback)."""
        request = seq.request
        pos = seq.pos
        n_emit = 0
        for tok in emitted:
            request.tokens.append(tok)
            n_emit += 1
            if request.first_token_time is None:
                request.first_token_time = time.perf_counter()
            hit_eos = (request.eos_id is not None
                       and tok == request.eos_id)
            final = (hit_eos
                     or len(request.tokens) >= request.max_new_tokens
                     or pos + n_emit >= self.max_seq_len)
            if request.stream is not None:
                try:
                    request.stream(request, tok, bool(final))
                except Exception:
                    pass  # a streaming consumer must not kill the engine
            if final:
                # EOS inside an accepted run: the remaining accepted
                # drafts and the correction token are DISCARDED here,
                # never emitted; their KV writes are rolled back below
                seq.finished = True
                seq.dispatch_done = True
                request._finish()
                self._note_departed(request, seq, "finished")
                break
        seq.pos = pos + n_emit
        seq.n_dispatched = len(request.tokens)
        if (len(request.tokens) >= request.max_new_tokens
                or seq.pos >= self.max_seq_len):
            seq.dispatch_done = True
        # KV rollback: blocks past the last verified/committed position
        # return to the pool (and the table re-points at the null block)
        keep = blocks_needed(seq.pos, self.pool.block_size)
        dropped = self.pool.truncate_owner(seq, keep)
        if dropped:
            self.spec_blocks_rolled_back += len(dropped)
            self.block_tables[seq.slot, keep:keep + len(dropped)] = \
                self.pool.NULL_BLOCK
        self.spec_emitted += n_emit
        return n_emit

    # -- lagged result processing --------------------------------------
    def record_token(self, seq, gen_idx, token, top_logit=None):
        """Fold one materialized decode output back into its sequence
        (called in dispatch order — possibly several steps after the
        dispatch, under the async window). ``top_logit``: the token's
        logit, where the step returned it."""
        seq.pending -= 1
        if gen_idx is None or seq.finished:
            return
        request = seq.request
        if len(request.tokens) != gen_idx:
            # a later step of a sequence that already hit EOS — the
            # overshoot tokens are dropped
            return
        request.tokens.append(int(token))
        if top_logit is not None:
            request.top_logits.append(float(top_logit))
        if len(request.tokens) == 1:
            request.first_token_time = time.perf_counter()
        hit_eos = (request.eos_id is not None
                   and int(token) == request.eos_id)
        final = (hit_eos
                 or len(request.tokens) >= request.max_new_tokens
                 or (seq.dispatch_done
                     and gen_idx == seq.n_dispatched - 1))
        if request.stream is not None:
            try:
                request.stream(request, int(token), bool(final))
            except Exception:
                pass  # a streaming consumer must not kill the engine
        if final:
            seq.finished = True
            seq.dispatch_done = True
            request._finish()
            self._note_departed(request, seq, "finished")

    def _note_departed(self, request, seq, outcome):
        """`request` has left with `outcome` (``finished``, ``failed``,
        ``expired``); `seq` is None where it never reached a slot, and
        nothing was noted of it. While the request log records it is
        kept in `departed`, with what the worker noted of it, until the
        worker writes the log at the end of its tick; otherwise nothing
        is."""
        if seq is None:
            if _metrics.enabled():
                self.departed.append((request, _RequestLog(), outcome))
        elif seq.log is not None:
            self.departed.append((request, seq.log, outcome))

    def expire_deadlines(self, queue, now=None):
        """Fail every request whose deadline passed — queued requests
        leave the queue immediately; mid-batch sequences stop
        dispatching and retire through the normal ``reap`` path once
        their in-flight steps drain, so the KV pool accounting stays
        exactly the retirement path's. Called by the engine at step
        boundaries (only when a live request actually carries a
        deadline — the deadline-free engine path is untouched).
        Returns the number of requests expired."""
        if now is None:
            now = time.perf_counter()
        expired = 0
        for request in queue.pop_expired(now):
            request._finish(DeadlineExceededError(
                "request %d exceeded its deadline while queued "
                "(waited %.3fs)" % (request.id,
                                    now - request.submit_time)))
            self._note_expired(request, "queued")
            self._note_departed(request, None, "expired")
            expired += 1
        for seq in self.slots:
            if seq is None or seq.finished:
                continue
            deadline = seq.request.deadline
            if deadline is None or now < deadline:
                continue
            seq.finished = True
            seq.dispatch_done = True
            seq.request._finish(DeadlineExceededError(
                "request %d exceeded its deadline mid-generation "
                "(%d/%d tokens emitted)"
                % (seq.request.id, len(seq.request.tokens),
                   seq.request.max_new_tokens)))
            self._note_expired(seq.request, "mid_generation")
            self._note_departed(seq.request, seq, "expired")
            expired += 1
        if expired:
            self.deadline_expired += expired
            _metrics.counter("serving/requests_failed").inc(expired)
            _metrics.counter("serving/deadline_expired").inc(expired)
        return expired

    @staticmethod
    def _note_expired(request, where):
        """Trace marker + flight-recorder event for one expired request
        (both no-ops on the defaults-off path)."""
        if request.trace_id is not None and _tracing.enabled():
            _tracing.instant("deadline_expired", trace_id=request.trace_id,
                             request=request.id, where=where)
        _blackbox.record_event("deadline_expired", request=request.id,
                               where=where)

    def reap(self):
        """Retire slots whose sequence is complete AND fully drained
        (no in-flight step still scatters into their blocks). Returns
        the number of freed slots."""
        freed = 0
        for slot, seq in enumerate(self.slots):
            if seq is None or seq.pending:
                continue
            if seq.dispatch_done and not seq.finished:
                # ran out of budget (max_new/max_seq) without EOS
                seq.finished = True
                seq.request._finish()
                self._note_departed(seq.request, seq, "finished")
            if seq.finished:
                self.pool.free_owner(seq)
                self._release_draft_state(seq)
                self.slots[slot] = None
                self.active[slot] = False
                freed += 1
        return freed

    def _release_draft_state(self, seq):
        """Drop the drafter's per-sequence state (draft KV blocks /
        memoized suffix index) when its sequence retires."""
        if self.drafter is not None and hasattr(self.drafter, "release"):
            self.drafter.release(seq.request.id)

    def fail_all(self, error):
        """Engine-fatal path: deliver `error` to every occupied slot and
        free its blocks."""
        for slot, seq in enumerate(self.slots):
            if seq is None:
                continue
            self.pool.free_owner(seq)
            self._release_draft_state(seq)
            if not seq.request.finished:
                seq.request._finish(error)
                _metrics.counter("serving/requests_failed").inc()
                self._note_departed(seq.request, seq, "failed")
            self.slots[slot] = None
            self.active[slot] = False
