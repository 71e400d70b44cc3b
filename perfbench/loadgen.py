"""The benchmark's traffic: one general generator over data files.

A traffic mix is a file ``perfbench/traffic/<mix>.json`` of parameters;
this module turns it and ``--seed`` into the inputs the program gets.
The program receives only those inputs: token ids, a token budget, and
for a server the moment each request is due.

Steadiness rule: every seed gets the same work. Lengths and
inter-arrival gaps are the evenly spaced quantiles of their
distribution (a fixed multiset for a given count) in one fixed order:
the schedule (who arrives when, how long) is part of the cell, and
``--seed`` draws the token ids (and the weights). It is a replayed
quantile schedule, not a Poisson draw: no clusters or long gaps come by
chance.
Seeded orders were measured first (PR 23, doc-steady, 28 requests a
window): one seed repeated its median time to first token within 0.2 %,
six seeds spread it by 6-10 %, because with tens of requests in a
window the order of arrivals decides the tails.

Kinds:

``token_stream``  training batches ``[batch, seq_len + 1]`` of uniform
                  token ids, an endless deterministic sequence: batch
                  ``i`` depends on (seed, i) only.
``open_loop``     requests due at fixed times whether or not earlier
                  ones finished: ``rate_per_s * seconds`` requests whose
                  gaps are the quantiles of an exponential
                  distribution, scaled so the last is due inside the
                  window.
``closed_loop``   ``clients`` callers, each sending its next request
                  when its last finished; requests are dealt from one
                  list in order.
"""

import collections
import statistics

import numpy as np

Request = collections.namedtuple(
    "Request", ["index", "due_s", "prompt", "max_new_tokens"])

CLOSED_LOOP_CYCLE = 256   # distinct length pairs before the list repeats
ORDER_SEED = 0            # the one order every mix's schedule is dealt in
WARMUP_REQUESTS = 2       # the longest prompt and half of it


def rng_for(seed, stream):
    """A generator for one named stream of one seed (seeds may exceed
    32 bits; ``stream`` keeps lengths, gaps and tokens independent)."""
    return np.random.default_rng([int(seed), int(stream)])


def _norm_ppf(u):
    return np.array([statistics.NormalDist().inv_cdf(float(x)) for x in u])


def length_quantiles(spec, n):
    """``n`` lengths at the evenly spaced quantiles of ``spec``:
    ``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
    ``{"dist": "uniform", "min", "max"}``. Sorted ascending; the caller
    permutes."""
    u = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    elif dist == "lognormal":
        x = spec["median"] * np.exp(spec["sigma"] * _norm_ppf(u))
    else:
        raise ValueError("unknown length distribution %r" % (dist,))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def gap_quantiles(n, total_s):
    """``n`` inter-arrival gaps summing to ``total_s``: the evenly
    spaced quantiles of an exponential, rescaled to the window."""
    g = -np.log1p(-(np.arange(n) + 0.5) / n)
    return g * (total_s / g.sum())


def _requests(seed, mix, n, due, vocab_size):
    prompts = rng_for(ORDER_SEED, 1).permutation(
        length_quantiles(mix["prompt_len"], n))
    outputs = rng_for(ORDER_SEED, 2).permutation(
        length_quantiles(mix["output_len"], n))
    tok = rng_for(seed, 3)
    return [Request(i, float(due[i]),
                    tok.integers(0, vocab_size, int(prompts[i]),
                                 dtype=np.int32),
                    int(outputs[i]))
            for i in range(n)]


def open_loop(seed, mix, seconds, vocab_size):
    """The requests due inside a window of ``seconds``; the first is
    due after the first gap, the last just before the window closes."""
    n = max(1, int(round(mix["rate_per_s"] * seconds)))
    gaps = rng_for(ORDER_SEED, 0).permutation(
        gap_quantiles(n, float(seconds)))
    due = np.cumsum(gaps) * (1.0 - 0.5 / n)   # last due inside the window
    return _requests(seed, mix, n, due, vocab_size)


def closed_loop(seed, mix, vocab_size):
    """The list the clients deal from (``due_s`` is 0: a request is due
    when a client is free). The runner cycles it if a window outlasts
    it; CLOSED_LOOP_CYCLE pairs are far more than any window finishes."""
    n = CLOSED_LOOP_CYCLE
    return _requests(seed, mix, n, np.zeros(n), vocab_size)


def warmup_requests(mix, vocab_size):
    """Fixed requests for set-up: they cover the mix's longest prompt
    (every prefill shape) and a few decode steps. Not from the seed:
    set-up does the same work in every run."""
    tok = np.random.default_rng(12345)
    longest = int(length_quantiles(mix["prompt_len"], 64).max())
    return [Request(-1 - i, 0.0,
                    tok.integers(0, vocab_size, max(1, longest >> i),
                                 dtype=np.int32), 8)
            for i in range(WARMUP_REQUESTS)]


def token_batch(seed, index, batch, seq_len, vocab_size):
    """Training batch ``index`` of a seed: (tokens, labels), int32
    ``[batch, seq_len]``, labels the next token. All rows differ."""
    rng = np.random.default_rng([int(seed), 4, int(index)])
    t = rng.integers(0, vocab_size, (batch, seq_len + 1), dtype=np.int32)
    return t[:, :-1].copy(), t[:, 1:].copy()


def percentile(values, q):
    """The q-th percentile (0..100) of raw samples, linear between
    order statistics; None for no samples."""
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


SLOW_GAP_FACTOR = 2.0     # a gap this many medians long held a slow step


def slow_gap_share(gaps):
    """The share of token gaps longer than ``SLOW_GAP_FACTOR`` times
    the run's median gap: with a server whose decode step is a fifth of
    its mixed step, the gaps that held a prefill chunk (or a stall).
    None for no gaps."""
    if not len(gaps):
        return None
    g = np.asarray(gaps, np.float64)
    return float(np.mean(g > SLOW_GAP_FACTOR * np.median(g)))


def percentile_is_clear(shares, q):
    """May the ``q``-th percentile (0..100) of a cell's token gaps be
    judged? Only where it sits on one kind of step in every run: each
    run's ``slow_gap_share`` stands a factor of two or more from the
    ``1 - q/100`` of gaps beyond the percentile, all on the same side.
    Between the two the percentile is interpolated across the cliff
    from a decode step to a mixed step, and two or three gaps move it
    by milliseconds (PERF.md section 2)."""
    beyond = (100.0 - q) / 100.0
    shares = [round(float(s), 9) for s in shares]   # 0.1 is 2 x 0.05
    return bool(shares) and (
        all(s >= round(2.0 * beyond, 9) for s in shares)
        or all(s <= round(beyond / 2.0, 9) for s in shares))


def samples_beyond(n, q):
    """How many of ``n`` samples lie beyond the ``q``-th percentile
    (0..100); a judged percentile wants ten."""
    return int(np.floor(n * (1.0 - q / 100.0)))
