"""The one general traffic generator.  A traffic mix is a data file
under ``benchmark/traffic/``; everything here is driven by its keys.

Every seed gets the SAME multiset of sizes and inter-arrival gaps, in an
order of its own.  Sizes and gaps are the distribution's quantiles at
evenly spaced probabilities, so the offered load is the same to the
token whatever the seed; ``--seed`` draws the order they come in, the
token ids, the weights and the sampling seeds.  A seed is therefore
another sample of the same traffic: which request meets which
neighbours, and when the bursts fall, differ from seed to seed.  What a
seed may NOT change is the amount of work in a window (lengths drawn
independently would move the load of a window of some tens of requests
by a fifth, and of several hundred by a twentieth).

The inverse-transform power law and the identity-folded request seed
are copied from ``mxnet_tpu/serve/traffic.py`` (``_power_law``,
``request_seed``), whose arrival process runs on a virtual clock.
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass
from typing import Any, Dict, List, Sequence

import numpy as np


@dataclass(frozen=True)
class RequestSpec:
    prompt: tuple
    max_new_tokens: int
    temperature: float
    top_k: int
    seed: int


def request_seed(trace_seed: int, client: int, turn: int) -> int:
    """Sampling seed folded from identity, never from arrival order."""
    return zlib.crc32(
        ("%d:%d:%d" % (trace_seed, client, turn)).encode()) & 0x7FFFFFFF


def _mid_probabilities(n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def length_quantiles(spec: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` integer lengths: the quantiles of ``spec`` at (i + 0.5)/n.

    ``{"dist": "power_law", "alpha": a, "min": lo, "max": hi}`` is the
    discrete bounded Pareto ``min(hi, floor(lo * u**(-1/a)))``;
    ``{"dist": "uniform", "min": lo, "max": hi}`` covers lo..hi
    inclusive.
    """
    dist = spec["dist"]
    p = _mid_probabilities(n)
    lo, hi = int(spec["min"]), int(spec["max"])
    if lo < 1 or hi < lo:
        raise ValueError(f"bad length range in {spec}")
    if dist == "uniform":
        return np.minimum(hi, lo + np.floor(p * (hi - lo + 1))).astype(np.int64)
    if dist == "power_law":
        u = 1.0 - p            # u -> 0 is the long tail
        x = np.floor(lo * u ** (-1.0 / float(spec["alpha"])))
        return np.clip(x, lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist!r}")


def arrival_gaps(spec: Dict[str, Any], seconds: float) -> np.ndarray:
    """The gaps that follow each of an open loop's arrivals, in quantile
    order (the caller draws their order from the seed).

    ``{"process": "exponential_quantiles", "rate_per_s": r}``:
    ``round(r * seconds)`` gaps that are the exponential distribution's
    quantiles, scaled to fill the span exactly.  Shuffled, they have a
    Poisson stream's burstiness with a fixed count and a fixed sum; it
    is not a Poisson process, whose count in a window varies.
    """
    if spec["process"] != "exponential_quantiles":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    rate = float(spec["rate_per_s"])
    n = int(round(rate * seconds))
    if n < 1:
        raise ValueError(f"rate {rate}/s gives no arrival in {seconds} s")
    gaps = -np.log(1.0 - _mid_probabilities(n))
    return gaps * (seconds / gaps.sum())


def _sampling_plan(mix: Sequence[Dict[str, Any]], n: int) -> List[Dict[str, Any]]:
    """``n`` sampling settings in the mix's shares, largest remainders."""
    shares = np.array([float(m["share"]) for m in mix])
    if abs(shares.sum() - 1.0) > 1e-6:
        raise ValueError(f"sampling shares sum to {shares.sum()}, not 1")
    exact = shares * n
    counts = np.floor(exact).astype(int)
    for i in np.argsort(-(exact - counts))[:n - counts.sum()]:
        counts[i] += 1
    plan: List[Dict[str, Any]] = []
    for m, c in zip(mix, counts):
        plan += [m] * int(c)
    return plan


def make_requests(traffic: Dict[str, Any], vocab: int, seed: int, n: int,
                  stream: int = 0) -> List[RequestSpec]:
    """``n`` requests whose prompt lengths, output budgets and sampling
    settings are the mix's quantiles and shares, each in an order drawn
    from ``seed``; the seed also draws the token ids (1..vocab-1; 0 is
    padding in the engine's tables) and the sampling seeds.  ``stream``
    tells apart the draws of one run (a client, or the lead-in)."""
    rng = np.random.default_rng([int(seed), int(stream), n, 0x7AFF1C])
    plens = rng.permutation(length_quantiles(traffic["prompt_tokens"], n))
    outs = rng.permutation(length_quantiles(traffic["output_tokens"], n))
    plan = _sampling_plan(traffic["sampling"], n)
    reqs = []
    for i, j in enumerate(rng.permutation(n)):
        toks = rng.integers(1, vocab, int(plens[i]))
        reqs.append(RequestSpec(
            prompt=tuple(toks.tolist()), max_new_tokens=int(outs[i]),
            temperature=float(plan[j]["temperature"]),
            top_k=int(plan[j]["top_k"]),
            seed=request_seed(int(seed), stream, i)))
    return reqs


def open_loop(traffic: Dict[str, Any], vocab: int, seed: int, seconds: float,
              lead_in_s: float):
    """An open loop's schedule: ``(lead, body)``, two lists of
    ``(due offset in seconds, RequestSpec)``.  The body's offsets start
    at 0 (the window's opening) and stay under ``seconds``; the lead-in
    is a shorter stretch of the same traffic (its own quantiles at the
    same rate, its own order) at negative offsets back to
    ``-lead_in_s``."""
    def shuffled_gaps(span, stream):
        rng = np.random.default_rng([int(seed), stream, 0x6A95])
        return rng.permutation(arrival_gaps(traffic["arrivals"], span))

    gaps = shuffled_gaps(seconds, 0)
    body_t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    body = make_requests(traffic, vocab, seed, len(gaps))
    lead_t = -np.cumsum(shuffled_gaps(lead_in_s, 1))[::-1]
    lead = make_requests(traffic, vocab, seed, len(lead_t), stream=1)
    return list(zip(lead_t, lead)), list(zip(body_t, body))


def describe_lengths(reqs: Sequence[RequestSpec]) -> Dict[str, Any]:
    """The distribution actually drawn, for the run's earlier lines."""
    def summ(xs):
        xs = np.asarray(xs)
        return {"min": int(xs.min()), "p50": float(np.median(xs)),
                "mean": float(xs.mean()), "max": int(xs.max()),
                "sum": int(xs.sum())}
    return {"n": len(reqs),
            "prompt_tokens": summ([len(r.prompt) for r in reqs]),
            "output_tokens": summ([r.max_new_tokens for r in reqs]),
            "greedy": sum(1 for r in reqs if r.temperature == 0.0)}


def batch_arrays(inputs: Dict[str, Dict[str, Any]], count: int,
                 seed: int) -> Dict[str, np.ndarray]:
    """Seeded host batches for a training job: ``count`` distinct
    batches of every input, stacked on dim 0.

    ``{"shape": [...], "kind": "uniform"}`` draws float32 in [0, 1);
    ``{"shape": [...], "kind": "int", "high": h}`` draws integer ids in
    [0, h) stored as float32 (the program's input dtype).
    """
    rng = np.random.default_rng([int(seed), 0x7EA1])
    out: Dict[str, np.ndarray] = {}
    for name, spec in inputs.items():
        shape = [int(s) for s in spec["shape"]]
        full = [count * shape[0]] + shape[1:]
        if spec["kind"] == "uniform":
            out[name] = rng.random(full, dtype=np.float32)
        elif spec["kind"] == "int":
            out[name] = rng.integers(
                0, int(spec["high"]), full).astype(np.float32)
        else:
            raise ValueError(f"unknown input kind {spec['kind']!r}")
    return out
