"""One general open-loop traffic generator, driven by a mix's data file.

A mix (`benchmark/traffic/<mix>.json`) gives the arrival process and rate,
the length distributions, the sharing of prefixes and the sampling. Every
seed gets the same lengths and the same inter-arrival gaps (the quantiles of
the distributions, one per request, shuffled once from the mix's
`order_seed`) at the same places in the window; `--seed` draws the token ids
(and, in `weights.py`, the weights). The seed must not change the work: a
reshuffle per seed moved the 95th percentile of time-to-first-token by a
fifth between seeds, and even a rotation of one fixed cyclic order, which
only changes what the window's edges cut, spread it by 6 % and the token rate
by 5 % (my chip runs, PR 25), where a bound may be 10 % at most. Requests are
due by this schedule whether or not earlier ones finished."""
from __future__ import annotations

from dataclasses import dataclass, field
from statistics import NormalDist
from typing import List, Optional

import numpy as np


@dataclass
class Request:
    index: int
    due_s: float            # seconds after the window opens
    prompt: List[int]
    out_tokens: int
    temperature: float = 0.0
    # filled in by the driver (host monotonic clock)
    t_due: Optional[float] = None
    t_sent: Optional[float] = None
    handle: object = None
    stamps: List[float] = field(default_factory=list)
    error: Optional[str] = None


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lengths(spec: dict, n: int) -> np.ndarray:
    """`n` lengths at the quantiles of the distribution, ascending."""
    u = _quantiles(n)
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "uniform":
        x = spec["min"] + (spec["max"] - spec["min"]) * u
    elif dist == "constant":
        x = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo = spec.get("min", 1)
    hi = spec.get("max", float("inf"))
    return np.clip(np.rint(x), lo, hi).astype(int)


def _gaps(spec: dict, n: int) -> np.ndarray:
    """`n` inter-arrival gaps at the quantiles of the process, mean 1/rate.
    `poisson` is gamma with cv 1; `gamma` takes `cv` (bursts for cv > 1)."""
    u = _quantiles(n)
    proc = spec["process"]
    if proc == "poisson":
        g = -np.log1p(-u)
    elif proc == "gamma":
        # shape k = 1/cv^2; quantiles by inverting a sampled cdf from a fixed
        # stream (no scipy here): fixed, so every seed sees the same set
        k = 1.0 / float(spec["cv"]) ** 2
        ref = np.sort(np.random.default_rng(0).gamma(k, 1.0 / k, 65536))
        g = ref[(u * len(ref)).astype(int)]
    elif proc == "uniform":
        g = np.ones(n)
    else:
        raise ValueError(f"unknown arrival process {proc!r}")
    return g / g.mean() / float(spec["rate_per_s"])


def n_requests(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["arrival"]["rate_per_s"] * seconds)))


def length_limits(mix: dict) -> dict:
    """The reachable range of lengths, seed-independent: what set-up warms."""
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    lim = {"prompt_min": int(p.get("min", p.get("value", 1))),
           "prompt_max": int(p.get("max", p.get("value", 0))),
           "out_min": int(o.get("min", o.get("value", 1))),
           "out_max": int(o.get("max", o.get("value", 0)))}
    total = mix.get("max_total_tokens")
    lim["total_max"] = int(total) if total else lim["prompt_max"] + lim["out_max"]
    return lim


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> List[Request]:
    """The window's requests, in due order. Same seed, same inputs."""
    n = n_requests(mix, seconds)
    order = np.random.default_rng(int(mix.get("order_seed", 0)))
    rng = np.random.default_rng(int(seed))
    prompts = order.permutation(_lengths(mix["prompt_tokens"], n))
    outs = order.permutation(_lengths(mix["output_tokens"], n))
    gaps = order.permutation(_gaps(mix["arrival"], n))
    # all n are due inside the window: the last half a mean gap before its end
    gaps = gaps * (seconds - 0.5 / mix["arrival"]["rate_per_s"]) / gaps.sum()
    due = np.cumsum(gaps)
    total = mix.get("max_total_tokens")
    share = mix.get("shared_prefix") or {}
    prefixes = []
    if share:
        plens = _lengths(share["tokens"], int(share["groups"]))
        prefixes = [rng.integers(0, vocab, int(k)).tolist() for k in plens]
    temperature = float(mix.get("sampling", {}).get("temperature", 0.0))
    reqs = []
    for i in range(n):
        p_len, o_len = int(prompts[i]), int(outs[i])
        if total:
            o_len = max(1, min(o_len, int(total) - p_len))
        ids = rng.integers(0, vocab, p_len).tolist()
        if prefixes and rng.random() < float(share.get("share", 1.0)):
            pre = prefixes[int(rng.integers(len(prefixes)))][:p_len - 1]
            ids[:len(pre)] = pre
        reqs.append(Request(i, float(due[i]), ids, o_len, temperature))
    return reqs
