"""The comparison that decides `correct`.

After the window has closed, a sample of the requests it finished (drawn
from the seed, the longest always in it) is run once through the plain
reference: prompt and served tokens, teacher-forced. For every served token
the reference's logits at that position give the *gap*: how far the served
token's logit lies below the reference's best. A sound bfloat16 engine
serves the reference's best token, or one rounding away from it; the number
compared is the widest gap over the sample. Valid for greedy tokens only.

The control (`quant="fp8"`) reads, at the same positions, the gap of the
token the lower precision puts first. It need not decode."""
from __future__ import annotations

from typing import List, Optional

import numpy as np


def pick(rows: List[dict], seed: int, k: int) -> List[dict]:
    done = [r for r in rows if r["done"] is not None and r["tokens"]
            and not r["error"]]
    if not done:
        return []
    longest = max(done, key=lambda r: (r["prompt_len"] + len(r["tokens"]),
                                       -r["index"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(int(seed) ^ 0xC0FFEE)
    idx = rng.permutation(len(rest))[:max(0, k - 1)]
    return [longest] + [rest[i] for i in sorted(idx)]


def _pad(n: int, to: int) -> int:
    return -(-n // to) * to


def _batch_gaps(logits_at, params, cfg, batch, prompts, T, P, quants):
    import jax.numpy as jnp

    R = len(batch)
    ids = np.zeros((R, T), np.int32)
    pos = np.zeros((R, P), np.int32)
    tok = np.zeros((R, P), np.int32)
    live = np.zeros((R, P), bool)
    for i, r in enumerate(batch):
        if r is None:                       # padding of the last batch
            continue
        p, n = r["prompt_len"], len(r["tokens"])
        ids[i, :p] = prompts[r["index"]]
        ids[i, p:p + n] = r["tokens"]
        pos[i, :n] = p - 1 + np.arange(n)   # token j follows position p-1+j
        tok[i, :n] = r["tokens"]
        live[i, :n] = True
    ids, pos = jnp.asarray(ids), jnp.asarray(pos)
    ref = logits_at(params, cfg, ids, pos)
    best = ref.max(-1)
    out = {}
    for q in quants:
        chosen = jnp.asarray(tok) if q is None else \
            logits_at(params, cfg, ids, pos, quant=q).argmax(-1)
        gap = best - jnp.take_along_axis(ref, chosen[..., None], -1)[..., 0]
        out[q] = np.asarray(gap, np.float64)[live]
    return out


def gaps(logits_at, params: dict, cfg: dict, sample: List[dict],
         prompts: dict, t_pad: int, p_pad: int, quants=(None,),
         batch: int = 4) -> dict:
    """`logits_at` is the plain reference of the configuration's family
    (`families/<model_type>/reference.py`); `prompts[index]` is a request's
    prompt ids. For each entry of `quants`
    (None: the served tokens; "fp8"/"int8": the control's first choices) the
    gap statistics over the sample, run through the reference `batch`
    requests at a time at one padded shape (one compiled program a cell)."""
    T = _pad(max(t_pad, max(r["prompt_len"] + len(r["tokens"])
                            for r in sample)), 512)
    P = max(p_pad, max(len(r["tokens"]) for r in sample))
    parts = {q: [] for q in quants}
    for i in range(0, len(sample), batch):
        chunk = sample[i:i + batch]
        chunk = chunk + [None] * (batch - len(chunk))
        for q, g in _batch_gaps(logits_at, params, cfg, chunk, prompts, T, P,
                                quants).items():
            parts[q].append(g)
    out = {}
    for q, gs in parts.items():
        g = np.concatenate(gs)
        out[q] = {"max_gap": float(g.max()), "mean_gap": float(g.mean()),
                  "not_best_share": float((g > 0).mean()),
                  "tokens": int(g.size), "requests": len(sample),
                  "longest": int(max(r["prompt_len"] + len(r["tokens"])
                                     for r in sample))}
    return out


def verdict(rows: List[dict], window: dict, gap: Optional[dict],
            limits: dict) -> dict:
    """Each number compared beside its limit; `correct` is all within."""
    unfinished = sum(1 for r in rows if r["done"] is None or r["error"])
    wrong_len = sum(1 for r in rows if r["done"] is not None
                    and not r["error"] and len(r["tokens"]) != r["out_len"])
    checks = {
        "unfinished": [unfinished, 0],
        "wrong_length": [wrong_len, 0],
        "compiles_in_window": [int(sum(window["compiles"].values())), 0],
        "max_gap": [gap["max_gap"] if gap else float("inf"),
                    float(limits["max_gap"])],
    }
    ok = all(v <= lim for v, lim in checks.values())
    return {"correct": bool(ok), "checks": checks, "failed": unfinished}
