"""What the per-metric readers share: selections over the run's rows and
spans, and the work of an interval as the configuration's family counts it
(`run["family"].work`, found by `model_type`)."""
from __future__ import annotations

from typing import Iterable, List, Tuple


def decode_depths(rows: Iterable[dict], t_lo: float, t_hi: float) -> List[int]:
    """Keys attended by each decode-step token stamped in [t_lo, t_hi]: token
    j >= 1 of a request is produced by a decode step over prompt + j keys
    (token 0 comes out of the last prefill chunk)."""
    out = []
    for r in rows:
        for j, t in enumerate(r["stamps"]):
            if j >= 1 and t_lo <= t <= t_hi:
                out.append(r["prompt_len"] + j)
    return out


def chunks(run: dict, t_lo: float, t_hi: float) -> List[Tuple]:
    """(tokens, depth0, final, span) of each `prefill_chunk` span begun in
    [t_lo, t_hi]; depth from the request's earlier chunks in the window."""
    plen = {r["id"]: r["prompt_len"] for r in run["rows"]}
    fed, out = {}, []
    for s in run["window"]["spans"]:
        if s["name"] != "prefill_chunk" or s.get("request") not in plen:
            continue
        d0 = fed.get(s["request"], 0)
        fed[s["request"]] = d0 + s["tokens"]
        if t_lo <= s["t"] <= t_hi:
            out.append((s["tokens"], d0, d0 + s["tokens"]
                        >= plen[s["request"]], s))
    return out


def decode_work(run: dict, depths: List[int], executions: int,
                t_lo: float, t_hi: float):
    """Work of the `executions` decode steps of [t_lo, t_hi] that together
    fed `depths`: the weights are read once per step, the rest per token.
    The family's `work` gets the run and the interval beside the depths
    (what a routed layer reads depends on what was routed): it picks the
    spans and counters it needs from `run["window"]` itself."""
    if not depths or executions < 1:
        return 0.0, 0.0
    work, cfg = run["family"].work, run["cfg"]
    f, b = work.decode_step(cfg, depths, run=run, t_lo=t_lo, t_hi=t_hi)
    _, b1 = work.decode_step(cfg, [], run=run, t_lo=t_lo, t_hi=t_hi)
    return f, b + (executions - 1) * b1


def prefill_work(run: dict, chs: List[Tuple]):
    """Work of the chunks that `chunks` found; the family's `work` gets the
    run and each chunk's own span beside its sizes."""
    work, cfg = run["family"].work, run["cfg"]
    f = b = 0.0
    for n, d0, final, span in chs:
        fi, bi = work.prefill_chunk(cfg, n, d0, final, run=run, span=span)
        f, b = f + fi, b + bi
    return f, b


def traced(run: dict):
    """(summary, t_on, t_off) of the traced interval, or None."""
    tr = run.get("trace")
    if not tr or not tr.get("summary"):
        return None
    return tr["summary"], tr["t_on"], tr["t_off"]
