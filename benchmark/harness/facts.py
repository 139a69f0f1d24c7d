"""What the per-metric readers share: selections over the run's rows."""
from __future__ import annotations

from typing import Iterable, List, Tuple

from . import work


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


def chunks(run: dict, t_lo: float, t_hi: float) -> List[Tuple[int, int, bool]]:
    """(tokens, depth0, final) of each `prefill_chunk` span begun in
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
                        >= plen[s["request"]]))
    return out


def decode_work(cfg: dict, depths: List[int], executions: int):
    """Work of `executions` decode steps that together fed `depths`: the
    weights are read once per step, the rest per token."""
    if not depths or executions < 1:
        return 0.0, 0.0
    f, b = work.decode_step(cfg, depths)
    _, b1 = work.decode_step(cfg, [])
    return f, b + (executions - 1) * b1


def prefill_work(cfg: dict, chs: List[Tuple[int, int, bool]]):
    f = b = 0.0
    for n, d0, final in chs:
        fi, bi = work.prefill_chunk(cfg, n, d0, final)
        f, b = f + fi, b + bi
    return f, b


def traced(run: dict):
    """(summary, t_on, t_off) of the traced interval, or None."""
    tr = run.get("trace")
    if not tr or not tr.get("summary"):
        return None
    return tr["summary"], tr["t_on"], tr["t_off"]
