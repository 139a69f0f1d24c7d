"""Reduction of a profiler trace (`.xplane.pb`) to device facts.

Read with `jax.profiler.ProfileData` alone (no tensorflow/tsl import). A
TPU's plane is `/device:TPU:<n>`; its line `XLA Modules` has one event per
execution of a compiled program (named `jit_<fn>(<fingerprint>)`), and its
line `XLA Ops` one event per operation. Busy time is the union of the
operations' intervals; the window is the span those events cover on the
device's own clock. The copy of the idea in `tools/xplane_summary.py`
imports tensorflow's protobufs and is not used."""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional

from .stats import union_seconds

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULES_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return files[-1] if files else None


def module_name(event_name: str) -> str:
    return _SUFFIX.sub("", event_name)


def summarize(path: str, top: int = 10) -> Optional[dict]:
    """None when the trace has no device plane (a CPU rehearsal)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = [p for p in data.planes if DEVICE_PLANE.match(p.name)]
    if not planes:
        return None
    busy, spans = [], []
    modules: List[dict] = []          # device 0's program executions
    op_seconds: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    for n, plane in enumerate(planes):
        ops, mods = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                       for e in line.events]
            elif line.name == MODULES_LINE:
                mods = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                        for e in line.events]
        work = ops or mods
        if not work:
            continue
        busy.append(union_seconds((s, e) for s, e, _ in work) / 1e9)
        spans.append((min(s for s, _, _ in work),
                      max(e for _, e, _ in work)))
        if n == 0:
            for s, e, name in mods:
                modules.append({"name": module_name(name), "start_ns": s,
                                "seconds": (e - s) / 1e9})
            for s, e, name in ops:
                name = name[:96]     # an op's name is its whole HLO line
                op_seconds[name] = op_seconds.get(name, 0.0) + (e - s) / 1e9
            # idle gaps between program executions, by the program that
            # ended the gap (what the host was about to dispatch)
            edge = None
            for s, e, name in sorted(mods):
                if edge is not None and s > edge:
                    key = "before " + module_name(name)
                    gaps[key] = gaps.get(key, 0.0) + (s - edge) / 1e9
                edge = e if edge is None else max(edge, e)
    if not busy:
        return None
    window = max(e for _, e in spans) - min(s for s, _ in spans)
    return {
        "window_s": window / 1e9,
        "busy_s": sum(busy) / len(busy),
        "t_first_ns": min(s for s, _ in spans),
        "devices": len(busy),
        "modules": modules,
        "device_ops": sorted(([k, v] for k, v in op_seconds.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:top],
    }


def module_seconds(summary: dict, pattern: str) -> tuple:
    """(executions, device seconds) of the programs whose name matches."""
    rx = re.compile(pattern)
    hits = [m["seconds"] for m in summary["modules"] if rx.search(m["name"])]
    return len(hits), sum(hits)
