"""Finds what belongs to one architecture by the configuration's
`model_type`: `benchmark/families/<model_type>/` holds four files, loaded by
path the way `runner.reader` loads a metric. No registry: a later PR adds an
architecture by adding the directory, a configuration file that names it,
and a cell.

    weights.py    make_params(cfg, seed, dtype)       the harness's own tree,
                                                      what the reference takes
    graph.py      build_conf(cfg, dtype)              the program's graph, by
                                                      its public builder DSL
                  graph_tree(params)                  that tree under the
                                                      program's layer names
    reference.py  logits_at(params, cfg, ids, pos, quant=None)
                                                      float32, `highest`,
                                                      nothing of the program
    work.py       decode_step(cfg, depths, bytes_per_el=2, run=None,
                              t_lo=None, t_hi=None)
                  prefill_chunk(cfg, n_tokens, depth0, final, bytes_per_el=2,
                                run=None, span=None)  -> (flops, bytes)
                                                      `run` is what a reader
                                                      gets, for the work that
                                                      depends on what the
                                                      program recorded
                  param_count(cfg)
                  kv_bytes_per_position(cfg, bytes_per_el=2)

A family's files import what every family shares from `benchmark.harness`
(`precision`, `draw`, `peaks`) and nothing of another family."""
from __future__ import annotations

import importlib.util
from pathlib import Path
from types import SimpleNamespace

PARTS = {
    "weights": ("make_params",),
    "graph": ("build_conf", "graph_tree"),
    "reference": ("logits_at",),
    "work": ("decode_step", "prefill_chunk", "param_count",
             "kv_bytes_per_position"),
}


def module_at(name: str, path: Path):
    """The file at `path`, imported under `name`: how the benchmark finds
    what a name in its data leads to (a metric's reader, a family's part)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(root: Path, cfg: dict) -> SimpleNamespace:
    """The family of `cfg["model_type"]` under `root`, its four files
    imported and their functions checked, so that a missing one fails here
    and not in mid-run. Every failure is a KeyError that names what to add:
    `run.py` turns it into exit 2 before any device is asked for."""
    name = cfg.get("model_type")
    if not isinstance(name, str) or not name:
        raise KeyError("the configuration names no model_type: it is the "
                       "directory under benchmark/families/ to load")
    here = Path(root) / "benchmark" / "families" / name
    if not here.is_dir():
        raise KeyError(f"no family for model_type {name!r}: add the "
                       f"directory benchmark/families/{name}/ with "
                       f"{', '.join(p + '.py' for p in PARTS)}")
    fam = SimpleNamespace(name=name, path=here)
    for part, functions in PARTS.items():
        path = here / f"{part}.py"
        if not path.is_file():
            raise KeyError(f"family {name!r} lacks "
                           f"benchmark/families/{name}/{part}.py")
        mod = module_at(f"_family_{name}_{part}", path)
        missing = [f for f in functions if not callable(getattr(mod, f, None))]
        if missing:
            raise KeyError(f"benchmark/families/{name}/{part}.py lacks "
                           f"{', '.join(missing)}")
        setattr(fam, part, mod)
    return fam
