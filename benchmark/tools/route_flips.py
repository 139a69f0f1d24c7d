"""Why a routed cell's `max_gap` is wide: does the program choose other
experts than the reference, and is that what moves its logits? Not run by
the driver; its readings are in PERF.md beside the cell's limit.

For each seed: new weights, `--rows` sequences of `--tokens` uniform ids
through the program's own graph (its full forward in the cell's dtype, no
engine) and through the family's reference, teacher-forced. At every
position: the experts each routed layer of the program chose against the
reference's own choice (`reference.routing_at`), and the gap `check.py`
reads — the reference's best logit minus its logit of the program's first
choice — three times: against the plain reference (the cell's statistic),
against the reference made to take the program's choices
(`logits_at(routes=…)`: what is left is everything but the routing), and
for the control's first choice (`--control`, the reference in fp8). One
JSON line a seed.

    python3 benchmark/tools/route_flips.py --workload <cell> --seeds 1,2 --tokens 2048 --rows 4
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _common import start  # noqa: E402


def program_pass(net, vocab, dtype):
    """ids [1, T] -> (the program's first choice after every position [T],
    the experts each routed layer chose [layers, T, k])."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.layers.experts import RoutedExpertsLayerImpl

    conf = net.conf
    routed = [name for name, impl in net._impls.items()
              if isinstance(impl, RoutedExpertsLayerImpl)]

    @jax.jit
    def run(params, variables, ids):
        acts, _, _ = net._forward_impl(
            params, variables, [jax.nn.one_hot(ids, vocab, dtype=dtype)],
            train=False, rng=None)
        chose = [net._impls[name].route(
            params[name], acts[conf.vertex_inputs[name][0]][0])[0]
            for name in routed]
        return acts[conf.network_outputs[0]][0].argmax(-1), jnp.stack(chose)

    return run


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--tokens", type=int, default=2048)
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--bench-root", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    root, runner = start(args.bench_root, args.rehearse_cpu)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmark.harness import engine_driver

    ctx = runner.load_cell(root, args.workload)
    if runner.find_device(ctx["cell"]["chips"], args.rehearse_cpu) is None:
        return 3
    cfg, fam = ctx["cfg"], ctx["family"]
    dtype = jnp.dtype(ctx["wl"].get("dtype", "bfloat16"))
    first = cfg.get("experts_held_first", 0)
    held = np.arange(first, first + cfg["n_routed_experts"])
    T = args.tokens
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    net = run = params = None
    lines = []
    for seed in (int(s) for s in args.seeds.split(",")):
        if net is not None:         # free the old weights before the new
            net.params, params = {}, None
        params = fam.weights.make_params(cfg, seed, dtype)
        if net is None:
            net = engine_driver.build_net(fam, cfg, params, str(dtype))
            run = program_pass(net, cfg["vocab_size"], dtype)
        else:
            net.params = fam.graph.graph_tree(params)
        rng = np.random.default_rng(seed ^ 0xF11B5)
        gaps = {"plain": [], "program_routes": [], args.control: []}
        flips, held_flips = [], []
        for _ in range(args.rows):
            ids = jnp.asarray(rng.integers(0, cfg["vocab_size"], (1, T)),
                              jnp.int32)
            tok, chose = run(net.params, net.variables, ids)
            tok, chose = tok[None, :, None], np.asarray(chose)
            ref = fam.reference.logits_at(params, cfg, ids, pos)
            own = np.stack([np.asarray(c[0]) for c in
                            fam.reference.routing_at(params, cfg, ids)])
            forced = fam.reference.logits_at(
                params, cfg, ids, pos, routes=[jnp.asarray(c)[None]
                                               for c in chose])
            low = fam.reference.logits_at(params, cfg, ids, pos,
                                          quant=args.control)
            for key, logits, t in (
                    ("plain", ref, tok), ("program_routes", forced, tok),
                    (args.control, ref, low.argmax(-1)[..., None])):
                gap = logits.max(-1) - jnp.take_along_axis(logits, t, -1)[..., 0]
                gaps[key].append(np.asarray(gap, np.float64)[0])
            # [layers, T, experts]: which experts either side chose
            a = (chose[..., None] == np.arange(cfg["router_outputs"])).any(-2)
            b = (own[..., None] == np.arange(cfg["router_outputs"])).any(-2)
            flips.append((a != b).any(-1))
            held_flips.append((a != b)[..., held].any(-1))
        flips, held_flips = np.stack(flips), np.stack(held_flips)
        plain = np.stack(gaps["plain"])
        wide = plain > 0.2
        line = {"seed": seed, "positions": int(plain.size),
                "layers": int(flips.shape[1]),
                **{f"{k}.{stat}": float(getattr(np.concatenate(v), stat)())
                   for k, v in gaps.items() for stat in ("max", "mean")},
                "other_set_share": float(flips.mean()),
                "other_held_share": float(held_flips.mean()),
                "positions_a_held_expert_differs": float(
                    held_flips.any(1).mean()),
                "wide_gaps": int(wide.sum()),
                "wide_gaps_with_a_held_expert_differing": int(
                    (wide & held_flips.any(1)).sum())}
        lines.append(line)
        print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
