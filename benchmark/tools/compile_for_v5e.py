"""Compile a cell's deepest decode and prefill programs at the real size for
a *described* TPU v5e (no chip attached), and print the compiler's memory
analysis: what the chip's compiler would refuse costs no chip time here.
A compile that passes is not a chip run. Not run by the driver.

    JAX_PLATFORMS=cpu python3 benchmark/tools/compile_for_v5e.py --workload <cell>
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from _common import start  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--bench-root", default=None)
    args = ap.parse_args()
    root, runner = start(args.bench_root, True)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from benchmark.harness import engine_driver, loadgen
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.inference.engine import DecodeScheduler

    jax.config.update("jax_enable_compilation_cache", False)
    ctx = runner.load_cell(root, args.workload)
    cfg, wl, fam = ctx["cfg"], ctx["wl"], ctx["family"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    dt = jnp.dtype(wl.get("dtype", "bfloat16"))

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip)

    # the family's own tree, as shapes: nothing is drawn
    tree = jax.tree_util.tree_map(
        sds, jax.eval_shape(lambda: fam.weights.make_params(cfg, 0, dt)))
    net = ComputationGraph(fam.graph.build_conf(cfg, str(dt)))
    params = fam.graph.graph_tree(tree)
    eng = DecodeScheduler(net, cfg["vocab_size"], **wl["engine"])
    states = jax.tree_util.tree_map(sds, eng._states)
    lim = loadgen.length_limits(ctx["mix"])
    facts = engine_driver.engine_facts(eng)
    nb = engine_driver._bucket(-(-lim["total_max"] // eng.kv_block),
                               facts["table_buckets"])
    n = eng.n_slots
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=chip)
    print(f"{args.workload}: pool {eng.pool.capacity_blocks} blocks, table "
          f"buckets {facts['table_buckets']}, deepest reached {nb}")
    jobs = {
        f"decode nb={nb}": lambda: eng._jstep.lower(
            params, {}, i32(n), jax.ShapeDtypeStruct((n,), jnp.bool_,
                                                     sharding=chip),
            i32(n, nb), states),
        f"prefill c={eng.prefill_chunk} nb={nb}": lambda: eng._jprefill.lower(
            params, {}, i32(1), i32(eng.prefill_chunk), i32(1), i32(n, nb),
            states),
    }
    for name, low in jobs.items():
        t = time.time()
        comp = low().compile()
        m = comp.memory_analysis()
        print(f"{name}: compiled in {time.time() - t:.1f} s; arguments "
              f"{m.argument_size_in_bytes / 1e9:.2f} GB, outputs "
              f"{m.output_size_in_bytes / 1e9:.2f} GB, temporaries "
              f"{m.temp_size_in_bytes / 1e9:.2f} GB, aliased "
              f"{m.alias_size_in_bytes / 1e9:.2f} GB", flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
