"""Nemotron-H's weights from `--seed`, made on the device by jitted calls,
bfloat16: the share one chip of the stated deployment holds (the
configuration's `n_routed_experts` experts of each routed block, its
`vocab_size` rows of the embedding and the head), every width as published,
one block a letter of `hybrid_override_pattern` (`M` Mamba-2, `*`
attention, `E` routed experts with the shared one).

The tree is the harness's own (the reference's naming); `graph.py` beside
this file maps it onto the program's layer names. The draw is
`harness/draw.py`'s (gains 1 + 0.1 N, other vectors 0.002 N, matrices
`init_std` N; an expert stack [held, in, out] is drawn as a matrix) but for
the leaves whose scale IS the mechanism, which take the family's own draws
(`_own`; listed under `assumed`): at the matrices' scale a state would decay
in two steps and carry nothing, and a dropped state, conv or selection bias
would not show in the comparison.

    a_log    ln U(1, 16)                         (A = -exp(a_log))
    d_skip   1
    dt_bias  softplus^-1(D), D log-uniform on [time_step_min, time_step_max]
             floored at time_step_floor
    conv_w   U(-1, 1) / sqrt(conv_kernel)        (a depthwise conv's default)
    b_sel    0.02 N                              (the choice's bias)
    we_down  init_std / 4 N                      (a routed expert's way out)

A routed expert's down matrix is a quarter of the matrices' scale so that
the comparison reads the precision of the 52 blocks and not ties in 23
top-k choices: the bfloat16 stream chooses another set than float32 does at
a third of (block, position) pairs (scores 0.01 apart at rank 6, moved by
0.004), and at `init_std` one expert chosen otherwise moved the logits by
more than rounding everywhere does, so a sound engine's widest gap came to
1.8 where fp8 read 2.3; at a quarter it reads what rounding leaves (PERF.md
section 6, PR 36). The router, the gates and the up matrix keep their
scale, so what is chosen, and how often, is as before.

No Mamba projection, attention projection or routed expert has a bias, as
published; the conv has one (`use_conv_bias`). The biases of the embedding,
the attention's output projection, the shared expert's two matrices and the
head are the graph's (`departures`)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.harness import draw


def dims(cfg: dict) -> dict:
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N = cfg["n_groups"], cfg["ssm_state_size"]
    return {"d": cfg["hidden_size"], "pattern": cfg["hybrid_override_pattern"],
            "mH": H, "mP": P, "mG": G, "mN": N, "mK": cfg["conv_kernel"],
            "inner": H * P, "conv": H * P + 2 * G * N,
            "H": cfg["num_attention_heads"], "Hkv": cfg["num_key_value_heads"],
            "Dh": cfg["head_dim"], "f": cfg["moe_intermediate_size"],
            "fs": cfg["moe_shared_expert_intermediate_size"]
            * cfg["n_shared_experts"],
            "held": cfg["n_routed_experts"],
            "experts": cfg.get("router_outputs", cfg["n_routed_experts"]),
            "k": cfg["num_experts_per_tok"], "v": cfg["vocab_size"]}


def shapes(cfg: dict) -> dict:
    m = dims(cfg)
    d = m["d"]
    if len(m["pattern"]) != cfg["num_hidden_layers"] \
            or set(m["pattern"]) - set("ME*"):
        raise KeyError("hybrid_override_pattern must give one of M, E, * "
                       "for each of num_hidden_layers blocks ('-' blocks "
                       "are not written down here)")
    kinds = {
        "M": {"ln_g": (d,), "w_in": (d, m["inner"] + m["conv"] + m["mH"]),
              "conv_w": (m["mK"], m["conv"]), "conv_b": (m["conv"],),
              "a_log": (m["mH"],), "d_skip": (m["mH"],),
              "dt_bias": (m["mH"],), "norm_g": (m["inner"],),
              "w_out": (m["inner"], d)},
        "*": {"ln_g": (d,), "wq": (d, m["H"] * m["Dh"]),
              "wk": (d, m["Hkv"] * m["Dh"]), "wv": (d, m["Hkv"] * m["Dh"]),
              "wo": (m["H"] * m["Dh"], d), "bo": (d,)},
        "E": {"ln_g": (d,), "w_router": (d, m["experts"]),
              "b_sel": (m["experts"],),
              "we_up": (m["held"], d, m["f"]),
              "we_down": (m["held"], m["f"], d),
              "ws_up": (d, m["fs"]), "bs_up": (m["fs"],),
              "ws_down": (m["fs"], d), "bs_down": (d,)},
    }
    return {"embed_w": (m["v"], d), "embed_b": (d,),
            "blocks": [dict(kinds[c]) for c in m["pattern"]],
            "lnf_g": (d,), "head_w": (d, m["v"]), "head_b": (m["v"],)}


def _own(part: dict, cfg: dict, key, dtype) -> dict:
    """The family's own draws over the leaves of `part` that take them."""
    f32 = jnp.float32
    out = dict(part)
    ks = jax.random.split(key, 4)
    if "a_log" in part:
        n = part["a_log"].shape
        out["a_log"] = jnp.log(jax.random.uniform(ks[0], n, f32, 1.0, 16.0))
        out["d_skip"] = jnp.ones(n, f32)
        step = jnp.exp(jax.random.uniform(
            ks[1], n, f32, jnp.log(float(cfg["time_step_min"])),
            jnp.log(float(cfg["time_step_max"]))))
        step = jnp.maximum(step, float(cfg["time_step_floor"]))
        out["dt_bias"] = step + jnp.log(-jnp.expm1(-step))
        out["conv_w"] = jax.random.uniform(
            ks[2], part["conv_w"].shape, f32, -1.0, 1.0) \
            * float(cfg["conv_kernel"]) ** -0.5
    if "b_sel" in part:
        out["b_sel"] = 0.02 * jax.random.normal(ks[3], part["b_sel"].shape,
                                                f32)
        out["we_down"] = 0.25 * part["we_down"].astype(f32)
    return {k: v.astype(dtype) for k, v in out.items()}


def make_params(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """One small jitted program per kind of part (the three kinds of block,
    the two ends), with the seed and the block's index as traced arguments:
    the same programs for every seed and every block."""
    tree = shapes(cfg)
    std = float(cfg.get("init_std", 0.02))
    ends = {k: v for k, v in tree.items() if k != "blocks"}
    lo, hi = draw.split_seed(seed)
    programs = {}

    def one(shapes_, a, b, n):
        key = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
            jax.random.PRNGKey(a), b), n), 0x0FA)
        return _own(draw.part(shapes_, a, b, n, std, dtype), cfg, key, dtype)

    def block(i, shapes_):
        kind = tuple(sorted(shapes_))
        if kind not in programs:
            programs[kind] = jax.jit(lambda a, b, n: one(shapes_, a, b, n))
        return programs[kind](lo, hi, jnp.uint32(i))

    out = jax.jit(lambda a, b: draw.part(ends, a, b, jnp.uint32(draw.ENDS),
                                         std, dtype))(lo, hi)
    out["blocks"] = [block(i, s) for i, s in enumerate(tree["blocks"])]
    return out
