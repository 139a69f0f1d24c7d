"""Nemotron-H's graph, built with the program's public builder DSL, and the
map of the harness's weight tree (`weights.py` beside this file) onto the
program's layer names. With `harness/engine_driver.py`, the only code of the
benchmark that imports the program.

One mixer a block behind one RMSNorm (`LayerNormalization(rms)`, gain only)
and a residual add, by `hybrid_override_pattern`: `M` a `Mamba2Layer`, `*` a
`SelfAttentionLayer` with its own `head_dim` and no RoPE, `E` a
`RoutedExpertsLayer` told which experts it holds (plain `relu2` experts, a
selection bias) beside the shared expert as two `DenseLayer`s; the routed
part, the shared part and the stream meet in one add vertex. A final
RMSNorm and a softmax `RnnOutputLayer` whose logits are float32. The input
is a one-hot row into a `DenseLayer`, as every graph this engine serves.

A program without the state-space layer cannot run the family at all: that
is said when the family is loaded (`harness/family.py`: a KeyError, which
`run.py` turns into exit 2 before any device is asked for), not at set-up
with the weights already drawn."""
from __future__ import annotations

try:
    from deeplearning4j_tpu.nn.conf.layers import Mamba2Layer  # noqa: F401
except ImportError as e:
    raise KeyError("family 'nemotron_h' needs a program that has "
                   "deeplearning4j_tpu.nn.conf.layers.Mamba2Layer (a "
                   f"state-space layer the engine serves): {e}") from e


def build_conf(cfg: dict, dtype: str = "bfloat16"):
    from deeplearning4j_tpu.nn.conf.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex
    from deeplearning4j_tpu.nn.conf.layers import (
        DenseLayer, LayerNormalization, Mamba2Layer, RnnOutputLayer,
        RoutedExpertsLayer, SelfAttentionLayer)
    from deeplearning4j_tpu.nn.updater.updaters import Sgd

    d, v = cfg["hidden_size"], cfg["vocab_size"]
    eps = float(cfg["layer_norm_epsilon"])
    held = cfg["n_routed_experts"]
    fs = cfg["moe_shared_expert_intermediate_size"] * cfg["n_shared_experts"]

    gb = (NeuralNetConfiguration.builder()
          .seed(0).learning_rate(0.0).updater(Sgd())
          .dtype(dtype)
          .graph_builder()
          .add_inputs("in")
          .add_layer("embed", DenseLayer(n_in=v, n_out=d,
                                         activation="identity"), "in"))
    prev = "embed"
    for i, kind in enumerate(cfg["hybrid_override_pattern"]):
        gb.add_layer(f"ln{i}", LayerNormalization(
            n_in=d, n_out=d, eps=eps, rms=True, activation="identity"), prev)
        if kind == "M":
            gb.add_layer(f"mamba{i}", Mamba2Layer(
                n_in=d, n_out=d, n_heads=cfg["mamba_num_heads"],
                head_dim=cfg["mamba_head_dim"],
                state_size=cfg["ssm_state_size"], n_groups=cfg["n_groups"],
                conv_kernel=cfg["conv_kernel"], chunk_size=cfg["chunk_size"],
                eps=eps, activation="identity"), f"ln{i}")
            parts = [f"mamba{i}"]
        elif kind == "*":
            gb.add_layer(f"attn{i}", SelfAttentionLayer(
                n_in=d, n_out=d, n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"], causal=True, rope=False,
                max_cache_len=int(cfg["max_position_embeddings"]),
                activation="identity"), f"ln{i}")
            parts = [f"attn{i}"]
        elif kind == "E":
            gb.add_layer(f"moe{i}", RoutedExpertsLayer(
                n_in=d, n_out=d,
                n_experts=cfg.get("router_outputs", held),
                held=(cfg.get("experts_held_first", 0), held),
                top_k=cfg["num_experts_per_tok"], scoring="sigmoid",
                norm_topk=bool(cfg["norm_topk_prob"]),
                scale=float(cfg["routed_scaling_factor"]),
                width=cfg["moe_intermediate_size"], gated=False,
                expert_activation=cfg["mlp_hidden_act"], selection_bias=True,
                activation="identity"), f"ln{i}")
            gb.add_layer(f"sup{i}", DenseLayer(
                n_in=d, n_out=fs, activation=cfg["mlp_hidden_act"]),
                f"ln{i}")
            gb.add_layer(f"sdown{i}", DenseLayer(
                n_in=fs, n_out=d, activation="identity"), f"sup{i}")
            parts = [f"moe{i}", f"sdown{i}"]
        else:
            raise KeyError(f"block {i} of hybrid_override_pattern is "
                           f"{kind!r}: M, E and * are built")
        gb.add_vertex(f"res{i}", ElementWiseVertex(op="add"), prev, *parts)
        prev = f"res{i}"
    gb.add_layer("ln_f", LayerNormalization(
        n_in=d, n_out=d, eps=eps, rms=True, activation="identity"), prev)
    gb.add_layer("out", RnnOutputLayer(
        n_in=d, n_out=v, activation="softmax", loss="mcxent",
        logits_dtype="float32"), "ln_f")
    gb.set_outputs("out")
    return gb.build()


def graph_tree(params: dict) -> dict:
    """The harness's weight tree under the graph's layer names."""
    tree = {"embed": {"W": params["embed_w"], "b": params["embed_b"]},
            "ln_f": {"gain": params["lnf_g"]},
            "out": {"W": params["head_w"], "b": params["head_b"]}}
    for i, p in enumerate(params["blocks"]):
        tree[f"ln{i}"] = {"gain": p["ln_g"]}
        if "w_in" in p:
            tree[f"mamba{i}"] = {
                "W_in": p["w_in"], "conv_w": p["conv_w"],
                "conv_b": p["conv_b"], "A_log": p["a_log"],
                "D": p["d_skip"], "dt_bias": p["dt_bias"],
                "norm_g": p["norm_g"], "W_out": p["w_out"]}
        elif "wq" in p:
            tree[f"attn{i}"] = {"Wq": p["wq"], "Wk": p["wk"], "Wv": p["wv"],
                                "Wo": p["wo"], "b": p["bo"]}
        else:
            tree[f"moe{i}"] = {"Wr": p["w_router"], "b_sel": p["b_sel"],
                               "Wu": p["we_up"], "Wd": p["we_down"]}
            tree[f"sup{i}"] = {"W": p["ws_up"], "b": p["bs_up"]}
            tree[f"sdown{i}"] = {"W": p["ws_down"], "b": p["bs_down"]}
    return tree
