"""A.X-K1's graph, built with the program's public builder DSL, and the map
of the harness's weight tree (`weights.py` beside this file) onto the
program's layer names. With `harness/engine_driver.py`, the only code of the
benchmark that imports the program.

Pre-norm blocks of RMSNorm (`LayerNormalization(rms)`, gain only),
`LatentAttentionLayer` (MLA with YaRN; no biases), residual add, then either
the dense gated FFN (the `first_k_dense_replace` leading layers) or a
`RoutedExpertsLayer` told which experts it holds beside the shared expert,
both gated FFNs as three `DenseLayer`s and a product vertex (`swish` = silu
on the gate); the routed part, the shared part and the stream meet in one
add vertex. A final RMSNorm and a softmax `RnnOutputLayer` whose logits are
float32. The input is a one-hot row into a `DenseLayer`, as every graph this
engine serves."""
from __future__ import annotations


def build_conf(cfg: dict, dtype: str = "bfloat16"):
    from deeplearning4j_tpu.nn.conf.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex
    from deeplearning4j_tpu.nn.conf.layers import (
        DenseLayer, LatentAttentionLayer, LayerNormalization,
        RnnOutputLayer, RoutedExpertsLayer)
    from deeplearning4j_tpu.nn.updater.updaters import Sgd

    d, v = cfg["hidden_size"], cfg["vocab_size"]
    eps = float(cfg["rms_norm_eps"])
    yarn = cfg.get("rope_scaling") or {}
    held = cfg["n_routed_experts"]

    def rms():
        return LayerNormalization(n_in=d, n_out=d, eps=eps, rms=True,
                                  activation="identity")

    gb = (NeuralNetConfiguration.builder()
          .seed(0).learning_rate(0.0).updater(Sgd())
          .dtype(dtype)
          .graph_builder()
          .add_inputs("in")
          .add_layer("embed", DenseLayer(n_in=v, n_out=d,
                                         activation="identity"), "in"))

    def gated_ffn(i, tag, width, src):
        gb.add_layer(f"{tag}gate{i}", DenseLayer(n_in=d, n_out=width,
                                                 activation="swish"), src)
        gb.add_layer(f"{tag}up{i}", DenseLayer(n_in=d, n_out=width,
                                               activation="identity"), src)
        gb.add_vertex(f"{tag}glu{i}", ElementWiseVertex(op="product"),
                      f"{tag}gate{i}", f"{tag}up{i}")
        gb.add_layer(f"{tag}down{i}", DenseLayer(n_in=width, n_out=d,
                                                 activation="identity"),
                     f"{tag}glu{i}")
        return f"{tag}down{i}"

    prev = "embed"
    for i in range(cfg["num_hidden_layers"]):
        gb.add_layer(f"ln{i}a", rms(), prev)
        gb.add_layer(f"attn{i}", LatentAttentionLayer(
            n_in=d, n_out=d, n_heads=cfg["num_attention_heads"],
            q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
            qk_nope_head_dim=cfg["qk_nope_head_dim"],
            qk_rope_head_dim=cfg["qk_rope_head_dim"],
            v_head_dim=cfg["v_head_dim"], eps=eps,
            rope_base=float(cfg["rope_theta"]),
            max_cache_len=int(cfg["max_position_embeddings"]),
            yarn_factor=float(yarn.get("factor", 1.0)),
            yarn_original_max=int(yarn.get(
                "original_max_position_embeddings", 4096)),
            yarn_beta_fast=float(yarn.get("beta_fast", 32)),
            yarn_beta_slow=float(yarn.get("beta_slow", 1)),
            yarn_mscale=float(yarn.get("mscale", 1)),
            yarn_mscale_all_dim=float(yarn.get("mscale_all_dim", 0)),
            activation="identity"), f"ln{i}a")
        gb.add_vertex(f"res{i}a", ElementWiseVertex(op="add"),
                      prev, f"attn{i}")
        gb.add_layer(f"ln{i}b", rms(), f"res{i}a")
        if i < cfg["first_k_dense_replace"]:
            parts = [gated_ffn(i, "", cfg["intermediate_size"], f"ln{i}b")]
        else:
            gb.add_layer(f"moe{i}", RoutedExpertsLayer(
                n_in=d, n_out=d,
                n_experts=cfg.get("router_outputs", held),
                held=(cfg.get("experts_held_first", 0), held),
                top_k=cfg["num_experts_per_tok"],
                scoring=cfg["scoring_func"],
                norm_topk=bool(cfg["norm_topk_prob"]),
                scale=float(cfg["routed_scaling_factor"]),
                width=cfg["moe_intermediate_size"],
                activation="identity"), f"ln{i}b")
            parts = [f"moe{i}", gated_ffn(
                i, "s", cfg["moe_intermediate_size"]
                * cfg["n_shared_experts"], f"ln{i}b")]
        gb.add_vertex(f"res{i}b", ElementWiseVertex(op="add"),
                      f"res{i}a", *parts)
        prev = f"res{i}b"
    gb.add_layer("ln_f", rms(), prev)
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

    def ffn(i, tag, p, w, b):
        for part in ("gate", "up", "down"):
            tree[f"{tag}{part}{i}"] = {"W": p[f"{w}_{part}"],
                                       "b": p[f"{b}_{part}"]}

    for i, p in enumerate(params["blocks"]):
        tree[f"ln{i}a"] = {"gain": p["ln1_g"]}
        tree[f"attn{i}"] = {"Wdq": p["wdq"], "q_gain": p["qn_g"],
                            "Wuq": p["wuq"], "Wdkv": p["wdkv"],
                            "kv_gain": p["kvn_g"], "Wukv": p["wukv"],
                            "Wo": p["wo"]}
        tree[f"ln{i}b"] = {"gain": p["ln2_g"]}
        if "w_router" in p:
            tree[f"moe{i}"] = {"Wr": p["w_router"], "Wg": p["we_gate"],
                               "Wu": p["we_up"], "Wd": p["we_down"]}
            ffn(i, "s", p, "ws", "bs")
        else:
            ffn(i, "", p, "w", "b")
    return tree
