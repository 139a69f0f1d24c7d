"""EvaByte's graph, built with the program's public builder DSL, and the map
of the harness's weight tree (`weights.py` beside this file) onto the
program's layer names. With `harness/engine_driver.py`, the only code of the
benchmark that imports the program.

Pre-norm blocks of `LayerNormalization(rms, unit_offset)`,
`EvaAttentionLayer` (causal, RoPE, chunk summaries; no biases), residual add,
the gated FFN as three `DenseLayer`s and a product vertex (`swish` = silu on the gate),
a final RMSNorm and a softmax `RnnOutputLayer` whose 320 logits are computed
in float32 (`fp32_logits`). The input is a one-hot row into a `DenseLayer`,
as every graph this engine serves."""
from __future__ import annotations


def build_conf(cfg: dict, dtype: str = "bfloat16"):
    from deeplearning4j_tpu.nn.conf.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex
    from deeplearning4j_tpu.nn.conf.layers import (
        DenseLayer, EvaAttentionLayer, LayerNormalization, RnnOutputLayer)
    from deeplearning4j_tpu.nn.updater.updaters import Sgd

    d, v, ff = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]
    eps = float(cfg["rms_norm_eps"])

    def rms():
        return LayerNormalization(
            n_in=d, n_out=d, eps=eps, rms=True,
            unit_offset=bool(cfg.get("norm_add_unit_offset", False)),
            activation="identity")

    gb = (NeuralNetConfiguration.builder()
          .seed(0).learning_rate(0.0).updater(Sgd())
          .dtype(dtype)
          .graph_builder()
          .add_inputs("in")
          .add_layer("embed", DenseLayer(n_in=v, n_out=d,
                                         activation="identity"), "in"))
    prev = "embed"
    for i in range(cfg["num_hidden_layers"]):
        gb.add_layer(f"ln{i}a", rms(), prev)
        gb.add_layer(f"attn{i}", EvaAttentionLayer(
            n_in=d, n_out=d, n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"], rope=True,
            rope_base=float(cfg["rope_theta"]),
            max_cache_len=int(cfg["max_position_embeddings"]),
            window_size=int(cfg["window_size"]),
            chunk_size=int(cfg["chunk_size"]),
            activation="identity"), f"ln{i}a")
        gb.add_vertex(f"res{i}a", ElementWiseVertex(op="add"),
                      prev, f"attn{i}")
        gb.add_layer(f"ln{i}b", rms(), f"res{i}a")
        gb.add_layer(f"gate{i}", DenseLayer(n_in=d, n_out=ff,
                                            activation="swish"), f"ln{i}b")
        gb.add_layer(f"up{i}", DenseLayer(n_in=d, n_out=ff,
                                          activation="identity"), f"ln{i}b")
        gb.add_vertex(f"glu{i}", ElementWiseVertex(op="product"),
                      f"gate{i}", f"up{i}")
        gb.add_layer(f"down{i}", DenseLayer(n_in=ff, n_out=d,
                                            activation="identity"), f"glu{i}")
        gb.add_vertex(f"res{i}b", ElementWiseVertex(op="add"),
                      f"res{i}a", f"down{i}")
        prev = f"res{i}b"
    gb.add_layer("ln_f", rms(), prev)
    gb.add_layer("out", RnnOutputLayer(
        n_in=d, n_out=v, activation="softmax", loss="mcxent",
        logits_dtype="float32" if cfg.get("fp32_logits") else None), "ln_f")
    gb.set_outputs("out")
    return gb.build()


def graph_tree(params: dict) -> dict:
    """The harness's weight tree under the graph's layer names."""
    tree = {"embed": {"W": params["embed_w"], "b": params["embed_b"]},
            "ln_f": {"gain": params["lnf_g"]},
            "out": {"W": params["head_w"], "b": params["head_b"]}}
    for i, p in enumerate(params["blocks"]):
        tree[f"ln{i}a"] = {"gain": p["ln1_g"]}
        tree[f"attn{i}"] = {"Wq": p["wq"], "Wk": p["wk"], "Wv": p["wv"],
                            "Wo": p["wo"], "mu": p["mu"], "phi": p["phi"]}
        tree[f"ln{i}b"] = {"gain": p["ln2_g"]}
        tree[f"gate{i}"] = {"W": p["w_gate"], "b": p["b_gate"]}
        tree[f"up{i}"] = {"W": p["w_up"], "b": p["b_up"]}
        tree[f"down{i}"] = {"W": p["w_down"], "b": p["b_down"]}
    return tree
