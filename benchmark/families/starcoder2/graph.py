"""StarCoder2's graph, built with the program's public builder DSL, and the
map of the harness's weight tree (`weights.py` beside this file) onto the
program's layer names. With `harness/engine_driver.py`, the only code of the
benchmark that imports the program.

A copy of `deeplearning4j_tpu/models/zoo.py:transformer_lm` (PR 21's tree),
because that function does not pass `rope_base`, `max_cache_len` or the
LayerNorm epsilon, and builds an Adam updater whose state would triple the
weights' memory. Nothing else differs: pre-LN blocks of LayerNormalization,
SelfAttentionLayer (causal, RoPE, grouped KV heads), residual add, GELU
DenseLayer pair, final LayerNormalization, softmax RnnOutputLayer."""
from __future__ import annotations


def build_conf(cfg: dict, dtype: str = "bfloat16"):
    from deeplearning4j_tpu.nn.conf.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex
    from deeplearning4j_tpu.nn.conf.layers import (
        DenseLayer, LayerNormalization, RnnOutputLayer, SelfAttentionLayer)
    from deeplearning4j_tpu.nn.updater.updaters import Sgd

    d, v = cfg["hidden_size"], cfg["vocab_size"]
    eps = float(cfg["norm_epsilon"])

    def ln():
        return LayerNormalization(n_in=d, n_out=d, eps=eps,
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
        gb.add_layer(f"ln{i}a", ln(), prev)
        gb.add_layer(f"attn{i}", SelfAttentionLayer(
            n_in=d, n_out=d, n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"], causal=True, rope=True,
            rope_base=float(cfg["rope_theta"]),
            max_cache_len=int(cfg["sliding_window"]),
            activation="identity"), f"ln{i}a")
        gb.add_vertex(f"res{i}a", ElementWiseVertex(op="add"),
                      prev, f"attn{i}")
        gb.add_layer(f"ln{i}b", ln(), f"res{i}a")
        gb.add_layer(f"ff{i}", DenseLayer(
            n_in=d, n_out=cfg["intermediate_size"], activation="gelu"),
            f"ln{i}b")
        gb.add_layer(f"ff{i}o", DenseLayer(
            n_in=cfg["intermediate_size"], n_out=d, activation="identity"),
            f"ff{i}")
        gb.add_vertex(f"res{i}b", ElementWiseVertex(op="add"),
                      f"res{i}a", f"ff{i}o")
        prev = f"res{i}b"
    gb.add_layer("ln_f", ln(), prev)
    gb.add_layer("out", RnnOutputLayer(n_in=d, n_out=v, activation="softmax",
                                       loss="mcxent"), "ln_f")
    gb.set_outputs("out")
    return gb.build()


def graph_tree(params: dict) -> dict:
    """The harness's weight tree under the zoo graph's layer names."""
    tree = {"embed": {"W": params["embed_w"], "b": params["embed_b"]},
            "ln_f": {"gain": params["lnf_g"], "beta": params["lnf_b"]},
            "out": {"W": params["head_w"], "b": params["head_b"]}}
    for i, p in enumerate(params["blocks"]):
        tree[f"ln{i}a"] = {"gain": p["ln1_g"], "beta": p["ln1_b"]}
        tree[f"attn{i}"] = {"Wq": p["wq"], "Wk": p["wk"], "Wv": p["wv"],
                            "Wo": p["wo"], "b": p["bo"]}
        tree[f"ln{i}b"] = {"gain": p["ln2_g"], "beta": p["ln2_b"]}
        tree[f"ff{i}"] = {"W": p["w_up"], "b": p["b_up"]}
        tree[f"ff{i}o"] = {"W": p["w_down"], "b": p["b_down"]}
    return tree
