"""The toy post-LN graph in the program's builder DSL: LayerNormalization
*after* each residual add, a ReLU DenseLayer pair whose biases the family
holds at zero (it draws none), no final LayerNormalization."""
from __future__ import annotations


def build_conf(cfg: dict, dtype: str = "bfloat16"):
    from deeplearning4j_tpu.nn.conf.config import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.graph import ElementWiseVertex
    from deeplearning4j_tpu.nn.conf.layers import (
        DenseLayer, LayerNormalization, RnnOutputLayer, SelfAttentionLayer)
    from deeplearning4j_tpu.nn.updater.updaters import Sgd

    d, v, ff = cfg["hidden_size"], cfg["vocab_size"], cfg["intermediate_size"]

    def ln():
        return LayerNormalization(n_in=d, n_out=d,
                                  eps=float(cfg["norm_epsilon"]),
                                  activation="identity")

    gb = (NeuralNetConfiguration.builder()
          .seed(0).learning_rate(0.0).updater(Sgd()).dtype(dtype)
          .graph_builder().add_inputs("in")
          .add_layer("tok", DenseLayer(n_in=v, n_out=d,
                                       activation="identity"), "in"))
    prev = "tok"
    for i in range(cfg["num_hidden_layers"]):
        gb.add_layer(f"mix{i}", SelfAttentionLayer(
            n_in=d, n_out=d, n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"], causal=True, rope=True,
            rope_base=float(cfg["rope_theta"]),
            max_cache_len=int(cfg["sliding_window"]),
            activation="identity"), prev)
        gb.add_vertex(f"sum{i}a", ElementWiseVertex(op="add"), prev,
                      f"mix{i}")
        gb.add_layer(f"norm{i}a", ln(), f"sum{i}a")
        gb.add_layer(f"up{i}", DenseLayer(n_in=d, n_out=ff,
                                          activation="relu"), f"norm{i}a")
        gb.add_layer(f"down{i}", DenseLayer(n_in=ff, n_out=d,
                                            activation="identity"), f"up{i}")
        gb.add_vertex(f"sum{i}b", ElementWiseVertex(op="add"), f"norm{i}a",
                      f"down{i}")
        gb.add_layer(f"norm{i}b", ln(), f"sum{i}b")
        prev = f"norm{i}b"
    gb.add_layer("out", RnnOutputLayer(n_in=d, n_out=v, activation="softmax",
                                       loss="mcxent"), prev)
    gb.set_outputs("out")
    return gb.build()


def graph_tree(params: dict) -> dict:
    import jax.numpy as jnp

    e = params["ends"]
    tree = {"tok": {"W": e["tok_w"], "b": e["tok_b"]},
            "out": {"W": e["head_w"], "b": e["head_b"]}}
    for i, p in enumerate(params["layers"]):
        tree[f"mix{i}"] = {"Wq": p["q"], "Wk": p["k"], "Wv": p["v"],
                           "Wo": p["o"], "b": p["o_b"]}
        tree[f"norm{i}a"] = {"gain": p["n1_g"], "beta": p["n1_b"]}
        tree[f"up{i}"] = {"W": p["up"], "b": jnp.zeros(
            p["up"].shape[1:], p["up"].dtype)}
        tree[f"down{i}"] = {"W": p["down"], "b": jnp.zeros(
            p["down"].shape[1:], p["down"].dtype)}
        tree[f"norm{i}b"] = {"gain": p["n2_g"], "beta": p["n2_b"]}
    return tree
