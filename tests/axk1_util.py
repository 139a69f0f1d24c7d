"""What the A.X-K1 tests share: the small CPU size of ISSUE 34 (hidden 64,
4 heads, 16 experts of which 2 a token, ranks 24 / 16, 3 layers of which the
first is dense, vocabulary 96) as a configuration of the `axk1` family, its
seeded weights, the program's net over them and the family's plain
reference. `cfg(first, count)` is the same model cut to one share of its
experts."""
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark.harness import engine_driver, family  # noqa: E402

CFG = {"model_type": "axk1", "hidden_size": 64, "num_hidden_layers": 3,
       "num_attention_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 16,
       "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
       "intermediate_size": 160, "moe_intermediate_size": 32,
       "n_routed_experts": 16, "router_outputs": 16,
       "num_experts_per_tok": 2, "n_shared_experts": 1,
       "first_k_dense_replace": 1, "vocab_size": 96, "rms_norm_eps": 1e-6,
       "rope_theta": 10000, "max_position_embeddings": 512,
       "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                        "mscale": 1, "mscale_all_dim": 1,
                        "original_max_position_embeddings": 64,
                        "type": "yarn"},
       "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
       "norm_topk_prob": True, "init_std": 0.05}
BLOCK = 8
ROW = CFG["kv_lora_rank"] + CFG["qk_rope_head_dim"]
# a block of 8 positions: 3 layers x one leaf of 24 values x 4 bytes
BLOCK_BYTES = CFG["num_hidden_layers"] * BLOCK * ROW * 4


def pool_mb(blocks: int, itemsize: int = 4) -> float:
    """MiB that buy exactly `blocks` usable blocks (+1 scratch)."""
    return (blocks + 1) * BLOCK_BYTES * itemsize / 4 / float(1 << 20)


def cfg(first: int = 0, count: int = 16) -> dict:
    return {**CFG, "n_routed_experts": count, "experts_held_first": first}


def load(seed: int = 7, dtype: str = "float32", conf: dict = CFG):
    import jax.numpy as jnp
    fam = family.load(REPO, conf)
    params = fam.weights.make_params(conf, seed, jnp.dtype(dtype))
    return fam, params, engine_driver.build_net(fam, conf, params, dtype)
