"""What the Nemotron-H tests share: a small CPU size of ISSUE 36 (hidden 48,
Mamba-2 of 4 heads of 8 with state 16 in 2 groups, conv of 4, chunks of 8;
4 query heads over 2 kv heads of 16; 16 plain relu2 experts of 24 of which 3
a token plus one shared of 40; the pattern's first 13 letters; vocabulary
96) as a configuration of the `nemotron_h` family, its seeded weights, the
program's net over them and the family's plain reference. `cfg(first,
count)` is the same model cut to one share of its experts."""
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark.harness import engine_driver, family  # noqa: E402

PATTERN = "MEMEM*EMEMEM*"
CFG = {"model_type": "nemotron_h", "hidden_size": 48,
       "num_hidden_layers": len(PATTERN),
       "hybrid_override_pattern": PATTERN,
       "mamba_num_heads": 4, "mamba_head_dim": 8, "ssm_state_size": 16,
       "n_groups": 2, "conv_kernel": 4, "chunk_size": 8,
       "time_step_min": 0.001, "time_step_max": 0.1,
       "time_step_floor": 0.0001,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
       "max_position_embeddings": 512,
       "moe_intermediate_size": 24, "moe_shared_expert_intermediate_size": 40,
       "n_shared_experts": 1, "n_routed_experts": 16, "router_outputs": 16,
       "num_experts_per_tok": 3, "norm_topk_prob": True,
       "routed_scaling_factor": 2.5, "mlp_hidden_act": "relu2",
       "layer_norm_epsilon": 1e-5, "vocab_size": 96, "init_std": 0.05}
BLOCK = 8
N_ATTN = PATTERN.count("*")
N_MAMBA = PATTERN.count("M")
N_MOE = PATTERN.count("E")
# a block of 8 positions: 2 attention layers x (k, v) x 2 heads of 16 x 4 B
BLOCK_BYTES = N_ATTN * 2 * BLOCK * CFG["num_key_value_heads"] \
    * CFG["head_dim"] * 4
# what a slot holds whatever its length: ssm float32 + conv (3 rows)
CONV = CFG["mamba_num_heads"] * CFG["mamba_head_dim"] \
    + 2 * CFG["n_groups"] * CFG["ssm_state_size"]
STATE_BYTES = N_MAMBA * (CFG["mamba_num_heads"] * CFG["mamba_head_dim"]
                         * CFG["ssm_state_size"] * 4
                         + (CFG["conv_kernel"] - 1) * CONV * 4)


def pool_mb(blocks: int) -> float:
    """MiB that buy exactly `blocks` usable blocks (+1 scratch)."""
    return (blocks + 1) * BLOCK_BYTES / float(1 << 20)


def cfg(first: int = 0, count: int = 16) -> dict:
    return {**CFG, "n_routed_experts": count, "experts_held_first": first}


def load(seed: int = 7, dtype: str = "float32", conf: dict = CFG):
    import jax.numpy as jnp
    fam = family.load(REPO, conf)
    params = fam.weights.make_params(conf, seed, jnp.dtype(dtype))
    return fam, params, engine_driver.build_net(fam, conf, params, dtype)
