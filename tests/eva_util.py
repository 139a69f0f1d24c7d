"""What the EVA tests share: the small CPU size of ISSUE 30 (hidden 64, 4
heads of 16, window 32, chunk 4, 2 layers, vocabulary 320) as a configuration
of the `evabyte` family, its seeded weights, the program's net over them and
the family's plain reference."""
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

from benchmark.harness import engine_driver, family  # noqa: E402

CFG = {"model_type": "evabyte", "hidden_size": 64, "num_hidden_layers": 2,
       "num_attention_heads": 4, "num_key_value_heads": 4,
       "intermediate_size": 176, "vocab_size": 320, "rms_norm_eps": 1e-5,
       "norm_add_unit_offset": True, "rope_theta": 100000.0,
       "window_size": 32, "chunk_size": 4, "max_position_embeddings": 512,
       "init_std": 0.02, "fp32_logits": True}
WINDOW, CHUNK, BLOCK = CFG["window_size"], CFG["chunk_size"], 8
# a block of 8 positions: 2 layers x (k, v) x 4 heads x 16 x 4 bytes
BLOCK_BYTES = 2 * 2 * BLOCK * 4 * 16 * 4


def pool_mb(blocks: int, itemsize: int = 4) -> float:
    """MiB that buy exactly `blocks` usable blocks (+1 scratch)."""
    return (blocks + 1) * BLOCK_BYTES * itemsize / 4 / float(1 << 20)


def load(seed: int = 7, dtype: str = "float32"):
    import jax.numpy as jnp
    fam = family.load(REPO, CFG)
    params = fam.weights.make_params(CFG, seed, jnp.dtype(dtype))
    return fam, params, engine_driver.build_net(fam, CFG, params, dtype)
