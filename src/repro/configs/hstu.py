"""HSTU-large [arXiv:2402.17152] as published for ML-20m: embed 256, 8
blocks, 2 heads, dqk = dv = 128, 128 sampled negatives at temperature 0.05;
at 4,096 positions, the input length of the paper's industrial runs."""
import jax.numpy as jnp

from repro.configs.base import ArchSpec, RECSYS_SHAPES
from repro.models.hstu import HSTUConfig

FULL = HSTUConfig(
    name="hstu", embed_dim=256, n_blocks=8, n_heads=2, dqk=128, dv=128,
    seq_len=4096, item_vocab=26_745, num_negatives=128, temperature=0.05,
)

SMOKE = HSTUConfig(
    name="hstu-smoke", embed_dim=16, n_blocks=2, n_heads=2, dqk=8, dv=8,
    seq_len=16, item_vocab=300, num_negatives=16, compute_dtype=jnp.float32,
)


def spec() -> ArchSpec:
    return ArchSpec("hstu", "recsys", FULL, SMOKE, RECSYS_SHAPES)
