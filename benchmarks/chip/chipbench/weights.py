"""What every architecture's weights share: the key made from the seed.

Each architecture plug-in (``arch/<model_type>.py``) makes its own
weights from this key, in the layout the program takes, and may keep
large leaves on the host as ``numpy`` arrays; the harness hands them to
the program and to the reference as they are.
"""
from __future__ import annotations

import jax


def seed_key(seed: int):
    """A PRNG key for any non-negative seed, including seeds beyond 32
    bits (the low word seeds the key, the high word is folded in)."""
    if seed < 0:
        raise ValueError("seed must be >= 0")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
