"""The one map from a run's ``--seed`` to the key its weights come from.

The harness hands this key to the program's ``init_lm``; the plain
reference derives its own copy of the weights from the same key. Seeds may
exceed 32 bits, which ``PRNGKey`` alone would silently truncate.
"""
from __future__ import annotations

import jax


def model_key(seed: int) -> jax.Array:
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)
