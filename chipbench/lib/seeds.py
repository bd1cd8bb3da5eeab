"""Everything a run draws comes from ``--seed`` through here.

``--seed`` may exceed 32 bits, and ``jax.random.PRNGKey`` keeps only the
low 32 bits of a larger seed, so the keys are drawn from a
``numpy.random.SeedSequence`` of the whole seed instead.
"""
from __future__ import annotations

import numpy as np

# one stream per purpose, so adding a draw to one purpose never shifts
# another's
PURPOSES = ("fleet", "serve", "weights", "traffic")


def raw_key(seed: int, purpose: str) -> np.ndarray:
    """A raw threefry key (uint32[2], the layout ``PRNGKey`` returns)."""
    i = PURPOSES.index(purpose)
    words = np.random.SeedSequence([int(seed), i]).generate_state(2)
    return np.asarray(words, np.uint32)


def rng(seed: int, purpose: str) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([int(seed), PURPOSES.index(purpose)]))
