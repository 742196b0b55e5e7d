"""Random streams from a run's `--seed`, which may exceed 32 bits."""
from __future__ import annotations

import numpy as np


def jax_key(seed: int, stream: int = 0):
    """A JAX key from all the bits of `seed` (PRNGKey alone keeps only
    the low 32), split off for one `stream`."""
    import jax
    key = jax.random.PRNGKey(seed % 2**32)
    key = jax.random.fold_in(key, seed // 2**32)
    return jax.random.fold_in(key, stream)


def rng(seed: int, stream: int = 0) -> np.random.Generator:
    return np.random.default_rng([seed, stream])


class Reservoir:
    """A uniform sample of at most `size` items of a stream of unknown
    length, drawn from the seed."""

    def __init__(self, size: int, seed: int, stream: int = 0):
        self.size = size
        self.items = []
        self.seen = 0
        self._rng = rng(seed, stream)

    def offer(self, item) -> None:
        if len(self.items) < self.size:
            self.items.append(item)
        else:
            j = int(self._rng.integers(self.seen + 1))
            if j < self.size:
                self.items[j] = item
        self.seen += 1
