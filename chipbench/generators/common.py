"""Pieces the traffic generators share: keys from the seed, the check record."""

from __future__ import annotations

def root_key(seed: int):
    """The run's root PRNG key: any seed up to 2**63 maps to its own key."""
    import jax

    seed = int(seed)
    if not 0 <= seed < 2 ** 63:
        raise ValueError(f"seed {seed} outside [0, 2**63)")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def numpy_seed(seed: int, *salt: int):
    """A numpy Generator drawn from the seed (and a salt per use)."""
    import numpy as np

    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  *salt])


class Checks:
    """Numbers compared with their limits; ``correct`` is every one within
    (a number that is not finite fails)."""

    def __init__(self, limits: dict):
        self.limits = limits
        self.items = []

    def add(self, name: str, value):
        lim = self.limits[name]
        self.items.append((name, value, lim))

    @property
    def correct(self) -> bool:
        import math

        return bool(self.items) and all(
            isinstance(v, (int, float)) and math.isfinite(v) and v <= lim
            for _, v, lim in self.items)

    def as_dict(self) -> dict:
        return {n: {"value": v, "limit": lim} for n, v, lim in self.items}
