"""Input blocks drawn from ``--seed``, the DAG's index and the block's name.

The same three always give the same block, so the reference draws again
what the timed path was given, without keeping it.
"""

from __future__ import annotations

import hashlib

import numpy as np


def key_data(seed: int, index: int, names: list[str]) -> np.ndarray:
    """(len(names), 2) uint32 threefry keys; any whole ``seed``."""
    out = np.empty((len(names), 2), np.uint32)
    for i, name in enumerate(names):
        h = hashlib.blake2b(f"{seed}/{index}/{name}".encode(), digest_size=8).digest()
        out[i] = np.frombuffer(h, np.uint32)
    return out


class BlockDrawer:
    """Normal blocks of side ``n``, times ``scale``, drawn on the default
    device in one jitted call per DAG."""

    def __init__(self, n: int, dtype: str = "float32", scale: float = 1.0):
        self.n, self.dtype, self.scale = n, dtype, scale
        self._fns: dict = {}

    def _fn(self, k: int):
        if k not in self._fns:
            import jax

            n, dtype, scale = self.n, self.dtype, self.scale

            def draw(keys):
                return tuple(
                    jax.random.normal(jax.random.wrap_key_data(keys[i]), (n, n), dtype)
                    * scale
                    for i in range(k)
                )

            self._fns[k] = jax.jit(draw)
        return self._fns[k]

    def __call__(self, seed: int, index: int, names: list[str]) -> dict:
        names = sorted(names)
        if not names:
            return {}
        blocks = self._fn(len(names))(key_data(seed, index, names))
        return dict(zip(names, blocks))
