"""Pallas kernels for the compute hot-spots the paper offloads (AES, CRC,
ML-DPI, DLRM preprocessing, collective reduction), each with a pure-jnp
oracle in ``ref.py`` and a public wrapper in ``ops.py``."""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Pallas interpret mode: on for the CPU backend only.  Decided when a
    kernel is called, never at import, so a process whose accelerator
    failed to initialise cannot run the kernels interpreted under a flag
    fixed before the backend was known."""
    return jax.default_backend() == "cpu" if interpret is None else interpret


def split_table(table: np.ndarray) -> np.ndarray:
    """(..., 256) lookup table -> (..., 2, 128) int32 halves for
    ``lane_lookup`` (bit patterns preserved for uint32 tables)."""
    t = np.asarray(table).astype(np.uint32).view(np.int32)
    return t.reshape(t.shape[:-1] + (2, 128))


def lane_lookup(lo: jax.Array, hi: jax.Array, idx: jax.Array) -> jax.Array:
    """``table[idx]`` for a 256-entry table inside a TPU kernel.

    Mosaic gathers only along the 128-lane axis of a 2-D array, so the
    table arrives as its two 128-entry halves ``lo`` and ``hi`` (rows
    broadcastable to ``idx``'s shape: one table for every row, or one
    table per row), each half is lane-gathered, and the index's top bit
    picks the half.  ``idx`` is int32 in ``[0, 256)`` with 128 lanes."""
    low7 = idx & 127

    def gather(half):
        return jnp.take_along_axis(jnp.broadcast_to(half, idx.shape), low7,
                                   axis=1, mode="promise_in_bounds")

    return jnp.where(idx >= 128, gather(hi), gather(lo))
