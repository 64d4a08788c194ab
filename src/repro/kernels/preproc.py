"""DLRM preprocessing Pallas kernels (paper §8.1).

Fuses the paper's three stateless operators into one pass:
  Neg2Zero  — clip negative dense features to zero
  Logarithm — log1p on dense features (large-value compression)
  Modulus   — restrict sparse feature range for the embedding tables

The FPGA achieves II=1 deep pipelines over 64-byte beats; the TPU dual
is a single elementwise kernel — one HBM read, one write, zero
intermediate traffic (vs. three separate ops).  Two entries share the
elementwise semantics (``preproc_words``):

  * ``preproc_packets_pallas`` over (N, MTU) uint8 packet rows as they
    arrive: a word's treatment depends only on its position in the
    packet, so the kernel assembles the little-endian words in VMEM,
    transforms them and splits them back into bytes, with no record
    relayout in HBM around the call;
  * ``preproc_pallas`` over (M, record) int32 records, for tiles that
    are records already (the streaming ingest's tile decoder).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode
from repro.kernels import ref as R

BLOCK_M = 512
PKT_ROWS = 256        # packet rows per grid step: 1 MiB of a 4 KiB MTU
SUB_ROWS = 32         # rows of one (32, 128) uint8 tile, the inner step
LANES = 128


def preproc_words(x, dense, sparse, modulus: int):
    """int32 words ``x``: float32 bits of ``log1p(max(x, 0))`` where
    ``dense``, ``x mod modulus`` (non-negative) where ``sparse``, ``x``
    elsewhere.  The masks broadcast against ``x``."""
    d = jnp.log1p(jnp.maximum(x.astype(jnp.float32), 0.0))
    d_bits = jax.lax.bitcast_convert_type(d, jnp.int32)
    s = jnp.remainder(x, modulus)
    return jnp.where(dense, d_bits, jnp.where(sparse, s, x))


def _preproc_kernel(recs_ref, out_ref, *, n_dense: int, modulus: int):
    recs = recs_ref[...]                        # (BM, RW) int32
    col = jax.lax.broadcasted_iota(jnp.int32, recs.shape, 1)
    out_ref[...] = preproc_words(recs, col < n_dense, col >= n_dense,
                                 modulus)


@functools.partial(jax.jit, static_argnames=("n_dense", "modulus",
                                             "interpret"))
def preproc_pallas(recs: jax.Array, n_dense: int, modulus: int, *,
                   interpret: Optional[bool] = None) -> jax.Array:
    """recs (M, RW) int32 -> (M, RW) int32 (dense part = f32 bits)."""
    m, rw = recs.shape
    pad = (-m) % BLOCK_M
    x = jnp.pad(recs, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_preproc_kernel, n_dense=n_dense, modulus=modulus),
        grid=((m + pad) // BLOCK_M,),
        in_specs=[pl.BlockSpec((BLOCK_M, rw), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((BLOCK_M, rw), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m + pad, rw), jnp.int32),
        name="preproc_pallas",
        interpret=interpret_mode(interpret),
    )(x)
    return out[:m]


def _packets_kernel(pay_ref, out_ref, *, n_dense: int, rec_words: int,
                    modulus: int):
    """One (rows, MTU) block of packet rows, 128 bytes of 32 rows at a
    time.  Bytes ride in int32 lanes (the VPU has no 8-bit lanes): lane
    ``j`` of a row holds byte ``j``, so the word of bytes ``4k..4k+3``
    is assembled in lane ``4k`` by two lane rotations, and the result's
    bytes are spread back over lanes ``4k..4k+3`` by two more.  Words
    never straddle a 128-byte group, so the rotations stay inside it."""
    rows, mtu = pay_ref.shape
    rec_end = (mtu // 4) // rec_words * rec_words   # words in whole records
    for c0 in range(0, mtu, LANES):
        w = min(LANES, mtu - c0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (SUB_ROWS, w), 1)
        word = (c0 + lane) >> 2
        col = word % rec_words
        dense = (word < rec_end) & (col < n_dense)
        sparse = (word < rec_end) & (col >= n_dense)
        odd, high = (lane & 1) != 0, (lane & 2) != 0

        def step(r, carry):
            sl = (pl.ds(pl.multiple_of(r * SUB_ROWS, SUB_ROWS), SUB_ROWS),
                  slice(c0, c0 + w))
            b = pay_ref[sl].astype(jnp.int32)
            # lane j: bytes j, j+1, then j..j+3 (little-endian)
            h = b | (pltpu.roll(b, w - 1, 1) << 8)
            x = h | (pltpu.roll(h, w - 2, 1) << 16)
            y = preproc_words(x, dense, sparse, modulus)
            # lanes 4k+2, 4k+3 take word 4k's upper half, then odd lanes
            # the next byte of their even neighbour
            y = jnp.where(high, pltpu.roll(y >> 16, 2, 1), y)
            y = jnp.where(odd, pltpu.roll(y >> 8, 1, 1), y)
            out_ref[sl] = y.astype(jnp.uint8)
            return carry

        jax.lax.fori_loop(0, rows // SUB_ROWS, step, 0)


@functools.partial(jax.jit, static_argnames=("n_dense", "rec_words",
                                             "modulus", "interpret"))
def preproc_packets_pallas(payload: jax.Array, n_dense: int, rec_words: int,
                           modulus: int, *,
                           interpret: Optional[bool] = None) -> jax.Array:
    """payload (N, MTU) uint8 packet rows -> (N, MTU) uint8.  Word ``w``
    (int32, little-endian) below ``n_rec * rec_words``, ``n_rec`` the
    whole records a row holds, is column ``w % rec_words`` of a record:
    dense below ``n_dense``, Modulus from there on; the tail words pass
    through.  Rows are independent."""
    n, mtu = payload.shape
    rows = min(PKT_ROWS, -(-n // SUB_ROWS) * SUB_ROWS)
    pad = (-n) % rows
    x = jnp.pad(payload, ((0, pad), (0, 0))) if pad else payload
    out = pl.pallas_call(
        functools.partial(_packets_kernel, n_dense=n_dense,
                          rec_words=rec_words, modulus=modulus),
        grid=((n + pad) // rows,),
        in_specs=[pl.BlockSpec((rows, mtu), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, mtu), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n + pad, mtu), jnp.uint8),
        name="preproc_pallas",
        interpret=interpret_mode(interpret),
    )(x)
    return out[:n] if pad else out


@functools.partial(jax.jit, static_argnames=("n_dense", "rec_words",
                                             "modulus"))
def preproc_packets_ref(payload: jax.Array, n_dense: int, rec_words: int,
                        modulus: int) -> jax.Array:
    """The oracle of ``preproc_packets_pallas``: decode the rows' whole
    records, ``preproc_ref`` them, encode them back before the tail."""
    n, mtu = payload.shape
    words = mtu // 4
    n_rec = words // rec_words
    x = jax.lax.bitcast_convert_type(
        payload[:, :4 * words].reshape(n, words, 4), jnp.int32)
    recs = x[:, :n_rec * rec_words].reshape(n * n_rec, rec_words)
    out = R.preproc_ref(recs, n_dense, modulus).reshape(n, n_rec * rec_words)
    out_bytes = jax.lax.bitcast_convert_type(out, jnp.uint8).reshape(n, -1)
    return jnp.concatenate([out_bytes, payload[:, out_bytes.shape[1]:]],
                           axis=1)


def preproc_tile(recs: jax.Array, n_dense: int, modulus: int, *,
                 tile_recs: int = BLOCK_M,
                 interpret: Optional[bool] = None) -> jax.Array:
    """Tile-granular streaming entry: preprocess one fragment tile of at
    most ``tile_recs`` records the moment its bytes are acknowledged.

    A streaming ingest hands tiles over mid-transfer, so the tile is
    padded to the fixed ``(tile_recs, record)`` shape before entering the
    jitted kernel — every mid-stream call reuses ONE compiled executable
    regardless of how many records the final (short) tile carries.
    Numerics are identical to the one-shot ``preproc_pallas`` over the
    same rows (same kernel, element-wise), which is what lets streamed
    output be diffed bit-for-bit against the one-shot oracle."""
    n = recs.shape[0]
    if n > tile_recs:
        raise ValueError(f"tile carries {n} records > tile_recs={tile_recs}")
    x = jnp.pad(recs, ((0, tile_recs - n), (0, 0)))
    out = preproc_pallas(x, n_dense, modulus, interpret=interpret)
    return out[:n]


preproc_ref = R.preproc_ref
