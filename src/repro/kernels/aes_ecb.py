"""AES-128-ECB Pallas kernel (paper §5.1.1 on-datapath crypto service).

TPU adaptation of the FPGA's 10-stage AES pipeline: instead of one block
per clock through unrolled rounds, the kernel processes a VMEM tile of
16-byte blocks per grid step with the 10 rounds fully unrolled inside
the kernel (static Python loop -> straight-line VPU code).  Blocks ride
eight to a 128-lane row: S-box lookups are lane gathers from the two
halves of the table, ShiftRows and the MixColumns neighbours are lane
permutations, and GF(2^8) math is shift/xor on int32 lanes (the VPU has
no 8-bit lanes, so bytes ride in int32).

Validated against ref.py (which itself is pinned to FIPS-197 vectors in
tests): in interpret mode on the CPU, bit for bit on the chip by
``chip_smoke.py``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode, lane_lookup, split_table
from repro.kernels import ref as R
from repro.kernels.ref import expand_key  # re-export for services

BLOCKS_PER_ROW = 8      # 16-byte blocks per 128-lane row
TILE_ROWS = 512         # rows per VMEM tile: 4096 blocks, 256 KiB of int32


def _lanes(x, src):
    """out[:, j] = x[:, src[:, j]] (a lane permutation)."""
    return jnp.take_along_axis(x, src, axis=1, mode="promise_in_bounds")


def _perms(shape, decrypt: bool):
    """(ShiftRows or InvShiftRows, (rot1, rot2, rot3)) as lane-index
    arrays over ``shape``.  Lane ``16b + r + 4c`` holds byte (row r,
    column c) of block b (the FIPS-197 column-major state); ShiftRows
    rotates row r left by r columns, and rot_k brings row ``r + k`` of
    the same column to row r (the MixColumns neighbours)."""
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    r, c = lane & 3, (lane >> 2) & 3
    col = (c - r if decrypt else c + r) & 3
    shift = (lane - (lane & 15)) + r + 4 * col
    rots = tuple((lane - r) + ((r + k) & 3) for k in (1, 2, 3))
    return shift, rots


def _encrypt_kernel(st_ref, rk_ref, sbox_ref, out_ref):
    st = st_ref[...]                          # (rows, 128) int32 bytes
    rk = rk_ref[...]                          # (11, 128) round keys
    sbox = sbox_ref[...]                      # (2, 128) S-box halves
    shift, (rot1, rot2, rot3) = _perms(st.shape, decrypt=False)
    st = st ^ rk[0:1]
    for r in range(1, 11):
        a = _lanes(lane_lookup(sbox[0:1], sbox[1:2], st), shift)
        if r < 10:
            # MixColumns, row r of a column: 2a_r ^ 3a_r+1 ^ a_r+2 ^ a_r+3
            a2 = R._xt(a)
            a = a2 ^ _lanes(a2 ^ a, rot1) ^ _lanes(a, rot2) ^ _lanes(a, rot3)
        st = a ^ rk[r:r + 1]
    out_ref[...] = st


def _decrypt_kernel(st_ref, rk_ref, sbox_ref, out_ref):
    st = st_ref[...]                          # (rows, 128) int32 bytes
    rk = rk_ref[...]                          # (11, 128) round keys
    inv_sbox = sbox_ref[...]                  # (2, 128) inverse S-box halves
    ishift, (rot1, rot2, rot3) = _perms(st.shape, decrypt=True)
    st = st ^ rk[10:11]
    for r in range(9, -1, -1):
        st = _lanes(st, ishift)
        st = lane_lookup(inv_sbox[0:1], inv_sbox[1:2], st) ^ rk[r:r + 1]
        if r > 0:
            # InvMixColumns: 14a_r ^ 11a_r+1 ^ 13a_r+2 ^ 9a_r+3
            x2 = R._xt(st)
            x4 = R._xt(x2)
            x8 = R._xt(x4)
            st = ((x8 ^ x4 ^ x2) ^ _lanes(x8 ^ x2 ^ st, rot1)
                  ^ _lanes(x8 ^ x4 ^ st, rot2) ^ _lanes(x8 ^ st, rot3))
    out_ref[...] = st


def _lane_round_keys(round_keys) -> jax.Array:
    """(11, 16) round keys -> (11, 128) int32, one copy per block lane."""
    return jnp.tile(jnp.asarray(round_keys).astype(jnp.int32),
                    (1, BLOCKS_PER_ROW))


@functools.partial(jax.jit, static_argnames=("decrypt", "interpret"))
def aes_ecb_pallas(blocks: jax.Array, round_keys, *, decrypt: bool = False,
                   interpret: Optional[bool] = None) -> jax.Array:
    """blocks (N, 16) uint8 -> (N, 16) uint8."""
    n = blocks.shape[0]
    rows = -(-n // BLOCKS_PER_ROW)
    tile = min(TILE_ROWS, -(-rows // 8) * 8)
    rows_p = -(-rows // tile) * tile
    x = jnp.pad(blocks, ((0, rows_p * BLOCKS_PER_ROW - n), (0, 0)))
    x = x.astype(jnp.int32).reshape(rows_p, 128)
    rk = _lane_round_keys(round_keys)
    kernel = _decrypt_kernel if decrypt else _encrypt_kernel
    sbox = jnp.asarray(split_table(R.INV_SBOX if decrypt else R.SBOX))
    out = pl.pallas_call(
        kernel,
        grid=(rows_p // tile,),
        in_specs=[
            pl.BlockSpec((tile, 128), lambda i: (i, 0)),
            pl.BlockSpec((11, 128), lambda i: (0, 0)),
            pl.BlockSpec((2, 128), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((tile, 128), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows_p, 128), jnp.int32),
        name="aes_ecb_pallas",
        interpret=interpret_mode(interpret),
    )(x, rk, sbox)
    return out.reshape(-1, 16)[:n].astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("decrypt",))
def aes_ecb_ref(blocks: jax.Array, round_keys, *, decrypt: bool = False
                ) -> jax.Array:
    if decrypt:
        return R.aes_decrypt_ref(blocks, round_keys)
    return R.aes_encrypt_ref(blocks, round_keys)
