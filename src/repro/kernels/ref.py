"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` layer).

These are the ground truth the kernels are allclose-tested against
(tests/test_kernels.py sweeps shapes/dtypes; AES additionally checks
FIPS-197 vectors, CRC32 checks zlib).
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

# ===========================================================================
# AES-128 (FIPS-197)
# ===========================================================================

SBOX = np.array([
    0x63, 0x7c, 0x77, 0x7b, 0xf2, 0x6b, 0x6f, 0xc5, 0x30, 0x01, 0x67, 0x2b,
    0xfe, 0xd7, 0xab, 0x76, 0xca, 0x82, 0xc9, 0x7d, 0xfa, 0x59, 0x47, 0xf0,
    0xad, 0xd4, 0xa2, 0xaf, 0x9c, 0xa4, 0x72, 0xc0, 0xb7, 0xfd, 0x93, 0x26,
    0x36, 0x3f, 0xf7, 0xcc, 0x34, 0xa5, 0xe5, 0xf1, 0x71, 0xd8, 0x31, 0x15,
    0x04, 0xc7, 0x23, 0xc3, 0x18, 0x96, 0x05, 0x9a, 0x07, 0x12, 0x80, 0xe2,
    0xeb, 0x27, 0xb2, 0x75, 0x09, 0x83, 0x2c, 0x1a, 0x1b, 0x6e, 0x5a, 0xa0,
    0x52, 0x3b, 0xd6, 0xb3, 0x29, 0xe3, 0x2f, 0x84, 0x53, 0xd1, 0x00, 0xed,
    0x20, 0xfc, 0xb1, 0x5b, 0x6a, 0xcb, 0xbe, 0x39, 0x4a, 0x4c, 0x58, 0xcf,
    0xd0, 0xef, 0xaa, 0xfb, 0x43, 0x4d, 0x33, 0x85, 0x45, 0xf9, 0x02, 0x7f,
    0x50, 0x3c, 0x9f, 0xa8, 0x51, 0xa3, 0x40, 0x8f, 0x92, 0x9d, 0x38, 0xf5,
    0xbc, 0xb6, 0xda, 0x21, 0x10, 0xff, 0xf3, 0xd2, 0xcd, 0x0c, 0x13, 0xec,
    0x5f, 0x97, 0x44, 0x17, 0xc4, 0xa7, 0x7e, 0x3d, 0x64, 0x5d, 0x19, 0x73,
    0x60, 0x81, 0x4f, 0xdc, 0x22, 0x2a, 0x90, 0x88, 0x46, 0xee, 0xb8, 0x14,
    0xde, 0x5e, 0x0b, 0xdb, 0xe0, 0x32, 0x3a, 0x0a, 0x49, 0x06, 0x24, 0x5c,
    0xc2, 0xd3, 0xac, 0x62, 0x91, 0x95, 0xe4, 0x79, 0xe7, 0xc8, 0x37, 0x6d,
    0x8d, 0xd5, 0x4e, 0xa9, 0x6c, 0x56, 0xf4, 0xea, 0x65, 0x7a, 0xae, 0x08,
    0xba, 0x78, 0x25, 0x2e, 0x1c, 0xa6, 0xb4, 0xc6, 0xe8, 0xdd, 0x74, 0x1f,
    0x4b, 0xbd, 0x8b, 0x8a, 0x70, 0x3e, 0xb5, 0x66, 0x48, 0x03, 0xf6, 0x0e,
    0x61, 0x35, 0x57, 0xb9, 0x86, 0xc1, 0x1d, 0x9e, 0xe1, 0xf8, 0x98, 0x11,
    0x69, 0xd9, 0x8e, 0x94, 0x9b, 0x1e, 0x87, 0xe9, 0xce, 0x55, 0x28, 0xdf,
    0x8c, 0xa1, 0x89, 0x0d, 0xbf, 0xe6, 0x42, 0x68, 0x41, 0x99, 0x2d, 0x0f,
    0xb0, 0x54, 0xbb, 0x16], np.int32)

INV_SBOX = np.zeros(256, np.int32)
INV_SBOX[SBOX] = np.arange(256)

# flat index i = r + 4c (column-major state); ShiftRows: row r rotates
# left by r columns.
_SHIFT_IDX = np.array([(i % 4) + 4 * (((i // 4) + (i % 4)) % 4)
                       for i in range(16)], np.int32)
_INV_SHIFT_IDX = np.array([(i % 4) + 4 * (((i // 4) - (i % 4)) % 4)
                           for i in range(16)], np.int32)

_RCON = np.array([0x01, 0x02, 0x04, 0x08, 0x10,
                  0x20, 0x40, 0x80, 0x1B, 0x36], np.int32)


def expand_key(key: np.ndarray) -> np.ndarray:
    """FIPS-197 key schedule: (16,) uint8 -> (11, 16) uint8 round keys."""
    key = np.asarray(key, np.uint8)
    assert key.shape == (16,)
    w = [key[4 * i:4 * i + 4].astype(np.int32) for i in range(4)]
    for i in range(4, 44):
        t = w[i - 1].copy()
        if i % 4 == 0:
            t = np.roll(t, -1)
            t = SBOX[t]
            t[0] ^= _RCON[i // 4 - 1]
        w.append(w[i - 4] ^ t)
    rk = np.stack([np.concatenate(w[4 * r:4 * r + 4]) for r in range(11)])
    return rk.astype(np.uint8)


def _xt(x):
    """GF(2^8) xtime on int32 lanes."""
    return ((x << 1) ^ jnp.where((x & 0x80) != 0, 0x1B, 0)) & 0xFF


def _mix_columns(s):
    """s: (..., 16) int32 column-major; per column [a0..a3]:
    b0 = 2a0^3a1^a2^a3 etc."""
    a = s.reshape(s.shape[:-1] + (4, 4))      # (..., c, r)
    a0, a1, a2, a3 = a[..., 0], a[..., 1], a[..., 2], a[..., 3]
    b0 = _xt(a0) ^ (_xt(a1) ^ a1) ^ a2 ^ a3
    b1 = a0 ^ _xt(a1) ^ (_xt(a2) ^ a2) ^ a3
    b2 = a0 ^ a1 ^ _xt(a2) ^ (_xt(a3) ^ a3)
    b3 = (_xt(a0) ^ a0) ^ a1 ^ a2 ^ _xt(a3)
    return jnp.stack([b0, b1, b2, b3], axis=-1).reshape(s.shape)


def _inv_mix_columns(s):
    a = s.reshape(s.shape[:-1] + (4, 4))
    a0, a1, a2, a3 = a[..., 0], a[..., 1], a[..., 2], a[..., 3]

    def m(x, c):
        x2 = _xt(x)
        x4 = _xt(x2)
        x8 = _xt(x4)
        out = jnp.zeros_like(x)
        if c & 8:
            out = out ^ x8
        if c & 4:
            out = out ^ x4
        if c & 2:
            out = out ^ x2
        if c & 1:
            out = out ^ x
        return out

    b0 = m(a0, 14) ^ m(a1, 11) ^ m(a2, 13) ^ m(a3, 9)
    b1 = m(a0, 9) ^ m(a1, 14) ^ m(a2, 11) ^ m(a3, 13)
    b2 = m(a0, 13) ^ m(a1, 9) ^ m(a2, 14) ^ m(a3, 11)
    b3 = m(a0, 11) ^ m(a1, 13) ^ m(a2, 9) ^ m(a3, 14)
    return jnp.stack([b0, b1, b2, b3], axis=-1).reshape(s.shape)


def aes_encrypt_ref(blocks: jax.Array, round_keys) -> jax.Array:
    """blocks: (N, 16) uint8; round_keys (11, 16) uint8 -> (N, 16) uint8."""
    sbox = jnp.asarray(SBOX)
    sidx = jnp.asarray(_SHIFT_IDX)
    rk = jnp.asarray(round_keys).astype(jnp.int32)
    st = blocks.astype(jnp.int32)
    st = st ^ rk[0]
    for r in range(1, 10):
        st = sbox[st]
        st = st[:, sidx]
        st = _mix_columns(st)
        st = st ^ rk[r]
    st = sbox[st]
    st = st[:, sidx]
    st = st ^ rk[10]
    return st.astype(jnp.uint8)


def aes_decrypt_ref(blocks: jax.Array, round_keys) -> jax.Array:
    inv_sbox = jnp.asarray(INV_SBOX)
    iidx = jnp.asarray(_INV_SHIFT_IDX)
    rk = jnp.asarray(round_keys).astype(jnp.int32)
    st = blocks.astype(jnp.int32)
    st = st ^ rk[10]
    for r in range(9, 0, -1):
        st = st[:, iidx]
        st = inv_sbox[st]
        st = st ^ rk[r]
        st = _inv_mix_columns(st)
    st = st[:, iidx]
    st = inv_sbox[st]
    st = st ^ rk[0]
    return st.astype(jnp.uint8)


# ===========================================================================
# CRC32 (reflected 0xEDB88320 — Ethernet/RoCE ICRC polynomial)
# ===========================================================================

def _crc_table() -> np.ndarray:
    t = np.zeros(256, np.uint32)
    for i in range(256):
        c = np.uint32(i)
        for _ in range(8):
            c = np.uint32((c >> 1) ^ (0xEDB88320 if (c & 1) else 0))
        t[i] = c
    return t

CRC_TABLE = _crc_table()

# slice-by-8 tables: T[k][b] = crc of byte b advanced by k+1 zero bytes
def _crc_tables8() -> np.ndarray:
    t = np.zeros((8, 256), np.uint32)
    t[0] = CRC_TABLE
    for k in range(1, 8):
        t[k] = (t[k - 1] >> np.uint32(8)) ^ CRC_TABLE[t[k - 1] & 0xFF]
    return t

CRC_TABLES8 = _crc_tables8()


def crc32_ref(payload: jax.Array, plen: jax.Array) -> jax.Array:
    """Per-packet CRC32 over payload[:plen].  payload (N, MTU) uint8,
    plen (N,) int32 -> (N,) uint32."""
    table = jnp.asarray(CRC_TABLE.astype(np.int64)).astype(jnp.uint32)
    data = payload.astype(jnp.uint32)
    n, mtu = payload.shape
    crc0 = jnp.full((n,), 0xFFFFFFFF, jnp.uint32)

    def body(i, crc):
        byte = data[:, i]
        new = (crc >> 8) ^ table[(crc ^ byte) & 0xFF]
        return jnp.where(i < plen, new, crc)

    crc = jax.lax.fori_loop(0, mtu, body, crc0)
    return crc ^ jnp.uint32(0xFFFFFFFF)


# ===========================================================================
# DPI ternary MLP (paper §5.1.2): 64-byte beat -> score
# ===========================================================================

DPI_DIMS = (64, 128, 64)      # input, hidden1, hidden2 (output dim 1)


def dpi_scores_ref(payload: jax.Array, params: Dict) -> jax.Array:
    """payload (N, MTU) uint8 -> per-beat scores (N, MTU//64) float32.

    params: w1 (64,128) int8 ternary, s1 (); w2 (128,64) int8, s2 ();
            w3 (64,1) int8, s3 (); biases b1,b2 float32."""
    n, mtu = payload.shape
    beats = mtu // 64
    # full f32 matmuls: the TPU's default f32 precision rounds operands
    # to bfloat16, which no kernel could be held to
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    x = payload.reshape(n * beats, 64).astype(jnp.float32) / 128.0 - 1.0
    h = jax.nn.relu(dot(x, params["w1"].astype(jnp.float32) * params["s1"])
                    + params["b1"])
    h = jax.nn.relu(dot(h, params["w2"].astype(jnp.float32) * params["s2"])
                    + params["b2"])
    y = dot(h, params["w3"].astype(jnp.float32) * params["s3"])
    return y[:, 0].reshape(n, beats)


# ===========================================================================
# DLRM preprocessing (paper §8.1): Neg2Zero -> Log (dense), Modulus (sparse)
# ===========================================================================

def preproc_ref(recs: jax.Array, n_dense: int, modulus: int) -> jax.Array:
    """recs (M, n_dense+n_sparse) int32.  Dense part: clip negatives to
    zero then log1p, stored as float32 bit pattern; sparse part: value
    mod ``modulus`` (non-negative)."""
    dense = recs[:, :n_dense]
    sparse = recs[:, n_dense:]
    d = jnp.log1p(jnp.maximum(dense.astype(jnp.float32), 0.0))
    d_bits = jax.lax.bitcast_convert_type(d, jnp.int32)
    s = jnp.remainder(sparse, modulus)
    return jnp.concatenate([d_bits, s], axis=1)
