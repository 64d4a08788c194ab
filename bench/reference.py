"""Plain references the benchmark holds the program to.

They import nothing of the program and take nothing it made: AES-128
(FIPS-197), the ternary DPI MLP with its training, DLRM preprocessing,
and the go-back-N RX header FSM of RoCE v2 (IBTA vol. 1, section 9.7),
written the straightforward way.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

# ================================================================ AES-128

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
# state byte i = row i % 4, column i // 4; ShiftRows rotates row r left by r
_SHIFT = np.array([(i % 4) + 4 * ((i // 4 + i % 4) % 4) for i in range(16)])
_INV_SHIFT = np.array([(i % 4) + 4 * ((i // 4 - i % 4) % 4)
                       for i in range(16)])
_RCON = (0x01, 0x02, 0x04, 0x08, 0x10, 0x20, 0x40, 0x80, 0x1B, 0x36)


def expand_key(key: np.ndarray) -> np.ndarray:
    """(16,) uint8 key -> (11, 16) int32 round keys."""
    w = [np.asarray(key[4 * i:4 * i + 4], np.int32) for i in range(4)]
    for i in range(4, 44):
        t = w[i - 1].copy()
        if i % 4 == 0:
            t = SBOX[np.roll(t, -1)]
            t[0] ^= _RCON[i // 4 - 1]
        w.append(w[i - 4] ^ t)
    return np.stack([np.concatenate(w[4 * r:4 * r + 4]) for r in range(11)])


def _xtime(x):
    return ((x << 1) ^ jnp.where(x & 0x80, 0x1B, 0)) & 0xFF


def _gmul(x, c: int):
    out, p = jnp.zeros_like(x), x
    for bit in range(4):
        if c >> bit & 1:
            out = out ^ p
        p = _xtime(p)
    return out


def _mix(s, coeffs):
    a = s.reshape(s.shape[:-1] + (4, 4))           # (..., column, row)
    rows = [functools.reduce(jnp.bitwise_xor,
                             [_gmul(a[..., (r + k) % 4], coeffs[k])
                              for k in range(4)]) for r in range(4)]
    return jnp.stack(rows, axis=-1).reshape(s.shape)


@jax.jit
def aes_encrypt(blocks: jax.Array, rk: jax.Array) -> jax.Array:
    """(N, 16) uint8 plaintext blocks -> ciphertext, AES-128-ECB."""
    sbox = jnp.asarray(SBOX)
    st = blocks.astype(jnp.int32) ^ rk[0]
    for r in range(1, 11):
        st = sbox[st][:, _SHIFT]
        if r < 10:
            st = _mix(st, (2, 3, 1, 1))
        st = st ^ rk[r]
    return st.astype(jnp.uint8)


@jax.jit
def aes_decrypt(blocks: jax.Array, rk: jax.Array) -> jax.Array:
    """(N, 16) uint8 ciphertext blocks -> plaintext, AES-128-ECB."""
    inv = jnp.asarray(INV_SBOX)
    st = blocks.astype(jnp.int32) ^ rk[10]
    for r in range(9, -1, -1):
        st = inv[st[:, _INV_SHIFT]] ^ rk[r]
        if r > 0:
            st = _mix(st, (14, 11, 13, 9))
    return st.astype(jnp.uint8)


def encrypt_packets(plain: jax.Array, rk: jax.Array) -> jax.Array:
    n, mtu = plain.shape
    return aes_encrypt(plain.reshape(-1, 16), rk).reshape(n, mtu)


# ================================================================== DPI

DPI_DIMS = (64, 128, 64)


@functools.partial(jax.jit, static_argnames=("steps",))
def train_dpi(x_u8, y, key, *, steps: int, lr: float):
    """Full-batch gradient descent of the float 64-128-64-1 MLP on
    labelled beats (logistic loss), then ternarization: weights in
    {-1, 0, 1} above 0.7 mean |w|, one scale per layer (the mean kept
    magnitude).  One jitted call on the device."""
    x = x_u8.astype(jnp.float32) / 128.0 - 1.0
    ks = jax.random.split(key, 3)
    dims = DPI_DIMS + (1,)
    p = {f"w{i + 1}": jax.random.normal(ks[i], dims[i:i + 2]) * 0.2
         for i in range(3)}
    p.update(b1=jnp.zeros(dims[1]), b2=jnp.zeros(dims[2]))

    def loss(p):
        h = jax.nn.relu(x @ p["w1"] + p["b1"])
        h = jax.nn.relu(h @ p["w2"] + p["b2"])
        z = (h @ p["w3"])[:, 0]
        return jnp.mean(jnp.maximum(z, 0) - z * y
                        + jnp.log1p(jnp.exp(-jnp.abs(z))))

    p = jax.lax.fori_loop(
        0, steps, lambda _, p: jax.tree.map(
            lambda a, g: a - lr * g, p, jax.grad(loss)(p)), p)
    out = {"b1": p["b1"], "b2": p["b2"]}
    for i in (1, 2, 3):
        w = p[f"w{i}"]
        mag = jnp.abs(w)
        keep = mag > 0.7 * mag.mean()
        out[f"w{i}"] = (jnp.sign(w) * keep).astype(jnp.int8)
        out[f"s{i}"] = jnp.sum(mag * keep) / jnp.maximum(keep.sum(), 1)
    return out


def _round(x, mantissa_bits: int):
    """``x`` rounded to ``mantissa_bits`` (23 leaves float32 as it is;
    7 is bfloat16).  ``reduce_precision``, which XLA may not drop as it
    may drop a pair of casts."""
    return x if mantissa_bits >= 23 else jax.lax.reduce_precision(
        x, exponent_bits=8, mantissa_bits=mantissa_bits)


@functools.partial(jax.jit, static_argnames=("mantissa_bits",))
def dpi_scores(payload: jax.Array, params: Dict,
               mantissa_bits: int = 23) -> jax.Array:
    """(N, MTU) uint8 -> (N, MTU // 64) float32 beat scores, every dot in
    float32.  ``mantissa_bits`` 7 is the control: each dot's operands
    rounded to bfloat16, as one pass of the MXU takes them, and their
    products summed in float32."""
    n, mtu = payload.shape

    def dot(a, b):
        return jnp.dot(_round(a, mantissa_bits), _round(b, mantissa_bits),
                       precision=jax.lax.Precision.HIGHEST)
    x = payload.reshape(-1, 64).astype(jnp.float32) / 128.0 - 1.0
    h = jax.nn.relu(dot(x, params["w1"].astype(jnp.float32) * params["s1"])
                    + params["b1"])
    h = jax.nn.relu(dot(h, params["w2"].astype(jnp.float32) * params["s2"])
                    + params["b2"])
    y = dot(h, params["w3"].astype(jnp.float32) * params["s3"])
    return y[:, 0].reshape(n, mtu // 64)


def packet_scores(payload, plen, params, mantissa_bits: int = 23):
    """A packet's DPI score: the highest score of its valid beats."""
    s = dpi_scores(payload, params, mantissa_bits)
    valid = jnp.arange(s.shape[1])[None, :] * 64 < plen[:, None]
    return jnp.max(jnp.where(valid, s, -jnp.inf), axis=1)


# ============================================================ preprocess

@functools.partial(jax.jit, static_argnames=("n_dense", "n_sparse",
                                             "modulus", "mantissa_bits"))
def preproc(recs, *, n_dense: int, n_sparse: int, modulus: int,
            mantissa_bits: int = 23):
    """Neg2Zero then log1p on the ``n_dense`` dense features (float32
    bits), the ``n_sparse`` categorical ids after them modulo
    ``modulus``, and the columns after those (the click label) passed
    through.  ``mantissa_bits`` 7 is the control: the input and the
    result of log1p rounded to bfloat16."""
    d = _round(jnp.log1p(_round(jnp.maximum(recs[:, :n_dense], 0)
                                .astype(jnp.float32), mantissa_bits)),
               mantissa_bits)
    d = jax.lax.bitcast_convert_type(d, jnp.int32)
    end = n_dense + n_sparse
    return jnp.concatenate([d, jnp.remainder(recs[:, n_dense:end], modulus),
                            recs[:, end:]], axis=1)


# ============================================================ RX header FSM

_PAYLOAD = {0x06, 0x07, 0x08, 0x0A, 0x0D, 0x0E, 0x0F, 0x10}
_RETH = {0x06, 0x0A, 0x0C, 0x0D, 0x10}
_LAST = {0x08, 0x0A, 0x0F, 0x10}
_HALF = 0x7FFFFF
RX_FIELDS = ("accept", "dup", "ooo", "dropped_credit", "dma_addr", "dma_len",
             "ack_psn", "send_ack", "send_nak")
STATE_FIELDS = ("epsn", "msn", "credits", "cur_vaddr", "acc_cnt")


def rx_go_back_n(hdr: Dict[str, np.ndarray], state: Dict[str, np.ndarray]):
    """Go-back-N receiver, one packet at a time in arrival order.

    A payload packet is accepted when its PSN is the expected one and the
    QP has a credit; it then advances the expected PSN, spends a credit,
    and DMAs to its RETH address (or continues the open message).  A PSN
    behind the expected one is a duplicate (re-ACK), one ahead is out of
    order (NAK).  Returns per-packet outputs and the state after."""
    st = {k: np.array(v, np.int64) for k, v in state.items()}
    h = {k: np.asarray(v).tolist() for k, v in hdr.items()}
    n = len(h["qpn"])
    out = {k: np.zeros(n, np.int64) for k in RX_FIELDS}
    for i in range(n):
        q, op, psn = h["qpn"][i], h["opcode"][i], h["psn"][i]
        plen, valid = h["plen"][i], h["valid"][i] > 0
        payload = op in _PAYLOAD
        epsn = int(st["epsn"][q])
        in_seq = psn == epsn
        behind = (psn - epsn) % (_HALF * 2 + 2) > _HALF
        accept = payload and in_seq and st["credits"][q] > 0 and valid
        addr = h["vaddr"][i] if op in _RETH else int(st["cur_vaddr"][q])
        out["accept"][i] = accept
        out["dup"][i] = behind and payload
        out["ooo"][i] = (not in_seq) and (not behind) and payload
        out["dropped_credit"][i] = (payload and in_seq and valid
                                    and st["credits"][q] <= 0)
        out["dma_addr"][i] = addr
        out["dma_len"][i] = plen
        if accept:
            st["epsn"][q] = (epsn + 1) & (_HALF * 2 + 1)
            st["msn"][q] += op in _LAST
            st["credits"][q] -= 1
            st["cur_vaddr"][q] = addr + plen
            st["acc_cnt"][q] += 1
        out["ack_psn"][i] = psn if accept else \
            (st["epsn"][q] - 1) & (_HALF * 2 + 1)
        out["send_ack"][i] = (accept and (op in _LAST or h["ack_req"][i] > 0)
                              ) or out["dup"][i]
        out["send_nak"][i] = out["ooo"][i]
    return out, st
