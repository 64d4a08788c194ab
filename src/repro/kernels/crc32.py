"""ICRC / CRC32 Pallas kernel (paper §4.5).

The FPGA meets line rate with three parallel combinational pipelines
(full 512-bit beats, 320-bit partial beats, 32-bit chunks).  The TPU
dual: *slice-by-8* table lookups vectorized across a tile of packets.
Packets ride the 128 lanes and their little-endian 32-bit words the
sublanes (the payload is transposed outside the kernel), so one loop
step reads an aligned (8, 128) window — 32 bytes of 128 packets — and
folds it in four slice-by-8 steps.  Each step is ONE lane gather: the
eight table lookups sit on the eight sublanes, each against its own
table, and an xor tree over the sublanes combines them.  Ragged tails
(plen % 8) take the byte recurrence, masked per packet — the analogue of
the paper's 32-bit-chunk pipeline.

Polynomial: reflected 0xEDB88320 (Ethernet / RoCE ICRC).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode, lane_lookup, split_table
from repro.kernels import ref as R

BLOCK_N = 128           # packets per tile (one per lane)

# sublane k of a slice-by-8 step looks byte k of (crc ^ w0, w1) up in
# table 7 - k; halves stacked as rows [0, 8) low, [8, 16) high
_TABLES = split_table(R.CRC_TABLES8[::-1]).transpose(1, 0, 2).reshape(16, 128)


def _srl(x, n):
    return lax.shift_right_logical(x, jnp.asarray(n, jnp.int32))


def _crc_kernel(words_ref, plen_ref, tabs_ref, out_ref):
    tabs = tabs_ref[...]                             # (16, 128)
    t_lo, t_hi = tabs[0:8], tabs[8:16]               # per-sublane tables
    t0_lo, t0_hi = tabs[7:8], tabs[15:16]            # table 0 (byte step)
    bn = plen_ref.shape[1]
    # Mosaic gathers whole (8, 128) tiles, so the CRC state is carried
    # replicated on all eight sublanes
    plen = jnp.broadcast_to(plen_ref[...], (8, bn))
    sub = lax.broadcasted_iota(jnp.int32, (8, bn), 0)
    byte_shift = 8 * (sub & 3)

    def step(i, crc):
        w = words_ref[pl.ds(pl.multiple_of(i * 8, 8), 8), :]    # (8, BN)
        for s in range(4):
            pos = i * 32 + s * 8
            w0, w1 = w[2 * s:2 * s + 1], w[2 * s + 1:2 * s + 2]
            # ---- fast path: slice-by-8 (all 8 bytes inside the payload)
            word = jnp.where(sub < 4, crc ^ w0, w1)
            g = lane_lookup(t_lo, t_hi, _srl(word, byte_shift) & 0xFF)
            g = g[0:4] ^ g[4:8]
            g = g[0:2] ^ g[2:4]
            fast = jnp.broadcast_to(g[0:1] ^ g[1:2], (8, bn))
            # ---- tail path: byte recurrence, masked per byte
            slow = crc
            for j in range(8):
                byte = _srl(w0 if j < 4 else w1, 8 * (j % 4)) & 0xFF
                nxt = _srl(slow, 8) ^ lane_lookup(t0_lo, t0_hi,
                                                  (slow ^ byte) & 0xFF)
                slow = jnp.where(pos + j < plen, nxt, slow)
            crc = jnp.where(pos + 8 <= plen, fast, slow)
        return crc

    crc0 = jnp.full((8, bn), -1, jnp.int32)
    crc = lax.fori_loop(0, words_ref.shape[0] // 8, step, crc0)
    out_ref[...] = ~crc[0:1]


@functools.partial(jax.jit, static_argnames=("interpret",))
def crc32_pallas(payload: jax.Array, plen: jax.Array, *,
                 interpret: Optional[bool] = None) -> jax.Array:
    """payload (N, MTU) uint8, plen (N,) int32 -> (N,) uint32."""
    n, mtu = payload.shape
    n_p = -(-n // BLOCK_N) * BLOCK_N
    mtu_p = -(-mtu // 32) * 32               # bytes past plen never count
    data = jnp.pad(payload, ((0, n_p - n), (0, mtu_p - mtu)))
    words = lax.bitcast_convert_type(data.reshape(n_p, mtu_p // 4, 4),
                                     jnp.int32).T             # (W, N)
    pl2 = jnp.pad(plen.astype(jnp.int32), (0, n_p - n))[None, :]
    out = pl.pallas_call(
        _crc_kernel,
        grid=(n_p // BLOCK_N,),
        in_specs=[
            pl.BlockSpec((mtu_p // 4, BLOCK_N), lambda i: (0, i)),
            pl.BlockSpec((1, BLOCK_N), lambda i: (0, i)),
            pl.BlockSpec((16, 128), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_N), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n_p), jnp.int32),
        name="crc32_pallas",
        interpret=interpret_mode(interpret),
    )(words, pl2, jnp.asarray(_TABLES))
    return lax.bitcast_convert_type(out[0, :n], jnp.uint32)


crc32_ref = R.crc32_ref
