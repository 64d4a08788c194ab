"""Fused service-chain Pallas kernel (beyond-paper optimization).

The paper composes services as separate pipeline stages (AES core, DPI
core, ...), each with its own stream pass.  On TPU the equivalent chain
costs one HBM round trip *per service*; this kernel fuses
AES-ECB-decrypt + ML-DPI scoring into a single VMEM-resident pass —
payload bytes are read from HBM exactly once, decrypted in registers,
scored, and written once: by construction half the HBM traffic of the
two-stage chain on the receiver hot path.  No module on the data path
calls it, and the TPU compiler refuses it (in-kernel gathers and
reshapes): it runs in interpret mode only, off the chip path.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode
from repro.kernels import ref as R
from repro.kernels.ref import DPI_DIMS

BLOCK_N = 16            # packets per tile (x 4096 B = 256 KiB VMEM tile)
D_IN, D_H1, D_H2 = DPI_DIMS


def _fused_kernel(pay_ref, rk_ref, sbox_ref, sidx_ref, w1_ref, b1_ref,
                  w2_ref, b2_ref, w3_ref, scales_ref, out_ref, score_ref):
    pay = pay_ref[...]                       # (BN, MTU) int32 bytes
    bn, mtu = pay.shape
    rk = rk_ref[...]
    inv_sbox = sbox_ref[...]
    iidx = sidx_ref[...]

    # ---- AES-128-ECB decrypt, unrolled rounds (values stay in VMEM) ----
    st = pay.reshape(bn * (mtu // 16), 16)
    st = st ^ rk[10][None, :]
    for r in range(9, 0, -1):
        st = jnp.take(st, iidx, axis=1)
        st = jnp.take(inv_sbox, st, axis=0)
        st = st ^ rk[r][None, :]
        st = R._inv_mix_columns(st)
    st = jnp.take(st, iidx, axis=1)
    st = jnp.take(inv_sbox, st, axis=0)
    st = st ^ rk[0][None, :]
    plain = st.reshape(bn, mtu)
    out_ref[...] = plain

    # ---- DPI on the just-decrypted bytes (no HBM round trip) -----------
    s = scales_ref[...]
    x = plain.reshape(bn * (mtu // 64), 64).astype(jnp.float32) / 128.0 - 1.0
    h = jnp.maximum(
        jnp.dot(x, w1_ref[...].astype(jnp.float32) * s[0, 0],
                preferred_element_type=jnp.float32) + b1_ref[...], 0.0)
    h = jnp.maximum(
        jnp.dot(h, w2_ref[...].astype(jnp.float32) * s[0, 1],
                preferred_element_type=jnp.float32) + b2_ref[...], 0.0)
    y = jnp.dot(h, w3_ref[...].astype(jnp.float32) * s[0, 2],
                preferred_element_type=jnp.float32)
    score_ref[...] = jnp.max(y.reshape(bn, mtu // 64), axis=1)[:, None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_decrypt_dpi_pallas(payload: jax.Array, round_keys,
                             dpi_params: Dict, *,
                             interpret: Optional[bool] = None
                             ) -> Tuple[jax.Array, jax.Array]:
    """payload (N, MTU) uint8 -> (plaintext (N, MTU) uint8, max-beat
    DPI score (N,) float32) in ONE pass."""
    n, mtu = payload.shape
    pad = (-n) % BLOCK_N
    x = jnp.pad(payload, ((0, pad), (0, 0))).astype(jnp.int32)
    rk = jnp.asarray(round_keys).astype(jnp.int32)
    inv_sbox = jnp.asarray(R.INV_SBOX)
    iidx = jnp.asarray(R._INV_SHIFT_IDX)
    scales = jnp.stack([dpi_params["s1"], dpi_params["s2"],
                        dpi_params["s3"]]).astype(jnp.float32)[None, :]
    out, score = pl.pallas_call(
        _fused_kernel,
        grid=((n + pad) // BLOCK_N,),
        in_specs=[
            pl.BlockSpec((BLOCK_N, mtu), lambda i: (i, 0)),
            pl.BlockSpec((11, 16), lambda i: (0, 0)),
            pl.BlockSpec((256,), lambda i: (0,)),
            pl.BlockSpec((16,), lambda i: (0,)),
            pl.BlockSpec((D_IN, D_H1), lambda i: (0, 0)),
            pl.BlockSpec((D_H1,), lambda i: (0,)),
            pl.BlockSpec((D_H1, D_H2), lambda i: (0, 0)),
            pl.BlockSpec((D_H2,), lambda i: (0,)),
            pl.BlockSpec((D_H2, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 3), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_N, mtu), lambda i: (i, 0)),
            pl.BlockSpec((BLOCK_N, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n + pad, mtu), jnp.int32),
            jax.ShapeDtypeStruct((n + pad, 1), jnp.float32),
        ],
        name="fused_decrypt_dpi_pallas",
        interpret=interpret_mode(interpret),
    )(x, rk, inv_sbox, iidx,
      dpi_params["w1"].astype(jnp.int32), dpi_params["b1"],
      dpi_params["w2"].astype(jnp.int32), dpi_params["b2"],
      dpi_params["w3"].astype(jnp.int32), scales)
    return out[:n].astype(jnp.uint8), score[:n, 0]


def fused_decrypt_dpi_tile(payload: jax.Array, round_keys,
                           dpi_params: Dict, *, tile_pkts: int = BLOCK_N,
                           interpret: Optional[bool] = None
                           ) -> Tuple[jax.Array, jax.Array]:
    """Tile-granular streaming entry: run the fused decrypt+DPI pass over
    one fragment tile of at most ``tile_pkts`` packets as it arrives.

    Pads to the fixed ``(tile_pkts, MTU)`` shape so every mid-stream call
    hits one compiled executable (the streaming ingest hands tiles over
    the moment their bytes are acknowledged, including a short final
    tile).  Bit-identical per row to the one-shot ``fused_decrypt_dpi_
    pallas`` — AES and the DPI MLP are row-independent."""
    n = payload.shape[0]
    if n > tile_pkts:
        raise ValueError(f"tile carries {n} packets > tile_pkts={tile_pkts}")
    x = jnp.pad(payload, ((0, tile_pkts - n), (0, 0)))
    out, score = fused_decrypt_dpi_pallas(x, round_keys, dpi_params,
                                          interpret=interpret)
    return out[:n], score[:n]


def fused_decrypt_dpi_ref(payload: jax.Array, round_keys, dpi_params: Dict
                          ) -> Tuple[jax.Array, jax.Array]:
    """Two-pass oracle: decrypt, then DPI-score the plaintext."""
    n, mtu = payload.shape
    blocks = payload.reshape(n * (mtu // 16), 16)
    plain = R.aes_decrypt_ref(blocks, round_keys).reshape(n, mtu)
    scores = R.dpi_scores_ref(plain, dpi_params)
    return plain, jnp.max(scores, axis=1)
