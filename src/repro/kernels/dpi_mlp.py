"""ML-based deep-packet-inspection Pallas kernel (paper §5.1.2).

A ternary fully-connected network (weights in {-1, 0, +1} with a float
scale, as produced by hls4ml-style quantization) scores every 64-byte
beat of every payload; the per-packet decision is the aggregated max.
On the FPGA this runs at 44 ns/beat beside the packet pipeline; the TPU
dual fuses the three matmuls over a tile of beats in one VMEM-resident
kernel, so the whole MLP is a single HBM round trip (the MXU-friendly
dims are multiples of 64/128).

``train_dpi_params`` trains the float model on synthetic "big-data
payloads vs. executables" (repro.data.dpi_dataset) and ternarizes —
detection quality is benchmarked in benchmarks/fig8_dpi.py.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode
from repro.kernels import ref as R

BLOCK_B = 512           # beats per tile
D_IN, D_H1, D_H2 = R.DPI_DIMS


def _dpi_kernel(beats_ref, w1_ref, b1_ref, w2_ref, b2_ref, w3_ref,
                scales_ref, out_ref):
    # full f32 contractions: at default precision the MXU rounds operands
    # to bfloat16 and the scores leave the reference by ~3e-2 on a v5e
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32,
                            precision=jax.lax.Precision.HIGHEST)
    x = beats_ref[...].astype(jnp.float32) / 128.0 - 1.0     # (BB, 64)
    s = scales_ref[...]                                      # (1, 3)
    h = jnp.maximum(
        dot(x, w1_ref[...].astype(jnp.float32) * s[0, 0]) + b1_ref[...], 0.0)
    h = jnp.maximum(
        dot(h, w2_ref[...].astype(jnp.float32) * s[0, 1]) + b2_ref[...], 0.0)
    y = dot(h, w3_ref[...].astype(jnp.float32) * s[0, 2])
    out_ref[...] = y


@functools.partial(jax.jit, static_argnames=("interpret",))
def dpi_scores_pallas(payload: jax.Array, params: Dict, *,
                      interpret: Optional[bool] = None) -> jax.Array:
    """payload (N, MTU) uint8 -> per-beat scores (N, MTU//64) float32."""
    n, mtu = payload.shape
    beats = mtu // 64
    x = payload.reshape(n * beats, 64).astype(jnp.int32)
    m = x.shape[0]
    pad = (-m) % BLOCK_B
    x = jnp.pad(x, ((0, pad), (0, 0)))
    scales = jnp.stack([params["s1"], params["s2"], params["s3"]]
                       ).astype(jnp.float32)[None, :]
    out = pl.pallas_call(
        _dpi_kernel,
        grid=((m + pad) // BLOCK_B,),
        in_specs=[
            pl.BlockSpec((BLOCK_B, D_IN), lambda i: (i, 0)),
            pl.BlockSpec((D_IN, D_H1), lambda i: (0, 0)),
            pl.BlockSpec((D_H1,), lambda i: (0,)),
            pl.BlockSpec((D_H1, D_H2), lambda i: (0, 0)),
            pl.BlockSpec((D_H2,), lambda i: (0,)),
            pl.BlockSpec((D_H2, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 3), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_B, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m + pad, 1), jnp.float32),
        name="dpi_scores_pallas",
        interpret=interpret_mode(interpret),
    )(x, params["w1"].astype(jnp.int32), params["b1"],
      params["w2"].astype(jnp.int32), params["b2"],
      params["w3"].astype(jnp.int32), scales)
    return out[:m, 0].reshape(n, beats)


dpi_scores_ref = R.dpi_scores_ref


# ---------------------------------------------------------------------------
# Training + ternarization
# ---------------------------------------------------------------------------

def init_dpi_params(key) -> Dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w1": jax.random.normal(k1, (D_IN, D_H1)) * 0.2,
        "b1": jnp.zeros((D_H1,), jnp.float32),
        "w2": jax.random.normal(k2, (D_H1, D_H2)) * 0.2,
        "b2": jnp.zeros((D_H2,), jnp.float32),
        "w3": jax.random.normal(k3, (D_H2, 1)) * 0.2,
        "s1": jnp.asarray(1.0), "s2": jnp.asarray(1.0), "s3": jnp.asarray(1.0),
    }


def _float_forward(p, x):
    h = jax.nn.relu(x @ p["w1"] + p["b1"])
    h = jax.nn.relu(h @ p["w2"] + p["b2"])
    return (h @ p["w3"])[:, 0]


def ternarize(params: Dict) -> Dict:
    """Magnitude-threshold ternarization with per-layer scale (TWN rule:
    threshold = 0.7 * mean|w|, scale = mean|w| over kept entries)."""
    out = {}
    for i, w_name in enumerate(("w1", "w2", "w3"), 1):
        w = np.asarray(params[w_name])
        thr = 0.7 * np.abs(w).mean()
        tern = np.sign(w) * (np.abs(w) > thr)
        kept = np.abs(w[np.abs(w) > thr])
        scale = float(kept.mean()) if kept.size else 1.0
        out[w_name] = jnp.asarray(tern, jnp.int8)
        out[f"s{i}"] = jnp.asarray(scale, jnp.float32)
    out["b1"] = jnp.asarray(params["b1"], jnp.float32)
    out["b2"] = jnp.asarray(params["b2"], jnp.float32)
    return out


def train_dpi_params(beats: np.ndarray, labels: np.ndarray,
                     steps: int = 300, lr: float = 3e-3, seed: int = 0
                     ) -> Dict:
    """beats (M, 64) uint8, labels (M,) {0,1}.  Returns ternary params."""
    x = jnp.asarray(beats, jnp.float32) / 128.0 - 1.0
    y = jnp.asarray(labels, jnp.float32)
    p = init_dpi_params(jax.random.key(seed))

    def loss_fn(p):
        logits = _float_forward(p, x)
        return jnp.mean(jnp.maximum(logits, 0) - logits * y
                        + jnp.log1p(jnp.exp(-jnp.abs(logits))))

    @jax.jit
    def step(p):
        l, g = jax.value_and_grad(loss_fn)(p)
        return jax.tree.map(lambda a, b: a - lr * b, p, g), l

    for _ in range(steps):
        p, l = step(p)
    return ternarize(p)
