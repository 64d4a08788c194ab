"""Segmented payload-reduction kernel — the math of the collective
subsystem (ring reduce-scatter / allreduce and the in-fabric reduction
offload of ``repro.core.collectives``).

The operation: fold K contribution payloads (rows) into one, summing
element-wise in **row order** — ``((x0 + x1) + x2) + ...`` — a strict
left fold.  Order is part of the contract: float32 addition is
commutative but not associative, and the collective layer's bit-identity
guarantee (ring schedule == switch offload == jnp oracle) holds exactly
because every path folds contributions in the same canonical order.

FPGA -> TPU design dual: on a SmartNIC this is the reduction engine
RecoNIC-style offloads place next to the DMA path, summing streams as
they arrive at line rate; the dual folds a (K, L) batch of payloads with
one jitted kernel — the Pallas variant tiles the element axis across the
grid and runs the K-deep fold in VMEM, the jnp oracle is the same fold
written as ``lax.fori_loop`` (bit-identical, property-tested in
tests/test_kernels.py).

Payloads are wire bytes (uint8); ``chunk_reduce`` bit-casts them to the
collective dtype, folds, and casts back — zero-copy in-graph, exactly
like the preprocessing service handles record words.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

BLOCK_L = 512           # elements per grid tile (f32: 2 KB VMEM per row)

DTYPES = {"float32": jnp.float32, "int32": jnp.int32}


def reduce_fold_ref(x: jax.Array) -> jax.Array:
    """(K, L) -> (L,): strict left fold over rows (the jnp oracle)."""
    def step(i, acc):
        return acc + x[i]
    return jax.lax.fori_loop(1, x.shape[0], step, x[0])


def _fold_kernel(x_ref, o_ref):
    def step(i, acc):                           # rows read from the ref:
        return acc + x_ref[pl.ds(i, 1), :]      # (1, BLOCK_L)

    o_ref[...] = jax.lax.fori_loop(1, x_ref.shape[0], step, x_ref[0:1, :])


@functools.partial(jax.jit, static_argnames=("interpret",))
def reduce_fold_pallas(x: jax.Array, *, interpret: Optional[bool] = None
                       ) -> jax.Array:
    """(K, L) -> (L,): the same left fold, tiled over the element axis.
    Pad lanes compute garbage that is sliced off — rows are folded in
    identical order, so real lanes are bit-identical to the oracle."""
    k, n = x.shape
    pad = (-n) % BLOCK_L
    xp = jnp.pad(x, ((0, 0), (0, pad)))
    out = pl.pallas_call(
        _fold_kernel,
        grid=((n + pad) // BLOCK_L,),
        in_specs=[pl.BlockSpec((k, BLOCK_L), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, BLOCK_L), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n + pad), x.dtype),
        name="reduce_fold_pallas",
        interpret=interpret_mode(interpret),
    )(xp)
    return out[0, :n]


@functools.partial(jax.jit, static_argnames=("dtype", "impl"))
def chunk_reduce(payload: jax.Array, *, dtype: str = "float32",
                 impl: str = "pallas") -> jax.Array:
    """Fold K wire payloads into one: (K, L) uint8 -> (L,) uint8.

    ``L`` must be a multiple of the dtype width (collective chunks are
    element-aligned by construction).  ``dtype`` selects the element
    interpretation; ``impl`` selects the Pallas kernel or the jnp
    oracle (bit-identical either way)."""
    jt = DTYPES[dtype]
    k, nbytes = payload.shape
    width = jnp.dtype(jt).itemsize
    assert nbytes % width == 0, (nbytes, dtype)
    x = jax.lax.bitcast_convert_type(
        payload.reshape(k, nbytes // width, width), jt)
    fold = reduce_fold_pallas if impl == "pallas" else reduce_fold_ref
    folded = fold(x)                                    # (L/width,)
    back = jax.lax.bitcast_convert_type(
        folded.reshape(nbytes // width, 1), jnp.uint8)
    return back.reshape(nbytes)
