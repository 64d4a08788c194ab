"""Compile the main-path Pallas kernels for a described TPU v5e at
deployment widths — no chip attached.

Interpret mode (every other kernel test) cannot see what the TPU
compiler refuses: gathers Mosaic does not lower, unaligned dynamic
slices, VMEM overruns.  These tests hand each kernel shapes sharded on
one chip of a described ``v5e:2x2`` topology and require the compiled
program to contain the Mosaic kernel (``tpu_custom_call``).

Widths: MTU-sized payloads (4096 B) in a line-rate batch of 8192
packets (32 MiB, ~2.7 ms at 100 Gbit/s); the DLRM records of that batch;
the 8-rank allreduce of 262144 float32 elements.

The topology is described inside a module fixture and only there: the
TPU library may be loaded by one process at a time, and describing it
while modules are imported would give every test worker a different
view of which tests exist.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import aes_ecb, crc32, dpi_mlp, preproc, reduce
from repro.kernels.ref import DPI_DIMS

N_PKTS, MTU = 8192, 4096
D_IN, D_H1, D_H2 = DPI_DIMS


@pytest.fixture(scope="module")
def one_chip(tmp_path_factory):
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # the TPU library logs under /tmp unless given an existing directory
    os.environ.setdefault("TPU_LOG_DIR",
                          str(tmp_path_factory.mktemp("tpu_logs")))
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler here: nothing to compile
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a chipless compile is written to the persistent cache but cannot
    # be read back; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes, **kw):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(lambda *a: fn(*a, **kw)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("decrypt", [False, True])
def test_aes_compiles_for_v5e(one_chip, decrypt):
    _compile(lambda b, rk: aes_ecb.aes_ecb_pallas(b, rk, decrypt=decrypt,
                                                  interpret=False),
             one_chip, ((N_PKTS * MTU // 16, 16), jnp.uint8),
             ((11, 16), jnp.uint8))


def test_crc32_compiles_for_v5e(one_chip):
    _compile(lambda p, n: crc32.crc32_pallas(p, n, interpret=False),
             one_chip, ((N_PKTS, MTU), jnp.uint8), ((N_PKTS,), jnp.int32))


_DPI_SHAPES = (((D_IN, D_H1), jnp.int8), ((D_H1,), jnp.float32),
               ((D_H1, D_H2), jnp.int8), ((D_H2,), jnp.float32),
               ((D_H2, 1), jnp.int8), ((3,), jnp.float32))


def _dpi_params(w1, b1, w2, b2, w3, s):
    return {"w1": w1, "b1": b1, "w2": w2, "b2": b2, "w3": w3,
            "s1": s[0], "s2": s[1], "s3": s[2]}


def test_dpi_compiles_for_v5e(one_chip):
    _compile(lambda pay, *p: dpi_mlp.dpi_scores_pallas(
        pay, _dpi_params(*p), interpret=False),
        one_chip, ((N_PKTS, MTU), jnp.uint8), *_DPI_SHAPES)


def test_preproc_compiles_for_v5e(one_chip):
    n_dense, n_sparse = 13, 26            # Criteo: 13 dense, 26 sparse
    recs = N_PKTS * ((MTU // 4) // (n_dense + n_sparse))
    _compile(lambda r: preproc.preproc_pallas(r, n_dense, 100_000,
                                              interpret=False),
             one_chip, ((recs, n_dense + n_sparse), jnp.int32))


CRITEO = (13, 27, 40_000_000)   # dense, categorical + label, id cap


def test_preproc_packets_compiles_for_v5e(one_chip):
    n_dense, n_sparse, modulus = CRITEO
    _compile(lambda p: preproc.preproc_packets_pallas(
        p, n_dense, n_dense + n_sparse, modulus, interpret=False),
        one_chip, ((N_PKTS, MTU), jnp.uint8))


def test_preproc_service_lowers_without_relayout(one_chip, monkeypatch):
    """The service hands the packet rows to the kernel as they are: no
    concatenate and no uint8 tensor with a minor dimension of 4 (the
    byte-to-word relayout) around the call."""
    from repro.core.services import PreprocService
    monkeypatch.setattr(preproc, "interpret_mode", lambda interpret=None:
                        False if interpret is None else interpret)
    n_dense, n_sparse, modulus = CRITEO
    svc = PreprocService(n_dense=n_dense, n_sparse=n_sparse,
                         modulus=modulus, use_pallas=True)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in (((N_PKTS, MTU), jnp.uint8), ((N_PKTS,), jnp.int32))]
    text = jax.jit(lambda p, n: svc(p, n)).lower(*args).as_text()
    assert "tpu_custom_call" in text
    assert "concatenate" not in text
    assert not re.search(r"x4xui8>", text)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
def test_reduce_compiles_for_v5e(one_chip, dtype):
    _compile(lambda x: reduce.reduce_fold_pallas(x, interpret=False),
             one_chip, ((8, 262144), dtype))
