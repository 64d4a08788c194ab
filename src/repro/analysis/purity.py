"""Trace-purity pass: walk the closed jaxprs of every jitted data-plane
entry point and flag anything that would drag the device graph back to
the host or silently widen it.

Per entry point (RX/TX pipelines in both engines and both rx_modes,
every public kernel wrapper, the fused service chain, the collectives
fold) the pass traces with small representative arguments and checks:

* ``host-callback``  — ``pure_callback``/``io_callback``/
  ``debug_callback`` primitives anywhere in the (recursively nested)
  jaxpr: a host round-trip per invocation;
* ``f64-promotion``  — any float64 intermediate (the data plane is
  int32/float32; an f64 doubles bandwidth and diverges across
  backends);
* ``missing-donation`` — state-carrying entry points (the four
  pipeline engines, whose first argument is the carried table state)
  that do not donate their input buffers: each call copies the whole
  table set (ROADMAP item 2's fused core needs donation to be
  alloc-free per epoch);
* ``concretization`` — tracing itself raises a concretization error
  (a data-dependent Python branch snuck into the graph).

The registry below IS the inventory of jitted entry points; adding a
data-plane entry without registering it here is what code review is
for.
"""
from __future__ import annotations

import dataclasses
import inspect
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.analysis.violations import Violation, relpath

CALLBACK_PRIMITIVES = {"pure_callback", "io_callback", "debug_callback",
                       "callback"}


@dataclasses.dataclass
class EntryPoint:
    """One jitted data-plane entry: ``fn(*args())`` must trace."""
    name: str
    fn: Callable
    args: Callable[[], Tuple[tuple, dict]]
    carries_state: bool = False    # first arg is carried state -> must donate
    site: Optional[Callable] = None   # def site to report (when fn wraps)


def _def_site(fn: Callable) -> Tuple[str, int]:
    target = inspect.unwrap(fn)
    target = getattr(target, "__wrapped__", target)
    try:
        path = inspect.getsourcefile(target) or "<unknown>"
        _, line = inspect.getsourcelines(target)
    except (OSError, TypeError):
        return "<unknown>", 0
    return relpath(path), line


# --------------------------------------------------------------------------
# entry-point registry (small, fixed-seed example arguments)
# --------------------------------------------------------------------------

def _rx_args(sr: int):
    def build():
        import jax.numpy as jnp
        from repro.core import packet as pk
        from repro.core import pipeline as pipe
        tables = pipe.make_rx_tables(4)
        if sr:
            tables = tables._replace(sr=jnp.ones(4, jnp.int32))
        pkts = [pk.Packet(opcode=pk.WRITE_ONLY, qpn=q, psn=0, dma_len=64,
                          payload=np.zeros(64, np.uint8), ack_req=True)
                for q in range(4)]
        batch_np = pk.batch_from_packets(pkts)
        batch = {k: jnp.asarray(v) for k, v in batch_np.items()
                 if k != "payload"}
        return (tables, batch), {}
    return build


def _tx_args():
    import jax.numpy as jnp
    from repro.core import pipeline as pipe
    tables = pipe.make_tx_tables(4)
    cmds = {"qpn": jnp.asarray([0, 1, 2, 3], jnp.int32),
            "n_pkts": jnp.asarray([2, 1, 3, 1], jnp.int32)}
    return (tables, cmds), {}


def _payload(n=4, mtu=4096):
    import jax.numpy as jnp
    rng = np.random.default_rng(3)
    return jnp.asarray(rng.integers(0, 256, (n, mtu), dtype=np.uint8))


def _round_keys():
    from repro.kernels.ref import expand_key
    rng = np.random.default_rng(5)
    return expand_key(rng.integers(0, 256, 16, dtype=np.uint8))


def _dpi_params():
    from repro.kernels.dpi_mlp import init_dpi_params, ternarize
    return ternarize(init_dpi_params(jax.random.key(7)))


def registry() -> List[EntryPoint]:
    import jax.numpy as jnp
    from repro.core import pipeline as pipe
    from repro.kernels import fused_chain, ops, preproc as pre, reduce as red

    def aes_args():
        import jax.numpy as jnp
        rng = np.random.default_rng(3)
        blocks = jnp.asarray(rng.integers(0, 256, (8, 16), dtype=np.uint8))
        return (blocks, _round_keys()), {}

    def crc_args():
        pay = _payload()
        plen = jnp.asarray([64, 128, 4096, 1], jnp.int32)
        return (pay, plen), {}

    def dpi_args():
        return (_payload(), _dpi_params()), {}

    def preproc_args():
        rng = np.random.default_rng(9)
        recs = jnp.asarray(rng.integers(0, 1 << 20, (16, 39),
                                        dtype=np.int32))
        return (recs,), {}

    def packets_args():
        return (_payload(),), {}

    def fused_args():
        return (_payload(), _round_keys(), _dpi_params()), {}

    def fold_args():
        rng = np.random.default_rng(11)
        x = jnp.asarray(rng.standard_normal((4, 512)).astype(np.float32))
        return (x,), {}

    def chunk_args():
        rng = np.random.default_rng(12)
        pay = jnp.asarray(rng.integers(0, 256, (4, 512), dtype=np.uint8))
        return (pay,), {}

    eps = [
        EntryPoint("rx_pipeline[gbn]", pipe.rx_pipeline, _rx_args(0),
                   carries_state=True),
        EntryPoint("rx_pipeline[sr]", pipe.rx_pipeline, _rx_args(1),
                   carries_state=True),
        EntryPoint("rx_pipeline_batched[gbn]", pipe.rx_pipeline_batched,
                   _rx_args(0), carries_state=True),
        EntryPoint("rx_pipeline_batched[sr]", pipe.rx_pipeline_batched,
                   _rx_args(1), carries_state=True),
        EntryPoint("tx_pipeline", pipe.tx_pipeline, lambda: _tx_args(),
                   carries_state=True),
        EntryPoint("tx_pipeline_batched", pipe.tx_pipeline_batched,
                   lambda: _tx_args(), carries_state=True),
        EntryPoint("kernels.aes_ecb[pallas]",
                   lambda b, rk: ops.aes_ecb(b, rk, impl="pallas"),
                   aes_args, site=ops.aes_ecb),
        EntryPoint("kernels.crc32[pallas]",
                   lambda p, n: ops.crc32(p, n, impl="pallas"), crc_args,
                   site=ops.crc32),
        EntryPoint("kernels.dpi_scores[pallas]",
                   lambda p, w: ops.dpi_scores(p, w, impl="pallas"),
                   dpi_args, site=ops.dpi_scores),
        # n_dense/modulus/tile_recs are Python-static config (callers
        # close over them) — trace them closed so only arrays are traced
        EntryPoint("kernels.preproc[pallas]",
                   lambda r: ops.preproc(r, 13, 100_000, impl="pallas"),
                   preproc_args, site=ops.preproc),
        EntryPoint("kernels.preproc_packets_pallas",
                   lambda p: pre.preproc_packets_pallas(p, 13, 40,
                                                        40_000_000),
                   packets_args, site=pre.preproc_packets_pallas),
        EntryPoint("kernels.preproc_tile",
                   lambda r: ops.preproc_tile(r, 13, 100_000,
                                              tile_recs=32),
                   preproc_args, site=ops.preproc_tile),
        EntryPoint("kernels.chunk_reduce[pallas]",
                   lambda p: ops.chunk_reduce(p, impl="pallas"),
                   chunk_args, site=ops.chunk_reduce),
        EntryPoint("kernels.fused_decrypt_dpi_pallas",
                   fused_chain.fused_decrypt_dpi_pallas, fused_args),
        EntryPoint("kernels.fused_decrypt_dpi_tile",
                   fused_chain.fused_decrypt_dpi_tile, fused_args),
        EntryPoint("kernels.reduce_fold_ref", red.reduce_fold_ref,
                   fold_args),
        EntryPoint("kernels.reduce_fold_pallas", red.reduce_fold_pallas,
                   fold_args),
    ]
    eps.append(_fused_epoch_entry())
    return eps


def _fused_epoch_entry() -> EntryPoint:
    """The fused epoch core (ROADMAP item 2): pack a minimal star world
    and register its jitted blob->blob epoch function.  carries_state
    pins the donated-carry contract — the whole point of the fused core
    is ONE donated input buffer per epoch, so losing the donation
    annotation is a regression balint must catch."""
    import jax.numpy as jnp
    from repro.core import fused as fz
    from repro.core import netsim
    from repro.core.rdma import RdmaNode

    cfg = netsim.FabricConfig(port_bandwidth=2, port_delay=2,
                              queue_capacity=16, seed=3)
    fab = netsim.SwitchedFabric(2, cfg)
    recv = RdmaNode(0, fab, n_qps=8)
    snd = RdmaNode(1, fab, n_qps=8)
    qpn, _, _ = snd.init_rdma(4096, recv)
    snd.rdma_write(qpn, np.zeros(1024, np.uint8))
    world = fz.try_pack([recv, snd], 64, 8, None)
    assert world is not None, "canonical star world must be fusable"
    return EntryPoint("fused.epoch[star-gbn]",
                      fz.make_epoch_fn(world.skey),
                      lambda: ((jnp.asarray(world.vec0),), {}),
                      carries_state=True, site=fz.make_epoch_fn)


# --------------------------------------------------------------------------
# jaxpr walking
# --------------------------------------------------------------------------

def iter_eqns(jaxpr):
    """Yield every eqn in ``jaxpr`` and in every nested sub-jaxpr
    (pjit / scan / while / cond / pallas_call bodies)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from iter_eqns(sub)


def _avals(eqn):
    for v in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "dtype"):
            yield aval


def check_entry(ep: EntryPoint) -> List[Violation]:
    path, line = _def_site(ep.site or ep.fn)
    out: List[Violation] = []
    try:
        args, kwargs = ep.args()
        closed = jax.make_jaxpr(ep.fn)(*args, **kwargs)
    except Exception as e:      # noqa: BLE001 — tracing failures are findings
        kind = type(e).__name__
        if "Concretization" in kind or "TracerBool" in kind \
                or "TracerInteger" in kind:
            out.append(Violation(
                "concretization", path, line,
                f"entry `{ep.name}` fails to trace: {kind}"))
        else:
            out.append(Violation(
                "concretization", path, line,
                f"entry `{ep.name}` raised {kind} during tracing"))
        return out

    callbacks = sorted({e.primitive.name for e in iter_eqns(closed.jaxpr)
                        if e.primitive.name in CALLBACK_PRIMITIVES})
    if callbacks:
        out.append(Violation(
            "host-callback", path, line,
            f"entry `{ep.name}` embeds host callback(s) "
            f"{callbacks} — one device->host round-trip per call"))

    f64 = sorted({e.primitive.name for e in iter_eqns(closed.jaxpr)
                  if any(str(a.dtype) == "float64" for a in _avals(e))})
    if f64:
        out.append(Violation(
            "f64-promotion", path, line,
            f"entry `{ep.name}` carries float64 through {f64}"))

    if ep.carries_state and not _donates(ep, args, kwargs):
        out.append(Violation(
            "missing-donation", path, line,
            f"entry `{ep.name}` does not donate its carried table "
            "state — every call reallocates the full table set"))
    return out


def _donates(ep: EntryPoint, args, kwargs) -> bool:
    """True when the jitted entry point donates at least one input
    buffer (CPU ignores donation at run time but the lowering still
    records donor annotations, so this works on every backend)."""
    lower = getattr(ep.fn, "lower", None)
    if lower is None:
        return False
    try:
        text = lower(*args, **kwargs).as_text()
    except Exception:           # noqa: BLE001
        return False
    return "jax.buffer_donor" in text or "tf.aliasing_output" in text


def run(names: Optional[List[str]] = None) -> List[Violation]:
    out: List[Violation] = []
    for ep in registry():
        if names is not None and ep.name not in names:
            continue
        out.extend(check_entry(ep))
    return out
