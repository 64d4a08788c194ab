"""Service chain (paper §5): protocol enhancements attached to the
datapath.

Two placements, exactly as Fig. 1:
  * OnPathService     — transforms the payload stream in-line (①, e.g.
                        AES); its latency adds, its throughput must hold
                        line rate.
  * ParallelPathService — observes a multiplexed copy and feeds a
                        decision back to the pipeline (②, e.g. ML-DPI);
                        its latency must hide behind the packet pipeline.

FPGA -> TPU design dual: the FPGA attaches services as streaming
kernels on the AXI payload bus, one word per cycle at line rate; here
payload batches are (N, MTU) uint8 arrays and the whole chain compiles
to one jitted function — "deep pipeline" becomes "fused batch kernel" —
with each service backed by a Pallas kernel plus a pure-jnp oracle.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class OnPathService:
    """Payload transformer: (N, MTU) uint8 -> (N, MTU) uint8.  Row
    independent: row i of the output depends on row i of the input
    only (the RX path runs the chain on a zero-padded batch and keeps
    the first N rows)."""
    name = "identity"

    def __call__(self, payload: jax.Array, plen: jax.Array) -> jax.Array:
        return payload


class ParallelPathService:
    """Payload inspector: (N, MTU) uint8 -> (N,) int32 flags.  Row
    independent, as ``OnPathService``."""
    name = "null-inspect"

    def __call__(self, payload: jax.Array, plen: jax.Array) -> jax.Array:
        return jnp.zeros(payload.shape[0], jnp.int32)


def _default_pallas() -> bool:
    """Pallas kernels target TPU; on the CPU container they run in
    interpret mode (a Python loop over grid steps) which is for
    correctness only — timing-sensitive paths use the XLA-compiled jnp
    oracle instead."""
    return jax.default_backend() != "cpu"


@dataclasses.dataclass
class AesService(OnPathService):
    """AES-128-ECB on the payload stream (paper §5.1.1).  Keys are
    exchanged out-of-band at QP setup; ECB blocks are independent, so the
    stream pipelines with zero throughput cost."""
    key: np.ndarray = None            # (16,) uint8
    decrypt: bool = False
    use_pallas: bool = dataclasses.field(default_factory=_default_pallas)
    name: str = "aes-ecb"

    def __post_init__(self):
        from repro.kernels import aes_ecb as ops
        self._round_keys = ops.expand_key(np.asarray(self.key, np.uint8))

    def __call__(self, payload: jax.Array, plen: jax.Array) -> jax.Array:
        from repro.kernels import aes_ecb as ops
        fn = ops.aes_ecb_pallas if self.use_pallas else ops.aes_ecb_ref
        n, mtu = payload.shape
        blocks = payload.reshape(n * (mtu // 16), 16)
        out = fn(blocks, self._round_keys, decrypt=self.decrypt)
        return out.reshape(n, mtu)


@dataclasses.dataclass
class DpiService(ParallelPathService):
    """ML-based deep packet inspection (paper §5.1.2): a ternary
    fully-connected net scores every 64-byte beat; per-packet flags are
    the aggregated decision, fed back into the host-directed command."""
    params: Dict = None               # ternary MLP weights
    # decision margin over the max beat score; calibrated so benign
    # big-data payloads (max score <~0.7) never fire while fully or
    # partially embedded executables (>~1.8) do — the paper's
    # "fine-grained differentiation policy based on the ML decisions".
    threshold: float = 1.0
    use_pallas: bool = dataclasses.field(default_factory=_default_pallas)
    name: str = "ml-dpi"

    def __call__(self, payload: jax.Array, plen: jax.Array) -> jax.Array:
        from repro.kernels import dpi_mlp as ops
        fn = ops.dpi_scores_pallas if self.use_pallas else ops.dpi_scores_ref
        scores = fn(payload, self.params)           # (N, beats)
        beats = payload.shape[1] // 64
        beat_valid = (jnp.arange(beats)[None, :] * 64) < plen[:, None]
        agg = jnp.max(jnp.where(beat_valid, scores, -jnp.inf), axis=1)
        return (agg > self.threshold).astype(jnp.int32)


@dataclasses.dataclass
class PreprocService(OnPathService):
    """DLRM preprocessing offload (paper §8.1): Neg2Zero -> Log on dense
    features, Modulus on sparse features, at line rate on the stream.

    Layout contract: each packet row carries ``n_rec = (MTU // 4) //
    rec_words`` whole records (``rec_words = n_dense + n_sparse`` int32
    little-endian words, dense columns first) and then a tail.  Word
    ``w`` below ``n_rec * rec_words`` is column ``w % rec_words`` of a
    record: float32 bits of ``log1p(max(x, 0))`` below ``n_dense``,
    ``x mod modulus`` from there on; the tail bytes pass through.  Rows
    are independent and a word's treatment depends only on its position,
    so the kernel transforms the packet rows as they arrive, with no
    record relayout around it."""
    n_dense: int = 13
    n_sparse: int = 26
    modulus: int = 100_000
    use_pallas: bool = dataclasses.field(default_factory=_default_pallas)
    name: str = "dlrm-preproc"

    def __call__(self, payload: jax.Array, plen: jax.Array) -> jax.Array:
        from repro.kernels import preproc as ops
        fn = (ops.preproc_packets_pallas if self.use_pallas
              else ops.preproc_packets_ref)
        return fn(payload, self.n_dense, self.n_dense + self.n_sparse,
                  self.modulus)


@dataclasses.dataclass
class CrcService(ParallelPathService):
    """ICRC verification (paper §4.5) as a parallel-path check: flags
    payloads whose CRC32 does not match the attached checksum."""
    use_pallas: bool = dataclasses.field(default_factory=_default_pallas)
    name: str = "icrc"

    def __call__(self, payload: jax.Array, plen: jax.Array) -> jax.Array:
        from repro.kernels import crc32 as ops
        fn = ops.crc32_pallas if self.use_pallas else ops.crc32_ref
        return fn(payload, plen).astype(jnp.int32)


class ServiceChain:
    """Composable datapath: on-path services apply in order; parallel-path
    services run on a multiplexed copy and merge decision flags into the
    host-directed command.  ``process`` is one jitted function over the
    packet batch.

    Placement matters (paper Fig. 1): ``parallel`` inspectors tap the
    stream as it arrives (before on-path transforms — e.g. ICRC over the
    wire bytes); ``parallel_after`` inspectors tap it after the on-path
    services (e.g. DPI over the *decrypted* payload of an encrypted
    flow).

    Every service must be row independent: ``RdmaNode`` hands the chain
    its RX batch padded with zero rows to a power-of-two multiple of
    ``rdma.RX_PAD`` (so the chain compiles once per padded size, not
    once per packet count) and keeps the first N rows of the result."""

    MAX_INSPECTORS = 32          # decision flags pack into one 32-bit word

    def __init__(self, on_path: Sequence[OnPathService] = (),
                 parallel: Sequence[ParallelPathService] = (),
                 parallel_after: Sequence[ParallelPathService] = ()):
        self.on_path = list(on_path)
        self.parallel = list(parallel)
        self.parallel_after = list(parallel_after)
        inspectors = self.parallel + self.parallel_after
        if len(inspectors) > self.MAX_INSPECTORS:
            raise ValueError(
                f"{len(inspectors)} parallel-path inspectors; the "
                f"host-directed command carries at most "
                f"{self.MAX_INSPECTORS} decision flag bits")
        # explicit flag-bit layout: bit i belongs to inspectors[i]
        # (pre-transform taps first, then post-transform taps), exposed
        # by *name* so consumers never depend on insertion order.  Bits
        # are assigned by position, so the same inspector instance
        # tapping both placements gets two distinct bits.
        self._par_bits = list(range(len(self.parallel)))
        self._par_after_bits = list(range(len(self.parallel),
                                          len(inspectors)))
        self.flag_bits: Dict[str, int] = {}
        for bit, svc in enumerate(inspectors):
            name = svc.name
            if name in self.flag_bits:       # duplicate service names
                name = f"{name}@{bit}"
            self.flag_bits[name] = bit
        # the device trace names the program after this function
        # (``jit_service_chain``) and each service's ops after its name
        process = self._process

        def service_chain(payload, plen):
            return process(payload, plen)
        self._jitted = jax.jit(service_chain)

    def _process(self, payload, plen):
        flags = jnp.zeros(payload.shape[0], jnp.int32)
        for svc, bit in zip(self.parallel, self._par_bits):
            with jax.named_scope(svc.name):
                flags = flags | (svc(payload, plen) << bit)
        out = payload
        for svc in self.on_path:
            with jax.named_scope(svc.name):
                out = svc(out, plen)
        for svc, bit in zip(self.parallel_after, self._par_after_bits):
            with jax.named_scope(svc.name):
                flags = flags | (svc(out, plen) << bit)
        return out, flags

    def process(self, payload, plen):
        return self._jitted(payload, plen)

    def describe(self) -> str:
        on = " -> ".join(s.name for s in self.on_path) or "(none)"
        par = ", ".join(s.name for s in self.parallel + self.parallel_after) \
            or "(none)"
        return f"on-path: {on}; parallel-path: {par}"
