#!/usr/bin/env python3
"""Smoke test of the RDMA data plane on one TPU chip.

Drives the system's main paths once, through the entry points a user
calls, at deployment sizes, and checks every result against the
repository's own reference:

  rx_engine   the batched multi-QP RX engine against the per-packet scan
              oracle (500 QPs, an 8192-packet trace);
  services    the secure flow (AES encrypt on TX, AES decrypt on-path,
              ML-DPI in parallel) between two RDMA nodes at MTU 4096,
              1 MiB per flow, plus AES / DPI / ICRC kernels against
              their references on one 8192 x 4096 B batch;
  incast      the 8:1 star incast at 1 MiB per sender in the fused epoch
              core against per-tick stepping, with no per-tick fallback;
  ingest      the streamed DLRM ingest at 4 replicas (Pallas preproc)
              against the one-shot preproc oracle;
  allreduce   the in-fabric reduction offload over 8 ranks, 262144
              float32 elements, against the allreduce oracle.

One process holds the chip for the whole run.  The script refuses any
platform but ``tpu`` before a phase runs.  Each phase prints one line
(sizes, set-up seconds, check); the last line is the JSON result, and
it is printed only when every phase passed.

    python chip_smoke.py
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
# the TPU library logs under /tmp unless given an existing directory
os.environ.setdefault("TPU_LOG_DIR", tempfile.gettempdir())

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

MTU = 4096
KEY = np.arange(16, dtype=np.uint8)
INCAST_QPS = 500        # QPs per node in the paper's incast (RdmaNode default)


def _expect(cond, what: str):
    if not cond:
        raise AssertionError(what)


def _same(a, b) -> bool:
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and np.array_equal(a, b)


# ---------------------------------------------------------------------------
# phases: each takes its sizes, raises on a failed check, and returns
# (sizes, check) strings for its report line
# ---------------------------------------------------------------------------

def phase_rx_engine(n_qps: int = 500, n_pkts: int = 8192):
    from benchmarks.fig6_multiqp import _trace_batch
    from repro.core import pipeline as pipe
    batch = _trace_batch(n_qps, n_pkts)
    tables = pipe.make_rx_tables(n_qps, initial_credits=1 << 30)
    t_ref, r_ref = pipe.rx_pipeline(pipe.clone_tables(tables), batch)
    t_bat, r_bat = pipe.rx_pipeline_batched(pipe.clone_tables(tables), batch)
    for name in t_ref._fields:
        _expect(_same(getattr(t_ref, name), getattr(t_bat, name)),
                f"RX table {name} differs between engines")
    for name in r_ref._fields:
        _expect(_same(getattr(r_ref, name), getattr(r_bat, name)),
                f"RX result {name} differs between engines")
    accepted = int(np.asarray(r_bat.accept).sum())
    _expect(accepted == n_pkts, f"{accepted}/{n_pkts} in-sequence packets "
            f"accepted")
    return (f"rx_pipeline_batched vs rx_pipeline, {n_qps} QPs, {n_pkts} pkts",
            f"{len(t_ref._fields)} tables + {len(r_ref._fields)} result "
            f"fields identical, {accepted} accepted")


def phase_services(flow_bytes: int = 1 << 20, batch_pkts: int = 8192,
                   seed: int = 0):
    from repro.core.netsim import LinkConfig, Network
    from repro.core.rdma import RdmaNode, run_network
    from repro.core.services import (AesService, CrcService, DpiService,
                                     ServiceChain)
    from repro.data.dpi_dataset import (make_dataset,
                                        payload_with_embedded_malware)
    from repro.kernels import ops
    from repro.kernels.dpi_mlp import train_dpi_params

    # ---- the secure flow, as examples/secure_flow.py runs it ----------
    x, y = make_dataset(2048, seed=0)
    dpi_params = train_dpi_params(x, y, steps=200)
    rng = np.random.default_rng(seed)
    flows = (("benign", payload_with_embedded_malware(flow_bytes, 0.0, rng)),
             ("malicious", payload_with_embedded_malware(flow_bytes, 0.2,
                                                         rng)))
    enc = AesService(key=KEY)
    dec = AesService(key=KEY, decrypt=True)
    dpi = DpiService(params=dpi_params)
    crc = CrcService()
    # services run the Pallas kernels on any backend but the CPU (main()
    # admits only the TPU; the CPU tests run the phases at tiny size)
    pallas = jax.default_backend() != "cpu"
    _expect(all(s.use_pallas == pallas for s in (enc, dec, dpi, crc)),
            f"services did not pick the Pallas kernels on "
            f"{jax.default_backend()}")
    net = Network(2, LinkConfig(loss_prob=0.02, latency_ticks=3, seed=1))
    a = RdmaNode(0, net, mtu=MTU)
    b = RdmaNode(1, net, mtu=MTU,
                 services=ServiceChain(on_path=[dec], parallel_after=[dpi]))
    qpn_a, _, _ = a.init_rdma(flow_bytes, b)
    flagged = {}
    for name, data in flows:
        n = len(data) // MTU
        ct = np.asarray(enc(jnp.asarray(data.reshape(n, MTU)),
                            jnp.full((n,), MTU, jnp.int32)))
        before = b.stats.dpi_flagged
        a.rdma_write(qpn_a, ct.reshape(-1))
        run_network([a, b], max_ticks=500_000)
        _expect(_same(b._qp_buffer[1][1][:len(data)], data),
                f"{name} flow not delivered byte-exact")
        flagged[name] = b.stats.dpi_flagged - before
    _expect(flagged["malicious"] > 0, "DPI missed the malicious flow")

    # ---- kernels against their references on one line-rate batch ------
    key = jax.random.key(seed)
    pay = jax.random.randint(key, (batch_pkts, MTU), 0, 256,
                             jnp.int32).astype(jnp.uint8)
    plen = jax.random.randint(jax.random.fold_in(key, 1), (batch_pkts,), 0,
                              MTU + 1, jnp.int32)
    rk = enc._round_keys
    blocks = pay.reshape(-1, 16)
    ct = ops.aes_ecb(blocks, rk, impl="pallas")
    _expect(_same(ct, ops.aes_ecb(blocks, rk, impl="ref")),
            "AES encrypt differs from the reference")
    pt = ops.aes_ecb(ct, rk, decrypt=True, impl="pallas")
    _expect(_same(pt, ops.aes_ecb(ct, rk, decrypt=True, impl="ref")),
            "AES decrypt differs from the reference")
    _expect(_same(pt, blocks), "AES decrypt(encrypt(x)) != x")
    s_k = np.asarray(ops.dpi_scores(pay, dpi_params, impl="pallas"))
    s_r = np.asarray(ops.dpi_scores(pay, dpi_params, impl="ref"))
    # float matmuls accumulate in another order on the MXU: the repo's
    # kernel-test tolerance, and identical per-packet decisions
    _expect(np.allclose(s_k, s_r, rtol=1e-5, atol=1e-5),
            f"DPI scores off the reference by {np.abs(s_k - s_r).max()}")
    valid = (np.arange(MTU // 64)[None, :] * 64) < np.asarray(plen)[:, None]
    dec_k = np.where(valid, s_k, -np.inf).max(1) > dpi.threshold
    dec_r = np.where(valid, s_r, -np.inf).max(1) > dpi.threshold
    _expect(_same(dec_k, dec_r), "DPI decisions differ from the reference")
    _expect(_same(crc(pay, plen),
                  ops.crc32(pay, plen, impl="ref").astype(jnp.int32)),
            "ICRC differs from the reference")
    return (f"secure flow 2 x {flow_bytes} B at MTU {MTU} (AES TX, AES "
            f"on-path, DPI parallel); kernels on {batch_pkts} x {MTU} B",
            f"delivered byte-exact, DPI flagged benign "
            f"{flagged['benign']} / malicious {flagged['malicious']} pkts; "
            f"AES enc/dec + ICRC identical, DPI max |diff| "
            f"{float(np.abs(s_k - s_r).max()):.3g}, decisions identical")


def phase_incast(n_senders: int = 8, message_bytes: int = 1 << 20):
    from repro.core.netsim import incast_scenario
    arms = {}
    for mode in ("tick", "fused"):
        res = incast_scenario(n_senders, message_bytes=message_bytes,
                              epoch_mode=mode)
        for i, data in enumerate(res.payloads):
            _expect(_same(res.receiver._qp_buffer[i + 1][1][:len(data)],
                          data), f"{mode}: sender {i + 1} not delivered")
        arms[mode] = (res.ticks, res.fabric.total_tail_dropped,
                      sum(s.stats.retransmissions for s in res.senders))
        epochs = res.fabric.epochs
        n_qps = res.receiver.qp.tables.npsn.shape[0]
        _expect(n_qps == INCAST_QPS, f"{mode}: {n_qps} QPs per node, not "
                f"{INCAST_QPS}")
    _expect(epochs.unfused == 0 and epochs.fused > 0,
            f"fused core fell back to per-tick stepping: {epochs}")
    _expect(arms["fused"] == arms["tick"],
            f"(ticks, drops, retransmits) fused {arms['fused']} != tick "
            f"{arms['tick']}")
    return (f"{n_senders}:1 star incast, {message_bytes} B per sender, "
            f"{n_qps} QPs per node, fused epoch core vs per-tick",
            f"delivered exact; ticks/drops/retx {arms['fused']} equal; "
            f"{epochs.fused} fused epochs, {epochs.unfused} per-tick "
            f"fallbacks")


def phase_ingest(n_pkts: int = 64, replicas: int = 4):
    from benchmarks.fig10_dlrm import MOD, N_DENSE, N_SPARSE, RPP, _shard_fn
    from repro.core.ingest import (BalboaIngest, IngestConfig,
                                   make_dlrm_tile_decoder)
    from repro.data import synthetic as syn
    from repro.kernels.preproc import preproc_ref
    ing = BalboaIngest(
        IngestConfig(batch_bytes=n_pkts * MTU, n_storage_nodes=replicas,
                     link_bw_pkts_per_tick=1, tile_pkts=2),
        None, _shard_fn(n_pkts),
        tile_to_batch=make_dlrm_tile_decoder(N_DENSE, N_SPARSE, MOD))
    batch, rep = ing.fetch_shard_streaming(0)
    n_rec = RPP * n_pkts
    want = np.asarray(preproc_ref(
        jnp.asarray(syn.dlrm_shard(0, n_rec, N_DENSE, N_SPARSE)),
        N_DENSE, MOD))
    dense = np.asarray(batch["dense"])[:n_rec].view(np.int32)
    _expect(_same(dense, want[:, :N_DENSE]), "dense features differ")
    _expect(_same(np.asarray(batch["sparse"])[:n_rec], want[:, N_DENSE:]),
            "sparse features differ")
    _expect(ing.host_payload_bytes == 0, "payload crossed a host copy")
    return (f"streamed DLRM ingest, {n_pkts} pkts x {MTU} B, {replicas} "
            f"replicas, {rep.tiles} tiles, Pallas preproc",
            f"{n_rec} records bit-identical to the one-shot oracle")


def phase_allreduce(world: int = 8, n_elems: int = 262144):
    from benchmarks.fig11_allreduce import BASE_FABRIC, _tensors
    from repro.core.collectives import allreduce_oracle, make_ring_group
    g = make_ring_group(world, max_bytes=n_elems * 4 + world * 4,
                        fabric_cfg=BASE_FABRIC, offload=True, impl="pallas")
    xs = _tensors(world, n_elems)
    out = g.allreduce(xs)
    want = allreduce_oracle(xs).view(np.uint8)
    for r in range(world):
        _expect(_same(out[r].view(np.uint8), want),
                f"rank {r} differs from the allreduce oracle")
    red = g.service.reducer
    return (f"allreduce offload, {world} ranks, {n_elems} float32, "
            f"impl=pallas",
            f"all ranks bit-identical to the oracle; switch absorbed "
            f"{red.absorbed}, forwarded {red.reduced_forwarded}")


PHASES = (("rx_engine", phase_rx_engine), ("services", phase_services),
          ("incast", phase_incast), ("ingest", phase_ingest),
          ("allreduce", phase_allreduce))


class _CompileClock:
    """Seconds JAX spent tracing, lowering and compiling since a mark."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event in self.EVENTS:
            self.total += duration


def main(argv=None) -> int:
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {device}", file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache
    print(f"[setup] device {device}; compile cache {enable_compile_cache()}",
          flush=True)
    clock = _CompileClock()
    failed = []
    for name, phase in PHASES:
        c0, t0 = clock.total, time.perf_counter()
        try:
            sizes, check = phase()
            status = "PASS"
        except Exception as e:          # report, then run the next phase
            traceback.print_exc()
            sizes, check, status = "", f"{type(e).__name__}: {e}", "FAIL"
            failed.append(name)
        wall = time.perf_counter() - t0
        print(f"[{name}] {sizes} | set-up incl. compile: compile "
              f"{clock.total - c0:.1f} s, wall {wall:.1f} s | {status}: "
              f"{check}", flush=True)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
