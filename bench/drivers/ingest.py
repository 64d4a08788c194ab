"""Streamed DLRM ingest: ``BalboaIngest.fetch_shard_streaming`` of one
training-step shard per request, one after another (a trainer that waits
for each step's shard: a closed loop of one).  The shard is striped over
the storage replicas' QPs, each tile is preprocessed on the device the
moment its bytes land, and a request is done when the landed dense and
sparse features are ready on the device.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import gen, harness
from bench.deploy import Deployment


class Cell:
    def __init__(self, cfg, traffic, seed, control=None):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.control = control
        self.calls = {}
        self.recording = False

    def setup(self):
        from repro.core.ingest import (BalboaIngest, IngestConfig,
                                       make_dlrm_tile_decoder)
        r, ing_cfg = self.cfg["records"], self.cfg["ingest"]
        dep = self.dep = Deployment(self.cfg)
        self.mtu = dep.mtu
        self.rec_w = dep.rec_words
        rpp = gen.records_per_packet(self.mtu, self.rec_w)
        self.n_rec = ing_cfg["records_per_step"]
        self.n_pkts = -(-self.n_rec // rpp)
        key = gen.seed_key(self.seed)
        pool = self.traffic["pool_shards"]
        self.recs = [dep.records(jax.random.fold_in(key, i), self.n_rec)
                     for i in range(pool)]
        shards = [np.asarray(gen.encode_packets(x, self.mtu)).reshape(-1)
                  for x in self.recs]
        # the click label rides as one more Modulus column, as in the
        # deployment's chain
        decode = make_dlrm_tile_decoder(r["n_dense"], r["n_sparse"] + 1,
                                        r["modulus"], mtu=self.mtu)
        if self.control is not None:
            decode = self._control_decoder(r)
        tile = ing_cfg["tile_pkts"]
        tile_recs = tile * rpp

        def tile_to_batch(t):
            if self.recording:
                self.calls.setdefault("preproc", []).append(
                    (tile_recs, self.rec_w))
            with jax.profiler.TraceAnnotation("bench.tile_to_batch"):
                return decode(t)

        self.ing = BalboaIngest(
            IngestConfig(batch_bytes=self.n_pkts * self.mtu,
                         n_storage_nodes=ing_cfg["storage_replicas"],
                         qps_per_node=ing_cfg["qps_per_replica"],
                         link_bw_pkts_per_tick=ing_cfg["link_pkts_per_tick"],
                         tile_pkts=tile),
            None, lambda i: shards[i % pool], tile_to_batch=tile_to_batch)
        self.rng = np.random.default_rng(self.seed)
        self.next_i = 0
        self._loop(lambda i: i < self.traffic["warm_shards"])

    def _control_decoder(self, r):
        """The reference in the decoder's place, log1p in bfloat16."""
        @jax.jit
        def decode(t):
            out = self.dep.preproc(gen.decode_packets(t, self.rec_w), 7)
            return {"dense": jax.lax.bitcast_convert_type(
                out[:, :r["n_dense"]], jax.numpy.float32),
                "sparse": out[:, r["n_dense"]:]}
        return decode

    def _loop(self, go, on_done=None):
        n = 0
        while go(n):
            i = self.next_i
            self.next_i += 1
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.fetch_shard_streaming"):
                batch, _ = self.ing.fetch_shard_streaming(i)
                jax.block_until_ready(batch)
            if on_done is not None:
                on_done(i, t, time.perf_counter(), batch)
            n += 1

    def window(self, seconds):
        lat, self.sample = [], []
        t_end = time.perf_counter() + seconds
        tick0 = self.ing.net.now
        rx0 = self._rx_count()
        attempted = [0]

        def on_done(i, tp, tr, batch):
            # a shard that lands after the close is late, not wrong: it
            # may be compared, but its latency and its bits do not count
            harness.reservoir_keep(self.sample, (i, batch), attempted[0],
                                   self.traffic["sample"], self.rng)
            attempted[0] += 1
            if tr <= t_end:
                lat.append(tr - tp)

        self.recording = True
        self._loop(lambda _: time.perf_counter() < t_end, on_done)
        self.recording = False
        return {"latencies_s": lat,
                "payload_bytes": len(lat) * self.n_pkts * self.mtu,
                "seconds": seconds, "attempted": attempted[0], "failed": 0,
                "counters": {"ticks": self.ing.net.now - tick0,
                             "rx_pkts": self._rx_count() - rx0}}

    def _rx_count(self):
        s = self.ing.trainer.stats
        return s.accepted + s.dup_dropped + s.ooo_nak + s.credit_dropped

    def release(self):
        self.ing = None

    def check(self):
        """Sampled shards' landed features (dense, categorical and the
        click label) against the reference preprocessing of the records
        the shard carried."""
        r = self.cfg["records"]
        diff = 0
        for i, batch in self.sample:
            want = self.dep.preproc(jax.numpy.pad(
                self.recs[i % len(self.recs)],
                ((0, batch["dense"].shape[0] - self.n_rec), (0, 0))))
            dense = jax.lax.bitcast_convert_type(batch["dense"],
                                                 jax.numpy.int32)
            diff += int((dense != want[:, :r["n_dense"]]).sum())
            diff += int((batch["sparse"] != want[:, r["n_dense"]:]).sum())
        return [("feature_diff", diff, 0),
                ("uncompared", int(not self.sample), 0)]
