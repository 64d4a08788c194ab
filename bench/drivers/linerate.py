"""Line-rate RX batches straight into the device path, host bypassed.

Each request is one device-resident RX batch (``batch_pkts`` full-MTU
WRITE_ONLY packets over the configuration's QPs): ``rx_pipeline_batched``
checks its headers against the carried QP tables and the service chain
processes its payload.  ``in_flight`` batches are kept posted, as a
double-buffered RX ring would.  PSNs continue from batch to batch, so
every packet is accepted and no table is cloned in the window.  A
request is posted when its calls are dispatched and done when its chain
outputs are ready; a request's payload bits count once it is done.
"""
from __future__ import annotations

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import gen, harness, reference
from bench.deploy import Deployment


class Cell:
    def __init__(self, cfg, traffic, seed, control=None):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.control = control
        self.n = traffic["batch_pkts"]
        self.n_qps = cfg["transport"]["qps_per_node"]
        self.calls = {}
        self.recording = False

    def setup(self):
        from repro.core import pipeline as pipe
        self.pipe = pipe
        dep = self.dep = Deployment(self.cfg)
        self.chain = dep.chain if self.control is None else \
            dep.control_chain(self.control)
        key = gen.seed_key(self.seed)
        self.qpn, self.rank, self.cnt = gen.rx_trace(
            jax.random.fold_in(key, 0), self.n_qps, self.n)
        pool = [dep.packets(jax.random.fold_in(key, 1 + i), self.n)
                for i in range(self.traffic["pool_batches"])]
        self.wire = [w for w, _ in pool]
        self.plain = [p for _, p in pool]
        self.plen = jnp.full(self.n, dep.mtu, jnp.int32)
        self.credits0 = 1 << 30
        self.tables = pipe.make_rx_tables(self.n_qps,
                                          initial_credits=self.credits0)
        self.next_b = 0
        self.rng = np.random.default_rng(self.seed)
        # every shape the window uses, compiled and run: the ring's
        # first rounds, as the window runs them
        for d in self._loop(time.perf_counter() + 1e9,
                            2 * self.traffic["in_flight"]):
            jax.block_until_ready(d[2])

    def _post(self, b):
        with jax.profiler.TraceAnnotation("bench.post.rx_pipeline_batched"):
            hdr = gen.rx_batch(jnp.int32(b), self.qpn, self.rank, self.cnt,
                               mtu=self.dep.mtu)
            self.tables, res = self.pipe.rx_pipeline_batched(self.tables,
                                                             hdr)
        with jax.profiler.TraceAnnotation("bench.post.service_chain"):
            out, flags = self.chain.process(self.wire[b % len(self.wire)],
                                            self.plen)
        if self.recording:
            for k, args in self.dep.call_sizes(self.n).items():
                self.calls.setdefault(k, []).append(args)
        return res, out, flags

    def _loop(self, t_end, max_requests=None, on_done=None):
        """Keep ``in_flight`` batches posted until ``t_end``; returns the
        requests still in the ring."""
        ring = collections.deque()
        posted = 0
        while time.perf_counter() < t_end and (max_requests is None
                                                or posted < max_requests):
            b = self.next_b
            self.next_b += 1
            ring.append((b, time.perf_counter(), self._post(b)))
            posted += 1
            if len(ring) >= self.traffic["in_flight"]:
                b0, t0, outs = ring.popleft()
                with jax.profiler.TraceAnnotation("bench.wait.batch"):
                    jax.block_until_ready(outs)
                if on_done is not None:
                    on_done(b0, t0, time.perf_counter(), outs)
        return ring

    def window(self, seconds):
        lat, self.sample = [], []
        t_end = time.perf_counter() + seconds
        b_first = self.next_b

        def on_done(b, tp, tr, outs):
            # an answer that comes after the close is late, not wrong: it
            # may be compared, but its latency and its bits do not count
            harness.reservoir_keep(self.sample, (b, outs), b - b_first,
                                   self.traffic["sample"], self.rng)
            if tr <= t_end:
                lat.append(tr - tp)

        self.recording = True
        ring = self._loop(t_end, on_done=on_done)
        self.recording = False
        for b, tp, outs in ring:
            jax.block_until_ready(outs)
            on_done(b, tp, time.perf_counter(), outs)
        posted = self.next_b - b_first
        return {"latencies_s": lat,
                "payload_bytes": len(lat) * self.n * self.dep.mtu,
                "seconds": seconds, "attempted": posted, "failed": 0,
                "counters": {"rx_pkts": posted * self.n}}

    def release(self):
        """Drop the program's state that the check does not read."""
        self.wire = None

    def check(self):
        """Sampled batches against the reference: RX results, chain
        payload and DPI flags; the final QP tables against the state
        the reference FSM reaches after every batch posted."""
        rx_diff = pay_diff = flag_diff = 0
        cnt = np.asarray(self.cnt)
        for b, (res, out, flags) in self.sample:
            hdr = {k: np.asarray(v) for k, v in gen.rx_batch(
                jnp.int32(b), self.qpn, self.rank, self.cnt,
                mtu=self.dep.mtu).items()}
            want, _ = reference.rx_go_back_n(hdr, self._state(b, cnt, hdr))
            for k in reference.RX_FIELDS:
                rx_diff += int(np.sum(np.asarray(getattr(res, k)).astype(
                    np.int64) != want[k]))
            exp, fl = self.dep.expect(self.plain[b % len(self.plain)],
                                      self.plen)
            pay_diff += int(jnp.sum(out != exp))
            if fl is not None:
                flag_diff += int(jnp.sum(((flags != 0) != fl[0]) & ~fl[1]))
        hdr = {k: np.asarray(v) for k, v in gen.rx_batch(
            jnp.int32(self.next_b - 1), self.qpn, self.rank, self.cnt,
            mtu=self.dep.mtu).items()}
        _, final = reference.rx_go_back_n(
            hdr, self._state(self.next_b - 1, cnt, hdr))
        for k in reference.STATE_FIELDS:
            rx_diff += int(np.sum(np.asarray(getattr(self.tables, k)
                                             ).astype(np.int64) != final[k]))
        checks = [("rx_diff", rx_diff, 0), ("payload_diff", pay_diff, 0),
                  ("uncompared", int(not self.sample), 0)]
        if self.dep.params is not None:
            checks.append(("dpi_flag_diff", flag_diff, 0))
        return checks

    def _state(self, b, cnt, hdr):
        """QP state after batches ``0 .. b-1`` were all accepted: every
        QP's expected PSN, message count and credits moved by its packets,
        its write cursor after its last packet of batch ``b - 1``."""
        acc = b * cnt.astype(np.int64)
        cur = np.zeros(self.n_qps, np.int64)
        if b:
            prev = np.asarray(gen.batch_psn(b - 1, self.qpn, self.rank,
                                            self.cnt))
            q, rank = hdr["qpn"], np.asarray(self.rank)
            last = np.flatnonzero(rank == cnt[q] - 1)   # each QP's last
            cur[q[last]] = (prev[last] & 0x3FFF) * self.dep.mtu \
                + self.dep.mtu
        return {"epsn": acc & gen.PSN_MASK, "msn": acc,
                "credits": self.credits0 - acc, "cur_vaddr": cur,
                "acc_cnt": acc}
