"""RDMA WRITE flows between two ``RdmaNode``s on a lossy point-to-point
link, driven tick by tick through ``step_network``: the served path.

Shaped as perftest ``ib_write_bw -q <qps> -s <message_bytes>`` with
``outstanding`` WRITEs posted per QP: a closed loop.  A request is one
WRITE message, posted with ``rdma_write`` and done when the receiver
completes it (its last packet landed in registered memory after the
service chain).  Each message lands in its own slot of the receiver's
registered buffer.  Message payloads are AES-encrypted on the sender's
side by the benchmark; the receiver's chain decrypts and inspects.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
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
        from repro.core.netsim import LinkConfig, Network
        from repro.core.rdma import RdmaNode
        tr, tp = self.traffic, self.cfg["transport"]
        dep = self.dep = Deployment(self.cfg)
        self.chain = dep.chain if self.control is None else \
            dep.control_chain(self.control)
        self.kept, self.n_calls = [], 0
        self.call_rng = np.random.default_rng([self.seed, 1])
        self.mtu = dep.mtu
        self.msg = tr["message_bytes"]
        self.slots = tr["slots_per_qp"]
        self.net = Network(2, LinkConfig(loss_prob=tr["loss_prob"],
                                         latency_ticks=tr["latency_ticks"],
                                         seed=self.seed))
        node_kw = dict(n_qps=tp["qps_per_node"], mtu=self.mtu,
                       fc_window=tp["fc_window"], rx_credits=tp["rx_credits"])
        self.a = RdmaNode(0, self.net, **node_kw)
        self.b = RdmaNode(1, self.net, services=self, **node_kw)
        self.nodes = [self.a, self.b]
        # each QP is set up from the receiver's side, which hands back
        # its registered buffer: the bytes the check reads
        rx = [self.b.init_rdma(self.slots * self.msg, self.a)
              for _ in range(tr["qps"])]
        self.rqps = [q for q, _, _ in rx]
        self.bufs = [buf for _, _, buf in rx]
        self.qps = [self.b.remote_qpn(q) for q in self.rqps]
        # message pool: plaintext and its ciphertext, made on the device
        key = gen.seed_key(self.seed)
        pkts = tr["pool_messages"] * self.msg // self.mtu
        wire, plain = dep.packets(jax.random.fold_in(key, 1), pkts)
        self.wire = np.asarray(wire).reshape(tr["pool_messages"], -1)
        self.plain = plain.reshape(tr["pool_messages"], -1)
        self.rng = np.random.default_rng(self.seed)
        self.posted = [[] for _ in self.qps]    # per QP: (pool idx, t)
        self.done = [0] * len(self.qps)
        for q in range(len(self.qps)):
            for _ in range(tr["outstanding"]):
                self._post(q)
        # the RX engine and the chain compile once per padded RX batch
        # size (RdmaNode pads to powers of two from 16): run each size
        # the flow can reach, as RdmaNode.on_packets builds it, then a
        # warm flow
        from repro.core import packet as pk
        from repro.core import pipeline as pipe
        empty = pk.batch_from_packets([], self.mtu)
        rows = 16
        while rows <= tr["qps"] * tp["fc_window"]:
            batch = {k: jnp.asarray(np.zeros((rows,) + v.shape[1:], v.dtype))
                     for k, v in empty.items()}
            jax.block_until_ready(pipe.rx_pipeline_batched(
                pipe.make_rx_tables(tp["qps_per_node"], tp["rx_credits"]),
                batch))
            jax.block_until_ready(self.chain.process(batch["payload"],
                                                     batch["plen"]))
            rows *= 2
        self._run(lambda: self.net.now < tr["warm_ticks"])

    def process(self, payload, plen):
        """The chain as the receiver calls it.  In the window each call's
        kernel sizes are counted, and a uniform sample of the calls, drawn
        from the seed, is kept (input, lengths, flags) for the check."""
        with jax.profiler.TraceAnnotation("bench.service_chain"):
            out, flags = self.chain.process(payload, plen)
        if self.recording:
            for k, args in self.dep.call_sizes(payload.shape[0]).items():
                self.calls.setdefault(k, []).append(args)
            harness.reservoir_keep(self.kept, (payload, plen, flags),
                                   self.n_calls, self.traffic["sample_calls"],
                                   self.call_rng)
            self.n_calls += 1
        return out, flags

    def _post(self, q):
        i = len(self.posted[q])
        idx = int(self.rng.integers(0, len(self.wire)))
        with jax.profiler.TraceAnnotation("bench.rdma_write"):
            self.a.rdma_write(self.qps[q], self.wire[idx],
                              remote_addr=(i % self.slots) * self.msg)
        self.posted[q].append((idx, time.perf_counter()))

    def _run(self, go, on_done=None, post=True):
        """Step the network while ``go()``; each completed message is
        reported and, when ``post``, replaced by a new one."""
        from repro.core.rdma import step_network
        while go():
            with jax.profiler.TraceAnnotation("bench.step_network"):
                step_network(self.nodes)
            now = time.perf_counter()
            for q, rq in enumerate(self.rqps):
                c = self.b.check_completed(rq)
                while self.done[q] < c:
                    if on_done is not None:
                        on_done(q, self.done[q], now)
                    self.done[q] += 1
                    if post:
                        self._post(q)

    def window(self, seconds):
        from repro.core.rdma import network_pending
        lat, self.sample, late = [], [], []
        stats0, tick0 = self._rx_count(), self.net.now
        t_end = time.perf_counter() + seconds
        done0 = sum(self.done)

        def on_done(q, i, now):
            if now > t_end:
                late.append((q, i))
                return
            lat.append(now - self.posted[q][i][1])
            harness.reservoir_keep(self.sample, (q, i), len(lat) - 1,
                                   self.traffic["sample"], self.rng)

        self.recording = True
        self._run(lambda: time.perf_counter() < t_end, on_done)
        self.recording = False
        counters = {"ticks": self.net.now - tick0,
                    "rx_pkts": self._rx_count() - stats0}
        # messages outstanding at the start or posted in the window
        attempted = sum(len(p) for p in self.posted) - done0
        # drain outside the window: every posted message must complete
        limit = self.net.now + self.traffic["drain_ticks"]
        self._run(lambda: network_pending(self.nodes)
                  and self.net.now < limit, post=False)
        self.lost = sum(len(p) for p in self.posted) - sum(self.done)
        return {"latencies_s": lat,
                "payload_bytes": len(lat) * self.msg,
                "seconds": seconds, "attempted": attempted,
                "failed": self.lost, "counters": counters}

    def _rx_count(self):
        s = self.b.stats
        return s.accepted + s.dup_dropped + s.ooo_nak + s.credit_dropped

    def release(self):
        self.wire = None

    def check(self):
        """Sampled messages' bytes in the receiver's registered memory,
        messages never completed, the DPI flag of every packet (padding
        rows included) of the sampled chain calls, and the receiver's
        count of flagged packets against the reference's over every
        packet delivered since the flow began."""
        pay_diff = 0
        last = [(q, len(p) - 1) for q, p in enumerate(self.posted) if p]
        for q, i in set(self.sample) | set(last):
            if i < len(self.posted[q]) - self.slots:
                continue                      # slot written again since
            off = (i % self.slots) * self.msg
            want = np.asarray(self.plain[self.posted[q][i][0]])
            pay_diff += int(np.sum(self.bufs[q][off:off + self.msg] != want))
        checks = [("lost_messages", self.lost, 0),
                  ("payload_diff", pay_diff, 0)]
        if self.dep.params is None:
            return checks
        flag_diff = 0
        for payload, plen, flags in self.kept:
            _, (want, band) = self.dep.expect(self.dep.plain_of(payload),
                                              plen)
            flag_diff += int(jnp.sum(((flags != 0) != want) & ~band))
        n = self.msg // self.mtu
        idx = np.array([i for p in self.posted for i, _ in p])
        use = np.bincount(idx, minlength=len(self.plain))
        _, (flag, band) = self.dep.expect(
            self.plain.reshape(-1, self.mtu),
            jnp.full(self.plain.shape[0] * n, self.mtu, jnp.int32))
        per_msg = lambda a: np.asarray(a).reshape(-1, n).sum(1)
        lo = int(per_msg(flag & ~band) @ use)
        hi = lo + int(per_msg(band) @ use)
        got = self.b.stats.dpi_flagged
        return checks + [
            ("dpi_flag_diff", flag_diff, 0),
            ("dpi_count_diff", max(lo - got, got - hi, 0), 0),
            ("uncompared", int(not self.kept), 0)]
