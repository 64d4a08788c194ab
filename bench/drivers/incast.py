"""An N:1 RoCE incast served round after round on standing connections,
through ``IncastWorld.run(epoch_mode="fused")``: ``run_network`` on the
fused epoch core.

A request is one round: one WRITE of ``message_bytes`` per sender, each
on the sender's one QP into its own registered buffer at the receiver,
all posted together; it is done when ``run_network`` returns with every
WRITE completed.  Closed loop with one round outstanding: the next round
is posted when the last one is done.  Payload bytes are drawn per round
from the seed.  A round is failed when a QP is in error or a WRITE did
not complete.  Every round, warm-up included, is checked after the
window on a per-tick twin of the world (``bench.incast_reference``); each
round's landed bytes are checked against the bytes posted as it ends.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import incast_reference as ref
from repro.core.netsim import FabricConfig, incast_world


class Cell:
    def __init__(self, cfg, traffic, seed, control=None):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.calls = {}

    def setup(self):
        tp, fb, tr = self.cfg["transport"], self.cfg["fabric"], self.traffic
        if fb["ports"] != tr["fan_in"] + 1:
            raise ValueError(f"{fb['ports']} fabric ports for a "
                             f"{tr['fan_in']}:1 incast")
        self.world = self._build()
        for n in self.world.nodes:
            if n.mtu != tp["mtu"] or n.rx_mode != tp["rx_mode"]:
                raise ValueError(f"node {n.node_id}: MTU {n.mtu}, "
                                 f"{n.rx_mode}; the configuration states "
                                 f"{tp['mtu']}, {tp['rx_mode']}")
        # the oracle's world: same configuration, stepped only in check
        self.twin = self._build()
        self.per_write = self.world.senders[0].expected_completions(
            tr["message_bytes"])
        self.rounds, self.seen, self.landed, self.failed = 0, [], 0, 0
        for _ in range(tr["warm_rounds"]):
            self._round()

    def _build(self):
        tp, fb, tr = self.cfg["transport"], self.cfg["fabric"], self.traffic
        return incast_world(
            tr["fan_in"], message_bytes=tr["message_bytes"],
            fabric_cfg=FabricConfig(
                port_bandwidth=fb["port_bandwidth"],
                port_delay=fb["port_delay"],
                queue_capacity=fb["queue_capacity"], seed=fb["seed"]),
            rx_credits=tp["rx_credits"], fc_window=tp["fc_window"],
            n_qps=tp["qps_per_node"])

    def _payloads(self, r):
        """Round ``r``'s message for each sender, drawn from the seed."""
        n, msg = len(self.world.senders), self.traffic["message_bytes"]
        rng = np.random.default_rng([self.seed, 2, r])
        return list(np.frombuffer(rng.bytes(n * msg), np.uint8)
                    .reshape(n, msg))

    def _round(self):
        """Post round ``self.rounds`` and run it to quiescence; keep its
        host-side counters and count its landed bytes that differ from
        those posted.  Returns (seconds from post to done, ticks,
        whether every WRITE completed and no QP is in error)."""
        w = self.world
        payloads = self._payloads(self.rounds)
        want = [c + self.per_write for c in w.completions()]
        t0 = time.perf_counter()
        w.post_round(payloads)
        ticks = w.run(max_ticks=self.traffic["max_ticks"],
                      epoch_mode=self.traffic["epoch_mode"])
        dt = time.perf_counter() - t0
        self.rounds += 1
        self.seen.append(ref.counters(w, ticks))
        self.landed += ref.payload_diff(w.buffers, payloads)
        ok = w.completions() == want and not any(n.qp_errors
                                                 for n in w.nodes)
        self.failed += not ok
        return dt, ticks, ok

    def window(self, seconds):
        epochs = self.world.fabric.epochs
        e0 = dataclasses.asdict(epochs)
        lat, failed, ticks = [], 0, 0
        t_start = time.perf_counter()
        t_end = t_start + seconds
        while time.perf_counter() < t_end:
            dt, t, ok = self._round()
            lat.append(dt)
            ticks += t
            failed += not ok
        elapsed = time.perf_counter() - t_start
        counters = {k: v - e0[k] for k, v in dataclasses.asdict(epochs)
                    .items()}
        counters.update(rounds=len(lat), ticks=ticks)
        return {"latencies_s": lat,
                "payload_bytes": len(lat) * len(self.world.senders)
                * self.traffic["message_bytes"],
                "seconds": elapsed, "attempted": len(lat),
                "failed": failed, "counters": counters}

    def release(self):
        pass

    def check(self):
        """Every round the world ran, posted again on the twin and
        stepped per tick: each round's counters and, after the last, the
        whole carried state, field by field; every round's landed bytes
        against the bytes posted; rounds failed, warm-up included."""
        contract = 0
        for r, seen in enumerate(self.seen):
            self.twin.post_round(self._payloads(r))
            t = self.twin.run(max_ticks=self.traffic["max_ticks"],
                              epoch_mode="tick")
            contract += ref.contract_diff(seen, ref.counters(self.twin, t))
        contract += ref.contract_diff(ref.state(self.world),
                                      ref.state(self.twin))
        return [("contract_diff", contract, 0),
                ("payload_diff", self.landed, 0),
                ("failed_rounds", self.failed, 0)]
