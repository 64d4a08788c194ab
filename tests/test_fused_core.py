"""Oracle pinning for the fused epoch core (``repro.core.fused``).

The fused core runs whole simulator epochs inside one jitted
``while_loop`` on device; the per-tick Python netsim stays the oracle.
This suite is the trust anchor: property-tested random schedules (wire
loss, duplication via timeout retransmits, ECN thresholds, reorder
spray, QP counts, mid-flight packing) assert the fused epoch leaves the
ENTIRE Python world — RX tables, retransmit slots, flow control, credit
ledgers, per-QP completion/progress maps, delivered buffer bytes, node
stats, fabric port stats, wire/queue contents — bit-identical to
stepping the same world per-tick, for both ``go_back_n`` and
``selective_repeat`` RX modes, on both the switched star fabric and the
point-to-point link mesh.

Strictness: for every schedule drawn here the world is fusable by
construction, and the tests assert ``run_fused_epoch`` did NOT fall
back — a silently widened bail-out gate fails the suite instead of
quietly shifting coverage back to the per-tick path.

Equivalence excludes exactly three kinds of private state, all
re-derived before their next use: numpy ``Generator`` objects (chaos
mode replaces their draws), the per-tick chaos rank cursors
(``_ctick``/``_csend``/``_cpop``/``_cidx``, reset at the next tick
boundary), and queue ``on_event`` hooks (packing bails when one is
installed).
"""
import copy
import hashlib
import re
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, st

from repro.core import fused
from repro.core import packet as pk
from repro.core import pipeline as pipe
from repro.core.netsim import (FabricConfig, LinkConfig, Network,
                               SwitchedFabric, incast_scenario,
                               incast_world)
from repro.core.rdma import RdmaNode, run_network, step_network

MTU = 256                     # small MTU => multi-packet, multi-chunk plans


# ---------------------------------------------------------------------------
# full-world snapshot / structural diff
# ---------------------------------------------------------------------------

def _pkt_tuple(p):
    pay = None if p.payload is None or p.payload.size == 0 \
        else bytes(np.asarray(p.payload, np.uint8).tobytes())
    return (p.src_ip, p.dst_ip, p.src_port, p.dst_port, p.opcode, p.qpn,
            p.psn, bool(p.ack_req), p.vaddr, p.rkey, p.dma_len, p.ack_psn,
            p.msn, p.sack_bits, p.path_id, p.icrc, bool(p.dpi_flag),
            bool(p.ecn), p.coll_tag, p.coll_src, p.coll_nsrc, p.coll_frag,
            pay)


def snap_node(n):
    d = {}
    d["stats"] = dict(vars(n.stats))
    d["rx_tables"] = {f: np.asarray(getattr(n.rx_tables, f)).copy()
                      for f in pipe.RxTables._fields}
    d["npsn"] = list(n.qp.tables.npsn)
    d["retx_slots"] = {q: {psn: (_pkt_tuple(s.packet), s.deadline,
                                 s.retries)
                           for psn, s in slots.items()}
                       for q, slots in n.retx.slots.items()}
    d["retx_retrans"] = n.retx.retransmissions
    d["fc"] = (list(n.fc.budget), list(n.fc.outstanding),
               [len(q) for q in n.fc.pending], n.fc.total_passed)
    d["credits"] = (list(n.credits.credits), n.credits.accepted,
                    n.credits.granted, n.credits.dropped_no_credit,
                    list(n.credits.accepted_per_qp),
                    list(n.credits.dropped_per_qp))
    d["rx_progress"] = dict(n._rx_progress)
    d["completions"] = dict(n._completions)
    d["sr_pending_last"] = {k: list(v)
                            for k, v in n._sr_pending_last.items()}
    d["sr_pend"] = {k: dict(v) for k, v in n._sr_pend.items()}
    d["last_nak"] = dict(n._last_nak_resend)
    d["last_gap"] = dict(n._last_gap_resend)
    d["last_cnp"] = dict(n._last_cnp_sent)
    d["qp_errors"] = sorted(n.qp_errors)
    d["bufs"] = {q: bytes(b.tobytes())
                 for q, (_rk, b) in n._qp_buffer.items()}
    return d


def snap_net(net):
    d = {"now": net.now}
    if isinstance(net, SwitchedFabric):
        d["seq"] = net._seq
        d["injected"] = net.injected
        d["wire"] = sorted((a, s, dst, _pkt_tuple(p))
                           for a, s, dst, p in net._wire)
        d["rings"] = [[_pkt_tuple(p) for p, _m in eg._q]
                      for eg in net.egress]
        d["port_stats"] = [dict(vars(st_)) for st_ in net.port_stats]
    else:
        d["links"] = {
            k: {"seq": lk._seq, "sent": lk.sent, "dropped": lk.dropped,
                "heap": sorted((a, s, _pkt_tuple(p))
                               for a, s, p in lk._heap)}
            for k, lk in net.links.items()}
    return d


def snap(nodes):
    return {"nodes": [snap_node(n) for n in nodes],
            "net": snap_net(nodes[0].net)}


def diff(a, b, path=""):
    """Recursive structural diff; returns human-readable mismatch lines
    (empty list == bit-identical)."""
    out = []
    if isinstance(a, dict):
        for k in sorted(set(a) | set(b), key=repr):
            if k not in a:
                out.append(f"{path}.{k}: missing in oracle")
            elif k not in b:
                out.append(f"{path}.{k}: missing in fused")
            else:
                out += diff(a[k], b[k], f"{path}.{k}")
    elif isinstance(a, (list, tuple)):
        if len(a) != len(b):
            out.append(f"{path}: len {len(a)} vs {len(b)}")
        for i, (x, y) in enumerate(zip(a, b)):
            out += diff(x, y, f"{path}[{i}]")
    elif isinstance(a, np.ndarray):
        if not np.array_equal(a, b):
            idx = np.nonzero(a != b)[0][:5]
            out.append(f"{path}: arrays differ at {idx} "
                       f"a={a[idx]} b={b[idx]}")
    elif a != b:
        out.append(f"{path}: {a!r} vs {b!r}")
    return out


def live_work(nodes):
    """The oracle's own counts of what the fused tick's live-work loops
    visit: packets popped off the star fabric's wire into an egress ring
    (enqueued or tail-dropped), PSNs handed out (first sends) and
    retransmissions (timer, NAK and gap bumps).  A trip of those loops
    is one of these, so their sum is ``EpochStats.loop_trips``."""
    net = nodes[0].net
    pops = (sum(st_.enqueued + st_.tail_dropped for st_ in net.port_stats)
            if isinstance(net, SwitchedFabric) else 0)
    psns = sum(int(v) for n in nodes for v in n.qp.tables.npsn)
    retx = sum(n.stats.retransmissions for n in nodes)
    return np.array([pops, psns, retx])


def assert_fused_matches_oracle(nodes, max_ticks=100_000, idle_done=8,
                                watermarks=None, expect_fused=True):
    """Run one fused epoch on ``nodes`` and the same number of per-tick
    oracle steps on a deepcopy; assert the two worlds are bit-identical
    and that the epoch's ``loop_trips`` is the live work the oracle did.
    Returns the fused result dict (or None when ``expect_fused`` is
    False and the world legitimately does not pack)."""
    oracle = copy.deepcopy(nodes)
    work0, trips0 = live_work(oracle), nodes[0].net.epochs.loop_trips
    res = fused.run_fused_epoch(nodes, max_ticks=max_ticks,
                                idle_done=idle_done, watermarks=watermarks)
    if res is None:
        assert not expect_fused, "schedule was expected to pack+fuse"
        return None
    assert expect_fused
    for _ in range(res["steps"]):
        step_network(oracle)
    d = diff(snap(oracle), snap(nodes))
    assert not d, "fused epoch diverged from per-tick oracle:\n  " \
        + "\n  ".join(d[:40])
    res["work"] = live_work(oracle) - work0
    assert nodes[0].net.epochs.loop_trips - trips0 == res["work"].sum()
    return res


# ---------------------------------------------------------------------------
# world builders (fusable by construction)
# ---------------------------------------------------------------------------

def build_star(seed, *, sr=False, loss=0.0, kmax=0, nbytes=2000,
               n_senders=2, bw=3, cap=16, window=16, presteps=0,
               extra_qps=0, timeout=64):
    cfg = FabricConfig(port_bandwidth=bw, port_delay=2,
                       queue_capacity=cap, loss_prob=loss,
                       ecn_kmin=4, ecn_kmax=kmax, seed=seed % 1000,
                       chaos_seed=seed if (loss or kmax) else None)
    fab = SwitchedFabric(n_senders + 1, cfg)
    mode = "selective_repeat" if sr else "go_back_n"
    kw = dict(fc_window=window, rx_mode=mode, n_qps=32, mtu=MTU)
    recv = RdmaNode(0, fab, **kw)
    senders = [RdmaNode(i + 1, fab, **kw) for i in range(n_senders)]
    for n in [recv] + senders:
        n.retx.timeout = timeout
    rng = np.random.default_rng(seed)
    for i, s in enumerate(senders):
        for j in range(1 + (extra_qps if i == 0 else 0)):
            q, _rk, _buf = s.init_rdma(1 << 16, recv)
            s.rdma_write(q, rng.integers(
                0, 256, max(nbytes + 777 * i - 301 * j, 1),
                dtype=np.uint8))
    nodes = [recv] + senders
    for _ in range(presteps):
        step_network(nodes)
    return nodes


def build_p2p(seed, *, sr=False, loss=0.0, reorder=0.0, jitter=0,
              nbytes=2000, latency=2, bw=0, window=16, presteps=0,
              n_flows=2, timeout=64):
    chaos = seed if (loss or reorder or jitter) else None
    cfg = LinkConfig(loss_prob=loss, reorder_prob=reorder,
                     jitter_ticks=jitter, latency_ticks=latency,
                     bandwidth_pkts_per_tick=bw, seed=seed % 1000,
                     chaos_seed=chaos)
    net = Network(2, cfg)
    mode = "selective_repeat" if sr else "go_back_n"
    kw = dict(fc_window=window, rx_mode=mode, n_qps=32, mtu=MTU)
    a, b = RdmaNode(0, net, **kw), RdmaNode(1, net, **kw)
    a.retx.timeout = b.retx.timeout = timeout
    rng = np.random.default_rng(seed)
    for i in range(n_flows):
        q, _rk, _buf = a.init_rdma(1 << 16, b)
        a.rdma_write(q, rng.integers(0, 256, nbytes + 501 * i,
                                     dtype=np.uint8))
    nodes = [a, b]
    for _ in range(presteps):
        step_network(nodes)
    return nodes


# ---------------------------------------------------------------------------
# property suites — random schedules, bit-identity, both RX modes
# ---------------------------------------------------------------------------

@settings(max_examples=5, deadline=None)
@given(st.integers(1, 2 ** 31), st.sampled_from([0.02, 0.08, 0.15]),
       st.integers(200, 3200), st.integers(0, 24), st.integers(0, 2))
def test_star_gbn_loss_bit_identical(seed, loss, nbytes, presteps,
                                     extra_qps):
    """Star fabric, go-back-N, chaos wire loss (drops force timeout
    retransmits => the receiver sees genuine duplicates), random message
    sizes / QP counts / mid-flight pack points."""
    nodes = build_star(seed, loss=loss, nbytes=nbytes, presteps=presteps,
                       extra_qps=extra_qps)
    assert_fused_matches_oracle(nodes)


@settings(max_examples=5, deadline=None)
@given(st.integers(1, 2 ** 31), st.sampled_from([0.02, 0.1]),
       st.integers(200, 3200), st.integers(0, 24))
def test_star_sr_loss_bit_identical(seed, loss, nbytes, presteps):
    """Star fabric, selective repeat: loss exercises the SACK bitmap,
    out-of-order DMA landing, gap resend and the pending-LAST flush."""
    nodes = build_star(seed, sr=True, loss=loss, nbytes=nbytes,
                       presteps=presteps)
    assert_fused_matches_oracle(nodes)


@settings(max_examples=5, deadline=None)
@given(st.integers(1, 2 ** 31), st.sampled_from([6, 8, 12]),
       st.integers(500, 3200), st.integers(0, 16))
def test_star_ecn_thresholds_bit_identical(seed, kmax, nbytes, presteps):
    """Star fabric under RED/ECN marking: random Kmax thresholds, a
    shallow drop-tail queue, CNP emission + holdoff on the receiver."""
    nodes = build_star(seed, kmax=kmax, nbytes=nbytes, n_senders=2,
                       bw=2, cap=14, presteps=presteps)
    assert_fused_matches_oracle(nodes)


@settings(max_examples=5, deadline=None)
@given(st.integers(1, 2 ** 31), st.sampled_from([0.02, 0.08]),
       st.sampled_from([0.1, 0.25]), st.integers(1, 3),
       st.integers(0, 24))
def test_p2p_gbn_spray_bit_identical(seed, loss, reorder, jitter,
                                     presteps):
    """Point-to-point links with chaos loss + reorder spray + jitter:
    go-back-N OOO NAKs, NAK holdoff, dup re-ACKs."""
    nodes = build_p2p(seed, loss=loss, reorder=reorder, jitter=jitter,
                      presteps=presteps)
    assert_fused_matches_oracle(nodes)


@settings(max_examples=5, deadline=None)
@given(st.integers(1, 2 ** 31), st.sampled_from([0.02, 0.08]),
       st.sampled_from([0.15, 0.3]), st.integers(1, 3),
       st.integers(0, 24))
def test_p2p_sr_spray_bit_identical(seed, loss, reorder, jitter,
                                    presteps):
    """Selective repeat under reorder spray: the bitmap advance,
    interval-merge progress tracking and SACK-driven release paths."""
    nodes = build_p2p(seed, sr=True, loss=loss, reorder=reorder,
                      jitter=jitter, presteps=presteps, bw=3)
    assert_fused_matches_oracle(nodes)


# ---------------------------------------------------------------------------
# deterministic pins
# ---------------------------------------------------------------------------

def test_zero_tick_roundtrip_is_identity():
    """max_ticks=0: pack -> epoch(0 steps) -> unpack must be a perfect
    round trip (the strongest possible layout/unpack pin)."""
    nodes = build_star(3)
    res = assert_fused_matches_oracle(nodes, max_ticks=0)
    assert res["steps"] == 0 and not res["idle_exit"]


def test_epoch_runs_to_idle_exit():
    nodes = build_star(5)
    res = assert_fused_matches_oracle(nodes)
    assert res["idle_exit"] and res["steps"] == res["ticks"] + 1
    # delivered bytes: every flow's buffer region matches what was sent
    recv = nodes[0]
    for s in nodes[1:]:
        for sq, dst in s._peer.items():
            assert dst == 0 and s.retx.slots.get(sq, {}) == {}


def test_watermark_exit_partial_epoch():
    """An armed completion watermark (the ingest micro-epoch contract)
    exits the epoch early — mid-transfer — and the partially advanced
    world still matches the oracle stepped the same number of ticks."""
    nodes = build_star(11, nbytes=3000, bw=2)
    recv, snd = nodes[0], nodes[1]
    rq = next(iter(recv._peer))
    wm = {(0, rq): 512}
    res = assert_fused_matches_oracle(nodes, watermarks=wm)
    assert res["wm_hit"] and not res["idle_exit"]
    assert recv.rx_progress(rq) >= 512
    # transfer not finished at the exit point
    assert any(snd.retx.slots.get(q) for q in snd._peer) \
        or any(len(p) for p in snd.fc.pending)


def test_unfusable_world_left_pristine():
    """A world the twin does not model (DCQCN rate state) must fall
    back with the Python objects untouched."""
    net = Network(2, LinkConfig(latency_ticks=2))
    a = RdmaNode(0, net, congestion_control="dcqcn", mtu=MTU)
    b = RdmaNode(1, net, congestion_control="dcqcn", mtu=MTU)
    q, _rk, _buf = a.init_rdma(1 << 14, b)
    a.rdma_write(q, np.arange(900, dtype=np.uint8) % 251)
    before = snap([a, b])
    assert fused.run_fused_epoch([a, b]) is None
    assert not diff(before, snap([a, b]))


def test_run_network_fused_mode_equivalent():
    """The run_network('fused') driver delivers the same bytes, stats
    and tick count as per-tick stepping on a fusable world."""
    results = {}
    for mode in ("tick", "fused"):
        nodes = build_star(17, loss=0.08, nbytes=2800)
        t = run_network(nodes, epoch_mode=mode)
        results[mode] = (t, snap(nodes))
    assert results["tick"][0] == results["fused"][0]
    d = diff(results["tick"][1], results["fused"][1])
    assert not d, "run_network fused diverged:\n  " + "\n  ".join(d[:40])


def test_default_width_incast_packs_and_matches_oracle():
    """The paper's 8:1 incast at the node defaults (500 QPs, MTU 4096,
    receiver window 64) needs more wire slots than any small world:
    it must pack — no silent per-tick fallback — and stay bit-identical
    to the oracle."""
    fab = SwitchedFabric(9, FabricConfig(port_bandwidth=4, port_delay=2,
                                         queue_capacity=32, seed=7))
    recv = RdmaNode(0, fab, rx_credits=64)
    senders = [RdmaNode(i + 1, fab, fc_window=16) for i in range(8)]
    rng = np.random.default_rng(13)
    for s in senders:
        q, _rk, _buf = s.init_rdma(1 << 15, recv)
        s.rdma_write(q, rng.integers(0, 256, 1 << 15, dtype=np.uint8))
    nodes = [recv] + senders
    assert recv.qp.tables.npsn.shape[0] == 500
    assert fused.try_pack(nodes, 100_000, 8) is not None
    res = assert_fused_matches_oracle(nodes)
    assert res["idle_exit"]
    assert fab.epochs.fused == 1 and fab.epochs.unfused == 0


def test_engine_counter_contract_rides_the_carry():
    """PR 8 contract: engine counter columns (accepted / dup / ooo /
    credit-drop / ecn totals) are harvested at the epoch boundary and
    match the oracle's per-tick accumulation exactly."""
    nodes = build_star(23, loss=0.1, nbytes=2600, n_senders=2)
    oracle = copy.deepcopy(nodes)
    res = fused.run_fused_epoch(nodes)
    assert res is not None
    for _ in range(res["steps"]):
        step_network(oracle)
    for nd_o, nd_f in zip(oracle, nodes):
        for f in ("acc_cnt", "dup_cnt", "ooo_cnt", "cdrop_cnt",
                  "ecn_tot"):
            np.testing.assert_array_equal(
                np.asarray(getattr(nd_o.rx_tables, f)),
                np.asarray(getattr(nd_f.rx_tables, f)), err_msg=f)
        assert vars(nd_o.stats) == vars(nd_f.stats)


def test_standing_incast_rounds_stay_fused_and_match_oracle():
    """A served incast keeps its QPs and takes round after round: PSNs
    continue, credits and flow-control ledgers carry over, receive
    buffers are written again.  Three rounds of a 4:1 incast at 64 KiB
    over 16-QP nodes stay fused, leave the world bit-identical to the
    same rounds stepped per tick, and land every byte of every round."""
    worlds = {m: incast_world(4, message_bytes=1 << 16, n_qps=16)
              for m in ("tick", "fused")}
    for r in range(3):
        rng = np.random.default_rng([29, r])
        pay = [rng.integers(0, 256, 1 << 16, dtype=np.uint8)
               for _ in range(4)]
        ticks = {}
        for mode, w in worlds.items():
            w.post_round(pay)
            ticks[mode] = w.run(epoch_mode=mode)
            assert w.completions() == [2 * (r + 1)] * 4
            for buf, p in zip(w.buffers, pay):
                np.testing.assert_array_equal(buf, p)
        assert ticks["tick"] == ticks["fused"]
        d = diff(snap(worlds["tick"].nodes), snap(worlds["fused"].nodes))
        assert not d, f"round {r} diverged:\n  " + "\n  ".join(d[:40])
    ep = worlds["fused"].fabric.epochs
    assert ep.fused == 3 and ep.unfused == 0 and ep.aborted == 0
    assert ep.carry_bytes > 0 and ep.carry_bytes % 3 == 0
    assert worlds["tick"].fabric.epochs.carry_bytes == 0


def test_star_epoch_sorts_per_tick():
    """The device trace counts the star epoch's ticks by its sorts: the
    compiled program holds ``WIRE_SORTS_PER_TICK`` of them, all in the
    tick body (the while loop's body is lowered once)."""
    w = incast_world(2, message_bytes=1 << 12, n_qps=4)
    w.post_round([np.zeros(1 << 12, np.uint8)] * 2)
    world = fused.try_pack(w.nodes, 1000, 8, None)
    assert world is not None
    hlo = fused.make_epoch_fn(world.skey).lower(
        jnp.asarray(world.vec0)).compile().as_text()
    assert len(re.findall(r"\bsort\(", hlo)) == fused.WIRE_SORTS_PER_TICK


def _deadlines(nodes):
    return {(n.node_id, q, psn): slot.deadline for n in nodes
            for q, slots in n.retx.slots.items()
            for psn, slot in slots.items()}


def widest_bump(nodes, steps):
    """Step ``nodes`` ``steps`` ticks; the most QPs that had held slots
    bumped (deadline moved) in one tick."""
    widest = 0
    for _ in range(steps):
        before = _deadlines(nodes)
        step_network(nodes)
        after = _deadlines(nodes)
        widest = max(widest, len({k[:2] for k, dl in before.items()
                                  if after.get(k, dl) != dl}))
    return widest


@pytest.mark.parametrize("sr", [False, True], ids=["gbn", "sr"])
@pytest.mark.parametrize("fabric", ["star", "p2p"])
def test_timeout_burst_across_flows_bit_identical(fabric, sr):
    """Every flow's window posted in the same tick, a retransmission
    timeout shorter than the round trip and chaos loss: the windows of
    all flows time out in the same tick, again at each backoff.  The
    compacted timer scan then decides in which order the flows' rows
    take wire slots, sequence numbers, loss ranks and retransmission
    counts, and the world must still match the oracle bit for bit."""
    if fabric == "star":
        nodes = build_star(41, sr=sr, loss=0.05, nbytes=1500, n_senders=3,
                           extra_qps=1, timeout=3)
    else:
        nodes = build_p2p(43, sr=sr, loss=0.05, nbytes=1500, n_flows=3,
                          timeout=3)
    flows = {k[:2] for k in _deadlines(nodes)}      # QPs holding a window
    assert len(flows) == (4 if fabric == "star" else 3)
    twin = copy.deepcopy(nodes)
    res = assert_fused_matches_oracle(nodes)
    assert widest_bump(twin, res["steps"]) == len(flows)
    assert res["work"][2] > 2 * len(flows)


def test_loop_trips_counts_live_work():
    """A 4:1 star incast at 128 KiB: ``EpochStats.loop_trips`` is the
    oracle's wire pops plus first sends plus retransmissions, each kind
    present, and a few trips a tick where looping over every slot would
    take F x PC + WCAP."""
    w = incast_world(4, message_bytes=1 << 17, n_qps=16)
    w.post_round([np.full(1 << 17, i, np.uint8) for i in range(4)])
    world = fused.try_pack(w.nodes, 100_000, 8)
    res = assert_fused_matches_oracle(w.nodes)
    pops, firsts, retx = res["work"]
    assert pops > 0 and firsts > 0 and retx > 0
    ep = w.fabric.epochs
    assert ep.loop_trips == pops + firsts + retx
    sk = world.skey
    assert ep.loop_trips * 20 < res["steps"] * (sk.F * sk.PC + sk.WCAP)


@pytest.mark.parametrize("mode", ["tick", "fused"])
def test_incast_scenario_unchanged(mode):
    """``incast_scenario`` is one round of the standing incast world and
    returns what it did before that world existed: ticks, tail drops,
    retransmissions and the payloads (digest) of a 4:1 incast at 64 KiB."""
    res = incast_scenario(4, message_bytes=1 << 16, epoch_mode=mode)
    assert (res.ticks, res.fabric.total_tail_dropped,
            sum(s.stats.retransmissions for s in res.senders)) == (81, 32, 32)
    digest = hashlib.sha256(b"".join(p.tobytes() for p in res.payloads))
    assert digest.hexdigest()[:16] == "dcdc55bb520922d0"
    for s, p in zip(res.senders, res.payloads):
        q = next(iter(s._peer))
        np.testing.assert_array_equal(
            res.receiver._buffer_for(s.remote_qpn(q)), p)
    assert res.fabric.epochs.unfused == 0
    assert res.fabric.epochs.fused == (mode == "fused")


if __name__ == "__main__":
    sys.exit(0)
