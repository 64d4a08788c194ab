"""Fused epoch core: whole simulator epochs inside one jitted loop.

Every per-tick construct of the Python simulator — the fabric's ingress
wire and drop-tail egress rings, RED/ECN mark state, the RDMA nodes'
retransmission slots, ACK-clocked flow-control ledgers and the RX
header-FSM tables — is packed into ONE flat int32 vector ("the blob")
and an entire epoch of network ticks runs inside a single jitted
``lax.while_loop`` with donated buffers.  The Python-object netsim
(`netsim.SwitchedFabric` / `netsim.Network`) stays the oracle: the
property suite (tests/test_fused_core.py) asserts the fused epoch is
bit-identical to per-tick stepping under loss / dup / ECN / reorder
schedules, for both go-back-N and selective-repeat RX modes.

Design
------
* ``try_pack(nodes)`` inspects the live simulation.  If every feature in
  play is one the in-graph twin models (see the gate list in
  ``try_pack``), it returns a ``_World`` — the blob plus the host-side
  plan needed to unpack.  Anything else returns ``None`` and the caller
  falls back to per-tick ``rdma.step_network`` — fused mode is a fast
  path, never a semantic fork.
* The *plan*: per directed flow (sender QP -> receiver QP), every packet
  that can possibly appear during the epoch is precomputed on the host
  (held retransmit slots, in-flight wire packets, and the fragments of
  still-queued flow-control chunks).  In-graph, a data packet is just
  ``(flow, plan_row)`` — payload bytes never touch the device; the DMA
  writes are replayed on the host at unpack from the recorded
  ``(accepted, address, order)`` columns.
* Randomness: loss / RED / jitter / reorder decisions replay the
  counter-keyed hash of ``repro.core.chaos`` — pure functions of
  ``(seed, purpose, tick, rank)`` that the sequential oracle and this
  vector core rank identically.
* The engine-counter contract of the telemetry plane is intact: the
  per-QP counter columns (``pipeline.COUNTER_FIELDS``) ride the blob
  and are harvested exactly once, at the epoch boundary.

The in-graph tick still mirrors the oracle *sequentially*, in exact
oracle event order — bit-identity is the gate — but its loops run over
live work only: the timer scan, the wire pop and the ACK/NAK resend and
dispatch loops first build their mask or count with one vector op, then
loop over the live indices alone, in the oracle's order (the carry's
``loop_trips`` counts those trips).  The win is host<->device traffic,
which drops from O(ticks) to O(1) per epoch (see BENCH_sync_census.json
before/after), and a tick whose cost follows the packets and timers in
play, not the slots allocated for them.
"""
from __future__ import annotations

import collections
import dataclasses
import heapq
from functools import lru_cache, partial
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import chaos
from repro.core import netsim
from repro.core import packet as pk
from repro.core import telemetry
from repro.core.pipeline import _STATE_FIELDS, _rx_decide
from repro.core.rdma import RdmaNode
from repro.core.retransmit import RetransmissionBuffer, _Slot

MASK = pk.PSN_MASK
SPAN = MASK + 1
HALF = MASK // 2
NEG = -(10 ** 9)             # "never happened" holdoff sentinel (rdma.py)
BIG = np.int32(2 ** 31 - 1)  # sort key for not-due wire slots
# Sorts in one ``_wire_due_perm`` (two argsorts), which the star fabric's
# tick calls once and which nothing else in the epoch program sorts
# besides: the device trace counts ticks by them.
WIRE_SORTS_PER_TICK = 2

_LAST_OPS = (pk.WRITE_LAST, pk.WRITE_ONLY,
             pk.READ_RESP_LAST, pk.READ_RESP_ONLY)

_PC_BUCKETS = (8, 16, 32, 64, 128, 256, 512)
_CC_BUCKETS = (4, 8, 16, 32, 64, 128)
_W_BUCKETS = (64, 128, 256, 512, 1024, 2048)


def _bucket(n: int, opts) -> Optional[int]:
    for o in opts:
        if n <= o:
            return o
    return None


def _i32(x: int) -> int:
    """uint32 value -> the int32 with the same bit pattern (the blob is
    all-int32; unsigned thresholds are compared via bitcast in-graph)."""
    x = int(x) & 0xFFFFFFFF
    return x - (1 << 32) if x >= (1 << 31) else x


def _u32(x):
    """Bitcast an int32 lane back to uint32 for unsigned compares."""
    return lax.bitcast_convert_type(jnp.asarray(x, jnp.int32), jnp.uint32)


def _hash(seed_u32, tag: int, tick, idx):
    """In-graph twin of ``chaos.hash32`` (uint32 lanes)."""
    u = jnp.uint32
    x = (seed_u32
         ^ (u(tag) * u(0x9E3779B1))
         ^ (jnp.asarray(tick, jnp.int32).astype(jnp.uint32) * u(0x85EBCA77))
         ^ (jnp.asarray(idx, jnp.int32).astype(jnp.uint32) * u(0xC2B2AE3D)))
    x = x ^ (x >> u(16))
    x = x * u(0x7FEB352D)
    x = x ^ (x >> u(15))
    x = x * u(0x846CA68B)
    x = x ^ (x >> u(16))
    return x


# ---------------------------------------------------------------------------
# Blob layout
# ---------------------------------------------------------------------------

class _Layout:
    """Name -> (offset, shape) map over one flat int32 vector.  The
    layout is a pure function of the shape key, so the jitted epoch
    function (cached per shape key) slices it with static offsets."""

    def __init__(self, spec):
        self.index: Dict[str, Tuple[int, Tuple[int, ...], int]] = {}
        off = 0
        for name, shape in spec:
            n = 1
            for s in shape:
                n *= s
            self.index[name] = (off, tuple(shape), n)
            off += n
        self.size = off

    def pack(self, vals: Dict[str, object]) -> np.ndarray:
        vec = np.zeros(self.size, np.int32)
        for name, (off, shape, n) in self.index.items():
            v = vals.get(name)
            if v is None:
                continue
            a = np.asarray(v, np.int64).reshape(-1)
            if a.size != n:
                raise ValueError(f"{name}: got {a.size} values, want {n}")
            vec[off:off + n] = a.astype(np.int32)
        return vec

    def unpack_jnp(self, vec) -> Dict[str, jax.Array]:
        c = {}
        for name, (off, shape, n) in self.index.items():
            v = vec[off:off + n]
            c[name] = v.reshape(shape) if shape else v[0]
        return c

    def concat(self, c: Dict[str, jax.Array]) -> jax.Array:
        parts = []
        for name, (off, shape, n) in self.index.items():
            v = jnp.asarray(c[name], jnp.int32)
            parts.append(v.reshape(-1) if shape else v.reshape(1))
        return jnp.concatenate(parts)

    def get(self, vec_np: np.ndarray, name: str):
        off, shape, n = self.index[name]
        v = vec_np[off:off + n]
        return v.reshape(shape) if shape else int(v[0])


@dataclasses.dataclass(frozen=True)
class ShapeKey:
    """Everything that decides trace shapes.  One jitted epoch function
    (and one layout) exists per distinct key (``make_epoch_fn`` is
    lru-cached on it)."""
    mode: str                 # "star" | "p2p"
    N: int                    # nodes
    P: int                    # star ports (0 for p2p)
    L: int                    # directed links (0 for star)
    G: int                    # delivery groups (= P or L)
    F: int                    # directed flows
    PC: int                   # plan rows per flow (bucketed)
    CC: int                   # pending chunks per flow (bucketed)
    WCAP: int                 # wire slots (bucketed)
    RCAP: int                 # egress ring depth (= queue_capacity)
    DEL: Tuple[int, ...]      # per-group delivery budget (static)
    LDST: Tuple[int, ...]     # per-link destination node (p2p)
    loss_on: bool
    ecn_on: bool
    jit_on: bool
    reo_on: bool
    wm_on: bool


def _layout_for(skey: ShapeKey) -> _Layout:
    N, P, L, G, F, PC, CC = (skey.N, skey.P, skey.L, skey.G, skey.F,
                             skey.PC, skey.CC)
    WCAP, RCAP = skey.WCAP, skey.RCAP
    S = ()                                    # scalar shape
    spec = [
        # -- globals ----------------------------------------------------
        ("now", S), ("steps", S), ("idle", S), ("abort", S),
        ("acc_ctr", S), ("wm_hit", S), ("max_ticks", S), ("idle_done", S),
        ("loop_trips", S),
        # -- flows ------------------------------------------------------
        ("f_snd", (F,)), ("f_sq", (F,)), ("f_rcv", (F,)), ("f_rq", (F,)),
        ("f_sr", (F,)), ("f_window", (F,)), ("f_gap_lag", (F,)),
        ("f_timeout", (F,)), ("f_base", (F,)), ("f_plan_len", (F,)),
        ("f_nchunks", (F,)), ("f_cursor", (F,)), ("f_next", (F,)),
        ("f_budget", (F,)), ("f_out", (F,)), ("f_tpassed_d", (F,)),
        ("f_last_nak", (F,)), ("f_last_nak_w", (F,)),
        ("f_last_gap", (F,)), ("f_last_gap_w", (F,)),
        ("f_last_cnp", (F,)), ("f_last_cnp_w", (F,)),
        ("f_wm", (F,)), ("f_wm_armed", (F,)), ("f_wm_thresh", (F,)),
        ("f_maxcred", (F,)), ("f_lastgid", (F,)),
        # -- plan -------------------------------------------------------
        ("p_op", (F, PC)), ("p_plen", (F, PC)), ("p_vaddr", (F, PC)),
        ("p_dlen", (F, PC)), ("p_ackreq", (F, PC)), ("p_rkey", (F, PC)),
        ("p_held", (F, PC)), ("p_retr", (F, PC)), ("p_dl", (F, PC)),
        ("p_acc", (F, PC)), ("p_aseq", (F, PC)), ("p_aaddr", (F, PC)),
        ("c_np", (F, CC)),
        # -- receiver RX rows (gathered QP-table rows, one per flow) ----
        ("rx_epsn", (F,)), ("rx_msn", (F,)), ("rx_bytes", (F,)),
        ("rx_cur", (F,)), ("rx_cred", (F,)), ("rx_rkey", (F,)),
        ("rx_rxbit", (F,)), ("rx_srf", (F,)),
        ("rx_acc", (F,)), ("rx_dup", (F,)), ("rx_ooo", (F,)),
        ("rx_cdrop", (F,)), ("rx_ecn", (F,)),
        # -- node stat deltas -------------------------------------------
        ("n_tx", (N,)), ("n_rx", (N,)), ("n_retx", (N,)),
        ("n_sacked", (N,)), ("n_cnptx", (N,)), ("n_cnprx", (N,)),
        # -- wire slots -------------------------------------------------
        ("w_valid", (WCAP,)), ("w_arr", (WCAP,)), ("w_seq", (WCAP,)),
        ("w_dst", (WCAP,)), ("w_flow", (WCAP,)), ("w_pidx", (WCAP,)),
        ("w_kind", (WCAP,)), ("w_ap", (WCAP,)), ("w_sack", (WCAP,)),
        # -- order tables -----------------------------------------------
        ("t_order", (F,)), ("cnp_ord", (G, F)),
    ]
    if skey.mode == "star":
        spec += [
            ("seq", S), ("injected_d", S), ("cseed", S), ("loss_t", S),
            ("kmin", S), ("kmax", S), ("csend", S), ("cpop", S),
            ("delay", (P,)), ("red_t", (RCAP + 1,)),
            ("pt_enq", (P,)), ("pt_del", (P,)), ("pt_tdrop", (P,)),
            ("pt_wdrop", (P,)), ("pt_ecn", (P,)), ("pt_maxd", (P,)),
            ("r_head", (P,)), ("r_len", (P,)),
            ("r_flow", (P, RCAP)), ("r_pidx", (P, RCAP)),
            ("r_kind", (P, RCAP)), ("r_ap", (P, RCAP)),
            ("r_sack", (P, RCAP)),
        ]
    else:
        spec += [
            ("l_seed", (L,)), ("l_loss_t", (L,)), ("l_reorder_t", (L,)),
            ("l_jitter", (L,)), ("l_lat", (L,)), ("l_seq", (L,)),
            ("l_sent_d", (L,)), ("l_drop_d", (L,)), ("l_cidx", (L,)),
            ("f_ldata", (F,)), ("f_lctrl", (F,)),
        ]
    return _Layout(spec)


@lru_cache(maxsize=None)
def _cached_layout(skey: ShapeKey) -> _Layout:
    return _layout_for(skey)


# ---------------------------------------------------------------------------
# Packing: live Python simulation -> blob (or None when not fusable)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _Flow:
    """Host-side view of one directed flow (sender QP -> receiver QP)."""
    idx: int
    snd: object                  # RdmaNode
    rcv: object
    sq: int                      # sender-local QPN
    rq: int                      # receiver-local QPN
    base: int                    # PSN of plan row 0
    plan: List[Optional[pk.Packet]]    # row -> packet template (or None)
    n_chunks: int
    window: int
    had_slot_key: bool           # retx.slots had the sq key at pack
    rx_prog0: int
    rx_prog_had_key: bool
    rx0: np.ndarray              # packed (13,) receiver table row


@dataclasses.dataclass
class _World:
    skey: ShapeKey
    layout: _Layout
    vec0: np.ndarray
    flows: List[_Flow]
    net: object
    link_keys: List[Tuple[int, int]]   # p2p only


def _ctrl_tuple(p: pk.Packet, flow: _Flow) -> Optional[Tuple[int, int, int]]:
    """Classify an in-flight control packet and verify it is exactly the
    packet the in-graph twin would reconstruct.  Returns (kind, ack_psn,
    sack) or None."""
    if p.opcode == pk.ACK:
        ref, kind = pk.make_ack(flow.sq, p.ack_psn, sack=p.sack_bits), 1
    elif p.opcode == pk.NAK:
        ref, kind = pk.make_ack(flow.sq, p.ack_psn, nak=True), 2
    elif p.opcode == pk.CNP:
        ref = pk.make_cnp(flow.sq, src_ip=flow.rcv.node_id, path_id=-1)
        kind = 3
    else:
        return None
    if not _pkt_eq(p, ref):
        return None
    return kind, int(p.ack_psn) & MASK, int(p.sack_bits)


def _pkt_eq(a: pk.Packet, b: pk.Packet) -> bool:
    for f in dataclasses.fields(pk.Packet):
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if f.name == "payload":
            an = va is None or va.size == 0
            bn = vb is None or vb.size == 0
            if an != bn or (not an and not np.array_equal(va, vb)):
                return False
        elif va != vb:
            return False
    return True


def try_pack(nodes, max_ticks: int, idle_done: int,
             watermarks: Optional[Dict[Tuple[int, int], int]] = None
             ) -> Optional[_World]:
    """Inspect the live simulation; return a packed ``_World`` when every
    feature in play is modeled in-graph, else None (caller falls back to
    per-tick stepping).  Packing never mutates the Python objects."""
    if not nodes:
        return None
    net = nodes[0].net
    N = len(nodes)
    for i, nd in enumerate(nodes):
        if (nd.net is not net or nd.node_id != i
                or nd.services is not None or nd.sniffer is not None
                or nd.recorder is not None or nd.fc.rate is not None
                or nd._retx_staged or nd._fatal_qps or nd.qp_errors):
            return None

    link_keys: List[Tuple[int, int]] = []
    if type(net) is netsim.SwitchedFabric:
        mode = "star"
        cfg = net.cfg
        if (net.reducer is not None or net.recorder is not None
                or net.n_nodes != N
                or any(q.on_event is not None for q in net.egress)
                or any(d < 1 for d in net.delay)):
            return None
        if (cfg.loss_prob > 0 or cfg.ecn_kmax > 0) and cfg.chaos_seed is None:
            return None
        P, L, G = N, 0, N
        loss_on, ecn_on = cfg.loss_prob > 0, cfg.ecn_kmax > 0
        jit_on = reo_on = False
        RCAP = int(cfg.queue_capacity)
    elif type(net) is netsim.Network:
        mode = "p2p"
        if net.recorder is not None:
            return None
        link_keys = list(net.links)          # oracle delivery order
        links = [net.links[k] for k in link_keys]
        if not links:
            return None
        c0 = links[0].cfg
        for (a, b), lk in zip(link_keys, links):
            lc = lk.cfg
            if (lk.on_event is not None or a >= N or b >= N
                    or lc.latency_ticks < 1
                    or lc.loss_prob != c0.loss_prob
                    or lc.reorder_prob != c0.reorder_prob
                    or lc.jitter_ticks != c0.jitter_ticks
                    or (lc.chaos_seed is None) != (c0.chaos_seed is None)):
                return None
        loss_on, reo_on = c0.loss_prob > 0, c0.reorder_prob > 0
        jit_on = c0.jitter_ticks > 0
        if (loss_on or reo_on or jit_on) and c0.chaos_seed is None:
            return None
        P, L, G = 0, len(links), len(links)
        ecn_on = False
        RCAP = 1                              # unused; keep layout small
    else:
        return None

    # ---- enumerate directed flows -------------------------------------
    flows: List[_Flow] = []
    by_rcv: Dict[Tuple[int, int], _Flow] = {}
    by_snd: Dict[Tuple[int, int], _Flow] = {}
    for s in nodes:
        for sq in sorted(s._peer):
            dst = s._peer[sq]
            if not 0 <= dst < N:
                return None
            r = nodes[dst]
            rq = int(s.qp.tables.remote_qpn[sq])
            if (int(r.qp.tables.remote_qpn[rq]) != sq or s._sr != r._sr):
                return None
            fl = _Flow(idx=len(flows), snd=s, rcv=r, sq=sq, rq=rq,
                       base=0, plan=[], n_chunks=0,
                       window=int(s.fc.cfg.window),
                       had_slot_key=sq in s.retx.slots,
                       rx_prog0=r._rx_progress.get(rq, 0),
                       rx_prog_had_key=rq in r._rx_progress,
                       rx0=np.zeros(13, np.int64))
            flows.append(fl)
            by_rcv[(r.node_id, rq)] = fl
            by_snd[(s.node_id, sq)] = fl
    F = len(flows)
    if F == 0:
        return None

    # ---- collect every in-flight packet -------------------------------
    # (container, dst, arrival, seq) tuples; classification below
    inflight: List[Tuple[str, int, int, int, pk.Packet]] = []
    ring_content: List[List[pk.Packet]] = []
    if mode == "star":
        for arr, seq, dst, p in net._wire:
            inflight.append(("wire", dst, arr, seq, p))
        for port, q in enumerate(net.egress):
            pkts = []
            for item in q._q:
                p, meta = item
                if meta is not None:
                    return None
                pkts.append(p)
                inflight.append(("ring", port, 0, 0, p))
            ring_content.append(pkts)
    else:
        for li, lk in enumerate(links):
            for arr, seq, p in lk._heap:
                inflight.append(("wire", li, arr, seq, p))

    def _flow_of(p: pk.Packet, dst_node: int) -> Optional[Tuple[_Flow, int]]:
        if p.coll_tag or p.ecn or p.path_id != -1:
            return None
        if p.opcode in pk.PAYLOAD_OPS:
            fl = by_rcv.get((dst_node, p.qpn))
            return None if fl is None else (fl, 0)
        fl = by_snd.get((dst_node, p.qpn))
        if fl is None:
            return None
        ct = _ctrl_tuple(p, fl)
        return None if ct is None else (fl, ct[0])

    # map in-flight data packets onto their flow (psn -> packet)
    data_by_flow: List[Dict[int, pk.Packet]] = [dict() for _ in range(F)]
    for where, loc, arr, seq, p in inflight:
        dst_node = loc if mode == "star" else link_keys[loc][1]
        hit = _flow_of(p, dst_node)
        if hit is None:
            return None
        fl, kind = hit
        if kind == 0:
            prev = data_by_flow[fl.idx].setdefault(p.psn & MASK, p)
            if prev is not p and not _pkt_eq(prev, p):
                return None

    # ---- per-flow plan construction -----------------------------------
    tbl = [np.asarray(jnp.stack(
        [jnp.asarray(getattr(nd.rx_tables, f)) for f in _STATE_FIELDS]))
        for nd in nodes]
    chunk_rows: List[List[int]] = []
    for fl in flows:
        s, r, sq, rq = fl.snd, fl.rcv, fl.sq, fl.rq
        held = s.retx.slots.get(sq, {})
        for slot in held.values():
            if slot.packet.opcode not in pk.PAYLOAD_OPS:
                return None
        npsn = int(s.qp.tables.npsn[sq])
        psns = set(held) | set(data_by_flow[fl.idx])
        offs = [(npsn - psn) & MASK for psn in psns]
        if any(o == 0 or o > HALF for o in offs):
            return None
        base = npsn - (max(offs) if offs else 0)
        if base < 0:
            return None
        templates: List[Optional[pk.Packet]] = []
        for row in range(npsn - base):
            psn = base + row
            if psn in held:
                templates.append(held[psn].packet)
            elif psn in data_by_flow[fl.idx]:
                templates.append(data_by_flow[fl.idx][psn])
            else:
                templates.append(None)
        cur, npkts = npsn, []
        for n_req, item in s.fc.pending[sq]:
            kind, addr, data, coll = item
            if kind == "read" or coll is not None:
                return None
            pkts = pk.fragment_message(
                rq, cur, addr, s._remote_rkey[sq], data,
                op="write" if kind == "write" else "read_resp",
                mtu=s.mtu, src_ip=s.node_id,
                dst_ip=int(s.qp.tables.remote_ip[sq]),
                addr_per_pkt=s._sr)
            if len(pkts) != n_req:
                return None
            templates.extend(pkts)
            npkts.append(n_req)
            cur = (cur + n_req) & MASK
        if base + len(templates) >= SPAN:
            return None
        for row, t in enumerate(templates):
            if t is None:
                continue
            if (t.psn != base + row or t.opcode not in pk.PAYLOAD_OPS
                    or t.vaddr < 0 or t.vaddr + t.dma_len >= 2 ** 31
                    or t.payload_len > min(s.mtu, r.mtu)):
                return None
        for psn, p in data_by_flow[fl.idx].items():
            if not _pkt_eq(p, templates[psn - base]):
                return None
        fl.base, fl.plan, fl.n_chunks = base, templates, len(npkts)
        chunk_rows.append(npkts)
        # receiver-side invariants
        if (r.credits.credits[rq] != r.credits.max_credits
                or fl.rx_prog0 >= 2 ** 31 or r._buffer_for(rq) is None):
            return None
        row13 = tbl[r.node_id][:, rq].astype(np.int64)
        if bool(row13[_STATE_FIELDS.index("sr")]) != s._sr:
            return None
        fl.rx0 = row13
        if watermarks and (r.node_id, rq) in watermarks and s._sr:
            return None                       # watermark exit is GBN-only

    # ---- buckets / shape key ------------------------------------------
    PC = _bucket(max(max((len(fl.plan) for fl in flows)), 1), _PC_BUCKETS)
    CC = _bucket(max(max((fl.n_chunks for fl in flows)), 1), _CC_BUCKETS)
    n_wire = sum(1 for e in inflight if e[0] == "wire")
    WCAP = _bucket(n_wire + 2 * sum(fl.window for fl in flows)
                   + 2 * F + 16, _W_BUCKETS)
    if PC is None or CC is None or WCAP is None:
        return None
    if mode == "star":
        DEL = tuple(min(b, RCAP) for b in net.bandwidth)
        LDST: Tuple[int, ...] = ()
    else:
        DEL = tuple(min(lk.cfg.bandwidth_pkts_per_tick or (1 << 30), WCAP)
                    for lk in links)
        LDST = tuple(b for (_a, b) in link_keys)
    skey = ShapeKey(mode=mode, N=N, P=P, L=L, G=G, F=F, PC=PC, CC=CC,
                    WCAP=WCAP, RCAP=RCAP, DEL=DEL, LDST=LDST,
                    loss_on=loss_on, ecn_on=ecn_on, jit_on=jit_on,
                    reo_on=reo_on, wm_on=bool(watermarks))
    layout = _cached_layout(skey)

    # ---- blob values ---------------------------------------------------
    v: Dict[str, object] = {
        "now": net.now, "max_ticks": max_ticks, "idle_done": idle_done,
        "f_snd": [fl.snd.node_id for fl in flows],
        "f_sq": [fl.sq for fl in flows],
        "f_rcv": [fl.rcv.node_id for fl in flows],
        "f_rq": [fl.rq for fl in flows],
        "f_sr": [int(fl.snd._sr) for fl in flows],
        "f_window": [fl.window for fl in flows],
        "f_gap_lag": [fl.snd.sr_gap_lag for fl in flows],
        "f_timeout": [fl.snd.retx.timeout for fl in flows],
        "f_base": [fl.base for fl in flows],
        "f_plan_len": [len(fl.plan) for fl in flows],
        "f_nchunks": [fl.n_chunks for fl in flows],
        "f_budget": [fl.snd.fc.budget[fl.sq] for fl in flows],
        "f_out": [fl.snd.fc.outstanding[fl.sq] for fl in flows],
        "f_last_nak": [fl.snd._last_nak_resend.get(fl.sq, NEG)
                       for fl in flows],
        "f_last_gap": [fl.snd._last_gap_resend.get(fl.sq, NEG)
                       for fl in flows],
        "f_last_cnp": [fl.rcv._last_cnp_sent.get(fl.rq, NEG)
                       for fl in flows],
        "f_wm": [fl.rx_prog0 for fl in flows],
        "f_wm_armed": [int(bool(watermarks)
                           and (fl.rcv.node_id, fl.rq) in watermarks)
                       for fl in flows],
        "f_wm_thresh": [(watermarks or {}).get((fl.rcv.node_id, fl.rq), 0)
                        for fl in flows],
        "f_maxcred": [fl.rcv.credits.max_credits for fl in flows],
    }
    p_op = np.zeros((F, PC), np.int64)
    p_plen = np.zeros((F, PC), np.int64)
    p_vaddr = np.zeros((F, PC), np.int64)
    p_dlen = np.zeros((F, PC), np.int64)
    p_ackreq = np.zeros((F, PC), np.int64)
    p_rkey = np.zeros((F, PC), np.int64)
    p_held = np.zeros((F, PC), np.int64)
    p_retr = np.zeros((F, PC), np.int64)
    p_dl = np.zeros((F, PC), np.int64)
    p_aseq = np.full((F, PC), -1, np.int64)
    c_np = np.zeros((F, CC), np.int64)
    for fl, npkts in zip(flows, chunk_rows):
        held = fl.snd.retx.slots.get(fl.sq, {})
        for row, t in enumerate(fl.plan):
            if t is None:
                continue
            p_op[fl.idx, row] = t.opcode
            p_plen[fl.idx, row] = t.payload_len
            p_vaddr[fl.idx, row] = t.vaddr
            p_dlen[fl.idx, row] = t.dma_len
            p_ackreq[fl.idx, row] = int(t.ack_req)
            p_rkey[fl.idx, row] = t.rkey
        for psn, slot in held.items():
            row = psn - fl.base
            p_held[fl.idx, row] = 1
            p_retr[fl.idx, row] = slot.retries
            p_dl[fl.idx, row] = slot.deadline
        c_np[fl.idx, :len(npkts)] = npkts
        v["f_next"] = v.get("f_next", [])
    v["f_next"] = [int(fl.snd.qp.tables.npsn[fl.sq]) - fl.base
                   for fl in flows]
    v.update(p_op=p_op, p_plen=p_plen, p_vaddr=p_vaddr, p_dlen=p_dlen,
             p_ackreq=p_ackreq, p_rkey=p_rkey, p_held=p_held,
             p_retr=p_retr, p_dl=p_dl, p_aseq=p_aseq, c_np=c_np)
    rx_names = ("rx_epsn", "rx_msn", "rx_bytes", "rx_cur", "rx_cred",
                "rx_rkey", "rx_rxbit", "rx_srf", "rx_acc", "rx_dup",
                "rx_ooo", "rx_cdrop", "rx_ecn")
    rxm = np.stack([fl.rx0 for fl in flows], axis=1)    # (13, F)
    for k, name in enumerate(rx_names):
        v[name] = rxm[k]

    # wire slots
    wn = ("w_valid", "w_arr", "w_seq", "w_dst", "w_flow", "w_pidx",
          "w_kind", "w_ap", "w_sack")
    wv = {n: np.zeros(WCAP, np.int64) for n in wn}
    wi = 0
    for where, loc, arr, seq, p in inflight:
        if where != "wire":
            continue
        dst_node = loc if mode == "star" else link_keys[loc][1]
        fl, kind = _flow_of(p, dst_node)
        if kind == 0:
            pidx, ap, sack = (p.psn & MASK) - fl.base, 0, 0
        else:
            _, ap, sack = _ctrl_tuple(p, fl)
            pidx = 0
        wv["w_valid"][wi] = 1
        wv["w_arr"][wi] = arr
        wv["w_seq"][wi] = seq
        wv["w_dst"][wi] = loc
        wv["w_flow"][wi] = fl.idx
        wv["w_pidx"][wi] = pidx
        wv["w_kind"][wi] = kind
        wv["w_ap"][wi] = ap
        wv["w_sack"][wi] = sack
        wi += 1
    v.update(wv)

    # order tables
    v["t_order"] = sorted(range(F), key=lambda i: (flows[i].snd.node_id,
                                                   flows[i].sq))
    cnp_ord = np.full((G, F), -1, np.int64)
    for g in range(G):
        dst_node = g if mode == "star" else LDST[g]
        fs = sorted((fl for fl in flows if fl.rcv.node_id == dst_node),
                    key=lambda fl: fl.rq)
        for j, fl in enumerate(fs):
            cnp_ord[g, j] = fl.idx
    v["cnp_ord"] = cnp_ord

    if mode == "star":
        red = np.zeros(RCAP + 1, np.int64)
        if cfg.ecn_kmax > 0:
            for d in range(RCAP + 1):
                ramp = cfg.ecn_pmax * (d - cfg.ecn_kmin) / max(
                    cfg.ecn_kmax - cfg.ecn_kmin, 1)
                red[d] = _i32(chaos.u32_prob(min(max(ramp, 0.0), 1.0)))
        v.update(
            seq=net._seq, cseed=_i32(cfg.chaos_seed or 0),
            loss_t=_i32(chaos.u32_prob(cfg.loss_prob)),
            kmin=cfg.ecn_kmin, kmax=cfg.ecn_kmax,
            delay=net.delay, red_t=red,
            pt_maxd=[st.max_depth for st in net.port_stats],
            r_len=[len(q) for q in ring_content],
        )
        rn = ("r_flow", "r_pidx", "r_kind", "r_ap", "r_sack")
        rv = {n: np.zeros((P, RCAP), np.int64) for n in rn}
        for port, pkts in enumerate(ring_content):
            for j, p in enumerate(pkts):
                fl, kind = _flow_of(p, port)
                if kind == 0:
                    pidx, ap, sack = (p.psn & MASK) - fl.base, 0, 0
                else:
                    _, ap, sack = _ctrl_tuple(p, fl)
                    pidx = 0
                rv["r_flow"][port, j] = fl.idx
                rv["r_pidx"][port, j] = pidx
                rv["r_kind"][port, j] = kind
                rv["r_ap"][port, j] = ap
                rv["r_sack"][port, j] = sack
        v.update(rv)
    else:
        v.update(
            l_seed=[_i32(lk.cfg.chaos_seed or 0) for lk in links],
            l_loss_t=[_i32(chaos.u32_prob(lk.cfg.loss_prob))
                      for lk in links],
            l_reorder_t=[_i32(chaos.u32_prob(lk.cfg.reorder_prob))
                         for lk in links],
            l_jitter=[lk.cfg.jitter_ticks for lk in links],
            l_lat=[lk.cfg.latency_ticks for lk in links],
            l_seq=[lk._seq for lk in links],
            f_ldata=[link_keys.index((fl.snd.node_id, fl.rcv.node_id))
                     for fl in flows],
            f_lctrl=[link_keys.index((fl.rcv.node_id, fl.snd.node_id))
                     for fl in flows],
        )

    vec0 = layout.pack(v)
    return _World(skey=skey, layout=layout, vec0=vec0, flows=flows,
                  net=net, link_keys=link_keys)


# ---------------------------------------------------------------------------
# The jitted epoch graph
# ---------------------------------------------------------------------------

def _up(c, **kw):
    d = dict(c)
    d.update(kw)
    return d


@lru_cache(maxsize=None)
def make_epoch_fn(skey: ShapeKey):
    """Build (and cache, per shape key) the jitted blob -> blob epoch
    function.  The in-graph tick mirrors the Python oracle *in exact
    event order*, looping over live rows and wire slots only
    (``_live_loop``); the payoff is that the entire epoch is ONE device
    program with ONE donated input and ONE output — host<->device
    traffic no longer scales with ticks."""
    layout = _cached_layout(skey)
    star = skey.mode == "star"
    N, F, PC, CC = skey.N, skey.F, skey.PC, skey.CC
    RCAP = skey.RCAP
    ARPC = jnp.arange(PC, dtype=jnp.int32)
    I32 = partial(jnp.asarray, dtype=jnp.int32)

    def _live_loop(c, live, body):
        """``body(i, c)`` for each live index ``i``, ascending: the
        oracle's visiting order with the idle visits left out.  ``live``
        is a flat mask (its set indices, compacted without a sort) or a
        count ``n`` (indices ``0 .. n-1``).  The trips add to
        ``loop_trips``."""
        if live.ndim:
            idx = jnp.nonzero(live, size=live.size, fill_value=0)[0]
            n = jnp.sum(live.astype(jnp.int32))
            step = lambda j, c: body(idx[j], c)        # noqa: E731
        else:
            n, step = live, body
        c = _up(c, loop_trips=c["loop_trips"] + n)
        return lax.fori_loop(0, n, step, c)

    # ---- wire / ring primitives ---------------------------------------
    def _wire_push(c, arr, loc, seqv, f, kind, pidx, ap, sack):
        free = jnp.argmin(c["w_valid"])
        c = _up(c, abort=c["abort"] | c["w_valid"][free],
                w_valid=c["w_valid"].at[free].set(1),
                w_arr=c["w_arr"].at[free].set(arr),
                w_seq=c["w_seq"].at[free].set(seqv),
                w_dst=c["w_dst"].at[free].set(loc),
                w_flow=c["w_flow"].at[free].set(f),
                w_pidx=c["w_pidx"].at[free].set(pidx),
                w_kind=c["w_kind"].at[free].set(kind),
                w_ap=c["w_ap"].at[free].set(ap),
                w_sack=c["w_sack"].at[free].set(sack))
        return c

    def _ring_enq(c, dst, f, kind, pidx, ap, sack):
        depth = c["r_len"][dst]

        def drop(c):
            return _up(c, pt_tdrop=c["pt_tdrop"].at[dst].add(1))

        def enq(c):
            slot = (c["r_head"][dst] + depth) % RCAP
            return _up(
                c,
                r_flow=c["r_flow"].at[dst, slot].set(f),
                r_pidx=c["r_pidx"].at[dst, slot].set(pidx),
                r_kind=c["r_kind"].at[dst, slot].set(kind),
                r_ap=c["r_ap"].at[dst, slot].set(ap),
                r_sack=c["r_sack"].at[dst, slot].set(sack),
                r_len=c["r_len"].at[dst].add(1),
                pt_enq=c["pt_enq"].at[dst].add(1),
                pt_maxd=c["pt_maxd"].at[dst].set(
                    jnp.maximum(c["pt_maxd"][dst], depth + 1)))
        return lax.cond(depth >= RCAP, drop, enq, c)

    # ---- transmit (mirrors net.send called from RdmaNode._send) -------
    def _send(c, src, f, kind, pidx, ap, sack):
        c = _up(c, n_tx=c["n_tx"].at[src].add(1))
        if star:
            dst = jnp.where(kind == 0, c["f_rcv"][f], c["f_snd"][f])
            c = _up(c, injected_d=c["injected_d"] + 1)

            def push(c):
                seqv = c["seq"] + 1
                c = _up(c, seq=seqv)
                return _wire_push(c, c["now"] + c["delay"][src], dst,
                                  seqv, f, kind, pidx, ap, sack)
            if skey.loss_on:
                h = _hash(_u32(c["cseed"]), chaos.TAG_LOSS,
                          c["now"], c["csend"])
                lost = h < _u32(c["loss_t"])
                c = _up(c, csend=c["csend"] + 1)
                c = lax.cond(
                    lost,
                    lambda c: _up(c, pt_wdrop=c["pt_wdrop"].at[dst].add(1)),
                    push, c)
            else:
                c = push(c)
        else:
            link = jnp.where(kind == 0, c["f_ldata"][f], c["f_lctrl"][f])
            c = _up(c, l_sent_d=c["l_sent_d"].at[link].add(1))
            rank = c["l_cidx"][link]
            c = _up(c, l_cidx=c["l_cidx"].at[link].add(1))
            seed = _u32(c["l_seed"][link])

            def push(c):
                delay = c["l_lat"][link]
                if skey.jit_on:
                    jit = _hash(seed, chaos.TAG_JITTER, c["now"], rank) % (
                        c["l_jitter"][link] + 1).astype(jnp.uint32)
                    delay = delay + jit.astype(jnp.int32)
                if skey.reo_on:
                    hit = _hash(seed, chaos.TAG_REORDER, c["now"],
                                rank) < _u32(c["l_reorder_t"][link])
                    extra = jnp.int32(1) + (
                        _hash(seed, chaos.TAG_RDELAY, c["now"], rank)
                        % jnp.uint32(7)).astype(jnp.int32)
                    delay = delay + jnp.where(hit, extra, 0)
                seqv = c["l_seq"][link] + 1
                c = _up(c, l_seq=c["l_seq"].at[link].set(seqv))
                return _wire_push(c, c["now"] + delay, link, seqv,
                                  f, kind, pidx, ap, sack)
            if skey.loss_on:
                lost = _hash(seed, chaos.TAG_LOSS, c["now"],
                             rank) < _u32(c["l_loss_t"][link])
                c = lax.cond(
                    lost,
                    lambda c: _up(c, l_drop_d=c["l_drop_d"].at[link].add(1)),
                    push, c)
            else:
                c = push(c)
        return c

    def _send_data(c, f, row):
        return _send(c, c["f_snd"][f], f, I32(0), row, I32(0), I32(0))

    def _send_ctrl(c, f, kind, ap, sack):
        return _send(c, c["f_rcv"][f], f, kind, I32(0), ap, sack)

    # ---- retransmit bump (retransmit._bump + rdma._send_retx) ---------
    def _bump_send(c, f, row):
        r = c["p_retr"][f, row] + 1
        c = _up(c, p_retr=c["p_retr"].at[f, row].set(r))
        exh = r > RetransmissionBuffer.MAX_RETRIES
        c = _up(c, abort=c["abort"] | exh.astype(jnp.int32))

        def fire(c):
            dl = c["now"] + c["f_timeout"][f] * jnp.left_shift(
                jnp.int32(1), jnp.minimum(r, 4))
            c = _up(c, p_dl=c["p_dl"].at[f, row].set(dl),
                    n_retx=c["n_retx"].at[c["f_snd"][f]].add(1))
            return _send_data(c, f, row)
        return lax.cond(exh, lambda c: c, fire, c)

    # ---- control-plane handlers ---------------------------------------
    def _on_ack(c, f, ap, sack):
        psn_row = (c["f_base"][f] + ARPC) & MASK
        held = c["p_held"][f] > 0
        # cumulative release (retransmit.ack): everything at or behind ap
        rel1 = held & (((ap - psn_row) & MASK) <= HALF)
        n1 = jnp.sum(rel1.astype(jnp.int32))
        held1 = held & ~rel1
        # selective release (retransmit.sack_release): bit j>=1 -> ap+1+j
        sacknz = sack != 0
        off2 = (psn_row - ap - 1) & MASK
        inb = (off2 >= 1) & (off2 <= 31)
        bitv = jnp.bitwise_and(
            lax.shift_right_logical(sack, jnp.where(inb, off2, 0)), 1)
        rel2 = held1 & inb & (bitv > 0) & sacknz
        n2 = jnp.sum(rel2.astype(jnp.int32))
        held2 = held1 & ~rel2
        anyrel = (n1 > 0) | (n2 > 0)
        c = _up(c,
                p_held=c["p_held"].at[f].set(held2.astype(jnp.int32)),
                p_retr=c["p_retr"].at[f].set(
                    jnp.where(held2 & anyrel, 0, c["p_retr"][f])),
                n_sacked=c["n_sacked"].at[c["f_snd"][f]].add(n2))
        # SACK-driven gap resend (rdma._maybe_gap_resend)
        do_gap = sacknz & ~((c["now"] - c["f_last_gap"][f])
                            < RdmaNode.NAK_HOLDOFF)
        bl = (jnp.int32(32) - lax.clz(_u32(sack)).astype(jnp.int32))
        hi = (ap + bl) & MASK
        offg = (psn_row - ap) & MASK
        lag = (hi - psn_row) & MASK
        gmask = (held2 & (offg > 0) & (offg <= HALF) & (lag <= HALF)
                 & (lag >= c["f_gap_lag"][f]) & do_gap)
        c = lax.cond(
            jnp.any(gmask),
            lambda c: _up(c,
                          f_last_gap=c["f_last_gap"].at[f].set(c["now"]),
                          f_last_gap_w=c["f_last_gap_w"].at[f].set(1)),
            lambda c: c, c)
        c = _live_loop(c, gmask, lambda row, c: _bump_send(c, f, row))
        # ACK-clocked flow control (flow_control.ack + _drain + dispatch)
        rel = jnp.maximum(n1 + n2, 1)
        out0 = jnp.maximum(0, c["f_out"][f] - rel)
        bud0 = jnp.minimum(c["f_window"][f], c["f_budget"][f] + rel)
        cur0, nch, row_np = c["f_cursor"][f], c["f_nchunks"][f], c["c_np"][f]

        def drain_body(k, st):
            go, bud, taken, tot = st
            idx = jnp.minimum(cur0 + k, CC - 1)
            fit = go & ((cur0 + k) < nch) & (row_np[idx] <= bud)
            return (fit, jnp.where(fit, bud - row_np[idx], bud),
                    taken + fit.astype(jnp.int32),
                    tot + jnp.where(fit, row_np[idx], 0))
        _go, bud1, taken, tot = lax.fori_loop(
            0, CC, drain_body,
            (jnp.asarray(True), bud0, I32(0), I32(0)))
        nxt0 = c["f_next"][f]
        c = _up(c,
                f_cursor=c["f_cursor"].at[f].add(taken),
                f_next=c["f_next"].at[f].add(tot),
                f_out=c["f_out"].at[f].set(out0 + tot),
                f_budget=c["f_budget"].at[f].set(bud1),
                f_tpassed_d=c["f_tpassed_d"].at[f].add(taken))

        def fire(k, c):
            row = nxt0 + k
            c = _up(c, p_held=c["p_held"].at[f, row].set(1),
                    p_retr=c["p_retr"].at[f, row].set(0),
                    p_dl=c["p_dl"].at[f, row].set(
                        c["now"] + c["f_timeout"][f]))
            return _send_data(c, f, row)
        return _live_loop(c, tot, fire)

    def _on_nak(c, f, ap):
        skip = (c["now"] - c["f_last_nak"][f]) < RdmaNode.NAK_HOLDOFF

        def doit(c):
            c = _up(c, f_last_nak=c["f_last_nak"].at[f].set(c["now"]),
                    f_last_nak_w=c["f_last_nak_w"].at[f].set(1))
            expected = (ap + 1) & MASK
            psn_row = (c["f_base"][f] + ARPC) & MASK
            mask = (c["p_held"][f] > 0) & (
                ((psn_row - expected) & MASK) <= HALF)
            return _live_loop(c, mask, lambda row, c: _bump_send(c, f, row))
        return lax.cond(skip, lambda c: c, doit, c)

    def _on_cnp(c, f, _ap):
        return _up(c, n_cnprx=c["n_cnprx"].at[c["f_snd"][f]].add(1))

    # ---- one delivered batch through one node (rdma.on_packets) -------
    def _process_batch(c, g, dst, buf, B):
        bv, bf, bp_, bk, ba, bs, be = buf
        c = _up(c, n_rx=c["n_rx"].at[dst].add(jnp.sum(bv)))

        # pass A: control packets, batch order
        def ctrl_body(i, c):
            def do(c):
                f = bf[i]
                return lax.switch(
                    bk[i] - 1,
                    [lambda c: _on_ack(c, f, ba[i], bs[i]),
                     lambda c: _on_nak(c, f, ba[i]),
                     lambda c: _on_cnp(c, f, ba[i])],
                    c)
            return lax.cond((bv[i] > 0) & (bk[i] > 0), do, lambda c: c, c)
        c = lax.fori_loop(0, B, ctrl_body, c)

        # pass E: data packets through the RX decide FSM, batch order.
        # on_packets copies the WHOLE host credit column into the table
        # before running the engine on a data-bearing batch; the host
        # ledger is back at max between batches (every accept replenishes
        # what the engine debited — see the invariant note in try_pack),
        # so the copy is a column-wide reset to max for this node.
        anydata = jnp.sum((bv > 0) & (bk == 0)) > 0
        c = _up(c, rx_cred=jnp.where(
            anydata & (c["f_rcv"] == dst), c["f_maxcred"], c["rx_cred"]))

        def data_body(i, st):
            def do(st):
                c, ecn_f, o_ack, o_ap, o_sk, o_nak = st
                f, pidx = bf[i], bp_[i]
                state = {
                    "epsn": c["rx_epsn"][f], "msn": c["rx_msn"][f],
                    "bytes_left": c["rx_bytes"][f],
                    "cur_vaddr": c["rx_cur"][f],
                    "credits": c["rx_cred"][f], "rkey": c["rx_rkey"][f],
                    "rxbit": c["rx_rxbit"][f], "sr": c["rx_srf"][f],
                    "acc_cnt": c["rx_acc"][f], "dup_cnt": c["rx_dup"][f],
                    "ooo_cnt": c["rx_ooo"][f],
                    "cdrop_cnt": c["rx_cdrop"][f],
                    "ecn_tot": c["rx_ecn"][f]}
                p = {"qpn": c["f_rq"][f], "opcode": c["p_op"][f, pidx],
                     "psn": (c["f_base"][f] + pidx) & MASK,
                     "plen": c["p_plen"][f, pidx],
                     "vaddr": c["p_vaddr"][f, pidx],
                     "dma_len": c["p_dlen"][f, pidx],
                     "ack_req": c["p_ackreq"][f, pidx], "ecn": be[i],
                     "rkey": c["p_rkey"][f, pidx], "valid": jnp.int32(1)}
                ns, out = _rx_decide(state, p)
                c = _up(c,
                        rx_epsn=c["rx_epsn"].at[f].set(ns["epsn"]),
                        rx_msn=c["rx_msn"].at[f].set(ns["msn"]),
                        rx_bytes=c["rx_bytes"].at[f].set(
                            jnp.asarray(ns["bytes_left"], jnp.int32)),
                        rx_cur=c["rx_cur"].at[f].set(
                            jnp.asarray(ns["cur_vaddr"], jnp.int32)),
                        rx_cred=c["rx_cred"].at[f].set(ns["credits"]),
                        rx_rxbit=c["rx_rxbit"].at[f].set(ns["rxbit"]),
                        rx_acc=c["rx_acc"].at[f].set(ns["acc_cnt"]),
                        rx_dup=c["rx_dup"].at[f].set(ns["dup_cnt"]),
                        rx_ooo=c["rx_ooo"].at[f].set(ns["ooo_cnt"]),
                        rx_cdrop=c["rx_cdrop"].at[f].set(ns["cdrop_cnt"]),
                        rx_ecn=c["rx_ecn"].at[f].set(ns["ecn_tot"]),
                        abort=c["abort"] | out["rkey_err"].astype(jnp.int32))
                ecn_f = ecn_f.at[f].add(out["ecn_echo"].astype(jnp.int32))

                def rec(c):
                    aseq = c["acc_ctr"]
                    dma_a = jnp.asarray(out["dma_addr"], jnp.int32)
                    wm = jnp.maximum(c["f_wm"][f], dma_a + out["dma_len"])
                    return _up(
                        c, acc_ctr=aseq + 1,
                        p_acc=c["p_acc"].at[f, pidx].set(1),
                        p_aseq=c["p_aseq"].at[f, pidx].set(aseq),
                        p_aaddr=c["p_aaddr"].at[f, pidx].set(dma_a),
                        f_wm=c["f_wm"].at[f].set(
                            jnp.where(c["rx_srf"][f] > 0,
                                      c["f_wm"][f], wm)))
                c = lax.cond(out["accept"], rec, lambda c: c, c)
                return (c, ecn_f,
                        o_ack.at[i].set(out["send_ack"].astype(jnp.int32)),
                        o_ap.at[i].set(out["ack_psn"]),
                        o_sk.at[i].set(out["sack"]),
                        o_nak.at[i].set(out["send_nak"].astype(jnp.int32)))
            return lax.cond((bv[i] > 0) & (bk[i] == 0), do,
                            lambda st: st, st)
        c, ecn_f, o_ack, o_ap, o_sk, o_nak = lax.fori_loop(
            0, B, data_body,
            (c, jnp.zeros(F, jnp.int32), jnp.zeros(B, jnp.int32),
             jnp.zeros(B, jnp.int32), jnp.zeros(B, jnp.int32),
             jnp.zeros(B, jnp.int32)))

        # CNP emission (rdma._emit_cnps): QPN-ascending, before the ACKs
        if skey.ecn_on:
            def cnp_body(k, c):
                fidx = c["cnp_ord"][g, k]
                f = jnp.maximum(fidx, 0)

                def do(c):
                    def fire(c):
                        c = _up(
                            c,
                            f_last_cnp=c["f_last_cnp"].at[f].set(c["now"]),
                            f_last_cnp_w=c["f_last_cnp_w"].at[f].set(1),
                            n_cnptx=c["n_cnptx"].at[dst].add(1))
                        return _send_ctrl(c, f, I32(3), I32(0), I32(0))
                    hold = ((c["now"] - c["f_last_cnp"][f])
                            < RdmaNode.CNP_HOLDOFF)
                    return lax.cond(hold, lambda c: c, fire, c)
                return lax.cond((fidx >= 0) & (ecn_f[f] > 0),
                                do, lambda c: c, c)
            c = lax.fori_loop(0, F, cnp_body, c)

        # pass D: ACK / NAK responses, batch order
        def resp_body(i, c):
            f = bf[i]
            ds = (bv[i] > 0) & (bk[i] == 0)
            c = lax.cond(
                ds & (o_ack[i] > 0),
                lambda c: _send_ctrl(c, f, I32(1), o_ap[i], o_sk[i]),
                lambda c: c, c)
            return lax.cond(
                ds & (o_nak[i] > 0),
                lambda c: _send_ctrl(c, f, I32(2), o_ap[i], I32(0)),
                lambda c: c, c)
        return lax.fori_loop(0, B, resp_body, c)

    # ---- one network tick (netsim.tick + rdma.step_network) -----------
    def _wire_due_perm(c, due):
        """Pop order of the wire heap: (arrival, seq) lexicographic, in
        ``WIRE_SORTS_PER_TICK`` sorts."""
        perm1 = jnp.argsort(jnp.where(due, c["w_seq"], BIG))
        key2 = jnp.where(due, c["w_arr"], BIG)[perm1]
        return perm1[jnp.argsort(key2, stable=True)]

    def _tick(c):
        c = _up(c, now=c["now"] + 1)
        if star:
            if skey.loss_on or skey.ecn_on:
                c = _up(c, csend=I32(0), cpop=I32(0))
            # phase 1: due wire packets land in egress rings
            due = (c["w_valid"] > 0) & (c["w_arr"] <= c["now"])
            perm = _wire_due_perm(c, due)
            n_due = jnp.sum(due.astype(jnp.int32))

            def pop(i, c):
                s = perm[i]
                c = _up(c, w_valid=c["w_valid"].at[s].set(0))
                return _ring_enq(c, c["w_dst"][s], c["w_flow"][s],
                                 c["w_kind"][s], c["w_pidx"][s],
                                 c["w_ap"][s], c["w_sack"][s])
            c = _live_loop(c, n_due, pop)
            # phase 2: drain each port, feed the batch to its node
            for port in range(skey.P):
                B = skey.DEL[port]
                if B == 0:
                    continue
                len0, head0 = c["r_len"][port], c["r_head"][port]
                n_pop = jnp.minimum(B, len0)

                def drain_body(j, st, port=port, len0=len0, head0=head0,
                               n_pop=n_pop):
                    c, bv, bf, bp_, bk, ba, bs, be = st
                    active = j < n_pop
                    slot = (head0 + j) % RCAP
                    if skey.ecn_on:
                        depth = len0 - j
                        rank = c["cpop"]
                        c = _up(c, cpop=c["cpop"]
                                + jnp.where(active, 1, 0))
                        h = _hash(_u32(c["cseed"]), chaos.TAG_RED,
                                  c["now"], rank)
                        mark = active & (
                            (depth >= c["kmax"])
                            | ((depth > c["kmin"])
                               & (h < _u32(c["red_t"][depth]))))
                        c = _up(c, pt_ecn=c["pt_ecn"].at[port].add(
                            mark.astype(jnp.int32)))
                        be = be.at[j].set(mark.astype(jnp.int32))
                    a32 = active.astype(jnp.int32)
                    return (c,
                            bv.at[j].set(a32),
                            bf.at[j].set(a32 * c["r_flow"][port, slot]),
                            bp_.at[j].set(a32 * c["r_pidx"][port, slot]),
                            bk.at[j].set(a32 * c["r_kind"][port, slot]),
                            ba.at[j].set(a32 * c["r_ap"][port, slot]),
                            bs.at[j].set(a32 * c["r_sack"][port, slot]),
                            be)
                z = jnp.zeros(B, jnp.int32)
                c, bv, bf, bp_, bk, ba, bs, be = lax.fori_loop(
                    0, B, drain_body, (c, z, z, z, z, z, z, z))
                c = _up(c,
                        r_head=c["r_head"].at[port].set(
                            (head0 + n_pop) % RCAP),
                        r_len=c["r_len"].at[port].add(-n_pop),
                        pt_del=c["pt_del"].at[port].add(n_pop))
                c = _process_batch(c, port, port,
                                   (bv, bf, bp_, bk, ba, bs, be), B)
        else:
            if skey.loss_on or skey.jit_on or skey.reo_on:
                c = _up(c, l_cidx=jnp.zeros(skey.L, jnp.int32))
            # per-link deliver + node batch, link order
            for li in range(skey.L):
                B = skey.DEL[li]
                due = ((c["w_valid"] > 0) & (c["w_arr"] <= c["now"])
                       & (c["w_dst"] == li))
                perm = _wire_due_perm(c, due)
                n_take = jnp.minimum(jnp.sum(due.astype(jnp.int32)), B)

                def take_body(j, st, n_take=n_take, perm=perm):
                    c, bv, bf, bp_, bk, ba, bs = st
                    active = j < n_take
                    s = perm[j]
                    c = lax.cond(
                        active,
                        lambda c: _up(c,
                                      w_valid=c["w_valid"].at[s].set(0)),
                        lambda c: c, c)
                    a32 = active.astype(jnp.int32)
                    return (c,
                            bv.at[j].set(a32),
                            bf.at[j].set(a32 * c["w_flow"][s]),
                            bp_.at[j].set(a32 * c["w_pidx"][s]),
                            bk.at[j].set(a32 * c["w_kind"][s]),
                            ba.at[j].set(a32 * c["w_ap"][s]),
                            bs.at[j].set(a32 * c["w_sack"][s]))
                z = jnp.zeros(B, jnp.int32)
                c, bv, bf, bp_, bk, ba, bs = lax.fori_loop(
                    0, B, take_body, (c, z, z, z, z, z, z))
                c = _process_batch(c, li, skey.LDST[li],
                                   (bv, bf, bp_, bk, ba, bs, z), B)

        # phase 3: retransmission timers (rdma.tick, node x QPN order).
        # A bump changes only its own row's retries and deadline, never
        # another row's, so the due mask taken up front is the oracle's
        # test at each visit; flat index k * PC + row, f = t_order[k].
        order = c["t_order"]
        due = (c["p_held"][order] > 0) & (c["now"] >= c["p_dl"][order])
        c = _live_loop(c, due.reshape(-1),
                       lambda i, c: _bump_send(c, order[i // PC], i % PC))

        # phase 4: idle / watermark accounting (rdma.run_network)
        pending = (jnp.any(c["w_valid"] > 0) | jnp.any(c["p_held"] > 0)
                   | jnp.any(c["f_cursor"] < c["f_nchunks"]))
        if star:
            pending = pending | jnp.any(c["r_len"] > 0)
        c = _up(c, idle=jnp.where(pending, 0, c["idle"] + 1),
                steps=c["steps"] + 1)
        if skey.wm_on:
            hit = jnp.any((c["f_wm_armed"] > 0)
                          & (c["f_wm"] >= c["f_wm_thresh"]))
            c = _up(c, wm_hit=hit.astype(jnp.int32))
        return c

    def _cond(c):
        return ((c["abort"] == 0) & (c["wm_hit"] == 0)
                & (c["idle"] < c["idle_done"])
                & (c["steps"] < c["max_ticks"]))

    def fused_epoch(vec):
        with jax.named_scope("fused_epoch"):
            c = layout.unpack_jnp(vec)
            c = lax.while_loop(_cond, _tick, c)
            return layout.concat(c)

    # the device trace names the program jit_fused_epoch
    return jax.jit(fused_epoch, donate_argnums=(0,))


# ---------------------------------------------------------------------------
# Unpacking: blob -> live Python simulation
# ---------------------------------------------------------------------------

_RX_NAMES = ("rx_epsn", "rx_msn", "rx_bytes", "rx_cur", "rx_cred",
             "rx_rkey", "rx_rxbit", "rx_srf", "rx_acc", "rx_dup",
             "rx_ooo", "rx_cdrop", "rx_ecn")


def _rebuild_pkt(fl: _Flow, kind: int, pidx: int, ap: int,
                 sack: int) -> pk.Packet:
    if kind == 0:
        return fl.plan[pidx].clone()
    if kind == 1:
        return pk.make_ack(fl.sq, ap, sack=sack)
    if kind == 2:
        return pk.make_ack(fl.sq, ap, nak=True)
    return pk.make_cnp(fl.sq, src_ip=fl.rcv.node_id, path_id=-1)


def _apply(world: _World, out: np.ndarray, nodes) -> Tuple[int, int]:
    """Write the epoch's final blob back into the Python objects,
    reproducing exactly the state the per-tick oracle would have.
    Returns the DMA writes replayed into receive buffers and their
    bytes."""
    lay, flows, skey = world.layout, world.flows, world.skey
    g = lambda name: lay.get(out, name)               # noqa: E731
    g0 = lambda name: lay.get(world.vec0, name)       # noqa: E731
    star = skey.mode == "star"

    held, retr, dl = g("p_held"), g("p_retr"), g("p_dl")
    acc, aseq, aaddr = g("p_acc"), g("p_aseq"), g("p_aaddr")
    nextv, next0, cur = g("f_next"), g0("f_next"), g("f_cursor")
    rxf = {n: g(n) for n in _RX_NAMES}

    # ---- DMA replay (+ SR interval merge), global acceptance order ----
    recs = []
    for fl in flows:
        for row in np.nonzero(acc[fl.idx])[0]:
            recs.append((int(aseq[fl.idx, row]), fl.idx, int(row)))
    recs.sort()
    dma_bytes = 0
    for _s, fi, row in recs:
        fl = world.flows[fi]
        t = fl.plan[row]
        a, ln = int(aaddr[fl.idx, row]), t.payload_len
        buf = fl.rcv._buffer_for(fl.rq)
        if ln:
            buf[a:a + ln] = t.payload[:ln]
            dma_bytes += ln
        if fl.snd._sr:
            fl.rcv._sr_note_progress(fl.rq, a, ln)

    for fl in flows:
        s, r, sq, rq, i = fl.snd, fl.rcv, fl.sq, fl.rq, fl.idx
        accd = int(rxf["rx_acc"][i]) - int(fl.rx0[8])
        dupd = int(rxf["rx_dup"][i]) - int(fl.rx0[9])
        oood = int(rxf["rx_ooo"][i]) - int(fl.rx0[10])
        cdropd = int(rxf["rx_cdrop"][i]) - int(fl.rx0[11])
        ecnd = int(rxf["rx_ecn"][i]) - int(fl.rx0[12])

        # receiver: progress watermark + message completions
        last_rows = [row for row in np.nonzero(acc[i])[0]
                     if fl.plan[row].opcode in _LAST_OPS]
        if s._sr:
            lst = list(r._sr_pending_last.get(rq, []))
            lst += [fl.base + int(row) for row in
                    sorted(last_rows, key=lambda rr: int(aseq[i, rr]))]
            if lst:
                epsn = int(rxf["rx_epsn"][i])
                done = [ps for ps in lst if ((ps - epsn) % SPAN) > HALF]
                rest = [ps for ps in lst if ((ps - epsn) % SPAN) <= HALF]
                if done:
                    r._completions[rq] = r._completions.get(rq, 0) \
                        + len(done)
                if rest:
                    r._sr_pending_last[rq] = rest
                else:
                    r._sr_pending_last.pop(rq, None)
        else:
            if accd > 0:
                r._rx_progress[rq] = int(g("f_wm")[i])
            if last_rows:
                r._completions[rq] = r._completions.get(rq, 0) \
                    + len(last_rows)

        # receiver: credit ledger (note_accepted/note_dropped/replenish)
        r.credits.accepted += accd
        r.credits.accepted_per_qp[rq] += accd
        r.credits.granted += accd
        r.credits.dropped_no_credit += cdropd
        r.credits.dropped_per_qp[rq] += cdropd

        # receiver: per-QP node stats driven by the engine verdicts
        r.stats.accepted += accd
        r.stats.dup_dropped += dupd
        r.stats.ooo_nak += oood
        r.stats.credit_dropped += cdropd
        r.stats.ecn_marked_rx += ecnd

        # sender: PSN space, retransmit slots, flow control, holdoffs
        s.qp.tables.npsn[sq] = (fl.base + int(nextv[i])) & MASK
        slots = {}
        for row in np.nonzero(held[i])[0]:
            psn = fl.base + int(row)
            slots[psn] = _Slot(psn, fl.plan[row].clone(),
                               int(dl[i, row]), int(retr[i, row]))
        if slots or fl.had_slot_key or int(nextv[i]) > int(next0[i]):
            s.retx.slots[sq] = slots
        s.fc.budget[sq] = int(g("f_budget")[i])
        s.fc.outstanding[sq] = int(g("f_out")[i])
        for _ in range(int(cur[i])):
            s.fc.pending[sq].popleft()
        s.fc.total_passed += int(g("f_tpassed_d")[i])
        if g("f_last_nak_w")[i]:
            s._last_nak_resend[sq] = int(g("f_last_nak")[i])
        if g("f_last_gap_w")[i]:
            s._last_gap_resend[sq] = int(g("f_last_gap")[i])
        if g("f_last_cnp_w")[i]:
            r._last_cnp_sent[rq] = int(g("f_last_cnp")[i])

    # ---- RX table scatter (one device write per receiving node) -------
    by_node: Dict[int, List[_Flow]] = {}
    for fl in flows:
        by_node.setdefault(fl.rcv.node_id, []).append(fl)
    for nid, fls in by_node.items():
        nd = nodes[nid]
        rows = jnp.asarray([fl.rq for fl in fls], jnp.int32)
        updates = {}
        for blob_name, field in zip(_RX_NAMES, _STATE_FIELDS):
            vals = jnp.asarray([int(rxf[blob_name][fl.idx]) for fl in fls],
                               jnp.int32)
            updates[field] = getattr(nd.rx_tables, field).at[rows].set(vals)
        nd.rx_tables = nd.rx_tables._replace(**updates)

    # ---- node-level stat deltas ---------------------------------------
    for n, nd in enumerate(nodes):
        nd.stats.tx_pkts += int(g("n_tx")[n])
        nd.stats.rx_pkts += int(g("n_rx")[n])
        nd.stats.retransmissions += int(g("n_retx")[n])
        nd.stats.sacked += int(g("n_sacked")[n])
        nd.stats.cnp_tx += int(g("n_cnptx")[n])
        nd.stats.cnp_rx += int(g("n_cnprx")[n])
        nd.retx.retransmissions += int(g("n_retx")[n])

    # ---- fabric / link state ------------------------------------------
    net = world.net
    now = g("now")
    wv = {n_: g(n_) for n_ in ("w_valid", "w_arr", "w_seq", "w_dst",
                               "w_flow", "w_pidx", "w_kind", "w_ap",
                               "w_sack")}

    def _wire_entries():
        for si in range(skey.WCAP):
            if not wv["w_valid"][si]:
                continue
            pkt = _rebuild_pkt(flows[int(wv["w_flow"][si])],
                               int(wv["w_kind"][si]),
                               int(wv["w_pidx"][si]),
                               int(wv["w_ap"][si]),
                               int(wv["w_sack"][si]))
            yield (int(wv["w_arr"][si]), int(wv["w_seq"][si]),
                   int(wv["w_dst"][si]), pkt)

    if star:
        net.now = now
        net._seq = g("seq")
        net.injected += g("injected_d")
        net._ctick, net._csend, net._cpop = now, g("csend"), g("cpop")
        for p in range(skey.P):
            st = net.port_stats[p]
            st.enqueued += int(g("pt_enq")[p])
            st.delivered += int(g("pt_del")[p])
            st.tail_dropped += int(g("pt_tdrop")[p])
            st.wire_dropped += int(g("pt_wdrop")[p])
            st.ecn_marked += int(g("pt_ecn")[p])
            st.max_depth = int(g("pt_maxd")[p])
        wire = [(a, s_, d, p) for a, s_, d, p in _wire_entries()]
        heapq.heapify(wire)
        net._wire = wire
        rl, rh = g("r_len"), g("r_head")
        rf, rp_ = g("r_flow"), g("r_pidx")
        rk, ra, rs = g("r_kind"), g("r_ap"), g("r_sack")
        for p in range(skey.P):
            q = collections.deque()
            for j in range(int(rl[p])):
                slot = (int(rh[p]) + j) % skey.RCAP
                q.append((_rebuild_pkt(flows[int(rf[p, slot])],
                                       int(rk[p, slot]), int(rp_[p, slot]),
                                       int(ra[p, slot]),
                                       int(rs[p, slot])), None))
            net.egress[p]._q = q
    else:
        net.now = now
        heaps: List[List] = [[] for _ in world.link_keys]
        for arr, seqv, li, pkt in _wire_entries():
            heaps[li].append((arr, seqv, pkt))
        for li, key in enumerate(world.link_keys):
            lk = net.links[key]
            heapq.heapify(heaps[li])
            lk._heap = heaps[li]
            lk._seq = int(g("l_seq")[li])
            lk.sent += int(g("l_sent_d")[li])
            lk.dropped += int(g("l_drop_d")[li])
            lk._ctick, lk._cidx = now, int(g("l_cidx")[li])
    return len(recs), dma_bytes


def run_fused_epoch(nodes, max_ticks: int = 100_000, idle_done: int = 8,
                    watermarks: Optional[Dict[Tuple[int, int], int]] = None
                    ) -> Optional[Dict[str, int]]:
    """Pack, run one fused epoch on device, unpack: the three phases are
    the spans ``balboa.fused.pack``, ``.epoch`` and ``.unpack``.

    Returns None when the world is not fusable or the in-graph twin hit
    a case it does not model (retry exhaustion, rkey protection error,
    wire-capacity overflow) — in that case the Python objects are
    untouched and the caller falls back to per-tick stepping.  Either
    outcome is counted in the network's ``epochs`` (``EpochStats``).

    On success the Python world has advanced exactly as ``for _ in
    range(steps): rdma.step_network(nodes)`` would have, and the return
    dict carries ``steps``, ``wm_hit``, ``idle_exit`` and ``ticks`` (the
    ``rdma.run_network`` return-value convention).
    """
    with telemetry.span("balboa.fused.pack") as sp:
        world = try_pack(nodes, max_ticks, idle_done, watermarks)
        if world is not None:
            carry = jnp.asarray(world.vec0)
            rows = sum(len(fl.plan) for fl in world.flows)
            sp.set_metadata(flows=world.skey.F, plan_rows=rows,
                            wire_slots=world.skey.WCAP)
    out = None
    if world is not None:
        lay, epochs = world.layout, world.net.epochs
        with telemetry.span("balboa.fused.epoch") as sp:
            out = np.asarray(make_epoch_fn(world.skey)(carry))
            sp.set_metadata(steps=lay.get(out, "steps"),
                            trips=lay.get(out, "loop_trips"))
        epochs.carry_bytes += world.vec0.nbytes + out.nbytes
        if lay.get(out, "abort"):
            epochs.aborted += 1
            out = None
    if out is None:
        if nodes:
            nodes[0].net.epochs.unfused += 1
        return None
    steps = lay.get(out, "steps")
    idle_exit = lay.get(out, "idle") >= idle_done
    with telemetry.span("balboa.fused.unpack") as sp:
        dmas, nbytes = _apply(world, out, nodes)
        sp.set_metadata(dmas=dmas, bytes=nbytes)
    epochs.fused += 1
    epochs.fused_ticks += steps
    epochs.loop_trips += lay.get(out, "loop_trips")
    return {"steps": steps, "wm_hit": bool(lay.get(out, "wm_hit")),
            "idle_exit": idle_exit,
            "ticks": (steps - 1) if idle_exit else max_ticks}
