"""The reference that ``incast.fused`` is held to: the per-tick oracle
and the bytes posted.

The driver builds a twin of the world in set-up, from the same
configuration, and leaves it unstepped.  After the window, ``check``
posts every round the fused world ran (warm-up and window alike) on the
twin, with the same payloads, and steps it tick by tick
(``run_network(..., epoch_mode="tick")``, the per-tick path that
ROADMAP names the fused core's oracle).  ``contract_diff`` counts the
fields in which the two differ: after each round those the host keeps
(``counters``: the driver takes them in the window, with no device
read), and after the last round the whole state that carries from one
round to the next (``state``: PSNs, retransmission slots, flow-control
and credit ledgers, RX tables and progress, holdoff stamps, buffers and
the fabric's clock, wire and queues).  The twin never starts from the
fused world's state, so a fault that the fused core leaves in carried
state shows however many rounds it was carried.

Both sides decide each received packet with the same function
(``pipeline._rx_decide``, which ``core/fused.py`` imports), so a fault
in it would show on both and cancel out of ``contract_diff``.
``payload_diff`` needs nothing of the program: it compares the bytes in
the receiver's buffers with the bytes each sender posted.
"""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

from repro.core.pipeline import RxTables

_MISSING = object()


def counters(world, ticks: int) -> Dict[str, object]:
    """One round's host-side contract: ticks, tail drops,
    retransmissions, the receiver's completion counts and every node's
    ``NodeStats``."""
    out: Dict[str, object] = {
        "ticks": ticks,
        "tail_drops": world.fabric.total_tail_dropped,
        "retransmissions": sum(n.retx.retransmissions for n in world.nodes),
        "completions": tuple(world.completions()),
    }
    for i, n in enumerate(world.nodes):
        for f, v in vars(n.stats).items():
            out[f"node{i}.stats.{f}"] = v
    return out


def _pkt(p):
    pay = None if p.payload is None or p.payload.size == 0 \
        else np.asarray(p.payload, np.uint8).tobytes()
    return (p.src_ip, p.dst_ip, p.src_port, p.dst_port, p.opcode, p.qpn,
            p.psn, bool(p.ack_req), p.vaddr, p.rkey, p.dma_len, p.ack_psn,
            p.msn, p.sack_bits, p.path_id, p.icrc, bool(p.dpi_flag),
            bool(p.ecn), p.coll_tag, p.coll_src, p.coll_nsrc, p.coll_frag,
            pay)


def state(world) -> Dict[str, object]:
    """Everything a round leaves for the next, by field: each node's
    stats, RX tables, send PSNs, retransmission slots, flow-control and
    credit ledgers, RX progress, completions, selective-repeat notes,
    NAK/gap/CNP holdoff stamps, QP errors and registered buffers; the
    fabric's clock, sequence, wire, egress queues and port stats."""
    out: Dict[str, object] = {}
    for i, n in enumerate(world.nodes):
        fc, cr = n.fc, n.credits
        node = {
            "stats": dict(vars(n.stats)),
            "npsn": [int(v) for v in n.qp.tables.npsn],
            "retx_slots": {q: {psn: (_pkt(s.packet), s.deadline, s.retries)
                               for psn, s in slots.items()}
                           for q, slots in n.retx.slots.items()},
            "retransmissions": n.retx.retransmissions,
            "fc": (list(fc.budget), list(fc.outstanding),
                   [len(q) for q in fc.pending], fc.total_passed),
            "credits": (list(cr.credits), cr.accepted, cr.granted,
                        cr.dropped_no_credit, list(cr.accepted_per_qp),
                        list(cr.dropped_per_qp)),
            "rx_progress": dict(n._rx_progress),
            "completions": dict(n._completions),
            "sr_pending_last": {k: list(v)
                                for k, v in n._sr_pending_last.items()},
            "sr_pend": {k: dict(v) for k, v in n._sr_pend.items()},
            "last_nak": dict(n._last_nak_resend),
            "last_gap": dict(n._last_gap_resend),
            "last_cnp": dict(n._last_cnp_sent),
            "qp_errors": sorted(n.qp_errors),
            "bufs": {q: b.tobytes() for q, (_rk, b) in n._qp_buffer.items()},
        }
        for f, v in node.items():
            out[f"node{i}.{f}"] = v
        for f in RxTables._fields:
            out[f"node{i}.rx.{f}"] = np.asarray(getattr(n.rx_tables, f))
    fab = world.fabric
    out.update({
        "fabric.now": fab.now,
        "fabric.seq": fab._seq,
        "fabric.injected": fab.injected,
        "fabric.wire": sorted((a, s, dst, _pkt(p))
                              for a, s, dst, p in fab._wire),
        "fabric.rings": [[_pkt(p) for p, _m in eg._q] for eg in fab.egress],
        "fabric.port_stats": [dict(vars(st)) for st in fab.port_stats],
    })
    return out


def contract_diff(fused: Dict[str, object],
                  oracle: Dict[str, object]) -> int:
    """Fields of two ``counters`` or ``state`` results that differ (a
    field missing on one side counts)."""
    n = 0
    for k in fused.keys() | oracle.keys():
        a, b = fused.get(k, _MISSING), oracle.get(k, _MISSING)
        if a is _MISSING or b is _MISSING:
            n += 1
        elif isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
            n += int(not np.array_equal(a, b))
        else:
            n += int(a != b)
    return n


def payload_diff(buffers: Sequence[np.ndarray],
                 payloads: Sequence[np.ndarray]) -> int:
    """Bytes of each sender's buffer at the receiver that differ from
    what the sender posted (a message's length from offset 0)."""
    return sum(int(np.count_nonzero(b[:len(p)] != p))
               + max(len(p) - len(b), 0)
               for b, p in zip(buffers, payloads))
