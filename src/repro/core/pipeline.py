"""Vectorized RoCE v2 packet-processing pipeline (paper §4.1, Fig. 2).

FPGA -> TPU design dual
-----------------------
The FPGA realizes one deep pipeline processing one header beat per cycle
at line rate; per-QP state (ePSN/MSN/credits) lives in BRAM tables the
pipeline reads and writes in flight.  The TPU-idiomatic dual keeps the
same per-QP tables as jax arrays, but exposes the parallelism along a
different axis: PSN checking is *inherently sequential per QP* yet
*embarrassingly parallel across QPs*, exactly the axis the paper scales
along (hundreds of QPs, Fig. 2/6).

Two jitted engines implement the same RX semantics:

``rx_pipeline``         — the per-packet oracle: one ``lax.scan`` step
                          per packet in arrival order.  Honest, simple,
                          and O(batch) sequential steps.
``rx_pipeline_batched`` — the batched multi-QP engine: packets are
                          stable-sorted by QP (preserving per-QP arrival
                          order), ranked within their QP segment, and
                          processed in *waves*: wave ``t`` handles the
                          ``t``-th packet of every QP simultaneously.
                          One wave is a fully vectorized gather ->
                          decide -> scatter over all lanes, so the
                          sequential depth is the *longest per-QP
                          segment* (≈ batch/Q for even traffic), not the
                          batch size.  Bit-identical to the oracle
                          (property-tested in tests/test_fabric.py).

Both engines share ``_rx_decide`` — the pure header FSM — so they cannot
drift apart.  The TX path gets the same treatment: ``tx_pipeline`` scans
commands; ``tx_pipeline_batched`` assigns PSN ranges with a per-QP
segmented cumulative sum.

RX semantics (paper §4.1 + §4.3):
  strip/inspect headers -> PSN check against the state table ->
  accept (emit DMA command, bump ePSN/MSN) | drop-duplicate (re-ACK) |
  drop-out-of-order (NAK, triggers remote retransmit) -> credit check
  may still drop an otherwise valid packet (peer retransmits).

All paths are jittable, differentiation-free integer programs.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import packet as pk


# Selective-repeat receive window (packets).  Bounded by the int32
# bitmap the batched engine packs per-QP state into: bit k of ``rxbit``
# marks PSN ``epsn + k`` as received-but-not-yet-cumulative, so the
# window must fit a non-negative int32.  The flow-control window (<= 16
# in every test/bench profile) must stay below this or in-window
# arrivals could land beyond the bitmap.
SR_WINDOW = 24


class RxTables(NamedTuple):
    """The jax-side mirror of QPTables fields the RX pipeline mutates."""
    epsn: jax.Array        # (Q,) int32
    msn: jax.Array         # (Q,) int32
    bytes_left: jax.Array  # (Q,) int64
    cur_vaddr: jax.Array   # (Q,) int64
    credits: jax.Array     # (Q,) int32   downstream capacity (§4.3)
    rkey: jax.Array        # (Q,) int32   registered buffer's rkey (read-only)
    rxbit: jax.Array       # (Q,) int32   SR bitmap: bit k = epsn+k received
    sr: jax.Array          # (Q,) int32   1 = selective-repeat RX mode
    # telemetry counters (monotonic, per QP).  They ride the carried
    # state exactly like the protocol fields — updated in-graph by
    # ``_rx_decide`` in both engines, harvested on the host only at
    # epoch boundaries (RdmaNode.engine_counters), so observability adds
    # zero host round-trips to a jitted epoch.
    acc_cnt: jax.Array     # (Q,) int32   payloads accepted (DMA'd)
    dup_cnt: jax.Array     # (Q,) int32   duplicates dropped (re-ACKed)
    ooo_cnt: jax.Array     # (Q,) int32   out-of-order drops (NAKed)
    cdrop_cnt: jax.Array   # (Q,) int32   credit drops
    ecn_tot: jax.Array     # (Q,) int32   CE-marked payload arrivals


class RxResult(NamedTuple):
    accept: jax.Array      # (N,) bool   payload forwarded to DMA
    dup: jax.Array         # (N,) bool   duplicate (re-ACK, no DMA)
    ooo: jax.Array         # (N,) bool   out-of-order (NAK)
    dropped_credit: jax.Array  # (N,) bool dropped for lack of credits
    rkey_err: jax.Array    # (N,) bool   RETH rkey mismatch (NAK_PROT, no DMA)
    dma_addr: jax.Array    # (N,) int64  target address for accepted payloads
    dma_len: jax.Array     # (N,) int32
    ack_psn: jax.Array     # (N,) int32  cumulative ack to send back
    ack_qpn: jax.Array     # (N,) int32
    send_ack: jax.Array    # (N,) bool
    send_nak: jax.Array    # (N,) bool
    sack: jax.Array        # (N,) int32  SR bitmap to ship with the ACK
    ecn_echo: jax.Array    # (N,) bool   CE-marked payload arrival (NP input)
    ecn_cnt: jax.Array     # (Q,) int32  CE-marked arrivals per QP this batch


# ---------------------------------------------------------------------------
# Shared header FSM (used by both the scan oracle and the batched engine)
# ---------------------------------------------------------------------------

def _rx_decide(state: Dict[str, jax.Array], p: Dict[str, jax.Array]
               ) -> Tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
    """The pure per-packet decision function of the RX header pipeline.

    ``state`` holds the packet's QP-table row (gathered); ``p`` the
    packet header fields.  Shape-polymorphic: scalars inside the scan
    oracle, (N,) lanes inside a batched wave.  Returns the updated row
    and the per-packet outputs.
    """
    opcode = p["opcode"]
    psn = p["psn"]
    plen = p["plen"].astype(jnp.int32)
    epsn = state["epsn"]
    credits = state["credits"]

    is_payload = jnp.isin(opcode, jnp.asarray(pk.PAYLOAD_OPS, jnp.int32))
    has_reth = jnp.isin(opcode, jnp.asarray(pk.RETH_OPS, jnp.int32))
    is_last = jnp.isin(opcode, jnp.asarray(
        (pk.WRITE_LAST, pk.WRITE_ONLY, pk.READ_RESP_LAST, pk.READ_RESP_ONLY),
        jnp.int32))

    valid = p["valid"] > 0
    sr = state["sr"] > 0

    in_seq = psn == epsn
    behind = (psn - epsn) % (pk.PSN_MASK + 1) > (pk.PSN_MASK // 2)
    has_credit = credits > 0

    # ---- go-back-N verdicts (the original in-order-only FSM) ----------
    ooo_g = ~in_seq & ~behind
    # remote-access protection (§4.6): a RETH-bearing packet must present
    # the rkey of the registered buffer it targets; a mismatch is NAKed
    # with a protection error instead of being served.  Table rkey 0
    # means "nothing registered" (QPManager hands out rkeys from 1), so
    # unarmed QPs — synthetic pipeline traces — keep accepting.
    # MIDDLE/LAST fragments carry no RETH and inherit the verdict
    # implicitly: a rejected FIRST never advances ePSN, so they fall
    # out as OOO.
    rkey_ok_g = ~has_reth | (state["rkey"] == 0) | (p["rkey"] == state["rkey"])

    accept_g = is_payload & in_seq & has_credit & rkey_ok_g & valid
    dropped_g = is_payload & in_seq & ~has_credit & rkey_ok_g & valid
    rkey_err_g = is_payload & in_seq & ~rkey_ok_g & valid

    # DMA command formation (RETH starts a region; MIDDLE/LAST continue it)
    start_addr = jnp.where(has_reth, p["vaddr"], state["cur_vaddr"])
    new_epsn_g = jnp.where(accept_g, (epsn + 1) & pk.PSN_MASK, epsn)

    # ---- selective-repeat verdicts (out-of-order-tolerant window) -----
    # Any PSN inside [epsn, epsn + SR_WINDOW) is acceptable; a per-QP
    # bitmap remembers which offsets already landed.  Packets must be
    # self-contained (per-packet address/rkey, ``fragment_message(...,
    # addr_per_pkt=True)``) because an out-of-order arrival cannot lean
    # on the FIRST fragment's RETH cursor.
    d = ((psn - epsn) % (pk.PSN_MASK + 1)).astype(jnp.int32)
    in_win = ~behind & (d < SR_WINDOW)
    bit = jnp.where(
        in_win, jnp.left_shift(jnp.int32(1), jnp.minimum(d, SR_WINDOW - 1)),
        0).astype(jnp.int32)
    already = (state["rxbit"] & bit) != 0
    fresh = in_win & ~already
    # every SR payload packet carries its rkey, so protection is checked
    # on all of them (not just RETH opcodes)
    rkey_ok_s = (state["rkey"] == 0) | (p["rkey"] == state["rkey"])
    accept_s = is_payload & fresh & has_credit & rkey_ok_s & valid
    dropped_s = is_payload & fresh & ~has_credit & rkey_ok_s & valid
    rkey_err_s = is_payload & fresh & ~rkey_ok_s & valid
    dup_s = (behind | already) & is_payload
    ooo_s = ~behind & ~in_win & is_payload          # beyond the window

    # bitmap update + cumulative advance over the contiguous prefix:
    # count trailing ones of the updated bitmap via the lowest *zero*
    # bit (ctz(~bm) = popcount((~bm & -~bm) - 1); ~bm always has a set
    # bit above SR_WINDOW, so the count is <= SR_WINDOW)
    bm = state["rxbit"] | jnp.where(accept_s, bit, 0)
    inv = ~bm
    adv = jax.lax.population_count((inv & -inv) - 1).astype(jnp.int32)
    new_epsn_s = (epsn + adv) & pk.PSN_MASK
    new_rxbit_s = jax.lax.shift_right_logical(bm, adv)

    # ---- merge the two FSMs (per-QP mode select) ----------------------
    accept = jnp.where(sr, accept_s, accept_g)
    dup = jnp.where(sr, dup_s, behind & is_payload)
    ooo = jnp.where(sr, ooo_s, ooo_g & is_payload)
    dropped_credit = jnp.where(sr, dropped_s, dropped_g)
    rkey_err = jnp.where(sr, rkey_err_s, rkey_err_g)
    dma_addr = jnp.where(sr, p["vaddr"], start_addr)
    new_epsn = jnp.where(sr, new_epsn_s, new_epsn_g)
    new_rxbit = jnp.where(sr, new_rxbit_s, state["rxbit"])

    new_cur = jnp.where(accept, dma_addr + plen, state["cur_vaddr"])
    new_bytes = jnp.where(
        (has_reth | sr) & accept, p["dma_len"].astype(jnp.int32) - plen,
        jnp.where(accept, state["bytes_left"] - plen, state["bytes_left"]))
    new_msn = jnp.where(accept & is_last, state["msn"] + 1, state["msn"])
    new_credits = jnp.where(accept, credits - 1, credits)
    ecn_echo = (p["ecn"] > 0) & is_payload & valid

    new_state = {
        "epsn": new_epsn.astype(jnp.int32),
        "msn": new_msn.astype(jnp.int32),
        "bytes_left": new_bytes,
        "cur_vaddr": new_cur,
        "credits": new_credits.astype(jnp.int32),
        "rkey": state["rkey"],
        "rxbit": new_rxbit.astype(jnp.int32),
        "sr": state["sr"],
        # telemetry counters.  dup/ooo need the explicit ``valid`` gate:
        # unlike accept/credit-drop they never touch protocol state, so
        # the GBN FSM leaves them ungated for padding lanes (the batched
        # engine zeroes invalid lanes' *outputs* post-hoc, but counter
        # state must match the never-processed treatment bit-for-bit)
        "acc_cnt": state["acc_cnt"] + accept.astype(jnp.int32),
        "dup_cnt": state["dup_cnt"] + (dup & valid).astype(jnp.int32),
        "ooo_cnt": state["ooo_cnt"] + (ooo & valid).astype(jnp.int32),
        "cdrop_cnt": state["cdrop_cnt"] + dropped_credit.astype(jnp.int32),
        "ecn_tot": state["ecn_tot"] + ecn_echo.astype(jnp.int32),
    }
    out = {
        "accept": accept, "dup": dup, "ooo": ooo,
        "dropped_credit": dropped_credit, "rkey_err": rkey_err,
        "dma_addr": dma_addr.astype(jnp.int32),
        "dma_len": plen.astype(jnp.int32),
        # cumulative ACK: accepted in-order packets ack their own PSN
        # (== new_epsn - 1 for GBN); everything else re-acks the frontier
        "ack_psn": jnp.where(~sr & accept, psn,
                             (new_epsn - 1) & pk.PSN_MASK).astype(jnp.int32),
        "ack_qpn": p["qpn"].astype(jnp.int32),
        # ACK policy: ack accepted last/ack_req packets and duplicates.
        # SR additionally acks every out-of-order accept (the SACK is
        # what releases the sender's slot) and every gap-filling accept
        # that advanced the frontier by more than one.
        "send_ack": (accept & (is_last | (p["ack_req"] > 0) |
                               (sr & ((d > 0) | (adv > 1))))) | dup,
        "send_nak": ooo,
        # post-update bitmap, shipped with ACKs so the sender can
        # selectively release held slots and resend only the gaps
        "sack": jnp.where(sr, new_rxbit_s, 0).astype(jnp.int32),
        # ECN echo (DCQCN NP, §"opening the CC design space"): a CE mark
        # is congestion evidence regardless of the PSN verdict — dups and
        # credit-dropped packets crossed the congested queue too — so the
        # echo is stateless: every valid CE-marked payload packet counts.
        "ecn_echo": ecn_echo,
    }
    return new_state, out


_PKT_FIELDS = ("qpn", "opcode", "psn", "plen", "vaddr", "dma_len", "ack_req",
               "ecn", "rkey", "valid")
_STATE_FIELDS = ("epsn", "msn", "bytes_left", "cur_vaddr", "credits", "rkey",
                 "rxbit", "sr",
                 "acc_cnt", "dup_cnt", "ooo_cnt", "cdrop_cnt", "ecn_tot")
# the counter subset, exposed for epoch-boundary harvesting
COUNTER_FIELDS = ("acc_cnt", "dup_cnt", "ooo_cnt", "cdrop_cnt", "ecn_tot")


def _rx_one(tables: RxTables, p) -> Tuple[RxTables, Dict]:
    """Process one packet against the tables (scan body of the oracle)."""
    qpn = p["qpn"]
    state = {f: getattr(tables, f)[qpn] for f in _STATE_FIELDS}
    new_state, out = _rx_decide(state, p)
    tables = RxTables(**{
        f: getattr(tables, f).at[qpn].set(new_state[f])
        for f in _STATE_FIELDS})
    return tables, out


def _ensure_defaults(batch: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    """Batches built before the ECN / rkey-check eras lack those columns;
    default them to not-marked / key-0 (trace-time branch, free under
    jit; key 0 against the all-zero default rkey table passes, so legacy
    traces keep their exact decisions)."""
    n = batch["qpn"].shape[0]
    for col in ("ecn", "rkey"):
        if col not in batch:
            batch = dict(batch, **{col: jnp.zeros(n, jnp.int32)})
    return batch


@partial(jax.jit, donate_argnums=(0,))
@jax.named_scope("rx_engine")
def rx_pipeline(tables: RxTables, batch: Dict[str, jax.Array]
                ) -> Tuple[RxTables, RxResult]:
    """Per-packet oracle: scan the RX FSM over the batch in arrival
    order.  O(N) sequential steps — kept as the reference semantics the
    batched engine must reproduce bit-for-bit."""
    batch = _ensure_defaults(batch)

    def body(t, i):
        p = {k: batch[k][i] for k in _PKT_FIELDS}
        t, out = _rx_one(t, p)
        return t, out

    n = batch["qpn"].shape[0]
    n_qps = tables.epsn.shape[0]
    tables, outs = jax.lax.scan(body, tables, jnp.arange(n))
    # per-QP CE tally (the NP-side congestion signal); the oracle is
    # allowed the naive scatter-add the batched engine avoids
    outs["ecn_cnt"] = jnp.zeros(n_qps, jnp.int32).at[batch["qpn"]].add(
        outs["ecn_echo"].astype(jnp.int32), mode="drop")
    return tables, RxResult(**{k: outs[k] for k in RxResult._fields})


# ---------------------------------------------------------------------------
# Batched multi-QP engine
# ---------------------------------------------------------------------------

_OUT_KEYS = ("accept", "dup", "ooo", "dropped_credit", "rkey_err",
             "dma_addr", "dma_len", "ack_psn", "ack_qpn", "send_ack",
             "send_nak", "sack", "ecn_echo")
_OUT_BOOL = ("accept", "dup", "ooo", "dropped_credit", "rkey_err",
             "send_ack", "send_nak", "ecn_echo")


@partial(jax.jit, donate_argnums=(0,))
@jax.named_scope("rx_engine")
def rx_pipeline_batched(tables: RxTables, batch: Dict[str, jax.Array]
                        ) -> Tuple[RxTables, RxResult]:
    """Batched multi-QP RX engine (the tentpole: paper §4.1 at scale).

    Grouping (all in-graph, one jitted step):
      1. one stable sort by QP — per-QP arrival order (what the PSN FSM
         sequences over) becomes contiguous segments; segment lengths
         fall out of a ``searchsorted`` over the sorted keys, segment
         ranks out of index arithmetic;
      2. each active QP gets a dense *slot*, ordered by descending
         segment length, so the QPs still alive in wave ``t`` are always
         the slot prefix ``[0, m_t)`` of width ``W = min(Q, N)``;
      3. wave ``t`` reads slot ``s``'s ``t``-th packet at sorted
         position ``seg_off[s] + t`` — a ``(W,)`` gather — and writes
         its outputs as one contiguous block at offset
         ``start[t] = sum(m_0..m_{t-1})`` in (rank, slot) layout.

    The ``while_loop`` carries per-slot state *vectors*; per wave there
    is exactly one fused ``(fields, W)`` gather, one vectorized
    ``_rx_decide`` and one ``dynamic_update_slice`` of a packed output
    matrix — no table scatters inside the loop (XLA CPU scatter is the
    thing to avoid; the engine performs a single N-sized scatter total,
    for the inverse permutation).  Lanes past ``m_t`` in the fixed-width
    block compute garbage that the next wave's write overwrites.  Trip
    count = longest per-QP segment ≈ N/Q for even traffic, not the
    batch size.  State is scattered back to the QP tables once, at the
    end.

    Bit-identical to ``rx_pipeline`` on valid lanes (per-QP state is
    independent, so cross-QP reordering cannot change any decision);
    invalid (padding) lanes yield all-zero outputs.
    """
    batch = _ensure_defaults(batch)
    n = batch["qpn"].shape[0]
    n_qps = tables.epsn.shape[0]
    w = min(n_qps, n)                       # static wave width
    valid = batch["valid"] > 0
    key = jnp.where(valid, batch["qpn"], n_qps)   # invalid -> sentinel group
    idx = jnp.arange(n, dtype=jnp.int32)

    # one stable sort by QP; pack (key, lane) into a single int32 when it
    # fits — a value sort is several times cheaper than argsort here
    if (n_qps + 1) * n + n < 2 ** 31:
        packed = jnp.sort(key.astype(jnp.int32) * n + idx)
        sk = packed // n
        order_k = packed - sk * n
    else:
        order_k = jnp.argsort(key, stable=True)
        sk = key[order_k]
    # header fields (int32) in sorted order, padded by W so live-lane
    # wave gathers stay in bounds (dead lanes are clamped in the loop)
    fmat = jnp.stack([batch[k].astype(jnp.int32) for k in _PKT_FIELDS])
    fmat = jnp.concatenate(
        [fmat[:, order_k], jnp.zeros((len(_PKT_FIELDS), w), jnp.int32)],
        axis=1)

    # per-QP segment lengths from the sorted keys (no scatter needed)
    bounds = jnp.searchsorted(sk, jnp.arange(n_qps + 1)).astype(jnp.int32)
    counts = bounds[1:] - bounds[:-1]              # (Q,) valid pkts per QP
    seg_off_qp = bounds[:-1]                       # segment starts, sorted
    rank_sorted = idx - bounds[sk]                 # rank within segment

    # dense slots ordered by descending segment length
    slot_to_qp = jnp.argsort(-counts, stable=True)[:w]
    qp_to_slot = jnp.full(n_qps + 1, w, jnp.int32).at[slot_to_qp].set(
        jnp.arange(w, dtype=jnp.int32))
    slot_len = counts[slot_to_qp]                  # nonincreasing
    seg_off_slot = seg_off_qp[slot_to_qp]

    # wave t spans output positions [start[t], start[t] + m[t]) where
    # m[t] = #slots with segment length > t (a slot prefix)
    n_waves = slot_len[0] if w else jnp.int32(0)
    m_arr = jnp.searchsorted(-slot_len, -jnp.arange(n + 1), side="left"
                             ).astype(jnp.int32)
    start_arr = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(m_arr).astype(jnp.int32)])

    # (rank, slot) output position of every lane; invalid lanes go last
    n_valid = bounds[n_qps]
    pos_sorted = jnp.where(sk == n_qps, n_valid + rank_sorted,
                           start_arr[rank_sorted] + qp_to_slot[sk])
    # inverse permutation: original lane -> output position (packed sort
    # again, falling back to a scatter when the packing would overflow)
    if n * (n + w) + (n + w) < 2 ** 31:
        pos = jnp.sort(order_k * (n + w) + pos_sorted) % (n + w)
    else:
        pos = jnp.zeros(n, jnp.int32).at[order_k].set(pos_sorted)

    state0 = {f: getattr(tables, f)[slot_to_qp] for f in _STATE_FIELDS}
    outs0 = jnp.zeros((len(_OUT_KEYS), n + w), jnp.int32)
    lanes = jnp.arange(w, dtype=jnp.int32)

    def cond(carry):
        return carry[0] < n_waves

    def body(carry):
        t, state, outs = carry
        # slot s -> its t-th packet; dead slots (t >= slot_len[s]) would
        # index past their segment, so clamp explicitly — their lanes are
        # masked out of the state update below and their output columns
        # are overwritten by later waves
        lane_idx = jnp.minimum(seg_off_slot + t, n + w - 1)
        block = fmat[:, lane_idx]
        p = {k: block[i] for i, k in enumerate(_PKT_FIELDS)}
        new_state, out = _rx_decide(state, p)
        live = lanes < m_arr[t]
        state = {f: jnp.where(live, new_state[f], state[f])
                 for f in _STATE_FIELDS}
        outs = jax.lax.dynamic_update_slice(
            outs, jnp.stack([out[k].astype(jnp.int32) for k in _OUT_KEYS]),
            (0, start_arr[t]))
        return t + 1, state, outs

    _, state, outs = jax.lax.while_loop(
        cond, body, (jnp.int32(0), state0, outs0))

    tables = RxTables(**{
        f: getattr(tables, f).at[slot_to_qp].set(state[f])
        for f in _STATE_FIELDS})
    unsorted = jnp.where(valid, outs[:, pos], 0)   # fused unsort gather
    res = {}
    for i, k in enumerate(_OUT_KEYS):
        res[k] = unsorted[i] > 0 if k in _OUT_BOOL else unsorted[i]
    # per-QP CE tally as a segmented reduction over the sorted (wave)
    # layout: the CE echo is stateless, so it reads straight off the
    # sorted header columns — one cumsum + a (Q,)-gather, no scatter
    ecn_s = fmat[_PKT_FIELDS.index("ecn"), :n]
    opc_s = fmat[_PKT_FIELDS.index("opcode"), :n]
    flag = ((ecn_s > 0) & (sk < n_qps) &
            jnp.isin(opc_s, jnp.asarray(pk.PAYLOAD_OPS, jnp.int32)))
    csum = jnp.concatenate([jnp.zeros(1, jnp.int32),
                            jnp.cumsum(flag.astype(jnp.int32))])
    res["ecn_cnt"] = (csum[bounds[1:]] - csum[bounds[:-1]]).astype(jnp.int32)
    return tables, RxResult(**{k: res[k] for k in RxResult._fields})


class TxTables(NamedTuple):
    npsn: jax.Array        # (Q,) int32
    msn: jax.Array         # (Q,) int32


@partial(jax.jit, donate_argnums=(0,))
def tx_pipeline(tables: TxTables, cmds: Dict[str, jax.Array]
                ) -> Tuple[TxTables, Dict[str, jax.Array]]:
    """TX path oracle: assign consecutive PSNs per command (one command
    = one message of n_pkts fragments) and bump nPSN/MSN (§4.1 TX)."""
    def body(t, i):
        qpn = cmds["qpn"][i]
        n_pkts = cmds["n_pkts"][i]
        start = t.npsn[qpn]
        t = TxTables(
            npsn=t.npsn.at[qpn].set((start + n_pkts) & pk.PSN_MASK),
            msn=t.msn.at[qpn].add(1),
        )
        return t, {"start_psn": start}

    n = cmds["qpn"].shape[0]
    tables, outs = jax.lax.scan(body, tables, jnp.arange(n))
    return tables, outs


@partial(jax.jit, donate_argnums=(0,))
def tx_pipeline_batched(tables: TxTables, cmds: Dict[str, jax.Array]
                        ) -> Tuple[TxTables, Dict[str, jax.Array]]:
    """Batched TX engine: PSN-range assignment is a per-QP segmented
    exclusive cumulative sum — no sequential scan at all.  Bit-identical
    to ``tx_pipeline`` (same mod-2^24 arithmetic, per-QP independence).
    """
    qpn = cmds["qpn"]
    n_pkts = cmds["n_pkts"].astype(jnp.int32)
    n = qpn.shape[0]
    order = jnp.argsort(qpn, stable=True)
    sq = qpn[order]
    sn = n_pkts[order]
    excl = jnp.cumsum(sn) - sn                    # exclusive prefix sum
    idx = jnp.arange(n, dtype=jnp.int32)
    is_start = jnp.concatenate([jnp.ones((1,), bool), sq[1:] != sq[:-1]])
    # exclusive sum at each segment start, broadcast down the segment
    # (excl is nondecreasing, so a running max of the start values works)
    seg_base = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, excl, 0))
    start_sorted = (tables.npsn[sq] + (excl - seg_base)) & pk.PSN_MASK
    start_psn = jnp.zeros(n, jnp.int32).at[order].set(
        start_sorted.astype(jnp.int32))
    tables = TxTables(
        npsn=(tables.npsn.at[qpn].add(n_pkts)) & pk.PSN_MASK,
        msn=tables.msn.at[qpn].add(1),
    )
    return tables, {"start_psn": start_psn}


RX_ENGINES = {"scan": rx_pipeline, "batched": rx_pipeline_batched}
TX_ENGINES = {"scan": tx_pipeline, "batched": tx_pipeline_batched}


def clone_tables(t):
    """Deep-copy an Rx/TxTables value onto fresh device buffers.

    Every engine donates its carried-table argument (alloc-free carry
    for the fused epoch core), so the caller's input buffers are DEAD
    after the call.  The normal ``self.tables, res = engine(self.tables,
    batch)`` rebind never notices — but any caller that feeds the same
    table value to two engines (the scan/batched bit-identity tests) or
    re-times one call in a loop (the fig benches) must clone per use."""
    return type(t)(*(jnp.array(a) for a in t))


def make_rx_tables(n_qps: int, initial_credits: int = 64) -> RxTables:
    return RxTables(
        epsn=jnp.zeros(n_qps, jnp.int32),
        msn=jnp.zeros(n_qps, jnp.int32),
        bytes_left=jnp.zeros(n_qps, jnp.int32),
        cur_vaddr=jnp.zeros(n_qps, jnp.int32),
        credits=jnp.full((n_qps,), initial_credits, jnp.int32),
        rkey=jnp.zeros(n_qps, jnp.int32),
        rxbit=jnp.zeros(n_qps, jnp.int32),
        sr=jnp.zeros(n_qps, jnp.int32),
        acc_cnt=jnp.zeros(n_qps, jnp.int32),
        dup_cnt=jnp.zeros(n_qps, jnp.int32),
        ooo_cnt=jnp.zeros(n_qps, jnp.int32),
        cdrop_cnt=jnp.zeros(n_qps, jnp.int32),
        ecn_tot=jnp.zeros(n_qps, jnp.int32),
    )


def make_tx_tables(n_qps: int) -> TxTables:
    return TxTables(npsn=jnp.zeros(n_qps, jnp.int32),
                    msn=jnp.zeros(n_qps, jnp.int32))
