"""Unified telemetry tests: metric registry, flight recorder,
Chrome-trace export, counter conservation under loss/spray, engine
counter bit-identity, and the determinism contract (no wall-clock in
``repro.core``; two seeded runs export byte-identical traces).
"""
import json

import numpy as np
import pytest

import jax.numpy as jnp

from _hyp import given, settings, st
from repro.core import packet as pk
from repro.core import pipeline as pipe
from repro.core import telemetry as tm
from repro.core.netsim import (ClosConfig, FabricConfig,
                               clos_incast_scenario, incast_scenario)
from repro.core.rdma import ENGINE_COUNTERS


# ---------------------------------------------------------------------------
# MetricRegistry
# ---------------------------------------------------------------------------

def test_typed_metrics():
    c = tm.Counter()
    c.inc()
    c.inc(4)
    assert c.snapshot() == 5
    g = tm.Gauge()
    g.set(2.5)
    assert g.snapshot() == 2.5
    h = tm.Histogram(bounds=(1, 4, 16))
    for v in (0, 1, 3, 20, 1000):
        h.observe(v)
    s = h.snapshot()
    assert s["count"] == 5 and s["sum"] == 1024
    assert s["min"] == 0 and s["max"] == 1000
    assert s["buckets"] == [2, 1, 0, 2]       # <=1, <=4, <=16, overflow


def test_registry_register_and_reject():
    reg = tm.MetricRegistry()
    reg.counter("a/b").inc(3)
    with pytest.raises(ValueError):
        reg.counter("a/b")                    # duplicate
    for bad in ("", "/x", "x/"):
        with pytest.raises(ValueError):
            reg.register(bad, tm.Counter())
    assert reg.paths() == ["a/b"]


def test_registry_snapshot_flat_diff():
    reg = tm.MetricRegistry()
    c = reg.counter("net/tx")
    reg.gauge("net/depth", 7)
    reg.register("node", lambda: {"stats": {"rx": 2, "lst": [1, 2]}})
    c.inc(10)
    snap = reg.snapshot()
    assert snap == {"net": {"tx": 10, "depth": 7},
                    "node": {"stats": {"rx": 2, "lst": [1, 2]}}}
    flat = reg.flat(snap)
    assert flat == {"net/tx": 10, "net/depth": 7, "node/stats/rx": 2,
                    "node/stats/lst/0": 1, "node/stats/lst/1": 2}
    c.inc(5)
    d = reg.diff(snap, reg.snapshot())
    assert d["net/tx"] == 5 and d["node/stats/rx"] == 0


# ---------------------------------------------------------------------------
# FlightRecorder
# ---------------------------------------------------------------------------

def test_recorder_ring_bounds_and_counts():
    rec = tm.FlightRecorder(capacity=4)
    for i in range(10):
        rec.record(i, "inject", ("node", 0), psn=i)
    assert rec.total_events == 10
    assert rec.dropped_events == 6
    assert len(rec.events()) == 4
    assert [e.tick for e in rec.events()] == [6, 7, 8, 9]
    # monotonic per-kind counts are wrap-independent
    assert rec.counts["inject"] == 10
    snap = rec.snapshot()
    assert snap["events_total"] == 10 and snap["events_retained"] == 4
    rec.clear()
    assert rec.total_events == 0 and not rec.events()


def test_chrome_trace_phases_and_tracks():
    rec = tm.FlightRecorder()
    rec.record(1, "enqueue", ("port", 0), qpn=1, psn=0)
    rec.record(1, "qdepth", ("port", 0), depth=3)
    rec.record(2, "coll_transfer", ("coll", "world4"), dur=5, sends=2)
    rec.record(3, "retransmit", ("qp", "1:7"), psn=9)
    doc = rec.chrome_trace(tick_us=2)
    evs = doc["traceEvents"]
    by_ph = {}
    for e in evs:
        by_ph.setdefault(e["ph"], []).append(e)
    # process/thread metadata for 3 categories + 3 threads
    names = {e["args"]["name"] for e in by_ph["M"]
             if e["name"] == "process_name"}
    assert names == {"port", "coll", "qp"}
    [cnt] = by_ph["C"]
    assert cnt["name"] == "qdepth" and cnt["args"]["depth"] == 3
    [span] = by_ph["X"]
    assert span["ts"] == 4 and span["dur"] == 10   # tick_us scaling
    assert span["args"] == {"sends": 2}            # dur lifted out
    assert {e["name"] for e in by_ph["i"]} == {"enqueue", "retransmit"}
    json.loads(rec.chrome_trace_json())            # serializable


def test_chrome_trace_export_roundtrip(tmp_path):
    rec = tm.FlightRecorder()
    res = incast_scenario(2, message_bytes=8192, recorder=rec)
    path = tmp_path / "trace.json"
    n = rec.export_chrome_trace(str(path))
    assert n == len(rec.events()) > 0
    doc = json.loads(path.read_text())
    assert doc["otherData"]["clock"] == "sim_ticks"
    assert any(e["ph"] == "C" for e in doc["traceEvents"])


# ---------------------------------------------------------------------------
# Determinism contract
# ---------------------------------------------------------------------------

def test_core_determinism_lint_clean():
    """The simulator's only clock is the integer tick and its only
    randomness is seeded streams: the balint determinism pass (wall
    clock, unseeded RNG, set/dict iteration order on wire paths,
    mutable defaults — see docs/BALINT.md) must report zero violations
    over ``repro.core``.  Supersedes the old ad-hoc wall-clock grep."""
    from repro.analysis import run_analysis
    report = run_analysis(paths=["src/repro/core"],
                          passes=["determinism"])
    assert not report.violations, "\n".join(
        f"{v.path}:{v.line}: [{v.rule}] {v.message}"
        for v in report.violations)


def _traced_run():
    rec = tm.FlightRecorder()
    clos_incast_scenario(3, message_bytes=16384, fail_spine_at=10,
                         recorder=rec)
    return rec.chrome_trace_json()


def test_trace_byte_identical_across_runs():
    assert _traced_run() == _traced_run()


# ---------------------------------------------------------------------------
# Engine-carried counters (the ecn_cnt pattern)
# ---------------------------------------------------------------------------

def test_engine_counter_columns_zero_initialized():
    t = pipe.make_rx_tables(4)
    for col in pipe.COUNTER_FIELDS:
        arr = np.asarray(getattr(t, col))
        assert arr.shape == (4,) and arr.dtype == np.int32
        assert (arr == 0).all()


def test_engine_counters_match_outputs():
    """Counter columns must reconcile with the per-packet outputs the
    same pipeline call returns — on both engines."""
    rng = np.random.default_rng(7)
    n_pkts, n_qps = 64, 5
    pkts = []
    nxt = {}
    for _ in range(n_pkts):
        q = int(rng.integers(0, n_qps))
        p0 = nxt.get(q, 0)
        use = p0 if rng.random() < 0.7 else max(0, p0 - 1)
        if use == p0:
            nxt[q] = p0 + 1
        pkts.append(pk.Packet(opcode=pk.WRITE_ONLY, qpn=q, psn=use,
                              payload=np.zeros(32, np.uint8), dma_len=32))
    batch = {k: jnp.asarray(v)
             for k, v in pk.batch_from_packets(pkts, mtu=256).items()}
    t0 = pipe.make_rx_tables(n_qps)
    for fn in (pipe.rx_pipeline, pipe.rx_pipeline_batched):
        t1, r = fn(pipe.clone_tables(t0), batch)  # engines donate arg 0
        assert int(np.asarray(t1.acc_cnt).sum()) == \
            int(np.asarray(r.accept).sum())
        assert int(np.asarray(t1.ecn_tot).sum()) == \
            int(np.asarray(r.ecn_cnt).sum())


def test_engine_totals_match_host_stats_under_loss():
    """The jitted engine's carried counters, harvested once at snapshot
    time, must agree exactly with the host-side ``NodeStats`` — for
    every mapped counter, on a lossy run that exercises dup/ooo paths."""
    res = incast_scenario(
        4, message_bytes=32768,
        fabric_cfg=FabricConfig(port_bandwidth=2, port_delay=2,
                                queue_capacity=8, seed=3))
    for node in [res.receiver] + res.senders:
        totals = node.engine_totals()
        for host_name, val in totals.items():
            assert val == getattr(node.stats, host_name), (
                f"node {node.node_id}: engine {host_name}={val} != host "
                f"stats {getattr(node.stats, host_name)}")
    assert res.receiver.engine_totals()["accepted"] > 0


# ---------------------------------------------------------------------------
# Conservation + event reconciliation under random loss/spray
# ---------------------------------------------------------------------------

@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2**31), st.integers(2, 4),
       st.sampled_from([0.0, 0.02, 0.05]),
       st.sampled_from(["spray", "ecmp"]),
       st.sampled_from(["selective_repeat", "go_back_n"]))
def test_counters_reconcile_random_loss_spray(seed, fan_in, loss, path,
                                              rx_mode):
    """Packet conservation: every injected packet is delivered, dropped,
    or still in flight; retransmit stats match recorded retransmit
    events exactly."""
    rec = tm.FlightRecorder(capacity=1 << 18)
    cfg = ClosConfig(nodes_per_leaf=1, n_spines=2, port_bandwidth=4,
                     port_delay=1, queue_capacity=48, spine_delay=(1, 5),
                     loss_prob=loss, seed=seed % 997,
                     path_mode=path)
    res = clos_incast_scenario(fan_in, message_bytes=8192, clos_cfg=cfg,
                               rx_mode=rx_mode, path_select=path,
                               recorder=rec)
    reg, _ = tm.instrument(fabric=res.fabric,
                           nodes=[res.receiver] + res.senders,
                           recorder=rec)
    snap = reg.snapshot()
    fab = snap["fabric"]
    dropped = (fab["ports"]["wire_dropped"] + fab["ports"]["tail_dropped"]
               + fab["uplinks"]["wire_dropped"]
               + fab["uplinks"]["tail_dropped"]
               + fab["spine_down"]["wire_dropped"]
               + fab["spine_down"]["tail_dropped"]
               + fab["failure_dropped"])
    assert fab["injected"] == (dropped + fab["ports"]["delivered"]
                               + fab["in_flight"]), \
        "packet conservation violated"
    by = snap["flight"]["by_kind"]
    retx = sum(n["retx"]["retransmissions"]
               for k, n in snap.items() if k.startswith("node"))
    stats_retx = sum(s.stats.retransmissions
                     for s in [res.receiver] + res.senders)
    assert by.get("retransmit", 0) == stats_retx
    assert retx >= stats_retx          # buffer counts staged resends too
    # every send recorded either an inject or a wire_drop event
    assert by.get("inject", 0) + by.get("wire_drop", 0) == fab["injected"]


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2**31), st.integers(1, 24), st.integers(1, 96))
def test_counter_columns_scan_vs_batched(seed, n_qps, n_pkts):
    """The five counter columns are part of the carried state, so the
    batched engine must produce bit-identical arrays to the scan
    oracle — including on traces with dup/gap/invalid lanes."""
    rng = np.random.default_rng(seed)
    pkts, nxt = [], {}
    for _ in range(n_pkts):
        q = int(rng.integers(0, n_qps))
        p0 = nxt.get(q, 0)
        r = rng.random()
        if r < 0.6:
            use, nxt[q] = p0, p0 + 1
        elif r < 0.8:
            use = max(0, p0 - int(rng.integers(1, 3)))
        else:
            use = p0 + int(rng.integers(1, 3))
        pkts.append(pk.Packet(opcode=pk.WRITE_ONLY, qpn=q, psn=use,
                              payload=np.zeros(16, np.uint8), dma_len=16))
    b = pk.batch_from_packets(pkts, mtu=256)
    b["valid"][rng.random(n_pkts) < 0.15] = 0      # invalid lanes
    batch = {k: jnp.asarray(v) for k, v in b.items()}
    t0 = pipe.make_rx_tables(n_qps, initial_credits=4)
    ta, _ = pipe.rx_pipeline(pipe.clone_tables(t0), batch)
    tb, _ = pipe.rx_pipeline_batched(t0, batch)
    for col in pipe.COUNTER_FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(ta, col)), np.asarray(getattr(tb, col)),
            err_msg=f"counter column {col}")


# ---------------------------------------------------------------------------
# The acceptance scenario: 8:1 incast + mid-run spine failure
# ---------------------------------------------------------------------------

def test_incast_spine_failure_trace_reconciles(tmp_path):
    """Perfetto trace of the 8:1 incast with a mid-run spine failure:
    the export is valid JSON and its event counts reconcile exactly
    with the MetricRegistry snapshot."""
    rec = tm.FlightRecorder(capacity=1 << 20)
    res = clos_incast_scenario(8, message_bytes=16384, fail_spine_at=10,
                               recorder=rec)
    reg, _ = tm.instrument(fabric=res.fabric,
                           nodes=[res.receiver] + res.senders,
                           recorder=rec)
    snap = reg.snapshot()
    assert rec.dropped_events == 0
    by = snap["flight"]["by_kind"]
    assert by["inject"] == snap["fabric"]["injected"]
    assert by.get("enqueue", 0) == \
        by.get("dequeue", 0) + by.get("flush", 0)
    assert by.get("spine_fail", 0) == 1
    assert snap["fabric"]["alive_spines"] == 1
    # every trace event is retained, so the exported JSON has exactly
    # the registry's total (plus track metadata records)
    path = tmp_path / "incast.json"
    rec.export_chrome_trace(str(path))
    doc = json.loads(path.read_text())
    data_events = [e for e in doc["traceEvents"] if e["ph"] != "M"]
    assert len(data_events) == snap["flight"]["events_total"]
    assert sum(by.values()) == snap["flight"]["events_total"]


# ---------------------------------------------------------------------------
# Program spans and host-path counters
# ---------------------------------------------------------------------------

def _span_calls():
    """(file, line, first argument) of every ``span(...)`` call in
    ``repro.core``: ``telemetry.span(...)`` or a bare ``span(...)``."""
    import ast
    import pathlib
    root = pathlib.Path(tm.__file__).parent
    out = []
    for path in sorted(root.glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            name = f.attr if isinstance(f, ast.Attribute) else \
                getattr(f, "id", None)
            if name == "span" and node.args:
                arg = node.args[0]
                out.append((path.name, node.lineno,
                            arg.value if isinstance(arg, ast.Constant)
                            else None))
    return out


def test_span_call_sites_name_declared_spans():
    calls = _span_calls()
    assert calls
    for fname, line, name in calls:
        assert name in tm.SPANS, f"{fname}:{line}: span {name!r} is not " \
            f"declared in telemetry.SPANS"
    assert set(tm.SPANS) == {n for _, _, n in calls}, \
        "every declared span has a call site"
    assert all(n.startswith("balboa.") for n in tm.SPANS)
    assert len(set(tm.SPANS)) == len(tm.SPANS)


class _RecordingChain:
    """A service chain that passes payloads through and records the
    rows of each call and how many of them carry a payload."""

    def __init__(self):
        from repro.core.services import ServiceChain
        self.chain = ServiceChain()
        self.calls = []

    def process(self, payload, plen):
        self.calls.append((payload.shape[0], int(np.sum(np.asarray(plen)
                                                        > 0))))
        return self.chain.process(payload, plen)


def _two_node_flow(chain=None, rx_mode="go_back_n", flows=4, msg=3000,
                   loss=0.05, seed=11, n_qps=8):
    from repro.core.netsim import LinkConfig, Network
    from repro.core.rdma import RdmaNode
    net = Network(2, LinkConfig(loss_prob=loss, latency_ticks=2, seed=seed))
    kw = dict(n_qps=n_qps, mtu=256, fc_window=8, rx_credits=16,
              rx_mode=rx_mode)
    a = RdmaNode(0, net, **kw)
    b = RdmaNode(1, net, services=chain, **kw)
    rng = np.random.default_rng(seed)
    qps = [a.init_rdma(msg, b)[0] for _ in range(flows)]
    for q in qps:
        a.rdma_write(q, rng.integers(0, 256, msg, dtype=np.uint8))
    return [a, b]


def test_chain_rows_follow_the_padding_rule():
    """Each RX batch of n data packets reaches the chain padded to the
    smallest power of two from ``RX_PAD`` that holds it; the node counts
    those rows and the padding among them."""
    from repro.core.rdma import RX_PAD, run_network
    chain = _RecordingChain()
    nodes = _two_node_flow(chain)
    run_network(nodes, max_ticks=2000)
    b = nodes[1]
    assert len(chain.calls) > 3
    for rows, n in chain.calls:
        want = RX_PAD
        while want < n:
            want *= 2
        assert rows == want, (rows, n)
    assert any(n > RX_PAD for _, n in chain.calls)
    hs = b.host_stats
    assert hs.rx_batches == len(chain.calls)
    assert hs.chain_rows == sum(r for r, _ in chain.calls)
    assert hs.chain_pad_rows == sum(r - n for r, n in chain.calls)
    assert nodes[0].host_stats.chain_rows == 0      # ACKs only, no chain
    assert b.snapshot()["host"]["chain_pad_rows"] == hs.chain_pad_rows


@pytest.mark.parametrize("rx_mode", ["go_back_n", "selective_repeat"])
def test_d2h_reads_match_a_patched_asarray(rx_mode, monkeypatch):
    """``d2h_reads`` counts every device array the nodes read back, as
    a patched ``np.asarray`` sees them, over the same ticks."""
    import jax
    from repro.core.rdma import step_network
    nodes = _two_node_flow(_RecordingChain(), rx_mode=rx_mode)
    for _ in range(5):
        step_network(nodes)
    before = sum(nd.host_stats.d2h_reads for nd in nodes)
    calls0 = len(nodes[1].services.calls)
    seen = [0]
    real = np.asarray

    def counting(x, *args, **kw):
        if isinstance(x, jax.Array):
            seen[0] += 1
        return real(x, *args, **kw)

    monkeypatch.setattr(np, "asarray", counting)
    for _ in range(60):
        step_network(nodes)
    nodes[1].snapshot()              # engine counters read back too
    monkeypatch.undo()
    # the recording chain's own read of ``plen`` is not the node's
    chain_reads = len(nodes[1].services.calls) - calls0
    got = sum(nd.host_stats.d2h_reads for nd in nodes) - before
    assert got > 0
    assert got == seen[0] - chain_reads


@pytest.mark.parametrize("rx_mode", ["go_back_n", "selective_repeat"])
def test_one_read_back_per_rx_batch(rx_mode):
    """Each RX batch reaches the host in one read: on every tick of a
    lossy flow through a chain, each node's ``d2h_reads`` grows by as
    much as its ``rx_batches``."""
    from repro.core.rdma import network_pending, step_network
    nodes = _two_node_flow(_RecordingChain(), rx_mode=rx_mode, msg=20000)
    for _ in range(3000):
        before = [(nd.host_stats.d2h_reads, nd.host_stats.rx_batches)
                  for nd in nodes]
        step_network(nodes)
        for nd, (d2h, batches) in zip(nodes, before):
            assert (nd.host_stats.d2h_reads - d2h
                    == nd.host_stats.rx_batches - batches)
        if not network_pending(nodes):
            break
    b = nodes[1]
    assert sum(b._completions.values()) == 4 * b.expected_completions(20000)
    assert b.host_stats.rx_batches > 10


def _xor_flag_chain():
    """A chain whose output and flags both depend on the payload: XOR
    on the path, then an inspector flagging rows whose first byte is
    odd."""
    from repro.core.services import (OnPathService, ParallelPathService,
                                      ServiceChain)

    class Xor(OnPathService):
        name = "xor"

        def __call__(self, payload, plen):
            return payload ^ jnp.uint8(0x5A)

    class Odd(ParallelPathService):
        name = "odd"

        def __call__(self, payload, plen):
            return (payload[:, 0] & 1).astype(jnp.int32)

    return ServiceChain(on_path=[Xor()], parallel_after=[Odd()])


def _separate_reads(node, cols, rows):
    """``RdmaNode._read_back`` as one read per device array."""
    return ({k: node._to_host(v) for k, v in cols.items()},
            None if rows is None else node._to_host(rows))


# flows -> the padded RX batch sizes they must reach
_READBACK_FLOWS = {
    "narrow": (dict(flows=7), {16, 64}),
    "wide": (dict(n_qps=41, flows=40, msg=2048), {512}),
}


@pytest.mark.parametrize("flow", sorted(_READBACK_FLOWS))
@pytest.mark.parametrize("chained", [True, False])
@pytest.mark.parametrize("rx_mode", ["go_back_n", "selective_repeat"])
def test_packed_read_back_matches_separate_reads(rx_mode, chained, flow,
                                                 monkeypatch):
    """The one packed read of an RX batch gives every result column,
    ``ecn_cnt``, ``credits``, ``epsn``, payload and flags exactly as a
    separate read of the same device array does; a lossy flow landed
    through it ends as one landed through separate reads: same stats,
    credits, completions and landed bytes."""
    from repro.core.rdma import RdmaNode, run_network
    kw, want_rows = _READBACK_FLOWS[flow]
    packed = RdmaNode._read_back
    rows_seen = set()

    def checked(node, cols, rows):
        host, out = packed(node, cols, rows)
        assert set(host) == set(cols)
        pairs = [(host[k], cols[k]) for k in cols]
        if rows is None:
            assert out is None
        else:
            pairs.append((out, rows))
        for got, dev in pairs:
            want = np.asarray(dev)
            assert got.dtype == want.dtype and got.shape == want.shape
            assert np.array_equal(got, want)
        assert ("epsn" in cols) == (rx_mode == "selective_repeat")
        assert ("flags" in cols) == chained == (rows is not None)
        rows_seen.add(cols["accept"].shape[0])
        return host, out

    def run(read_back):
        monkeypatch.setattr(RdmaNode, "_read_back", read_back)
        nodes = _two_node_flow(_xor_flag_chain() if chained else None,
                               rx_mode=rx_mode, **kw)
        run_network(nodes, max_ticks=3000)
        return nodes

    nodes = run(checked)
    assert want_rows <= rows_seen, rows_seen
    b = nodes[1]
    msg = kw.get("msg", 3000)
    assert sum(b._completions.values()) == \
        kw["flows"] * b.expected_completions(msg)
    assert (b.stats.dpi_flagged > 0) == chained
    for nd, ref in zip(nodes, run(_separate_reads)):
        assert nd.stats == ref.stats
        assert nd.credits.credits == ref.credits.credits
        assert nd._completions == ref._completions
        assert nd._rx_progress == ref._rx_progress
        got, want = nd.host_stats.snapshot(), ref.host_stats.snapshot()
        assert got.pop("d2h_reads") <= want.pop("d2h_reads")
        assert got == want
        assert nd._qp_buffer.keys() == ref._qp_buffer.keys()
        for q, (_, buf) in nd._qp_buffer.items():
            assert np.array_equal(buf, ref._qp_buffer[q][1])


def test_read_back_compiles_each_batch_size_before_it_is_met():
    """The first RX batch larger than any before compiles the read-back
    for its size and every smaller padded size, each once; a batch of a
    size met before, or smaller, lowers nothing."""
    import jax
    from repro.core.rdma import RX_PAD, network_pending, step_network
    lowered, on = [], [True]

    def listen(event, _secs, fun_name=None, **_kw):
        if on[0] and fun_name == "jit(rx_readback)" and \
                event.endswith("jaxpr_to_mlir_module_duration"):
            lowered.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        # 37 QPs: shapes no other test compiles
        nodes = _two_node_flow(n_qps=37, flows=36, msg=8192)
        b = nodes[1]
        quiet_batches = 0
        for _ in range(3000):
            seen, batches, n0 = b._readback_rows, b.host_stats.rx_batches, \
                len(lowered)
            step_network(nodes)
            if b._readback_rows == seen:
                assert len(lowered) == n0
                quiet_batches += b.host_stats.rx_batches - batches
            if not network_pending(nodes):
                break
    finally:
        on[0] = False
    assert b._readback_rows == 512 and quiet_batches > 10
    assert len(lowered) == (512 // RX_PAD).bit_length()   # 16, 32, ..., 512


@pytest.mark.parametrize("rows", [16, 64, 512])
def test_read_back_keeps_every_bit(rows):
    """Random bit patterns of each dtype the RX path reads back (bool,
    int32, uint8 payload rows) come back exactly, with and without
    payload rows."""
    from repro.core.netsim import LinkConfig, Network
    from repro.core.rdma import RdmaNode
    node = RdmaNode(0, Network(1, LinkConfig()), n_qps=500)
    rng = np.random.default_rng(rows)
    cols = {"accept": rng.integers(0, 2, rows).astype(bool),
            "dma_addr": rng.integers(-2**31, 2**31, rows, dtype=np.int32),
            "ecn_cnt": rng.integers(-2**31, 2**31, 500, dtype=np.int32),
            "send_ack": rng.integers(0, 2, rows).astype(bool)}
    payload = rng.integers(0, 256, (rows, 4096), dtype=np.uint8)
    for with_rows in (False, True):
        host, out = node._read_back(
            {k: jnp.asarray(v) for k, v in cols.items()},
            jnp.asarray(payload) if with_rows else None)
        for k, v in cols.items():
            assert host[k].dtype == v.dtype and np.array_equal(host[k], v)
        assert out is None if not with_rows else \
            np.array_equal(out, payload)
    assert node.host_stats.d2h_reads == 2


FUSED_SPANS = {"balboa.fused.pack", "balboa.fused.epoch",
               "balboa.fused.unpack"}


def _profiled_spans(tmp_path, body):
    """Run ``body`` under a CPU ``jax.profiler`` session; return its
    ``balboa.*`` host events as (name, start ns, end ns, stats)."""
    import glob
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                       dict(e.stats)) for e in line.events
                      if e.name.startswith("balboa.")]
    return spans


def test_profiled_ticks_hold_nested_spans_with_stats(tmp_path):
    """Under a CPU ``jax.profiler`` session a few ticks of a two-node
    flow record every declared span of the per-tick path with its stats;
    receive spans run in order and do not overlap; a message sent from
    ACK handling nests in ``balboa.rx.ctrl``."""
    from repro.core.rdma import step_network
    nodes = _two_node_flow(_RecordingChain(), loss=0.0)
    step_network(nodes)                          # compile outside

    def ticks():
        for _ in range(40):
            step_network(nodes)
    spans = _profiled_spans(tmp_path, ticks)
    assert {n for n, *_ in spans} == set(tm.SPANS) - FUSED_SPANS
    by = {}
    for n, s, e, st_ in spans:
        by.setdefault(n, []).append((s, e, st_))
    for _, _, st_ in by["balboa.tx"]:
        assert set(st_) == {"qp", "psn", "pkts"} and st_["pkts"] >= 1
    for _, _, st_ in by["balboa.rx.sync"]:
        assert st_["rows"] >= 16 and 0 <= st_["pad"] < st_["rows"]
    assert sum(st_["done"] for *_, st_ in by["balboa.rx.land"]) > 0

    def inside(inner, outer):
        return outer[0] <= inner[0] and inner[1] <= outer[1]

    # each data batch: stage, then sync, then land, one after the other
    for st0 in by["balboa.rx.stage"]:
        sy = min((x for x in by["balboa.rx.sync"] if x[0] >= st0[1]),
                 key=lambda x: x[0])
        ld = min((x for x in by["balboa.rx.land"] if x[0] >= sy[1]),
                 key=lambda x: x[0])
        assert st0[1] <= sy[0] and sy[1] <= ld[0]
    assert any(inside(t, c) for t in by["balboa.tx"]
               for c in by["balboa.rx.ctrl"])
    # nothing is sent from inside the fabric's tick
    assert not any(inside(t, f) for t in by["balboa.tx"]
                   for f in by["balboa.fabric"])


def test_profiled_fused_epoch_holds_its_spans_with_stats(tmp_path):
    """One fused run of a 2:1 incast records ``balboa.fused.pack``,
    ``.epoch`` and ``.unpack`` in that order, one after the other, with
    their stats: the flows, plan rows and wire slots packed, the ticks
    run and the trips of their live-work loops, and the DMA writes and
    bytes replayed into receive buffers."""
    from repro.core.netsim import incast_world
    w = incast_world(2, message_bytes=8192, n_qps=8)
    w.post_round([np.full(8192, 7, np.uint8)] * 2)
    w.run(epoch_mode="fused")                    # compile outside
    w.post_round([np.full(8192, 9, np.uint8)] * 2)
    trips0 = w.fabric.epochs.loop_trips
    spans = _profiled_spans(tmp_path, lambda: w.run(epoch_mode="fused"))
    by = {n: (s, e, st_) for n, s, e, st_ in spans if n in FUSED_SPANS}
    assert set(by) == FUSED_SPANS
    assert len([n for n, *_ in spans if n in FUSED_SPANS]) == 3
    pack, epoch, unpack = (by[f"balboa.fused.{k}"]
                           for k in ("pack", "epoch", "unpack"))
    assert pack[1] <= epoch[0] and epoch[1] <= unpack[0]
    # two directed flows a connection; 8 KiB is 2 MTU packets a sender;
    # wire slots: the bucket over the packets in flight, twice the flows'
    # windows (16 a sender, 64 at the receiver), twice the flows, and 16
    assert pack[2] == {"flows": 4, "plan_rows": 4, "wire_slots": 512}
    assert epoch[2]["steps"] == w.fabric.epochs.fused_ticks // 2
    # the live-work loops' trips, as EpochStats adds them up
    assert epoch[2]["trips"] == w.fabric.epochs.loop_trips - trips0 > 0
    assert unpack[2] == {"dmas": 4, "bytes": 2 * 8192}
    assert all((b == 9).all() for b in w.buffers)
