"""CPU checks of what reads the program's own spans and device names:
``bench/program_spans.py`` (self time, coverage, idle gaps by program
span, the split of device time) and the ``chain_glue_ns_per_pkt``
reader, on a small synthetic trace with nested ``balboa.*`` spans on two
threads and ``jit_service_chain`` programs, and on a CPU profile of a
few ticks of the program.

    python -m pytest bench/tests
"""
import glob
import os
import sys

import numpy as np
import pytest

from bench import harness

sys.path.insert(0, harness.BENCH)
import program_spans as ps  # noqa: E402

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
T1, T2 = "/host:CPU#0", "/host:CPU#1"


def _spans():
    # thread 1: a tick 0-600 holding the fabric, a control dispatch that
    # sends a message (tx inside ctrl) and the stage/sync/land of a batch;
    # thread 2: a user's post 600-900 holding one tx
    S = ps.Span
    return [S("balboa.fabric", 0, 50, T1, {}),
            S("balboa.rx.ctrl", 50, 200, T1, {}),
            S("balboa.tx", 100, 180, T1, {"qp": 1, "psn": 0, "pkts": 2}),
            S("balboa.rx.stage", 200, 300, T1, {}),
            S("balboa.rx.sync", 300, 500, T1, {"rows": 16, "pad": 3}),
            S("balboa.rx.land", 500, 560, T1, {"done": 1}),
            S("balboa.timers", 560, 600, T1, {}),
            S("balboa.tx", 650, 850, T2, {"qp": 2, "psn": 4, "pkts": 1})]


def _outer():
    return [ps.Span("bench.step_network", 0, 600, T1, {}),
            ps.Span("bench.rdma_write", 600, 900, T2, {})]


def _trace(chain="jit_service_chain(3)"):
    # device: RX engine 310-360; chain 380-480 with a kernel 390-440 and
    # glue 440-470; an op outside both programs 700-720
    ops = [("%while.1 = (s32[8]) while(%t)", 310, 350),
           ("%fusion.2 = s32[8] fusion(%a)", 350, 360),
           ("%reshape.3 = s32[8] reshape(%p)", 380, 390),
           ("%aes_ecb_pallas.1 = s32[8] custom-call(%reshape.3)", 390, 440),
           ("%convert.7 = u8[8] convert(%aes_ecb_pallas.1)", 440, 470),
           ("%copy.1 = s32[8] copy(%x)", 700, 720)]
    mods = [("jit_rx_pipeline_batched(1)", 310, 360), (chain, 380, 480)]
    spans = [(harness.WINDOW_SPAN, 0, 1000)] + \
        [(s.name, s.start, s.end) for s in _outer()]
    return harness.Trace(ops, mods, spans, (0, 1000))


def test_self_time_takes_out_children():
    got = ps.self_seconds(_spans())
    assert got["balboa.rx.ctrl"] == pytest.approx(70e-9)      # 150 - 80
    assert got["balboa.tx"] == pytest.approx(280e-9)          # 80 + 200
    assert got["balboa.rx.sync"] == pytest.approx(200e-9)
    # self times add up to the union the spans cover
    assert sum(got.values()) == pytest.approx(800e-9)


def test_coverage_of_the_benchmark_spans():
    # tick 0-600 fully covered; post 600-900 covered 650-850
    assert ps.covered_share(_outer(), _spans()) == pytest.approx(800 / 900)
    assert ps.covered_share(_outer(), []) == 0
    assert ps.covered_share([], _spans()) is None


def test_idle_gaps_by_program_span():
    gaps = dict(ps.idle_gaps_program(_trace(), _spans()))
    # gaps: 0-310 (midpoint 155: the tx inside rx.ctrl), 360-380 (sync),
    # 470-700 (midpoint 585: timers), 720-1000 (midpoint 860: none open)
    assert gaps == {"balboa.tx": pytest.approx(310e-9),
                    "balboa.rx.sync": pytest.approx(20e-9),
                    "balboa.timers": pytest.approx(230e-9),
                    "no span": pytest.approx(280e-9)}
    # the benchmark's own attribution of the same gaps
    assert dict(_trace().idle_gaps()) == {
        "bench.step_network": pytest.approx(560e-9),
        "bench.rdma_write": pytest.approx(280e-9)}


def test_device_split_sums_to_busy():
    d = ps.device_split(_trace())
    assert d["rx_engine"] == pytest.approx(50e-9)
    assert d["kernels"] == pytest.approx(50e-9)
    assert d["chain_glue"] == pytest.approx(40e-9)
    assert d["other"] == pytest.approx(20e-9)
    assert d["parts_sum"] == pytest.approx(d["busy"])


def _ctx(trace, counters=None, calls=None):
    return harness.layer_context(trace, counters or {}, calls or {}, PEAK)


def test_chain_glue_reader():
    read = harness.metric_reader("chain_glue_ns_per_pkt").read
    # line-rate: rows are the packets handed over
    assert read(_ctx(_trace(), {"rx_pkts": 4})) == pytest.approx(10.0)
    # served: the chain calls' padded rows
    calls = {"dpi_mlp": [(16, 4096), (4, 4096)], "aes_ecb": [(8192,)]}
    assert read(_ctx(_trace(), {"rx_pkts": 4}, calls)) == pytest.approx(2.0)
    # a program that names its chain otherwise, no rows, no trace
    assert read(_ctx(_trace("jit__process(3)"), {"rx_pkts": 4})) is None
    assert read(_ctx(_trace(), {})) is None
    assert read(_ctx(None, {"rx_pkts": 4})) is None


def test_kernel_ops_by_opcode_or_name():
    from bench.metrics import chain_glue_ns_per_pkt as glue
    assert glue.is_kernel("%dpi_scores_pallas.1 = f32[8] custom-call()")
    assert glue.is_kernel("%preproc_pallas.2")
    assert glue.is_kernel("%custom-call.4 = u8[8] custom-call(%x)")
    assert not glue.is_kernel("%fusion = s32[8] fusion(%aes_ecb_pallas.1)")
    assert not glue.is_kernel("%reshape.10 = s32[8] reshape(%p)")


def test_spans_of_a_cpu_profile(tmp_path):
    """A CPU profile of a few ticks of two nodes: every tick's time is
    inside a program span, and the self times add up to the time the
    spans cover."""
    import jax
    from jax.profiler import ProfileData
    from repro.core.netsim import LinkConfig, Network
    from repro.core.rdma import RdmaNode, step_network
    from repro.core.services import ServiceChain
    net = Network(2, LinkConfig(latency_ticks=2, seed=1))
    a = RdmaNode(0, net, n_qps=4, mtu=256, fc_window=8)
    b = RdmaNode(1, net, n_qps=4, mtu=256, fc_window=8,
                 services=ServiceChain())
    q, _, _ = a.init_rdma(4096, b)
    a.rdma_write(q, np.arange(4096, dtype=np.uint8))
    step_network([a, b])
    with ps.profiled(str(tmp_path)):
        for _ in range(30):
            with jax.profiler.TraceAnnotation("bench.step_network"):
                step_network([a, b])
    [path] = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                       recursive=True)
    profile = ProfileData.from_file(path)
    spans = ps.read_spans(profile)
    outer = ps.read_spans(profile, "bench.step_network")
    assert len(outer) == 30 and spans
    assert {s.name for s in spans} >= {"balboa.fabric", "balboa.timers",
                                       "balboa.rx.sync"}
    assert 0.5 < ps.covered_share(outer, spans) <= 1.0
    union = sum(e - s for s, e in ps._union((x.start, x.end)
                                             for x in spans)) * 1e-9
    assert sum(ps.self_seconds(spans).values()) == pytest.approx(union)
