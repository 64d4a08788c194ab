"""``incast.fused`` at a tiny size on the CPU: a sound run reads correct,
and the comparison that decides ``correct`` sees the timed path broken
underneath it, once in the bytes it lands and once in the protocol
state it leaves; the cell's per-layer readers on small synthetic
traces.

    python -m pytest bench/tests/test_incast.py
"""
import pytest

from bench import harness, run
from repro.core import fused

TINY = {"fan_in": 4, "message_bytes": 65536}
TINY_CFG = {"transport": {"qps_per_node": 16}, "fabric": {"ports": 5}}


def _run():
    return run.run_cell("incast.fused", 2 ** 31 + 77, 1.0, False,
                        overrides=TINY, cfg_overrides=TINY_CFG, t_start=0.0)


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["metrics"]["goodput_gbps"]["value"] > 0
    assert out["metrics"]["p95_ms"]["value"] > 0


def test_skipped_dma_fails(monkeypatch):
    """The unpack leaves out one DMA write of every epoch: those bytes
    keep what the receive buffer held before."""
    real = fused._apply

    def apply(world, out, nodes):
        acc = world.layout.get(out, "p_acc")
        fi, row = (int(v[0]) for v in acc.nonzero())
        fl = world.flows[fi]
        a = int(world.layout.get(out, "p_aaddr")[fi, row])
        buf = fl.rcv._buffer_for(fl.rq)
        keep = buf[a:a + fl.plan[row].payload_len].copy()
        res = real(world, out, nodes)
        buf[a:a + len(keep)] = keep
        return res

    monkeypatch.setattr(fused, "_apply", apply)
    out = _run()
    assert out["checks"]["payload_diff"]["value"] > 0, out["checks"]
    assert not out["correct"]


def test_miscounted_retransmission_fails(monkeypatch):
    """Every epoch adds one retransmission to the first sender's stats
    after the unpack."""
    real = fused._apply

    def apply(world, out, nodes):
        res = real(world, out, nodes)
        nodes[1].stats.retransmissions += 1
        return res

    monkeypatch.setattr(fused, "_apply", apply)
    out = _run()
    assert out["checks"]["contract_diff"]["value"] > 0, out["checks"]
    assert not out["correct"]


def test_carried_flow_control_fault_fails(monkeypatch):
    """Every epoch leaves the first sender's flow-control budget on its
    QP one packet short: nothing a round's counters hold, only the state
    the next round starts from."""
    real = fused._apply

    def apply(world, out, nodes):
        res = real(world, out, nodes)
        snd = world.flows[0].snd
        snd.fc.budget[world.flows[0].sq] -= 1
        return res

    monkeypatch.setattr(fused, "_apply", apply)
    out = _run()
    assert out["checks"]["contract_diff"]["value"] > 0, out["checks"]
    assert not out["correct"]


def _read(name, trace, **counters):
    ctx = harness.layer_context(trace, counters, {}, {})
    return harness.metric_reader(name).read(ctx)


def _epochs(n_modules, ticks_in_first):
    """Epoch programs 10,000 ns apart, each 6,000 ns long, the first
    starting at 100 ns; the first holds ``ticks_in_first`` ticks of 100
    ns, each opening with its wire sorts, the others none (recording
    stopped)."""
    per = fused.WIRE_SORTS_PER_TICK
    mods = [(f"jit_fused_epoch({i})", 100 + 10_000 * i, 6_100 + 10_000 * i)
            for i in range(n_modules)]
    ops = [(f"%sort.{per * k + j} = s32[64] sort(s32[64] %a)",
            110 + 100 * k + 5 * j, 113 + 100 * k + 5 * j)
           for k in range(ticks_in_first) for j in range(per)]
    ops.append(("%sort.999 = s32[64] sort(s32[64] %b)", 7_000, 7_005))
    return harness.Trace(ops, mods, [], (0, 10_000 * n_modules))


def test_fused_epoch_ns_per_tick_reads_a_fixed_count_of_ticks():
    reader = harness.metric_reader("fused_epoch_ns_per_tick")
    k = reader.TICKS
    # tick k begins 10 ns after the epoch plus k ticks of 100 ns
    want = (10 + 100 * k) / k
    assert _read("fused_epoch_ns_per_tick",
                 _epochs(1, k + 1)) == pytest.approx(want)
    # more ticks held, or later epochs after the cap: the same ticks
    assert _read("fused_epoch_ns_per_tick",
                 _epochs(3, k + 15)) == pytest.approx(want)
    # the trace ends before tick k begins: nothing to read
    assert _read("fused_epoch_ns_per_tick", _epochs(1, k)) is None
    assert _read("fused_epoch_ns_per_tick", _epochs(0, 0)) is None


def test_counter_readers():
    empty = harness.Trace([], [], [], (0, 1))
    assert _read("fused_tick_share", empty, fused_ticks=90,
                 unfused=10) == pytest.approx(90.0)
    assert _read("fused_tick_share", empty, fused_ticks=5) == 100.0
    assert _read("fused_tick_share", empty) is None
    assert _read("d2h_per_round", empty, d2h=30, rounds=3) == 10.0
    assert _read("d2h_per_round", empty, rounds=3) is None
    assert _read("d2h_per_round", empty, d2h=3, rounds=0) is None
