"""CPU checks of the benchmark's yardstick: resolution by name, the
refusal of a CPU, the generators, the references, the kernel counts and
each per-layer metric's reduction on a small synthetic trace.

    python -m pytest bench/tests
"""
import json
import os
import shutil
import subprocess
import sys
import types

import jax.numpy as jnp
import numpy as np
import pytest

from bench import gen, harness, reference
from bench.metrics import kernel_counts

ROOT = harness.ROOT
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


# ------------------------------------------------------------ resolution

def test_every_entry_resolves_by_name():
    bm = harness.benchmark()
    for cell in bm["workloads"]:
        entry, cfg, traffic, _ = harness.resolve(cell["name"])
        assert cfg["name"] == cell["config"]
        assert hasattr(harness.driver(traffic), "Cell")
    for m in bm["per_layer"]:
        assert callable(harness.metric_reader(m["name"]).read)
    for c in bm["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith(bm["paths"][0] + "/")


def test_added_entries_resolve_without_editing_a_file(tmp_path):
    """A later PR adds a configuration, a traffic mix, a cell and a
    per-layer metric as new files and new BENCHMARK.json entries."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bm = harness.benchmark()
    (tmp_path / "bench/configs/new_cfg.json").write_text(
        json.dumps({"name": "new_cfg", "transport": {"mtu": 1024}}))
    (tmp_path / "bench/traffic/new_mix.json").write_text(
        json.dumps({"driver": "linerate", "batch_pkts": 16}))
    (tmp_path / "bench/metrics/new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bm["configs"].append({"name": "new_cfg", "source": "x",
                          "file": "bench/configs/new_cfg.json",
                          "reduced": [], "why": "x"})
    bm["workloads"].append({"name": "new.cell", "config": "new_cfg",
                            "traffic": "new_mix", "chips": 1, "why": "x"})
    bm["per_layer"].append({"name": "new_metric", "unit": "count",
                            "better": "lower", "source": "program_counter",
                            "layer": "device", "moves": "goodput_gbps",
                            "workloads": ["new.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    h = harness.load_module(str(tmp_path / "bench/harness.py"), "h_copy")
    entry, cfg, traffic, bm2 = h.resolve("new.cell")
    assert cfg["transport"]["mtu"] == 1024 and traffic["batch_pkts"] == 16
    assert h.metric_reader("new_metric").read(None) == 42.0
    ctx = types.SimpleNamespace()
    assert h.read_layer_metrics("new.cell", bm2, ctx) == {
        "new_metric": {"value": 42.0, "unit": "count"}}


def test_runner_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "secure.linerate", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_runner_refuses_without_the_program(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "secure.linerate", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_peaks_refuse_an_unknown_device():
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks("cpu")


# ------------------------------------------------------------ generators

def _gens(seed):
    key = gen.seed_key(seed)
    qpn, rank, cnt = gen.rx_trace(key, 7, 64)
    hdr = gen.rx_batch(jnp.int32(3), qpn, rank, cnt, mtu=256)
    return {"hdr": np.stack([np.asarray(v) for v in hdr.values()]),
            "dpi": np.asarray(gen.dpi_packets(key, 4, 256, 0.5, 0.25)),
            "ds": np.asarray(gen.dpi_dataset(key, 8)[0]),
            "rec": np.asarray(gen.encode_packets(
                gen.criteo_records(key, 30, 13, 26, 0.5), 4096))}


def test_generators_repeat_for_a_seed():
    big = 2 ** 31 + 12345
    a, b, c = _gens(big), _gens(big), _gens(big + 1)
    for k in a:
        assert np.array_equal(a[k], b[k]), k
        assert not np.array_equal(a[k], c[k]), k


def test_rx_trace_continues_psns():
    qpn, rank, cnt = gen.rx_trace(gen.seed_key(5), 5, 40)
    q, r, n = (np.asarray(x) for x in (qpn, rank, cnt))
    assert n.sum() == 40
    for j in range(5):
        assert sorted(r[q == j]) == list(range(n[j]))
    p1 = np.asarray(gen.batch_psn(1, qpn, rank, cnt))
    assert np.array_equal(p1, n[q] + r)


def test_record_packets_round_trip():
    recs = gen.criteo_records(gen.seed_key(1), 60, 13, 26, 0.5)
    assert recs.shape == (60, 40)          # 160 B, 25 to a 4 KiB packet
    assert set(np.asarray(recs[:, 39]).tolist()) == {0, 1}
    pk = gen.encode_packets(recs, 4096)
    assert pk.shape == (3, 4096)
    back = np.asarray(gen.decode_packets(pk, 40))
    assert np.array_equal(back[:60], np.asarray(recs))
    assert not back[60:].any()


@pytest.fixture(scope="module")
def secure():
    from bench.deploy import Deployment
    return Deployment(harness.load_json(ROOT, "bench/configs/"
                                        "secure_rocev2.json"))


def test_edge_beats_score_near_the_threshold(secure):
    thr, params = secure.threshold, secure.params
    e = gen.edge_beats(gen.seed_key(3), 64, 1024, params, thr, (2e-4, 5e-3))
    d = np.abs(np.asarray(reference.dpi_scores(e, params)[:, 0]) - thr)
    plain = gen.dpi_packets(gen.seed_key(3), 64, 4096, 0.0, 0.2)
    far = np.abs(np.asarray(reference.packet_scores(
        plain, jnp.full(64, 4096), params)) - thr)
    assert np.median(d) < 0.01 < np.median(far)


def test_bf16_control_moves_scores(secure):
    plain = gen.dpi_packets(gen.seed_key(4), 8, 4096, 0.5, 0.2)
    s = np.asarray(reference.dpi_scores(plain, secure.params))
    s7 = np.asarray(reference.dpi_scores(plain, secure.params, 7))
    assert 1e-4 < np.abs(s - s7).max() < 0.1


# ------------------------------------------------------------ references

def test_aes_fips197_and_round_trip():
    key = np.arange(16, dtype=np.uint8)
    rk = jnp.asarray(reference.expand_key(key))
    pt = np.frombuffer(bytes.fromhex("00112233445566778899aabbccddeeff"),
                       np.uint8)[None]
    ct = np.asarray(reference.aes_encrypt(jnp.asarray(pt), rk))
    assert ct.tobytes().hex() == "69c4e0d86a7b0430d8cdb78070b4c55a"
    assert np.array_equal(np.asarray(reference.aes_decrypt(
        jnp.asarray(ct), rk)), pt)


def test_rx_reference_accepts_duplicates_and_gaps():
    hdr = {"qpn": [0, 0, 0, 1, 0], "opcode": [0x0A] * 5,
           "psn": [0, 1, 1, 5, 3], "plen": [64] * 5,
           "vaddr": [0, 64, 64, 0, 192], "dma_len": [64] * 5,
           "ack_req": [0] * 5, "valid": [1] * 5}
    st = {"epsn": [0, 0], "msn": [0, 0], "credits": [10, 10],
          "cur_vaddr": [0, 0], "acc_cnt": [0, 0]}
    out, new = reference.rx_go_back_n(hdr, st)
    assert out["accept"].tolist() == [1, 1, 0, 0, 0]
    assert out["dup"].tolist() == [0, 0, 1, 0, 0]
    assert out["ooo"].tolist() == [0, 0, 0, 1, 1]
    assert out["ack_psn"].tolist() == [0, 1, 1, 0xFFFFFF, 1]
    assert new["epsn"].tolist() == [2, 0] and new["msn"].tolist() == [2, 0]
    assert new["cur_vaddr"].tolist() == [128, 0]


def test_preproc_reference():
    recs = jnp.asarray([[-5, 0, 9, 123456, 7, 1]], jnp.int32)
    out = np.asarray(reference.preproc(recs, n_dense=3, n_sparse=2,
                                       modulus=1000))
    dense = out[0, :3].view(np.float32)
    assert np.allclose(dense, np.log1p([0.0, 0.0, 9.0]))
    assert out[0, 3:].tolist() == [456, 7, 1]       # label passed through


# ----------------------------------------------------------- kernel counts

def test_kernel_counts_match_hand_counts():
    assert kernel_counts.aes_ecb(4096) == (0, 8192)
    # 2 packets of 128 B = 4 beats; 64*128 + 128*64 + 64*1 MACs per beat
    flops, nbytes = kernel_counts.dpi_mlp(2, 128)
    assert flops == 4 * 2 * 16448
    assert nbytes == 256 + 4 * 4 + 16448 + 128 * 4 + 64 * 4
    assert kernel_counts.preproc(25, 40) == (0, 25 * 40 * 4 * 2)
    assert kernel_counts.crc32(3, 64) == (0, 3 * 64 + 12)
    assert kernel_counts.chunk_reduce(4, 10) == (30, 200)
    assert kernel_counts.need("aes_ecb", [(16,), (32,)]) == (0, 96)


# ------------------------------------------------------- metric reductions

def _trace():
    # window 0..1000 ns; ops: rx module 100-300, aes 300-600, dpi 600-700
    ops = [("%fusion.1 = s32[8] fusion(s32[8] %aes_ecb_pallas.1)", 100, 300),
           ("%aes_ecb_pallas.1 = s32[8] custom-call(s32[8] %x)", 300, 600),
           ("%dpi_scores_pallas.1 = f32[8] custom-call()", 600, 700),
           ("%preproc_pallas.1 = s32[8] custom-call()", 700, 750)]
    mods = [("jit_rx_pipeline_batched(1)", 100, 300),
            ("jit__process(2)", 300, 700)]
    spans = [(harness.WINDOW_SPAN, 0, 1000), ("bench.wait.batch", 650, 1000)]
    return harness.Trace(ops, mods, spans, (0, 1000))


def _ctx(**counters):
    calls = {"aes_ecb": [(1000,)], "dpi_mlp": [(1, 4096)],
             "preproc": [(25, 40)]}
    return harness.layer_context(_trace(), counters, calls, PEAK)


def _read(name, ctx):
    return harness.metric_reader(name).read(ctx)


def test_trace_busy_idle_and_breakdown():
    t = _trace()
    assert t.window_s == pytest.approx(1e-6)
    assert t.busy_s == pytest.approx(650e-9)
    assert _read("device_idle", _ctx()) == pytest.approx(35.0)
    assert [n for n, _ in t.top_ops(2)] == ["%aes_ecb_pallas.1",
                                            "%fusion.1"]
    assert t.top_ops(1)[0][1] == pytest.approx(3e-7)
    # gaps 0-100 (no span) and 750-1000 (wait.batch)
    assert t.idle_gaps() == [["bench.wait.batch", pytest.approx(2.5e-7)],
                             ["no span", pytest.approx(1e-7)]]


def test_counter_metrics():
    ctx = _ctx(d2h=30, ticks=10, lowerings=2, rx_pkts=4)
    assert _read("d2h_per_tick", ctx) == 3.0
    assert _read("compiles_in_window", ctx) == 2
    assert _read("rx_engine_ns_per_pkt", ctx) == pytest.approx(50.0)
    assert _read("d2h_per_tick", _ctx(d2h=3, ticks=0)) is None


def test_roofline_metrics():
    ctx = _ctx()
    # AES: 2000 B over 819 GB/s, in 300 ns
    assert _read("aes_ecb_roofline", ctx) == pytest.approx(
        100 * 2000 / 819e9 / 300e-9)
    f, b = kernel_counts.dpi_mlp(1, 4096)
    assert _read("dpi_mlp_roofline", ctx) == pytest.approx(
        100 * max(f / 197e12, b / 819e9) / 100e-9)
    assert _read("preproc_roofline", ctx) == pytest.approx(
        100 * 25 * 40 * 8 / 819e9 / 50e-9)


def test_readers_return_nothing_without_their_events():
    empty = harness.layer_context(
        harness.Trace([], [], [], (0, 1000)), {}, {}, PEAK)
    for name in ("aes_ecb_roofline", "dpi_mlp_roofline", "preproc_roofline",
                 "rx_engine_ns_per_pkt", "d2h_per_tick"):
        assert _read(name, empty) is None
    assert _read("aes_ecb_roofline", _ctx()) is not None
