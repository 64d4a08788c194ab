"""``chip_smoke.py`` at tiny sizes on the CPU: every phase runs and passes
its own reference check, and ``main`` refuses a CPU backend before any
phase runs (the chip run itself happens on a TPU)."""
import importlib.util
import json
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


TINY = {
    "rx_engine": dict(n_qps=8, n_pkts=64),
    "services": dict(flow_bytes=4 * 4096, batch_pkts=8),
    "incast": dict(n_senders=2, message_bytes=8192),
    "ingest": dict(n_pkts=8, replicas=2),
    "allreduce": dict(world=2, n_elems=1024),
}


@pytest.mark.parametrize("name", list(TINY))
def test_phase_passes_at_tiny_size(smoke, name):
    phase = dict(smoke.PHASES)[name]
    sizes, check = phase(**TINY[name])
    assert sizes and check


def test_main_refuses_cpu_before_any_phase(smoke, capsys, monkeypatch):
    ran = []
    monkeypatch.setattr(smoke, "PHASES",
                        tuple((n, lambda n=n: ran.append(n))
                              for n, _ in smoke.PHASES))
    assert smoke.main() != 0
    assert ran == []
    out = capsys.readouterr().out
    for line in out.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
