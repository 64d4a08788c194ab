"""The comparison that decides ``correct`` can fail.

Each test skips the runner's look for a chip and drives the rest of a
run (set-up, window, check) at a tiny size on the CPU, with the timed
path broken underneath, and sees ``correct`` come out false: a step
that returns its state unchanged, half of the batch left out, an answer
altered where it is produced, DPI flags handed to the wrong rows, and
the control (the reference in the chain's place, in bfloat16 where the
configuration states float32).  The
cells run on one chip, so no exchange between chips can be left out.

    python -m pytest bench/tests
"""
import jax.numpy as jnp
import pytest

from bench import harness, run
from repro.core import ingest as ingest_mod
from repro.core import pipeline as pipe
from repro.core import services

TINY = {
    "secure.linerate": {"batch_pkts": 64},
    "dlrm.linerate": {"batch_pkts": 64},
    "secure.flow": {"qps": 2, "message_bytes": 16384, "warm_ticks": 30,
                    "slots_per_qp": 64, "pool_messages": 8,
                    "drain_ticks": 300},
    "dlrm.ingest": {"warm_shards": 1},
}
# a tiny cell compares few packets: more of them near the DPI threshold,
# and shards small enough to finish in the window
TINY_CFG = {
    "secure.linerate": {"payload": {"edge_pkt_share": 0.25}},
    "secure.flow": {"payload": {"edge_pkt_share": 0.5}},
    "dlrm.ingest": {"ingest": {"records_per_step": 500}},
}


# dlrm.ingest is held out of BENCHMARK.json until the program's streamed
# ingest lands each shard's own records at MLPerf's shard size; its driver
# is still checked here
HELD = [{"name": "dlrm.ingest", "config": "dlrm_criteo_ingest",
         "traffic": "ingest", "chips": 1, "why": "held back"}]


@pytest.fixture(autouse=True)
def held_cells(monkeypatch):
    bm = harness.benchmark()
    bm["workloads"] += HELD
    monkeypatch.setattr(harness, "benchmark", lambda: bm)


def _run(cell, control=None):
    return run.run_cell(cell, 2 ** 31 + 99, 1.0, False, control=control,
                        overrides=TINY[cell], cfg_overrides=TINY_CFG.get(cell),
                        t_start=0.0)


@pytest.mark.parametrize("cell", sorted(TINY))
def test_sound_run_is_correct(cell):
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert out["metrics"]["goodput_gbps"]["value"] > 0


def _stuck_engine(real):
    """The RX step computes its outputs but hands back the tables it was
    given: the carried state never advances."""
    def engine(tables, batch):
        keep = pipe.clone_tables(tables)
        _, res = real(tables, batch)
        return keep, res
    return engine


@pytest.mark.parametrize("cell", ["secure.linerate", "secure.flow"])
def test_state_left_unchanged_fails(cell, monkeypatch):
    stuck = _stuck_engine(pipe.rx_pipeline_batched)
    monkeypatch.setattr(pipe, "rx_pipeline_batched", stuck)
    monkeypatch.setitem(pipe.RX_ENGINES, "batched", stuck)
    assert not _run(cell)["correct"]


def _broken_chain(kind):
    real = services.ServiceChain._process

    def process(self, payload, plen):
        out, flags = real(self, payload, plen)
        n = payload.shape[0]
        if kind == "half":        # the second half of the rows skipped
            out = out.at[n // 2:].set(payload[n // 2:])
            flags = flags.at[n // 2:].set(0)
        elif kind == "altered":   # one byte of each call altered
            out = out.at[0, 5].add(jnp.uint8(1))
        else:                     # flags one row off, their count kept
            flags = jnp.roll(flags, 1)
        return out, flags
    return process


@pytest.mark.parametrize("kind", ["half", "altered"])
@pytest.mark.parametrize("cell", ["secure.linerate", "dlrm.linerate",
                                  "secure.flow"])
def test_broken_chain_fails(cell, kind, monkeypatch):
    monkeypatch.setattr(services.ServiceChain, "_process",
                        _broken_chain(kind))
    assert not _run(cell)["correct"]


@pytest.mark.parametrize("cell", ["secure.linerate", "secure.flow"])
def test_flags_on_wrong_rows_fail(cell, monkeypatch):
    monkeypatch.setattr(services.ServiceChain, "_process",
                        _broken_chain("shifted"))
    out = _run(cell)
    assert out["checks"]["dpi_flag_diff"]["value"] > 0, out["checks"]


@pytest.mark.parametrize("kind", ["half", "altered"])
def test_broken_ingest_fails(kind, monkeypatch):
    real = ingest_mod.make_dlrm_tile_decoder

    def make(*a, **kw):
        dec = real(*a, **kw)

        def decode(tile):
            out = dec(tile)
            n = out["sparse"].shape[0]
            if kind == "half":
                return {k: v.at[n // 2:].set(0) for k, v in out.items()}
            return dict(out, sparse=out["sparse"].at[0, 0].add(1))
        return decode

    monkeypatch.setattr(ingest_mod, "make_dlrm_tile_decoder", make)
    assert not _run("dlrm.ingest")["correct"]


@pytest.mark.parametrize("cell", sorted(TINY))
def test_control_fails(cell):
    out = _run(cell, "bf16")
    assert not out["correct"], out["checks"]
