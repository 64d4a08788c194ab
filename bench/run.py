"""Run one benchmark cell on the accelerator this process is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Prints progress and each compared number with its limit on standard
error, and as its last line of standard output one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``, and
``checks`` last.  Exits non-zero, printing no result, where JAX finds no
TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the benchmark, the program under test, and one fixed compile cache in
# the checkout
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache"))
# the TPU runtime logs under /tmp unless pointed elsewhere
os.environ.setdefault("TPU_LOG_DIR", tempfile.gettempdir())

from bench.harness import log  # noqa: E402


def p95(values):
    if len(values) < 2:
        return values[0] if values else None
    return statistics.quantiles(values, n=20, method="inclusive")[-1]


def end_to_end(win: dict, setup_s: float) -> dict:
    lat = win["latencies_s"]
    out = {"goodput_gbps": {"value": win["payload_bytes"] * 8 / 1e9
                            / win["seconds"], "unit": "Gbit/s"},
           "setup_s": {"value": setup_s, "unit": "s"}}
    if lat:
        out["p95_ms"] = {"value": p95(lat) * 1e3, "unit": "ms"}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             control=None, overrides=None, cfg_overrides=None,
             t_start=None) -> dict:
    """Everything after the look for a chip: set-up, the window, the
    check against the reference, the metrics.  ``control`` puts the
    reference in the chain's place (see ``deploy.control_chain``);
    ``overrides`` replaces traffic parameters and ``cfg_overrides``
    entries of the configuration's sections (tests run tiny cells)."""
    import jax
    from bench import harness
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    t_start = T_START if t_start is None else t_start
    _, cfg, traffic, bm = harness.resolve(workload)
    traffic = dict(traffic, **(overrides or {}))
    for section, values in (cfg_overrides or {}).items():
        cfg = dict(cfg, **{section: dict(cfg[section], **values)})
    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev)}
    clock = harness.CompileClock()
    log(f"{workload}: JAX on {device['kind']} at "
        f"{time.perf_counter() - t_start:.2f} s")
    cell = harness.driver(traffic).Cell(cfg, traffic, seed, control)
    cell.setup()
    setup_s = time.perf_counter() - t_start
    log(f"{workload}: set-up {setup_s:.2f} s (compile {clock.seconds:.2f} s,"
        f" {clock.lowerings} programs lowered, persistent cache "
        f"{clock.cache_hits} hits / {clock.cache_misses} misses)")

    low0 = clock.lowerings
    tr = None
    if trace:
        with harness.count_transfers() as xfer, harness.profiled() as prof:
            win = cell.window(min(seconds, traffic["trace_seconds"]))
        tr = prof.trace
        log(f"{workload}: trace read, {len(tr.ops) if tr else 0} device "
            f"ops in the window")
    else:
        xfer = None
        win = cell.window(seconds)
    counters = dict(win["counters"], lowerings=clock.lowerings - low0)
    if xfer is not None:
        counters.update(d2h=xfer.d2h)
    log(f"{workload}: window {win['seconds']} s, "
        f"{len(win['latencies_s'])} requests done, counters {counters}")
    stats = dev[0].memory_stats() or {}
    device["memory_peak_bytes"] = stats.get("peak_bytes_in_use", 0)

    cell.release()
    t_check = time.perf_counter()
    checks = cell.check()
    log(f"{workload}: check took {time.perf_counter() - t_check:.2f} s")
    correct = all(v <= lim for _, v, lim in checks)
    if trace:
        device["busy_s"] = tr.busy_s if tr else 0.0
        device["window_s"] = tr.window_s if tr else 0.0
        peak = harness.peaks(device["kind"]) if tr else {}
        ctx = harness.layer_context(tr, counters, cell.calls, peak)
        metrics = harness.read_layer_metrics(workload, bm, ctx)
    else:
        metrics = end_to_end(win, setup_s)
    out = {"correct": correct, "attempted": win["attempted"],
           "failed": win["failed"], "metrics": metrics, "device": device}
    if tr is not None:
        out["breakdown"] = {"device_ops": tr.top_ops(),
                            "idle_gaps": tr.idle_gaps()}
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    try:
        cell_entry = harness.resolve(args.workload)[0]
        import repro  # noqa: F401  (the program under test)
    except (KeyError, OSError, ImportError) as e:
        log(f"cannot run {args.workload!r}: {e}")
        return 2
    import jax
    dev = jax.devices()
    if dev[0].platform != "tpu" or len(dev) < cell_entry["chips"]:
        log(f"needs {cell_entry['chips']} TPU chip(s); JAX found "
            f"{len(dev)} {dev[0].platform} device(s)")
        return 3
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    for name, c in out["checks"].items():
        log(f"check {name} = {c['value']} (limit {c['limit']})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
