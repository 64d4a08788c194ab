"""What every cell shares: the compile clock, the device-to-host transfer
counter, the profiler window and its reduction to device busy time,
kernel time and idle gaps, and the per-layer metric readers.

The compile clock copies ``chip_smoke._CompileClock`` (a
``jax.monitoring`` listener); the transfer counter copies
``repro.analysis.census.sync_census``.
"""
from __future__ import annotations

import contextlib
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import types
from typing import Dict, List, Optional, Tuple

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT, "BENCHMARK.json")


def load_module(path: str, name: str):
    """Import one file by path (metric files are named after metrics,
    whose names hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(workload: str) -> Tuple[dict, dict, dict, dict]:
    """Cell name -> (cell entry, configuration, traffic mix, benchmark),
    each found by the names in ``BENCHMARK.json``."""
    bm = benchmark()
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    cfg = load_json(ROOT, cfg_entry["file"])
    traffic = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    return cell, cfg, traffic, bm


def driver(traffic: dict):
    return load_module(os.path.join(BENCH, "drivers",
                                    traffic["driver"] + ".py"),
                       "bench_driver_" + traffic["driver"])


def metric_reader(name: str):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"),
                       "bench_metric_" + name.replace(".", "_"))


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH, "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json")
    return table[device_kind]


def reservoir_keep(sample: list, item, i: int, k: int, rng) -> None:
    """Keep ``item`` (the ``i``-th of a stream) in a uniform sample of
    ``k``, drawn from ``rng``."""
    if i < k:
        sample.append(item)
    else:
        j = int(rng.integers(0, i + 1))
        if j < k:
            sample[j] = item


# ------------------------------------------------------------- counters

class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, how many
    programs it lowered (a lowering inside the window is a new shape),
    and how many compiles the persistent cache answered or missed."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.lowerings = self.cache_hits = self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_kw):
        if event in self.EVENTS:
            self.seconds += duration
        if event == self.LOWER:
            self.lowerings += 1

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


@contextlib.contextmanager
def count_transfers():
    """Count device->host transfers (``np.asarray`` of a device array)
    while the body runs.  Counting only."""
    import jax
    c = types.SimpleNamespace(d2h=0)
    np_asarray = np.asarray

    def np_asarray_c(a, *args, **kw):
        if isinstance(a, jax.Array):
            c.d2h += 1
        return np_asarray(a, *args, **kw)

    np.asarray = np_asarray_c
    try:
        yield c
    finally:
        np.asarray = np_asarray


# ------------------------------------------------------------ the trace

# the benchmark's own host spans (jax.profiler.TraceAnnotation) start so
SPAN = "bench."
WINDOW_SPAN = SPAN + "window"


@contextlib.contextmanager
def profiled():
    """Profile the body with ``jax.profiler`` into a temporary directory
    and yield a holder whose ``trace`` is the reduced ``Trace``."""
    import jax
    holder = types.SimpleNamespace(trace=None)
    d = tempfile.mkdtemp(prefix="bench_trace_")
    # no Python function tracer: it records every call of the host path
    # and the trace of one tick-driven window grows past what can be read
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    try:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                yield holder
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                          recursive=True)
        if files:
            holder.trace = Trace.from_file(files[0])
    finally:
        shutil.rmtree(d, ignore_errors=True)


Event = Tuple[str, int, int]           # name, start ns, end ns


class Trace:
    """Device op events, XLA module events and host spans of one traced
    window, all on the profiler's clock."""

    def __init__(self, ops: List[Event], modules: List[Event],
                 spans: List[Event], window: Tuple[int, int]):
        self.window = window
        lo, hi = window
        clip = lambda evs: [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                            if e > lo and s < hi]
        self.ops, self.modules, self.spans = clip(ops), clip(modules), \
            clip(spans)

    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        ops, modules, spans = [], [], []
        for plane in pd.planes:
            device = plane.name.startswith("/device:") and \
                "TPU" in plane.name
            host = plane.name.startswith("/host:")
            for line in plane.lines:
                evs = [(e.name, int(e.start_ns), int(e.end_ns))
                       for e in line.events]
                if device and line.name == "XLA Ops":
                    ops += evs
                elif device and line.name == "XLA Modules":
                    modules += evs
                elif host:
                    spans += [e for e in evs if e[0].startswith(SPAN)]
        win = [s for s in spans if s[0] == WINDOW_SPAN]
        window = (win[0][1], win[0][2]) if win else \
            (min(e[1] for e in ops + spans), max(e[2] for e in ops + spans))
        return cls(ops, modules, spans, window)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_intervals(self) -> List[Tuple[int, int]]:
        merged: List[List[int]] = []
        for _, s, e in sorted(self.ops, key=lambda x: x[1]):
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return [(s, e) for s, e in merged]

    @property
    def busy_s(self) -> float:
        return sum(e - s for s, e in self.busy_intervals()) * 1e-9

    def op_seconds(self, prefixes) -> Tuple[float, int]:
        """Total device seconds and count of ops whose name (the HLO
        instruction, ``%name.N = ...``) starts with any of ``prefixes``;
        ops that merely take such a result as an operand do not count."""
        prefixes = tuple(prefixes)
        hit = [e - s for n, s, e in self.ops if n.startswith(prefixes)]
        return sum(hit) * 1e-9, len(hit)

    def module_seconds(self, patterns) -> Tuple[float, int]:
        hit = [e - s for n, s, e in self.modules
               if any(p in n for p in patterns)]
        return sum(hit) * 1e-9, len(hit)

    def top_ops(self, k: int = 10) -> List[list]:
        """The ``k`` ops that took most device time, summed by HLO
        instruction name (``%aes_ecb_pallas.1``, not its whole text)."""
        tot: Dict[str, int] = {}
        for n, s, e in self.ops:
            n = n.split(" = ", 1)[0]
            tot[n] = tot.get(n, 0) + e - s
        return [[n, v * 1e-9] for n, v in
                sorted(tot.items(), key=lambda x: -x[1])[:k]]

    def idle_gaps(self, k: int = 10) -> List[list]:
        """Idle time between device ops, attributed to the innermost of
        the benchmark's host spans open at each gap's midpoint (spans of
        one thread nest, so the one opened last), summed by span name."""
        lo, hi = self.window
        edges = [lo]
        for s, e in self.busy_intervals():
            edges += [s, e]
        edges.append(hi)
        gaps = [(g0, g1) for g0, g1 in zip(edges[::2], edges[1::2])
                if g1 > g0]
        # sweep span openings, closings and gap midpoints in time order
        points = []
        for i, (n, s, e) in enumerate(self.spans):
            if n != WINDOW_SPAN:
                points += [(s, 0, i), (e, 2, i)]
        points += [((g0 + g1) // 2, 1, j) for j, (g0, g1) in enumerate(gaps)]
        open_spans: Dict[int, int] = {}
        tot: Dict[str, int] = {}
        for t, kind, i in sorted(points):
            if kind == 0:
                open_spans[i] = self.spans[i][1]
            elif kind == 2:
                open_spans.pop(i, None)
            else:
                g0, g1 = gaps[i]
                name = self.spans[max(open_spans, key=open_spans.get)][0] \
                    if open_spans else "no span"
                tot[name] = tot.get(name, 0) + g1 - g0
        return [[n, v * 1e-9] for n, v in
                sorted(tot.items(), key=lambda x: -x[1])[:k]]


def layer_context(trace: Trace, counters: dict, calls: dict,
                  peak: dict) -> types.SimpleNamespace:
    """What a per-layer metric reader reads."""
    return types.SimpleNamespace(trace=trace, counters=counters, calls=calls,
                                 peak=peak)


def read_layer_metrics(cell_name: str, bm: dict, ctx) -> Dict[str, dict]:
    out = {}
    for m in bm["per_layer"]:
        if "workloads" in m and cell_name not in m["workloads"]:
            continue
        value = metric_reader(m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def roofline_share(seconds: float, flops: float, nbytes: float,
                   peak: dict) -> Optional[float]:
    """Per cent of the chip's roofline: the least time the work could
    take (the larger of flops over peak FLOP/s and bytes over peak
    bandwidth) over the time it took.  None where nothing was timed."""
    if seconds <= 0 or (flops <= 0 and nbytes <= 0):
        return None
    least = max(flops / peak["bf16_flops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds
