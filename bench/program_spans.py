"""The program's own spans and counters in one traced window of a cell.

    python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s>

Sets the cell up as ``run.py`` does, then, for a cell driven tick by
tick (``secure.flow``), steps it for ``--seconds`` untraced and again
for ``--seconds`` under ``jax.profiler``; any other cell runs one traced
window.  Prints one JSON object as its last line of standard output:

- ``ticks_per_s``: simulated ticks a second, untraced and traced (the
  cost of recording the spans is their ratio; a program without spans
  gives the profiler's own cost);
- ``host_us_per_tick``: each ``balboa.*`` span's self time (its duration
  less what its ``balboa.*`` children on the same thread cover) per tick;
- ``covered``: the share of the benchmark's ``bench.step_network`` and
  ``bench.rdma_write`` spans that ``balboa.*`` spans cover;
- ``idle_gaps_program``: device idle time put down to the innermost
  ``balboa.*`` span open at each gap's midpoint, and the share of all
  idle time that lands on one;
- ``host_stats``: the nodes' ``HostPathStats`` over the traced window,
  per tick, beside ``d2h``, what a patched ``np.asarray`` counted;
- ``device``: traced busy seconds split into the RX engine's programs,
  the service kernels and the rest of the chain's program (its glue),
  and what ran outside both, with the glue by service (the ops'
  ``jax.named_scope``) where the trace carries it.

Each part is None where the program or the trace does not have what it
reads.  No part of this feeds ``run.py``: it measures the program's
instrumentation beside the benchmark's own.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache"))
os.environ.setdefault("TPU_LOG_DIR", tempfile.gettempdir())

from bench import harness  # noqa: E402

PREFIX = "balboa."
OUTER = ("bench.step_network", "bench.rdma_write")
RX_MODULE = "rx_pipeline_batched"

Span = collections.namedtuple("Span", "name start end thread stats")


# ------------------------------------------------------------ reductions

def read_spans(profile, prefix: str = PREFIX) -> list:
    """Host events of a ``jax.profiler.ProfileData`` whose name starts
    with ``prefix``, with their thread (one line of a host plane) and
    stats."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out += [Span(e.name, int(e.start_ns), int(e.end_ns),
                         f"{plane.name}#{i}", dict(e.stats))
                    for e in line.events if e.name.startswith(prefix)]
    return out


def _by_thread(spans):
    threads = collections.defaultdict(list)
    for sp in spans:
        threads[sp.thread].append(sp)
    return threads


def self_seconds(spans) -> dict:
    """Per span name, the summed self time: each span's duration less
    what its direct children (spans of its thread inside it) cover."""
    tot = collections.Counter()

    def close(top):
        name, start, end, child = top
        tot[name] += end - start - child

    for ss in _by_thread(spans).values():
        stack = []                       # [name, start, end, child ns]
        for sp in sorted(ss, key=lambda x: (x.start, -x.end)):
            while stack and stack[-1][2] <= sp.start:
                close(stack.pop())
            if stack:
                stack[-1][3] += min(sp.end, stack[-1][2]) - sp.start
            stack.append([sp.name, sp.start, sp.end, 0])
        while stack:
            close(stack.pop())
    return {n: v * 1e-9 for n, v in tot.items()}


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def covered_share(outer, inner):
    """Share of the time inside the ``outer`` spans that ``inner`` spans
    of the same thread cover; None where there is no outer time."""
    inner_by = {t: _union((s.start, s.end) for s in ss)
                for t, ss in _by_thread(inner).items()}
    starts = {t: [s for s, _ in ivs] for t, ivs in inner_by.items()}
    total = hit = 0
    for sp in outer:
        total += sp.end - sp.start
        ivs = inner_by.get(sp.thread, [])
        i = max(bisect.bisect_right(starts.get(sp.thread, []), sp.start) - 1,
                0)
        while i < len(ivs) and ivs[i][0] < sp.end:
            hit += max(0, min(ivs[i][1], sp.end) - max(ivs[i][0], sp.start))
            i += 1
    return hit / total if total else None


def idle_gaps_program(trace, spans, k: int = 10):
    """The trace's device idle gaps put down to the innermost program
    span, as ``Trace.idle_gaps`` puts them down to the benchmark's."""
    return harness.Trace(trace.ops, trace.modules,
                         [(s.name, s.start, s.end) for s in spans],
                         trace.window).idle_gaps(k)


def _in_modules(trace, pattern):
    """Ops of ``trace`` inside intervals of modules named ``pattern``."""
    mods = sorted((s, e) for n, s, e in trace.modules if pattern in n)
    starts = [s for s, _ in mods]
    for op in trace.ops:
        i = bisect.bisect_right(starts, op[1]) - 1
        if i >= 0 and op[2] <= mods[i][1]:
            yield op


def device_split(trace):
    """Traced busy seconds: the RX engine's programs, the service kernels
    and glue inside the chain's program, and ops outside both."""
    from bench.metrics import chain_glue_ns_per_pkt as glue
    rx = trace.module_seconds((RX_MODULE,))[0]
    chain = list(_in_modules(trace, glue.MODULE))
    kern = sum(e - s for n, s, e in chain if glue.is_kernel(n)) * 1e-9
    chain_s = sum(e - s for _, s, e in chain) * 1e-9
    inside = set(chain) | set(_in_modules(trace, RX_MODULE))
    other = sum(op[2] - op[1] for op in trace.ops
                if op not in inside) * 1e-9
    parts = {"rx_engine": rx, "kernels": kern, "chain_glue": chain_s - kern,
             "other": other}
    return dict(parts, busy=trace.busy_s, parts_sum=sum(parts.values()))


def glue_by_scope(profile, trace):
    """Chain glue seconds by the service scope its ops carry in their
    stats (``jit(service_chain)/<service>/...``), or None where no op
    stat names one."""
    from bench.metrics import chain_glue_ns_per_pkt as glue
    mods = sorted((s, e) for n, s, e in trace.modules if glue.MODULE in n)
    starts = [s for s, _ in mods]
    tot = collections.Counter()
    tag = "service_chain)/"
    for plane in profile.planes:
        if not (plane.name.startswith("/device:") and "TPU" in plane.name):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                s, en = int(e.start_ns), int(e.end_ns)
                i = bisect.bisect_right(starts, s) - 1
                if i < 0 or en > mods[i][1] or glue.is_kernel(e.name):
                    continue
                scope = "(none)"
                for _, v in e.stats:
                    if isinstance(v, str) and tag in v:
                        scope = v.split(tag, 1)[1].split("/", 1)[0]
                        break
                tot[scope] += en - s
    if set(tot) <= {"(none)"}:
        return None
    return {k: v * 1e-9 for k, v in tot.most_common()}


# ------------------------------------------------------------ the window

@contextlib.contextmanager
def profiled(d: str):
    """Profile the body into directory ``d`` with ``harness.profiled``'s
    options and window span."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(harness.WINDOW_SPAN):
            yield
    finally:
        jax.profiler.stop_trace()


def _host_stats(cell):
    nodes = getattr(cell, "nodes", None) or []
    if not nodes or not hasattr(nodes[0], "host_stats"):
        return None
    tot = collections.Counter()
    for nd in nodes:
        tot.update(nd.host_stats.snapshot())
    return tot


def _steps(cell, seconds):
    """Step a tick-driven cell for ``seconds``, posting as it completes;
    returns the ticks stepped."""
    t0 = cell.net.now
    t_end = time.perf_counter() + seconds
    cell._run(lambda: time.perf_counter() < t_end)
    return cell.net.now - t0


def measure(workload: str, seed: int, seconds: float) -> dict:
    import jax
    from jax.profiler import ProfileData
    _, cfg, traffic, _ = harness.resolve(workload)
    cell = harness.driver(traffic).Cell(cfg, traffic, seed)
    cell.setup()
    out = {"workload": workload, "seed": seed, "seconds": seconds,
           "device": jax.devices()[0].device_kind,
           "setup_s": time.perf_counter() - T_START}
    ticked = hasattr(cell, "net")
    if ticked:
        t = time.perf_counter()
        ticks = _steps(cell, seconds)
        out["ticks_per_s"] = {"untraced": ticks / (time.perf_counter() - t)}
    hs0 = _host_stats(cell)
    with tempfile.TemporaryDirectory(prefix="bench_spans_") as d:
        with harness.count_transfers() as xfer, profiled(d):
            t = time.perf_counter()
            if ticked:
                ticks = _steps(cell, seconds)
            else:
                cell.window(seconds)
            wall = time.perf_counter() - t
        [path] = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                           recursive=True)
        trace = harness.Trace.from_file(path)
        profile = ProfileData.from_file(path)
    spans = read_spans(profile)
    outer = [s for s in read_spans(profile, "bench.") if s.name in OUTER]
    out["glue_by_scope"] = glue_by_scope(profile, trace)
    if ticked:
        out["ticks"] = ticks
        out["ticks_per_s"]["traced"] = ticks / wall
        out["host_wall_us_per_tick"] = wall * 1e6 / ticks
        per = {n[len(PREFIX):]: v * 1e6 / ticks
               for n, v in sorted(self_seconds(spans).items())}
        out["host_us_per_tick"] = dict(per, sum=sum(per.values())) \
            if per else None
        out["covered"] = covered_share(outer, spans) if spans else None
        hs1 = _host_stats(cell)
        out["host_stats"] = None if hs0 is None else {
            k: (hs1[k] - hs0[k]) / ticks for k in hs1}
        out["d2h_per_tick"] = xfer.d2h / ticks
    gaps = idle_gaps_program(trace, spans, k=20)
    idle = sum(v for _, v in gaps)
    out["idle_gaps_program"] = gaps
    out["idle_in_program_span"] = \
        sum(v for n, v in gaps if n != "no span") / idle if idle else None
    out["idle_gaps"] = trace.idle_gaps()
    out["window_s"] = trace.window_s
    out["device"] = {"kind": out["device"], **device_split(trace)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        harness.log("needs a TPU; JAX found "
                    f"{jax.devices()[0].platform}")
        return 3
    print(json.dumps(measure(args.workload, args.seed, args.seconds)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
