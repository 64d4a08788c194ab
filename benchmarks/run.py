"""Benchmark aggregator — one harness per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (plus section markers).

The roofline sweep (40 cells x 2 meshes) is NOT run from here (it takes
~40 min of fresh-process compiles); run it via
``python -m benchmarks.roofline`` — results land in results/*.jsonl and
EXPERIMENTS.md.  A summary of the latest sweep is echoed below if
present."""
from __future__ import annotations

import json
import os
import sys
import traceback


def _section(name):
    print(f"# --- {name} ---")


def main() -> int:
    """Run every harness; returns the number that raised."""
    from benchmarks import (fig4_transport, fig5_breakdown, fig6_multiqp,
                            fig7_aes, fig8_dpi, fig10_dlrm, fig11_allreduce,
                            table2_resources)
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    failed = 0
    print("name,us_per_call,derived")
    for mod in (fig4_transport, fig5_breakdown, fig6_multiqp, fig7_aes,
                fig8_dpi, table2_resources, fig10_dlrm, fig11_allreduce):
        _section(mod.__name__)
        try:
            mod.main()
        except Exception as e:           # keep the suite running
            failed += 1
            print(f"{mod.__name__},nan,ERROR:{type(e).__name__}:{e}")
            traceback.print_exc()

    # echo the roofline sweep summary if a baseline file exists
    path = os.path.join(os.path.dirname(__file__), "..", "results",
                        "roofline_baseline2.jsonl")
    if os.path.exists(path):
        _section("roofline (latest sweep summary)")
        n_ok = n_skip = n_fail = 0
        with open(path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                except json.JSONDecodeError:
                    continue
                s = r.get("status")
                n_ok += s == "ok"
                n_skip += s == "skip"
                n_fail += s == "FAIL"
        print(f"roofline_cells,0.0,ok={n_ok};skip={n_skip};fail={n_fail}")
    return failed


if __name__ == "__main__":
    sys.exit(1 if main() else 0)
