"""Benchmark aggregator — one harness per paper table/figure.
Prints ``name,us_per_call,derived`` CSV (plus section markers)."""
from __future__ import annotations

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
    return failed


if __name__ == "__main__":
    sys.exit(1 if main() else 0)
