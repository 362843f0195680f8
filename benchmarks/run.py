"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. §Roofline rows read from the
dry-run artifacts in experiments/dryrun (run `python -m repro.launch.dryrun`
first for those; missing artifacts just skip that section). Every module
runs even when an earlier one fails; the exit code counts the failures.
"""
from __future__ import annotations

import sys
import traceback

from repro import compile_cache


def main() -> int:
    compile_cache.enable()
    from benchmarks import (
        fig2_disparity,
        fig3_overhead,
        fig7_speedup,
        fig8_memory_energy,
        fig9_accuracy,
        kernels_micro,
        na_dispatch,
        roofline,
        sgb_build,
    )

    print("name,us_per_call,derived", flush=True)
    failed = []
    for mod in (
        sgb_build,
        na_dispatch,
        fig2_disparity,
        fig3_overhead,
        fig7_speedup,
        fig8_memory_energy,
        fig9_accuracy,
        kernels_micro,
        roofline,
    ):
        try:
            mod.main()
        except Exception as e:  # keep the harness going; record the failure
            print(f"{mod.__name__},0.0,ERROR:{type(e).__name__}:{e}", flush=True)
            traceback.print_exc()
            failed.append(mod.__name__)
    if failed:
        print(f"FAILED: {', '.join(failed)}", file=sys.stderr, flush=True)
    return len(failed)


if __name__ == "__main__":
    sys.exit(main())
