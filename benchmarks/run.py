"""Benchmark runner: one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (benchmarks/common.csv_line).
Roofline reporting (from dry-run artifacts) appended when artifacts exist.

``--e2e`` runs only the streaming hot-path benchmark (BENCH_e2e.json);
``--quick`` shrinks it to the tier-1-safe smoke invocation
(``make bench-smoke``). ``--scenario`` adds the dirty-stream robustness
point (gap + glitch spurious suppression) to BENCH_stream.json, and
``--serve`` the concurrent serving-tier benchmark (BENCH_serve.json:
QPS / latency split / shed rate under closed-loop clients).
"""
from __future__ import annotations

import argparse
import os
import sys
import time
import traceback

from repro.compile_cache import enable_compile_cache


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--e2e", action="store_true",
                    help="run only the end-to-end hot-path benchmark")
    ap.add_argument("--quick", action="store_true",
                    help="smoke-size the e2e benchmark")
    ap.add_argument("--scenario", action="store_true",
                    help="also record the dirty-stream robustness point "
                         "(BENCH_stream.json scenario key)")
    ap.add_argument("--serve", action="store_true",
                    help="also run the concurrent serving-tier benchmark "
                         "(BENCH_serve.json)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    t0 = time.time()
    if args.e2e or args.serve:
        if args.e2e:
            from benchmarks import bench_e2e
            bench_e2e.main(["--quick"] if args.quick else [])
        if args.scenario:
            from benchmarks import bench_stream
            bench_stream.main(["--scenario-only"])
        if args.serve:
            from benchmarks import bench_serve
            bench_serve.main(["--quick"] if args.quick else [])
        print(f"# total bench time {time.time()-t0:.0f}s")
        return

    from benchmarks import (bench_alternatives, bench_bandpass, bench_e2e,
                            bench_factor_analysis, bench_lsh_params,
                            bench_mad_sampling, bench_occurrence_filter,
                            bench_partitions, bench_scaling, bench_serve,
                            bench_stream)
    # bench_stream / bench_e2e parse argv — hand them an explicit list so
    # the runner's own flags (--quick) never leak in via sys.argv; the
    # remaining mains take no arguments
    suites = [
        ("factor_analysis(Fig10/Tab5)", lambda: bench_factor_analysis.main()),
        ("occurrence_filter(Tab1)", lambda: bench_occurrence_filter.main()),
        ("bandpass(Fig11)", lambda: bench_bandpass.main()),
        ("lsh_params(Fig12/Fig6)", lambda: bench_lsh_params.main()),
        ("partitions(Fig13)", lambda: bench_partitions.main()),
        ("scaling(Fig14)", lambda: bench_scaling.main()),
        ("mad_sampling(Tab6)", lambda: bench_mad_sampling.main()),
        ("alternatives(Tab2)", lambda: bench_alternatives.main()),
        ("stream(incremental_index)",
         lambda: bench_stream.main(["--scenario"])),
        ("stream_e2e(hot_path)",
         lambda: bench_e2e.main(["--quick"] if args.quick else [])),
        ("serve(query_tier)",
         lambda: bench_serve.main(["--quick"] if args.quick else [])),
    ]
    failures = 0
    for name, fn in suites:
        print(f"# === {name} ===", flush=True)
        try:
            fn()
        except Exception:
            failures += 1
            print(f"# {name} FAILED:\n{traceback.format_exc()[-1500:]}")
    if os.path.isdir("artifacts/dryrun"):
        print("# === roofline (from dry-run artifacts) ===")
        try:
            from benchmarks import roofline
            roofline.main("artifacts/dryrun")
        except Exception:
            print(f"# roofline FAILED:\n{traceback.format_exc()[-800:]}")
    print(f"# total bench time {time.time()-t0:.0f}s, "
          f"{failures} suite failures")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
