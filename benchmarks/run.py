"""Run every paper-table benchmark (small default sizes; CPU-feasible).

  PYTHONPATH=src python -m benchmarks.run [--full] [--json PATH]

``--json`` writes machine-readable per-suite results (wall seconds,
status, and each suite's CSV rows) so benchmark trajectories can be
tracked across commits instead of scraping stdout.
"""

import argparse
import json
import sys
import time
import traceback


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow on CPU)")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="write per-suite timings/rows as JSON")
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (chaos_recovery, fig13_scaling, overlap_gain,
                   table2_saxpy, table3_particle, table4_flux,
                   table5_eikonal, table_layout, table_tuned)
    jobs = [
        ("Chaos recovery (injected faults: replay cost + latency)",
         lambda: chaos_recovery.main(
             num_steps=40 if not args.full else 200)),
        ("Async overlap gain (event-driven host callbacks)",
         overlap_gain.main),
        ("Tuned vs heuristic (measured autotuner)", table_tuned.main),
        ("Layout table (AoS/SoA/AoSoA)", lambda: table_layout.main(
            saxpy_n=1 << 18 if not args.full else 1 << 22,
            particle_n=65_536 if not args.full else 1_048_576,
            flux_shape=(128, 128) if not args.full else (1024, 1024))),
        ("Table 2 (SAXPY)", lambda: table2_saxpy.main(
            sizes=(1 << 18, 1 << 20) if not args.full
            else (1 << 20, 10 << 20, 100 << 20))),
        ("Table 3 (particle)", lambda: table3_particle.main(
            sizes=(65_536, 262_144) if not args.full
            else (100_000, 1_000_000, 10_000_000))),
        ("Table 4 (FORCE flux)", lambda: table4_flux.main(
            sizes=((128, 128),) if not args.full
            else ((1024, 1024), (2048, 2048)))),
        ("Table 5 (eikonal FIM)", lambda: table5_eikonal.main(
            sizes=(128,) if not args.full else (1024, 2048))),
        ("Fig 13 (Euler scaling + 2D overlap)", fig13_scaling.main),
    ]
    failed = 0
    results = []
    for name, fn in jobs:
        print(f"\n=== {name} ===")
        t0 = time.perf_counter()
        rows, err = None, None
        try:
            rows = fn()
        except Exception:
            failed += 1
            err = traceback.format_exc()
            traceback.print_exc()
        results.append({
            "suite": name,
            "ok": err is None,
            "seconds": round(time.perf_counter() - t0, 3),
            "rows": rows if isinstance(rows, (list, dict)) else None,
            "error": err,
        })
    print(f"\n[benchmarks] {len(jobs) - failed}/{len(jobs)} suites OK")
    if args.json:
        payload = {"full": args.full, "unix_time": time.time(),
                   "suites": results}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"[benchmarks] wrote {args.json}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
