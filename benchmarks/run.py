"""Benchmark harness entry point — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--skip-slow]
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--skip-slow", action="store_true",
                    help="skip the 480-job table and xi calibration")
    args = ap.parse_args(argv)

    from . import (fig4_fig5_jct_queue, fig6a_load, fig6b_xi,
                   replay_validation, sim_throughput,
                   table2_physical, table3_240, table4_480, xi_calibration)

    stages = [
        ("table2_physical (Table II)", table2_physical.run),
        ("table3_240 (Table III)", table3_240.run),
        ("fig4_fig5 (JCT dists / queueing)", fig4_fig5_jct_queue.run),
        ("fig6a_load (load sweep)", fig6a_load.run),
        ("fig6b_xi (xi sweep)", fig6b_xi.run),
    ]
    if not args.skip_slow:
        stages.insert(2, ("table4_480 (Table IV)", table4_480.run))
        stages.append(("xi_calibration (calibration pipeline)",
                       xi_calibration.run))
        stages.append(("replay_validation (closed-loop executor replay)",
                       replay_validation.run))
        stages.append(("sim_throughput (engine before/after)",
                       sim_throughput.run))

    failures = 0
    for name, fn in stages:
        print(f"\n=== {name} ===", flush=True)
        t0 = time.time()
        try:
            fn()
            print(f"--- {name}: {time.time() - t0:.1f}s")
        except FileNotFoundError as e:
            print(f"--- {name}: SKIPPED (missing artifact: {e})")
        except Exception as e:
            failures += 1
            import traceback
            traceback.print_exc()
            print(f"--- {name}: FAILED ({e})")
    print(f"\nbenchmarks complete, {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
