"""Benchmark orchestrator: one bench per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full]

Prints ``name,value,derived`` CSV.  --full uses paper-scale parameters
(slower); the default sizes finish in a few minutes on CPU.  A suite
that raises prints an ``ERROR`` row and its traceback, the remaining
suites still run, and the command exits non-zero.
"""
from __future__ import annotations

import sys
import time
import traceback


def main() -> None:
    full = "--full" in sys.argv
    from benchmarks import (bench_discovery, bench_envelope,
                            bench_inference_scaling, bench_task_overhead,
                            bench_value_server)

    suites = [
        ("task_overhead (Fig 5)", bench_task_overhead.run,
         {} if full else {"T": 60}),
        ("value_server (Fig 6)", bench_value_server.run,
         {} if full else {"T": 40, "sizes": (1 << 10, 1 << 17, 1 << 20,
                                             10 << 20)}),
        ("inference_scaling (Figs 7/8)", bench_inference_scaling.run,
         {} if full else {"T": 30, "workers": (1, 4, 8)}),
        ("envelope (Fig 9)", bench_envelope.run,
         {} if full else {"T_per_worker": 4}),
        ("discovery (Fig 4)", bench_discovery.run,
         {} if full else {"num_molecules": 600, "qc_budget": 48}),
    ]
    print("name,value,derived")
    failed = []
    for title, fn, kw in suites:
        t0 = time.perf_counter()
        try:
            rows = fn(**kw)
        except Exception as e:                     # noqa: BLE001
            traceback.print_exc()
            print(f"{title},ERROR,{e!r}")
            failed.append(title)
            continue
        for name, val, extra in rows:
            if isinstance(val, float):
                print(f"{name},{val:.4f},{extra}")
            else:
                print(f"{name},{val},{extra}")
        print(f"# {title} done in {time.perf_counter()-t0:.1f}s")
    if failed:
        sys.exit(f"{len(failed)} suite(s) failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
