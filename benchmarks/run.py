"""Benchmark harness — one module per paper table/figure (deliverable d).

Prints ``name,us_per_call,derived`` CSV; ``--json PATH`` additionally
writes the parsed rows as JSON with a stable schema
(``{"schema": 1, "rows": [{"name", "us_per_call", "derived"}],
"failures": N}``). The repo commits a ``BENCH_table2.json`` snapshot of
``--only table2`` so the perf trajectory (prefilter rows-touched ratios,
delta-refresh speedups) is tracked across PRs, and CI regenerates +
uploads the same file as a workflow artifact, re-asserting the
incremental-artifact and prefilter sections from it
(scripts/assert_table2_*.py).

  PYTHONPATH=src python -m benchmarks.run [--only table2,fig4a,...]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import types

BENCHES = ["table2", "fig4a", "fig4b", "fig4b_micro", "fig4c", "fig5",
           "roofline"]


def main() -> None:
    from repro.common.device import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(BENCHES))
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the rows as JSON to PATH")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else set(BENCHES)

    from benchmarks import (fig4a_strategy_accuracy, fig4b_strategy_throughput,
                            fig4c_batch_size, fig5_pshea, roofline_bench,
                            table2_pipeline)

    mods = {
        "table2": table2_pipeline,
        "fig4a": fig4a_strategy_accuracy,
        "fig4b": fig4b_strategy_throughput,
        # fused-vs-unfused greedy selection: asserts one pool read/center
        "fig4b_micro": types.SimpleNamespace(
            run=fig4b_strategy_throughput.run_micro),
        "fig4c": fig4c_batch_size,
        "fig5": fig5_pshea,
        "roofline": roofline_bench,
    }
    print("name,us_per_call,derived")
    failures = 0
    records = []

    def emit(line: str):
        print(line, flush=True)
        name, us, derived = line.split(",", 2)
        records.append({"name": name, "us_per_call": float(us),
                        "derived": derived})

    for name in BENCHES:
        if name not in only:
            continue
        t0 = time.perf_counter()
        try:
            for line in mods[name].run():
                emit(line)
        except Exception as e:  # keep the harness going
            failures += 1
            emit(f"{name}/ERROR,0.0,{type(e).__name__}: {e}")
        emit(f"{name}/_wall,{(time.perf_counter()-t0)*1e6:.0f},done")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"schema": 1, "rows": records, "failures": failures},
                      f, indent=1)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
