"""Readings of the program and of the control at a cell's own size, on the
chip, through the harness's own run and check.

    python3 bench/control.py --workload cifar10_resnet18.round \
        --seeds 11,12,13 --control-seeds 21,22,23 --seconds 10

Each seed is one run of the cell (set-up, a window of ``--seconds``, the
check) in this process; ``--control-seeds`` run with the scorer's plain
reference one precision below the configuration's in the program's place
(``Run(control=True)``). Every run prints one JSON line: the seed, whether
it was the control, ``correct`` and the numbers compared with their
limits. The limits in ``bench/limits/`` lie between the program's largest
reading and the control's smallest. The benchmark's runs never execute
this file.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench import run as bench_run
    from bench.harness import spec as spec_lib
    spec = spec_lib.load(ROOT, args.workload)
    bench_run.configure_jax(ROOT)
    devices, peaks = bench_run.device_check(spec, ROOT)
    if devices is None:
        print(f"control: {peaks}", file=sys.stderr)
        return 1
    runs = ([(s, False) for s in _seeds(args.seeds)]
            + [(s, True) for s in _seeds(args.control_seeds)])
    for seed, control in runs:
        t0 = time.perf_counter()
        out = bench_run.run_cell(spec, seed, args.seconds, 0,
                                 devices[:spec.chips], peaks, t_start=t0,
                                 control=control)
        print(json.dumps({"workload": spec.name, "seed": seed,
                          "control": control, "correct": out["correct"],
                          "attempted": out["attempted"],
                          "seconds": time.perf_counter() - t0,
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
