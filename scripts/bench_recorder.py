"""Run one benchmark cell with the program's span recorder on or off, to
measure what the recorder costs:

    python3 scripts/bench_recorder.py off --workload cifar10_resnet18.round \
        --seed 7 --seconds 51 --trace 0

The first argument is ``on`` or ``off`` (``repro.common.telemetry``'s
``set_enabled``); the rest are ``bench/run.py``'s. Both settings import
the recorder, and with it JAX, before ``bench/run.py`` starts its set-up
clock, so their ``setup_s`` compare with each other, not with a plain
``bench/run.py`` run.
"""
import runpy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main() -> None:
    mode, args = sys.argv[1], sys.argv[2:]
    if mode not in ("on", "off"):
        sys.exit(f"first argument must be 'on' or 'off', got {mode!r}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.common import telemetry
    telemetry.set_enabled(mode == "on")
    run = str(ROOT / "bench" / "run.py")
    sys.argv = [run] + args
    runpy.run_path(run, run_name="__main__")


if __name__ == "__main__":
    main()
