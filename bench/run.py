"""Run one benchmark cell of the AL server on the chips of this machine.

    python3 bench/run.py --workload cifar10_resnet18.round --seed 7 \
        --seconds 51 --trace 0

The cell's configuration, traffic mix, chips, metrics and correctness
limits are found by its name (``BENCHMARK.json`` and the files under
``bench/``). The run sets up the server through its TCP entry, measures
for ``--seconds``, checks what the timed path produced against the plain
references (``bench/references/``, ``bench/harness/reference.py``), and
prints, as the last line
of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device`` and, when traced,
``breakdown``; ``checks`` comes last, each compared number with its
limit, and the same lines end standard error.

It exits non-zero without a result when JAX finds no TPU, fewer chips than
the cell needs, a device missing from ``bench/peaks.json``, or no program
beside the benchmark (``src/``).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def configure_jax(root: Path):
    """Persistent compile cache at a fixed path inside the checkout, every
    compile written to it (JAX skips compiles under a second by default,
    so a warm run would still compile those), and no size limit from the
    environment: an LRU limit evicts the programs a run loads first."""
    import jax
    cache = root / "bench" / ".cache" / "jax"
    cache.mkdir(parents=True, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)


def device_check(spec, root: Path):
    """(devices, peaks) or an error message: a TPU, enough chips, and a
    known device kind."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return None, f"no TPU: JAX found {devices[0].platform}"
    if len(devices) < spec.chips:
        return None, (f"{spec.name} needs {spec.chips} chips, JAX found "
                      f"{len(devices)}")
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    kind = devices[0].device_kind
    if kind not in peaks["devices"]:
        return None, f"device {kind!r} is not in bench/peaks.json"
    return devices, peaks["devices"][kind]


def result(run, devices, peaks) -> dict:
    from bench.harness import spec as spec_lib
    run.peaks = peaks
    if run.trace:
        metrics = spec_lib.read_metrics(run.spec.root, "layer_metrics",
                                        run.spec.per_layer, run)
    else:
        metrics = spec_lib.read_metrics(run.spec.root, "end_to_end",
                                        run.spec.end_to_end, run)
    dev = devices[0]
    out = {"correct": bool(run.correct), "attempted": int(run.attempted),
           "failed": int(run.failed), "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(run.devices),
                      "memory_peak_bytes": int(run.memory_peak_bytes)}}
    if run.trace:
        red = run.trace_reduction
        out["device"]["busy_s"] = red.busy_s
        out["device"]["window_s"] = red.window_s
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in red.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in red.idle_gaps[:10]]}
    out["checks"] = {name: {"value": value, "limit": limit}
                     for name, value, limit in run.checks}
    return out


def run_cell(spec, seed, seconds, trace, devices, peaks,
             t_start=None, control=False) -> dict:
    from bench.harness.runner import Run
    run = Run(spec, seed, seconds, trace,
              T_START if t_start is None else t_start, control=control)
    run.devices = devices
    run.execute()
    return result(run, devices, peaks)


def main(argv=None) -> int:
    args = _parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program beside the benchmark ({ROOT / 'src'})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from bench.harness import spec as spec_lib
    spec = spec_lib.load(ROOT, args.workload)
    configure_jax(ROOT)
    devices, peaks = device_check(spec, ROOT)
    if devices is None:
        print(f"bench: {peaks}", file=sys.stderr)
        return 1
    out = run_cell(spec, args.seed, args.seconds, args.trace,
                   devices[:spec.chips], peaks)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
