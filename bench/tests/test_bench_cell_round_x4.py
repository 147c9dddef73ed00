"""cifar10_resnet18_x4.round at a size the CPU holds, on four forced host
devices (a subprocess: the device count is fixed when JAX starts): a sound
run passes its checks with the pick loop spanning the four devices, and
the run comes out not correct with a planted fault and with the control
in the program's place."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELL = "cifar10_resnet18_x4.round"
PROGRAM = r'''
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path[:0] = ["src", "."]
import time
import jax
from bench import run as bench_run
from bench.tests import tiny
from repro.common import telemetry


class Patch:
    def setattr(self, obj, name, value):
        setattr(obj, name, value)


out = {}
for name in ("sound", "fault_repeated_pick", "control"):
    from repro.core import selection
    real = selection.replica_greedy_select
    if name.startswith("fault"):
        getattr(tiny, name)(Patch())
    c0 = telemetry.snapshot()["counters"].get("select.loop_devices", 0)
    q0 = telemetry.snapshot()["spans"].get("alaas.query", {}).get("count", 0)
    res = bench_run.run_cell(tiny.spec(CELL), 5, 1.0, 0, jax.devices()[:4],
                             tiny.PEAKS, t_start=time.perf_counter(),
                             control=name == "control")
    selection.replica_greedy_select = real
    snap = telemetry.snapshot()
    res["loop_devices"] = snap["counters"].get("select.loop_devices", 0) - c0
    res["queries"] = snap["spans"].get("alaas.query", {}).get("count", 0) - q0
    out[name] = res
print("RESULT " + json.dumps(out))
'''.replace("CELL", repr(CELL))


@pytest.fixture(scope="module")
def runs():
    r = subprocess.run([sys.executable, "-c", PROGRAM], capture_output=True,
                       text=True, cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    line = next(ln for ln in r.stdout.splitlines()
                if ln.startswith("RESULT "))
    return json.loads(line[len("RESULT "):])


def test_sound_run_is_correct_across_four_devices(runs):
    out = runs["sound"]
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["count"] == 4
    # every k-center query of the run spans the four devices
    assert out["queries"] > 0
    assert out["loop_devices"] == 4 * out["queries"]


@pytest.mark.parametrize("name", ["fault_repeated_pick", "control"])
def test_fault_and_control_are_not_correct(runs, name):
    assert not runs[name]["correct"], runs[name]["checks"]
