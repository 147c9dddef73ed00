"""cifar10_resnet18.round at a size the CPU holds: a sound run passes its checks, and
the run comes out not correct with each fault the cell can have planted
underneath the harness, and with the control in the program's place."""
import pytest

from bench.tests import tiny

CELL = "cifar10_resnet18.round"
FAULTS = ['fault_half_batch', 'fault_stale_mind', 'fault_repeated_pick']


def test_sound_run_is_correct():
    out = tiny.run(CELL)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_is_not_correct(monkeypatch, fault):
    getattr(tiny, fault)(monkeypatch)
    out = tiny.run(CELL)
    assert not out["correct"], out["checks"]


def test_control_is_not_correct():
    out = tiny.run(CELL, control=True)
    assert not out["correct"], out["checks"]
