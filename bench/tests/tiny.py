"""Cells shrunk to a size the CPU test lane can hold: the cell's own
files (traffic mix, limits, metrics), a ResNet-18 of narrow stages on
16x16 images and a pool of a few hundred rows."""
from __future__ import annotations

import dataclasses
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]

CONFIG = {"stage_sizes": [2, 2, 2, 2], "widths": [8, 16, 32, 64],
          "image_hw": 16, "pool_rows": 480, "batch_size": 32}
MIX = {"al_rounds": {"warm_labels": 48, "budget": 24, "push_chunk": 96,
                     "warm_rounds": 3, "check_rows": 64},
       "bulk_push": {"tenants": 2, "chunk": 48, "bank_rows": 64,
                     "check_rows": 48}}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def spec(cell: str):
    from bench.harness import spec as spec_lib
    s = spec_lib.load(ROOT, cell)
    config = dict(s.config, **CONFIG)
    traffic = dict(s.traffic, **MIX[s.traffic["driver"]])
    return dataclasses.replace(s, config=config, traffic=traffic)


def run(cell: str, seed: int = 5, seconds: float = 1.0, trace: int = 0,
        control: bool = False):
    """The cell's run on the CPU: everything but the look for a chip
    (``control``: the scorer's reference one precision below in the
    program's place)."""
    import jax
    from bench import run as bench_run
    s = spec(cell)
    return bench_run.run_cell(s, seed, seconds, trace,
                              jax.devices()[:1], PEAKS,
                              t_start=time.perf_counter(), control=control)


# -------------------------------------------------- planted faults --
# Each breaks the timed path underneath the harness, where the answer is
# produced; a run with any of them must come out not correct.

def fault_half_batch(monkeypatch):
    """The forward computes half of each batch; the other half gets the
    mean of the computed rows."""
    from repro.service.backends import ResNetBackend
    real = ResNetBackend.features

    def half(self, batch):
        out = np.array(real(self, batch))
        h = max(len(out) // 2, 1)
        out[h:] = out[:h].mean(axis=0)
        return out

    monkeypatch.setattr(ResNetBackend, "features", half)


def fault_swapped_rows(monkeypatch):
    """An answer altered where it is produced: each pair of rows of a
    batch gets the other's features."""
    from repro.service.backends import ResNetBackend
    real = ResNetBackend.features

    def swapped(self, batch):
        out = np.array(real(self, batch))
        n = len(out) // 2 * 2
        out[0:n:2], out[1:n:2] = out[1:n:2].copy(), out[0:n:2].copy()
        return out

    monkeypatch.setattr(ResNetBackend, "features", swapped)


def fault_stale_mind(monkeypatch):
    """A k-center step that returns its state unchanged: the running
    min-distance comes back as it went in."""
    from repro.kernels.pairwise import ops
    real = ops.greedy_round

    def stale(x, mind, *args, **kwargs):
        _, nxt, score = real(x, mind, *args, **kwargs)
        return mind, nxt, score

    monkeypatch.setattr(ops, "greedy_round", stale)


def fault_repeated_pick(monkeypatch):
    """An answer altered where it is produced: the k-center loop's last
    pick repeats its first."""
    from repro.core import selection
    real = selection.replica_greedy_select

    def repeated(*args, **kwargs):
        sel = real(*args, **kwargs)
        sel[-1] = sel[0]
        return sel

    monkeypatch.setattr(selection, "replica_greedy_select", repeated)


def fault_unappended(monkeypatch):
    """A push that returns its state unchanged: acknowledged, never
    appended to the pool."""
    from repro.service.server import ALSession
    monkeypatch.setattr(ALSession, "_append_rows", lambda *a, **k: None)
