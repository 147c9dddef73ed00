"""One tenant's closed loop of AL rounds on a pushed pool.

Mix keys: ``warm_labels``, ``budget``, ``strategy``, ``push_chunk``,
``warm_rounds``, ``check_rows`` and optionally ``trace_s``.

Set-up pushes the seeded pool (``pool_rows`` of the configuration) with
asynchronous ``push_chunk``-row pushes and a ``flush``, warms the shapes
of ``warm_rounds`` window rounds (``warm_shapes``), labels
``warm_labels`` seeded rows, trains the head and runs one warm-up round.
The window repeats rounds, each ``query(budget, strategy)`` -> ``label``
the picks with their seeded classes -> ``train_eval``, until the
deadline, or until fewer than ``budget`` rows are left unlabeled; the
round open at the deadline finishes and counts.

The check compares the served features of ``check_rows`` seeded pool rows
with the scorer's reference (``feature_gap``), and every pick of every
round, the warm-up's included, teacher-forced against the reference
k-center step (``pick_gap``: the mean relative gap by which a pick's
distance to the chosen set lies below the farthest unlabeled row's;
infinite when a pick is missing, repeated or already labeled).
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

from bench.harness import data, reference


class Driver:
    def __init__(self, run):
        self.run = run
        self.mix = run.mix
        self.cfg = run.cfg
        self.cli = None
        self.keys: List[str] = []
        self.row_of: Dict[str, int] = {}
        self.labeled: List[int] = []
        self.rounds = []
        self.sample = None
        self.served = None

    def setup(self):
        cfg, run = self.cfg, self.run
        self.x, self.y = run.scorer.rows(run.seed, int(cfg["pool_rows"]), cfg)
        self.cli = run.client()
        chunk = int(self.mix["push_chunk"])
        tickets = [self.cli.push_data(list(self.x[s:s + chunk]),
                                      asynchronous=True)
                   for s in range(0, len(self.x), chunk)]
        self.cli.flush()
        self.keys = [k for t in tickets for k in t.keys]
        self.row_of = {k: i for i, k in enumerate(self.keys)}
        self.warm_shapes()
        warm = data.np_rng(run.seed, 1).choice(
            len(self.x), int(self.mix["warm_labels"]), replace=False)
        self.label(warm.tolist())
        self.cli.train_eval()
        self.round(0)                  # the warm-up round: same path

    def warm_shapes(self):
        """Every window round's labeled and unlabeled counts are new, and
        the program compiles each new count anew. Window round k queries
        with ``warm_labels + k * budget`` rows labeled and retrains with
        ``budget`` more, so a session of its own over the same rows
        (their features already cached) steps through those counts for
        rounds 1 .. ``warm_rounds``: label up to the count, retrain, query
        one row. Its session is closed before the window."""
        mix = self.mix
        w, b = int(mix["warm_labels"]), int(mix["budget"])
        steps = range(1, int(mix["warm_rounds"]) + 2)
        cli = self.run.client()
        try:
            chunk = int(mix["push_chunk"])
            tickets = [cli.push_data(list(self.x[s:s + chunk]),
                                     asynchronous=True)
                       for s in range(0, len(self.x), chunk)]
            cli.flush()
            keys = [k for t in tickets for k in t.keys]
            order = data.np_rng(self.run.seed, 3).permutation(len(keys))
            done = 0
            for j in steps:
                upto = min(w + j * b, len(keys) - 1)
                rows = order[done:upto]
                cli.label([keys[i] for i in rows], self.y[rows].tolist())
                done = upto
                cli.train_eval()
                cli.query(1, mix["strategy"], rng_seed=0)
        finally:
            cli.close_session()
            cli.close()

    def label(self, rows: List[int]):
        keys = [self.keys[i] for i in rows]
        self.cli.label(keys, self.y[rows].tolist())
        self.labeled += rows

    def round(self, index: int):
        run, mix = self.run, self.mix
        before = np.asarray(self.labeled, np.int64)
        with run.span("select"):
            out = self.cli.query(int(mix["budget"]), mix["strategy"],
                                 rng_seed=index)
        picks = [self.row_of.get(k, -1) for k in out["keys"]]
        with run.span("label"):
            self.label([p for p in picks if p >= 0])
        with run.span("retrain"):
            self.cli.train_eval()
        self.rounds.append((before, np.asarray(picks, np.int64)))

    def window(self, t0: float, deadline: float) -> float:
        run, n = self.run, 0
        run.round_log = []         # (start, end, unlabeled rows, budget)
        budget = int(self.mix["budget"])
        while (time.perf_counter() < deadline
               and len(self.x) - len(self.labeled) >= budget):
            run.attempted += 1
            start = time.perf_counter()
            unlabeled = len(self.x) - len(self.labeled)
            self.round(n + 1)
            run.round_log.append((start, time.perf_counter(), unlabeled,
                                  budget))
            n += 1
        t_end = time.perf_counter()
        run.e2e["round_s"] = (t_end - t0) / max(n, 1)
        run.e2e["rounds"] = n
        return t_end

    def capture(self):
        """Served features of a seeded sample of pool rows."""
        n = len(self.x)
        m = min(int(self.mix["check_rows"]), n)
        self.sample = data.np_rng(self.run.seed, 2).choice(n, m,
                                                           replace=False)
        cache = self.run.srv.cache
        got = [cache.get(self.keys[i]) for i in self.sample]
        self.served = (None if any(g is None for g in got)
                       else np.stack(got).astype(np.float32))

    def check(self):
        run = self.run
        ref = run.reference_features(self.x)
        gaps = []
        for before, picks in self.rounds:
            if (picks < 0).any() or len(picks) != int(self.mix["budget"]):
                gaps.append(np.inf)
                break
            gaps.extend(reference.greedy_gaps(ref, before, picks))
        return [("feature_gap", run.feature_gap(self.served,
                                                ref[self.sample]),
                 run.limit("feature_gap")),
                ("pick_gap", float(np.mean(gaps)), run.limit("pick_gap"))]

    def close(self):
        if self.cli is not None:
            self.cli.close()
            self.cli = None
