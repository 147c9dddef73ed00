"""Closed-loop bulk pushes of rows no one pushed before.

Mix keys: ``tenants``, ``chunk``, ``bank_rows``, ``check_rows`` and
optionally ``trace_s``.

``tenants`` clients, each in its own session, push ``chunk``-row
synchronous pushes in closed loops. Each tenant draws its rows from a
seeded bank of ``bank_rows`` rows made distinct by the scorer
(``distinct``), so the content cache always misses. Set-up runs one push
per tenant; the window runs until the deadline, and the pushes open at
the deadline finish and count.

The check: every acknowledged row is visible in its tenant's pool
(``missing_rows``, exact), and the served features of ``check_rows``
acknowledged rows, drawn from the seed, against the scorer's reference
(``feature_gap``).
"""
from __future__ import annotations

import threading
import time
from typing import List, Optional

import numpy as np

from bench.harness import data


class Driver:
    def __init__(self, run):
        self.run = run
        self.mix = run.mix
        self.cfg = run.cfg
        self.clients = []
        self.banks = []
        self.acks: List[List[tuple]] = []
        self.next = []

    def setup(self):
        run, mix, cfg = self.run, self.mix, self.cfg
        for t in range(int(mix["tenants"])):
            bank, _ = run.scorer.rows(run.seed, int(mix["bank_rows"]), cfg,
                                      stream=100 + t)
            self.banks.append(bank)
            self.clients.append(run.client())
            self.acks.append([])
            self.next.append(0)
        self._all(None)

    def _all(self, deadline: Optional[float]):
        threads = [threading.Thread(target=self._push, args=(t, deadline))
                   for t in range(len(self.clients))]
        for th in threads:
            th.start()
        for th in threads:
            th.join()

    def _push(self, t: int, deadline: Optional[float]):
        chunk = int(self.mix["chunk"])
        while True:
            start = self.next[t]
            rows = self.run.scorer.distinct(self.banks[t], start, chunk)
            try:
                with self.run.span("push"):
                    keys = self.clients[t].push_data(list(rows))
            except Exception as e:        # counted as failed, reported
                self.acks[t].append((start, None, time.perf_counter(),
                                     repr(e)))
                return
            self.next[t] = start + chunk
            self.acks[t].append((start, keys, time.perf_counter(), None))
            if deadline is None or time.perf_counter() >= deadline:
                return

    def window(self, t0: float, deadline: float) -> float:
        first = [len(a) for a in self.acks]
        self._all(deadline)
        done = [x for a, f in zip(self.acks, first) for x in a[f:]]
        run = self.run
        run.attempted = len(done)
        run.failed = sum(x[1] is None for x in done)
        t_end = max(x[2] for x in done)
        run.push_log = [(x[2], len(x[1])) for x in done if x[1] is not None]
        run.rows_pushed = sum(n for _, n in run.push_log)
        run.e2e["push_rows_per_s"] = run.rows_pushed / (t_end - t0)
        return t_end

    def capture(self):
        run = self.run
        self.visible = [int(c.stats()["pool"]) for c in self.clients]
        acked = [(t, start + j, k) for t, a in enumerate(self.acks)
                 for start, keys, *_ in a if keys is not None
                 for j, k in enumerate(keys)]
        self.acked_rows = [sum(len(x[1]) for x in a if x[1] is not None)
                           for a in self.acks]
        m = min(int(self.mix["check_rows"]), len(acked))
        pick = data.np_rng(run.seed, 2).choice(len(acked), m, replace=False)
        self.sample = [acked[i] for i in sorted(pick)]
        got = [run.srv.cache.get(k) for _, _, k in self.sample]
        self.served = (None if any(g is None for g in got)
                       else np.stack(got).astype(np.float32))

    def check(self):
        run = self.run
        rows = np.stack([run.scorer.distinct(self.banks[t], i, 1)[0]
                         for t, i, _ in self.sample])
        ref = run.reference_features(rows)
        missing = sum(abs(v - a) for v, a in zip(self.visible,
                                                  self.acked_rows))
        return [("missing_rows", float(missing), run.limit("missing_rows")),
                ("feature_gap", run.feature_gap(self.served, ref),
                 run.limit("feature_gap"))]

    def close(self):
        for c in self.clients:
            c.close()
        self.clients = []
