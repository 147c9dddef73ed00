"""One run of one cell: set-up, the measured window, the check.

The system under test is an ``ALServer`` behind ``serve_tcp`` in this
process, driven only by ``ALClient`` connections over TCP. The harness
builds the server around the configuration's scorer
(``bench/scorers/<scorer>.py``), hands it weights made here from the seed,
and counts from outside the rows its forward computes and JAX's compile
events (compiles and persistent-cache loads). The traffic mix's driver
(``bench/drivers/<driver>.py``) sets up, runs the window and checks what
the timed path produced against the plain references.

With ``control`` the scorer's forward is replaced by its plain reference
one precision below the configuration's: the run that ``correct`` must
refuse. The benchmark's own runs never set it.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import math
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench.harness import spec as spec_lib, trace as trace_lib


class Run:
    """State of one run, read by the metric readers after the window."""

    def __init__(self, spec, seed: int, seconds: float, trace: bool,
                 t_start: float, control: bool = False):
        self.spec = spec
        self.cfg = spec.config
        self.mix = spec.traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.control = bool(control)
        self.t_start = t_start
        self.scorer = spec_lib.module(spec.root, "scorers", self.cfg["scorer"])
        self.reference = spec_lib.module(spec.root, "references",
                                         self.cfg["scorer"])
        self.spans: Dict[str, List[Tuple[float, float]]] = \
            collections.defaultdict(list)
        self.e2e: Dict[str, float] = {}
        self.checks: List[Tuple[str, float, float]] = []
        self.attempted = 0
        self.failed = 0
        self.compiles = 0
        self.rows_fed = 0
        self.window: Tuple[float, float] = (0.0, 0.0)
        # host clock at which the profiler started; host-clock per-layer
        # metrics read only what ended before it
        self.traced_from = math.inf
        self.window_compiles = 0
        self.window_rows_fed = 0
        self.setup_s = 0.0
        self.memory_peak_bytes = 0
        self.trace_reduction: Optional[trace_lib.Reduction] = None
        self.devices = []
        self._lock = threading.Lock()
        self.srv = None
        self.rpc = None
        self.params = None

    # ------------------------------------------------------ instruments --
    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        with (jax.profiler.TraceAnnotation(f"bench.{name}") if self.trace
              else contextlib.nullcontext()):
            yield
        self.spans[name].append((t0, time.perf_counter()))

    def _on_compile(self, event, *args, **kwargs):
        if event in ("/jax/core/compile/backend_compile_duration",
                     "/jax/compilation_cache/cache_hits"):
            with self._lock:
                self.compiles += 1

    # ---------------------------------------------------------- server --
    def start_server(self):
        from repro.service.client import serve_tcp
        from repro.service.config import ALServiceConfig
        from repro.service.server import ALServer
        cfg = self.cfg
        self.params = self.scorer.params(self.seed, cfg)
        backend = self.scorer.backend(cfg, self.params)
        forward = backend.features
        if self.control:
            def forward(batch):
                return np.asarray(self.reference.features(
                    self.params, batch, cfg, control=True))

        def counted(batch):
            with self._lock:
                self.rows_fed += int(np.shape(batch)[0])
            return forward(batch)

        backend.features = counted
        self.srv = ALServer(ALServiceConfig(
            model_name=self.scorer.MODEL_NAME,
            batch_size=int(cfg["batch_size"]),
            replicas=int(cfg["replicas"])), backend=backend)
        self.rpc = serve_tcp(self.srv)

    def client(self, session: Optional[str] = "new"):
        from repro.service.client import ALClient
        return ALClient(url=f"127.0.0.1:{self.rpc.port}", session=session)

    def stop_server(self):
        if self.rpc is not None:
            self.rpc.stop()
        self.rpc = None
        self.srv = None
        gc.collect()

    # ----------------------------------------------------------- phases --
    def execute(self) -> None:
        import jax
        self.devices = jax.devices()[:self.spec.chips]
        driver = spec_lib.module(self.spec.root, "drivers",
                                 self.mix["driver"]).Driver(self)
        jax.monitoring.register_event_listener(self._on_compile)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_compile)
        tracer = None
        try:
            self.start_server()
            driver.setup()
            self.spans.clear()
            c0, f0 = self.compiles, self.rows_fed
            self.setup_s = time.perf_counter() - self.t_start
            t0 = time.perf_counter()
            deadline = t0 + self.seconds
            if self.trace:
                tracer = trace_lib.Tracer(self.spec.root, self.spec.name)
                tracer.arm(deadline - float(self.mix.get("trace_s",
                                                         self.seconds)))
            t_end = driver.window(t0, deadline)
            self.window_compiles = self.compiles - c0
            self.window_rows_fed = self.rows_fed - f0
            if tracer is not None:
                self.trace_reduction = tracer.stop(len(self.devices))
                self.traced_from = tracer.started_at
            self.window = (t0, t_end)
            self.memory_peak_bytes = max(
                int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                for d in self.devices)
            driver.capture()
        finally:
            if tracer is not None:
                tracer.cancel()
            driver.close()
            self.stop_server()
            jax.monitoring.unregister_event_listener(self._on_compile)
            jax.monitoring.unregister_event_duration_listener(
                self._on_compile)
        self.checks = driver.check()
        self.e2e["setup_s"] = self.setup_s

    @property
    def correct(self) -> bool:
        return self.failed == 0 and all(
            np.isfinite(v) and v <= lim for _, v, lim in self.checks)

    def feature_gap(self, served: np.ndarray, ref: np.ndarray) -> float:
        """Largest relative L2 distance of a served feature row from the
        reference row (a missing row reads infinity)."""
        if served is None or len(served) == 0:
            return float("inf")
        num = np.linalg.norm(served - ref, axis=1)
        den = np.maximum(np.linalg.norm(ref, axis=1), 1e-30)
        gap = float(np.max(num / den))
        return gap if np.isfinite(gap) else float("inf")

    def reference_features(self, rows: np.ndarray) -> np.ndarray:
        """The plain reference forward of ``rows``, at the configuration's
        precision."""
        return self.reference.pool_features(self.params, rows, self.cfg)

    def limit(self, name: str) -> float:
        return self.spec.limits[name]["limit"]
