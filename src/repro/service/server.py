"""ALServer — the paper's AL-as-a-service backend (Fig. 1), multi-tenant.

One ``ALServer`` owns the *shared* resources — scorer backend, the
content-addressed ``EmbeddingCache``, config — and hosts many independent
``ALSession`` objects (one per client/tenant). A session carries everything
that used to be server-global: the pool key list, raw copies, labels, the
trained head, the oracle, and a versioned pool-artifact cache. Content
addressing makes sharing the embedding cache across sessions safe: two
tenants pushing the same sample compute its features once.

Data path (stage-level pipeline, Fig. 3c):
  fetch (URI/bytes -> raw)  ->  preprocess  ->  infer (batched features via
  DynamicBatcher)  ->  EmbeddingCache

Query path:
  strategy != "auto": run one zoo strategy over the pooled artifacts.
  strategy == "auto": run the PSHEA agent (performance predictor + successive
  halving) against the attached oracle, per paper Alg. 1. With
  ``pshea_workers > 1`` the surviving candidates race on a thread pool, so a
  round costs max(candidate) instead of sum(candidate); per-(strategy, round)
  rng streams keep the parallel schedule bit-identical to the serial one.

Pool artifacts are INCREMENTAL, per shard and per column
(core.selection.ShardColumns): every shard carries its own ``rows_epoch``,
so a push invalidates only the shards it touched and the refresh embeds
only the appended rows, extending the shard's growable ``feats`` buffer in
place; ``feats`` and ``probs`` have decoupled lifetimes, so
``train_and_eval`` re-runs just the head forward over the cached feats
(zero re-embeds) and ``label`` invalidates nothing at all (the unlabeled
set is a separately-versioned mask applied at query time). Steady-state
query cost after a data change is O(delta) embed work, not O(pool) — and
PSHEA's 7-10 candidates still share ONE refresh per version instead of
re-stacking the pool per candidate.

Replica sharding (config ``replicas: N``): each session's pool is
hash-partitioned by content key across N shards. Artifacts are built per
shard in parallel, every query strategy runs its replica-sharded path
(local propose, global merge — core.selection), and selections are
bit-identical to ``replicas=1``. ``push_data(asynchronous=True)`` enqueues
onto a per-session ingest queue whose worker embeds drained batches per
shard and bumps pool_version once per batch; ``flush()`` is the barrier
label/query/sync-push take so they linearize after pending ingests.

The server is usable in-process (ALClient(local=server)) or over the msgpack
TCP transport in transport.py (gRPC stand-in; see DESIGN.md).
"""
from __future__ import annotations

import concurrent.futures as cf
import os
import shutil
import tempfile
import threading
import time
import uuid
import zlib
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.common import telemetry
from repro.core.agent.controller import run_pshea
from repro.distributed.worker import (PhaseFailureInjector, ShardWorkerPool)
from repro.core.prefilter import PrefilterConfig, maintain_summary
from repro.core.selection import (ColumnSpill, KCenterStateCache,
                                  ShardColumns, ShardView, grow_append,
                                  replica_map, replica_of)
from repro.core.strategies.zoo import HYBRIDS, PAPER_SEVEN, get_strategy
from repro.service.backends import FeatureBackend, HeadState, make_backend
from repro.service.batcher import DynamicBatcher
from repro.service.cache import EmbeddingCache, content_key
from repro.service.config import ALServiceConfig
from repro.service.errors import ServerOverloaded
from repro.service.pipeline import Stage, StagePipeline

DEFAULT_SESSION = "default"

# strategies whose sharded path starts from a warm (labeled-centers)
# min-dist fold — the ones the persisted KCenterStateCache can feed
_WARM_STATE_STRATEGIES = frozenset({"coreset", "weighted_kcenter"})


def _strategy_seed(strategy: str, round_index: int) -> int:
    """Deterministic per-(strategy, round) rng stream. Independent of how
    many candidates are live and of the order they execute in — the property
    that makes parallel PSHEA bit-identical to the serial schedule."""
    return zlib.crc32(f"{strategy}/{round_index}".encode())


class PushTicket:
    """Client-side future for ``push_data(asynchronous=True)``.

    ``keys`` (content hashes) are known at enqueue time. ``result()``
    blocks until the session's ingest worker has embedded and appended the
    batch (in-process mode) or until the server acknowledged the enqueue
    (TCP mode — the enqueue ack is what the connection returns); either
    way ``flush()`` on the client/session is the hard integration barrier.

    ``result(timeout=...)`` raises ``TimeoutError`` once the deadline
    passes — and raises it immediately, deadline or not, if the ingest
    worker serving this push has died without resolving it (``worker_alive``
    probe), instead of hanging the client forever.
    """

    _POLL_S = 0.1     # liveness re-check cadence while blocked on result()

    def __init__(self, keys: Sequence[str], future: "cf.Future",
                 worker_alive: Optional[Callable[[], bool]] = None):
        self.keys = list(keys)
        self._future = future
        self._worker_alive = worker_alive

    def done(self) -> bool:
        return self._future.done()

    def result(self, timeout: Optional[float] = None) -> List[str]:
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            wait = self._POLL_S
            if deadline is not None:
                wait = max(0.0, min(wait, deadline - time.monotonic()))
            try:
                self._future.result(wait)
                return self.keys
            except cf.TimeoutError:
                # on >=3.11 cf.TimeoutError IS TimeoutError: a future that
                # FAILED with one must propagate, not be mistaken for a poll
                if self._future.done():
                    raise
            # a dead worker can never resolve this future: fail fast even
            # with timeout=None rather than blocking forever
            if self._worker_alive is not None and not self._worker_alive():
                raise TimeoutError(
                    "ingest worker died before integrating this push; "
                    "the session is unusable for async ingest") from None
            if deadline is not None and time.monotonic() >= deadline:
                raise TimeoutError(
                    f"push not integrated within {timeout}s (ingest queue "
                    f"busy or stalled); flush() is the hard barrier"
                ) from None


class StandingQuery:
    """One registered ``(budget, strategy)`` subscription on a session.

    Every emit is the EXACT selection a one-shot ``query()`` would return
    over the pool at that moment (provisional/replace semantics — emits
    carry added/removed diffs against the previous emit), so the final
    emit after the stream settles is bit-identical to a one-shot query
    over the final pool. Between emits the replay engine (see
    ``ALSession._standing_replay``) stores the previous selection plus the
    per-slot merged winner scores captured by ``replica_greedy_select``;
    when no delta row beats any recorded winner, the selection is provably
    unchanged and the emit streams only the delta rows.

    All mutable fields are guarded by ``lock``; an emit holds it end to
    end, so concurrent triggers (ingest worker + a poll) serialize and the
    second sees fresh versions and no-ops.
    """

    def __init__(self, qid: str, budget: int, strategy: str, rng_seed: int):
        self.qid = qid
        self.budget = int(budget)
        self.strategy = strategy
        self.rng_seed = int(rng_seed)
        self.lock = threading.RLock()
        self.emits: List[dict] = []
        self.seq = 0
        self.cancelled: Optional[str] = None      # cancellation reason
        self.error: Optional[BaseException] = None
        # -- replay state (valid when the last emit used the full budget) --
        self.keys: Optional[List[str]] = None     # last emitted selection
        self.values: Optional[List[float]] = None  # per-slot winner scores
        self.n_unlabeled = 0      # unlabeled-list length at the last emit
        self.pool_version = -1
        self.labels_version = -1
        self.head_version = -1


class ALSession:
    """Per-tenant AL state: pool, labels, head, oracle, artifact cache."""

    def __init__(self, server: "ALServer", session_id: str):
        self.server = server
        self.session_id = session_id
        self.replicas = max(int(server.config.replicas), 1)
        self._keys: List[str] = []
        self._raw: Dict[str, np.ndarray] = {}
        self._labels: Dict[str, int] = {}
        self._labeled_keys: List[str] = []
        self._head: Optional[HeadState] = None
        self._eval_set: Optional[tuple] = None
        self._oracle: Optional[Callable[[Sequence[str]], Sequence[int]]] = None
        self._lock = threading.RLock()
        # -- incremental pool-artifact engine ---------------------------
        # One ShardColumns per replica shard (ONE shard at replicas=1 —
        # the unsharded query path is just its 1-shard case). Columns are
        # epoch-stamped and refreshed incrementally:
        #   rows appended -> only the touched shards' rows_epoch moves;
        #     refresh embeds ONLY the appended rows (growable buffers);
        #   train_and_eval -> head_version moves; refresh re-runs the head
        #     over cached feats, ZERO re-embeds;
        #   label -> labels_version moves; artifacts untouched (the
        #     unlabeled set is a mask applied at query time).
        # pool_version stays the coarse monotone row-append counter the
        # ingest contract is specified against (one bump per appending
        # push/drained batch).
        self.pool_version = 0
        self.head_version = 0
        self.labels_version = 0
        self.artifact_builds = 0     # refresh/build events that did work
        self.full_builds = 0         # shard feats columns built from empty
        self.delta_builds = 0        # shard feats columns extended in place
        self.probs_refreshes = 0     # head-only prob recomputes (0 embeds)
        # mmap spill for the artifact columns (shard_ram_bytes > 0): column
        # buffers past the RAM budget land in per-session spill files that
        # close() removes; None = RAM-only columns (the default)
        cfg = server.config
        self._spill: Optional[ColumnSpill] = None
        if int(cfg.shard_ram_bytes) > 0:
            base = cfg.shard_spill_dir or os.path.join(
                tempfile.gettempdir(), "repro-shard-spill")
            self._spill = ColumnSpill(
                os.path.join(base, f"{os.getpid()}-{uuid.uuid4().hex[:8]}"),
                int(cfg.shard_ram_bytes))
        # centroid prefilter (core.prefilter): summaries are maintained
        # alongside the columns when enabled; None = ungated full scans
        self._prefilter_cfg: Optional[PrefilterConfig] = None
        if cfg.prefilter:
            self._prefilter_cfg = PrefilterConfig(
                slack=float(cfg.prefilter_slack),
                clusters=int(cfg.prefilter_clusters),
                min_rows=int(cfg.prefilter_min_rows))
        self._columns = [ShardColumns(self._spill)
                         for _ in range(self.replicas)]
        self._index: Dict[str, Tuple[int, int]] = {}  # key -> (shard, row)
        # RLock: the worker runtime's on_death recovery hook resets a
        # shard's columns from INSIDE a refresh (which already holds the
        # lock on the supervising thread) as well as from query threads
        self._artifact_lock = threading.RLock()
        # shard recoveries: worker deaths whose on_death hook reset this
        # session's columns (the re-embed-from-raw path ran)
        self.shard_recoveries = 0
        # persisted k-center strategy state (strategy_state_cache): per-
        # shard min-dist vectors delta-extended on push, dropped on retrain
        self._kstate = KCenterStateCache()
        # -- standing queries -------------------------------------------
        # qid -> StandingQuery; the ingest worker emits after every
        # integrated batch, polls emit lazily for sync mutations
        self._standing: Dict[str, StandingQuery] = {}
        self._standing_lock = threading.Lock()
        self.standing_emits = 0
        self.standing_replay_emits = 0
        self.standing_full_emits = 0
        # -- async ingest queue -----------------------------------------
        # push_data(asynchronous=True) enqueues; a per-session worker
        # drains batches, embeds per shard, and bumps pool_version ONCE
        # per drained batch. flush() is the barrier label/query/sync-push
        # take so they linearize after every pending ingest.
        self._ingest_queue: List[tuple] = []
        self._ingest_cv = threading.Condition()
        self._ingest_busy = False
        self._ingest_thread: Optional[threading.Thread] = None
        self._ingest_stop = False
        self._ingest_error: Optional[BaseException] = None
        # bounded-ingest accounting (config ingest_max_rows/_bytes; 0 =
        # unbounded). rows/bytes span enqueue -> batch INTEGRATED, so the
        # cap bounds worker-held memory too, not just the queue
        self._ingest_rows = 0
        self._ingest_bytes = 0
        self._ingest_rows_hw = 0
        self._ingest_bytes_hw = 0
        self._ingest_depth_hw = 0
        self._ingest_shed = 0
        self._ingest_drain_ema_s = 0.05   # smoothed per-batch drain time
        # drained batches; pool_version bumps once per drained batch THAT
        # APPENDS NEW ROWS (all-duplicate or failed batches drain without
        # a bump), so pool_version <= ingest_batches always
        self.ingest_batches = 0

    # ------------------------------------------------------------- data --
    def push_data(self, items: Sequence[np.ndarray], pipelined: bool = True,
                  asynchronous: bool = False):
        """Synchronous: embed + append now, return keys. Asynchronous:
        enqueue for the ingest worker and return a ``PushTicket`` whose
        ``keys`` are immediately known (content hashes)."""
        if asynchronous:
            return self._push_async(items)
        return self._push_sync(items, pipelined)

    @telemetry.traced("push")
    def _push_sync(self, items: Sequence[np.ndarray],
                   pipelined: bool) -> List[str]:
        self.flush()     # sync pushes order AFTER every pending async push
        # sync embedding stays on ONE pipeline even at replicas>1 (per-
        # shard parallel embedding is the ingest queue's job). The feature
        # path itself is batch-insensitive — row-local forward + one
        # canonical batch shape (DynamicBatcher pad_to_max) — so any
        # chunking of the same rows lands the identical feature bytes
        with telemetry.span("push.hash"):
            keys = [content_key(np.asarray(it)) for it in items]
        todo = [(k, it) for k, it in zip(keys, items)
                if k not in self.server.cache]
        with self._lock:
            self._append_rows(keys, [np.asarray(it) for it in items])
        if todo:
            self.server._process(todo, pipelined=pipelined)
        return keys

    def _append_rows(self, keys: Sequence[str],
                     items: Sequence[np.ndarray]) -> None:
        """Append the new (key, raw) rows to the pool and stamp the shards
        they land on: ONE rows_epoch tick per touched shard and ONE
        pool_version tick per appending event — untouched shards keep their
        artifact columns fresh. Caller holds ``self._lock``."""
        touched = set()
        for k, it in zip(keys, items):
            if k in self._raw:
                continue
            self._raw[k] = it
            self._keys.append(k)
            si = 0 if self.replicas == 1 else replica_of(k, self.replicas)
            col = self._columns[si]
            self._index[k] = (si, len(col.keys))
            col.keys.append(k)
            touched.add(si)
        if touched:
            for si in touched:
                self._columns[si].rows_epoch += 1
            self.pool_version += 1

    # ----------------------------------------------------- async ingest --
    def _push_async(self, items: Sequence[np.ndarray]) -> PushTicket:
        items = [np.asarray(it) for it in items]
        with telemetry.span("push.hash"):
            keys = [content_key(it) for it in items]
        rows = len(items)
        nbytes = sum(int(it.nbytes) for it in items)
        cfg = self.server.config
        policy = cfg.ingest_policy
        if policy not in ("block", "shed"):
            raise ValueError(f"ingest_policy must be 'block' or 'shed', "
                             f"got {policy!r}")
        fut: cf.Future = cf.Future()
        with self._ingest_cv:
            if self._ingest_stop:
                raise RuntimeError(f"session {self.session_id!r} is closed")
            while self._ingest_over_cap(rows, nbytes):
                if policy == "shed":
                    # nothing was enqueued: the push is cleanly retryable
                    self._ingest_shed += 1
                    raise ServerOverloaded(
                        self._ingest_retry_after(),
                        f"ingest queue full ({self._ingest_rows} rows / "
                        f"{self._ingest_bytes} bytes outstanding); "
                        f"retry after the worker drains")
                # block: backpressure the producer until the worker drains
                t = self._ingest_thread
                if t is not None and not t.is_alive():
                    raise RuntimeError(
                        "ingest worker died with the queue at capacity; "
                        "the session cannot drain")
                self._ingest_cv.wait(timeout=0.1)
                if self._ingest_stop:
                    raise RuntimeError(
                        f"session {self.session_id!r} is closed")
            self._ingest_rows += rows
            self._ingest_bytes += nbytes
            self._ingest_rows_hw = max(self._ingest_rows_hw,
                                       self._ingest_rows)
            self._ingest_bytes_hw = max(self._ingest_bytes_hw,
                                        self._ingest_bytes)
            # the pushing request's span context rides with the entry, so
            # the worker's spans name the request that queued the rows
            self._ingest_queue.append((keys, items, fut,
                                       telemetry.context()))
            self._ingest_depth_hw = max(self._ingest_depth_hw,
                                        len(self._ingest_queue))
            if self._ingest_thread is None:
                self._ingest_thread = threading.Thread(
                    target=self._ingest_loop, daemon=True,
                    name=f"ingest-{self.session_id}")
                self._ingest_thread.start()
            self._ingest_cv.notify_all()
        return PushTicket(keys, fut, worker_alive=self._ingest_alive)

    def _ingest_over_cap(self, rows: int, nbytes: int) -> bool:
        """True when admitting (rows, nbytes) would exceed a configured
        cap. An oversize single push is still admitted once nothing is
        outstanding — it could otherwise never run. Caller holds
        ``_ingest_cv``."""
        if self._ingest_rows == 0 and self._ingest_bytes == 0:
            return False
        cfg = self.server.config
        max_rows = int(cfg.ingest_max_rows)
        max_bytes = int(cfg.ingest_max_bytes)
        return ((max_rows > 0 and self._ingest_rows + rows > max_rows)
                or (max_bytes > 0
                    and self._ingest_bytes + nbytes > max_bytes))

    def _ingest_retry_after(self) -> float:
        """Shed-push retry hint: time for the worker to drain the current
        backlog, from the smoothed per-batch drain time. Caller holds
        ``_ingest_cv``."""
        batches = (len(self._ingest_queue)
                   / max(self.server.config.ingest_batch, 1)
                   + (1 if self._ingest_busy else 0))
        return min(max(self._ingest_drain_ema_s * (batches + 1.0), 0.01),
                   5.0)

    def _ingest_alive(self) -> bool:
        """Liveness probe for PushTicket: a worker that exited with this
        push still queued/unresolved can never complete it."""
        t = self._ingest_thread
        return t is not None and t.is_alive()

    def _ingest_loop(self):
        while True:
            with self._ingest_cv:
                while not self._ingest_queue and not self._ingest_stop:
                    self._ingest_cv.wait()
                if not self._ingest_queue:   # stop requested, queue drained
                    return
                batch = self._ingest_queue[:self.server.config.ingest_batch]
                del self._ingest_queue[:len(batch)]
                self._ingest_busy = True
            t_drain = time.monotonic()
            err: Optional[BaseException] = None
            try:
                with telemetry.attached(batch[0][3]):
                    self._integrate(batch)
                for keys, _, fut, _ in batch:
                    fut.set_result(keys)
            except BaseException as batch_err:
                if len(batch) == 1:
                    err = batch_err
                    batch[0][2].set_exception(batch_err)
                else:
                    # isolate the blast radius: re-integrate each
                    # coalesced push on its own, so one malformed push
                    # cannot drop the rows of valid pushes drained in the
                    # same batch
                    for entry in batch:
                        keys, _, fut, ctx = entry
                        try:
                            with telemetry.attached(ctx):
                                self._integrate([entry])
                            fut.set_result(keys)
                        except BaseException as one_err:
                            err = one_err
                            fut.set_exception(one_err)
            # standing-query emits ride the ingest worker: every integrated
            # batch re-emits for each live subscription (still marked busy,
            # so flush()-takers observe the emit as part of the drain).
            # _standing_refresh never raises — an emit failure parks on the
            # query's ticket for the next poll to surface
            self._notify_standing()
            with self._ingest_cv:
                self._ingest_busy = False
                self.ingest_batches += 1
                # batch fully integrated (or failed): release its rows/
                # bytes from the cap and wake any blocked producer
                self._ingest_rows = max(
                    self._ingest_rows
                    - sum(len(keys) for keys, _, _, _ in batch), 0)
                self._ingest_bytes = max(
                    self._ingest_bytes
                    - sum(int(it.nbytes) for _, items, _, _ in batch
                          for it in items), 0)
                dt = time.monotonic() - t_drain
                self._ingest_drain_ema_s += 0.2 * (dt
                                                   - self._ingest_drain_ema_s)
                if err is not None:
                    self._ingest_error = err
                self._ingest_cv.notify_all()

    @telemetry.traced("ingest.integrate")
    def _integrate(self, batch: List[tuple]) -> None:
        """Embed + append ONE drained ingest batch: the un-cached items of
        every queued push are grouped by replica shard and embedded in
        parallel; pool_version bumps once for the whole batch."""
        todo, seen = [], set()
        for keys, items, _, _ in batch:
            for k, it in zip(keys, items):
                if k in seen or k in self.server.cache:
                    continue
                seen.add(k)
                todo.append((k, it))
        if todo:
            self.server._process_replicated(todo)
        with self._lock:
            # ONE _append_rows call for the whole drained batch: one
            # pool_version bump, one rows_epoch tick per touched shard
            self._append_rows(
                [k for keys, _, _, _ in batch for k in keys],
                [it for _, items, _, _ in batch for it in items])

    def flush(self, timeout: Optional[float] = None) -> None:
        """Ingest barrier: returns once every previously queued async push
        has been embedded and appended to the pool. label/query/sync-push
        call this on entry, so they linearize after pending ingests. A
        failed ingest re-raises here (once), and a DEAD worker with work
        still pending raises instead of waiting on a drain that can never
        happen (same fail-fast contract as ``PushTicket.result``).

        ``timeout`` bounds the wait: a queue not drained within it raises
        ``TimeoutError`` (like ``PushTicket.result``) with the backlog
        still intact — flush again to keep waiting; no rows are lost."""
        if self._ingest_thread is None:
            return
        deadline = (None if timeout is None
                    else time.monotonic() + float(timeout))
        with self._ingest_cv:
            while self._ingest_queue or self._ingest_busy:
                if not self._ingest_thread.is_alive():
                    raise RuntimeError(
                        "ingest worker died with pushes pending; the "
                        "session cannot drain its queue")
                wait = 0.1
                if deadline is not None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise TimeoutError(
                            f"flush(): ingest queue not drained within "
                            f"{timeout}s ({len(self._ingest_queue)} pushes "
                            f"still pending)")
                    wait = min(wait, remaining)
                self._ingest_cv.wait(timeout=wait)
            if self._ingest_error is not None:
                err, self._ingest_error = self._ingest_error, None
                raise RuntimeError("asynchronous ingest failed") from err

    def close(self) -> None:
        """Stop the ingest worker (drains what is already queued) and
        remove the session's spill directory, if any. Standing queries are
        cancelled FIRST, so the draining worker integrates the remaining
        pushes without emitting to a subscription whose owner is gone."""
        with self._standing_lock:
            for sq in self._standing.values():
                if sq.cancelled is None:
                    sq.cancelled = "session closed"
        with self._ingest_cv:
            self._ingest_stop = True
            self._ingest_cv.notify_all()
        if self._spill is not None:
            t = self._ingest_thread
            if t is not None:
                t.join(timeout=5.0)     # let the drain finish its appends
            shutil.rmtree(self._spill.directory, ignore_errors=True)

    # ------------------------------------------------------ label/oracle --
    def attach_oracle(self, oracle: Callable[[Sequence[str]], Sequence[int]],
                      eval_x: np.ndarray, eval_y: np.ndarray):
        """Oracle = the paper's human annotator; eval set scores rounds."""
        backend = self.server.backend
        self._oracle = oracle
        ex = backend.preprocess(np.asarray(eval_x))
        self._eval_set = (backend.features(ex), np.asarray(eval_y))

    def label(self, keys: Sequence[str], labels: Sequence[int]):
        """Labeling moves rows across the labeled/unlabeled boundary but
        changes NO pool content: it bumps only ``labels_version`` (the
        unlabeled set is a mask applied at query time), so the artifact
        columns survive every labeling round untouched."""
        self.flush()     # linearize after pending async ingests
        with self._lock:
            changed = False
            for k, y in zip(keys, labels):
                if k not in self._labels:
                    self._labels[k] = int(y)
                    self._labeled_keys.append(k)
                    changed = True
            if changed:
                self.labels_version += 1

    # --------------------------------------------------------- artifacts --
    def _recover_shard(self, si: int) -> None:
        """Worker-death recovery hook (distributed.worker ``on_death``):
        the shard's in-flight state died with its worker, so drop the
        shard's artifact columns entirely. The retried round then rebuilds
        them through ``_feats_for`` — re-embedding from raw + content keys
        in canonical batches, so the rebuilt bytes (and every later
        selection) are bit-identical to the no-failure run. The lineage
        bump ``reset()`` performs also invalidates any persisted k-center
        state derived from the lost columns."""
        with self._artifact_lock:
            self._columns[si % self.replicas].reset()
            self.shard_recoveries += 1

    def _feats_for(self, keys: Sequence[str]) -> np.ndarray:
        """Features for ``keys``, recomputing entries the EmbeddingCache
        evicted (tiny cache_bytes + no spill_dir) from the session's raw
        copies instead of feeding None into np.stack.

        Recompute runs in the CANONICAL batch shape: ``batch_size``-row
        chunks zero-padded to exactly ``batch_size`` — the same single
        shape the ingest pipeline's ``DynamicBatcher(pad_to_max=True)``
        feeds the jitted extractor. One shape + a row-local forward means
        a recomputed row reproduces the ingest-time feature bytes no
        matter how the pool was chunked when it was pushed or which
        neighbours shared the original batch."""
        cache = self.server.cache
        out: Dict[str, np.ndarray] = {}
        missing: List[str] = []
        for k in keys:
            v = cache.get(k)
            if v is None:
                missing.append(k)
            else:
                out[k] = v
        if missing:
            for k in missing:
                if k not in self._raw:
                    cache.require(k)   # no raw copy: canonical KeyError
            backend = self.server.backend
            bs = max(int(self.server.config.batch_size), 1)
            for s in range(0, len(missing), bs):
                grp = missing[s:s + bs]
                raw = np.stack([np.asarray(self._raw[k]) for k in grp])
                feats = self.server._embed_chunk(raw, bs, backend=backend)
                for k, f in zip(grp, feats):
                    f = np.asarray(f)
                    cache.put(k, f)
                    out[k] = f
            self.server.count_embeds(len(missing))
        return np.stack([out[k] for k in keys])

    def _refresh_artifacts(self):
        """Bring every shard's (feats, probs) columns up to date, touched
        shards in parallel on the shard pool. Caller holds _artifact_lock.

        Per shard, the refresh is column-local and O(change):
          * rows appended since the last refresh -> gather/embed ONLY
            ``keys[feats_rows:]`` and extend the growable feats buffer in
            place (``delta_builds``; a cold column is a ``full_builds``);
          * head_version moved -> recompute probs from the cached feats
            into a fresh buffer, zero re-embeds (``probs_refreshes``);
          * rows appended at an unchanged head -> append probs for just
            the new rows (probs are row-local, so chunked computation is
            bitwise identical to the full-matrix forward).
        An untouched shard is a pure cache hit: no work, no tick.
        """
        backend = self.server.backend
        incremental = self.server.config.incremental_artifacts
        with self._lock:   # consistent (row count, epoch) per shard
            targets = [(len(c.keys), c.rows_epoch) for c in self._columns]
            head = self._head
            head_v = self.head_version
        if head is None:
            head = backend.init_head()
        # staleness is judged by the epoch stamps: a shard whose feats were
        # stamped at an older rows_epoch (rows appended since), or whose
        # probs were stamped at an older head epoch, needs a refresh
        work = [(si, rows, epoch) for si, (rows, epoch) in enumerate(targets)
                if self._columns[si].feats_epoch != epoch
                or self._columns[si].probs_head_epoch != head_v]
        if not work:
            return

        def refresh(item):
            si, rows, epoch = item
            col = self._columns[si]
            if not incremental:
                col.reset()          # debugging fallback: O(shard) rebuilds
            kind = None
            if col.feats_epoch != epoch:
                if col.feats_rows < rows:    # every epoch tick appends rows
                    kind = "full" if col.feats_rows == 0 else "delta"
                    new = self._feats_for(col.keys[col.feats_rows:rows])
                    col.feats, col.feats_rows = grow_append(
                        col.feats, col.feats_rows, new, col.spill)
                col.feats_epoch = epoch
            if col.probs_head_epoch != head_v:
                # head-only refresh: fresh buffer (pinned snapshots keep
                # their rows), computed from cached feats — zero embeds
                old = col.probs
                newp = (np.asarray(backend.probs(
                    telemetry.h2d(col.feats[:col.feats_rows]), head))
                    if col.feats_rows else None)
                if newp is not None and col.spill is not None:
                    newp = col.spill.adopt(newp)
                col.probs = newp
                if col.spill is not None and old is not None:
                    col.spill.release(old)
                col.probs_rows = col.feats_rows
                col.probs_head_epoch = head_v
                kind = kind or "probs"
            elif col.probs_rows < col.feats_rows:
                newp = np.asarray(backend.probs(telemetry.h2d(
                    col.feats[col.probs_rows:col.feats_rows]), head))
                col.probs, col.probs_rows = grow_append(
                    col.probs, col.probs_rows, newp, col.spill)
            if self._prefilter_cfg is not None:
                # centroid summary rides the same epoch discipline: rebuilt
                # only when the tail outgrows the covered prefix, caps
                # refreshed per head bump (copy-on-write — pinned queries
                # keep their (probs, caps) pair)
                col.summary = maintain_summary(
                    col.summary,
                    col.feats[:col.feats_rows] if col.feats_rows else None,
                    col.probs[:col.probs_rows] if col.probs_rows else None,
                    head_epoch=head_v, cfg=self._prefilter_cfg,
                    spill=col.spill, salt=f"{self.session_id}/{si}")
            col.builds += 1
            return kind

        kinds = replica_map(
            refresh, work,
            self.server.shard_scoped("embed", on_death=self._recover_shard,
                                     shard_of=lambda i, it: it[0]))
        self.full_builds += sum(k == "full" for k in kinds)
        self.delta_builds += sum(k == "delta" for k in kinds)
        self.probs_refreshes += sum(k == "probs" for k in kinds)
        self.artifact_builds += 1

    def _artifact_snapshot(self):
        """(feats_l, probs_l, rows_l, key->(shard, row) index) over the
        pool — per-shard immutable row-range views of the incremental
        columns (``artifact_cache: true``, refreshed under a lock so
        racing PSHEA candidates share one refresh) or a from-scratch
        O(pool) build (``artifact_cache: false``, the bit-identity
        oracle). Rows appended after the snapshot is pinned sit beyond
        ``rows_l`` and are invisible to it."""
        return self._artifact_snapshot_ex()[:4]

    def _artifact_snapshot_ex(self):
        """``_artifact_snapshot`` plus the prefilter context pinned under
        the SAME lock hold: per-shard summary refs and the probs head
        epoch the snapshot is consistent at. Summaries are copy-on-write
        (core.prefilter), so a ref pinned here stays a consistent
        (geometry, caps) pair no matter what later refreshes publish."""
        backend = self.server.backend
        if not self.server.config.artifact_cache:
            f, p, r, i = self._build_from_scratch()
            return (f, p, r, i, [None] * self.replicas,
                    [-1] * self.replicas, [0] * self.replicas)
        with self._artifact_lock:
            with telemetry.span("artifact.refresh"):
                self._refresh_artifacts()
            feats_l = [c.feats_view(backend.feat_dim) for c in self._columns]
            probs_l = [c.probs_view(backend.num_classes)
                       for c in self._columns]
            summaries = [c.summary for c in self._columns]
            epochs = [c.probs_head_epoch for c in self._columns]
            lineages = [c.lineage for c in self._columns]
            return feats_l, probs_l, \
                [c.feats_rows for c in self._columns], self._index, \
                summaries, epochs, lineages

    def _build_from_scratch(self):
        """The O(pool) reference engine: re-gather + re-forward every shard
        on every call, no incremental state consulted — what
        ``artifact_cache: false`` runs and what the incremental columns
        must stay bit-identical to."""
        backend = self.server.backend
        with self._lock:
            shard_keys = [list(c.keys) for c in self._columns]
            head = self._head
        head = head or backend.init_head()

        def build(ks):
            if not ks:
                return (np.zeros((0, backend.feat_dim), np.float32),
                        np.zeros((0, backend.num_classes), np.float32))
            feats = self._feats_for(ks)
            return feats, backend.probs(feats, head)

        parts = replica_map(
            build, shard_keys,
            self.server.shard_scoped("embed", on_death=self._recover_shard))
        index: Dict[str, Tuple[int, int]] = {}
        for si, ks in enumerate(shard_keys):
            for li, k in enumerate(ks):
                index[k] = (si, li)
        self.artifact_builds += 1
        return ([p[0] for p in parts], [p[1] for p in parts],
                [len(ks) for ks in shard_keys], index)

    @telemetry.traced("retrain")
    def train_and_eval(self) -> float:
        self.flush()     # linearize after pending async ingests
        keys = list(self._labeled_keys)
        if not keys:
            return 0.0
        backend = self.server.backend
        feats = self._feats_for(keys)
        labels = np.asarray([self._labels[k] for k in keys])
        with self._lock, telemetry.span("retrain.fit"):
            self._head = backend.fit_head(feats, labels, head=None)
            self.head_version += 1
        # the spec's invalidation matrix: a retrain drops the persisted
        # min-dist vectors on every shard (feats columns are untouched, so
        # the NEXT warm query re-folds but re-embeds nothing)
        self._kstate.invalidate()
        with telemetry.span("retrain.eval"):
            if self._eval_set is None:  # train-set accuracy proxy
                return backend.evaluate(feats, labels, self._head)
            return backend.evaluate(*self._eval_set, self._head)

    # ------------------------------------------------------------- query --
    @telemetry.traced("query")
    def query(self, budget: int, strategy: Optional[str] = None,
              target_accuracy: Optional[float] = None, rng_seed: int = 0,
              pshea_workers: Optional[int] = None) -> dict:
        config = self.server.config
        strategy = strategy or config.strategy
        self.flush()       # linearize after pending async ingests
        with self._lock:   # consistent (pool, labels) snapshot
            unlabeled = [k for k in self._keys if k not in self._labels]
        if strategy != "auto":
            return self._query_one(unlabeled, budget, strategy, rng_seed)
        workers = (config.pshea_workers
                   if pshea_workers is None else pshea_workers)
        return self._query_auto(budget,
                                target_accuracy or config.target_accuracy,
                                workers)

    def _query_one(self, unlabeled, budget, strategy, rng_seed,
                   _capture=None) -> dict:
        if (self.replicas > 1 or self._prefilter_cfg is not None
                or self._use_kstate(strategy)):
            # the prefilter and the persisted k-center state live in the
            # sharded paths (their engines ARE the per-shard propose
            # step), so either feature routes through them even at
            # replicas=1 — the 1-shard case of the same bit-identical
            # merge
            return self._query_one_sharded(unlabeled, budget, strategy,
                                           rng_seed, _capture=_capture)
        strat = get_strategy(strategy)
        feats_l, probs_l, rows_l, index = self._artifact_snapshot()
        feats_all, probs_all, n_rows = feats_l[0], probs_l[0], rows_l[0]
        # a concurrent push_data may have appended keys after this query's
        # snapshot was pinned; score only the rows the snapshot covers
        # (the query ordered before the push)
        unlabeled = [k for k in unlabeled
                     if k in index and index[k][1] < n_rows]
        budget = min(budget, len(unlabeled))
        if budget == 0:    # fully-labeled pool: strategies need >= 1 row
            return {"keys": [], "indices": [], "strategy": strategy,
                    "cache": self.server.cache.stats()}
        with telemetry.span("select.gather"):
            rows = np.asarray([index[k][1] for k in unlabeled], np.int64)
            feats = feats_all[rows]
            probs = probs_all[rows]
            labeled_emb = None
            if self._labeled_keys:
                lab_rows = [index[k][1] for k in self._labeled_keys
                            if k in index and index[k][1] < n_rows]
                if lab_rows:
                    labeled_emb = feats_all[np.asarray(lab_rows, np.int64)]
        h2d = telemetry.h2d
        idx = strat.select(
            jax.random.PRNGKey(rng_seed), budget,
            probs=h2d(probs) if "probs" in strat.needs else None,
            embeddings=h2d(feats) if "embeddings" in strat.needs else None,
            labeled_embeddings=(h2d(labeled_emb)
                                if labeled_emb is not None else None))
        idx = np.asarray(idx)
        return {"keys": [unlabeled[i] for i in idx],
                "indices": idx.tolist(), "strategy": strategy,
                "cache": self.server.cache.stats()}

    def _use_kstate(self, strategy: str) -> bool:
        """Whether this query should run with the persisted k-center
        min-dist state. Requires the incremental artifact columns — their
        lineage stamps are what proves a cached vector is still an
        append-extension of the shard's feats."""
        cfg = self.server.config
        return bool(cfg.strategy_state_cache and cfg.artifact_cache
                    and strategy in _WARM_STATE_STRATEGIES)

    def _query_one_sharded(self, unlabeled, budget, strategy,
                           rng_seed, _capture=None) -> dict:
        """One strategy over the replica-sharded pool: per-shard views of
        the unlabeled rows (global order preserved inside each shard) feed
        the strategy's sharded path — selections bit-identical to
        ``replicas=1`` by construction (tests/test_sharding.py)."""
        strat = get_strategy(strategy)
        feats_l, probs_l, rows_l, index, summaries, epochs, lineages = \
            self._artifact_snapshot_ex()

        def covered(k):   # pinned-snapshot bound, per shard
            e = index.get(k)
            return e is not None and e[1] < rows_l[e[0]]

        unlabeled = [k for k in unlabeled if covered(k)]
        budget = min(budget, len(unlabeled))
        if budget == 0:
            return {"keys": [], "indices": [], "strategy": strategy,
                    "cache": self.server.cache.stats()}
        with telemetry.span("select.gather"):
            rows: List[List[int]] = [[] for _ in range(self.replicas)]
            gpos: List[List[int]] = [[] for _ in range(self.replicas)]
            for g, k in enumerate(unlabeled):
                si, li = index[k]
                rows[si].append(li)
                gpos[si].append(g)
            pf_cfg = self._prefilter_cfg
            shards = []
            for si in range(self.replicas):
                r = np.asarray(rows[si], np.int64)
                summ = summaries[si]
                # a summary older than the pinned view is fine (its tail is
                # scanned in full); one COVERING MORE rows than the view — a
                # racing refresh that rebuilt past our pin — is not usable
                if summ is not None and summ.covered > rows_l[si]:
                    summ = None
                shards.append(ShardView(
                    feats=feats_l[si][r] if r.size else feats_l[si][:0],
                    probs=probs_l[si][r] if r.size else probs_l[si][:0],
                    gidx=np.asarray(gpos[si], np.int64),
                    summary=summ if pf_cfg is not None else None,
                    # pool-level context: the prefilter engines and the
                    # persisted-state gather both address rows by their
                    # shard-local pool position (cheap views, always set)
                    pool_rows=r,
                    pool_feats=feats_l[si],
                    probs_epoch=epochs[si]))
            labeled_emb = None
            lab: List[Tuple[int, int]] = []
            if self._labeled_keys:
                lab = [index[k] for k in self._labeled_keys if covered(k)]
                if lab:
                    labeled_emb = telemetry.h2d(
                        np.stack([feats_l[si][li] for si, li in lab]))
        state = None
        if self._use_kstate(strategy) and labeled_emb is not None:
            state = self._kstate.prepare(
                feats_l=feats_l, rows_l=rows_l, lineages=lineages,
                head_version=self.head_version, locs=lab,
                centers=np.asarray(labeled_emb), capture=_capture)
        idx = np.asarray(strat.select_sharded(
            jax.random.PRNGKey(rng_seed), budget, shards,
            labeled_embeddings=labeled_emb,
            executor=self.server.shard_scoped(
                "propose", on_death=self._recover_shard),
            prefilter=pf_cfg, state=state))
        return {"keys": [unlabeled[i] for i in idx],
                "indices": idx.tolist(), "strategy": strategy,
                "cache": self.server.cache.stats()}

    def _query_auto(self, budget: int, target_accuracy: float,
                    workers: int) -> dict:
        """PSHEA (paper Alg. 1) — needs an attached oracle."""
        assert self._oracle is not None, "PSHEA needs attach_oracle(...)"
        session = self
        candidates = self.server._auto_candidates()

        class Task:
            """One independent AL line per strategy. Thread-safe: each
            strategy only touches its own labeled list + round counter, and
            the rng stream is a pure function of (strategy, round)."""

            def __init__(self):
                self.labeled: Dict[str, List[str]] = {s: [] for s in candidates}
                self.round: Dict[str, int] = {s: 0 for s in candidates}

            def initial_accuracy(self):
                return (session.train_and_eval()
                        if session._labeled_keys else 0.1)

            def select_and_label(self, strategy, round_budget):
                self.round[strategy] += 1
                pool = [k for k in session._keys
                        if k not in self.labeled[strategy]]
                res = session._query_one(
                    pool, round_budget, strategy,
                    _strategy_seed(strategy, self.round[strategy]))
                keys = res["keys"]
                self.labeled[strategy].extend(keys)
                return len(keys)

            def train_and_eval(self, strategy):
                keys = self.labeled[strategy]
                labels = session._oracle(keys)
                feats = session._feats_for(keys)
                head = session.server.backend.fit_head(
                    feats, np.asarray(labels))
                return session.server.backend.evaluate(
                    *session._eval_set, head)

        n_strats = len(candidates)
        round_budget = max(budget // (2 * n_strats), 1)
        result = run_pshea(Task(), candidates,
                           target_accuracy=target_accuracy,
                           budget_max=budget, round_budget=round_budget,
                           max_workers=workers)
        return {"strategy": result.best_strategy,
                "accuracy": result.best_accuracy,
                "stop_reason": result.stop_reason,
                "rounds": result.rounds,
                "eliminated": result.eliminated,
                "history": result.history,
                "budget_spent": result.budget_spent}

    # --------------------------------------------------- standing queries --
    def standing_register(self, budget: int, strategy: Optional[str] = None,
                          rng_seed: int = 0) -> dict:
        """Register a ``(budget, strategy)`` subscription: one initial emit
        now, then the ingest worker re-emits after every integrated batch
        and ``standing_poll`` re-emits lazily after sync mutations. Every
        emit is the exact one-shot ``query()`` selection at that moment."""
        config = self.server.config
        strategy = strategy or config.strategy
        if strategy == "auto":
            raise ValueError(
                "standing queries need a concrete strategy (the PSHEA "
                "auto agent consumes oracle labels per round)")
        get_strategy(strategy)            # unknown names fail at register
        if int(budget) < 1:
            raise ValueError("standing query budget must be >= 1")
        self.flush()
        sq = StandingQuery(uuid.uuid4().hex[:12], budget, strategy,
                           rng_seed)
        with self._standing_lock:
            self._standing[sq.qid] = sq
        self._standing_refresh(sq)
        with sq.lock:
            if sq.error is not None:
                err = sq.error
                with self._standing_lock:
                    self._standing.pop(sq.qid, None)
                raise RuntimeError(
                    "standing query initial emit failed") from err
            return {"query_id": sq.qid, "seq": sq.seq,
                    "keys": list(sq.keys or [])}

    def standing_cancel(self, query_id: str,
                        reason: str = "cancelled by client") -> None:
        """Cancel a subscription: later emits are suppressed (including
        from an ingest worker mid-drain) and polls raise."""
        with self._standing_lock:
            sq = self._standing.get(query_id)
        if sq is None:
            raise KeyError(f"unknown standing query {query_id!r}")
        with sq.lock:
            if sq.cancelled is None:
                sq.cancelled = reason

    def standing_poll(self, query_id: str, since: int = 0) -> dict:
        """Emits with ``seq > since`` plus the current cumulative
        selection. Takes the flush() barrier FIRST, so a dead ingest
        worker or a failed async push raises here ticket-style instead of
        the poll serving a stale selection; sync mutations since the last
        emit trigger a fresh emit on this thread."""
        with self._standing_lock:
            sq = self._standing.get(query_id)
        if sq is None:
            raise KeyError(f"unknown standing query {query_id!r}")
        if sq.cancelled is not None:
            raise RuntimeError(
                f"standing query {query_id} cancelled: {sq.cancelled}")
        self.flush()
        self._standing_refresh(sq)
        with sq.lock:
            if sq.error is not None:
                raise RuntimeError(
                    "standing query emit failed") from sq.error
            emits = [dict(e) for e in sq.emits if e["seq"] > int(since)]
            return {"query_id": query_id, "seq": sq.seq,
                    "keys": list(sq.keys or []), "emits": emits,
                    "pool_version": sq.pool_version,
                    "labels_version": sq.labels_version,
                    "head_version": sq.head_version}

    def _notify_standing(self) -> None:
        """Ingest-worker hook: re-emit every live subscription after an
        integrated batch. Swallows nothing it shouldn't — emit failures
        park on the query's ticket (``sq.error``), never kill the
        worker."""
        with self._standing_lock:
            sqs = [sq for sq in self._standing.values()
                   if sq.cancelled is None]
        for sq in sqs:
            self._standing_refresh(sq)

    def _standing_refresh(self, sq: StandingQuery) -> None:
        """Emit iff the session moved since ``sq``'s last emit. Never
        raises: failures park on ``sq.error`` for the next poll."""
        if sq.cancelled is not None:
            return
        with sq.lock:
            if sq.cancelled is not None:
                return
            try:
                self._standing_emit_locked(sq)
                sq.error = None
            except BaseException as e:
                sq.error = e

    def _standing_emit_locked(self, sq: StandingQuery) -> None:
        """One emit attempt; caller holds ``sq.lock``. Replays the stored
        selection against just the delta rows when provably unchanged,
        otherwise runs the full (bit-identical to ``query()``) path."""
        with self._lock:
            unlabeled = [k for k in self._keys if k not in self._labels]
            pv, lv, hv = (self.pool_version, self.labels_version,
                          self.head_version)
        if sq.keys is not None and (pv, lv, hv) == (
                sq.pool_version, sq.labels_version, sq.head_version):
            return                           # nothing moved: stay quiet
        keys = self._standing_replay(sq, unlabeled, lv, hv)
        if keys is not None:
            mode, values = "replay", sq.values
        else:
            cap: List[float] = []
            res = self._query_one(unlabeled, sq.budget, sq.strategy,
                                  sq.rng_seed, _capture=cap)
            keys, mode = res["keys"], "full"
            values = (cap if len(cap) == sq.budget
                      and len(keys) == sq.budget else None)
        prev = sq.keys or []
        prev_set, new_set = set(prev), set(keys)
        sq.seq += 1
        sq.emits.append({
            "seq": sq.seq, "mode": mode,
            "pool_version": pv, "labels_version": lv, "head_version": hv,
            "keys": list(keys),
            "added": [k for k in keys if k not in prev_set],
            "removed": [k for k in prev if k not in new_set]})
        sq.keys = list(keys)
        sq.values = values
        sq.n_unlabeled = len(unlabeled)
        sq.pool_version, sq.labels_version, sq.head_version = pv, lv, hv
        with self._standing_lock:
            self.standing_emits += 1
            if mode == "replay":
                self.standing_replay_emits += 1
            else:
                self.standing_full_emits += 1

    def _standing_replay(self, sq: StandingQuery, unlabeled, lv,
                         hv) -> Optional[List[str]]:
        """O(delta) emit: prove the stored selection is unchanged over the
        grown pool by streaming ONLY the delta rows, or return None for an
        honest full recompute.

        Eligibility: unweighted warm-started coreset with a full-budget
        previous emit and unchanged labels/head — then the previous
        unlabeled list is an exact prefix of the current one (append-only
        keys), every old row's min-dist trajectory is unchanged, and the
        stored per-slot winner scores remain the max over all old rows.
        A delta row displaces slot j iff its score after folding centers
        0..j-1 STRICTLY beats the stored winner score (ties lose on the
        higher global index every appended row has), so ``budget`` fused
        rounds over the delta rows decide the whole emit."""
        cfg = self.server.config
        if not (cfg.standing_replay and cfg.strategy_state_cache
                and cfg.artifact_cache):
            return None
        if sq.strategy != "coreset" or self._prefilter_cfg is not None:
            return None
        if sq.keys is None or sq.values is None:
            return None
        if (sq.labels_version, sq.head_version) != (lv, hv):
            return None
        if len(sq.keys) != sq.budget or len(sq.values) != sq.budget:
            return None
        n_prev = sq.n_unlabeled
        if len(unlabeled) < n_prev:
            return None
        delta = unlabeled[n_prev:]
        if not delta:
            return list(sq.keys)
        feats_l, probs_l, rows_l, index, summaries, epochs, lineages = \
            self._artifact_snapshot_ex()

        def covered(k):
            e = index.get(k)
            return e is not None and e[1] < rows_l[e[0]]

        if not all(covered(k) for k in delta):
            return None                      # racing snapshot: full path
        lab = [index[k] for k in self._labeled_keys if covered(k)]
        if not lab:
            return None
        centers = np.stack([feats_l[si][li] for si, li in lab])
        state = self._kstate.prepare(
            feats_l=feats_l, rows_l=rows_l, lineages=lineages,
            head_version=self.head_version, locs=lab, centers=centers)
        if state is None:
            return None
        sel_centers = []
        for k in sq.keys:
            if not covered(k):
                return None
            si, li = index[k]
            sel_centers.append(feats_l[si][li])
        drows = [index[k] for k in delta]
        import jax.numpy as jnp
        from repro.kernels.pairwise import ops
        # delta rows' persisted min-dists vs the labeled centers + their
        # embeddings — O(delta) gathers, no pool stream
        mj = jnp.asarray(np.asarray(
            [state.minds[si][li] for si, li in drows], np.float32))
        ej = jnp.asarray(np.stack([feats_l[si][li] for si, li in drows]),
                         jnp.float32)
        no_mask = jnp.full((1,), -1, jnp.int32)
        best = float(jnp.max(ops.masked_weighted_score(mj)))
        for j in range(sq.budget):
            if best > sq.values[j]:
                return None                  # slot j displaced: diverge
            if j + 1 < sq.budget:
                mj, _, lv_ = ops.greedy_round(
                    ej, mj, jnp.asarray(sel_centers[j],
                                        jnp.float32)[None, :], no_mask)
                best = float(lv_)
        return list(sq.keys)

    # -------------------------------------------------------------- misc --
    def stats(self) -> dict:
        with self._ingest_cv:
            pending = len(self._ingest_queue) + (1 if self._ingest_busy
                                                 else 0)
            ingest = {
                "pending": pending,
                "rows": self._ingest_rows,
                "bytes": self._ingest_bytes,
                "rows_hw": self._ingest_rows_hw,
                "bytes_hw": self._ingest_bytes_hw,
                "depth_hw": self._ingest_depth_hw,
                "shed": self._ingest_shed,
                "policy": self.server.config.ingest_policy,
                "max_rows": self.server.config.ingest_max_rows,
                "max_bytes": self.server.config.ingest_max_bytes,
            }
        return {"pool": len(self._keys), "labeled": len(self._labeled_keys),
                "pool_version": self.pool_version,
                "head_version": self.head_version,
                "labels_version": self.labels_version,
                "artifact_builds": self.artifact_builds,
                # incremental-artifact observability: build-kind tallies +
                # the per-shard epoch/row state a delta build is judged by
                "artifacts": {
                    "builds": self.artifact_builds,
                    "full_builds": self.full_builds,
                    "delta_builds": self.delta_builds,
                    "probs_refreshes": self.probs_refreshes,
                    "shard_builds": [c.builds for c in self._columns],
                    "rows_epoch": [c.rows_epoch for c in self._columns],
                    "feats_rows": [c.feats_rows for c in self._columns],
                    "head_epoch": self.head_version,
                    # shard-spill counters (0s when shard_ram_bytes == 0)
                    "spill_events": (self._spill.spill_events
                                     if self._spill else 0),
                    "spilled_bytes": (self._spill.spilled_bytes
                                      if self._spill else 0),
                    # centroid-prefilter summaries per shard (None = that
                    # shard full-scans: below min_rows or prefilter off)
                    "summary_builds": [
                        (c.summary.builds if c.summary is not None else 0)
                        for c in self._columns],
                    "summary_covered": [
                        (c.summary.covered if c.summary is not None else 0)
                        for c in self._columns],
                },
                "replicas": self.replicas,
                # worker deaths recovered by resetting this session's shard
                # columns (re-embed from raw + content keys on retry)
                "worker_recoveries": self.shard_recoveries,
                "ingest_pending": pending,
                "ingest_batches": self.ingest_batches,
                # bounded-ingest observability: outstanding rows/bytes,
                # high-waters, and the shed counter (policy == "shed")
                "ingest": ingest,
                # persisted k-center min-dist state (KCenterStateCache):
                # rebuilds = from-scratch folds, extends = O(delta-row)
                # appends, center_extends = O(new-center) folds over old
                # rows, invalidations = drops (retrain/lineage/center-
                # prefix breaks), rows_reused vs rows_extended = the
                # incremental win
                "strategy_state": {
                    "enabled": self.server.config.strategy_state_cache,
                    **self._kstate.stats()},
                "standing_queries": self._standing_stats()}

    def _standing_stats(self) -> dict:
        with self._standing_lock:
            live = sum(1 for sq in self._standing.values()
                       if sq.cancelled is None)
            return {"registered": len(self._standing), "live": live,
                    "emits": self.standing_emits,
                    "replay_emits": self.standing_replay_emits,
                    "full_emits": self.standing_full_emits}


class ALServer:
    """Hosts many ``ALSession`` tenants over one backend + embedding cache.

    All per-pool methods take ``session=`` (a session id from
    ``create_session``); omitted, they address the always-present default
    session — the original single-tenant API keeps working verbatim."""

    def __init__(self, config: Optional[ALServiceConfig] = None,
                 config_path: Optional[str] = None,
                 backend: Optional[FeatureBackend] = None,
                 fetch_fn: Optional[Callable] = None,
                 fetch_latency_s: float = 0.0,
                 failure_injector: Optional[PhaseFailureInjector] = None):
        if config is None:
            config = (ALServiceConfig.from_yaml(config_path)
                      if config_path else ALServiceConfig())
        self.config = config
        self.backend = (backend if backend is not None
                        else make_backend(config.model_name, config=config))
        self.cache = EmbeddingCache(config.cache_bytes,
                                    config.cache_spill_dir)
        self.fetch_fn = fetch_fn or (lambda x: x)
        self.fetch_latency_s = fetch_latency_s
        self.failure_injector = failure_injector
        self._sessions: Dict[str, ALSession] = {}
        self._sessions_lock = threading.Lock()
        self._shard_runtime: Optional[ShardWorkerPool] = None
        self._shard_pool_lock = threading.Lock()
        # op accounting: pool rows run through the feature extractor
        # (pipeline ingest + evicted-entry recompute; batcher padding rows
        # excluded). The incremental-artifact contract is stated in these
        # units: push B rows == B embeds, train_and_eval == 0, query after
        # a push == 0 (delta rows come out of the EmbeddingCache).
        self.embed_rows = 0
        self.embed_calls = 0
        self._embed_lock = threading.Lock()
        # serve_tcp points this at RPCServer.stats so stats() can report
        # admission/fairness counters; None when served in-process
        self._transport_stats: Optional[Callable[[], dict]] = None
        self.create_session(DEFAULT_SESSION)

    def count_embeds(self, rows: int) -> None:
        with self._embed_lock:
            self.embed_rows += int(rows)
            self.embed_calls += 1

    def shard_runtime(self) -> Optional[ShardWorkerPool]:
        """The shard-worker runtime (distributed.worker): one supervised
        lane per replica shard — straggler-timed, failure-injectable,
        restartable, device-pinned on multi-device hosts. Lazy; None at
        replicas=1 (the serial path needs no workers)."""
        if self.config.replicas <= 1:
            return None
        with self._shard_pool_lock:
            if self._shard_runtime is None:
                cfg = self.config
                self._shard_runtime = ShardWorkerPool(
                    cfg.replicas, timeout_s=cfg.worker_timeout_s,
                    max_retries=cfg.worker_retries,
                    backoff_s=cfg.worker_backoff_s,
                    injector=self.failure_injector)
            return self._shard_runtime

    def shard_executor(self):
        """Back-compat seam: the worker pool duck-types ``executor.map``,
        so callers that predate the runtime keep working (default phase,
        no recovery hook)."""
        return self.shard_runtime()

    def shard_scoped(self, phase: str, on_death: Optional[Callable] = None,
                     shard_of: Optional[Callable] = None):
        """Phase-scoped executor facade for ``replica_map`` fan-outs: a
        worker death during ``phase`` triggers ``on_death(shard)`` (e.g.
        the session's column-reset recovery) before the bounded retry.
        None at replicas=1."""
        rt = self.shard_runtime()
        if rt is None:
            return None
        return rt.scoped(phase, on_death=on_death, shard_of=shard_of)

    # ---------------------------------------------------------- sessions --
    def create_session(self, session_id: Optional[str] = None) -> str:
        sid = session_id or uuid.uuid4().hex[:16]
        with self._sessions_lock:
            if sid in self._sessions:
                raise ValueError(f"session {sid!r} already exists")
            self._sessions[sid] = ALSession(self, sid)
        return sid

    def session(self, session_id: Optional[str] = None) -> ALSession:
        sid = session_id or DEFAULT_SESSION
        with self._sessions_lock:
            try:
                return self._sessions[sid]
            except KeyError:
                raise KeyError(f"unknown session {sid!r}; call "
                               f"create_session() first") from None

    def close_session(self, session_id: str) -> None:
        if session_id == DEFAULT_SESSION:
            raise ValueError("the default session cannot be closed")
        with self._sessions_lock:
            sess = self._sessions.pop(session_id, None)
        if sess is not None:
            sess.close()     # stop its ingest worker

    def session_ids(self) -> List[str]:
        with self._sessions_lock:
            return list(self._sessions)

    # -------------------------------------------- shared feature pipeline --
    def _process(self, todo, *, pipelined: bool, chunk: int = 64):
        bs = max(self.config.batch_size, 1)
        # pad_to_max: every inference batch is padded to the one canonical
        # (bs, ...) shape, so with a row-local backend forward each row's
        # features are bitwise independent of how pushes were chunked or
        # interleaved (the batch-insensitivity contract standing queries
        # and the content-addressed cache rely on)
        # a shard lane pins its device with jax.default_device, which is
        # thread-local: carry it into the batcher thread that runs the
        # forward, or every shard would embed on the first device
        device = jax.config.jax_default_device

        def infer_batch(stacked, n_valid):
            with jax.default_device(device):
                return self._infer_batch(stacked, n_valid)

        batcher = DynamicBatcher(infer_batch, max_batch=bs, pad_to_max=True)

        @telemetry.traced("embed.fetch")
        def fetch(chunk_items):
            if self.fetch_latency_s:
                time.sleep(self.fetch_latency_s)
            return [(k, self.fetch_fn(v)) for k, v in chunk_items]

        @telemetry.traced("embed.preprocess")
        def preprocess(chunk_items):
            ks = [k for k, _ in chunk_items]
            raw = np.stack([np.asarray(v) for _, v in chunk_items])
            return ks, self.backend.preprocess(raw)

        @telemetry.traced("embed.infer")
        def infer(args):
            ks, batch = args
            feats = batcher.score(list(batch))
            return list(zip(ks, feats))

        stages = [Stage("fetch", fetch), Stage("preprocess", preprocess),
                  Stage("infer", infer)]
        pipe = StagePipeline(stages)
        chunks = [todo[i:i + chunk] for i in range(0, len(todo), chunk)]
        runner = pipe.run if pipelined else pipe.run_serial
        try:
            for out in runner(chunks):
                for k, f in out:
                    self.cache.put(k, np.asarray(f))
        finally:
            batcher.close()

    def _embed_chunk(self, raw: np.ndarray, bs: int, *,
                     backend: FeatureBackend) -> np.ndarray:
        """One canonical embed chunk: preprocess, zero-pad to the one
        ``bs``-row shape, feature forward."""
        x = np.asarray(backend.preprocess(raw))
        n = x.shape[0]
        if n < bs:           # zero-pad to the one canonical shape
            x = np.concatenate(
                [x, np.zeros((bs - n,) + x.shape[1:], x.dtype)])
        return np.asarray(backend.features(x))[:n]

    def _infer_batch(self, stacked: np.ndarray, n_valid: int):
        with telemetry.span("embed.forward"):
            feats = self.backend.features(stacked)
        self.count_embeds(n_valid)
        return [feats[i] for i in range(n_valid)]

    def _process_replicated(self, todo):
        """Embed a drained ingest batch: group items by replica shard and
        run the stage pipeline per shard in parallel (each group rides its
        own DynamicBatcher). Falls back to one pipeline at replicas=1."""
        replicas = max(self.config.replicas, 1)
        if replicas == 1:
            return self._process(todo, pipelined=True)
        groups = [[] for _ in range(replicas)]
        for k, it in todo:
            groups[replica_of(k, replicas)].append((k, it))
        groups = [g for g in groups if g]
        if len(groups) == 1:
            return self._process(groups[0], pipelined=True)
        # ingest-phase fan-out: a worker killed mid-drain restarts and the
        # group's pipeline retries — cache puts are content-addressed and
        # idempotent, and the rows append only after every group lands, so
        # a recovered kill loses nothing
        replica_map(lambda g: self._process(g, pipelined=True), groups,
                    self.shard_scoped("ingest"))

    def _auto_candidates(self) -> List[str]:
        """The PSHEA agent's strategy registry: the paper's 7, plus the
        weighted fused-round hybrids when configured ("hybrid")."""
        mode = self.config.auto_candidates
        if mode == "hybrid":
            return PAPER_SEVEN + HYBRIDS
        if mode != "paper":
            # a typo must not silently degrade to the default set
            raise ValueError(f"auto_candidates must be 'paper' or 'hybrid', "
                             f"got {mode!r}")
        return list(PAPER_SEVEN)

    # --------------------------------------- single-tenant facade (compat) --
    def push_data(self, items: Sequence[np.ndarray], pipelined: bool = True,
                  session: Optional[str] = None,
                  asynchronous: bool = False):
        return self.session(session).push_data(items, pipelined=pipelined,
                                               asynchronous=asynchronous)

    def flush(self, session: Optional[str] = None,
              timeout: Optional[float] = None) -> None:
        return self.session(session).flush(timeout=timeout)

    def attach_oracle(self, oracle: Callable[[Sequence[str]], Sequence[int]],
                      eval_x: np.ndarray, eval_y: np.ndarray,
                      session: Optional[str] = None):
        return self.session(session).attach_oracle(oracle, eval_x, eval_y)

    def label(self, keys: Sequence[str], labels: Sequence[int],
              session: Optional[str] = None):
        return self.session(session).label(keys, labels)

    def train_and_eval(self, session: Optional[str] = None) -> float:
        return self.session(session).train_and_eval()

    def query(self, budget: int, strategy: Optional[str] = None,
              target_accuracy: Optional[float] = None, rng_seed: int = 0,
              session: Optional[str] = None,
              pshea_workers: Optional[int] = None) -> dict:
        return self.session(session).query(budget, strategy, target_accuracy,
                                           rng_seed, pshea_workers)

    def standing_register(self, budget: int, strategy: Optional[str] = None,
                          rng_seed: int = 0,
                          session: Optional[str] = None) -> dict:
        return self.session(session).standing_register(
            budget, strategy, rng_seed)

    def standing_cancel(self, query_id: str,
                        reason: str = "cancelled by client",
                        session: Optional[str] = None) -> None:
        return self.session(session).standing_cancel(query_id, reason)

    def standing_poll(self, query_id: str, since: int = 0,
                      session: Optional[str] = None) -> dict:
        return self.session(session).standing_poll(query_id, since)

    def stats(self, session: Optional[str] = None) -> dict:
        s = self.session(session).stats()
        s["cache"] = self.cache.stats()
        s["embeds"] = {"rows": self.embed_rows, "calls": self.embed_calls}
        s["sessions"] = len(self.session_ids())
        rt = self._shard_runtime       # no lazy spin-up just for stats
        s["workers"] = (rt.stats() if rt is not None else {
            "lanes": 0, "tasks": 0, "restarts": 0,
            "straggler_events": 0})
        # transport admission/fairness counters (serve_tcp wires this;
        # absent/in-process -> a disabled placeholder, same shape)
        ts = self._transport_stats
        s["admission"] = (ts() if ts is not None else {"enabled": False})
        # the in-program spans and counters, process-wide
        s["trace"] = telemetry.snapshot()
        return s
