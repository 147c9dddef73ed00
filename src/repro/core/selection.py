"""Distributed AL selection over a device mesh (pod-scale data selection)
and host-level replica sharding (service scale-out).

The paper's stage-level parallelism scales out here: every data shard scores
its slice of the pool locally, then

  * ``distributed_top_k``  — budget-B selection via local top-B + all_gather
    merge (log-depth reduction semantics; each device ships only B
    candidates, not its whole shard), and
  * ``distributed_k_center`` — greedy k-center where each round does a local
    argmax + a tiny all_gather of (dist, index, vector) candidates,

both as ``shard_map`` programs over the ``data`` axis with ``jax.lax``
collectives. Selection cost per round is O(pool/n_devices) compute +
O(n_devices x d) comm — independent of global pool size.

The second half of this module generalizes the same local-propose /
global-merge round structure to *host-level replica shards* — the serving
layer's ``replicas: N`` config. A pool is hash-partitioned by content key
(``replica_of``), each shard scores its rows on a thread-pool worker, and
the merges (``replica_top_k`` for the uncertainty family,
``replica_greedy_select`` for every greedy/k-center-lineage strategy) are
constructed to be bit-identical to the single-pool path:

  * every per-row computation (distances, uncertainty scores, weights) is
    slice-invariant — a shard's rows produce the same floats they would
    inside the full matrix;
  * shard-local row order preserves global pool order, so a shard-local
    argmax tie-break (lowest local index) IS the lowest global index within
    that shard;
  * cross-shard merges order candidates by (value desc, global index asc),
    exactly ``jnp.argmax`` / ``jax.lax.top_k`` semantics on the
    concatenated vector.

The greedy loop runs on the device: on one device, or, when every
shard's lane has a device of its own, as one SPMD program across those
devices whose per-pick merge is a collective (``_lane_loop``).

``ShardColumns`` + ``grow_append`` are the storage side of the same
contract: each shard's (feats, probs) artifact columns live in growable
append-only buffers with per-column epoch stamps, so a data change
refreshes O(delta) rows on the touched shards only (incremental view
maintenance) while queries pin immutable row-range snapshots.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import threading
import zlib
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.shard_map import shard_map as _shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common import telemetry


def shard_map(f, **kw):
    """shard_map with the static-replication check disabled (outputs are
    made replicated *dynamically* by the trailing all_gathers)."""
    try:
        return _shard_map(f, check_vma=False, **kw)
    except TypeError:  # older jax spelling
        return _shard_map(f, check_rep=False, **kw)


def distributed_top_k(scores: jax.Array, budget: int, mesh: Mesh,
                      axis: str = "data") -> jax.Array:
    """Global top-``budget`` indices of a data-sharded score vector.

    scores: (N,) sharded over ``axis``. Returns (budget,) global indices,
    replicated.
    """
    n_dev = mesh.shape[axis]
    N = scores.shape[0]
    shard = N // n_dev

    def local(s):
        s = s.reshape(-1)
        b = min(budget, s.shape[0])
        v, i = jax.lax.top_k(s, b)
        if b < budget:
            v = jnp.pad(v, (0, budget - b), constant_values=-jnp.inf)
            i = jnp.pad(i, (0, budget - b))
        base = jax.lax.axis_index(axis) * shard
        gi = i + base
        # merge: gather every device's candidates, take global top-B
        av = jax.lax.all_gather(v, axis)            # (n_dev, B)
        ai = jax.lax.all_gather(gi, axis)
        fv, fi = jax.lax.top_k(av.reshape(-1), budget)
        return ai.reshape(-1)[fi].astype(jnp.int32)

    fn = shard_map(local, mesh=mesh, in_specs=P(axis),
                   out_specs=P())
    return fn(scores)


def distributed_k_center(embeddings: jax.Array, budget: int, mesh: Mesh,
                         axis: str = "data",
                         init_center: Optional[jax.Array] = None,
                         impl: str = "auto",
                         weights: Optional[jax.Array] = None) -> jax.Array:
    """Greedy k-center over a data-sharded (N, d) embedding pool.

    Per round: all_gather the previous round's (value, global index, vector)
    candidates -> replicated argmax picks the winner -> ONE fused local pool
    pass (repro/kernels/pairwise.greedy_round) folds the winning vector into
    the local min-dists, masks the winner on its home shard, and yields the
    next local candidate. Returns (budget,) global indices.

    ``weights`` (optional (N,), sharded like the pool) makes every local
    pass the *weighted* fused round: local candidates — and therefore the
    cross-shard argmax, which compares the rounds' returned scores — rank
    by ``min_dist * weight``. The hybrid strategies ship uncertainty here.
    """
    from repro.kernels.pairwise import ops
    n_dev = mesh.shape[axis]
    N, d = embeddings.shape
    shard = N // n_dev
    weighted = weights is not None
    w_arr = (jnp.ones((N,), jnp.float32) if weights is None
             else weights.astype(jnp.float32))

    def local(emb, wloc):
        emb = emb.reshape(shard, d).astype(jnp.float32)
        wloc = wloc.reshape(shard)
        base = jax.lax.axis_index(axis) * shard
        sel = jnp.zeros((budget,), jnp.int32)
        start = 0
        if init_center is None:
            # seed = global point 0; it IS the first returned center
            # (sel[0] stays 0 == the seed's global index)
            c0 = jax.lax.all_gather(emb[:1], axis)[0, 0]
            start = 1
        else:
            c0 = init_center.astype(jnp.float32)
        mind = jnp.sum((emb - c0) ** 2, axis=-1)
        if init_center is None:
            on_shard0 = jax.lax.axis_index(axis) == 0
            mind = jnp.where((jnp.arange(shard) == 0) & on_shard0, -1.0, mind)
        if weighted:
            score0 = ops.masked_weighted_score(mind, wloc)
        else:
            score0 = mind
        li = jnp.argmax(score0).astype(jnp.int32)
        lv = score0[li]

        def body(i, carry):
            mind, sel, li, lv = carry
            cand_v = jax.lax.all_gather(lv, axis)          # (n_dev,)
            cand_i = jax.lax.all_gather(li + base, axis)
            cand_e = jax.lax.all_gather(emb[li], axis)     # (n_dev, d)
            w = jnp.argmax(cand_v)
            sel = sel.at[i].set(cand_i[w].astype(jnp.int32))
            center = cand_e[w]
            # never re-pick the winner on its home shard
            is_mine = (cand_i[w] >= base) & (cand_i[w] < base + shard)
            mask = jnp.where(is_mine, cand_i[w] - base, -1).astype(jnp.int32)
            mind, li, lv = ops.greedy_round(
                emb, mind, center[None, :], mask[None],
                weights=wloc if weighted else None, impl=impl)
            return mind, sel, li, lv

        _, sel, _, _ = jax.lax.fori_loop(start, budget, body,
                                         (mind, sel, li, lv))
        return sel

    fn = shard_map(local, mesh=mesh, in_specs=(P(axis), P(axis)),
                   out_specs=P())
    return fn(embeddings, w_arr)


def sharded_scores(logits: jax.Array, kind: str, mesh: Mesh,
                   axis: str = "data") -> jax.Array:
    """Data-parallel fused uncertainty scoring (stays sharded)."""
    from repro.kernels.uncertainty import ops

    def local(lg):
        return ops.uncertainty_scores(lg, kind)

    fn = shard_map(local, mesh=mesh, in_specs=P(axis, None),
                   out_specs=P(axis))
    return fn(logits)


# ===========================================================================
# Host-level replica sharding (the serving layer's ``replicas: N``)
# ===========================================================================

def replica_of(key: str, replicas: int) -> int:
    """Content-hash shard assignment: stable across pool mutations, so a
    sample lands on the same replica no matter when (or how often) it is
    pushed."""
    return zlib.crc32(key.encode()) % max(int(replicas), 1)


@dataclasses.dataclass
class ShardView:
    """One replica shard's slice of the (unlabeled) pool.

    Rows are in global pool order; ``gidx[i]`` is row ``i``'s position in
    that global order. Preserving the order inside each shard is what makes
    shard-local argmax tie-breaks (lowest local index) compose with the
    cross-shard merge (lowest global index) into exactly the single-pool
    ``jnp.argmax`` rule.
    """
    feats: np.ndarray                 # (n, d)
    probs: Optional[np.ndarray]       # (n, C) or None
    gidx: np.ndarray                  # (n,) int64 global positions
    # -- centroid-prefilter context (optional; None = ungated) ----------
    # the shard's pinned CentroidSummary (core.prefilter), its pool-local
    # row ids for the view rows, the full pinned (rows, d) feats view the
    # summary's permutation indexes into, and the probs head epoch the
    # snapshot was pinned at (gates the summary's cached score caps)
    summary: Optional[Any] = None
    pool_rows: Optional[np.ndarray] = None    # (n,) int64 shard-local rows
    pool_feats: Optional[np.ndarray] = None   # (rows, d) pinned feats view
    probs_epoch: int = -1
    # the shard's lane device (``ShardWorkerPool.devices``); None = unpinned
    device: Optional[Any] = None

    @property
    def n(self) -> int:
        return int(self.gidx.shape[0])


class ColumnSpill:
    """mmap-backed allocation for artifact columns past a RAM budget.

    Buffers whose capacity exceeds ``ram_bytes`` are allocated as
    ``np.memmap`` files instead of RAM arrays, so a shard's pool can
    outgrow memory with NO change to the epoch/snapshot contract: the
    append-only discipline means spilled rows are immutable once written,
    and a pinned ``buf[:rows]`` view over a memmap behaves exactly like
    one over a RAM array.

    Files follow the cache's atomic-publish idiom (size via truncate on a
    tmp name, then ``os.replace``) so a killed process never leaves a
    half-sized file for a later reader to map. Unlike the cache's zstd
    spill, columns stay uncompressed — they are live random-access
    mappings, not cold blobs. ``release`` unlinks a superseded buffer's
    file; POSIX keeps the data alive for any still-pinned mapping, so
    snapshot views survive both growth and release.
    """

    def __init__(self, directory: str, ram_bytes: int):
        self.directory = directory
        self.ram_bytes = int(ram_bytes)
        os.makedirs(directory, exist_ok=True)
        self._lock = threading.Lock()
        self._seq = 0
        self.spill_events = 0       # allocations that went to disk
        self.spilled_bytes = 0      # capacity bytes currently mmap-backed

    def should_spill(self, nbytes: int) -> bool:
        return int(nbytes) > self.ram_bytes

    def allocate(self, shape: Tuple[int, ...], dtype) -> np.memmap:
        dt = np.dtype(dtype)
        nbytes = int(np.prod(shape)) * dt.itemsize
        with self._lock:
            seq = self._seq
            self._seq += 1
        final = os.path.join(self.directory, f"col-{seq:08d}.mmap")
        tmp = final + f".tmp.{os.getpid()}"
        os.makedirs(self.directory, exist_ok=True)   # survive a cleanup race
        with open(tmp, "wb") as f:
            f.truncate(max(nbytes, 1))
        os.replace(tmp, final)
        # open AFTER the rename so the mapping's .filename is the final
        # path — release() unlinks by that name
        m = np.memmap(final, dtype=dt, mode="r+", shape=shape)
        with self._lock:
            self.spill_events += 1
            self.spilled_bytes += nbytes
        return m

    def release(self, arr) -> None:
        """Unlink a superseded buffer's backing file (no-op for RAM
        arrays). Pinned snapshot views keep reading the unlinked data."""
        if not isinstance(arr, np.memmap):
            return
        with self._lock:
            self.spilled_bytes -= int(arr.nbytes)
        try:
            os.unlink(arr.filename)
        except OSError:
            pass

    def adopt(self, arr: np.ndarray) -> np.ndarray:
        """Copy ``arr`` into a fresh mmap buffer when it is past the RAM
        budget; return it unchanged otherwise (whole-buffer allocations
        such as head-refresh probs and summary permutations)."""
        if not self.should_spill(arr.nbytes):
            return arr
        m = self.allocate(arr.shape, arr.dtype)
        m[...] = arr
        return m


def grow_append(buf: Optional[np.ndarray], rows: int, new: np.ndarray,
                spill: Optional[ColumnSpill] = None
                ) -> Tuple[np.ndarray, int]:
    """Append ``new`` rows to a growable buffer; amortized O(rows added).

    Returns ``(buffer, valid_rows)``. Capacity doubles on overflow, so a
    pool built from B-row pushes costs O(N) row copies total instead of the
    O(N^2) of re-stacking the pool per push. The append discipline is what
    makes buffers safe to snapshot concurrently: rows ``[0:rows]`` are
    never rewritten (a reallocation leaves the old buffer intact for any
    pinned view), so a reader holding ``buf[:rows]`` can never observe a
    mutation.

    With ``spill`` (a ``ColumnSpill``), a reallocation whose capacity
    bytes exceed the spill's RAM budget lands in an mmap-backed file
    instead of RAM, and the superseded buffer's file (if any) is
    unlinked — pinned views keep their mapping either way.
    """
    new = np.asarray(new)
    if buf is not None and rows and (buf.shape[1:] != new.shape[1:]
                                     or buf.dtype != new.dtype):
        # appending incompatible rows would either crash the copy or
        # silently cast the old rows — both corrupt the column; fail loud
        raise ValueError(
            f"grow_append: rows of shape {new.shape[1:]}/{new.dtype} "
            f"cannot extend a buffer of {buf.shape[1:]}/{buf.dtype}")
    need = rows + int(new.shape[0])
    if buf is None or buf.shape[0] < need or buf.shape[1:] != new.shape[1:] \
            or buf.dtype != new.dtype:     # latter two only when rows == 0
        cap = max(need, 2 * (0 if buf is None else int(buf.shape[0])), 8)
        shape = (cap,) + new.shape[1:]
        nbytes = int(np.prod(shape)) * new.dtype.itemsize
        if spill is not None and spill.should_spill(nbytes):
            grown = spill.allocate(shape, new.dtype)
        else:
            grown = np.empty(shape, new.dtype)
        if buf is not None and rows:
            grown[:rows] = buf[:rows]
        if spill is not None and buf is not None:
            spill.release(buf)
        buf = grown
    buf[rows:need] = new
    return buf, need


class ShardColumns:
    """Incrementally-maintained artifact columns for ONE replica shard.

    The two columns have decoupled lifetimes, each stamped with the epoch
    it is fresh at:

    ``feats``
        Growable (cap, d) buffer; rows ``[0:feats_rows]`` valid, stamped
        ``feats_epoch`` (the shard's ``rows_epoch`` at refresh). A delta
        refresh embeds ONLY ``keys[feats_rows:]`` and extends the buffer
        in place — O(delta), never a full re-stack.
    ``probs``
        Growable (cap, C) buffer; rows ``[0:probs_rows]`` valid, stamped
        ``probs_head_epoch``. A head bump recomputes all rows from the
        cached feats into a FRESH buffer (zero re-embeds, and pinned
        snapshots keep their old rows); a rows-only change appends probs
        for just the new rows.

    Thread contract: mutated only under the owning session's artifact
    lock; ``keys`` is append-only (appends happen under the session pool
    lock), so slicing it against a captured bound is race-free.
    """

    __slots__ = ("keys", "rows_epoch", "feats", "feats_rows", "feats_epoch",
                 "probs", "probs_rows", "probs_head_epoch", "builds",
                 "spill", "summary", "lineage")

    def __init__(self, spill: Optional[ColumnSpill] = None):
        self.keys: list = []          # shard-local key order == global order
        self.rows_epoch = 0           # bumps per row-appending event
        self.feats: Optional[np.ndarray] = None
        self.feats_rows = 0
        self.feats_epoch = 0
        self.probs: Optional[np.ndarray] = None
        self.probs_rows = 0
        self.probs_head_epoch = -1    # -1 = never computed
        self.builds = 0               # refresh events that touched this shard
        self.spill = spill            # None = RAM-only columns
        self.summary = None           # CentroidSummary (core.prefilter)
        self.lineage = 0              # bumps when rows [0:feats_rows] are
        #                               no longer append-extensions of what a
        #                               cached per-row state saw (reset())

    def reset(self) -> None:
        """Drop both columns (the non-incremental full-rebuild path)."""
        if self.spill is not None:
            self.spill.release(self.feats)
            self.spill.release(self.probs)
            if self.summary is not None:
                self.spill.release(getattr(self.summary, "xperm", None))
        self.feats, self.feats_rows, self.feats_epoch = None, 0, 0
        self.probs, self.probs_rows, self.probs_head_epoch = None, 0, -1
        self.summary = None
        self.lineage += 1

    def feats_view(self, d: int) -> np.ndarray:
        if self.feats is None:
            return np.zeros((0, d), np.float32)
        return self.feats[:self.feats_rows]

    def probs_view(self, c: int) -> np.ndarray:
        if self.probs is None:
            return np.zeros((0, c), np.float32)
        return self.probs[:self.probs_rows]


def replica_map(fn: Callable, items: Sequence, executor=None) -> list:
    """Apply ``fn`` to every item — across the shard thread pool when one
    is given (per-shard scoring runs in parallel, under the caller's span),
    serially otherwise."""
    items = list(items)
    if executor is None or len(items) <= 1:
        return [fn(it) for it in items]
    ctx = telemetry.context()

    def run(it):
        with telemetry.attached(ctx):
            return fn(it)

    return list(executor.map(run, items))


def replica_total(shards: Sequence[ShardView]) -> int:
    return sum(s.n for s in shards)


def locate_row(shards: Sequence[ShardView], gidx: int) -> Tuple[int, int]:
    """(shard, local row) of a global pool position."""
    for si, s in enumerate(shards):
        j = int(np.searchsorted(s.gidx, gidx))
        if j < s.n and int(s.gidx[j]) == gidx:
            return si, j
    raise IndexError(f"global row {gidx} not on any shard")


def gather_rows(shards: Sequence[ShardView], rows: Sequence[int],
                arrays: Optional[Sequence[np.ndarray]] = None) -> np.ndarray:
    """Gather global pool rows into one array — the coordinator-side
    collect for warm starts, density references, DBAL's prefiltered subset
    and per-row scalars (``arrays`` may have any trailing shape; defaults
    to the shard feature matrices)."""
    if arrays is None:
        arrays = [np.asarray(s.feats) for s in shards]
    out = []
    for g in rows:
        si, li = locate_row(shards, int(g))
        out.append(np.asarray(arrays[si])[li])
    if not out:
        a0 = np.asarray(arrays[0])
        return np.zeros((0,) + a0.shape[1:], a0.dtype)
    return np.stack(out)


def replica_top_k(shards: Sequence[ShardView],
                  scores_list: Sequence[jax.Array], budget: int,
                  executor=None) -> Tuple[np.ndarray, np.ndarray]:
    """Exact ``jax.lax.top_k`` over a sharded score vector.

    Each shard ships only its local top-min(budget, n) candidates; the merge
    orders them by (value desc, global index asc) — ``lax.top_k``'s
    documented tie rule — so the returned (indices, values) match the
    single-pool call bit-for-bit.
    """
    def local(args):
        s, sc = args
        if s.n == 0:
            return np.zeros((0,), np.float32), np.zeros((0,), np.int64)
        b = min(budget, s.n)
        v, i = jax.lax.top_k(jnp.asarray(sc), b)
        return np.asarray(v), s.gidx[np.asarray(i)]

    parts = replica_map(local, list(zip(shards, scores_list)), executor)
    vals = np.concatenate([p[0] for p in parts])
    gidx = np.concatenate([p[1] for p in parts])
    order = np.lexsort((gidx, -vals))[:budget]
    return gidx[order], vals[order]


def replica_seed_min_dist(shards: Sequence[ShardView],
                          emb_list: Sequence[jax.Array], first: int):
    """Per-shard min sq-dists to the seed center at global row ``first``,
    with the seed's own row masked (-1.0) on its home shard — the shared
    init for every greedy loop whose first center is a random draw
    (k-center greedy, BADGE's D² sampling)."""
    from repro.kernels.pairwise import ops
    fsi, fli = locate_row(shards, first)
    center = emb_list[fsi][fli]
    mind = []
    for i, s in enumerate(shards):
        if s.n == 0:
            mind.append(None)
            continue
        # a shard on another device than the seed's gets its own copy
        m = ops.sq_dist_to_center(emb_list[i], jax.device_put(
            center, next(iter(emb_list[i].devices()))))
        if i == fsi:
            m = m.at[fli].set(-1.0)
        mind.append(m)
    return mind


def _merge_proposals(props):
    """Cross-shard winner: max value, ties to the lowest global index —
    the sharded spelling of ``jnp.argmax`` over the concatenated scores."""
    best = None
    for p in props:
        if p is None:
            continue
        if best is None or p[0] > best[0] or (p[0] == best[0]
                                              and p[1] < best[1]):
            best = p
    return best


_NO_ROW = np.iinfo(np.int32).max


def _merge_on_device(vals, locs, gidxs):
    """``_merge_proposals`` in ``jnp`` over the shards' ``(score, local
    row)`` proposals: the largest score, ties to the lowest global index.
    Returns the winner's ``(shard position, global index, score, local
    row)``."""
    gids = jnp.stack([g[li] for g, li in zip(gidxs, locs)])
    vals, locs = jnp.stack(vals), jnp.stack(locs)
    k = jnp.argmin(jnp.where(vals == jnp.max(vals), gids, _NO_ROW))
    return k, gids[k], vals[k], locs[k]


def lane_devices(shards: Sequence[ShardView]) -> Optional[tuple]:
    """The shards' lane devices when the pick loop runs across them: more
    than one shard, each pinned to a device of its own. None where shards
    share a device or are unpinned (the single-device loop)."""
    devs = tuple(s.device for s in shards)
    if len(devs) < 2 or any(d is None for d in devs) \
            or len(set(devs)) < len(devs):
        return None
    return devs


@functools.partial(jax.jit, static_argnames=("blocks", "impl"))
def _greedy_loop(embs, minds, gidxs, weight_for_slot, bounds, statics=None,
                 *, blocks, impl):
    """Slots ``bounds[0] .. bounds[1]`` of the sharded greedy loop as one
    device program. Returns ``(sel, scores)``, buffers of the pool's total
    row count: slot ``j`` holds its winner's global index and merged score.
    The bounds are traced, so one program serves every budget."""
    from repro.kernels.pairwise import ops
    from repro.kernels.pairwise.kernel import greedy_layout
    live = [i for i, e in enumerate(embs) if e.shape[0]]
    live_gidxs = [gidxs[i] for i in live]
    ns = [embs[i].shape[0] for i in live]
    nps = [greedy_layout(n, blocks[i])[1] for n, i in zip(ns, live)]
    total = sum(g.shape[0] for g in gidxs)
    start, stop = bounds[0], jnp.minimum(bounds[1], total)

    def row(v, n, np_):      # (n,) -> the kernel's padded (1, Np) row
        return jnp.pad(v.astype(jnp.float32), (0, np_ - n))[None, :]

    def weights(slot):
        if statics is not None:
            return [statics[i] for i in live]
        if weight_for_slot is None:
            return [None] * len(live)
        ws = weight_for_slot(slot, gidxs, total)
        return [ws[i] for i in live]

    # the pool is padded to the kernel's layout once, not once per pick
    xs = [jnp.pad(embs[i].astype(jnp.float32), ((0, np_ - n), (0, 0)))
          for n, np_, i in zip(ns, nps, live)]
    ms = [row(minds[i], n, np_) for n, np_, i in zip(ns, nps, live)]

    # slot ``start``: each shard's masked argmax, the flat path's pre-loop
    # proposal
    vals, locs = [], []
    for w, i in zip(weights(start), live):
        sc = ops.masked_weighted_score(minds[i], w)
        li = jnp.argmax(sc).astype(jnp.int32)
        vals.append(sc[li])
        locs.append(li)
    k, g, v, l = _merge_on_device(vals, locs, live_gidxs)
    sel = jnp.zeros((total,), jnp.int32).at[start].set(g)
    scores = jnp.zeros((total,), jnp.float32).at[start].set(v)

    def body(slot, carry):
        ms, sel, scores, k, l = carry
        # fold the previous winner (local row ``l`` of live shard ``k``)
        center = jnp.stack([x[jnp.minimum(l, n - 1)]
                            for x, n in zip(xs, ns)])[k][None, :]
        vals, locs, out = [], [], []
        for p, (w, i) in enumerate(zip(weights(slot), live)):
            nm, li, lv = ops.greedy_round_padded(
                xs[p], ms[p], center, jnp.where(k == p, l, -1)[None],
                None if w is None else row(w, ns[p], nps[p]),
                n=ns[p], n_block=blocks[i], impl=impl)
            out.append(nm)
            vals.append(lv)
            locs.append(li)
        k, g, v, l = _merge_on_device(vals, locs, live_gidxs)
        return out, sel.at[slot].set(g), scores.at[slot].set(v), k, l

    _, sel, scores, _, _ = jax.lax.fori_loop(
        start + 1, stop, body, (ms, sel, scores, k, l))
    return sel, scores


@functools.partial(jax.jit, static_argnames=("mesh", "n", "n_block",
                                             "total", "impl"))
def _lane_loop(x, mind, gidx, statics, weight_for_slot, bounds, *, mesh, n,
               n_block, total, impl):
    """``_greedy_loop`` as one SPMD program over the lanes' devices (the
    1-D ``mesh``). Each device holds its own shard, laid out alike on
    every device: x (Np, d) at ``greedy_layout(n, n_block)``, mind, gidx
    and ``statics`` (Np,); rows that hold no pool row carry gidx
    ``_NO_ROW`` and never win. Per pick each device folds the last winner
    into its shard with the fused round and takes its local argmax; one
    all-gather (``alaas.select.merge``) hands every device each proposal's
    score, global index and row, and every device picks the same winner by
    ``_merge_on_device``'s rule, whose row is the next fold's center.
    ``total`` (static) is the pool's row count that ``weight_for_slot``
    draws over. Returns the replicated ``(sel, scores)``, buffers of
    ``mesh.size * Np`` slots, as ``_greedy_loop``'s."""
    from repro.kernels.pairwise import ops
    axis = mesh.axis_names[0]
    f32, i32 = jnp.float32, jnp.int32
    bitcast = jax.lax.bitcast_convert_type

    def local(x, mind, gidx, statics, weight_for_slot, bounds):
        d = x.shape[1]
        me = jax.lax.axis_index(axis)
        real = gidx != _NO_ROW
        rows = jnp.where(real, gidx, 0)    # in range for a weights gather
        start, stop = bounds[0], bounds[1]

        def weights(slot):
            if statics is not None:
                return statics
            if weight_for_slot is None:
                return None
            return weight_for_slot(slot, (rows,), total)[0]

        def merge(li, lv):
            # one collective per pick: the proposals travel as int32 bits
            with jax.named_scope("alaas.select.merge"):
                bits = jnp.concatenate([bitcast(x[li], i32),
                                        jnp.stack([bitcast(lv, i32),
                                                   gidx[li]])])
                props = jax.lax.all_gather(bits, axis)      # (L, d + 2)
                vals, gids = bitcast(props[:, d], f32), props[:, d + 1]
                k = jnp.argmin(jnp.where(vals == jnp.max(vals), gids,
                                         _NO_ROW))
                return k, gids[k], vals[k], bitcast(props[k, :d], f32)

        m = jnp.where(real, mind.astype(f32), -1.0)
        sc = ops.masked_weighted_score(m, weights(start))
        li = jnp.argmax(sc).astype(i32)
        k, g, v, center = merge(li, sc[li])
        size = x.shape[0] * mesh.size
        sel = jnp.zeros((size,), i32).at[start].set(g)
        scores = jnp.zeros((size,), f32).at[start].set(v)

        def body(slot, carry):
            m, sel, scores, k, li, center = carry
            w = weights(slot)
            # the winner's own device masks it (its proposal was ``li``)
            m, li, lv = ops.greedy_round_padded(
                x, m, center[None, :], jnp.where(k == me, li, -1)[None],
                None if w is None else w[None, :], n=n, n_block=n_block,
                impl=impl)
            li = li.astype(i32)
            k, g, v, center = merge(li, lv)
            return m, sel.at[slot].set(g), scores.at[slot].set(v), k, li, \
                center

        _, sel, scores, _, _, _ = jax.lax.fori_loop(
            start + 1, stop, body, (m[None, :], sel, scores, k, li, center))
        return sel, scores

    lane = P(axis)
    return shard_map(local, mesh=mesh,
                     in_specs=(lane, lane, lane, lane, P(), P()),
                     out_specs=(P(), P()))(
        x, mind, gidx, statics, weight_for_slot, bounds)


@functools.partial(jax.jit, static_argnames=("rows", "fill"))
def _pad_rows(a, rows, fill):
    return jnp.pad(a, [(0, rows - a.shape[0])] + [(0, 0)] * (a.ndim - 1),
                   constant_values=fill)


def _lane_array(parts, rows: int, fill, dtype, lanes, sharding):
    """The shards' ``parts`` as one array sharded over the lanes: each
    padded to ``rows`` rows of ``fill`` on its lane's device. A host part
    is padded on the host and uploaded, so no program depends on its
    length; a device part is moved to its lane and padded there."""
    out = []
    for p, dev in zip(parts, lanes):
        if isinstance(p, jax.Array):
            out.append(_pad_rows(jax.device_put(p, dev).astype(dtype),
                                 rows, fill))
            continue
        p = np.asarray(p, dtype)
        buf = np.empty((rows,) + p.shape[1:], dtype)
        buf[:len(p)] = p
        buf[len(p):] = fill
        out.append(telemetry.h2d(buf, device=dev))
    return jax.make_array_from_single_device_arrays(
        (rows * len(out),) + out[0].shape[1:], sharding, out)


def _place_on_lanes(shards, emb_list, mind_list, weights_list,
                    weight_for_slot, start, stop, impl, lanes):
    """Upload the operands of ``_lane_loop`` over shards pinned one per
    device, and return the call. Rows are padded to the largest shard's
    pool (``pool_feats``; its view where there is none), which labels do
    not change: the program is one per pool, whatever the unlabeled
    split."""
    from repro.kernels.pairwise import ops
    from repro.kernels.pairwise.kernel import greedy_layout
    mesh = Mesh(np.asarray(lanes, dtype=object), ("lanes",))
    lane, whole = NamedSharding(mesh, P("lanes")), NamedSharding(mesh, P())
    n = max(len(s.pool_feats) if s.pool_feats is not None else s.n
            for s in shards)
    d = int(np.shape(emb_list[0])[1])
    n_block = ops.autotuned_blocks(n, d, jnp.float32).n_block
    rows = greedy_layout(n, n_block)[1]
    f32 = np.float32
    x = _lane_array(emb_list, rows, 0.0, f32, lanes, lane)
    mind = _lane_array([np.zeros((0,), f32) if m is None else m
                        for m in mind_list], rows, -1.0, f32, lanes, lane)
    gidx = _lane_array([s.gidx for s in shards], rows, _NO_ROW, np.int32,
                       lanes, lane)
    statics = (None if weights_list is None else
               _lane_array(weights_list, rows, 0.0, f32, lanes, lane))
    bounds = telemetry.h2d(np.asarray([start, stop], np.int32),
                           device=whole)
    return functools.partial(
        _lane_loop, x, mind, gidx, statics,
        jax.tree.map(lambda a: jax.device_put(a, whole), weight_for_slot),
        bounds, mesh=mesh, n=n, n_block=n_block,
        total=(None if weight_for_slot is None else replica_total(shards)),
        impl=impl)


def _place_on_one_device(shards, emb_list, mind_list, weights_list,
                         weight_for_slot, start, stop, impl):
    """Upload the operands of ``_greedy_loop`` to one device (the first
    device array's, else the first shard's lane, else the default one),
    and return the call."""
    from repro.kernels.pairwise import ops
    first = next((e for e in emb_list if isinstance(e, jax.Array)), None)
    dev = (next(iter(first.devices())) if first is not None
           else shards[0].device)

    def put(x, dtype=None):
        if isinstance(x, jax.Array):
            return x if dev is None else jax.device_put(x, dev)
        return telemetry.h2d(x, dtype, device=dev)

    embs = tuple(put(e, jnp.float32) for e in emb_list)
    minds = tuple(put(np.zeros((0,), np.float32) if m is None else m)
                  for m in mind_list)
    gidxs = tuple(put(np.asarray(s.gidx, np.int32)) for s in shards)
    bounds = put(np.asarray([start, stop], np.int32))
    blocks = tuple(ops.autotuned_blocks(*e.shape, e.dtype).n_block
                   if e.shape[0] else 0 for e in embs)
    return functools.partial(
        _greedy_loop, embs, minds, gidxs, jax.tree.map(put, weight_for_slot),
        bounds,
        None if weights_list is None else tuple(map(put, weights_list)),
        blocks=blocks, impl=impl)


def replica_greedy_select(shards: Sequence[ShardView],
                          emb_list: Sequence[Any], budget: int, *,
                          mind_list: Sequence[Optional[Any]],
                          sel: np.ndarray, start: int,
                          weights_list: Optional[Sequence[Any]] = None,
                          weight_for_slot=None, impl: str = "auto",
                          capture: Optional[list] = None) -> np.ndarray:
    """Local-propose / global-dedup greedy rounds over replica shards —
    ``distributed_k_center``'s round structure generalized to hash-sharded
    pools and per-slot weights (static weights for weighted k-center,
    fresh Gumbel draws per slot for BADGE's D² sampling).

    Per slot: every shard runs ONE fused ``greedy_round`` over its rows
    (min-dist fold + winner masking + local weighted argmax), proposes
    ``(score, global index)``, and the merge picks the winner: the largest
    score, ties to the lowest global index. Bit-identical to the
    single-pool greedy loop: the per-row floats are slice-invariant and
    both tie-break layers reduce to the lowest global index.

    Slots ``start .. budget`` run as one device program whose compile
    depends on the shards' shapes only, not on the budget; the host reads
    the selection back once, at the end. Shards pinned one per device
    (``lane_devices``) run it as one SPMD program across those devices
    (``_lane_loop``): each device holds and folds its own shard, and the
    proposals meet in one all-gather per pick. Otherwise every shard's
    arrays are put on one device (``_greedy_loop``).

    ``emb_list``/``mind_list`` hold each shard's pool rows and min-dists,
    host or device arrays (a host array is uploaded where it runs).
    ``weights_list`` holds static per-shard weights (weighted k-center,
    margin density); ``weight_for_slot`` is instead a
    ``jax.tree_util.Partial`` traced as ``weight_for_slot(slot, gidxs,
    total)`` into the weight vectors of the rows at global positions
    ``gidxs`` for ``slot``'s pick, out of a ``total``-row pool (BADGE's
    Gumbel draws).

    ``capture`` (optional list) records the merged winner's score per slot
    in slot order — the standing-query replay engine (service layer) stores
    them so a later emit over a grown pool can prove "no new row beats any
    recorded winner" by streaming only the delta rows.

    The span ``select.greedy`` covers the loop, from its dispatch to its
    selection on the host; the uploads come before it. Counts its picks
    in ``select.picks``, its read-back in ``select.d2h_syncs``, the
    devices its program spans in ``select.loop_devices`` and its uploads
    in ``h2d_bytes``.
    """
    from repro.kernels.pairwise import ops
    stop = min(budget, replica_total(shards))
    if stop <= start:
        return sel
    lanes = lane_devices(shards)
    args = (shards, emb_list, mind_list, weights_list, weight_for_slot,
            start, stop, impl)
    loop = (_place_on_one_device(*args) if lanes is None
            else _place_on_lanes(*args, lanes))
    with telemetry.span("select.greedy"):
        got, scores = jax.device_get(loop())
    for e in emb_list:
        if np.shape(e)[0]:
            ops.record_greedy_rounds(e, stop - start - 1)
    sel[start:stop] = got[start:stop]
    if capture is not None:
        capture.extend(float(v) for v in scores[start:stop])
    telemetry.count("select.picks", stop - start)
    telemetry.count("select.d2h_syncs", 1)
    telemetry.count("select.loop_devices", 1 if lanes is None
                    else len(lanes))
    return sel


# ===========================================================================
# Persistent per-session k-center strategy state (O(delta) warm starts)
# ===========================================================================

@dataclasses.dataclass
class KCenterState:
    """One query's view of the persisted min-dist state.

    ``minds[si]`` is the shard's (rows,) float32 min squared distance of
    every POOL row (labeled and unlabeled alike) to the folded center set.
    The arrays are owned by the cache and treated as immutable — consumers
    gather or copy, never write.
    """
    minds: Sequence[np.ndarray]
    rows: Sequence[int]
    # standing-query replay capture: when set, ``sharded_k_center`` threads
    # it into ``replica_greedy_select(capture=...)``
    capture: Optional[list] = None

    def view_minds(self, shards) -> list:
        """Per-shard min-dists gathered down to the query's (unlabeled)
        view rows, as host arrays that the greedy loop uploads where it
        runs. Requires ``ShardView.pool_rows``. Row gathers reproduce the
        exact floats a from-scratch ``warm_start_min_dist`` over the view
        would compute: per-(row, center) distances are slice-invariant
        (module contract) and the min fold is exact."""
        return [self.minds[i][np.asarray(s.pool_rows)] if s.n else None
                for i, s in enumerate(shards)]

    def pool_mind(self, i: int) -> np.ndarray:
        return self.minds[i]


class KCenterStateCache:
    """Per-session persisted k-center min-dist vectors (ROADMAP: carry the
    artifact epoch-stamping into strategy state).

    The cache keys per-shard min-dist columns on the same append-only
    discipline as ``ShardColumns``: a vector computed over rows
    ``[0:rows]`` against centers ``locs[:k]`` stays exact when rows are
    appended (extend by folding ALL centers over just the new rows) or
    centers are appended (fold just the new centers over all rows and take
    the elementwise min) — both O(delta), both bitwise equal to a
    from-scratch fold because per-(row, center) squared distances are
    invariant to which other rows/centers share the call and ``min`` is an
    exact, order-independent fold. Validity stamps:

      * shard ``lineage`` — a ``ShardColumns.reset()`` invalidates the
        shard (its feats rows are no longer an append-extension);
      * ``head_version`` — a head retrain invalidates everything (the
        spec's conservative row of the invalidation matrix; labeling a
        sample invalidates NOTHING since pool rows and feats are
        untouched, it only appends centers);
      * center ``locs`` prefix — cached center order must be a prefix of
        the query's fold order, else rebuild.

    Thread contract: ``prepare`` is the only mutator and serializes on an
    internal lock (PSHEA candidate races); handed-out arrays are never
    written again (extends allocate fresh arrays).
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._minds: dict = {}       # si -> np (rows,) f32
        self._rows: dict = {}        # si -> int
        self._lineage: dict = {}     # si -> int
        self._locs: tuple = ()       # ((si, li), ...) centers in fold order
        self._head_version = -1
        self.counters = {
            "rebuilds": 0, "extends": 0, "center_extends": 0,
            "invalidations": 0, "hits": 0,
            "rows_extended": 0, "rows_reused": 0,
        }

    def _drop_all(self):
        if self._minds or self._locs:
            self.counters["invalidations"] += 1
        self._minds, self._rows, self._lineage = {}, {}, {}
        self._locs = ()

    def invalidate(self) -> None:
        """Head retrain: min-dists are conservatively dropped on every
        shard; feats columns are untouched so nothing re-embeds."""
        with self._lock:
            self._drop_all()
            self._head_version = -1

    def stats(self) -> dict:
        with self._lock:
            return dict(self.counters)

    def prepare(self, *, feats_l, rows_l, lineages, head_version, locs,
                centers, capture=None) -> Optional[KCenterState]:
        """Produce this query's :class:`KCenterState`, reusing cached
        vectors where the stamps allow and folding only the row/center
        deltas. ``centers[k]`` must be the feats row at ``locs[k]``."""
        from repro.kernels.pairwise import ops
        h2d = telemetry.h2d
        locs = tuple(tuple(p) for p in locs)
        k = len(locs)
        if k == 0:
            return None
        centers = np.asarray(centers, np.float32)
        nsh = len(feats_l)
        with telemetry.span("select.warm_state"), self._lock:
            if head_version != self._head_version:
                self._drop_all()
                self._head_version = head_version
            kc = len(self._locs)
            if self._locs != locs[:kc]:
                # non-prefix center reorder (e.g. a relabel changed fold
                # order) — exactness is unprovable incrementally
                self._drop_all()
                kc = 0
            new_centers = centers[kc:]
            reused = False
            minds, rows_out = [], []
            for si in range(nsh):
                rows = int(rows_l[si])
                feats = np.asarray(feats_l[si])[:rows]
                m = self._minds.get(si)
                if m is not None and self._lineage.get(si) != lineages[si]:
                    self.counters["invalidations"] += 1
                    m = None
                if m is None:
                    if rows:
                        m = np.asarray(ops.warm_start_min_dist(
                            h2d(feats), h2d(centers)), np.float32)
                    else:
                        m = np.zeros((0,), np.float32)
                    self.counters["rebuilds"] += 1
                else:
                    reused = True
                    rc = int(self._rows[si])
                    if len(new_centers) and rc:
                        # center delta: fold only the new centers over the
                        # cached rows; elementwise min == one joint fold
                        nm = np.asarray(ops.warm_start_min_dist(
                            h2d(feats[:rc]), h2d(new_centers)), np.float32)
                        m = np.minimum(m[:rc], nm)
                        self.counters["center_extends"] += 1
                    if rows > rc:
                        # row delta: fold ALL centers over just the new rows
                        ext = np.asarray(ops.warm_start_min_dist(
                            h2d(feats[rc:rows]), h2d(centers)), np.float32)
                        m = np.concatenate([m[:rc], ext])
                        self.counters["extends"] += 1
                        self.counters["rows_extended"] += rows - rc
                    self.counters["rows_reused"] += min(rows, rc)
                if rows >= int(self._rows.get(si, -1)):
                    # store the newest view (a raced query pinned at older
                    # rows serves a slice without shrinking the cache)
                    self._minds[si] = m
                    self._rows[si] = max(rows, int(self._rows.get(si, 0)))
                    self._lineage[si] = lineages[si]
                minds.append(m[:rows])
                rows_out.append(rows)
            self._locs = locs
            if reused:
                self.counters["hits"] += 1
            return KCenterState(minds=minds, rows=rows_out, capture=capture)
