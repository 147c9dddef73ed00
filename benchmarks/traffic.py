"""Open-loop traffic harness: offered-load latency curve + failure drills.

Unlike the paired A/B sections of the table2 benchmark, this is an HONEST
heavy-traffic harness: a seeded open-loop generator (Poisson arrivals —
ops fire at their scheduled instants whether or not earlier ops finished,
so queueing delay counts against latency) drives a multi-tenant mix of
push / label / query / standing-poll against a replica-sharded server and
reports per-op p50/p99 latency plus achieved throughput AS A CURVE over
offered load, with the saturation point called out.

Four drills ride the same harness, asserted in-process and re-asserted
by CI from the uploaded JSON (scripts/assert_traffic.py):

  * graceful degradation — a deterministic op sequence runs on twin
    servers, one with shard workers killed mid-round (embed AND propose,
    via ``PhaseFailureInjector``); every query selection must stay
    BIT-IDENTICAL to the clean twin (kill -> detect -> reset shard ->
    re-embed from raw + content keys -> bounded retry), with worker
    restarts actually observed and p99 latency bounded vs the clean run;
  * kill-during-ingest — async pushes with a worker killed mid-drain must
    lose ZERO rows (retries re-run the idempotent content-addressed
    pipeline before rows append) — run UNDER the bounded-ingest cap;
  * overload — offered load >= 3x the measured saturation against the TCP
    server with admission control + a capped shed-policy ingest queue:
    queue memory stays bounded (ingest bytes high-water <= cap, scheduler
    inflight high-water <= bound), admitted-op p99 stays inside the
    envelope, per-tenant admitted throughput is fair (Jain >= JAIN_MIN),
    every shed op carries a positive retry_after_s, and zero acked rows
    are lost;
  * admission twin — the same deterministic serial sequence over TCP with
    admission OFF vs ON (tight bucket + client bounded retry): sheds and
    retries actually happen, yet selections stay BIT-IDENTICAL.

  PYTHONPATH=src python benchmarks/traffic.py --json BENCH_traffic.json --smoke
"""
from __future__ import annotations

import argparse
import concurrent.futures as cf
import json
import sys
import threading
import time

import numpy as np

from benchmarks.common import row
from repro.common.device import use_compile_cache
from repro.distributed.worker import PhaseFailureInjector
from repro.service.client import ALClient, serve_tcp
from repro.service.config import ALServiceConfig
from repro.service.errors import ServerOverloaded
from repro.service.server import ALServer

# p99 under injected worker death must stay within this factor of the
# clean run (the recovery path is a bounded rebuild, not a meltdown);
# scripts/assert_traffic.py re-asserts the same bound from the JSON
P99_DEGRADATION_BOUND = 50.0
# overload drill envelope: admitted ops (the ones admission let through)
# must finish within this p99 even at 3x saturation offered — admission
# keeps the dispatch queue short, so latency stays flat while excess
# load is shed with retry_after_s instead of queueing without bound
OVERLOAD_P99_BOUND_MS = 2000.0
# Jain's fairness index floor on per-tenant admitted throughput
JAIN_MIN = 0.9
# bounded-ingest cap for the overload drill (bytes outstanding per
# session; one 8x8x3 float32 row is 768B)
OVERLOAD_INGEST_CAP_BYTES = 64 << 10

OP_MIX = [("push", 0.45), ("label", 0.20), ("query", 0.25),
          ("poll", 0.10)]


def _rows(n, seed, shape=(8, 8, 3)):
    rng = np.random.default_rng(seed)
    return rng.normal(size=(n,) + shape).astype(np.float32)


def _make_server(replicas=2, injector=None, **cfg_kw):
    cfg = ALServiceConfig(replicas=replicas, batch_size=16,
                          worker_backoff_s=0.0, **cfg_kw)
    return ALServer(config=cfg, failure_injector=injector)


def _warm_tenant(srv, sid, seed, n=48):
    X = _rows(n, seed)
    keys = srv.push_data(list(X), session=sid)
    labels = [int(i % 2) for i in range(8)]
    srv.label(keys[:8], labels, session=sid)
    srv.train_and_eval(session=sid)
    qid = srv.standing_register(3, strategy="coreset",
                                session=sid)["query_id"]
    return keys, qid


def _schedule(n_ops, offered, tenants, seed):
    """Seeded open-loop schedule: exponential inter-arrivals at ``offered``
    ops/s, op type from the tenant mix, round-robin-free tenant draw."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / offered, size=n_ops)
    arrivals = np.cumsum(gaps)
    ops = rng.choice([op for op, _ in OP_MIX], size=n_ops,
                     p=[w for _, w in OP_MIX])
    ten = rng.integers(0, tenants, size=n_ops)
    return list(zip(arrivals.tolist(), ops.tolist(), ten.tolist()))


def _run_open_loop(srv, sids, warm, offered, n_ops, seed):
    """Fire the schedule open-loop; returns {op: [latency_s, ...]} and the
    wall seconds the burst took. Latency is completion minus SCHEDULED
    arrival — a stalled server pays for its queue."""
    sched = _schedule(n_ops, offered, len(sids), seed)
    fresh = _rows(n_ops, seed + 1)
    lat: dict = {op: [] for op, _ in OP_MIX}

    def execute(op, t, i, t_sched, t0):
        sid = sids[t]
        keys, qid = warm[t]
        rng = np.random.default_rng(seed + 7 * i)
        if op == "push":
            srv.push_data([fresh[i]], asynchronous=True, session=sid)
        elif op == "label":
            k = keys[int(rng.integers(0, len(keys)))]
            srv.label([k], [int(rng.integers(0, 2))], session=sid)
        elif op == "query":
            srv.query(4, strategy="mc", rng_seed=i, session=sid)
        else:
            srv.standing_poll(qid, session=sid)
        lat[op].append(time.perf_counter() - (t0 + t_sched))

    t0 = time.perf_counter()
    with cf.ThreadPoolExecutor(max_workers=32) as pool:
        futs = []
        for i, (t_arr, op, t) in enumerate(sched):
            now = time.perf_counter() - t0
            if t_arr > now:
                time.sleep(t_arr - now)
            futs.append(pool.submit(execute, op, t, i, t_arr, t0))
        for f in futs:
            f.result()
    for sid in sids:
        srv.flush(session=sid)       # ingest barrier: nothing in flight
    return lat, time.perf_counter() - t0


def _pcts(vals):
    if not vals:
        return 0.0, 0.0
    a = np.asarray(vals) * 1e3      # ms
    return float(np.percentile(a, 50)), float(np.percentile(a, 99))


def _load_curve(loads, n_ops, tenants, seed):
    """One offered-load level per row: the p50/p99-vs-load CURVE the
    paired-ratio benchmarks cannot show, plus the saturation point."""
    out = []
    achieved = []
    for offered in loads:
        srv = _make_server(replicas=2)
        sids = [srv.create_session(f"t{i}") for i in range(tenants)]
        warm = [_warm_tenant(srv, sid, seed + 11 * i)
                for i, sid in enumerate(sids)]
        lat, wall = _run_open_loop(srv, sids, warm, offered, n_ops, seed)
        done = sum(len(v) for v in lat.values())
        assert done == n_ops, f"open loop dropped ops: {done}/{n_ops}"
        thr = done / wall
        achieved.append(thr)
        parts = [f"offered={offered:g}", f"achieved={thr:.1f}"]
        for op, _ in OP_MIX:
            p50, p99 = _pcts(lat[op])
            parts += [f"p50_{op}_ms={p50:.2f}", f"p99_{op}_ms={p99:.2f}"]
        mean_ms = 1e3 * float(np.mean([v for vs in lat.values()
                                       for v in vs]))
        out.append(row(f"traffic/load_{offered:g}", mean_ms * 1e3,
                       ";".join(parts)))
    assert len(loads) >= 2, "a curve needs >= 2 offered-load levels"
    sat = max(achieved)
    out.append(row(
        "traffic/saturation", 0.0,
        f"throughput_ops_s={sat:.1f};levels={len(loads)};"
        f"loads={'/'.join(f'{ld:g}' for ld in loads)}"))
    return out, sat


def _deterministic_ops(srv, sid, keys, seed, n_ops=18):
    """A fixed op sequence (sync pushes so both twins see identical pool
    states); returns (query selections, query latencies)."""
    fresh = _rows(n_ops, seed + 2)
    sels, qlat = [], []
    for i in range(n_ops):
        kind = i % 3
        if kind == 0:
            srv.push_data([fresh[i]], session=sid)
        elif kind == 1:
            srv.label([keys[i % len(keys)]], [i % 2], session=sid)
        else:
            t0 = time.perf_counter()
            res = srv.query(4, strategy="coreset", rng_seed=i, session=sid)
            qlat.append(time.perf_counter() - t0)
            sels.append(res["keys"])
    return sels, qlat


def _degradation(seed):
    """Twin deterministic runs; the killed twin must select identically."""
    runs = {}
    # the throwaway "warm" pass eats every process-wide jit compile the
    # sequence triggers; without it whichever timed twin runs FIRST pays
    # the compiles and the p99 ratio measures xla, not the recovery path
    for mode in ("warm", "clean", "killed"):
        srv = _make_server(replicas=3)
        sid = srv.create_session("t0")
        keys, _ = _warm_tenant(srv, sid, seed)
        if mode == "killed":
            # arm AFTER warmup so the kills land mid-workload: the next
            # embed round and the next propose round each lose a worker
            srv.shard_runtime().injector = PhaseFailureInjector(
                {"embed": [0], "propose": [0]})
        runs[mode] = (_deterministic_ops(srv, sid, keys, seed),
                      srv.stats(session=sid))
    (sel_w, _), _ = runs.pop("warm")
    (sel_c, lat_c), _ = runs["clean"]
    (sel_k, lat_k), st_k = runs["killed"]
    identical = sel_c == sel_k
    assert sel_w == sel_c, "deterministic sequence is not repeatable"
    p99_c = float(np.percentile(np.asarray(lat_c) * 1e3, 99))
    p99_k = float(np.percentile(np.asarray(lat_k) * 1e3, 99))
    ratio = p99_k / max(p99_c, 1e-9)
    recoveries = st_k["worker_recoveries"]
    restarts = st_k["workers"]["restarts"]
    assert identical, "killed-worker run diverged from the clean run"
    assert recoveries >= 1 and restarts >= 2, (
        f"kills did not exercise recovery (recoveries={recoveries}, "
        f"restarts={restarts})")
    assert ratio <= P99_DEGRADATION_BOUND, (
        f"p99 degradation {ratio:.1f}x exceeds "
        f"{P99_DEGRADATION_BOUND:.0f}x")
    return [row(
        "traffic/degradation", p99_k * 1e3,
        f"killed_equals_clean={identical};p99_clean_ms={p99_c:.2f};"
        f"p99_killed_ms={p99_k:.2f};p99_ratio={ratio:.2f}x;"
        f"recoveries={recoveries};restarts={restarts}")]


def _ingest_kill(seed, n_push=40, cap_rows=8):
    """Async pushes with a worker killed mid-drain AND the bounded-ingest
    cap active (block policy): zero lost rows, cap held throughout."""
    srv = _make_server(replicas=2, ingest_max_rows=cap_rows,
                       ingest_policy="block")
    sid = srv.create_session("t0")
    srv.shard_runtime().injector = PhaseFailureInjector({"ingest": [0]})
    X = _rows(n_push, seed + 3)
    tickets = [srv.push_data([x], asynchronous=True, session=sid)
               for x in X]
    srv.flush(session=sid)
    uniq = {k for t in tickets for k in t.keys}
    st = srv.stats(session=sid)
    lost = len(uniq) - st["pool"]
    restarts = st["workers"]["restarts"]
    rows_hw = st["ingest"]["rows_hw"]
    assert lost == 0, f"kill during ingest drain lost {lost} rows"
    assert restarts >= 1, "ingest kill never fired"
    assert rows_hw <= cap_rows, (
        f"ingest cap breached under kill: {rows_hw} > {cap_rows}")
    return [row("traffic/ingest_kill", 0.0,
                f"pushed={len(uniq)};pool={st['pool']};lost_rows={lost};"
                f"restarts={restarts};rows_hw={rows_hw};"
                f"cap_rows={cap_rows}")]


def _jain(xs):
    xs = [float(x) for x in xs]
    denom = len(xs) * sum(x * x for x in xs)
    return (sum(xs) ** 2 / denom) if denom else 0.0


def _overload(seed, sat, tenants, n_ops, clients_per_tenant=4):
    """Offered load >= 3x saturation against the TCP server with the full
    overload stack on: admission (per-tenant buckets + inflight bound) and
    a capped shed-policy ingest queue. Asserts the acceptance criteria
    in-process; scripts/assert_traffic.py re-asserts them from the JSON."""
    offered = 3.0 * max(sat, 1.0)
    rate = max(sat / tenants, 4.0)          # binding per-tenant bucket
    max_inflight = 16
    srv = _make_server(replicas=2, admission=True,
                       admission_max_inflight=max_inflight,
                       admission_tenant_rate=rate,
                       admission_tenant_burst=4.0,
                       ingest_max_bytes=OVERLOAD_INGEST_CAP_BYTES,
                       ingest_policy="shed")
    rpc = serve_tcp(srv)
    sids = [srv.create_session(f"t{i}") for i in range(tenants)]
    warm = [_warm_tenant(srv, sid, seed + 11 * i)
            for i, sid in enumerate(sids)]
    # retries=0: a shed surfaces as ServerOverloaded at the call site, so
    # the drill can observe every rejection's retry_after_s directly
    clis = [[ALClient(url=f"127.0.0.1:{rpc.port}", session=sid, retries=0)
             for _ in range(clients_per_tenant)] for sid in sids]
    sched = _schedule(n_ops, offered, tenants, seed + 17)
    fresh = _rows(n_ops, seed + 19)
    lock = threading.Lock()
    lat_admitted = []                        # completion - scheduled
    admitted_by_tenant = [0] * tenants
    shed_retry_after = []                    # one entry per shed op
    acked_keys = [set() for _ in range(tenants)]

    def execute(op, t, i, t_sched, t0):
        cli = clis[t][i % clients_per_tenant]
        keys, qid = warm[t]
        try:
            if op == "push":
                ticket = cli.push_data([fresh[i]], asynchronous=True)
                ticket.result(timeout=60)    # server acked the enqueue
                with lock:
                    acked_keys[t].update(ticket.keys)
            elif op == "label":
                k = keys[i % len(keys)]
                cli.label([k], [i % 2])
            elif op == "query":
                cli.query(4, strategy="mc", rng_seed=i)
            else:
                cli.standing_poll(qid)
        except ServerOverloaded as e:
            with lock:
                shed_retry_after.append(float(e.retry_after_s))
            return
        with lock:
            lat_admitted.append(time.perf_counter() - (t0 + t_sched))
            admitted_by_tenant[t] += 1

    t0 = time.perf_counter()
    with cf.ThreadPoolExecutor(max_workers=32) as pool:
        futs = []
        for i, (t_arr, op, t) in enumerate(sched):
            now = time.perf_counter() - t0
            if t_arr > now:
                time.sleep(t_arr - now)
            futs.append(pool.submit(execute, op, t, i, t_arr, t0))
        for f in futs:
            f.result()
    wall = time.perf_counter() - t0
    # drain: flush is itself subject to admission — retry until admitted
    for t, sid in enumerate(sids):
        deadline = time.time() + 60
        while True:
            try:
                clis[t][0].flush()
                break
            except ServerOverloaded as e:
                assert time.time() < deadline, "drain flush starved"
                time.sleep(e.retry_after_s)
    # ---- acceptance criteria, asserted in-process ----
    sheds = len(shed_retry_after)
    assert sheds > 0, "overload drill never shed (not actually overloaded)"
    retry_ok = all(r > 0 for r in shed_retry_after)
    assert retry_ok, "a shed op came back without a usable retry_after_s"
    jain = _jain(admitted_by_tenant)
    assert jain >= JAIN_MIN, (
        f"admitted throughput unfair: Jain {jain:.3f} < {JAIN_MIN}"
        f" (per-tenant {admitted_by_tenant})")
    p99 = float(np.percentile(np.asarray(lat_admitted) * 1e3, 99))
    assert p99 <= OVERLOAD_P99_BOUND_MS, (
        f"admitted-op p99 {p99:.0f}ms outside the "
        f"{OVERLOAD_P99_BOUND_MS:.0f}ms envelope")
    adm = rpc.stats()
    assert adm["inflight_hw"] <= max_inflight, (
        f"inflight high-water {adm['inflight_hw']} breached the bound")
    bytes_hw = 0
    lost = 0
    for t, sid in enumerate(sids):
        st = srv.stats(session=sid)
        bytes_hw = max(bytes_hw, st["ingest"]["bytes_hw"])
        pool_keys = set(srv.session(sid)._keys)
        lost += len(acked_keys[t] - pool_keys)
    assert bytes_hw <= OVERLOAD_INGEST_CAP_BYTES, (
        f"ingest queue memory unbounded: {bytes_hw} > cap")
    assert lost == 0, f"overload lost {lost} acked rows"
    for row_clients in clis:
        for cli in row_clients:
            cli.close()
    rpc.stop()
    return [row(
        "traffic/overload", p99 * 1e3,
        f"offered={offered:.1f};sat={sat:.1f};wall_s={wall:.2f};"
        f"admitted={sum(admitted_by_tenant)};sheds={sheds};"
        f"retry_after_all_positive={retry_ok};jain={jain:.4f};"
        f"jain_min={JAIN_MIN};p99_admitted_ms={p99:.2f};"
        f"p99_bound_ms={OVERLOAD_P99_BOUND_MS:.0f};"
        f"inflight_hw={adm['inflight_hw']};max_inflight={max_inflight};"
        f"ingest_bytes_hw={bytes_hw};"
        f"ingest_cap_bytes={OVERLOAD_INGEST_CAP_BYTES};"
        f"acked_rows={sum(len(s) for s in acked_keys)};lost_rows={lost};"
        f"expired={adm['expired']}")]


def _client_ops(cli, keys, seed, n_ops=12):
    """The deterministic serial sequence of _deterministic_ops, driven
    through an ALClient (sync pushes -> identical pool states)."""
    fresh = _rows(n_ops, seed + 2)
    sels = []
    for i in range(n_ops):
        kind = i % 3
        if kind == 0:
            cli.push_data([fresh[i]])
        elif kind == 1:
            cli.label([keys[i % len(keys)]], [i % 2])
        else:
            sels.append(cli.query(4, strategy="coreset",
                                  rng_seed=i)["keys"])
    return sels


def _admission_twin(seed):
    """Deterministic twin over TCP: admission OFF vs ON (tight per-tenant
    bucket, so real sheds happen and the client's bounded retry does real
    work) — selections must stay bit-identical. Admission decides WHEN an
    op runs, never WHAT it computes."""
    results = {}
    for mode in ("off", "on"):
        kw = {} if mode == "off" else dict(
            admission=True, admission_max_inflight=16,
            admission_tenant_rate=2.0, admission_tenant_burst=1.0)
        srv = _make_server(replicas=2, **kw)
        sid = srv.create_session("t0")
        keys, _ = _warm_tenant(srv, sid, seed)
        rpc = serve_tcp(srv)
        cli = ALClient(url=f"127.0.0.1:{rpc.port}", session=sid,
                       retries=10, retry_jitter_s=0.01)
        sels = _client_ops(cli, keys, seed)
        stats = rpc.stats()
        cli.close()
        rpc.stop()
        results[mode] = (sels, stats)
    sels_off, _ = results["off"]
    sels_on, st_on = results["on"]
    identical = sels_off == sels_on
    sheds, retries = st_on["shed"], st_on["retries"]
    assert identical, "admission control changed the selections"
    assert sheds >= 1, "admission-on twin never shed (bucket not binding)"
    assert retries >= 1, "client retry layer never exercised"
    return [row(
        "traffic/admission_twin", 0.0,
        f"identical={identical};sheds={sheds};retries={retries};"
        f"queries={len(sels_on)}")]


def run(loads=(10.0, 30.0, 60.0), n_ops=150, tenants=3, seed=0):
    curve_rows, sat = _load_curve(list(loads), n_ops, tenants, seed)
    yield from curve_rows
    yield from _degradation(seed)
    yield from _ingest_kill(seed)
    yield from _overload(seed, sat, tenants, n_ops)
    yield from _admission_twin(seed)


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None, metavar="PATH")
    ap.add_argument("--loads", default=None,
                    help="comma-separated offered loads (ops/s)")
    ap.add_argument("--ops", type=int, default=None,
                    help="ops per load level")
    ap.add_argument("--tenants", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="small CI sizing (2 levels, fewer ops)")
    args = ap.parse_args()
    loads = ([float(x) for x in args.loads.split(",")] if args.loads
             else [5.0, 15.0] if args.smoke else [10.0, 30.0, 60.0])
    n_ops = args.ops if args.ops else (60 if args.smoke else 150)
    tenants = 2 if args.smoke and args.tenants == 3 else args.tenants

    print("name,us_per_call,derived")
    records, failures = [], 0

    def emit(line):
        print(line, flush=True)
        name, us, derived = line.split(",", 2)
        records.append({"name": name, "us_per_call": float(us),
                        "derived": derived})

    t0 = time.perf_counter()
    try:
        for line in run(loads=loads, n_ops=n_ops, tenants=tenants,
                        seed=args.seed):
            emit(line)
    except Exception as e:   # match benchmarks.run: record, don't crash
        failures += 1
        emit(f"traffic/ERROR,0.0,{type(e).__name__}: {e}")
    emit(f"traffic/_wall,{(time.perf_counter() - t0) * 1e6:.0f},done")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"schema": 1, "rows": records,
                       "failures": failures}, f, indent=1)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
