"""Serving suite: HTTP derive throughput/latency against a local server,
plus store-pressure numbers for the tiered artifact store.

Boots a MappingHTTPServer (mock backend, private temp store) on an
ephemeral port, then measures the two costs a fleet client actually pays:

  * cold derive — first request for a cell: full pipeline behind HTTP;
  * hot derive  — repeat request: server-side cache hit, so the number is
    pure serving overhead (HTTP + JSON + store read);
  * hot throughput — concurrent clients hammering cached cells.

The store-pressure sub-suite isolates where a hot hit resolves:

  * memory tier — resident rehydrated result: no disk, no JSON, no HTTP;
  * disk tier   — record read + checksum verify + rehydration per hit;
  * peer tier   — full HTTP round-trip to a sibling server per hit;
  * eviction churn — throughput when the disk budget is smaller than the
    working set, so records evict and re-derive continuously.

The cluster sub-suite (``--only cluster``) measures the two transports this
fleet actually pays for:

  * keep-alive vs fresh-connection hot derive — the pooled ``http.client``
    transport against the per-request ``Connection: close`` baseline;
  * owner-routed vs forwarded derive on a real 3-node ring — the client
    hashing locally and hitting the owner, against the server-side
    forwarding hop a ring-naive client pays.

The evaluate sub-suite (``--only evaluate``) measures the evaluation
plane added in PR 6:

  * cold vs warm query — first evaluation of a cell pays the pallas
    trace + XLA compile; repeats hit the compiled-executable cache, so
    the number is pure dispatch + transfer (acceptance: warm p50 at
    least 10x below cold);
  * batched heterogeneous request vs a sequential per-query loop over
    HTTP — grouping + one round-trip must beat N round-trips;
  * roofline sanity — the measured per-block cost against the TPU v5e
    projection from ``core/energy.py`` (an idealized lower bound; the
    ratio is recorded, not optimized).

The wire sub-suite (``--only wire``) measures what the PR 10 binary wire
format buys the evaluation hot path:

  * codec-level — encode+decode round-trip of one evaluation result at
    1k/100k/1M points, JSON (``wire_result``/``hydrate_result``) vs the
    binary frame (``wire.encode_frame``/``decode_frame``, zero-copy
    ``np.frombuffer`` hydration), with payload sizes;
  * end-to-end — served p50 for the same queries over the async
    frontend, one keep-alive client per format (acceptance: binary
    >= 3x JSON at 100k points, byte-identical decoded arrays);
  * warm single-query served p50 over binary wire — the request-plane
    floor a compiled-executable hit actually ships under.

The concurrency sub-suite (``--only concurrency``) races the two frontends
added across PR 1-7 head to head:

  * hot-path derive throughput + p50/p95 at increasing keep-alive
    connection counts (16/64/256 open connections), threaded
    one-thread-per-connection server vs the asyncio event loop — the
    acceptance bar is the async frontend sustaining the top connection
    count at >= 2x the threaded hot-path throughput.

The routing sub-suite (``--only routing``) measures the PR 9 load-aware
replica scheduler against the static ring-order baseline:

  * loaded vs static forwarding with one slowed replica — three
    storeful ring nodes plus a store-less routing edge; the *primary*
    owner of the hot cell sleeps ``serve_delay`` per derive, and 64
    keep-alive connections hammer the cell through the edge so every
    request pays the server-side routing hop (the edge can never serve
    from residency).  Static policy walks owners in ring order (always
    lands on the slow primary); the loaded policy's EWMA-latency +
    queue-depth selector shifts to the healthy replica after the first
    probes.  Acceptance: loaded sustains >= 2x the static hot-derive
    throughput;
  * ring vs rendezvous placement balance — primary-ownership share over
    a fixed keyset on a 5-node fleet for both placements (max/ideal and
    min/ideal recorded for each; a number, not an assertion).

Run metrics (cache hits, coalescing, p50/p95 from the server's own
/metrics, per-tier store counters) land in ``LAST_METRICS`` so ``run.py
--json`` can emit them.
"""
from __future__ import annotations

import concurrent.futures
import statistics
import tempfile
import threading
import time

from benchmarks.common import emit, header
from repro.core.artifact import ArtifactCache
from repro.core.backends import MockLLMBackend
from repro.core.store import DiskStore, PeerStore, TieredStore, build_store
from repro.serving import (
    AsyncMappingHTTPServer, MappingHTTPServer, MappingService,
    RemoteMappingService, batching_factory,
)

MODEL = "OSS:120b"
#: populated by run(); run.py --json folds this into BENCH_serving.json
LAST_METRICS: dict = {}


def run(n_hot: int = 50, n_clients: int = 8) -> dict:
    header("serving: HTTP derive latency/throughput (local server)")
    cache = ArtifactCache(tempfile.mkdtemp(prefix="bench_serving_"))
    factory = batching_factory(MockLLMBackend, max_batch=8, max_wait=0.005)
    service = MappingService(cache=cache, backend_factory=factory,
                             n_validate=20_000, sample_every=10)
    with MappingHTTPServer(service) as server:
        client = RemoteMappingService(server.url)

        # cold: one full derivation per domain, behind HTTP
        cold_us = []
        for domain in ("tri2d", "gasket2d", "msimplex3"):
            t0 = time.perf_counter()
            res = client.derive(domain, MODEL, 100)
            cold_us.append((time.perf_counter() - t0) * 1e6)
            assert res.compiled and not res.cache_hit
        emit("serving_derive_cold", statistics.median(cold_us), "http")

        # hot: repeats are server-side cache hits — serving overhead only
        hot_us = []
        for _ in range(n_hot):
            t0 = time.perf_counter()
            res = client.derive("tri2d", MODEL, 100)
            hot_us.append((time.perf_counter() - t0) * 1e6)
            assert res.cache_hit
        hot_us.sort()
        emit("serving_derive_hot_p50", hot_us[len(hot_us) // 2], "http")
        emit("serving_derive_hot_p95", hot_us[int(len(hot_us) * 0.95)], "http")

        # hot throughput: concurrent clients on cached cells
        def one_client(_):
            c = RemoteMappingService(server.url)
            for _ in range(n_hot // n_clients or 1):
                assert c.derive("gasket2d", MODEL, 100).cache_hit
            return c.stats.server_cache_hits

        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(n_clients) as pool:
            hits = sum(pool.map(one_client, range(n_clients)))
        dt = time.perf_counter() - t0
        emit("serving_derive_hot_throughput", dt / hits * 1e6,
             f"{hits / dt:.0f}rps")

        metrics = client.metrics()
    LAST_METRICS.clear()
    LAST_METRICS.update({
        "server": metrics,
        "client_stats": client.stats.as_dict(),
        "cold_us": cold_us,
        "hot_p50_us": hot_us[len(hot_us) // 2],
        "hot_p95_us": hot_us[int(len(hot_us) * 0.95)],
        "hot_rps": hits / dt,
    })
    svc_stats = metrics["service"]
    print(f"(server: {svc_stats['derivations']} derivations, "
          f"{svc_stats['cache_hits']} cache hits, "
          f"hit ratio {svc_stats['cache_hit_ratio']:.2f})")
    store_pressure()
    return LAST_METRICS


def _hot_us(svc, domain: str, n: int) -> list[float]:
    """Median-friendly per-hit latencies after a warmup request."""
    svc.derive(domain, MODEL, 100)
    out = []
    for _ in range(n):
        t0 = time.perf_counter()
        res = svc.derive(domain, MODEL, 100)
        out.append((time.perf_counter() - t0) * 1e6)
        assert res.cache_hit
    return out


def store_pressure(n_hot: int = 30, n_churn: int = 24) -> dict:
    """Hot-hit latency per store tier + throughput under eviction churn."""
    header("serving: store pressure (per-tier hot hits, eviction churn)")
    root = tempfile.mkdtemp(prefix="bench_store_")
    kw = dict(n_validate=20_000, sample_every=10)

    # memory tier: resident rehydrated result (the intended steady state)
    svc_mem = MappingService(store=build_store(root=f"{root}/mem"), **kw)
    svc_mem.derive("tri2d", MODEL, 100)
    mem_us = _hot_us(svc_mem, "tri2d", n_hot)
    assert svc_mem.store.disk.reads <= 2  # hot hits never touched disk
    emit("store_hot_memory_tier", statistics.median(mem_us), "lru")

    # disk tier: no memory tier, every hit reads + verifies + rehydrates
    svc_disk = MappingService(
        store=TieredStore(disk=DiskStore(f"{root}/disk")), **kw)
    svc_disk.derive("tri2d", MODEL, 100)
    disk_us = _hot_us(svc_disk, "tri2d", n_hot)
    emit("store_hot_disk_tier", statistics.median(disk_us), "checksum")

    # peer tier: every hit is an HTTP round-trip to the sibling that holds
    # the record (a peer-only store has no local tier to promote into)
    svc_origin = MappingService(store=build_store(root=f"{root}/origin"), **kw)
    svc_origin.derive("tri2d", MODEL, 100)
    with MappingHTTPServer(svc_origin) as origin:
        svc_peer = MappingService(
            store=TieredStore(peers=PeerStore([origin.url])), **kw)
        peer_us = _hot_us(svc_peer, "tri2d", n_hot)
    emit("store_hot_peer_tier", statistics.median(peer_us), "http")

    # eviction churn: working set > disk budget, so serves keep paying
    # eviction + re-derivation — the worst-case sustained throughput
    probe = DiskStore(f"{root}/probe")
    svc_probe = MappingService(store=TieredStore(disk=probe),
                               n_validate=2000, sample_every=1)
    rec_bytes = probe.path(
        svc_probe.derive("tri2d", MODEL, 100).cache_key).stat().st_size
    churn_store = build_store(root=f"{root}/churn",
                              max_bytes=int(rec_bytes * 2.5),
                              memory_entries=2)
    svc_churn = MappingService(store=churn_store, n_validate=2000,
                               sample_every=1)
    cells = [("tri2d", 20), ("tri2d", 50), ("tri2d", 100),
             ("gasket2d", 20), ("gasket2d", 50), ("gasket2d", 100)]
    t0 = time.perf_counter()
    for i in range(n_churn):
        domain, stage = cells[i % len(cells)]
        svc_churn.derive(domain, MODEL, stage)
    dt = time.perf_counter() - t0
    evicted = (churn_store.disk.evictions_bytes +
               churn_store.disk.evictions_ttl)
    emit("store_churn_throughput", dt / n_churn * 1e6,
         f"{n_churn / dt:.0f}ops")

    pressure = {
        "memory_p50_us": statistics.median(mem_us),
        "disk_p50_us": statistics.median(disk_us),
        "peer_p50_us": statistics.median(peer_us),
        "churn_ops_per_s": n_churn / dt,
        "churn_evictions": evicted,
        "churn_rederivations": svc_churn.stats.derivations,
        "memory_store_stats": svc_mem.store_stats(),
        "churn_store_stats": svc_churn.store_stats(),
    }
    LAST_METRICS["store_pressure"] = pressure
    print(f"(tiers p50: memory {pressure['memory_p50_us']:.0f}us, disk "
          f"{pressure['disk_p50_us']:.0f}us, peer "
          f"{pressure['peer_p50_us']:.0f}us; churn "
          f"{pressure['churn_ops_per_s']:.0f}ops/s with {evicted} evictions, "
          f"{svc_churn.stats.derivations} re-derivations)")
    return pressure


def _timed_derives(client, domain: str, stage: int, n: int,
                   before_each=None) -> list[float]:
    out = []
    for _ in range(n):
        if before_each is not None:
            before_each()
        t0 = time.perf_counter()
        res = client.derive(domain, MODEL, stage)
        out.append((time.perf_counter() - t0) * 1e6)
        assert res.cache_hit
    out.sort()
    return out


def cluster_suite(n_hot: int = 60) -> dict:
    """Keep-alive vs fresh-connection hot derive, and owner-routed vs
    forwarded derive latency on a 3-node consistent-hash ring."""
    header("serving: cluster (keep-alive transport, ring routing)")
    from repro.serving.cluster import ClusterMembership

    kw = dict(n_validate=20_000, sample_every=10)

    # -- keep-alive vs fresh connection (one server, hot cell) -------------
    svc = MappingService(store=build_store(
        root=tempfile.mkdtemp(prefix="bench_cluster_")), **kw)
    with MappingHTTPServer(svc) as server:
        pooled = RemoteMappingService(server.url)
        fresh = RemoteMappingService(server.url, keep_alive=False)
        pooled.derive("tri2d", MODEL, 100)  # derive once, then all hot
        keep_us = _timed_derives(pooled, "tri2d", 100, n_hot)
        fresh_us = _timed_derives(fresh, "tri2d", 100, n_hot)
    emit("cluster_hot_keepalive_p50", keep_us[len(keep_us) // 2], "pooled")
    emit("cluster_hot_keepalive_p95", keep_us[int(len(keep_us) * 0.95)],
         "pooled")
    emit("cluster_hot_fresh_p50", fresh_us[len(fresh_us) // 2], "tcp/req")
    emit("cluster_hot_fresh_p95", fresh_us[int(len(fresh_us) * 0.95)],
         "tcp/req")

    # -- owner-routed vs forwarded derive (3-node ring) --------------------
    root = tempfile.mkdtemp(prefix="bench_ring_")
    servers = []
    seeds = []
    for i in range(3):
        node = MappingHTTPServer(
            MappingService(store=build_store(root=f"{root}/n{i}"),
                           **kw)).start()
        node.attach_cluster(ClusterMembership(
            node.url, seeds=seeds, replicas=2, vnodes=64,
            heartbeat_interval=0.1, down_after=2.0, sync_interval=5.0))
        seeds = seeds or [node.url]
        servers.append(node)
    deadline = time.perf_counter() + 20
    while any(len(s.cluster.ring.nodes) < 3 for s in servers):
        assert time.perf_counter() < deadline, "ring never converged"
        time.sleep(0.05)
    try:
        key = servers[0].service.request_key("gasket2d", MODEL, 100)
        owners = servers[0].cluster.owners(key)
        non_owner = next(s for s in servers if s.url not in owners)
        client = RemoteMappingService(non_owner.url)
        client.derive("gasket2d", MODEL, 100)  # derive + learn the key
        cell = ("gasket2d", MODEL, 100)
        # forwarded: forget the key each time, so every request pays the
        # server-side hop from the non-owner to the ring owner
        fwd_us = _timed_derives(
            client, "gasket2d", 100, n_hot,
            before_each=lambda: client._cell_keys.pop(cell, None))
        # owner-routed: the client hashes locally and hits the owner
        client.derive("gasket2d", MODEL, 100)  # re-learn the key
        routed_us = _timed_derives(client, "gasket2d", 100, n_hot)
        assert client.stats.routed >= n_hot
        forwarded_total = non_owner.forwarded
    finally:
        for s in servers:
            s.close()
    emit("cluster_derive_forwarded_p50", fwd_us[len(fwd_us) // 2], "2hop")
    emit("cluster_derive_owner_routed_p50",
         routed_us[len(routed_us) // 2], "direct")

    cluster = {
        "keepalive_p50_us": keep_us[len(keep_us) // 2],
        "keepalive_p95_us": keep_us[int(len(keep_us) * 0.95)],
        "fresh_p50_us": fresh_us[len(fresh_us) // 2],
        "fresh_p95_us": fresh_us[int(len(fresh_us) * 0.95)],
        "keepalive_saving_p50_us": (fresh_us[len(fresh_us) // 2] -
                                    keep_us[len(keep_us) // 2]),
        "forwarded_p50_us": fwd_us[len(fwd_us) // 2],
        "owner_routed_p50_us": routed_us[len(routed_us) // 2],
        "forwarding_hop_cost_us": (fwd_us[len(fwd_us) // 2] -
                                   routed_us[len(routed_us) // 2]),
        "forwarded_requests": forwarded_total,
        "client_stats": client.stats.as_dict(),
    }
    LAST_METRICS["cluster"] = cluster
    print(f"(keep-alive p50 {cluster['keepalive_p50_us']:.0f}us vs fresh "
          f"{cluster['fresh_p50_us']:.0f}us; owner-routed p50 "
          f"{cluster['owner_routed_p50_us']:.0f}us vs forwarded "
          f"{cluster['forwarded_p50_us']:.0f}us)")
    return cluster


def _hammer_routed(entry, cell: tuple, n_conns: int,
                   per_conn: int) -> dict:
    """Like ``_hammer``, but every request forgets the client-side cell
    key first, so it lands on the non-owner entry node and pays the
    server-side hop through ``entry.router`` (a ring-aware client would
    otherwise learn the key and self-route straight to the owners)."""
    lat: list[float] = []
    mu = threading.Lock()
    gate = threading.Barrier(n_conns + 1)

    def worker():
        c = RemoteMappingService(entry.url)
        c.derive(*cell)  # opens + warms this thread's connection
        gate.wait()
        times = []
        for _ in range(per_conn):
            c._cell_keys.pop(cell, None)
            t0 = time.perf_counter()
            assert c.derive(*cell).cache_hit
            times.append(time.perf_counter() - t0)
        with mu:
            lat.extend(times)

    threads = [threading.Thread(target=worker) for _ in range(n_conns)]
    for t in threads:
        t.start()
    gate.wait()  # every connection is open before the clock starts
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    lat.sort()
    return {
        "connections": n_conns,
        "requests": n_conns * per_conn,
        "rps": n_conns * per_conn / dt,
        "p50_us": lat[len(lat) // 2] * 1e6,
        "p95_us": lat[int(len(lat) * 0.95)] * 1e6,
    }


def routing_suite(n_conns: int = 64, per_conn: int = 6,
                  serve_delay: float = 1.5) -> dict:
    """Load-aware ("loaded") vs static ring-order replica selection with
    one artificially slowed replica, plus ring-vs-rendezvous placement
    balance.  Acceptance: loaded sustains >= 2x the static hot-derive
    throughput at ``n_conns`` keep-alive connections."""
    header("serving: routing (load-aware replica selection, placement)")
    from repro.serving.cluster import (ClusterMembership, HashRing,
                                       RendezvousHash)
    from repro.serving.router import RequestRouter

    kw = dict(n_validate=20_000, sample_every=10)
    root = tempfile.mkdtemp(prefix="bench_routing_")
    servers: list = []
    seeds: list = []
    for i in range(4):
        # the 4th node is a store-less routing edge: it can never satisfy
        # the hot cell from residency, so every request it receives pays
        # the router dispatch + forward hop the suite is measuring
        store = build_store(root=f"{root}/n{i}") if i < 3 else None
        node = MappingHTTPServer(
            MappingService(store=store, **kw)).start()
        node.attach_cluster(ClusterMembership(
            node.url, seeds=seeds, replicas=2, vnodes=64,
            heartbeat_interval=0.1, down_after=2.0, sync_interval=5.0,
            weight=1.0 if i < 3 else 1e-9))
        seeds = seeds or [node.url]
        servers.append(node)
    deadline = time.perf_counter() + 20
    while any(len(s.cluster.ring.nodes) < 4 for s in servers):
        assert time.perf_counter() < deadline, "ring never converged"
        time.sleep(0.05)

    results: dict = {"connections": n_conns,
                     "serve_delay_s": serve_delay}
    try:
        entry = servers[3]
        # the edge holds one token-weight vnode, so a given cell has a
        # tiny chance of hashing to it — scan a few cells for one whose
        # owners are both storeful nodes (ports randomize the hashes, so
        # this must be computed per run, not hardcoded)
        cell = next(
            c for c in [(d, MODEL, s) for s in (100, 50, 20)
                        for d in ("tri2d", "gasket2d", "carpet2d")]
            if entry.url not in servers[0].cluster.owners(
                servers[0].service.request_key(*c)))
        results["cell"] = list(cell)
        key = servers[0].service.request_key(*cell)
        owners = servers[0].cluster.owners(key)
        slow = next(s for s in servers if s.url == owners[0])
        # derive once so both phases measure pure forwarded cache hits
        RemoteMappingService(entry.url).derive(*cell)
        slow.serve_delay = serve_delay  # the *primary* owner goes hot:
        # static ring-order forwarding lands every request on it
        for policy in ("static", "loaded"):
            # fresh router per phase: no learned state leaks from the
            # static baseline into the loaded run.  epsilon 0 keeps both
            # deterministic — measured latency + advertised depth do the
            # steering; exploration buys nothing with one candidate pair
            entry.router = RequestRouter(policy=policy, epsilon=0.0,
                                         seed=0)
            entry.cluster.load_provider = entry.router.load
            entry.cluster.on_load = entry.router.advertise
            row = _hammer_routed(entry, cell, n_conns, per_conn)
            row["selections"] = {
                url: snap["selections"] for url, snap in
                entry.router.selector.snapshot().items()}
            results[policy] = row
            emit(f"routing_{policy}_hot_fwd_p50", row["p50_us"],
                 f"{row['rps']:.0f}rps")
        slow.serve_delay = 0.0
    finally:
        for s in servers:
            s.close()

    speedup = results["loaded"]["rps"] / results["static"]["rps"]
    results["loaded_speedup"] = speedup

    # -- ring vs rendezvous placement balance (pure data structures) ------
    nodes = [f"http://10.0.0.{i}:8080" for i in range(1, 6)]
    keys = [f"cell-{i:04d}" for i in range(512)]
    ideal = len(keys) / len(nodes)
    balance: dict = {}
    for kind, placement in (
            ("ring", HashRing(nodes, vnodes=64, replicas=2)),
            ("rendezvous", RendezvousHash(nodes, replicas=2))):
        counts = {n: 0 for n in nodes}
        for k in keys:
            counts[placement.owners(k)[0]] += 1
        balance[kind] = {
            "max_over_ideal": max(counts.values()) / ideal,
            "min_over_ideal": min(counts.values()) / ideal,
        }
        emit(f"routing_balance_{kind}",
             balance[kind]["max_over_ideal"],
             f"min {balance[kind]['min_over_ideal']:.2f}x ideal")
    results["balance"] = balance

    LAST_METRICS["routing"] = results
    print(f"(loaded {results['loaded']['rps']:.0f}rps vs static "
          f"{results['static']['rps']:.0f}rps = {speedup:.1f}x with the "
          f"primary owner sleeping {serve_delay * 1e3:.0f}ms; balance "
          f"max/ideal ring {balance['ring']['max_over_ideal']:.2f}x vs "
          f"rendezvous {balance['rendezvous']['max_over_ideal']:.2f}x)")
    # acceptance: with one slowed replica, load-aware selection sustains
    # >= 2x the static ring-order hot-derive throughput
    assert speedup >= 2.0, (
        f"loaded policy only {speedup:.2f}x static with a slowed replica "
        f"at {n_conns} connections")
    return results


def evaluate_suite(n_warm: int = 30, n_loops: int = 3) -> dict:
    """Evaluation-plane numbers: cold trace vs warm compiled-cache hit,
    batched heterogeneous /v1/evaluate vs a sequential per-query loop,
    and the measured per-block cost against the energy-model roofline."""
    header("serving: evaluate (compile cache, batched hot path)")
    from repro.core import compile_cache as cc
    from repro.core.domains import DOMAINS
    from repro.core.energy import tpu_block_projection
    from repro.serving.evaluate import EvaluationService

    # -- cold trace vs warm hit (private cache, no HTTP in the way) --------
    local = EvaluationService(compile_cache=cc.CompileCache(max_entries=64))
    probes = [
        {"domain": "tri2d", "n_points": 4096},
        {"domain": "gasket2d", "n_points": 2048},
        {"domain": "msimplex3", "n_points": 1024},
        {"domain": "tri2d", "tier": "membership", "extent": [48, 48]},
    ]
    cold_us = []
    for q in probes:
        t0 = time.perf_counter()
        res = local.evaluate(q)
        cold_us.append((time.perf_counter() - t0) * 1e6)
        assert res["executable"] == "miss"
    warm_us = []
    for _ in range(n_warm):
        for q in probes:
            t0 = time.perf_counter()
            res = local.evaluate(q)
            warm_us.append((time.perf_counter() - t0) * 1e6)
            assert res["executable"] == "hit"
    warm_us.sort()
    cold_p50 = statistics.median(cold_us)
    warm_p50 = warm_us[len(warm_us) // 2]
    warm_p95 = warm_us[int(len(warm_us) * 0.95)]
    warm_speedup = cold_p50 / warm_p50
    emit("evaluate_cold_p50", cold_p50, "trace")
    emit("evaluate_warm_p50", warm_p50, "cached")
    emit("evaluate_warm_p95", warm_p95, "cached")
    assert warm_speedup >= 10, (
        f"warm path only {warm_speedup:.1f}x below cold (need >= 10x)")

    # -- batched heterogeneous request vs sequential loop, over HTTP -------
    cache = ArtifactCache(tempfile.mkdtemp(prefix="bench_evaluate_"))
    factory = batching_factory(MockLLMBackend, max_batch=8, max_wait=0.005)
    service = MappingService(cache=cache, backend_factory=factory,
                             n_validate=20_000, sample_every=10)
    hetero = [
        {"domain": "tri2d", "n_points": 512},
        {"domain": "tri2d", "n_points": 1024},
        {"domain": "tri2d", "n_points": 2048},
        {"domain": "gasket2d", "n_points": 512},
        {"domain": "gasket2d", "n_points": 1024},
        {"domain": "gasket2d", "n_points": 2048},
        {"domain": "msimplex3", "n_points": 512},
        {"domain": "msimplex3", "n_points": 1024},
        {"domain": "tri2d", "tier": "membership", "extent": [32, 32]},
        {"domain": "tri2d", "tier": "membership", "extent": [32, 32]},
        {"domain": "gasket2d", "tier": "membership", "extent": [27, 27]},
        {"domain": "msimplex3", "tier": "membership", "extent": [9, 9, 9]},
    ]

    def seq_pass(client) -> float:
        t0 = time.perf_counter()
        for q in hetero:
            if q.get("tier") == "membership":
                client.evaluate(q["domain"], tier="membership",
                                extent=q["extent"])
            else:
                client.evaluate(q["domain"], n_points=q["n_points"])
        return time.perf_counter() - t0

    with MappingHTTPServer(service) as server:
        client = RemoteMappingService(server.url)
        # warm both code paths: the batch uses group-padded executables,
        # the loop uses per-query ones — distinct cache entries
        batch_res = client.evaluate_batch(hetero)
        seq_pass(client)
        seq_s = min(seq_pass(client) for _ in range(n_loops))
        t_batch = []
        for _ in range(n_loops):
            t0 = time.perf_counter()
            batch_res = client.evaluate_batch(hetero)
            t_batch.append(time.perf_counter() - t0)
        batch_s = min(t_batch)
        groups = len({r["group"] for r in batch_res}) \
            if all("group" in r for r in batch_res) else 0
        metrics = client.metrics()
    batch_speedup = seq_s / batch_s
    emit("evaluate_seq_loop", seq_s / len(hetero) * 1e6, "n*http")
    emit("evaluate_batched", batch_s / len(hetero) * 1e6, "1*http")
    assert batch_speedup > 1, (
        f"batched request slower than sequential loop ({batch_speedup:.2f}x)")

    # -- roofline sanity: measured per-block cost vs TPU v5e projection ----
    n_points, block_n = 65_536, 1024
    roof_q = {"domain": "tri2d", "n_points": n_points, "block_n": block_n}
    local.evaluate(roof_q)  # compile
    t0 = time.perf_counter()
    roof_res = local.evaluate(roof_q)
    roof_s = time.perf_counter() - t0
    assert roof_res["executable"] == "hit"
    n_blocks = roof_res["padded"] // block_n
    dim = DOMAINS["tri2d"].dim
    # per-point work model: ~12 integer ops per digit of the address
    # computation, (dim coords + λ) words of traffic
    proj = tpu_block_projection(
        flops_per_block=block_n * roof_res["ndigits"] * 12,
        bytes_per_block=block_n * (dim + 1) * 4,
        n_blocks=n_blocks)
    measured_block_us = roof_s / n_blocks * 1e6
    roofline_block_us = proj["time_s"] / n_blocks * 1e6
    emit("evaluate_block_measured", measured_block_us, "warm")
    emit("evaluate_block_roofline", roofline_block_us, proj["bound"])
    # the projection is an idealized accelerator lower bound — a measured
    # interpret-mode CPU number below it would mean the model is broken
    assert measured_block_us >= roofline_block_us

    ev = {
        "cold_p50_us": cold_p50,
        "warm_p50_us": warm_p50,
        "warm_p95_us": warm_p95,
        "warm_speedup": warm_speedup,
        "seq_loop_s": seq_s,
        "batch_s": batch_s,
        "batch_speedup": batch_speedup,
        "batch_queries": len(hetero),
        "batch_groups": groups,
        "roofline": {
            "n_blocks": n_blocks,
            "measured_block_us": measured_block_us,
            "roofline_block_us": roofline_block_us,
            "bound": proj["bound"],
            "ratio": measured_block_us / roofline_block_us,
        },
        "local_stats": local.stats_dict(),
        "server_metrics": {k: metrics.get(k)
                           for k in ("evaluate", "compile_cache", "http")},
        "client_stats": client.stats.as_dict(),
    }
    LAST_METRICS["evaluate"] = ev
    print(f"(cold p50 {cold_p50 / 1e3:.1f}ms vs warm p50 "
          f"{warm_p50:.0f}us = {warm_speedup:.0f}x; batch of {len(hetero)} "
          f"in {groups} groups {batch_speedup:.1f}x faster than the loop; "
          f"measured/roofline per-block {ev['roofline']['ratio']:.0f}x)")
    return ev


def wire_suite(reps: int = 9, n_single: int = 100) -> dict:
    """Binary vs JSON evaluation wire: codec-level encode+decode cost,
    end-to-end served p50 at 1k/100k/1M points, and the warm single-query
    served p50 (the number the compiled-executable win was drowning under
    JSON).  Asserts binary end-to-end >= 3x JSON on the 100k row with
    byte-identical decoded arrays."""
    header("serving: wire (binary vs JSON evaluation framing)")
    import json

    import numpy as np

    from repro.core import compile_cache as cc
    from repro.serving import wire
    from repro.serving.evaluate import (
        EvaluationService, hydrate_result, wire_result,
    )
    from benchmarks.common import timed

    sizes = (1_000, 100_000, 1_000_000)
    local = EvaluationService(compile_cache=cc.CompileCache(max_entries=16))
    codec: dict = {}
    for n in sizes:
        res = local.evaluate({"domain": "tri2d", "n_points": n})
        blob_j = json.dumps(wire_result(res), default=str).encode()
        blob_b = wire.encode_frame(res)
        back_j = hydrate_result(json.loads(blob_j))
        back_b = wire.decode_frame(blob_b)
        # byte-identity: both framings return exactly what was computed
        np.testing.assert_array_equal(back_b["coords"], res["coords"])
        np.testing.assert_array_equal(back_j["coords"], res["coords"])
        assert back_b["coords"].dtype == res["coords"].dtype
        assert back_j["coords"].dtype == res["coords"].dtype
        _, je = timed(lambda r=res: json.dumps(
            wire_result(r), default=str).encode())
        _, jd = timed(lambda b=blob_j: hydrate_result(json.loads(b)))
        _, be = timed(lambda r=res: wire.encode_frame(r))
        _, bd = timed(lambda b=blob_b: wire.decode_frame(b))
        emit(f"wire_codec_json_{n}", je + jd, f"{len(blob_j)}B")
        emit(f"wire_codec_bin_{n}", be + bd, f"{len(blob_b)}B")
        codec[n] = {"json_us": je + jd, "bin_us": be + bd,
                    "json_bytes": len(blob_j), "bin_bytes": len(blob_b),
                    "codec_speedup": (je + jd) / (be + bd)}

    # -- end-to-end over the async frontend (the default serving shape) ----
    service = MappingService(store=None)
    e2e: dict = {}
    with AsyncMappingHTTPServer(service) as server:
        cli_b = RemoteMappingService(server.url)
        cli_j = RemoteMappingService(server.url, binary=False)
        for n in sizes:
            reps_n = reps if n < 1_000_000 else 3
            p50s = {}
            for name, cli in (("json", cli_j), ("bin", cli_b)):
                cli.evaluate("tri2d", n_points=n)  # warm: compile + blob LRU
                xs = []
                for _ in range(reps_n):
                    t0 = time.perf_counter()
                    got = cli.evaluate("tri2d", n_points=n)
                    xs.append(time.perf_counter() - t0)
                xs.sort()
                p50s[name] = xs[len(xs) // 2]
                p50s[name + "_res"] = got
            np.testing.assert_array_equal(p50s["bin_res"]["coords"],
                                          p50s["json_res"]["coords"])
            assert p50s["bin_res"]["coords"].dtype == \
                p50s["json_res"]["coords"].dtype
            emit(f"wire_e2e_json_{n}", p50s["json"] * 1e6, "p50")
            emit(f"wire_e2e_bin_{n}", p50s["bin"] * 1e6, "p50")
            e2e[n] = {"json_p50_us": p50s["json"] * 1e6,
                      "bin_p50_us": p50s["bin"] * 1e6,
                      "speedup": p50s["json"] / p50s["bin"]}
        # warm single-query served p50: one typical-size query on a hot
        # keep-alive connection, binary wire, measured at the socket (a
        # prebuilt request + minimal response parse) so the number is the
        # server's turnaround + binary decode — the request-plane floor a
        # compiled-executable hit actually ships under.  http.client's own
        # header-parsing tax lands in the pooled-client row next to it.
        single_p50 = _raw_single_p50(server.host, server.port, n_single)
        emit("wire_single_warm_p50", single_p50, "bin+socket")
        singles = []
        for _ in range(n_single):
            t0 = time.perf_counter()
            cli_b.evaluate("tri2d", n_points=1024)
            singles.append(time.perf_counter() - t0)
        singles.sort()
        client_p50 = singles[len(singles) // 2] * 1e6
        emit("wire_single_client_p50", client_p50, "bin+pooled")
        metrics = cli_b.metrics()
        cli_b.close()
        cli_j.close()
    speedup_100k = e2e[100_000]["speedup"]
    assert speedup_100k >= 3, (
        f"binary only {speedup_100k:.2f}x faster than JSON at 100k points "
        "(need >= 3x)")
    out = {
        "codec": codec,
        "e2e": e2e,
        "speedup_100k": speedup_100k,
        "single_warm_p50_us": single_p50,
        "single_client_p50_us": client_p50,
        "eval_wire_cache": metrics.get("evaluate_wire"),
        "aio": metrics.get("aio"),
    }
    LAST_METRICS["wire"] = out
    print(f"(100k e2e: JSON {e2e[100_000]['json_p50_us'] / 1e3:.1f}ms vs "
          f"binary {e2e[100_000]['bin_p50_us'] / 1e3:.2f}ms = "
          f"{speedup_100k:.1f}x; 1M e2e "
          f"{e2e[1_000_000]['speedup']:.1f}x; warm single-query served p50 "
          f"{single_p50:.0f}us, {client_p50:.0f}us through the pooled "
          "client)")
    return out


def _raw_single_p50(host: str, port: int, n: int,
                    n_points: int = 1024) -> float:
    """Served p50 (us) for one warm binary evaluate on a hot keep-alive
    socket: prebuilt request bytes in, status line + headers + body out,
    ``wire.decode_frame`` on the payload.  Asserts the timed responses
    come off the compiled-executable cache."""
    import json
    import socket

    from repro.serving import wire

    body = json.dumps({"domain": "tri2d", "n_points": n_points}).encode()
    req = (b"POST /v1/evaluate HTTP/1.1\r\nHost: bench\r\n"
           b"Content-Type: application/json\r\n"
           b"Accept: " + wire.CONTENT_TYPE.encode() + b"\r\n"
           b"Content-Length: " + str(len(body)).encode() + b"\r\n\r\n"
           + body)
    sock = socket.create_connection((host, port))
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    reader = sock.makefile("rb")

    def once() -> bytes:
        sock.sendall(req)
        clen = 0
        reader.readline()  # status line
        while True:
            line = reader.readline()
            if line in (b"\r\n", b""):
                break
            if line.lower().startswith(b"content-length:"):
                clen = int(line.split(b":", 1)[1])
        return reader.read(clen)

    try:
        once()  # compile + wire-LRU warmup
        wire.decode_frame(once())
        xs = []
        for _ in range(n):
            t0 = time.perf_counter()
            res = wire.decode_frame(once())
            xs.append((time.perf_counter() - t0) * 1e6)
        assert res["executable"] == "hit"
    finally:
        reader.close()
        sock.close()
    xs.sort()
    return xs[len(xs) // 2]


def _hammer(server, n_conns: int, per_conn: int) -> dict:
    """n_conns keep-alive connections (one pooled client each) hammering a
    hot cell: aggregate throughput + per-request p50/p95."""
    lat: list[float] = []
    mu = threading.Lock()
    gate = threading.Barrier(n_conns + 1)

    def worker():
        c = RemoteMappingService(server.url)
        c.derive("tri2d", MODEL, 100)  # opens + warms this thread's conn
        gate.wait()
        times = []
        for _ in range(per_conn):
            t0 = time.perf_counter()
            assert c.derive("tri2d", MODEL, 100).cache_hit
            times.append(time.perf_counter() - t0)
        with mu:
            lat.extend(times)

    threads = [threading.Thread(target=worker) for _ in range(n_conns)]
    for t in threads:
        t.start()
    gate.wait()  # every connection is open before the clock starts
    server_conns = getattr(server, "connections", n_conns)
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    lat.sort()
    return {
        "connections": n_conns,
        "server_connections": server_conns,
        "requests": n_conns * per_conn,
        "rps": n_conns * per_conn / dt,
        "p50_us": lat[len(lat) // 2] * 1e6,
        "p95_us": lat[int(len(lat) * 0.95)] * 1e6,
    }


def concurrency_suite(levels=(16, 64, 256), total: int = 2048) -> dict:
    """Threaded vs async frontend under rising connection counts.

    Both serve the same hot cell from identical private stores; every
    request is a server-side cache hit, so the numbers are pure frontend
    cost — thread-per-connection scheduling vs the event loop's inline
    fast path."""
    header("serving: concurrency (threaded vs async frontend)")
    results: dict = {"levels": list(levels), "threaded": {}, "async": {}}
    kw = dict(n_validate=20_000, sample_every=10)
    for kind in ("threaded", "async"):
        cache = ArtifactCache(tempfile.mkdtemp(prefix=f"bench_conc_{kind}_"))
        factory = batching_factory(MockLLMBackend, max_batch=8,
                                   max_wait=0.005)
        service = MappingService(cache=cache, backend_factory=factory, **kw)
        server = MappingHTTPServer(service) if kind == "threaded" \
            else AsyncMappingHTTPServer(service)
        with server:
            RemoteMappingService(server.url).derive("tri2d", MODEL, 100)
            for n in levels:
                row = _hammer(server, n, max(4, total // n))
                results[kind][n] = row
                emit(f"concurrency_{kind}_{n}conn", row["p50_us"],
                     f"{row['rps']:.0f}rps")

    top = levels[-1]
    speedup = (results["async"][top]["rps"] /
               results["threaded"][top]["rps"])
    results["top_connections"] = top
    results["async_speedup_at_top"] = speedup
    LAST_METRICS["concurrency"] = results
    print(f"(at {top} connections: async "
          f"{results['async'][top]['rps']:.0f}rps vs threaded "
          f"{results['threaded'][top]['rps']:.0f}rps = {speedup:.1f}x; "
          f"async p95 {results['async'][top]['p95_us'] / 1e3:.1f}ms)")
    # acceptance: the event loop sustains the top connection count at
    # >= 2x the threaded hot-path throughput
    assert results["async"][top]["server_connections"] >= top, (
        f"async frontend held {results['async'][top]['server_connections']} "
        f"of {top} connections")
    assert speedup >= 2.0, (
        f"async frontend only {speedup:.2f}x threaded at {top} connections "
        f"(need >= 2x)")
    return results


def loadgen_suite(requests: int = 200, concurrency: int = 8) -> dict:
    """Zipf trace replay against a self-hosted 2-node async fleet — the SLO
    harness exercised end to end (see ``benchmarks/loadgen.py``)."""
    from benchmarks import loadgen

    header("serving: trace-driven load generation (2-node fleet, zipf)")
    spec = loadgen.LoadSpec(requests=requests, concurrency=concurrency,
                            trace_sample=0.1)
    urls, close = loadgen._self_fleet(2)
    try:
        _, report = loadgen.run(urls, spec)
    finally:
        close()
    emit("loadgen_p50", report["p50_ms"] * 1e3,
         f"{report['throughput_rps']:.0f}rps")
    emit("loadgen_p99", report["p99_ms"] * 1e3,
         f"shed_rate={report['shed_rate']:.3f}")
    LAST_METRICS["loadgen"] = report
    print(f"(replayed {report['requests']} requests at "
          f"{report['throughput_rps']:.0f}rps: p50 {report['p50_ms']:.1f}ms "
          f"p99 {report['p99_ms']:.1f}ms, sheds {report['sheds']}, "
          f"errors {report['errors']})")
    assert report["error_rate"] == 0.0, \
        f"loadgen replay saw errors: {report}"
    return report


if __name__ == "__main__":
    run()
    cluster_suite()
    evaluate_suite()
    concurrency_suite()
    loadgen_suite()
