"""Observability plane: metrics registry, Prometheus exposition, request
tracing across frontends/threads/nodes, and the loadgen/SLO harness."""
from __future__ import annotations

import dataclasses
import json
import time
import urllib.error
import urllib.request

import pytest

from repro.core.backends import MockLLMBackend
from repro.core.store import build_store
from repro.obs import Observability
from repro.obs.metrics import (
    DEFAULT_BUCKETS,
    Counter,
    EndpointStats,
    Gauge,
    Histogram,
    MetricsRegistry,
    flatten_payload,
    parse_prometheus,
)
from repro.obs.trace import (
    TRACE_HEADER,
    TraceBuffer,
    activate,
    deactivate,
    new_trace_id,
    record_for_meta,
    span,
    valid_trace_id,
)
from repro.serving import (
    AsyncMappingHTTPServer,
    MappingHTTPServer,
    MappingService,
    RemoteMappingService,
)
from repro.serving.cluster import ClusterMembership

MODEL = "OSS:120b"


def make_service(tmp_path, name="svc"):
    return MappingService(store=build_store(root=tmp_path / name),
                          backend_factory=MockLLMBackend,
                          n_validate=2000, sample_every=1)


def post_json(url: str, body: dict, headers: dict | None = None):
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=30) as resp:
        return resp.status, dict(resp.headers), json.loads(resp.read())


def get_json(url: str, headers: dict | None = None):
    req = urllib.request.Request(url, headers=headers or {})
    with urllib.request.urlopen(req, timeout=30) as resp:
        return resp.status, dict(resp.headers), resp.read()


def wait_for_span(url: str, trace_id: str, name: str,
                  timeout: float = 5.0) -> dict:
    """Poll one node for a span — the ingress span lands an instant after
    the response bytes, so reads must tolerate that gap."""
    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        try:
            _, _, raw = get_json(f"{url}/v1/trace/{trace_id}")
            last = json.loads(raw)
            for sp in last["spans"]:
                if sp["name"] == name:
                    return last
        except urllib.error.HTTPError:
            pass
        time.sleep(0.02)
    raise AssertionError(
        f"span {name!r} never appeared in trace {trace_id} on {url}: {last}")


# ---------------------------------------------------------------------------
# Registry units
# ---------------------------------------------------------------------------


def test_counter_gauge_basics():
    c = Counter("repro_things_total", "things")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    g = Gauge("repro_level", "level", labels={"tier": "memory"})
    g.set(7)
    g.inc()
    g.dec(3)
    assert g.value == 5
    assert list(g.samples()) == [("repro_level", {"tier": "memory"}, 5)]


def test_histogram_fixed_buckets_and_quantiles():
    h = Histogram("repro_lat_seconds", buckets=(0.001, 0.01, 0.1))
    for _ in range(100):
        h.observe(0.0004)
    for _ in range(10_000):
        h.observe(0.05)
    h.observe(5.0)  # overflow bucket
    # storage is bounded by construction: one int per bucket + overflow
    assert len(h._counts) == 4
    assert h.count == 10_101
    assert h.quantile(0.5) > 0.0, "quantiles must be nonzero with samples"
    assert 0.01 <= h.quantile(0.5) <= 0.1
    # the open-ended bucket is capped at the observed max
    assert h.quantile(0.9999) <= 5.0


def test_histogram_first_bucket_quantile_nonzero():
    h = Histogram("repro_fast_seconds")
    for _ in range(8):
        h.observe(1e-5)  # far below the first bucket bound
    assert h.quantile(0.5) > 0.0
    assert h.quantile(0.95) > 0.0


def test_endpoint_stats_dict_shape():
    stats = EndpointStats(Histogram("repro_http_request_seconds"))
    stats.record(0.002, ok=True)
    stats.record(0.004, ok=False)
    d = stats.as_dict()
    assert d["requests"] == 2
    assert d["errors"] == 1
    for k in ("p50_ms", "p95_ms", "p99_ms"):
        assert d[k] > 0.0


def test_registry_get_or_create_and_kind_mismatch():
    reg = MetricsRegistry()
    c1 = reg.counter("repro_hits", "hits", tier="memory")
    c2 = reg.counter("repro_hits", tier="memory")
    assert c1 is c2
    # same name, different labels = a distinct series
    assert reg.counter("repro_hits", tier="disk") is not c1
    with pytest.raises(ValueError):
        reg.gauge("repro_hits", tier="memory")
    with pytest.raises(ValueError):
        reg.counter("bad name with spaces")


def test_prometheus_exposition_round_trips():
    reg = MetricsRegistry()
    reg.counter("repro_derivations_total", "count").inc(3)
    reg.histogram("repro_lat_seconds", "latency",
                  endpoint="derive").observe(0.002)
    text = reg.prometheus({"store": {"hits": 5, "nested": {"rate": 0.5}},
                           "name": "skipped-string"})
    series = parse_prometheus(text)
    assert series["repro_derivations_total"] == 3
    assert series["repro_lat_seconds_count{endpoint=\"derive\"}"] == 1
    assert series["repro_store_hits"] == 5
    assert series["repro_store_nested_rate"] == 0.5
    assert not any("skipped-string" in k for k in series)
    assert "# TYPE repro_lat_seconds histogram" in text


def test_prometheus_label_escaping():
    reg = MetricsRegistry()
    reg.counter("repro_c", "c", node='we"ird\nvalue\\x').inc()
    text = reg.prometheus()
    assert '\\"' in text and "\\n" in text and "\\\\" in text
    parse_prometheus(text)  # must still parse


def test_parse_prometheus_rejects_malformed():
    with pytest.raises(ValueError):
        parse_prometheus("repro_ok 1\njustonetoken")
    with pytest.raises(ValueError):
        parse_prometheus("repro_bad notanumber")


def test_flatten_payload_numeric_leaves_only():
    flat = dict(flatten_payload({
        "a": 1, "b": {"c": 2.5, "d": "str", "e": None, "f": [1, 2]},
        "ok": True}, "x"))
    assert flat == {"x_a": 1.0, "x_b_c": 2.5, "x_ok": 1.0}


# ---------------------------------------------------------------------------
# Trace primitives
# ---------------------------------------------------------------------------


def test_valid_trace_id():
    assert valid_trace_id(new_trace_id())
    assert valid_trace_id("ab" * 4)
    assert not valid_trace_id("AB" * 16)      # uppercase
    assert not valid_trace_id("xyz")          # short + non-hex
    assert not valid_trace_id("ab" * 40)      # too long
    assert not valid_trace_id(None)
    assert not valid_trace_id(123)


def test_trace_buffer_ring_bounds():
    buf = TraceBuffer(max_traces=2, max_spans=2)
    for i in range(4):
        buf.record(f"{i:032x}", {"name": f"s{i}"})
    assert len(buf.ids()) == 2
    assert buf.dropped_traces == 2
    tid = buf.ids()[-1]
    buf.record(tid, {"name": "extra1"})
    buf.record(tid, {"name": "extra2"})  # over max_spans
    assert buf.get(tid)["span_count"] == 2
    assert buf.dropped_spans == 1
    stats = buf.stats()
    assert stats["traces"] == 2 and stats["dropped_spans"] == 1


def test_span_noop_without_active_trace():
    with span("orphan", attr=1) as s:
        s["later"] = 2  # writable, but recorded nowhere
    # record_for_meta without a snapshot is also a no-op
    record_for_meta({}, "orphan", 0.1)


def test_span_records_into_active_trace_with_error():
    buf = TraceBuffer()
    token = activate(buf, "ab" * 16)
    try:
        with span("work", tier="disk") as s:
            s["hit"] = True
        with pytest.raises(RuntimeError):
            with span("boom"):
                raise RuntimeError("x")
    finally:
        deactivate(token)
    spans = buf.get("ab" * 16)["spans"]
    assert spans[0]["name"] == "work"
    assert spans[0]["tier"] == "disk" and spans[0]["hit"] is True
    assert spans[0]["duration_ms"] >= 0.0
    assert spans[1]["error"] == "RuntimeError"
    # deactivated: spans no longer record
    with span("after"):
        pass
    assert buf.get("ab" * 16)["span_count"] == 2


def test_observability_disabled_skips_tracing_not_metrics():
    obs = Observability(mode="x", enabled=False)
    assert obs.begin_request("ab" * 16) is None
    obs.end_request(None, "derive", 0.01, True)
    assert obs.traces.ids() == []
    obs.observe("derive", 0.01, True)  # metrics still flow
    assert obs.http_dict()["derive"]["requests"] == 1


# ---------------------------------------------------------------------------
# Frontend surface: parity, healthz, Prometheus, single-node traces
# ---------------------------------------------------------------------------


def _exercise(url: str):
    post_json(f"{url}/v1/derive",
              {"domain": "tri2d", "model": MODEL, "stage": 100})
    get_json(f"{url}/healthz")
    get_json(f"{url}/metrics")


def test_metrics_parity_between_frontends(tmp_path):
    with MappingHTTPServer(make_service(tmp_path, "t")) as threaded, \
            AsyncMappingHTTPServer(make_service(tmp_path, "a")) as aio:
        for server in (threaded, aio):
            _exercise(server.url)
        # endpoint stats land in a finally after response bytes: poll until
        # both frontends have recorded all three exercised endpoints
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            mt = threaded.metrics()
            ma = aio.metrics()
            if set(ma["http"]) == set(mt["http"]) == {
                    "derive", "healthz", "metrics"}:
                break
            time.sleep(0.02)
    # identical top-level key set, modulo the async-only "aio" alias
    assert set(ma) - {"aio"} == set(mt)
    # the shared frontend section carries the same keys too
    assert set(ma["frontend"]) - {"aio"} == set(mt["frontend"])
    assert mt["frontend"]["mode"] == "threaded"
    assert ma["frontend"]["mode"] == "async"
    # and the http sections saw the same endpoints with the same shape
    assert set(ma["http"]) == set(mt["http"])
    for section in (mt, ma):
        d = section["http"]["derive"]
        assert d["requests"] >= 1 and d["p50_ms"] > 0.0


@pytest.mark.parametrize("cls", [MappingHTTPServer, AsyncMappingHTTPServer])
def test_healthz_reports_uptime_and_mode(tmp_path, cls):
    with cls(make_service(tmp_path)) as server:
        _, _, raw = get_json(f"{server.url}/healthz")
        hz = json.loads(raw)
    assert hz["status"] == "ok"
    assert hz["mode"] in ("threaded", "async")
    assert hz["uptime_seconds"] > 0.0
    assert hz["started_unix"] <= time.time()
    assert hz["backend_names"] == []


@pytest.mark.parametrize("cls", [MappingHTTPServer, AsyncMappingHTTPServer])
def test_prometheus_endpoint_is_valid_exposition(tmp_path, cls):
    with cls(make_service(tmp_path)) as server:
        _exercise(server.url)
        _, headers, raw = get_json(
            f"{server.url}/metrics?format=prometheus")
    assert headers["Content-Type"].startswith("text/plain")
    series = parse_prometheus(raw.decode())
    assert any(k.startswith('repro_http_request_seconds_bucket{')
               for k in series)
    assert any(k.startswith("repro_service_") for k in series)
    # JSON /metrics numeric leaves are all scrapeable
    assert "repro_store_hits" in series


@pytest.mark.parametrize("cls", [MappingHTTPServer, AsyncMappingHTTPServer])
def test_trace_roundtrip_single_node(tmp_path, cls):
    tid = "cd" * 16
    with cls(make_service(tmp_path)) as server:
        status, headers, _ = post_json(
            f"{server.url}/v1/derive",
            {"domain": "tri2d", "model": MODEL, "stage": 100},
            headers={TRACE_HEADER: tid})
        assert status == 200
        # the trace ID echoes on the response
        assert headers[TRACE_HEADER] == tid
        trace = wait_for_span(server.url, tid, "derive")
        names = [sp["name"] for sp in trace["spans"]]
        # cold derive: local tier probes + inference + validation happened
        # under this request's trace
        assert "store_memory" in names
        assert "inference" in names
        assert "validation" in names
        assert trace["trace_id"] == tid
        assert trace["node"] == server.url
        # a hot repeat records a fresh (server-minted) trace too
        status, headers, _ = post_json(
            f"{server.url}/v1/derive",
            {"domain": "tri2d", "model": MODEL, "stage": 100})
        minted = headers[TRACE_HEADER]
        assert valid_trace_id(minted) and minted != tid
        _, _, raw = get_json(f"{server.url}/v1/traces")
        listing = json.loads(raw)
        assert tid in listing["traces"]
        assert listing["stats"]["max_traces"] > 0


def test_malformed_trace_header_gets_fresh_id(tmp_path):
    with MappingHTTPServer(make_service(tmp_path)) as server:
        _, headers, _ = post_json(
            f"{server.url}/v1/derive",
            {"domain": "tri2d", "model": MODEL, "stage": 100},
            headers={TRACE_HEADER: "NOT-HEX-AT-ALL!"})
        echoed = headers[TRACE_HEADER]
        assert valid_trace_id(echoed)
        assert echoed != "NOT-HEX-AT-ALL!"


@pytest.mark.parametrize("cls", [MappingHTTPServer, AsyncMappingHTTPServer])
def test_tracing_disabled_serves_without_traces(tmp_path, cls):
    tid = "ef" * 16
    with cls(make_service(tmp_path), observability=False) as server:
        status, headers, _ = post_json(
            f"{server.url}/v1/derive",
            {"domain": "tri2d", "model": MODEL, "stage": 100},
            headers={TRACE_HEADER: tid})
        assert status == 200
        assert TRACE_HEADER not in headers
        with pytest.raises(urllib.error.HTTPError):
            get_json(f"{server.url}/v1/trace/{tid}")
        # metrics keep flowing
        m = server.metrics()
        assert m["http"]["derive"]["requests"] == 1
        assert m["frontend"]["observability"] is False


def test_trace_unknown_id_is_404(tmp_path):
    with MappingHTTPServer(make_service(tmp_path)) as server:
        with pytest.raises(urllib.error.HTTPError) as exc:
            get_json(f"{server.url}/v1/trace/{'aa' * 16}")
        assert exc.value.code == 404


# ---------------------------------------------------------------------------
# Acceptance: one trace ID across a 3-node ring (forward hop + peer pull)
# ---------------------------------------------------------------------------


def boot_node(tmp_path, name: str, seeds, async_frontend: bool = False):
    svc = make_service(tmp_path, name)
    server = (AsyncMappingHTTPServer(svc).start() if async_frontend
              else MappingHTTPServer(svc).start())
    cluster = ClusterMembership(
        server.url, seeds=seeds or (), replicas=2, vnodes=64,
        heartbeat_interval=0.15, down_after=1.0, sync_interval=0.3,
        probe_timeout=1.0)
    server.attach_cluster(cluster)
    return server


def wait_fleet(servers, timeout=10.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if all(len(s.cluster.live_peers()) == len(servers) - 1
               for s in servers):
            return
        time.sleep(0.05)
    raise AssertionError("fleet never converged")


def test_one_trace_spans_forward_and_peer_pull(tmp_path):
    """The PR's acceptance scenario: a single client-injected trace ID
    covers the forwarded derive AND the peer pull it triggers —
    ingress node records the forward hop, the owner records admission +
    store probes + store_peer, and the pulled-from sibling records its
    replicate_pull, all retrievable per node from GET /v1/trace/<id>."""
    servers = []
    try:
        n0 = boot_node(tmp_path, "n0", seeds=None)
        servers.append(n0)
        for name in ("n1", "n2"):
            servers.append(boot_node(tmp_path, name, seeds=[n0.url]))
        wait_fleet(servers)

        # derive once so the cell exists on its 2 owners; learn the key
        res = RemoteMappingService(servers[0].url).derive(
            "gasket2d", MODEL, 100)
        key = res.cache_key
        deadline = time.monotonic() + 5.0
        owners = []
        while time.monotonic() < deadline:
            owners = [s for s in servers if key in s.service.store]
            if len(owners) == 2:
                break
            time.sleep(0.05)
        assert len(owners) == 2, f"expected 2 replicas, got {len(owners)}"
        non_owner = next(s for s in servers if s not in owners)
        # ring order, not the loaded selector's latency ranking, so that the
        # forwarder hops to its first replica peer — evict exactly that
        # node's copy so the forwarded derive must peer-pull
        non_owner.router.policy = "static"
        by_url = {s.url: s for s in servers}
        primary = by_url[non_owner.cluster.replica_peers(key)[0]]
        assert primary in owners
        sibling = next(s for s in owners if s is not primary)

        req = urllib.request.Request(
            f"{primary.url}/v1/artifact/{key}", method="DELETE")
        with urllib.request.urlopen(req, timeout=10):
            pass
        assert key not in primary.service.store

        # ONE trace ID through the whole story: non-owner forwards to the
        # primary owner, which misses locally and pulls from its sibling
        tid = new_trace_id()
        status, headers, payload = post_json(
            f"{non_owner.url}/v1/derive",
            {"domain": "gasket2d", "model": MODEL, "stage": 100},
            headers={TRACE_HEADER: tid})
        assert status == 200
        assert headers[TRACE_HEADER] == tid
        assert payload["key"] == key

        # ingress node: request-level span + the forward hop it took
        ingress = wait_for_span(non_owner.url, tid, "derive")
        fwd = next(sp for sp in ingress["spans"] if sp["name"] == "forward")
        assert fwd["owner"] == primary.url

        # owner: admission (derive), local tier probes, then the peer pull
        owner_trace = wait_for_span(primary.url, tid, "derive")
        names = [sp["name"] for sp in owner_trace["spans"]]
        assert "store_memory" in names and "store_disk" in names
        pull = next(sp for sp in owner_trace["spans"]
                    if sp["name"] == "store_peer")
        assert pull["hit"] is True
        assert pull["peer"] == sibling.url

        # pulled-from sibling: its replicate_pull ran under the same ID
        sib = wait_for_span(sibling.url, tid, "replicate_pull")
        assert sib["trace_id"] == tid

        # and the client-side fetchers see the same shards
        client = RemoteMappingService(non_owner.url)
        assert client.trace(tid)["trace_id"] == tid
        assert tid in client.traces(base=primary.url)["traces"]
    finally:
        for s in servers:
            s.close()


# ---------------------------------------------------------------------------
# Loadgen / SLO harness
# ---------------------------------------------------------------------------


def _loadgen():
    import importlib
    import pathlib
    import sys

    root = pathlib.Path(__file__).resolve().parent.parent
    if str(root) not in sys.path:
        sys.path.insert(0, str(root))
    return importlib.import_module("benchmarks.loadgen")


def test_synth_trace_zipf_and_determinism():
    lg = _loadgen()
    spec = lg.LoadSpec(requests=400, cells=8, zipf_s=1.3, seed=7,
                       trace_sample=0.25)
    t1, t2 = lg.synth_trace(spec), lg.synth_trace(spec)
    assert t1 == t2, "same seed must give the same trace"
    assert len(t1) == 400
    cells = lg.synth_cells(spec)
    counts = {c: 0 for c in cells}
    for op in t1:
        counts[op["cell"]] += 1
    # zipf skew: the hottest cell dominates the coldest
    assert counts[cells[0]] > counts[cells[-1]] * 2
    traced = [op for op in t1 if "trace_id" in op]
    assert traced and all(valid_trace_id(op["trace_id"]) for op in traced)
    assert lg.synth_trace(lg.LoadSpec(requests=400, seed=8)) != t1


def test_zipf_weights_normalized_and_skewed():
    lg = _loadgen()
    w = lg.zipf_weights(10, 1.1)
    assert abs(sum(w) - 1.0) < 1e-9
    assert w[0] > w[-1]
    assert w == sorted(w, reverse=True)


def test_arrival_offsets_pacing_and_bursts():
    lg = _loadgen()
    assert lg.arrival_offsets(lg.LoadSpec(rate=None)) is None
    spec = lg.LoadSpec(requests=20, rate=100.0, burst_every=0.05,
                       burst_size=4)
    offsets = lg.arrival_offsets(spec)
    assert len(offsets) == 20
    assert offsets == sorted(offsets)
    # bursts: some consecutive arrivals share an offset
    gaps = [b - a for a, b in zip(offsets, offsets[1:])]
    assert any(g == 0.0 for g in gaps)


def test_slo_report_and_check():
    lg = _loadgen()
    records = [
        {"op": "derive", "ok": True, "shed": False, "seconds": 0.010,
         "wall_seconds": 1.0},
        {"op": "derive", "ok": True, "shed": False, "seconds": 0.020,
         "wall_seconds": 1.0},
        {"op": "derive", "ok": False, "shed": True, "seconds": 0.001,
         "wall_seconds": 1.0},
        {"op": "evaluate", "ok": False, "shed": False, "seconds": 0.500,
         "error": "X", "wall_seconds": 1.0},
    ]
    report = lg.slo_report(records, lg.LoadSpec(requests=4))
    assert report["requests"] == 4
    assert report["sheds"] == 1 and report["errors"] == 1
    assert report["shed_rate"] == 0.25 and report["error_rate"] == 0.25
    assert report["p99_ms"] == pytest.approx(500.0)
    assert report["per_op"]["derive"]["requests"] == 3
    assert report["per_op"]["derive"]["sheds"] == 1
    assert lg.check_slo(report, None, None, None) == []
    violations = lg.check_slo(report, slo_p99_ms=100.0, max_shed_rate=0.0,
                              max_error_rate=0.1)
    assert len(violations) == 3
    assert lg.check_slo(report, 1000.0, 0.5, 0.5) == []


def test_ramp_finds_knee_and_bounds_accepted_p99():
    """The ramp steps the offered rate geometrically, stops at the first
    shedding step, calls the last shed-free step the knee, and reports the
    p99 of accepted requests only — sheds must not pollute the latency
    bound they exist to protect."""
    lg = _loadgen()

    def fake_runner(urls, spec):
        capacity = 100.0  # the fleet "sheds" past this offered rate
        n = spec.requests
        sheds = int(n * 0.3) if spec.rate > capacity else 0
        records = []
        for i in range(n):
            shed = i < sheds
            records.append({"op": "derive", "ok": not shed, "shed": shed,
                            "seconds": 0.001 if shed else 0.020,
                            "wall_seconds": 1.0})
        return records, lg.slo_report(records, spec)

    report = lg.ramp(["http://x"], lg.LoadSpec(requests=50),
                     start_rate=25.0, step_factor=2.0, max_steps=8,
                     runner=fake_runner)
    assert [s["offered_rps"] for s in report["steps"]] \
        == [25.0, 50.0, 100.0, 200.0]
    assert report["saturated"]
    assert report["steps"][-1]["sheds"] > 0
    assert report["knee_offered_rps"] == 100.0
    assert report["knee_goodput_rps"] == pytest.approx(50.0)
    # accepted p99 excludes the 1ms sheds on the saturated step
    assert report["accepted_p99_ms"] == pytest.approx(20.0)

    # a fleet that never sheds reports an unsaturated ramp, knee at the top
    calm = lg.ramp(["http://x"], lg.LoadSpec(requests=20),
                   start_rate=10.0, step_factor=2.0, max_steps=3,
                   runner=lambda u, s: fake_runner(
                       u, dataclasses.replace(s, rate=1.0)))
    assert not calm["saturated"]
    assert len(calm["steps"]) == 3
    assert calm["knee_offered_rps"] == 40.0


def test_ramp_live_smoke(tmp_path):
    """End-to-end ramp against one live node: well-formed steps whether or
    not the node saturates at these tiny rates."""
    lg = _loadgen()
    spec = lg.LoadSpec(requests=20, concurrency=4, cells=4,
                       mix={"derive": 1.0})
    with AsyncMappingHTTPServer(make_service(tmp_path)) as server:
        report = lg.ramp([server.url], spec, start_rate=200.0, max_steps=2)
    assert 1 <= len(report["steps"]) <= 2
    assert report["accepted_p99_ms"] > 0.0
    for step in report["steps"]:
        assert step["accepted"] + step["sheds"] + step["errors"] \
            >= spec.requests - step["errors"]
        assert step["goodput_rps"] <= step["achieved_rps"] + 1e-9


def test_loadgen_replay_against_live_node(tmp_path):
    lg = _loadgen()
    spec = lg.LoadSpec(requests=30, concurrency=4, cells=4,
                       trace_sample=0.5,
                       mix={"derive": 0.8, "artifact": 0.2})
    with AsyncMappingHTTPServer(make_service(tmp_path)) as server:
        records, report = lg.run([server.url], spec)
        # traced derives are retrievable from the node they hit
        traced = [r for r in records if r.get("trace_id")]
        assert traced
        wait_for_span(server.url, traced[0]["trace_id"], "derive")
    assert report["requests"] == 30
    assert report["errors"] == 0 and report["sheds"] == 0
    assert report["p99_ms"] >= report["p50_ms"] > 0.0
    assert report["throughput_rps"] > 0.0
    ops = {r["op"] for r in records}
    assert "derive" in ops
