"""Spans on the served evaluate path: what each frontend records, in the
request's trace and in the process-wide span ring, and the ``repro:``
profiler annotations each span opens."""
from __future__ import annotations

import collections
import os
import subprocess
import sys
import time
import types

import pytest

from repro.core.artifact import ArtifactCache
from repro.core.backends import MockLLMBackend
from repro.obs import trace as obs_trace
from repro.obs.trace import TraceBuffer, activate, deactivate, span
from repro.serving import (
    AsyncMappingHTTPServer,
    MappingHTTPServer,
    MappingService,
    RemoteMappingService,
)

#: two executable groups, so that every phase covers more than one launch
QUERIES = [
    {"domain": "tri2d", "n_points": 200, "block_n": 128},
    {"domain": "gasket2d", "n_points": 128, "block_n": 128},
    {"domain": "tri2d", "n_points": 150, "block_n": 128},
]

SHARED = {"eval_plan", "eval_dispatch", "eval_fetch", "eval_assemble",
          "wire_encode"}
SEND = {"wire_send", "wire_drain"}
EVALUATE_SPANS = SHARED | SEND | {"eval_queue"}


def local_service(tmp_path) -> MappingService:
    return MappingService(cache=ArtifactCache(tmp_path),
                          backend_factory=MockLLMBackend,
                          n_validate=2000, sample_every=1)


def traced_evaluate(server, client, trace_id: str) -> tuple[dict, list]:
    """One batch evaluate under ``trace_id``: the node's shard of the trace
    (once the ingress span has landed) and the ring's spans of the call."""
    t0 = time.monotonic()
    token = activate(TraceBuffer(), trace_id)
    try:
        got = client.evaluate_batch(QUERIES)
    finally:
        deactivate(token)
    assert [r["n_points"] for r in got] == [q["n_points"] for q in QUERIES]
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        shard = server.obs.trace_payload(trace_id)
        if shard and any(s["name"] == "evaluate" for s in shard["spans"]):
            return shard, obs_trace.spans_between(t0, time.monotonic())
        time.sleep(0.02)
    raise AssertionError(f"no ingress span in trace {trace_id}")


def names(spans) -> set:
    return {s["name"] if isinstance(s, dict) else s[0] for s in spans}


def test_aio_batch_evaluate_records_every_stage(tmp_path):
    with AsyncMappingHTTPServer(local_service(tmp_path)) as server:
        client = RemoteMappingService(server.url)
        shard, ring = traced_evaluate(server, client, "a1" * 16)
        client.close()
    assert EVALUATE_SPANS <= names(shard["spans"])
    assert EVALUATE_SPANS <= names(ring)
    by_name = {s["name"]: s for s in shard["spans"]}
    assert by_name["eval_plan"]["queries"] == 3
    assert by_name["eval_plan"]["groups"] == 2
    assert 0 <= by_name["eval_dispatch"]["fresh"] <= 2
    # one (8, padded) int32 block per group, fetched whole
    assert by_name["eval_fetch"]["bytes"] >= 8 * 4 * (256 + 128)
    assert by_name["wire_encode"]["bytes"] == by_name["wire_send"]["bytes"]
    assert by_name["eval_queue"]["depth"] >= 0
    for name, t0, t1, cpu_s, attrs in ring:
        assert t1 >= t0
        if name == "eval_queue":
            assert cpu_s is None  # a wait, recorded after the fact
        else:
            assert cpu_s >= 0.0


def test_threaded_frontend_records_the_shared_spans(tmp_path):
    with MappingHTTPServer(local_service(tmp_path)) as server:
        client = RemoteMappingService(server.url)
        shard, ring = traced_evaluate(server, client, "b2" * 16)
        client.close()
    for got in (names(shard["spans"]), names(ring)):
        assert SHARED <= got
        assert not (got & (SEND | {"eval_queue"}))  # the aio frontend's own


def test_wire_cache_hit_records_only_the_send(tmp_path):
    with AsyncMappingHTTPServer(local_service(tmp_path)) as server:
        client = RemoteMappingService(server.url)
        traced_evaluate(server, client, "c3" * 16)
        client.evaluate_batch(QUERIES)  # compiled: the encoded bytes cache
        hits = server.eval_wire_hits
        shard, ring = traced_evaluate(server, client, "c4" * 16)
        assert server.eval_wire_hits == hits + 1
        client.close()
    assert names(shard["spans"]) == SEND | {"evaluate"}
    assert names(ring) == SEND


def test_no_observability_records_no_span(tmp_path):
    with AsyncMappingHTTPServer(local_service(tmp_path),
                                observability=False) as server:
        client = RemoteMappingService(server.url)
        t0 = time.monotonic()
        for _ in range(2):
            client.evaluate_batch(QUERIES)
        client.close()
        assert server.obs.traces.ids() == []
    assert not names(obs_trace.spans_between(t0, time.monotonic())) \
        & EVALUATE_SPANS


def test_span_ring_is_bounded_and_windowed(monkeypatch):
    monkeypatch.setattr(obs_trace, "_ring", collections.deque(maxlen=4))
    token = activate(TraceBuffer(), "d5" * 16)
    try:
        for i in range(6):
            obs_trace.record_span("queued" if i % 2 else "other",
                                  float(i), i + 0.5, i=i)
        with span("live", tier="x") as sp:
            sp["later"] = 1
    finally:
        deactivate(token)
    held = obs_trace.spans_between(0.0, float("inf"))
    assert len(held) == 4
    assert [s[4].get("i") for s in held] == [3, 4, 5, None]
    # ended in [t0, t1): the span ending at 3.5 is in, the one at 4.5 out
    assert [s[4]["i"] for s in obs_trace.spans_between(3.5, 4.5)] == [3]
    assert [s[4]["i"] for s in obs_trace.spans_between(
        0.0, 10.0, name="queued")] == [3, 5]
    live = held[-1]
    assert live[0] == "live" and live[4] == {"tier": "x", "later": 1}
    assert live[3] is not None and live[3] >= 0.0
    # recorded nowhere without an active trace
    obs_trace.record_span("orphan", 7.0, 7.5)
    with span("orphan"):
        pass
    assert "orphan" not in names(obs_trace.spans_between(0.0, float("inf")))


class _StubAnnotation:
    opened: list = []

    def __init__(self, name):
        self.name = name
        self.state = "made"

    def __enter__(self):
        self.state = "open"
        _StubAnnotation.opened.append(self)
        return self

    def __exit__(self, *exc):
        self.state = "closed"
        return False


@pytest.mark.parametrize("jax_loaded", [True, False])
def test_span_opens_profiler_annotation_once_jax_is_loaded(monkeypatch,
                                                           jax_loaded):
    monkeypatch.setattr(obs_trace, "_annotation_cls", None)
    monkeypatch.setattr(_StubAnnotation, "opened", [])
    if jax_loaded:
        stub = types.SimpleNamespace(profiler=types.SimpleNamespace(
            TraceAnnotation=_StubAnnotation))
        monkeypatch.setitem(sys.modules, "jax", stub)
    else:
        monkeypatch.delitem(sys.modules, "jax", raising=False)
    buf = TraceBuffer()
    token = activate(buf, "e6" * 16)
    try:
        with span("eval_fetch"):
            inside = [a.state for a in _StubAnnotation.opened]
        obs_trace.record_span("eval_queue", 1.0, 2.0)  # a wait: none
    finally:
        deactivate(token)
    if jax_loaded:
        assert inside == ["open"]
        assert [(a.name, a.state) for a in _StubAnnotation.opened] == [
            ("repro:eval_fetch", "closed")]
    else:
        assert _StubAnnotation.opened == []
    assert names(buf.get("e6" * 16)["spans"]) == {"eval_fetch", "eval_queue"}


def test_obs_imports_without_jax():
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    code = ("import sys; import repro.obs; "
            "from repro.obs import trace; "
            "tid = 'ab' * 16; "
            "tok = trace.activate(trace.TraceBuffer(), tid)\n"
            "with trace.span('x'): pass\n"
            "assert 'jax' not in sys.modules, 'repro.obs imported jax'\n"
            "assert [s[0] for s in trace.spans_between(0, 1e18)] == ['x']")
    env = dict(os.environ, PYTHONPATH=src)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
