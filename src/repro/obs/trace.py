"""Per-request distributed tracing — stdlib only.

The serving stack spans processes (forward hops, peer pulls) and threads
(offload pools, batcher workers), so "why was this derive slow?" cannot be
answered from any single counter.  This module gives every request a trace
ID carried in the ``X-Repro-Trace-Id`` header: the ingress node generates
(or adopts) one, every outgoing hop re-sends it, and each node records the
spans *it* executed into a bounded ring buffer served by ``GET
/v1/trace/<id>``.  A cross-node trace is therefore assembled client-side by
asking each node for its shard of the same ID — no collector process, no
wire format beyond the JSON the servers already speak.

Span records are flat JSON dicts::

    {"name": "store_peer", "start_unix": ..., "duration_ms": ..., **attrs}

Propagation uses two mechanisms, matched to the two concurrency shapes in
the stack:

* **contextvars** for request-scoped call stacks: the HTTP frontends
  activate ``(buffer, trace_id)`` at ingress and everything that runs on
  that logical flow — including asyncio-offloaded work wrapped with
  ``contextvars.copy_context().run`` — records via :func:`span`.
* **the backend ``meta`` dict** for shared worker threads: a batcher's
  drain loop serves many requests from one thread, so contextvars cannot
  attribute its work.  :func:`meta_context` snapshots the active trace into
  ``meta[META_KEY]`` (in-process only — the tuple is never serialized) and
  the worker calls :func:`record_for_meta` against it.

Every span recorded goes to three sinks:

* the request's :class:`TraceBuffer` (``GET /v1/trace/<id>``);
* a ``jax.profiler.TraceAnnotation`` named ``repro:<span>``, open for the
  span's length on the thread doing the work, which puts the program's
  spans on the profiler's clock beside the device's ops in any capture
  (only once ``jax`` has been imported: this module never imports it, and
  a span recorded after the fact by :func:`record_span` or
  :func:`record_for_meta` has none);
* a process-wide bounded ring of recent finished spans, ``(name, t0, t1,
  cpu_s, attrs)`` on ``time.monotonic``, read by :func:`spans_between`.
  ``cpu_s`` is the recording thread's CPU time over the span
  (``time.thread_time``; None for a span recorded after the fact): wall
  minus CPU on a pure-host span is time spent waiting for the interpreter
  lock or the scheduler.  The ring outlives any one server.

Everything here is a no-op (one contextvar read) when no trace is active,
which is what keeps the hot path's instrumentation overhead in the noise.
"""
from __future__ import annotations

import collections
import contextvars
import sys
import threading
import time
import uuid
from typing import Any

#: wire header carrying the trace ID across forward hops and peer pulls
TRACE_HEADER = "X-Repro-Trace-Id"

#: reserved key under which `meta_context()` snapshots the active trace into
#: a backend `meta` dict (in-process hand-off to shared worker threads; the
#: value is a live (TraceBuffer, trace_id) tuple and must never hit the wire)
META_KEY = "_trace"

#: per-flow active trace: (TraceBuffer, trace_id) or None
_current: contextvars.ContextVar[tuple["TraceBuffer", str] | None] = \
    contextvars.ContextVar("repro_trace", default=None)


def new_trace_id() -> str:
    """A fresh 32-hex-char trace ID."""
    return uuid.uuid4().hex


def valid_trace_id(trace_id: Any) -> bool:
    """Lenient wire validation: 8..64 hex chars.  Anything else is ignored
    at ingress (a fresh ID is generated instead), so a hostile header can
    never grow the ring buffer's key space unboundedly per request."""
    if not isinstance(trace_id, str) or not 8 <= len(trace_id) <= 64:
        return False
    return all(c in "0123456789abcdef" for c in trace_id)


class TraceBuffer:
    """Bounded ring of recent traces (per node).

    At most ``max_traces`` trace IDs are held; recording into a new ID when
    full evicts the oldest trace wholesale.  Each trace holds at most
    ``max_spans`` spans — further records bump ``dropped_spans`` instead of
    growing, so a pathological request can't eat the buffer either."""

    def __init__(self, max_traces: int = 512, max_spans: int = 64):
        self.max_traces = max_traces
        self.max_spans = max_spans
        self.dropped_traces = 0  # whole traces evicted by the ring
        self.dropped_spans = 0   # spans refused by a full trace
        self._traces: "collections.OrderedDict[str, dict]" = \
            collections.OrderedDict()
        self._mu = threading.Lock()

    def record(self, trace_id: str, span: dict) -> None:
        with self._mu:
            entry = self._traces.get(trace_id)
            if entry is None:
                entry = self._traces[trace_id] = {
                    "trace_id": trace_id,
                    "started_unix": span.get("start_unix", time.time()),
                    "spans": [],
                    "dropped_spans": 0,
                }
                while len(self._traces) > self.max_traces:
                    self._traces.popitem(last=False)
                    self.dropped_traces += 1
            if len(entry["spans"]) >= self.max_spans:
                entry["dropped_spans"] += 1
                self.dropped_spans += 1
                return
            entry["spans"].append(span)

    def get(self, trace_id: str) -> dict | None:
        """This node's shard of one trace (a JSON-ready copy), or None."""
        with self._mu:
            entry = self._traces.get(trace_id)
            if entry is None:
                return None
            return {**entry, "spans": list(entry["spans"]),
                    "span_count": len(entry["spans"])}

    def ids(self) -> list[str]:
        """Known trace IDs, most recent last."""
        with self._mu:
            return list(self._traces)

    def stats(self) -> dict:
        with self._mu:
            return {"traces": len(self._traces),
                    "max_traces": self.max_traces,
                    "max_spans": self.max_spans,
                    "dropped_traces": self.dropped_traces,
                    "dropped_spans": self.dropped_spans}


# ---------------------------------------------------------------------------
# Process-wide span ring + profiler annotations
# ---------------------------------------------------------------------------

#: finished spans the ring holds; the oldest fall off past this
SPAN_RING_SIZE = 1 << 16

#: prefix of the program's profiler annotations
ANNOTATION_PREFIX = "repro:"

_ring: collections.deque = collections.deque(maxlen=SPAN_RING_SIZE)
_ring_mu = threading.Lock()
_annotation_cls = None


def _ring_add(name: str, t0: float, t1: float, cpu_s: float | None,
              attrs: dict) -> None:
    with _ring_mu:
        _ring.append((name, t0, t1, cpu_s, attrs))


def spans_between(t0: float, t1: float, name: str | None = None) -> list:
    """The ring's spans ``(name, t0, t1, cpu_s, attrs)`` that ended in
    ``[t0, t1)`` (``time.monotonic``), oldest first; only ``name``'s when
    given."""
    with _ring_mu:
        held = list(_ring)
    return [s for s in held
            if t0 <= s[2] < t1 and (name is None or s[0] == name)]


def _annotation(name: str):
    """An entered ``repro:<name>`` profiler annotation, or None while
    ``jax`` has not been imported (by anyone: this module never does)."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        jax = sys.modules.get("jax")
        cls = getattr(getattr(jax, "profiler", None), "TraceAnnotation",
                      None)
        if cls is None:
            return None
        _annotation_cls = cls
    ann = cls(ANNOTATION_PREFIX + name)
    ann.__enter__()
    return ann


# ---------------------------------------------------------------------------
# Context propagation + span recording
# ---------------------------------------------------------------------------


def activate(buffer: TraceBuffer, trace_id: str) -> contextvars.Token:
    """Make ``trace_id`` the active trace on this logical flow."""
    return _current.set((buffer, trace_id))


def deactivate(token: contextvars.Token) -> None:
    _current.reset(token)


def current_trace_id() -> str | None:
    """The active trace ID (what outgoing hops put on the wire), or None."""
    ctx = _current.get()
    return ctx[1] if ctx is not None else None


def wire_headers() -> dict:
    """``{TRACE_HEADER: id}`` when a trace is active, else ``{}`` — merge
    into any outgoing fleet request so the remote node records under the
    same ID."""
    ctx = _current.get()
    return {TRACE_HEADER: ctx[1]} if ctx is not None else {}


class _NoopSpan:
    __slots__ = ()

    def __enter__(self) -> dict:
        return {}

    def __exit__(self, *exc) -> bool:
        return False


_NOOP = _NoopSpan()


class _LiveSpan:
    __slots__ = ("_buffer", "_trace_id", "_name", "_attrs", "_t0", "_wall",
                 "_cpu", "_ann")

    def __init__(self, buffer: TraceBuffer, trace_id: str, name: str,
                 attrs: dict):
        self._buffer = buffer
        self._trace_id = trace_id
        self._name = name
        self._attrs = attrs

    def __enter__(self) -> dict:
        self._ann = _annotation(self._name)
        self._wall = time.time()
        self._cpu = time.thread_time()
        self._t0 = time.monotonic()
        return self._attrs  # caller may add attrs mid-span

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.monotonic()
        cpu_s = time.thread_time() - self._cpu
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        rec = {"name": self._name, "start_unix": self._wall,
               "duration_ms": (t1 - self._t0) * 1e3}
        if exc_type is not None:
            rec["error"] = exc_type.__name__
        rec.update(self._attrs)
        self._buffer.record(self._trace_id, rec)
        _ring_add(self._name, self._t0, t1, cpu_s, self._attrs)
        return False


def span(name: str, **attrs):
    """Context manager recording one span into the active trace (a shared
    no-op when none is active).  Yields the attrs dict, so callers can
    attach outcomes discovered mid-span::

        with span("store_peer") as s:
            rec = probe()
            s["hit"] = rec is not None
    """
    ctx = _current.get()
    if ctx is None:
        return _NOOP
    return _LiveSpan(ctx[0], ctx[1], name, attrs)


def meta_context() -> dict:
    """Snapshot of the active trace for a backend ``meta`` dict (``{}``
    when inactive) — lets shared worker threads attribute their work via
    :func:`record_for_meta`."""
    ctx = _current.get()
    return {META_KEY: ctx} if ctx is not None else {}


def _record_finished(ctx: tuple, name: str, t0: float, t1: float,
                     attrs: dict) -> None:
    buffer, trace_id = ctx
    buffer.record(trace_id, {
        "name": name, "start_unix": time.time() - (time.monotonic() - t0),
        "duration_ms": (t1 - t0) * 1e3, **attrs})
    _ring_add(name, t0, t1, None, attrs)


def record_span(name: str, t0: float, t1: float, **attrs) -> None:
    """Record a span that ran ``[t0, t1)`` (``time.monotonic``) into the
    active trace, after the fact: a wait in which nothing ran, such as a
    queue (no-op when no trace is active)."""
    ctx = _current.get()
    if ctx is not None:
        _record_finished(ctx, name, t0, t1, attrs)


def record_for_meta(meta: dict, name: str, seconds: float, **attrs) -> None:
    """Record a just-finished span of ``seconds`` against the trace carried
    in ``meta`` (no-op when the request was untraced)."""
    ctx = meta.get(META_KEY)
    if ctx is None:
        return
    t1 = time.monotonic()
    _record_finished(ctx, name, t1 - seconds, t1, attrs)
