"""CompileCache — process-wide cache of compiled map executables, plus the
one switch for JAX's own persistent compilation cache
(:func:`enable_persistent_cache`).

The deployment-side economics of the paper (Sec. V.C: the mapped kernel's
4833x speedup) only materialize if a deployed map runs at hardware speed on
*every* launch.  Until this layer existed, every ``map_coordinates`` /
``bb_membership`` call re-traced and re-jitted its Pallas call — tens to
hundreds of milliseconds of Python/XLA work in front of a ~1ms kernel.

This module caches the *compiled executable* (``jax.jit(...).lower()
.compile()``) keyed by everything that changes the lowering:

    (spec fingerprint, tier, shape, block_n, ndigits, dtype,
     interpret, device kind)

where the spec fingerprint is the artifact's content address for
LLM-derived maps (``artifact:<cache_key>``) and a registry identity for
ground-truth geometry (``domain:<name>`` / ``entry:<domain>:<logic>``).
A repeat evaluation with an identical key is therefore trace-free: it costs
one dict hit plus the device dispatch.

Persistence (optional): with a ``persist_dir``, each freshly-compiled
executable is serialized through ``jax.export`` next to its key digest, and
a cold *process* can rehydrate it without re-tracing.  Where the installed
jaxlib (or the kernel's lowering) cannot round-trip through ``jax.export``,
the cache degrades transparently to in-memory-only and counts the failure —
persistence is an optimization, never a correctness dependency.

Concurrency: per-key in-flight coalescing (the same shape the
MappingService uses for derivations) — N threads asking for one cold key
trigger exactly one trace/compile; everyone shares the executable.

Env surface (read by :func:`default_compile_cache`, overridable from
``launch/serve.py`` flags):

    REPRO_COMPILE_CACHE_ENTRIES   LRU capacity (default 128; 0 disables)
    REPRO_COMPILE_CACHE_DIR       on-disk persistence root (default: off)
    JAX_COMPILATION_CACHE_DIR     JAX's persistent cache (default:
                                  ``<checkout>/.jax_cache``)
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import os
import threading
import time
import warnings
from pathlib import Path
from typing import Any, Callable

DEFAULT_MAX_ENTRIES = 128

#: sentinel: "use the process-default cache" (None = bypass caching)
USE_DEFAULT = object()

#: JAX's persistent compilation cache when JAX_COMPILATION_CACHE_DIR is
#: unset: a fixed path (the path is part of every entry's key, so a
#: directory that moves never hits)
JAX_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_persistent_cache() -> str:
    """Point JAX's persistent compilation cache at one fixed directory and
    return it — called at the start of every entry point's ``main``.
    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    nothing else is set; otherwise the cache is :data:`JAX_CACHE_DIR`."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(JAX_CACHE_DIR))
    return str(JAX_CACHE_DIR)


def device_info() -> dict:
    """The devices this process computes on, as JAX reports them — what the
    serve banner, ``/healthz`` and every chip result name."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def device_kind() -> str:
    """The accelerator identity baked into every key — an executable
    compiled for one device kind must never serve another."""
    dev = device_info()
    return f"{dev['platform']}:{dev['kind']}"


def spec_fingerprint(spec) -> str:
    """Content identity of a map spec, for executable keying.

    * ``MappingArtifact`` -> ``artifact:<content address>`` (falls back to a
      digest of the validated source when the artifact never saw a store);
    * ``MapEntry``        -> ``entry:<domain>:<logic>``;
    * ``str`` / ``Domain``-> ``domain:<name>`` (ground-truth geometry).
    """
    from repro.core.artifact import MappingArtifact, resolve_spec

    if isinstance(spec, MappingArtifact):
        base = spec.cache_key or hashlib.sha256(
            spec.source.encode()).hexdigest()
        return f"artifact:{base}"
    domain, logic = resolve_spec(spec)
    if logic is None:
        return f"domain:{domain}"
    return f"entry:{domain}:{logic}"


@dataclasses.dataclass(frozen=True)
class ExecKey:
    """Everything that changes the lowered executable."""

    fingerprint: str          # spec_fingerprint(spec)
    tier: str                 # "map" | "membership" | "map_sharded" | ...
    shape: tuple[int, ...]    # padded output extent (and box extent)
    block_n: int
    ndigits: int
    dtype: str = "int32"
    interpret: bool = False
    device: str = dataclasses.field(default_factory=device_kind)

    def digest(self) -> str:
        """Stable file name for on-disk persistence."""
        payload = "|".join(
            str(p) for p in (self.fingerprint, self.tier, self.shape,
                             self.block_n, self.ndigits, self.dtype,
                             self.interpret, self.device))
        return hashlib.sha256(payload.encode()).hexdigest()


@dataclasses.dataclass
class CompileCacheStats:
    """Counters for the /metrics surface (all cumulative)."""

    hits: int = 0            # served from the in-memory LRU (trace-free)
    misses: int = 0          # full trace + compile paid
    coalesced: int = 0       # waited on another thread's in-flight compile
    evictions: int = 0       # LRU entries dropped at capacity
    disk_hits: int = 0       # rehydrated from persist_dir (trace-free)
    disk_stores: int = 0     # executables serialized to persist_dir
    disk_errors: int = 0     # serialize/deserialize failures (fallback)
    trace_seconds: float = 0.0   # total time spent tracing+compiling

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        total = self.hits + self.misses + self.disk_hits
        d["hit_ratio"] = ((self.hits + self.disk_hits) / total
                          if total else 0.0)
        return d


class _Compiled:
    """One cached executable + its provenance."""

    __slots__ = ("fn", "trace_seconds", "source")

    def __init__(self, fn: Callable, trace_seconds: float, source: str):
        self.fn = fn
        self.trace_seconds = trace_seconds
        self.source = source  # "compile" | "disk"


class _InFlight:
    __slots__ = ("event", "entry", "error")

    def __init__(self):
        self.event = threading.Event()
        self.entry: _Compiled | None = None
        self.error: BaseException | None = None


class CompileCache:
    """Bounded LRU of compiled zero-arg executables.

    ``get(key, build)`` returns a callable whose invocation runs the
    compiled program; ``build`` is a zero-arg *jittable* callable (e.g. the
    thunk ``build_map_call`` returns) that is traced at most once per key
    per process — or zero times, when the persist dir already holds it."""

    def __init__(self, max_entries: int = DEFAULT_MAX_ENTRIES,
                 persist_dir: str | Path | None = None):
        self.max_entries = max_entries
        self.persist_dir = Path(persist_dir) if persist_dir else None
        if self.persist_dir is not None:
            self.persist_dir.mkdir(parents=True, exist_ok=True)
        self.stats = CompileCacheStats()
        self._entries: collections.OrderedDict[ExecKey, _Compiled] = \
            collections.OrderedDict()
        self._inflight: dict[ExecKey, _InFlight] = {}
        self._mu = threading.Lock()

    def __len__(self) -> int:
        with self._mu:
            return len(self._entries)

    def __contains__(self, key: ExecKey) -> bool:
        with self._mu:
            return key in self._entries

    def keys(self) -> list[ExecKey]:
        with self._mu:
            return list(self._entries)

    # -- lookup ------------------------------------------------------------
    def get(self, key: ExecKey, build: Callable[[], Callable]) -> Callable:
        """The compiled executable for ``key`` (tracing via ``build()`` at
        most once per process, coalescing concurrent cold callers)."""
        with self._mu:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                self.stats.hits += 1
                return entry.fn
            fl = self._inflight.get(key)
            leader = fl is None
            if leader:
                fl = self._inflight[key] = _InFlight()
        if not leader:
            fl.event.wait()
            with self._mu:
                self.stats.coalesced += 1
            if fl.error is not None:
                raise fl.error
            return fl.entry.fn  # type: ignore[union-attr]
        try:
            entry = self._load_persisted(key)
            if entry is None:
                entry = self._compile(key, build)
            self._insert(key, entry)
            fl.entry = entry
            return entry.fn
        except BaseException as e:
            fl.error = e
            raise
        finally:
            with self._mu:
                self._inflight.pop(key, None)
            fl.event.set()

    def _insert(self, key: ExecKey, entry: _Compiled) -> None:
        with self._mu:
            self._entries[key] = entry
            self._entries.move_to_end(key)
            while len(self._entries) > max(self.max_entries, 1):
                self._entries.popitem(last=False)
                self.stats.evictions += 1

    # -- compile path ------------------------------------------------------
    def _compile(self, key: ExecKey, build: Callable[[], Callable]
                 ) -> _Compiled:
        import jax

        t0 = time.perf_counter()
        jitted = jax.jit(build())
        compiled = jitted.lower().compile()
        dt = time.perf_counter() - t0
        with self._mu:
            self.stats.misses += 1
            self.stats.trace_seconds += dt
        self._persist(key, jitted)
        return _Compiled(compiled, dt, "compile")

    # -- persistence -------------------------------------------------------
    def _path(self, key: ExecKey) -> Path | None:
        if self.persist_dir is None:
            return None
        return self.persist_dir / f"{key.digest()}.jaxexec"

    def _persist(self, key: ExecKey, jitted) -> None:
        """Best-effort AOT export of a freshly-jitted thunk.  Any failure
        (unsupported lowering, old jaxlib, full disk) degrades to
        in-memory-only and is counted, never raised."""
        path = self._path(key)
        if path is None or path.exists():
            return
        try:
            from jax import export

            data = export.export(jitted)().serialize()
            tmp = path.with_suffix(".tmp")
            tmp.write_bytes(data)
            os.replace(tmp, path)
            with self._mu:
                self.stats.disk_stores += 1
        except Exception:  # noqa: BLE001 — persistence is an optimization
            with self._mu:
                self.stats.disk_errors += 1

    def _load_persisted(self, key: ExecKey) -> _Compiled | None:
        path = self._path(key)
        if path is None or not path.exists():
            return None
        t0 = time.perf_counter()
        try:
            from jax import export

            exported = export.deserialize(bytearray(path.read_bytes()))
            fn = exported.call
        except Exception:  # noqa: BLE001 — corrupt/incompatible: recompile
            with self._mu:
                self.stats.disk_errors += 1
            try:
                path.unlink()
            except OSError:
                pass
            return None
        with self._mu:
            self.stats.disk_hits += 1
        return _Compiled(fn, time.perf_counter() - t0, "disk")

    # -- introspection -----------------------------------------------------
    def clear(self) -> int:
        with self._mu:
            n = len(self._entries)
            self._entries.clear()
        return n

    def stats_dict(self) -> dict[str, Any]:
        with self._mu:
            out = self.stats.as_dict()
            out["entries"] = len(self._entries)
        out["max_entries"] = self.max_entries
        out["persist_dir"] = str(self.persist_dir) if self.persist_dir \
            else None
        return out


# ---------------------------------------------------------------------------
# process default
# ---------------------------------------------------------------------------

_default: CompileCache | None = None
_default_off = False  # configure_default(0) disables the process default
_default_mu = threading.Lock()


def _env_int(name: str, fallback: int) -> int:
    raw = os.environ.get(name, "").strip()
    if not raw:
        return fallback
    try:
        return int(raw)
    except ValueError:
        warnings.warn(f"ignoring malformed {name}={raw!r}", stacklevel=2)
        return fallback


def default_compile_cache() -> CompileCache | None:
    """The process-wide cache (REPRO_COMPILE_CACHE_* env knobs).  Returns
    None when REPRO_COMPILE_CACHE_ENTRIES=0 — caching explicitly off."""
    global _default
    with _default_mu:
        if _default_off:
            return None
        if _default is None:
            entries = _env_int("REPRO_COMPILE_CACHE_ENTRIES",
                               DEFAULT_MAX_ENTRIES)
            if entries <= 0:
                return None
            persist = os.environ.get("REPRO_COMPILE_CACHE_DIR", "").strip() \
                or None
            _default = CompileCache(max_entries=entries, persist_dir=persist)
        return _default


def configure_default(max_entries: int | None = None,
                      persist_dir: str | Path | None = None
                      ) -> CompileCache | None:
    """Rebuild the process default from explicit knobs (the serve CLI path).
    ``max_entries=0`` disables caching process-wide."""
    global _default, _default_off
    with _default_mu:
        entries = max_entries if max_entries is not None else _env_int(
            "REPRO_COMPILE_CACHE_ENTRIES", DEFAULT_MAX_ENTRIES)
        if entries <= 0:
            _default = None
            _default_off = True
            return None
        if persist_dir is None:
            persist_dir = os.environ.get(
                "REPRO_COMPILE_CACHE_DIR", "").strip() or None
        _default_off = False
        _default = CompileCache(max_entries=entries, persist_dir=persist_dir)
        return _default


def resolve(cache) -> CompileCache | None:
    """Normalize a ``compile_cache=`` argument: the USE_DEFAULT sentinel ->
    process default, None -> bypass, a CompileCache -> itself."""
    if cache is USE_DEFAULT:
        return default_compile_cache()
    return cache
