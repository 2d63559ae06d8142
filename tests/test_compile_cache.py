"""CompileCache unit + regression coverage: LRU/stat semantics, key
sensitivity, in-flight coalescing, on-disk executable persistence, env
knobs — and the PR-6 regression that made the cache necessary: a repeat
``map_coordinates``/``bb_membership`` call must never re-trace."""
import threading

import numpy as np
import pytest

from repro.core import compile_cache as cc
from repro.core.domains import DOMAINS
from repro.kernels.domain_map import ops


def _key(tag: str, **kw) -> cc.ExecKey:
    base = dict(fingerprint=f"domain:{tag}", tier="map", shape=(0, 256),
                block_n=128, ndigits=13, interpret=True)
    base.update(kw)
    return cc.ExecKey(**base)


def _cheap_build(value: float):
    """A zero-arg jittable thunk that compiles in milliseconds."""
    import jax.numpy as jnp

    def build():
        return lambda: jnp.full((4,), value)

    return build


# ---------------------------------------------------------------------------
# LRU + stats semantics
# ---------------------------------------------------------------------------


def test_hit_miss_and_lru_eviction_order():
    cache = cc.CompileCache(max_entries=2)
    a, b, c = _key("a"), _key("b"), _key("c")
    fa = cache.get(a, _cheap_build(1.0))
    assert cache.stats.misses == 1 and cache.stats.hits == 0
    assert cache.get(a, _cheap_build(1.0)) is fa  # identical executable back
    assert cache.stats.hits == 1 and cache.stats.misses == 1
    assert cache.stats.trace_seconds > 0

    cache.get(b, _cheap_build(2.0))
    cache.get(a, _cheap_build(1.0))       # touch a: b is now the LRU entry
    cache.get(c, _cheap_build(3.0))       # capacity 2: evicts b, keeps a
    assert cache.stats.evictions == 1
    assert a in cache and c in cache and b not in cache
    cache.get(b, _cheap_build(2.0))       # re-compiling b is a fresh miss
    assert cache.stats.misses == 4
    d = cache.stats_dict()
    assert d["entries"] == 2 and d["max_entries"] == 2
    assert d["hit_ratio"] == pytest.approx(2 / 6)
    assert cache.clear() == 2 and len(cache) == 0


def test_key_fields_are_all_significant():
    """Any field that changes the lowering must change the key."""
    base = _key("x")
    variants = [
        _key("y"),
        _key("x", tier="membership"),
        _key("x", shape=(0, 512)),
        _key("x", block_n=256),
        _key("x", ndigits=9),
        _key("x", dtype="int64"),
        _key("x", interpret=False),
        _key("x", device="tpu:v5e"),
    ]
    assert len({base, *variants}) == len(variants) + 1
    assert len({k.digest() for k in (base, *variants)}) == len(variants) + 1
    cache = cc.CompileCache(max_entries=32)
    for i, k in enumerate((base, *variants)):
        cache.get(k, _cheap_build(float(i)))
    assert cache.stats.misses == len(variants) + 1  # no accidental sharing


def test_concurrent_cold_callers_coalesce_to_one_compile():
    cache = cc.CompileCache(max_entries=8)
    key = _key("shared")
    builds = []
    gate = threading.Event()

    def build():
        import jax.numpy as jnp

        builds.append(1)
        gate.wait(5)  # hold the leader so followers genuinely queue
        return lambda: jnp.zeros((2,))

    fns = []
    mu = threading.Lock()

    def caller():
        fn = cache.get(key, build)
        with mu:
            fns.append(fn)

    threads = [threading.Thread(target=caller) for _ in range(6)]
    for t in threads:
        t.start()
    while not builds:  # leader is inside build()
        pass
    gate.set()
    for t in threads:
        t.join()
    assert sum(builds) == 1                       # exactly one trace
    assert len({id(f) for f in fns}) == 1         # everyone shares it
    assert cache.stats.misses == 1
    assert cache.stats.coalesced == 5


def test_failed_build_propagates_and_is_not_cached():
    cache = cc.CompileCache(max_entries=8)
    key = _key("boom")

    def bad_build():
        raise RuntimeError("synthetic build failure")

    with pytest.raises(RuntimeError, match="synthetic"):
        cache.get(key, bad_build)
    assert key not in cache
    fn = cache.get(key, _cheap_build(7.0))  # key is retryable afterwards
    assert float(np.asarray(fn())[0]) == 7.0


# ---------------------------------------------------------------------------
# the PR-6 regression: repeat kernel calls are trace-free
# ---------------------------------------------------------------------------


def test_second_identical_map_call_performs_zero_traces(monkeypatch):
    """The per-call re-trace this PR removes: with a warm cache, a repeat
    ``map_coordinates`` (and ``bb_membership``) performs zero new builds —
    it is a cache hit plus a dispatch, byte-equal to the uncached path."""
    calls = {"map": 0, "bb": 0}
    real_map, real_bb = ops.build_map_call, ops.build_membership_call

    def counting_map(*a, **kw):
        calls["map"] += 1
        return real_map(*a, **kw)

    def counting_bb(*a, **kw):
        calls["bb"] += 1
        return real_bb(*a, **kw)

    monkeypatch.setattr(ops, "build_map_call", counting_map)
    monkeypatch.setattr(ops, "build_membership_call", counting_bb)
    cache = cc.CompileCache(max_entries=16)

    first = ops.map_coordinates("tri2d", 200, block_n=128, interpret=True,
                                compile_cache=cache)
    assert calls["map"] == 1
    second = ops.map_coordinates("tri2d", 200, block_n=128, interpret=True,
                                 compile_cache=cache)
    assert calls["map"] == 1              # ZERO new traces on the repeat
    assert cache.stats.hits == 1 and cache.stats.misses == 1
    np.testing.assert_array_equal(first, second)
    uncached = ops.map_coordinates("tri2d", 200, block_n=128, interpret=True,
                                   compile_cache=None)
    np.testing.assert_array_equal(first, uncached)
    assert calls["map"] == 2              # the bypass path does re-trace

    mask1 = ops.bb_membership("tri2d", (16, 16), block_n=128, interpret=True,
                              compile_cache=cache)
    mask2 = ops.bb_membership("tri2d", (16, 16), block_n=128, interpret=True,
                              compile_cache=cache)
    assert calls["bb"] == 1
    np.testing.assert_array_equal(mask1, mask2)


def test_distinct_launch_parameters_get_distinct_executables():
    cache = cc.CompileCache(max_entries=32)
    kw = dict(interpret=True, compile_cache=cache)
    ops.map_coordinates("tri2d", 200, block_n=128, **kw)
    ops.map_coordinates("tri2d", 300, block_n=128, **kw)   # pads 256 vs 384
    ops.map_coordinates("tri2d", 200, block_n=64, **kw)
    ops.map_coordinates("tri2d", 200, block_n=128, start=128, **kw)
    ops.map_coordinates("gasket2d", 200, block_n=128, **kw)
    assert cache.stats.misses == 5 and cache.stats.hits == 0


# ---------------------------------------------------------------------------
# persistence
# ---------------------------------------------------------------------------


def test_persisted_executable_survives_a_cold_cache(tmp_path):
    """Second cache over the same persist dir rehydrates without tracing —
    and produces identical bytes."""
    warm = cc.CompileCache(max_entries=8, persist_dir=tmp_path)
    out1 = ops.map_coordinates("tri2d", 200, block_n=128, interpret=True,
                               compile_cache=warm)
    if warm.stats.disk_errors:  # jaxlib that can't export pallas: degrade
        assert warm.stats.disk_stores == 0
        pytest.skip("jax.export cannot round-trip this lowering here")
    assert warm.stats.disk_stores == 1
    assert len(list(tmp_path.glob("*.jaxexec"))) == 1

    cold = cc.CompileCache(max_entries=8, persist_dir=tmp_path)
    out2 = ops.map_coordinates("tri2d", 200, block_n=128, interpret=True,
                               compile_cache=cold)
    assert cold.stats.disk_hits == 1 and cold.stats.misses == 0
    np.testing.assert_array_equal(out1, out2)
    # the rehydrated entry now lives in memory: repeats are plain hits
    ops.map_coordinates("tri2d", 200, block_n=128, interpret=True,
                        compile_cache=cold)
    assert cold.stats.hits == 1


def test_corrupt_persisted_file_recompiles_and_heals(tmp_path):
    warm = cc.CompileCache(max_entries=8, persist_dir=tmp_path)
    key = _key("p")
    warm.get(key, _cheap_build(5.0))
    files = list(tmp_path.glob("*.jaxexec"))
    if not files:
        pytest.skip("jax.export unavailable for persistence here")
    files[0].write_bytes(b"not an executable")

    cold = cc.CompileCache(max_entries=8, persist_dir=tmp_path)
    fn = cold.get(key, _cheap_build(5.0))
    assert float(np.asarray(fn())[0]) == 5.0
    assert cold.stats.disk_errors == 1         # corrupt file detected...
    assert cold.stats.misses == 1              # ...recompiled...
    assert not files[0].exists() or \
        files[0].read_bytes() != b"not an executable"  # ...and not trusted


# ---------------------------------------------------------------------------
# process default + env knobs
# ---------------------------------------------------------------------------


@pytest.fixture
def _fresh_default(monkeypatch):
    monkeypatch.setattr(cc, "_default", None)
    monkeypatch.setattr(cc, "_default_off", False)
    yield
    cc._default = None
    cc._default_off = False


def test_env_knobs_shape_the_default_cache(monkeypatch, tmp_path,
                                           _fresh_default):
    monkeypatch.setenv("REPRO_COMPILE_CACHE_ENTRIES", "7")
    monkeypatch.setenv("REPRO_COMPILE_CACHE_DIR", str(tmp_path))
    cache = cc.default_compile_cache()
    assert cache is not None and cache.max_entries == 7
    assert cache.persist_dir == tmp_path
    assert cc.default_compile_cache() is cache  # stable singleton
    assert cc.resolve(cc.USE_DEFAULT) is cache
    assert cc.resolve(None) is None
    mine = cc.CompileCache(max_entries=1)
    assert cc.resolve(mine) is mine


def test_env_zero_and_configure_zero_disable_caching(monkeypatch,
                                                     _fresh_default):
    monkeypatch.setenv("REPRO_COMPILE_CACHE_ENTRIES", "0")
    assert cc.default_compile_cache() is None
    monkeypatch.delenv("REPRO_COMPILE_CACHE_ENTRIES")
    assert cc.configure_default(max_entries=4).max_entries == 4
    assert cc.configure_default(max_entries=0) is None
    assert cc.default_compile_cache() is None  # stays off until reconfigured
    assert cc.configure_default(max_entries=2).max_entries == 2


def test_malformed_env_value_warns_and_falls_back(monkeypatch,
                                                  _fresh_default):
    monkeypatch.setenv("REPRO_COMPILE_CACHE_ENTRIES", "lots")
    with pytest.warns(UserWarning, match="REPRO_COMPILE_CACHE_ENTRIES"):
        cache = cc.default_compile_cache()
    assert cache is not None
    assert cache.max_entries == cc.DEFAULT_MAX_ENTRIES


def test_spec_fingerprint_identities():
    assert cc.spec_fingerprint("tri2d") == "domain:tri2d"
    assert cc.spec_fingerprint(DOMAINS["gasket2d"]) == "domain:gasket2d"


@pytest.mark.parametrize("env", [None, "elsewhere"])
def test_persistent_cache_is_env_dir_or_fixed_checkout_path(
        monkeypatch, tmp_path, env):
    """JAX_COMPILATION_CACHE_DIR, when set, is JAX's and nothing else is
    set; otherwise the cache is <checkout>/.jax_cache, the same path on
    every call."""
    import pathlib

    import jax

    before = jax.config.jax_compilation_cache_dir
    try:
        if env is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = str(pathlib.Path(__file__).resolve().parents[1]
                       / ".jax_cache")
            assert cc.enable_persistent_cache() == want
            assert cc.enable_persistent_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                               str(tmp_path / env))
            assert cc.enable_persistent_cache() == str(tmp_path / env)
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
