"""Binary evaluation wire (PR 10): frame codec round-trips (zero-copy
hydration, dtype fidelity including the bool membership mask), format
negotiation + binary/JSON answer parity on both frontends, malformed-frame
negative paths as structured 400s over raw ``http.client`` (the keep-alive
connection survives), the encoded-response LRU, and binary passthrough
across a one-hop owner forward on a 2-node ring."""
import http.client
import io
import json
import struct
import time
import tracemalloc

import numpy as np
import pytest

from repro.core import compile_cache as cc
from repro.core.artifact import ArtifactCache
from repro.core.backends import MockLLMBackend
from repro.core.store import build_store
from repro.obs import trace as obs_trace
from repro.serving import (
    AsyncMappingHTTPServer, ClusterMembership, MappingHTTPServer,
    MappingService, RemoteMappingService, WireFormatError,
)
from repro.serving import wire
from repro.serving.evaluate import EvaluationService, \
    encoded_batch_response, hydrate_result, wire_result

MODEL = "OSS:120b"
FRONTENDS = [MappingHTTPServer, AsyncMappingHTTPServer]


def local_service(tmp_path) -> MappingService:
    return MappingService(cache=ArtifactCache(tmp_path),
                          backend_factory=MockLLMBackend,
                          n_validate=2000, sample_every=1)


def _await(predicate, timeout: float = 20.0, what: str = "condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {what}")


# ---------------------------------------------------------------------------
# codec
# ---------------------------------------------------------------------------


def test_frame_roundtrip_preserves_dtypes_and_structure():
    payload = {
        "coords": np.arange(24, dtype=np.int32).reshape(12, 2),
        "mask": np.array([[True, False], [False, True]]),
        "lam": np.linspace(0.0, 1.0, 7, dtype=np.float64),
        "wide": np.array([1 << 40, -5], dtype=np.int64),
        "f32": np.array([1.5, -2.25], dtype=np.float32),
        "meta": {"domain": "tri2d", "n": 12, "nested": [1, "two", None],
                 "empty": np.array([], dtype=np.int32)},
        "scalar": np.int64(7),
    }
    back = wire.decode_frame(wire.encode_frame(payload))
    for field in ("coords", "mask", "lam", "wide", "f32"):
        np.testing.assert_array_equal(back[field], payload[field])
        assert back[field].dtype == payload[field].dtype
    assert back["meta"]["domain"] == "tri2d"
    assert back["meta"]["nested"] == [1, "two", None]
    assert back["meta"]["empty"].shape == (0,)
    assert back["scalar"] == 7  # numpy scalar rides the JSON header


def test_frame_normalizes_layout_and_endianness():
    """Non-contiguous and big-endian inputs encode to canonical LE bytes
    and decode value-equal."""
    base = np.arange(40, dtype=np.int32).reshape(10, 4)
    strided = base[::2, ::2]
    assert not strided.flags.c_contiguous
    big = base.astype(">i4")
    back = wire.decode_frame(wire.encode_frame(
        {"s": strided, "b": big}))
    np.testing.assert_array_equal(back["s"], strided)
    np.testing.assert_array_equal(back["b"].astype(np.int64),
                                  base.astype(np.int64))
    assert back["b"].dtype.byteorder in ("<", "=")


def test_decoded_arrays_are_zero_copy_views():
    blob = wire.encode_frame({"coords": np.arange(8, dtype=np.int32)})
    back = wire.decode_frame(blob)
    arr = back["coords"]
    assert arr.base is not None          # a view over the frame buffer,
    assert not arr.flags.writeable       # not a copy
    assert not arr.flags.owndata


def _tamper(blob: bytes | memoryview, what: str) -> bytes:
    blob = bytes(blob)
    if what == "magic":
        return b"XXXX" + blob[4:]
    if what == "version":
        return blob[:4] + struct.pack("<I", 99) + blob[8:]
    if what == "header-json":
        head_len = struct.unpack_from("<I", blob, 8)[0]
        return blob[:12] + b"{" * head_len + blob[12 + head_len:]
    if what == "header-overrun":
        return blob[:8] + struct.pack("<I", (1 << 20) + 1) + blob[12:]
    if what == "truncated-header":
        return blob[:10]
    if what == "truncated-segment":
        return blob[:-3]
    if what == "trailing-garbage":
        return blob + b"\x00\x01"
    raise AssertionError(what)


@pytest.mark.parametrize("what", ["magic", "version", "header-json",
                                  "header-overrun", "truncated-header",
                                  "truncated-segment", "trailing-garbage"])
def test_malformed_frames_raise_wireformaterror(what):
    blob = wire.encode_frame({"coords": np.arange(64, dtype=np.int32)})
    with pytest.raises(WireFormatError):
        wire.decode_frame(_tamper(blob, what))


def test_header_payload_segment_consistency_is_enforced():
    # a segment whose byte count disagrees with its declared dtype x shape
    arr = np.arange(16, dtype=np.int32)
    blob = bytearray(wire.encode_frame({"a": arr}))
    head_len = struct.unpack_from("<I", blob, 8)[0]
    header = json.loads(bytes(blob[12:12 + head_len]))
    header["segments"][0]["shape"] = [15]  # 60 bytes expected, 64 shipped
    new_head = json.dumps(header).encode()
    tampered = (bytes(blob[:8]) + struct.pack("<I", len(new_head))
                + new_head + bytes(blob[12 + head_len:]))
    with pytest.raises(WireFormatError, match="needs"):
        wire.decode_frame(tampered)
    def frame_with_header(header_obj, segment_bytes=b""):
        head = json.dumps(header_obj).encode()
        return (wire.MAGIC + struct.pack("<I", wire.VERSION)
                + struct.pack("<I", len(head)) + head + segment_bytes)

    # a payload referencing a segment that does not exist
    with pytest.raises(WireFormatError, match="references segment"):
        wire.decode_frame(frame_with_header(
            {"payload": {"__nd__": 3}, "segments": []}))
    # segments the payload never references are corruption, not padding
    with pytest.raises(WireFormatError, match="never references"):
        wire.decode_frame(frame_with_header(
            {"payload": None,
             "segments": [{"dtype": "int32", "shape": [2]}]},
            struct.pack("<I", 8) + arr[:2].tobytes()))
    with pytest.raises(WireFormatError, match="JSON object"):
        wire.decode_request(wire.encode_frame([1, 2, 3]))


def test_stream_framing_roundtrip_and_truncation():
    cells = [{"i": i, "coords": np.arange(4 * (i + 1), dtype=np.int32)}
             for i in range(3)]
    stream = b"".join(wire.stream_chunk(wire.encode_frame(c))
                      for c in cells)
    back = list(wire.iter_stream(io.BytesIO(stream).read))
    assert [c["i"] for c in back] == [0, 1, 2]
    np.testing.assert_array_equal(back[2]["coords"], cells[2]["coords"])
    # EOF mid-frame is an error, not a silent stop
    with pytest.raises(WireFormatError, match="truncated"):
        list(wire.iter_stream(io.BytesIO(stream[:-5]).read))
    with pytest.raises(WireFormatError, match="truncated"):
        list(wire.iter_stream(io.BytesIO(stream[:2]).read))


# ---------------------------------------------------------------------------
# one-pass encoder: the same bytes as the two-copy encoder, one allocation
# ---------------------------------------------------------------------------


def reference_frame(payload) -> bytes:
    """The frame as the two-copy encoder built it: a contiguous
    little-endian copy of each array, ``tobytes``, and one join."""
    segments: list = []
    stripped = wire._strip_arrays(payload, segments)
    segments = [a.astype(a.dtype.newbyteorder("<"))
                if a.dtype.byteorder == ">" else a for a in segments]
    head = json.dumps({"payload": stripped,
                       "segments": [{"dtype": a.dtype.name,
                                     "shape": list(a.shape)}
                                    for a in segments]},
                      default=str).encode()
    parts = [wire.MAGIC, struct.pack("<I", wire.VERSION),
             struct.pack("<I", len(head)), head]
    for arr in segments:
        raw = np.ascontiguousarray(arr).tobytes()
        parts += [struct.pack("<I", len(raw)), raw]
    return b"".join(parts)


def _block(n: int, seed: int = 0) -> np.ndarray:
    """An evaluate launch's output: (8, padded) int32, C-ordered."""
    padded = 1 << max(n - 1, 1).bit_length()
    return np.random.default_rng(seed).integers(
        -(1 << 30), 1 << 30, (8, padded), dtype=np.int32)


def _payload(case: str):
    block = _block(3000)
    if case.startswith("transposed-dim"):
        dim = int(case[len("transposed-dim"):])
        return {"domain": "tri2d", "n_points": 3000,
                "coords": block[:dim, :3000].T}
    if case == "bool-mask":
        return {"mask": block[0, :3000] > 0,
                "grid": (block[:3, :500] > 0).T}
    if case == "big-endian":
        return {"be": block[:3, :3000].T.astype(">i4"),
                "be_flat": block[0, :777].astype(">i8"),
                "be_f64": np.linspace(-1, 1, 33).astype(">f8")}
    if case == "zero-length":
        return {"coords": block[:2, :0].T,
                "empty": np.array([], dtype=np.int32),
                "tail": np.arange(5, dtype=np.int32)}
    if case == "nested-batch":
        results = [{"index": i, "domain": d, "n_points": n,
                    "coords": _block(n, i)[:dim, :n].T}
                   for i, (d, dim, n) in enumerate(
                       [("tri2d", 2, 1000), ("msimplex3", 3, 700),
                        ("msimplex5", 5, 513), ("msimplex4", 4, 64)])]
        results.append({"index": 4, "tier": "membership",
                        "mask": block[0, :144] > 0})
        return {"results": results,
                "batch": {"queries": 5, "groups": np.int64(4),
                          "strided": block[::2, ::3][:, :40],
                          "scalar": np.array(7, dtype=np.int16)}}
    raise AssertionError(case)


def _arrays(obj) -> list:
    found: list = []
    wire._strip_arrays(obj, found)
    return found


@pytest.mark.parametrize("case", [
    "transposed-dim2", "transposed-dim3", "transposed-dim4",
    "transposed-dim5", "transposed-dim7", "bool-mask", "big-endian", "zero-length",
    "nested-batch"])
def test_one_pass_frame_is_byte_identical_to_two_copy_frame(case):
    payload = _payload(case)
    blob = wire.encode_frame(payload)
    assert isinstance(blob, memoryview) and blob.readonly
    with pytest.raises(TypeError):
        blob[0] = 0
    assert bytes(blob) == reference_frame(payload)
    assert struct.unpack_from("<I", blob, 4)[0] == wire.VERSION == 1
    back = wire.decode_frame(blob)
    sent, got = _arrays(payload), _arrays(back)
    assert len(sent) == len(got)
    for a, b in zip(sent, got):
        np.testing.assert_array_equal(b, a)
        assert b.dtype == a.dtype.newbyteorder("<")
        assert b.shape == a.shape


def test_encoding_allocates_one_frame():
    """The frame buffer is the only large allocation: the peak of traced
    memory while encoding a batch of several MB stays within 1.1x the
    frame's length (the two-copy encoder peaks at 2x: its parts and
    their join)."""
    results = [{"index": i, "coords": _block(n, i)[:dim, :n].T}
               for i, (dim, n) in enumerate(
                   [(2, 300_000), (3, 250_000), (5, 200_000), (4, 100_000)])]
    payload = {"results": results, "batch": {"queries": len(results)}}
    frame_len = len(reference_frame(payload))
    assert frame_len > 10 << 20

    def peak(encode) -> int:
        tracemalloc.start()
        try:
            blob = encode(payload)
            _, top = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        assert len(blob) == frame_len
        return top

    assert peak(wire.encode_frame) <= 1.1 * frame_len
    assert peak(reference_frame) > 1.9 * frame_len
    stats: dict = {}
    blob = wire.encode_frame(payload, stats=stats)
    head_len = struct.unpack_from("<I", blob, 8)[0]
    assert stats["frame"] == len(blob) == frame_len
    assert stats["copied"] == \
        stats["frame"] - 12 - head_len - 4 * len(results)
    assert stats["columnwise"] == sum(
        1 for r in results
        if r["coords"].shape[1] <= wire._COLUMNWISE_MAX_DIM)


def test_wire_encode_span_counts_each_array_byte_once():
    """On the evaluate path, the ``wire_encode`` span carries the frame's
    length, the array bytes written into it (everything but the preamble,
    header and length prefixes) and the segments copied column by
    column."""
    ev = EvaluationService(compile_cache=cc.CompileCache(max_entries=8))
    queries = [{"domain": "tri2d", "n_points": 200, "block_n": 128},
               {"domain": "msimplex3", "n_points": 150, "block_n": 128},
               {"domain": "tri2d", "tier": "membership",
                "extent": [12, 12]}]
    t0 = time.monotonic()
    token = obs_trace.activate(obs_trace.TraceBuffer(), "e5" * 16)
    try:
        blob = encoded_batch_response(ev, None, queries, single=False,
                                      binary=True)
    finally:
        obs_trace.deactivate(token)
    (span,) = obs_trace.spans_between(t0, time.monotonic(), "wire_encode")
    attrs = span[4]
    head_len = struct.unpack_from("<I", blob, 8)[0]
    segments = json.loads(bytes(blob[12:12 + head_len]))["segments"]
    assert attrs["frame"] == attrs["bytes"] == len(blob)
    assert attrs["copied"] == \
        attrs["frame"] - 12 - head_len - 4 * len(segments)
    assert attrs["columnwise"] == 2  # the two coords blocks; not the mask
    assert [s["dtype"] for s in segments] == ["int32", "int32", "bool"]


def test_wire_cache_generations_and_artifact_invalidation():
    cache = wire.WireCache(entries=2)
    cell = ("bin", "single", ("k",))
    cache.put(cell, b"blob", generation=0, artifact_keys=("aa" * 32,))
    assert cache.get(cell, 0) == b"blob"
    # compile-cache rotation bumps the generation: stale entry stops serving
    assert cache.get(cell, 1) is None
    assert cache.stats_dict()["entries"] == 0
    cache.put(cell, b"blob", artifact_keys=("aa" * 32,))
    cache.invalidate_artifact("aa" * 32)
    assert cache.get(cell) is None
    # LRU evicts the oldest cell
    cache.put(("a",), b"1")
    cache.put(("b",), b"2")
    cache.put(("c",), b"3")
    assert cache.get(("a",)) is None and cache.get(("c",)) == b"3"
    stats = cache.stats_dict()
    assert stats["capacity"] == 2 and stats["hits"] >= 1


def test_wire_cache_bounds_the_bytes_it_holds(monkeypatch):
    """Past ``MAX_CACHED_BYTES`` the oldest blobs go, whatever the entry
    bound; a blob larger than the whole budget is not cached at all."""
    monkeypatch.setattr(wire, "MAX_CACHED_BYTES", 100)
    cache = wire.WireCache(entries=16)
    for i in range(4):
        cache.put((i,), wire.encode_frame({"i": i}))
    frame = len(cache.get((3,)))
    assert 25 < frame <= 50  # two fit in 100 bytes, three do not
    assert cache.get((0,)) is None and cache.get((1,)) is None
    assert cache.get((2,)) is not None
    assert cache.stats_dict()["bytes"] == 2 * frame
    cache.put(("big",), b"x" * 101)
    assert cache.get(("big",)) is None
    assert cache.stats_dict()["entries"] == 2
    cache.put((2,), b"y" * 10)  # replacing an entry re-counts its bytes
    assert cache.stats_dict()["bytes"] == frame + 10
    cache.invalidate_artifact("nothing")
    cache.clear()
    assert cache.stats_dict()["bytes"] == 0


# ---------------------------------------------------------------------------
# negotiation + parity, both frontends
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("frontend", FRONTENDS)
def test_negotiation_and_parity_over_raw_http(tmp_path, frontend):
    """Accept header, ?format=binary, and a binary request body each flip
    the response to binary; absent all three the answer stays JSON — and
    both framings carry numerically identical arrays."""
    svc = local_service(tmp_path)
    with frontend(svc) as server:
        conn = http.client.HTTPConnection(server.host, server.port)
        body = json.dumps({"domain": "tri2d", "n_points": 96,
                           "block_n": 128}).encode()

        def post(path, payload, headers):
            conn.request("POST", path, payload,
                         {"Content-Type": "application/json", **headers})
            resp = conn.getresponse()
            return resp.status, resp.getheader("Content-Type"), resp.read()

        st, ctype, raw = post("/v1/evaluate", body,
                              {"Accept": wire.CONTENT_TYPE})
        assert st == 200 and wire.is_binary(ctype)
        via_accept = wire.decode_frame(raw)

        st, ctype, raw = post("/v1/evaluate?format=binary", body, {})
        assert st == 200 and wire.is_binary(ctype)
        via_query = wire.decode_frame(raw)

        conn.request("POST", "/v1/evaluate",
                     wire.encode_frame(json.loads(body)),
                     {"Content-Type": wire.CONTENT_TYPE})
        resp = conn.getresponse()
        assert resp.status == 200
        assert wire.is_binary(resp.getheader("Content-Type"))
        via_body = wire.decode_frame(resp.read())

        st, ctype, raw = post("/v1/evaluate", body, {})
        assert st == 200 and ctype.startswith("application/json")
        via_json = hydrate_result(json.loads(raw))

        for res in (via_query, via_body, via_json):
            np.testing.assert_array_equal(res["coords"],
                                          via_accept["coords"])
            assert res["coords"].dtype == via_accept["coords"].dtype
        conn.close()


@pytest.mark.parametrize("frontend", FRONTENDS)
def test_binary_and_json_clients_agree_end_to_end(tmp_path, frontend):
    """The negotiated client and the JSON fallback client get the same
    dicts back — single, batch (membership mask as real bools), and the
    sweep stream — through either frontend."""
    svc = local_service(tmp_path)
    with frontend(svc) as server:
        cli_b = RemoteMappingService(server.url)
        cli_j = RemoteMappingService(server.url, binary=False)
        queries = [
            {"domain": "tri2d", "n_points": 200, "block_n": 128},
            {"domain": "gasket2d", "n_points": 128, "block_n": 128},
            {"domain": "tri2d", "tier": "membership", "extent": [12, 12]},
        ]
        got_b = cli_b.evaluate_batch(queries)
        got_j = cli_j.evaluate_batch(queries)
        for rb, rj in zip(got_b, got_j):
            assert set(rb) == set(rj)
            for field in ("coords", "mask"):
                if field in rb:
                    np.testing.assert_array_equal(rb[field], rj[field])
                    assert rb[field].dtype == rj[field].dtype
        assert got_b[2]["mask"].dtype == np.bool_  # not int32-coerced
        single_b = cli_b.evaluate("tri2d", n_points=200, block_n=128)
        np.testing.assert_array_equal(single_b["coords"],
                                      got_j[0]["coords"])
        sweep_b = list(cli_b.evaluate_sweep(["tri2d"], [64, 128],
                                            block_n=64))
        sweep_j = list(cli_j.evaluate_sweep(["tri2d"], [64, 128],
                                            block_n=64))
        assert len(sweep_b) == len(sweep_j) == 2
        for cb, cj in zip(sweep_b, sweep_j):
            np.testing.assert_array_equal(cb["coords"], cj["coords"])
        cli_b.close()
        cli_j.close()


@pytest.mark.parametrize("frontend", FRONTENDS)
def test_malformed_binary_bodies_answer_400_and_keep_alive(tmp_path,
                                                           frontend):
    """Wire-supplied garbage is a structured 400 — never a 500, and the
    keep-alive connection stays usable for the next (valid) request."""
    svc = local_service(tmp_path)
    with frontend(svc) as server:
        conn = http.client.HTTPConnection(server.host, server.port)
        good = wire.encode_frame({"domain": "tri2d", "n_points": 64})
        bad_bodies = [
            b"this is not a frame",
            _tamper(good, "version"),
            _tamper(good, "truncated-segment"),
            wire.encode_frame([1, 2]),  # frames fine, not a JSON object
        ]
        for bad in bad_bodies:
            conn.request("POST", "/v1/evaluate", bad,
                         {"Content-Type": wire.CONTENT_TYPE})
            resp = conn.getresponse()
            payload = json.loads(resp.read())
            assert resp.status == 400, payload
            assert "error" in payload
            # same connection, next request: served normally
            conn.request("POST", "/v1/evaluate", good,
                         {"Content-Type": wire.CONTENT_TYPE})
            resp = conn.getresponse()
            assert resp.status == 200
            assert wire.decode_frame(resp.read())["n_points"] == 64
        conn.close()


@pytest.mark.parametrize("frontend", FRONTENDS)
def test_repeat_evaluates_serve_from_the_wire_cache(tmp_path, frontend):
    svc = local_service(tmp_path)
    with frontend(svc) as server:
        cli = RemoteMappingService(server.url)
        first = cli.evaluate("tri2d", n_points=128, block_n=128)
        again = cli.evaluate("tri2d", n_points=128, block_n=128)
        np.testing.assert_array_equal(first["coords"], again["coords"])
        stats = cli.metrics()["evaluate_wire"]
        assert stats["entries"] >= 1
        assert stats["hits"] >= 1
        # only warm (all-executable-hit) responses are cached: the cached
        # blob must say so
        assert again["executable"] == "hit"
        cli.close()


def test_artifact_delete_invalidates_cached_wire_blobs(tmp_path):
    svc = local_service(tmp_path)
    with MappingHTTPServer(svc) as server:
        cli = RemoteMappingService(server.url)
        key = cli.derive("tri2d", MODEL, 20).cache_key
        cli.evaluate(key=key, n_points=96)   # compile (miss, uncached)
        cli.evaluate(key=key, n_points=96)   # warm: lands in the wire LRU
        assert server.eval_wire.stats_dict()["entries"] >= 1
        hits_before = server.eval_wire.stats_dict()["hits"]
        cli.evaluate(key=key, n_points=96)   # served straight off the LRU
        assert server.eval_wire.stats_dict()["hits"] == hits_before + 1
        cli.delete_artifact(key)
        assert server.eval_wire.stats_dict()["entries"] == 0
        cli.close()


# ---------------------------------------------------------------------------
# one-hop owner forward: binary passthrough
# ---------------------------------------------------------------------------


def test_forwarded_evaluate_relays_binary_verbatim(tmp_path):
    """A 2-node ring with replicas=1: the non-owner forwards an
    artifact-key evaluate to the owner and relays the owner's bytes +
    Content-Type untouched — the hop is binary end to end, and the decoded
    answer equals the owner's own."""
    def boot(name, seeds):
        svc = MappingService(store=build_store(root=tmp_path / name),
                             backend_factory=MockLLMBackend,
                             n_validate=2000, sample_every=1)
        server = MappingHTTPServer(svc).start()
        server.attach_cluster(ClusterMembership(
            server.url, seeds=seeds, replicas=1, vnodes=64,
            heartbeat_interval=0.15, down_after=2.0, sync_interval=0.3))
        return server
    a = boot("a", [])
    b = boot("b", [a.url])
    try:
        _await(lambda: all(len(s.cluster.ring.nodes) == 2 for s in (a, b)),
               what="2-node membership convergence")
        owner_cli = RemoteMappingService(a.url)
        key = owner_cli.derive("tri2d", MODEL, 20).cache_key
        owner, other = (a, b) if a.cluster.owns(key) else (b, a)
        _await(lambda: owner.service.store is not None
               and key in owner.service.store,
               what="record resident on its owner")
        assert not other.cluster.owns(key)
        assert key not in other.service.store

        reference = RemoteMappingService(owner.url).evaluate(
            key=key, n_points=96)
        conn = http.client.HTTPConnection(other.host, other.port)
        conn.request("POST", "/v1/evaluate",
                     json.dumps({"key": key, "n_points": 96}).encode(),
                     {"Content-Type": "application/json",
                      "Accept": wire.CONTENT_TYPE})
        resp = conn.getresponse()
        raw = resp.read()
        assert resp.status == 200
        assert wire.is_binary(resp.getheader("Content-Type"))
        hopped = wire.decode_frame(raw)
        np.testing.assert_array_equal(hopped["coords"],
                                      reference["coords"])
        assert hopped["coords"].dtype == reference["coords"].dtype
        assert other.forwarded >= 1      # the hop really happened
        assert other.eval_wire.stats_dict()["entries"] == 0  # relay, no cache
        conn.close()
    finally:
        for s in (a, b):
            s.close()


# ---------------------------------------------------------------------------
# wire_result/hydrate_result dtype fidelity (the JSON path)
# ---------------------------------------------------------------------------


def test_json_wire_dict_round_trips_mask_as_bool():
    ev = EvaluationService(compile_cache=cc.CompileCache(max_entries=8))
    res = ev.evaluate({"domain": "tri2d", "tier": "membership",
                       "extent": [8, 8]})
    assert res["mask"].dtype == np.bool_
    over_json = hydrate_result(json.loads(json.dumps(wire_result(res))))
    np.testing.assert_array_equal(over_json["mask"], res["mask"])
    assert over_json["mask"].dtype == np.bool_
    assert "dtype" not in over_json  # hydration consumes the annotation
    # a pre-PR-10 server's wire dict (no dtype field) still hydrates, on
    # the historical int32 default
    legacy = json.loads(json.dumps(wire_result(res)))
    legacy.pop("dtype")
    assert hydrate_result(legacy)["mask"].dtype == np.int32
