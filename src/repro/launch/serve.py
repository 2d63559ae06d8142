"""Serving launcher — the LM demo and the networked mapping service.

LM prefill/decode demo (the original path):

    PYTHONPATH=src python -m repro.launch.serve --arch yi-6b --smoke \
        --batch 4 --prompt-len 32 --max-new 32

Networked mapping service (HTTP frontend over a MappingService):

    PYTHONPATH=src python -m repro.launch.serve --serve-maps \
        --backend engine --port 8000 --max-batch 8 --max-wait 0.01

``--backend`` picks the inference backend behind the service: ``mock``
(paper replay bank), ``engine`` (real prefill/decode on the in-repo
transformer at ``--arch``'s published widths, or its smoke config with
``--smoke`` — see ``core/backends.EngineBackend``), or ``ollama`` (live
local GGUF models).  Derive requests for the same model are admitted
through a batching queue (``--max-batch`` / ``--max-wait`` /
``--max-pending``); same-cell requests coalesce inside the service.

By default the service runs on the asyncio event-loop frontend
(``serving/aio.py``) and — for the engine backend — continuous batching
(``--decode-slots`` / ``--admission-timeout``): new derives join in-flight
decode batches at the next step boundary.  ``--no-async`` restores the
threaded ``ThreadingHTTPServer`` + gather-then-drain batching.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import os
import time


def engine_config(args):
    """The model the engine backend runs: ``--arch``'s published config, or
    its smoke config with ``--smoke``."""
    from repro.configs import get_config, get_smoke_config

    arch = args.arch or "yi-6b"
    return get_smoke_config(arch) if args.smoke else get_config(arch)


def _backend_factory(args):
    from repro.core import backends

    if args.backend == "mock":
        return backends.MockLLMBackend
    if args.backend == "engine":
        return functools.partial(
            backends.EngineBackend, config=engine_config(args),
            max_new_tokens=args.max_new, temperature=args.temperature)
    if args.backend == "ollama":
        return backends.OllamaBackend
    raise ValueError(f"unknown backend {args.backend!r}")


def _store_from_args(args):
    """Assemble the tiered store from the CLI knobs, falling back to the
    documented env surface (REPRO_STORE_TTL / REPRO_STORE_MAX_BYTES /
    REPRO_MEMORY_ENTRIES / REPRO_PEERS) for any flag left unset — the
    flag/env pairs in the README stay equivalent.  None = store off,
    coalescing-only degradation."""
    from repro.core.store import build_store, env_knobs, split_peers

    knobs = env_knobs()
    if args.store_ttl is not None:
        knobs["ttl_seconds"] = args.store_ttl
    if args.store_max_bytes is not None:
        knobs["max_bytes"] = args.store_max_bytes
    if args.memory_entries is not None:
        knobs["memory_entries"] = args.memory_entries
    if args.peers is not None:
        knobs["peers"] = split_peers(args.peers)
    return build_store(**knobs)


def _pick(value, default):
    """``value`` unless unset — unlike ``or`` this keeps legitimate
    zeros (epsilon=0, gossip fanout 0 = auto)."""
    return default if value is None else value


def _env(name: str, flag_value, cast=str):
    """Flag wins; else the REPRO_CLUSTER_* env var; else None."""
    if flag_value is not None:
        return flag_value
    raw = os.environ.get(name, "").strip()
    if not raw:
        return None
    try:
        return cast(raw)
    except ValueError:
        import warnings

        warnings.warn(f"ignoring malformed {name}={raw!r}", stacklevel=2)
        return None


def _cluster_from_args(args, server):
    """Join a consistent-hash fleet when --cluster-seed (or
    REPRO_CLUSTER_SEED) names at least one live node.  The first node of a
    fleet seeds from its own URL; everyone else names any existing member.
    Returns the started ClusterMembership, or None (standalone — PR 4
    behavior, byte-identical)."""
    from repro.core.store import split_peers
    from repro.serving.cluster import (
        DEFAULT_REPLICAS, DEFAULT_VNODES, ClusterMembership,
    )

    seeds = split_peers(_env("REPRO_CLUSTER_SEED", args.cluster_seed))
    if not seeds:
        return None
    self_url = _env("REPRO_CLUSTER_ADVERTISE", args.advertise_url) \
        or server.url
    cluster = ClusterMembership(
        self_url=self_url,
        seeds=seeds,
        vnodes=_env("REPRO_CLUSTER_VNODES", args.vnodes, int)
        or DEFAULT_VNODES,
        replicas=_env("REPRO_CLUSTER_REPLICAS", args.replicas, int)
        or DEFAULT_REPLICAS,
        heartbeat_interval=_env("REPRO_CLUSTER_HEARTBEAT",
                                args.heartbeat_interval, float) or 1.0,
        sync_interval=_env("REPRO_CLUSTER_SYNC_INTERVAL",
                           args.sync_interval, float) or 5.0,
        placement=_env("REPRO_CLUSTER_PLACEMENT", args.placement) or "ring",
        weight=_pick(_env("REPRO_CLUSTER_WEIGHT", args.weight, float), 1.0),
        gossip_fanout=_pick(
            _env("REPRO_CLUSTER_FANOUT", args.gossip_fanout, int), 0),
    )
    return server.attach_cluster(cluster)


def _router_from_args(args):
    """Build the per-node request router from the CLI knobs, falling back
    to the REPRO_ROUTER_* env surface for any flag left unset."""
    from repro.serving.router import RequestRouter

    return RequestRouter(
        policy=_env("REPRO_ROUTER_POLICY", args.route_policy) or "loaded",
        max_pending=args.max_pending,
        ttl=_pick(_env("REPRO_ROUTER_TTL", args.router_ttl, float), 30.0),
        epsilon=_pick(
            _env("REPRO_ROUTER_EPSILON", args.router_epsilon, float), 0.05),
        depth_penalty_ms=_pick(
            _env("REPRO_ROUTER_DEPTH_PENALTY",
                 args.router_depth_penalty, float), 5.0),
    )


@dataclasses.dataclass
class MapServer:
    """One built serving stack: backend -> batcher -> MappingService -> HTTP
    frontend (-> cluster membership).  The async frontend is already bound
    and its loop running; the threaded one serves from ``serve_forever``."""

    args: argparse.Namespace
    service: object
    server: object
    router: object
    cluster: object
    compile_cache: object
    wire_entries: int
    serve_delay: float

    @property
    def url(self) -> str:
        return self.server.url

    def serve_forever(self) -> None:
        try:
            self.server.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            self.close()

    def close(self) -> None:
        if self.cluster is not None:
            self.cluster.close()
        if self.args.use_async:
            self.server.close()
        else:
            self.server.httpd.server_close()
        for backend in self.service.backends().values():
            close = getattr(backend, "close", None)
            if close is not None:
                close()


def build_map_server(args) -> MapServer:
    """Build the full stack from parsed CLI args (see :func:`build_parser`).

    ``--async`` (the default) serves from the asyncio event-loop frontend
    and, for the engine backend, drives generation through the continuous
    batcher (step-interleaved cohorts, ``--decode-slots``); ``--no-async``
    falls back to the threaded server + gather-then-drain batching."""
    from repro.core import compile_cache
    from repro.serving import (
        AsyncMappingHTTPServer, MappingHTTPServer, MappingService,
        batching_factory, continuous_factory,
    )

    # evaluation-plane knobs (flags win; REPRO_COMPILE_CACHE_* env fallback
    # is read inside configure_default/default_compile_cache)
    if args.compile_cache_entries is not None \
            or args.compile_cache_dir is not None:
        compile_cache.configure_default(
            max_entries=args.compile_cache_entries,
            persist_dir=args.compile_cache_dir)
    cc = compile_cache.default_compile_cache()

    if args.use_async and args.backend == "engine":
        # continuous batching: new derives join in-flight decodes at the
        # next step boundary instead of waiting for the batch to drain
        factory = continuous_factory(
            _backend_factory(args), decode_slots=args.decode_slots,
            max_pending=args.max_pending,
            admission_timeout=args.admission_timeout)
    else:
        factory = batching_factory(
            _backend_factory(args), max_batch=args.max_batch,
            max_wait=args.max_wait, max_pending=args.max_pending)
    service = MappingService(store=_store_from_args(args),
                             backend_factory=factory,
                             n_validate=args.n_validate)
    router = _router_from_args(args)
    serve_delay = _pick(_env("REPRO_SLOW_SERVE", args.slow_serve, float),
                        0.0)
    wire_entries = _pick(_env("REPRO_WIRE_CACHE_ENTRIES",
                              args.wire_cache_entries, int), 256)
    if args.use_async:
        server = AsyncMappingHTTPServer(
            service, host=args.host, port=args.port,
            max_pending=args.max_pending,
            observability=args.observability,
            router=router, serve_delay=serve_delay,
            wire_cache_entries=wire_entries)
        server.start()  # bind + loop up before cluster membership probes
    else:
        server = MappingHTTPServer(service, host=args.host, port=args.port,
                                   observability=args.observability,
                                   router=router, serve_delay=serve_delay,
                                   wire_cache_entries=wire_entries)
    cluster = _cluster_from_args(args, server)
    return MapServer(args, service, server, router, cluster, cc,
                     wire_entries, serve_delay)


def print_banner(stack: MapServer) -> None:
    from repro.core.compile_cache import device_info

    args, server, router = stack.args, stack.server, stack.router
    cluster, cc = stack.cluster, stack.compile_cache
    store = stack.service.store
    if store is None:
        desc = "off"
    else:
        mem = store.memory.max_entries if store.memory is not None else 0
        peers = store.peer.peers if store.peer is not None else []
        disk = (f"{store.root} (ttl={store.disk.ttl_seconds}, "
                f"max_bytes={store.disk.max_bytes})"
                if store.disk is not None else "diskless")
        desc = f"{disk} memory={mem} entries, peers={peers or 'none'}"
    mode = "async" if args.use_async else "threaded"
    print(f"mapping service on {server.url}  "
          f"(backend={args.backend}, frontend={mode}, store={desc})")
    dev = device_info()
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}")
    if args.backend == "engine":
        cfg = engine_config(args)
        print(f"engine model: {cfg.arch_id} layers={cfg.n_layers} "
              f"d_model={cfg.d_model} "
              f"({'smoke' if args.smoke else 'published'} config)")
    print(f"observability: tracing={'on' if args.observability else 'off'} "
          f"(X-Repro-Trace-Id; GET /v1/trace/<id>), metrics=json+prometheus "
          f"(GET /metrics?format=prometheus)")
    if args.use_async and args.backend == "engine":
        print(f"continuous batching: decode_slots={args.decode_slots} "
              f"admission_timeout={args.admission_timeout}s")
    if cc is None:
        print("compile cache: off")
    else:
        print(f"compile cache: {cc.max_entries} entries, "
              f"persist={cc.persist_dir or 'off'}")
    print(f"evaluate wire: binary framing via 'Accept: "
          f"application/x-repro-binary' or ?format=binary, "
          f"response LRU={stack.wire_entries} entries")
    if cluster is not None:
        print(f"cluster: self={cluster.self_url} replicas="
              f"{cluster.replicas} vnodes={cluster.vnodes} "
              f"placement={cluster.placement} weight={cluster.weight} "
              f"gossip_fanout={cluster.gossip_fanout or 'auto'} "
              f"heartbeat={cluster.heartbeat_interval}s "
              f"sync={cluster.sync_interval}s "
              f"peers_up={cluster.live_peers() or 'none'}")
    print(f"router: policy={router.policy} epsilon={router.selector.epsilon} "
          f"ttl={router.queue.ttl}s max_pending={router.queue.capacity}")
    if stack.serve_delay > 0:
        print(f"CHAOS: --slow-serve active, every derive sleeps "
              f"{stack.serve_delay}s before serving")
    print("endpoints: POST /v1/derive  POST /v1/evaluate  "
          "GET|DELETE /v1/artifact/<key>  "
          "POST /v1/grid  GET /v1/store/stats  GET /v1/cluster  "
          "GET /v1/replicate/manifest  GET|POST /v1/replicate/<key>  "
          "GET /v1/trace/<id>  GET /v1/traces  GET /healthz  GET /metrics")


def serve_maps(args) -> None:
    """Build the stack, print its banner, then serve until interrupted."""
    stack = build_map_server(args)
    print_banner(stack)
    stack.serve_forever()


def lm_demo(args) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, get_smoke_config
    from repro.models import transformer as T
    from repro.models.common import count_params
    from repro.serving.engine import generate

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = cfg.replace(max_seq=args.prompt_len + args.max_new)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    print(f"arch={cfg.arch_id} params={count_params(params):,}")

    key = jax.random.PRNGKey(1)
    prompts = jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size, jnp.int32)
    extra = None
    if cfg.family == "vlm":
        extra = jax.random.normal(
            key, (args.batch, cfg.vision_seq, cfg.d_model)) * 0.1
    if cfg.family == "audio":
        extra = jax.random.normal(
            key, (args.batch, cfg.encoder_seq, cfg.d_model)) * 0.1

    t0 = time.time()
    res = generate(params, cfg, prompts, args.max_new, extra=extra,
                   temperature=args.temperature)
    dt = time.time() - t0
    total_new = res.steps * args.batch
    print(f"generated {res.steps} steps x {args.batch} seqs in {dt:.2f}s "
          f"({total_new / dt:.1f} tok/s incl. compile)")
    print("sample:", res.tokens[0, args.prompt_len:args.prompt_len + 16].tolist())


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None,
                   help="model arch (LM demo; also the engine backend's "
                        "model, default yi-6b)")
    p.add_argument("--smoke", action="store_true",
                   help="run --arch's reduced smoke config instead of its "
                        "published widths (LM demo and engine backend)")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--max-new", type=int, default=32)
    p.add_argument("--temperature", type=float, default=0.0)
    # networked mapping service
    p.add_argument("--serve-maps", action="store_true",
                   help="serve mapping derivations over HTTP instead of "
                        "running the LM demo")
    p.add_argument("--backend", choices=("mock", "engine", "ollama"),
                   default="mock", help="inference backend for --serve-maps")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000)
    p.add_argument("--n-validate", type=int, default=100_000,
                   help="ground-truth points per served validation")
    p.add_argument("--max-batch", type=int, default=8,
                   help="max derive requests per batched backend call")
    p.add_argument("--max-wait", type=float, default=0.01,
                   help="seconds the batcher waits to fill a batch")
    p.add_argument("--max-pending", type=int, default=256,
                   help="admission queue depth (beyond this: HTTP 503)")
    p.add_argument("--observability", default=True,
                   action=argparse.BooleanOptionalAction,
                   help="per-request tracing (X-Repro-Trace-Id propagation "
                        "+ /v1/trace endpoints); --no-observability turns "
                        "tracing off (metrics always stay on)")
    p.add_argument("--async", dest="use_async", default=True,
                   action=argparse.BooleanOptionalAction,
                   help="serve from the asyncio event-loop frontend with "
                        "continuous batching for the engine backend "
                        "(--no-async falls back to the threaded server + "
                        "gather-then-drain batching)")
    p.add_argument("--decode-slots", type=int, default=8,
                   help="continuous batching: max requests decoding "
                        "concurrently across cohorts (engine backend, "
                        "async mode)")
    p.add_argument("--admission-timeout", type=float, default=30.0,
                   metavar="SECONDS",
                   help="continuous batching: a request waiting longer than "
                        "this for a free decode slot fails with HTTP 504")
    # artifact-store lifecycle (see core/store.py)
    p.add_argument("--store-ttl", type=float, default=None, metavar="SECONDS",
                   help="evict records idle longer than this (default: never)")
    p.add_argument("--store-max-bytes", type=int, default=None,
                   metavar="BYTES",
                   help="disk budget; least-recently-accessed records are "
                        "evicted past it (default: unbounded)")
    p.add_argument("--memory-entries", type=int, default=None,
                   help="LRU hot-tier capacity in records (0 disables the "
                        "memory tier; default 256)")
    p.add_argument("--peers", default=None, metavar="URL[,URL...]",
                   help="static sibling servers to replicate with (PR 4 "
                        "broadcast mesh; superseded by --cluster-seed)")
    # evaluation plane (see core/compile_cache.py + serving/evaluate.py)
    p.add_argument("--compile-cache-entries", type=int, default=None,
                   help="compiled-executable LRU capacity for /v1/evaluate "
                        "(0 disables; default 128) "
                        "[REPRO_COMPILE_CACHE_ENTRIES]")
    p.add_argument("--compile-cache-dir", default=None, metavar="DIR",
                   help="persist serialized executables here so a restarted "
                        "server skips re-tracing (best effort — falls back "
                        "to in-memory when the jaxlib can't round-trip) "
                        "[REPRO_COMPILE_CACHE_DIR]")
    p.add_argument("--wire-cache-entries", type=int, default=None,
                   help="encoded evaluate-response LRU capacity (binary and "
                        "JSON blobs; 0 disables; default 256) "
                        "[REPRO_WIRE_CACHE_ENTRIES]")
    # consistent-hash sharded fleet (see serving/cluster.py); every flag
    # falls back to its REPRO_CLUSTER_* env var
    p.add_argument("--cluster-seed", default=None, metavar="URL[,URL...]",
                   help="join a sharded fleet by asking these live nodes "
                        "for the membership view (the first node of a "
                        "fleet seeds from its own URL) "
                        "[REPRO_CLUSTER_SEED]")
    p.add_argument("--replicas", type=int, default=None,
                   help="copies of each record across the fleet "
                        "(default 2) [REPRO_CLUSTER_REPLICAS]")
    p.add_argument("--vnodes", type=int, default=None,
                   help="virtual nodes per server on the hash ring "
                        "(default 64) [REPRO_CLUSTER_VNODES]")
    p.add_argument("--sync-interval", type=float, default=None,
                   metavar="SECONDS",
                   help="anti-entropy repair cadence (default 5.0) "
                        "[REPRO_CLUSTER_SYNC_INTERVAL]")
    p.add_argument("--heartbeat-interval", type=float, default=None,
                   metavar="SECONDS",
                   help="membership probe cadence (default 1.0) "
                        "[REPRO_CLUSTER_HEARTBEAT]")
    p.add_argument("--advertise-url", default=None, metavar="URL",
                   help="URL peers should reach this node at (default "
                        "http://HOST:PORT — set this when binding 0.0.0.0) "
                        "[REPRO_CLUSTER_ADVERTISE]")
    p.add_argument("--placement", choices=("ring", "rendezvous"),
                   default=None,
                   help="key->owner placement: weighted consistent-hash "
                        "ring (default) or rendezvous hashing "
                        "[REPRO_CLUSTER_PLACEMENT]")
    p.add_argument("--weight", type=float, default=None,
                   help="this node's capacity weight — scales its share "
                        "of the keyspace (default 1.0) "
                        "[REPRO_CLUSTER_WEIGHT]")
    p.add_argument("--gossip-fanout", type=int, default=None,
                   help="peers probed per heartbeat round: N>0 caps at N, "
                        "0 = auto ceil(log2(fleet))+2 (default), "
                        "negative = probe everyone [REPRO_CLUSTER_FANOUT]")
    # load-aware request router (see serving/router.py); every flag falls
    # back to its REPRO_ROUTER_* env var
    p.add_argument("--route-policy", choices=("loaded", "static"),
                   default=None,
                   help="replica selection: 'loaded' ranks owners by EWMA "
                        "latency + advertised queue depth (default); "
                        "'static' keeps placement order "
                        "[REPRO_ROUTER_POLICY]")
    p.add_argument("--router-ttl", type=float, default=None,
                   metavar="SECONDS",
                   help="queued forwards older than this expire instead of "
                        "dispatching (default 30.0) [REPRO_ROUTER_TTL]")
    p.add_argument("--router-epsilon", type=float, default=None,
                   help="epsilon-greedy exploration rate: probability a "
                        "non-best replica is promoted so cold replicas get "
                        "re-measured (default 0.05) [REPRO_ROUTER_EPSILON]")
    p.add_argument("--router-depth-penalty", type=float, default=None,
                   metavar="MS",
                   help="cost penalty per advertised queued request when "
                        "ranking replicas (default 5.0 ms) "
                        "[REPRO_ROUTER_DEPTH_PENALTY]")
    p.add_argument("--slow-serve", type=float, default=None,
                   metavar="SECONDS",
                   help="chaos knob: sleep this long before serving every "
                        "derive — makes this replica artificially slow so "
                        "load-aware routing can be demonstrated "
                        "[REPRO_SLOW_SERVE]")
    return p


def main() -> None:
    from repro.core.compile_cache import enable_persistent_cache

    p = build_parser()
    args = p.parse_args()
    enable_persistent_cache()
    if args.serve_maps:
        serve_maps(args)
    else:
        if not args.arch:
            p.error("--arch is required for the LM demo "
                    "(or pass --serve-maps)")
        lm_demo(args)


if __name__ == "__main__":
    main()
