"""Closed-loop ``POST /v1/evaluate`` traffic.

Client processes (they never touch the chip) run ``clients`` threads between
them (``processes``, see ``run.Clients``); each thread sends its next request
when the last one is answered, through ``RemoteMappingService`` on the binary
wire.  The mix file sets:

* ``batch``      — queries per request (the ``{"queries": [...]}`` form);
* ``address``    — ``domain`` (the registry's geometry) or ``key`` (an
                   artifact derived in set-up through ``POST /v1/derive``);
* ``domains``, ``sizes`` and ``offset`` — a query asks for
                   ``n_points = size - step * k`` with ``k < count``, start 0,
                   so every launch pads to one of ``sizes`` and set-up warms
                   every executable the window can use;
* ``popularity`` — ``uniform`` weighs every (domain, size) alike;
                   ``zipf`` ranks every (domain, size, k) entry in an order
                   fixed by ``order_seed`` (the same for every ``--seed``, so
                   seeds change the order of arrivals, not the work) and
                   weighs rank r by 1 / r^s; each thread deals its entries
                   from a deck of ``deck`` cards laid out by those weights
                   (``_Draw``);
* ``sample``     — answers kept, by reservoir over the window, for the check
                   against the plain reference once the window has closed.

Every answer's shape and dtype is checked as it arrives; the sampled ones are
compared coordinate by coordinate.
"""
from __future__ import annotations

import threading
import time

import numpy as np


def zipf_weights(n: int, s: float) -> list[float]:
    """Normalized zipf(s) popularity over ranks 1..n."""
    raw = [1.0 / (rank ** s) for rank in range(1, n + 1)]
    total = sum(raw)
    return [w / total for w in raw]


# -- server side (the process that holds the chip) ---------------------------

def warm(ctx) -> dict:
    """Set-up: derive the artifacts the mix addresses (``address: key``)
    and run every (domain, size) once, so the window compiles nothing."""
    from repro.serving import RemoteMappingService

    mix, cfg = ctx.mix, ctx.config
    client = RemoteMappingService(ctx.url, timeout=600, retries=0)
    keys = {}
    try:
        if mix["address"] == "key":
            for name in mix["domains"]:
                res = client.derive(name, cfg["derive_model"],
                                    cfg["derive_stage"])
                if not res.perfect:
                    raise RuntimeError(f"set-up derive of {name} is not "
                                       f"deployable")
                keys[name] = res.cache_key
        for name in mix["domains"]:
            for size in mix["sizes"]:
                q = {"n_points": size}
                q.update({"key": keys[name]} if keys else {"domain": name})
                client.evaluate_batch([q])
    finally:
        client.close()
    return {"keys": keys}


def counters(ctx) -> dict:
    return {"wire_hits": ctx.stack.server.eval_wire.hits}


# -- client side (a process of its own, JAX on the CPU) -----------------------

class _Draw:
    """The queries of one client thread, a pure function of the seed.

    Entries are dealt from a deck, so that every seed serves the same mix of
    work in another order: a deck of ``popularity.deck`` cards (default: one
    per entry) takes the entries at the quantiles (i + u) / deck of the
    popularity law, with one u a deck drawn from the seed, which gives each
    entry its expected count give or take one; then it is shuffled.  Under
    ``uniform`` an entry is a (domain, size) and the offset k is drawn apart
    (it moves a query's size by under 2%)."""

    def __init__(self, mix: dict, keys: dict, seed: int, thread: int):
        self.mix = mix
        self.keys = keys
        self.rng = np.random.default_rng([seed, thread])
        pop = mix["popularity"]
        if pop["law"] == "zipf":
            entries = [(d, s, k) for d in mix["domains"]
                       for s in mix["sizes"]
                       for k in range(mix["offset"]["count"])]
            order = np.random.default_rng(pop["order_seed"]).permutation(
                len(entries))
            self.entries = [entries[i] for i in order]
            weights = zipf_weights(len(self.entries), pop["s"])
        elif pop["law"] == "uniform":
            self.entries = [(d, s, None) for d in mix["domains"]
                            for s in mix["sizes"]]
            weights = [1.0 / len(self.entries)] * len(self.entries)
        else:
            raise ValueError(f"unknown popularity law {pop['law']!r}")
        self.cdf = np.cumsum(weights)
        self.cdf[-1] = 1.0
        self.deck_size = pop.get("deck", len(self.entries))
        self.deck: list = []

    def _entry(self):
        if not self.deck:
            q = (np.arange(self.deck_size) + self.rng.random()) \
                / self.deck_size
            cards = np.searchsorted(self.cdf, q, side="right")
            self.deck = [int(i) for i in self.rng.permutation(cards)]
        domain, size, k = self.entries[self.deck.pop()]
        if k is None:
            k = int(self.rng.integers(self.mix["offset"]["count"]))
        return domain, size, k

    def next(self) -> list[tuple[str, dict]]:
        out = []
        for _ in range(self.mix["batch"]):
            domain, size, k = self._entry()
            q = {"n_points": int(size - self.mix["offset"]["step"] * k)}
            q.update({"key": self.keys[domain]} if self.keys
                     else {"domain": domain})
            out.append((domain, q))
        return out


def _thread(spec, shared, thread, t_load, t_w0, t_w1, out):
    from repro.serving import RemoteMappingService

    from bench.reference import maps

    mix = spec["mix"]
    specs = spec["config"]["domains"]
    draw = _Draw(mix, shared["keys"], spec["seed"], thread)
    pick = np.random.default_rng([spec["seed"], thread, 1])
    keep = max(1, mix["sample"] // mix["clients"])
    client = RemoteMappingService(spec["url"], timeout=300, retries=0)
    records, sample, seen, malformed = [], [], 0, 0
    while time.monotonic() < t_load:
        time.sleep(0.001)
    try:
        while time.monotonic() < t_w1:
            batch = draw.next()
            t0 = time.monotonic()
            try:
                res = client.evaluate_batch([q for _, q in batch])
                ok = len(res) == len(batch)
            except Exception as e:  # noqa: BLE001 — a failed request counts
                res, ok = [], False
                out["errors"].append(repr(e)[:300])
            t1 = time.monotonic()
            points = nbytes = 0
            for (domain, q), r in zip(batch, res):
                c = r.get("coords")
                shape = (q["n_points"], maps.dim(specs[domain]))
                if c is None or c.shape != shape or c.dtype != np.int32 \
                        or r.get("domain") != domain:
                    malformed += 1
                    continue
                points += q["n_points"]
                nbytes += c.nbytes
                if not t_w0 <= t1 < t_w1:
                    continue
                seen += 1
                slot = len(sample) if len(sample) < keep \
                    else int(pick.integers(seen))
                if slot < keep:
                    item = (domain, q["n_points"], np.array(c))
                    if slot == len(sample):
                        sample.append(item)
                    else:
                        sample[slot] = item
            records.append((t0, t1, ok, points, nbytes))
    finally:
        client.close()
    out["records"][thread] = records
    out["samples"][thread] = sample
    out["malformed"][thread] = malformed


def client_run(spec: dict, shared: dict, t_load: float, t_w0: float,
               t_w1: float) -> dict:
    """Drive the window from this process's threads (``spec['threads']``,
    of the mix's ``clients``), then compare the sampled answers with the
    plain reference.  Every value is a list or a count, so that the
    results of several processes add up."""
    from concurrent.futures import ThreadPoolExecutor

    from bench.reference import maps

    n = spec["mix"]["clients"]
    out = {"records": [None] * n, "samples": [None] * n,
           "malformed": [0] * n, "errors": []}
    threads = [threading.Thread(target=_thread, args=(
        spec, shared, i, t_load, t_w0, t_w1, out))
        for i in spec.get("threads", range(n))]
    for t in threads:
        t.start()
    while time.monotonic() < t_w0:
        time.sleep(0.01)
    cpu0 = time.process_time()
    while time.monotonic() < t_w1:
        time.sleep(0.01)
    cpu_s = time.process_time() - cpu0
    for t in threads:
        t.join()

    specs = spec["config"]["domains"]
    items = [it for s in out["samples"] if s for it in s]

    def compare(item):
        domain, n_points, got = item
        ref = maps.coords(specs[domain], 0, n_points)
        ctl = maps.narrowed(ref).astype(np.int32)
        return maps.mismatches(got, ref), maps.mismatches(ctl, ref)

    # two at a time: each holds a sampled answer, its reference and the
    # control (up to about 120 MB), beside the server's wire LRU
    with ThreadPoolExecutor(2) as pool:
        pairs = list(pool.map(compare, items))
    records = [dict(t0=a, t1=b, ok=ok, points=p, bytes=nb, kind="evaluate")
               for r in out["records"] if r for a, b, ok, p, nb in r]
    return {
        "records": records,
        "cpu_s": cpu_s,
        "malformed": sum(out["malformed"]),
        "errors": out["errors"][:5],
        "compared_queries": len(items),
        "compared_coords": int(sum(it[2].size for it in items)),
        "mismatches": sum(p[0] for p in pairs),
        "control_mismatches": sum(p[1] for p in pairs),
    }


# -- after the window, server side -------------------------------------------

def check(ctx, result: dict, control: bool) -> dict:
    """The numbers compared for ``correct``, each with its limit."""
    limits = ctx.config["limits"]
    checks = {
        "coord_mismatches": {
            "value": result["mismatches"] + result["malformed"],
            "limit": limits["coord_mismatches"],
            "compared_queries": result["compared_queries"],
            "compared_coords": result["compared_coords"]},
    }
    if control:
        checks["control_coord_mismatches"] = {
            "value": result["control_mismatches"],
            "limit": limits["coord_mismatches"]}
    return checks
