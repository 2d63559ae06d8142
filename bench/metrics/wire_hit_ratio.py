"""wire_hit_ratio (ratio; frontend + wire): evaluate requests answered from
the frontends' encoded-response LRU (``WireCache`` hits, delta across the
window) over the evaluate requests sent in the window (a hit is counted
as the server receives the request)."""


def read(run):
    n = len(run.sent("evaluate"))
    if not n:
        return None
    hits = run.counters["after"]["wire_hits"] \
        - run.counters["before"]["wire_hits"]
    return hits / n
