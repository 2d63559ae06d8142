"""frontend_ms (ms; frontend + wire): mean per evaluate request of the
client latency not spent inside ``EvaluationService.evaluate_batch``:
parse, route, wire encode, socket and client decode (a wire hit's whole
latency)."""


def read(run):
    reqs = run.answered("evaluate")
    if not reqs:
        return None
    client = sum(r["t1"] - r["t0"] for r in reqs)
    service = sum(t1 - t0 for _, t0, t1, _ in run.spans("evaluate_batch"))
    return (client - service) / len(reqs) * 1e3
