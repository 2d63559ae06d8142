"""validation_ms (ms; derivation service): mean wall time in
``pipeline.stage_validation`` per derive — the pure-Python check of the
configured validation points, holding the interpreter lock."""


def read(run):
    spans = run.spans("stage_validation")
    if not spans:
        return None
    return sum(t1 - t0 for _, t0, t1, _ in spans) / len(spans) * 1e3
