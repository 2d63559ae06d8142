"""eval_service_ms (ms; evaluation service): mean wall time inside
``EvaluationService.evaluate_batch``: planning, dispatch, the wait for the
device, the device-to-host copy and slicing."""


def read(run):
    spans = run.spans("evaluate_batch")
    if not spans:
        return None
    return sum(t1 - t0 for _, t0, t1, _ in spans) / len(spans) * 1e3
