"""decode_step_ms (ms; engine + batcher): mean wall time of the
``EngineStepper.step`` calls that ended in the window.  The call only
dispatches the decode program, but the device runs a bounded queue of
programs, so in a window that the device paces each call waits for an
earlier one to finish: the mean is the server's time per decode step, the
gap between the tokens of a cohort that has the device alone."""


def read(run):
    spans = run.spans("engine_step")
    if not spans:
        return None
    return sum(t1 - t0 for _, t0, t1, _ in spans) / len(spans) * 1e3
