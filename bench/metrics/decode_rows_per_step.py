"""decode_rows_per_step (rows; engine + batcher): sequences advanced per
decode-program call (the cohort's rows), averaged over the calls the
``EngineStepper.step`` wrapper counted in the window.  The batcher steps
every cohort separately, so this shows how far cohorts split."""


def read(run):
    spans = run.spans("engine_step")
    if not spans:
        return None
    return sum(info["rows"] for *_, info in spans) / len(spans)
