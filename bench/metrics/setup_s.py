"""setup_s (s, host clock): process start to window start — loading,
building the server, warming every shape the cell's traffic uses, and the
load generator's ramp."""


def read(run):
    return run.setup_s
