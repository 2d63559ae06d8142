"""compiles.eval (count; evaluation service): executables JAX built inside
the window (its backend-compile event fires for a compile and for a load
from the persistent cache alike).  Set-up warms every shape: should be 0."""


def read(run):
    return run.compiles()
