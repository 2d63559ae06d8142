"""The load-generating client process of one benchmark run.

Started by ``run.py`` with ``JAX_PLATFORMS=cpu``: it never touches the chip.
Protocol on stdin/stdout, one JSON line each way:

1. the run's spec (traffic kind, mix, configuration, seed, output path);
   the process imports its generator and answers ``ready``;
2. ``{"url", "shared", "t_load", "t_w0", "t_w1"}`` — the server, what
   set-up made for the clients, and the times (``time.monotonic``, one clock
   for every process of the host) at which load starts and the measured
   window opens and closes; it drives the window, runs its part of the
   check, writes the result (pickle) to the output path and answers
   ``done``.
"""
from __future__ import annotations

import json
import pickle
import sys


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    sys.path[:0] = [spec["root"], spec["src"]]
    from bench.common import traffic_kind

    kind = traffic_kind(spec["mix"]["kind"])
    import repro.serving  # noqa: F401 — the import cost belongs to set-up

    print("ready", flush=True)
    go = json.loads(sys.stdin.readline())
    spec["url"] = go["url"]
    result = kind.client_run(spec, go["shared"], go["t_load"], go["t_w0"],
                             go["t_w1"])
    with open(spec["out"], "wb") as f:
        pickle.dump(result, f)
    print("done", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
