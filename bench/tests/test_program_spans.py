"""The readers of the program's evaluate-path spans, on a synthetic run
whose spans are put into ``repro.obs.trace``'s ring by hand."""
import collections

import pytest

from bench import common
from repro.obs import trace

READERS = ("eval_queue_ms", "wire_encode_ms", "wire_send_ms",
           "eval_fetch_ms", "eval_host_ms", "host_wait_share")


def make_run(window=(100.0, 200.0)):
    return common.Run(cell={"name": "eval-batch"}, config={}, mix={}, seed=1,
                      seconds=window[1] - window[0], window=window,
                      setup_s=0.0, requests=[], rec=None, counters={},
                      trace=None, peaks={})


@pytest.fixture
def ring(monkeypatch):
    """An empty ring, and ``put(name, t0, t1, cpu_s)`` to fill it."""
    monkeypatch.setattr(trace, "_ring", collections.deque(maxlen=1024))

    def put(name, t0, t1, cpu_s=None):
        trace._ring_add(name, t0, t1, cpu_s, {})

    return put


def read(name, run):
    return common.metric_reader(name).read(run)


def test_readers_mean_per_request_and_per_batch(ring):
    for i in range(2):  # two requests inside the window
        base = 110.0 + 10 * i
        ring("eval_queue", base, base + 0.004 * (i + 1))
        ring("eval_plan", base, base + 0.001, 0.001)
        ring("eval_dispatch", base, base + 0.002, 0.001)
        ring("eval_fetch", base, base + 0.100)
        ring("eval_assemble", base, base + 0.001, 0.001)
        ring("wire_encode", base, base + 0.040, 0.020)
        ring("wire_send", base, base + 0.006, 0.006)
        ring("wire_drain", base, base + 0.010)
    run = make_run()
    assert read("eval_queue_ms", run) == pytest.approx(6.0)
    assert read("wire_encode_ms", run) == pytest.approx(40.0)
    assert read("wire_send_ms", run) == pytest.approx(16.0)
    assert read("eval_fetch_ms", run) == pytest.approx(100.0)
    assert read("eval_host_ms", run) == pytest.approx(4.0)
    # CPU 0.029 of 0.050 wall per request: 42% waiting
    assert read("host_wait_share", run) == pytest.approx(42.0)


def test_readers_take_only_spans_that_ended_in_the_window(ring):
    ring("wire_encode", 99.0, 99.5, 0.5)        # ended before the window
    ring("wire_encode", 199.9, 200.1, 0.2)      # ended after it
    ring("wire_encode", 150.0, 150.010, 0.005)
    run = make_run()
    assert read("wire_encode_ms", run) == pytest.approx(10.0)
    assert read("host_wait_share", run) == pytest.approx(50.0)
    assert read("eval_fetch_ms", run) is None   # no such span in it


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_none_without_spans(ring, name):
    assert read(name, make_run()) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_gives_none_on_a_program_without_the_ring(ring, monkeypatch,
                                                        name):
    for span_name in ("eval_queue", "eval_plan", "eval_dispatch",
                      "eval_fetch", "eval_assemble", "wire_encode",
                      "wire_send", "wire_drain"):
        ring(span_name, 150.0, 150.5, 0.1)
    monkeypatch.delattr(trace, "spans_between")
    assert read(name, make_run()) is None


def test_benchmark_lists_each_reader_for_the_cell():
    per_layer = {m["name"]: m for m in common.benchmark()["per_layer"]}
    names = {m["name"] for m in common.metrics_for("eval-batch", True)}
    for name in READERS:
        assert per_layer[name]["source"] == "program_span"
        assert per_layer[name]["workloads"] == ["eval-batch"]
        assert name in names
