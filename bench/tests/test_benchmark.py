"""Every item ``BENCHMARK.json`` names resolves to a file that loads, and the
derive cell's per-layer readers read what the harness records.

The run-level test drives a derive run at a size a test run holds
(``run_cell`` without the harness's look for a chip, profiler on); it takes
about a minute on the CPU."""
import dataclasses

import pytest

from bench import common, run
from bench.tests.test_faults import DEVICE, small_derive

BENCH = common.benchmark()
CELLS = {c["name"]: c for c in BENCH["workloads"]}
CONFIGS = {c["name"]: c for c in BENCH["configs"]}
HOOKS = ("warm", "counters", "client_run", "check")


@pytest.mark.parametrize(
    "name", [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
def test_every_metric_has_a_reader(name):
    assert callable(common.metric_reader(name).read)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_every_config_file_loads(name):
    entry = CONFIGS[name]
    assert entry["file"] == f"bench/configs/{name}.json"
    assert common.config(name) == common.load_json(common.ROOT / entry["file"])


@pytest.mark.parametrize("name", sorted(CELLS))
def test_every_cell_resolves(name):
    cell = CELLS[name]
    assert cell["config"] in CONFIGS
    mix = common.mix(cell["traffic"])
    kind = common.traffic_kind(mix["kind"])
    for hook in HOOKS:
        assert callable(getattr(kind, hook)), hook
    e2e = {m["name"] for m in common.metrics_for(name, False)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert common.metrics_for(name, True)


def test_every_workloads_list_names_cells():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS), m["name"]


def test_decode_step_ms_is_the_mean_of_the_step_spans():
    from bench import spans

    rec = spans.Recorder()
    for t0, dt in [(99.0, 0.5), (110.0, 0.010), (120.0, 0.030),
                   (199.99, 0.02)]:  # the first and last end outside
        rec._span("engine_step", t0, t0 + dt, {"rows": 8, "position": 60})
    rec._span("engine_prefill", 130.0, 131.0, {"rows": 8, "prompt": 48})
    r = common.Run(cell=CELLS["derive-closed8"], config={}, mix={}, seed=1,
                   seconds=100.0, window=(100.0, 200.0), setup_s=0.0,
                   requests=[], rec=rec, counters={}, trace=None, peaks={})
    assert common.metric_reader("decode_step_ms").read(r) == \
        pytest.approx(20.0)
    empty = dataclasses.replace(r, rec=spans.Recorder())
    assert common.metric_reader("decode_step_ms").read(empty) is None


@pytest.fixture(scope="module")
def traced_derive():
    """A small derive run with the profiler and the harness's spans on."""
    cell, cfg, mix = small_derive()
    holder = {}
    orig = common.Run

    def keep(*args, **kwargs):
        holder["run"] = orig(*args, **kwargs)
        return holder["run"]

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(common, "Run", keep)
        out = run.run_cell(cell, cfg, mix, 2**31 + 17, 6.0, True, False,
                           DEVICE)
    return out, holder["run"]


def test_derive_per_layer_metrics_read_what_the_harness_records(
        traced_derive):
    """On the CPU the trace has no TPU plane: the readers of the device
    trace find nothing there (below, a made-up device trace); every other
    per-layer reader of the derive cell reads a span or counter of the run."""
    out, _ = traced_derive
    assert out["correct"], out["checks"]
    wanted = common.metrics_for("derive-closed8", True)
    assert {m["name"] for m in wanted} >= {
        "compiles.derive", "decode_rows_per_step", "decode_step_roofline",
        "mfu.derive", "idle_share.derive", "validation_ms", "decode_step_ms"}
    for m in wanted:
        if m["source"] == "device_trace":
            continue
        assert m["name"] in out["metrics"], m["name"]
    assert out["metrics"]["compiles.derive"]["value"] == 0
    assert 1 <= out["metrics"]["decode_rows_per_step"]["value"] <= 8


def test_derive_device_readers_read_a_device_trace(traced_derive):
    _, r = traced_derive
    steps = r.spans("engine_step")
    assert steps
    busy = 0.5 * r.seconds
    r.trace = {"window_s": r.seconds, "busy_s": busy,
               "per_op_s": {"fusion.1": busy},
               "per_module": {"jit__lambda": (len(steps), busy)},
               "gaps": []}
    for m in common.metrics_for("derive-closed8", True):
        if m["source"] == "device_trace":
            value = common.metric_reader(m["name"]).read(r)
            assert value is not None and value > 0, m["name"]
    assert common.metric_reader("idle_share.derive").read(r) == \
        pytest.approx(50.0)

