"""The trace reduction, on a hand-made event set and on a small trace
recorded on one TPU v5e (``data/v5e_small.xplane.pb``, when present)."""
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).parent / "data"


def device(ops, modules=()):
    return {"/device:TPU:0": {tr.OPS_LINE: list(ops),
                              tr.MODULES_LINE: list(modules)}}


def test_busy_union_ops_and_named_gaps():
    devices = device([("a", 0, 10), ("b", 5, 20), ("c", 40, 50)],
                     [("jit_f", 0, 20), ("jit_f", 40, 50)])
    spans = [("evaluate_batch", 15, 45), ("engine_step", 60, 95)]
    s = tr.reduce(devices, spans, (0, 100))
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["busy_s"] == pytest.approx(30e-9)
    assert s["per_op_s"] == pytest.approx({"a": 10e-9, "b": 15e-9,
                                           "c": 10e-9})
    assert s["per_module"]["jit_f"][0] == 2
    assert [n for n, _ in s["gaps"]] == ["engine_step", "evaluate_batch"]
    assert s["gaps"][0][1] == pytest.approx(50e-9)
    b = tr.breakdown(s)
    assert b["device_ops"][0][0] == "b"
    assert len(b["idle_gaps"]) == 2


def test_window_clips_events_and_gap_without_span_is_none():
    devices = device([("a", -50, 10), ("b", 90, 150)])
    s = tr.reduce(devices, [], (0, 100))
    assert s["busy_s"] == pytest.approx(20e-9)
    assert s["gaps"] == [("none", pytest.approx(80e-9))]


def test_average_over_devices():
    devices = device([("a", 0, 50)])
    devices["/device:TPU:1"] = {tr.OPS_LINE: [("a", 0, 10)],
                                tr.MODULES_LINE: []}
    s = tr.reduce(devices, [], (0, 100))
    assert s["busy_s"] == pytest.approx(30e-9)


def test_op_name_drops_hlo_text_and_map_kernel_is_found():
    from bench import common

    full = ('%tpu_custom_call.1 = s32[8,2097152]{1,0:T(8,128)} custom-call(),'
            ' custom_call_target="tpu_custom_call", kernel_metadata={}')
    assert tr.op_name(full) == "tpu_custom_call.1 s32[8,2097152]"
    assert tr.op_name("fusion.3") == "fusion.3"
    reader = common.metric_reader("map_kernel_roofline")
    assert reader.is_map_kernel(tr.op_name(full))
    assert reader.is_map_kernel(full)
    assert not reader.is_map_kernel(tr.op_name("%fusion.2 = f32[8]{0} fusion()"))


def test_recorded_v5e_trace():
    path = DATA / "v5e_small.xplane.pb"
    if not path.exists():
        pytest.skip("no recorded trace")
    s = tr.summarize(str(path))
    assert s["devices"] == 1
    assert 0 < s["busy_s"] < s["window_s"]
    assert any("map_kernel" in n for n in s["per_op_s"])
    names = {n for n, _ in s["gaps"]}
    assert "evaluate_batch" in names or "none" in names


def test_decode_roofline_takes_every_program_run():
    """The decode share divides the least time of every step and prefill by
    the device time of every program run, whatever the programs are named."""
    from bench import common, work

    reader = common.metric_reader("decode_step_roofline")
    cfg = common.config("yi-6b")
    peaks = common.peaks("TPU v5 lite")
    steps = [{"rows": 3, "position": 60}] * 4
    prefills = [{"rows": 3, "prompt": 48}]
    need = reader.least_seconds(cfg, peaks, steps, prefills)
    step_s = work.decode_bytes(cfg, 3, 60) / peaks["hbm_bytes_per_s"]
    assert need == pytest.approx(4 * step_s + work.prefill_bytes(cfg, 3, 48)
                                 / peaks["hbm_bytes_per_s"])
    # a step reads all weights but the embedding table, less than 12.1 GB
    assert 11.5e9 < work.decode_bytes(cfg, 3, 60) < work.weight_bytes(cfg)
