"""A run with the timed path broken underneath must come out not correct.

Each case drives the rest of a run on the CPU at a size a test run holds
(``run_cell`` without the harness's look for a chip), once sound and once
with a fault planted where the answer is produced:

* evaluate — one coordinate of every map launch altered in the kernel's
  output;
* derive   — every token the engine chooses altered (the next token id);
  and a decode step that returns its state, the KV cache, unchanged.

Slow (about a minute a case): run by hand, ``JAX_PLATFORMS=cpu python -m
pytest -q bench/tests/test_faults.py``."""
import pytest

from bench import common, run

DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def small_eval(mix_name):
    cell = {"name": mix_name, "config": "maps-serve", "traffic": mix_name,
            "chips": 1}
    mix = common.mix(mix_name)
    mix.update(domains=["tri2d", "cantor2d"], sizes=[2048, 4096], clients=2,
               sample=8, offset={"step": 16, "count": 8})
    return cell, common.config(cell["config"]), mix


def small_derive():
    cell = {"name": "derive-closed8", "config": "yi-6b",
            "traffic": "derive_closed8", "chips": 1}
    cfg = common.config("yi-6b")
    cfg.update(serve_args=[*cfg["serve_args"], "--smoke"], hidden_size=64,
               intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, num_hidden_layers=3,
               vocab_size=256, torch_dtype="float32")
    mix = common.mix(cell["traffic"])
    mix.update(clients=4, sample_derives=3, artifact_checks=2)
    return cell, cfg, mix


def plant_answer_fault(monkeypatch):
    from repro.kernels.domain_map import ops

    orig = ops.mapped_executable

    def broken(*args, **kwargs):
        call = orig(*args, **kwargs)
        return lambda: call().at[0, 3].add(1)

    monkeypatch.setattr(ops, "mapped_executable", broken)


def plant_token_fault(monkeypatch):
    from repro.serving import engine

    orig = engine.greedy

    def broken(logits, key=None, temperature=0.0):
        return (orig(logits, key, temperature) + 1) % logits.shape[-1]

    monkeypatch.setattr(engine, "greedy", broken)


def plant_state_fault(monkeypatch):
    from repro.models import transformer as T

    orig = T.decode_step

    def broken(params, cfg, token, cache, extra=None):
        logits, _ = orig(params, cfg, token, cache, extra)
        return logits, cache

    monkeypatch.setattr(T, "decode_step", broken)


@pytest.mark.parametrize("mix_name", ["eval_batch8", "eval_single_zipf"])
@pytest.mark.parametrize("fault", [False, True])
def test_evaluate_answer_fault(monkeypatch, mix_name, fault):
    if fault:
        plant_answer_fault(monkeypatch)
    cell, cfg, mix = small_eval(mix_name)
    out = run.run_cell(cell, cfg, mix, 2**31 + 11, 3.0, False, False, DEVICE)
    assert out["attempted"] > 0
    assert out["correct"] is (not fault), out["checks"]


@pytest.mark.parametrize("fault", [False, True])
def test_derive_token_fault(monkeypatch, fault):
    if fault:
        plant_token_fault(monkeypatch)
    cell, cfg, mix = small_derive()
    out = run.run_cell(cell, cfg, mix, 2**31 + 13, 6.0, False, False, DEVICE)
    assert out["attempted"] > 0
    assert out["correct"] is (not fault), out["checks"]


def test_derive_state_fault(monkeypatch):
    plant_state_fault(monkeypatch)
    cell, cfg, mix = small_derive()
    out = run.run_cell(cell, cfg, mix, 2**31 + 13, 6.0, False, False, DEVICE)
    assert out["attempted"] > 0
    assert out["correct"] is False, out["checks"]
