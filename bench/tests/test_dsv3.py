"""The ``derive-closed8-dsv3`` cell (``deepseek-v3-ep32``): its reference
against the program and against ``transformers``, the work functions against
the program's parameters, faults that must make a run not correct, and the
harness finding every file of the cell by name.

The runs drive the cell at a size a test run holds (``run_cell`` without the
harness's look for a chip, the program's smoke share: 1 dense + 2 MoE
layers, 16 experts, 4 held); about a minute each on the CPU."""
import importlib.util

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import common, run, work_mla_moe
from bench.reference import deepseek_v3 as ref
from bench.tests.test_faults import DEVICE

CELL = "derive-closed8-dsv3"


def smoke_config(held: int = 4) -> dict:
    """The bench configuration at the program's smoke share's widths."""
    cfg = common.config("deepseek-v3-ep32")
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
               q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
               qk_rope_head_dim=8, v_head_dim=16, intermediate_size=96,
               moe_intermediate_size=32, num_hidden_layers=3,
               first_k_dense_replace=1, n_routed_experts=held,
               num_experts_per_tok=4, n_group=4, topk_group=2,
               vocab_size=256, torch_dtype="float32")
    cfg["deployment"] = dict(cfg["deployment"], router_experts=16)
    return cfg


def program(arch="deepseek-v3-ep32", **kw):
    from repro.configs import get_smoke_config
    from repro.models import transformer as T

    pcfg = get_smoke_config(arch).replace(**kw)
    return pcfg, T.init_params(jax.random.PRNGKey(0), pcfg)


def test_harness_finds_every_file_of_the_cell():
    cell = common.cell(CELL)
    cfg = common.config(cell["config"])
    mix = common.mix(cell["traffic"])
    kind = common.traffic_kind(mix["kind"])
    base = common.traffic_kind("closed_derive")
    assert mix["kind"] == "closed_derive_ref"
    assert {k: v for k, v in mix.items() if k not in ("kind", "why")} == \
        {k: v for k, v in common.mix("derive_closed8").items()
         if k not in ("kind", "why")}
    for hook in ("warm", "counters", "client_run"):
        assert getattr(kind, hook).__code__.co_filename == \
            getattr(base, hook).__code__.co_filename
    assert importlib.util.find_spec(f"bench.reference.{cfg['reference']}")
    names = {m["name"] for m in common.metrics_for(CELL, True)}
    assert names == {"decode_step_ms", "decode_rows_per_step",
                     "compiles.derive", "idle_share.derive", "validation_ms",
                     "moe_expert_use", "mla_moe_step_roofline",
                     "mfu.derive-mla-moe"}
    assert {m["name"] for m in common.metrics_for(CELL, False)} == \
        {"derive_tokens_per_s", "derive_p50_s", "setup_s"}
    assert cfg["limits"]["dropped_assignments"] == 0


def test_published_widths_and_the_cut():
    cfg = common.config("deepseek-v3-ep32")
    assert (cfg["hidden_size"], cfg["intermediate_size"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["kv_lora_rank"], cfg["q_lora_rank"]) == \
        (7168, 18432, 2048, 8, 512, 1536)
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts"]
    assert cfg["published"] == {"num_hidden_layers": 61,
                                "n_routed_experts": 256}
    assert cfg["deployment"]["router_experts"] * 1 == \
        cfg["deployment"]["expert_parallel"] * cfg["n_routed_experts"]
    from repro.configs import get_config

    pcfg = get_config(cfg["deployment"]["arch"])
    assert (pcfg.n_layers, pcfg.experts_held, pcfg.n_experts) == \
        (cfg["num_hidden_layers"], cfg["n_routed_experts"],
         cfg["deployment"]["router_experts"])


def test_weights_are_the_programs():
    pcfg, params = program()
    m = ref.dims(smoke_config())
    _, _, keys = ref._keys(m["layers"], 0)
    for i in range(m["layers"]):
        dense = i < m["dense"]
        w = ref._layer_weights(keys[i], m, dense)
        stack = params["dense_layers" if dense else "layers"]
        lp = jax.tree.map(lambda t: t[i if dense else i - m["dense"]], stack)
        assert np.array_equal(w["attn"]["wuq"], lp["attn"]["wuq"])
        assert np.array_equal(w["attn"]["wo"], lp["attn"]["wo"])
        if dense:
            assert np.array_equal(w["mlp"]["down"], lp["mlp"]["down"])
            continue
        assert np.array_equal(w["router"], lp["moe"]["router"])
        assert np.array_equal(w["gate"], jnp.moveaxis(lp["moe"]["gate"], 1, 0))
        assert np.array_equal(w["down"], jnp.moveaxis(lp["moe"]["down"], 1, 0))
        assert np.array_equal(w["shared"]["up"], lp["moe"]["shared"]["up"])


def _bias(n_moe, experts):
    return jnp.tile(jnp.linspace(-0.3, 0.3, experts)[None], (n_moe, 1))


def plant_bias(monkeypatch):
    """A nonzero selection bias, in the program's weights and the
    reference's alike."""
    from repro.models import transformer as T

    orig_init, orig_ref = T.init_params, ref._layer_weights

    def init(key, cfg):
        params = orig_init(key, cfg)
        moe = params["layers"]["moe"]
        moe["router_bias"] = _bias(*moe["router_bias"].shape)
        return params

    def weights(key, m, dense):
        w = orig_ref(key, m, dense)
        if not dense:
            w["bias"] = _bias(1, m["experts"])[0]
        return w

    monkeypatch.setattr(T, "init_params", init)
    monkeypatch.setattr(ref, "_layer_weights", weights)
    jax.clear_caches()


def served(pcfg, params, prompt, steps=15):
    from repro.models import transformer as T

    logits, cache = T.prefill(params, pcfg, prompt)
    tok = jnp.argmax(logits[:, -1:], -1)
    out = [tok]
    for _ in range(steps):
        logits, cache = T.decode_step(params, pcfg, tok, cache)
        tok = jnp.argmax(logits, -1)
        out.append(tok)
    return np.asarray(jnp.concatenate(out, 1))


@pytest.mark.parametrize("bias", [False, True])
def test_reference_matches_the_program(monkeypatch, bias):
    """Greedy tokens of the program's prefill and cached decode sit at the
    reference's best logit (float32 both: gap zero to rounding); the float8
    control misses by far more."""
    if bias:
        plant_bias(monkeypatch)
    from repro.models import transformer as T

    pcfg = program(max_seq=40)[0]
    params = T.init_params(jax.random.PRNGKey(0), pcfg)
    prompt = jax.random.randint(jax.random.PRNGKey(5), (2, 8), 0, 256)
    tokens = served(pcfg, params, prompt)
    gap, gap_ctl = ref.token_gaps(smoke_config(), np.asarray(prompt),
                                  tokens, control=True)
    assert gap.shape == tokens.shape
    assert gap.max() < 1e-4
    assert gap_ctl.max() > 10 * max(gap.max(), 1e-6)
    jax.clear_caches()


def test_reference_matches_transformers():
    """The reference's logits, all 16 experts held, against
    ``DeepseekV3ForCausalLM`` with the program's weights copied in."""
    torch = pytest.importorskip("torch")
    spec = importlib.util.spec_from_file_location(
        "dsv3_program_tests", common.ROOT / "tests" / "test_deepseek_v3.py")
    helpers = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(helpers)
    pcfg, params = program("deepseek-v3", max_seq=32)
    model = helpers._hf_model(pcfg, params)
    seq = np.asarray(jax.random.randint(jax.random.PRNGKey(3), (2, 20), 0,
                                        256), np.int32)
    with torch.no_grad():
        want = model(torch.tensor(seq)).logits.numpy()
    m = ref.dims(smoke_config(held=16))
    mk = tuple(sorted(m.items()))
    k_embed, k_head, k_layers = ref._keys(m["layers"], 0)
    x, _ = ref._embed(jnp.asarray(seq), k_embed, mk, False)
    for i in range(m["layers"]):
        x, _ = ref._block(x, x, k_layers[i], mk, i < m["dense"], False)
    head = ref._trunc(k_head, (m["d"], m["padded_vocab"]), m["d"], m["dtype"])
    got = np.asarray(jnp.dot(ref._rms(x, m["eps"]), head,
                             precision=ref.HIGHEST))[..., :m["vocab"]]
    assert np.abs(got - want).max() < 2e-4


def test_work_counts_match_the_program():
    """The share's 13.08 GB of parameters, and the smoke share's bytes
    counted from shapes equal what the program's init allocates."""
    from repro.configs import get_smoke_config
    from repro.models import transformer as T

    assert work_mla_moe.weight_bytes(
        common.config("deepseek-v3-ep32")) // 10**7 == 1307
    shapes = jax.eval_shape(lambda: T.init_params(
        jax.random.PRNGKey(0), get_smoke_config("deepseek-v3-ep32")))
    # the smoke share is float32 throughout: 4 bytes a parameter
    have = sum(int(np.prod(t.shape)) * 4 for t in jax.tree.leaves(shapes))
    assert work_mla_moe.weight_bytes(smoke_config()) == have


def test_expert_shares_read_the_window_delta():
    before = {"engine": {"moe_routed": 100, "moe_to_held": 10,
                         "moe_held_hit": 3, "moe_held_computed": 40}}
    after = {"engine": {"moe_routed": 1100, "moe_to_held": 40,
                        "moe_held_hit": 13, "moe_held_computed": 440}}
    assert work_mla_moe.expert_shares(
        {"before": before, "after": after}) == (10 / 400, 30 / 1000)
    # a program without the counters: nothing to read
    assert work_mla_moe.expert_shares(
        {"before": {"engine": {}}, "after": {"engine": {}}}) is None


# --- runs through the harness ---------------------------------------------


def small_dsv3():
    cell = common.cell(CELL)
    cfg = smoke_config()
    cfg["serve_args"] = [*cfg["serve_args"], "--smoke"]
    mix = common.mix(cell["traffic"])
    mix.update(clients=4, sample_derives=3, artifact_checks=2)
    return cell, cfg, mix


def plant(monkeypatch, fault):
    from repro.models import attention as attn
    from repro.models import moe as M

    if fault == "router_bias_ignored":
        plant_bias(monkeypatch)
        route = M.route

        def no_bias(p, cfg, logits):
            return route({**p, "router_bias": jnp.zeros_like(
                p["router_bias"])}, cfg, logits)

        monkeypatch.setattr(M, "route", no_bias)
    elif fault == "routed_scaling_left_out":
        route = M.route

        def unscaled(p, cfg, logits):
            w, e, probs = route(p, cfg, logits)
            return w / cfg.routed_scaling, e, probs

        monkeypatch.setattr(M, "route", unscaled)
    elif fault == "yarn_mscale_left_out":
        monkeypatch.setattr(attn, "yarn_softmax_scale", lambda s: 1.0)
    elif fault == "held_experts_dropped":
        serve = M.moe_serve

        def shared_only(p, cfg, x):
            return serve({**p, "down": jnp.zeros_like(p["down"])}, cfg, x)

        monkeypatch.setattr(M, "moe_serve", shared_only)


@pytest.mark.parametrize("fault", [None, "router_bias_ignored",
                                   "routed_scaling_left_out",
                                   "yarn_mscale_left_out",
                                   "held_experts_dropped"])
def test_dsv3_faults(monkeypatch, fault):
    jax.clear_caches()
    plant(monkeypatch, fault)
    cell, cfg, mix = small_dsv3()
    out = run.run_cell(cell, cfg, mix, 2**31 + 19, 6.0, False, False, DEVICE)
    jax.clear_caches()
    assert out["attempted"] > 0
    assert out["checks"]["dropped_assignments"]["value"] == 0
    assert out["correct"] is (fault is None), out["checks"]


def test_dsv3_per_layer_metrics_read_what_the_harness_records():
    """A traced run: the counter and span readers read; the device readers
    find no TPU plane on the CPU, and read a made-up device trace."""
    cell, cfg, mix = small_dsv3()
    holder = {}
    orig = common.Run

    def keep(*args, **kwargs):
        holder["run"] = orig(*args, **kwargs)
        return holder["run"]

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(common, "Run", keep)
        out = run.run_cell(cell, cfg, mix, 2**31 + 23, 6.0, True, False,
                           DEVICE)
    assert out["correct"], out["checks"]
    for name in ("moe_expert_use", "mfu.derive-mla-moe", "decode_step_ms",
                 "compiles.derive"):
        assert name in out["metrics"], name
    assert 0 < out["metrics"]["moe_expert_use"]["value"] <= 100
    r = holder["run"]
    busy = 0.5 * r.seconds
    r.trace = {"window_s": r.seconds, "busy_s": busy,
               "per_op_s": {"fusion.1": busy},
               "per_module": {"jit__lambda": (1, busy)}, "gaps": []}
    value = common.metric_reader("mla_moe_step_roofline").read(r)
    assert value is not None and 0 < value <= 100
