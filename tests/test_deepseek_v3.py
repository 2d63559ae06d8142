"""DeepSeek-V3 (DeepSeek-R1's architecture) and its one-chip EP32 share.

The smoke share: 1 dense + 2 MoE layers, 16 experts in 4 groups (2 kept),
top-4, 1 shared expert, YaRN on; the share holds experts 0-3.  Float32
throughout, checked against ``transformers``' independent implementation
(``DeepseekV3ForCausalLM``, rotate-half rope) and against plain numpy.
"""
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.configs.base import Yarn
from repro.models import moe as M
from repro.models import transformer as T
from repro.models.common import rope, swiglu

#: float32 program against float32 ``transformers`` on the CPU: the two
#: differ only in summation order (6e-6 on logits of scale 4); the same
#: program in bfloat16 misses by about 1 (router choices flip)
TOL = 2e-4


def _cfg(arch="deepseek-v3", **kw):
    return get_smoke_config(arch).replace(**kw)


def test_published_config_and_share():
    full, share = get_config("deepseek-v3"), get_config("deepseek-v3-ep32")
    assert (full.n_layers, full.d_model, full.d_ff, full.n_experts,
            full.expert_d_ff, full.first_dense_layers) == \
        (61, 7168, 18432, 256, 2048, 3)
    assert (full.n_groups, full.topk_groups, full.moe_top_k,
            full.routed_scaling, full.vocab_size) == (8, 4, 8, 2.5, 129280)
    assert full.rope_scaling == Yarn(40.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert full.experts_held == 0
    assert share == full.replace(arch_id="deepseek-v3-ep32", n_layers=8,
                                 experts_held=8)


# --- (a) against transformers -------------------------------------------


def _hf_model(cfg, params):
    torch = pytest.importorskip("torch")
    tr = pytest.importorskip("transformers")
    hc = tr.DeepseekV3Config(
        vocab_size=cfg.vocab_size, hidden_size=cfg.d_model,
        intermediate_size=cfg.d_ff, moe_intermediate_size=cfg.expert_d_ff,
        num_hidden_layers=cfg.n_layers,
        first_k_dense_replace=cfg.first_dense_layers,
        n_routed_experts=cfg.n_experts, num_experts_per_tok=cfg.moe_top_k,
        n_group=cfg.n_groups, topk_group=cfg.topk_groups,
        n_shared_experts=cfg.n_shared_experts,
        routed_scaling_factor=cfg.routed_scaling, norm_topk_prob=True,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_heads,
        q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_dim, qk_rope_head_dim=cfg.qk_rope_dim,
        v_head_dim=cfg.v_head_dim, rope_theta=cfg.rope_theta,
        rope_scaling={"type": "yarn", "factor": cfg.rope_scaling.factor,
                      "original_max_position_embeddings":
                          cfg.rope_scaling.original_max_position,
                      "beta_fast": cfg.rope_scaling.beta_fast,
                      "beta_slow": cfg.rope_scaling.beta_slow,
                      "mscale": cfg.rope_scaling.mscale,
                      "mscale_all_dim": cfg.rope_scaling.mscale_all_dim},
        max_position_embeddings=163840, rms_norm_eps=cfg.norm_eps,
        rope_interleave=False, tie_word_embeddings=False,
        attn_implementation="eager")
    model = tr.DeepseekV3ForCausalLM(hc).eval()
    w = jax.tree.map(lambda t: torch.tensor(np.asarray(t, np.float32)),
                     params)
    h, e = cfg.n_heads, cfg.n_experts
    sd = {"model.embed_tokens.weight": w["embed"][:cfg.vocab_size],
          "lm_head.weight": w["lm_head"][:, :cfg.vocab_size].T,
          "model.norm.weight": w["final_norm"]}
    stacks = [w["dense_layers"]] * cfg.first_dense_layers + \
        [w["layers"]] * (cfg.n_layers - cfg.first_dense_layers)
    for i, stack in enumerate(stacks):
        j = i if i < cfg.first_dense_layers else i - cfg.first_dense_layers
        lp = jax.tree.map(lambda t: t[j], stack)
        a, pre = lp["attn"], f"model.layers.{i}."
        sd.update({
            pre + "input_layernorm.weight": lp["ln1"],
            pre + "post_attention_layernorm.weight": lp["ln2"],
            pre + "self_attn.q_a_proj.weight": a["wdq"].T,
            pre + "self_attn.q_a_layernorm.weight": a["q_norm"],
            pre + "self_attn.q_b_proj.weight":
                a["wuq"].reshape(cfg.q_lora_rank, -1).T,
            pre + "self_attn.kv_a_proj_with_mqa.weight":
                torch.cat([a["wdkv"], a["wkr"]], 1).T,
            pre + "self_attn.kv_a_layernorm.weight": a["kv_norm"],
            pre + "self_attn.kv_b_proj.weight":
                torch.cat([a["wuk"], a["wuv"]], 2).reshape(
                    cfg.kv_lora_rank, -1).T,
            pre + "self_attn.o_proj.weight": a["wo"].T,
        })
        if "mlp" in lp:
            for name in ("gate", "up", "down"):
                sd[pre + f"mlp.{name}_proj.weight"] = lp["mlp"][name].T
            continue
        m = lp["moe"]
        sd[pre + "mlp.gate.weight"] = m["router"].T
        sd[pre + "mlp.gate.e_score_correction_bias"] = m["router_bias"]
        for x in range(e):
            for name in ("gate", "up", "down"):
                sd[pre + f"mlp.experts.{x}.{name}_proj.weight"] = \
                    m[name][:, x, :].T
        for name in ("gate", "up", "down"):
            sd[pre + f"mlp.shared_experts.{name}_proj.weight"] = \
                m["shared"][name].T
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not unexpected and not missing, (missing, unexpected)
    assert h == hc.num_attention_heads
    return model


def _program_logits(params, cfg, seq, prompt):
    """Prefill ``prompt`` tokens, then decode the rest one at a time
    through the MLA cache (the absorbed path): logits at every position."""
    pre, cache = T.prefill(params, cfg, seq[:, :prompt])
    out = [pre]
    for i in range(prompt, seq.shape[1]):
        step, cache = T.decode_step(params, cfg, seq[:, i:i + 1], cache)
        out.append(step)
    return jnp.concatenate(out, 1)[..., :cfg.vocab_size]


@pytest.fixture(scope="module")
def against_hf():
    torch = pytest.importorskip("torch")
    cfg = _cfg(max_seq=32)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    model = _hf_model(cfg, params)
    seq = jax.random.randint(jax.random.PRNGKey(3), (2, 20), 0,
                             cfg.vocab_size)
    with torch.no_grad():
        want = model(torch.tensor(np.asarray(seq))).logits.numpy()
    return cfg, params, seq, want


def test_program_matches_transformers(against_hf):
    cfg, params, seq, want = against_hf
    got = np.asarray(_program_logits(params, cfg, seq, 12))
    assert np.abs(want).max() > 0.5           # logits of scale 1
    assert np.abs(got - want).max() < TOL


def test_bfloat16_program_misses_the_tolerance(against_hf):
    """The tolerance is tight enough to catch the precision step below."""
    cfg, params, seq, want = against_hf
    bf = jax.tree.map(lambda t: t.astype(jnp.bfloat16)
                      if t.dtype == jnp.float32 else t, params)
    for stack in ("dense_layers", "layers"):
        if "moe" in bf[stack]:
            bf[stack]["moe"]["router"] = params[stack]["moe"]["router"]
            bf[stack]["moe"]["router_bias"] = \
                params[stack]["moe"]["router_bias"]
    got = np.asarray(_program_logits(bf, cfg.replace(dtype="bfloat16"),
                                     seq, 12), np.float32)
    assert np.abs(got - want).max() > TOL


# --- (b) the shares sum to the uncut layer -------------------------------


def _moe_layer(cfg):
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    return jax.tree.map(lambda t: t[0], params["layers"]["moe"])


def test_shares_sum_to_the_uncut_layer():
    full_cfg = _cfg()
    share_cfg = _cfg("deepseek-v3-ep32")
    full = _moe_layer(full_cfg)
    share = _moe_layer(share_cfg)
    # the share's init draws exactly the whole model's experts 0-3
    for name in ("gate", "up", "down"):
        np.testing.assert_array_equal(share[name], full[name][:, :4])
    np.testing.assert_array_equal(share["router"], full["router"])

    x = jax.random.normal(jax.random.PRNGKey(4), (3, 5, full_cfg.d_model))
    want, _ = M.moe_serve(full, full_cfg, x)
    per = full_cfg.n_experts // full_cfg.n_groups    # one share = one group
    shared = swiglu(x, full["shared"]["gate"], full["shared"]["up"],
                    full["shared"]["down"])
    total = shared
    for k in range(full_cfg.n_experts // 4):
        # relabel so that group k's experts are this share's 0-3: routing
        # permutes with whole groups
        order = np.arange(full_cfg.n_experts).reshape(-1, per)
        order[[0, k]] = order[[k, 0]]
        order = order.reshape(-1)
        p = {"router": full["router"][:, order],
             "router_bias": full["router_bias"][order],
             "shared": full["shared"],
             **{n: full[n][:, order[:4]] for n in ("gate", "up", "down")}}
        out, counts = M.moe_serve(p, share_cfg, x)
        assert int(counts[M.COUNTERS.index("dropped")]) == 0
        total = total + (out - shared)
    np.testing.assert_allclose(total, want, atol=1e-5)


# --- (c) the router against plain numpy noaux_tc --------------------------


def _np_noaux_tc(logits, bias, groups, keep_groups, k, scaling):
    s = 1 / (1 + np.exp(-logits))
    choice = s + bias
    n, e = s.shape
    g = choice.reshape(n, groups, -1)
    group_score = np.sort(g, -1)[..., -2:].sum(-1)
    kept = np.argsort(-group_score, -1)[:, :keep_groups]
    mask = np.zeros((n, groups), bool)
    np.put_along_axis(mask, kept, True, -1)
    choice = np.where(np.repeat(mask, e // groups, -1), choice, -np.inf)
    top = np.argsort(-choice, -1)[:, :k]
    w = np.take_along_axis(s, top, -1)
    return top, w / w.sum(-1, keepdims=True) * scaling


def test_router_matches_numpy_noaux_tc():
    cfg = _cfg()
    logits = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (64, 16)))
    bias = np.asarray(jax.random.uniform(jax.random.PRNGKey(6), (16,))) * 0.4
    args = (cfg.n_groups, cfg.topk_groups, cfg.moe_top_k, cfg.routed_scaling)
    for b in (np.zeros(16, np.float32), bias.astype(np.float32)):
        w, e, _ = M.route({"router_bias": jnp.asarray(b)}, cfg,
                          jnp.asarray(logits))
        want_e, want_w = _np_noaux_tc(logits, b, *args)
        order = np.argsort(np.asarray(e), -1)
        got_e = np.take_along_axis(np.asarray(e), order, -1)
        got_w = np.take_along_axis(np.asarray(w), order, -1)
        worder = np.argsort(want_e, -1)
        np.testing.assert_array_equal(got_e,
                                      np.take_along_axis(want_e, worder, -1))
        np.testing.assert_allclose(
            got_w, np.take_along_axis(want_w, worder, -1), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(w).sum(-1),
                                   cfg.routed_scaling, rtol=1e-5)
    # the bias moves the choice ...
    _, e0, _ = M.route({"router_bias": jnp.zeros(16)}, cfg,
                       jnp.asarray(logits))
    _, e1, _ = M.route({"router_bias": jnp.asarray(bias)}, cfg,
                       jnp.asarray(logits))
    assert (np.sort(np.asarray(e0), -1) != np.sort(np.asarray(e1), -1)).any()
    # ... but not the weights: they are the unbiased scores, renormalised
    w1, _, _ = M.route({"router_bias": jnp.asarray(bias)}, cfg,
                       jnp.asarray(logits))
    s = 1 / (1 + np.exp(-logits))
    picked = np.take_along_axis(s, np.asarray(e1), -1)
    np.testing.assert_allclose(
        np.asarray(w1),
        picked / picked.sum(-1, keepdims=True) * cfg.routed_scaling,
        rtol=1e-5)


# --- (d) YaRN at factor 1 is plain RoPE ------------------------------------


def test_yarn_at_factor_one_is_plain_rope():
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 9, 3, 64))
    pos = jnp.broadcast_to(jnp.arange(100, 109)[None], (2, 9))
    plain = rope(x, pos, 10000.0)
    yarn = rope(x, pos, 10000.0, Yarn(factor=1.0, original_max_position=4096))
    np.testing.assert_allclose(yarn, plain, rtol=1e-6, atol=1e-6)
    scaled = rope(x, pos, 10000.0, Yarn(factor=40.0,
                                        original_max_position=4096))
    assert float(jnp.abs(scaled - plain).max()) > 1e-2


# --- (e) serving drops nothing ---------------------------------------------


def test_prefill_of_identical_rows_drops_nothing():
    cfg = _cfg("deepseek-v3-ep32")
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    row = jax.random.randint(jax.random.PRNGKey(8), (1, 12), 0, 256)
    toks = jnp.repeat(row, 8, axis=0)
    logits, cache = T.prefill(params, cfg, toks)
    counts = dict(zip(M.COUNTERS, np.asarray(cache["moe_counts"]).tolist()))
    n_moe = cfg.n_layers - cfg.first_dense_layers
    assert counts["dropped"] == 0
    assert counts["routed"] == 8 * 12 * cfg.moe_top_k * n_moe
    assert 0 < counts["to_held"] <= counts["routed"]
    assert counts["held_computed"] == cfg.experts_held * n_moe
    assert counts["held_hit"] <= counts["held_computed"]
    # every row got what one row alone gets
    one, _ = T.prefill(params, cfg, row)
    np.testing.assert_allclose(logits, jnp.repeat(one, 8, axis=0),
                               atol=1e-5)
    # identical tokens all pick the same experts: the capacity-bound
    # training path drops there
    full = _cfg()
    _, _, dropped = M._moe_global(
        _moe_layer(full), full, jnp.ones((8, 12, full.d_model)),
        M.capacity_for(96, full), False)
    assert int(dropped[M.COUNTERS.index("dropped")]) > 0


def test_grouped_serving_counts_as_the_global_path():
    cfg = _cfg()
    layer = _moe_layer(cfg)
    x = jax.random.normal(jax.random.PRNGKey(9), (4, 6, cfg.d_model))
    want, want_counts = M.moe_serve(layer, cfg, x)
    got, counts = M.moe_serve(layer, cfg.replace(moe_groups=2,
                                                 capacity_factor=16.0), x)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_array_equal(counts, want_counts)
    assert int(counts[M.COUNTERS.index("dropped")]) == 0


def test_grouped_and_a2a_refuse_a_share():
    cfg = _cfg("deepseek-v3-ep32", moe_groups=2)
    layer = _moe_layer(_cfg("deepseek-v3-ep32"))
    with pytest.raises(ValueError, match="share"):
        M.moe_apply(layer, cfg, jnp.ones((2, 4, cfg.d_model)))


# --- (f) the engine path ---------------------------------------------------


def test_engine_continuous_matches_drained_and_counts():
    from repro.core.backends import EngineBackend
    from repro.serving import engine
    from repro.serving.async_engine import ContinuousBatcher, EngineStepper

    served = {}

    class Recording(EngineStepper):
        def finalize(self, state, metas):
            out = super().finalize(state, metas)
            toks = np.concatenate([np.asarray(t) for t in state.generated], 1)
            for meta, row in zip(metas, toks):
                served[meta["i"]] = row
            return out

    inner = EngineBackend("OSS:120b", arch="deepseek-v3-ep32",
                          max_new_tokens=4)
    batcher = ContinuousBatcher(Recording(inner), decode_slots=4)
    prompts = [f"derive the map of domain {i}" for i in range(4)]
    try:
        gate = threading.Barrier(4)
        futs = {}

        def go(i):
            gate.wait()
            futs[i] = batcher.submit(prompts[i], {"domain": "tri2d", "i": i})

        threads = [threading.Thread(target=go, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for fut in futs.values():
            fut.result(timeout=300)
        stats = batcher.stats
    finally:
        batcher.close()

    params, cfg = inner._ensure_engine()
    for i, p in enumerate(prompts):
        toks = jnp.asarray(inner._tokenize(p, cfg.vocab_size)[None])
        drained = engine.generate(params, cfg, toks, 4).tokens
        np.testing.assert_array_equal(served[i],
                                      np.asarray(drained)[0, toks.shape[1]:])

    n_moe = cfg.n_layers - cfg.first_dense_layers
    k, held, new = cfg.moe_top_k, cfg.experts_held, inner.max_new_tokens
    tokens = 4 * (inner.prompt_tokens + new)       # every row's tokens
    assert stats.moe_routed == tokens * k * n_moe
    assert stats.moe_held_computed == stats.cohorts * (1 + new) * held * n_moe
    assert stats.moe_dropped == 0
    assert 0 < stats.moe_to_held <= stats.moe_routed
    assert 0 < stats.moe_held_hit <= stats.moe_held_computed
    assert stats.as_dict()["moe_routed"] == stats.moe_routed
