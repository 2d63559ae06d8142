"""serving/engine generation paths: eos early-stop, fixed-seed sampling
determinism, cache-size guard — plus the EngineBackend that drives the
engine as a real (non-mock) LLMBackend behind the mapping service."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.backends import EngineBackend, MockLLMBackend, canonical_code
from repro.models import transformer as T
from repro.serving.engine import generate

PROMPT_LEN = 8
MAX_NEW = 6


@pytest.fixture(scope="module")
def engine_setup():
    cfg = get_smoke_config("yi-6b").replace(max_seq=PROMPT_LEN + MAX_NEW)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (1, PROMPT_LEN), 0, cfg.vocab_size, jnp.int32)
    return params, cfg, prompts


def test_eos_early_stop(engine_setup):
    """eos_id matching the first generated token stops decode after step 1
    instead of running out max_new_tokens."""
    params, cfg, prompts = engine_setup
    full = generate(params, cfg, prompts, MAX_NEW)
    assert full.steps == MAX_NEW
    first_tok = int(full.tokens[0, PROMPT_LEN])
    stopped = generate(params, cfg, prompts, MAX_NEW, eos_id=first_tok)
    assert stopped.steps == 1
    assert stopped.tokens.shape == (1, PROMPT_LEN + 1)
    assert int(stopped.tokens[0, PROMPT_LEN]) == first_tok


def test_eos_waits_for_whole_batch(engine_setup):
    """With batch > 1, decode only stops once *every* row has emitted eos —
    a row finishing early must not truncate its neighbours."""
    params, cfg, _ = engine_setup
    prompts = jax.random.randint(
        jax.random.PRNGKey(2), (3, PROMPT_LEN), 0, cfg.vocab_size, jnp.int32)
    full = generate(params, cfg, prompts, MAX_NEW)
    # pick the first token of row 0 only; other rows almost surely differ
    eos = int(full.tokens[0, PROMPT_LEN])
    res = generate(params, cfg, prompts, MAX_NEW, eos_id=eos)
    others = np.asarray(full.tokens[1:, PROMPT_LEN])
    if not (others == eos).any():
        assert res.steps > 1


def test_temperature_sampling_deterministic_under_fixed_seed(engine_setup):
    """temperature > 0 draws through jax.random with an explicit seed: the
    same seed must reproduce the exact token sequence; greedy must be
    unaffected by the seed entirely."""
    params, cfg, prompts = engine_setup
    a = generate(params, cfg, prompts, MAX_NEW, temperature=0.9, seed=42)
    b = generate(params, cfg, prompts, MAX_NEW, temperature=0.9, seed=42)
    np.testing.assert_array_equal(np.asarray(a.tokens), np.asarray(b.tokens))
    g1 = generate(params, cfg, prompts, MAX_NEW, temperature=0.0, seed=1)
    g2 = generate(params, cfg, prompts, MAX_NEW, temperature=0.0, seed=2)
    np.testing.assert_array_equal(np.asarray(g1.tokens), np.asarray(g2.tokens))


def test_cache_too_small_asserts(engine_setup):
    """prompt + max_new beyond cfg.max_seq must fail loudly, not overflow
    the KV cache."""
    params, cfg, prompts = engine_setup
    with pytest.raises(AssertionError, match="cache too small"):
        generate(params, cfg, prompts, MAX_NEW + 1)


# ---------------------------------------------------------------------------
# EngineBackend: the engine as a real LLMBackend
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine_backend():
    return EngineBackend("OSS:120b", max_new_tokens=4)


def test_engine_backend_fallback_is_canonical(engine_backend):
    """The untrained smoke model's sampled text fails synthesis, so the
    backend must emit the canonical derivation for the requested domain."""
    from repro.core.backends import build_prompt
    from repro.core.domains import DOMAINS

    prompt = build_prompt(DOMAINS["tri2d"], 20)
    resp = engine_backend.generate(prompt, meta={"domain": "tri2d", "stage": 20})
    assert canonical_code("tri2d") in resp.text
    assert resp.tokens_out == 4       # real decode steps, not replayed priors
    assert resp.seconds > 0 and resp.joules > 0


def test_engine_backend_batch_matches_single(engine_backend):
    """generate_batch (one padded prefill) and generate (singleton batch)
    must emit identical text for the same cell — batching is a throughput
    knob, never a behaviour change."""
    metas = [{"domain": "tri2d", "stage": 20},
             {"domain": "msimplex3", "stage": 20}]
    prompts = ["0 -> (0, 0)\n1 -> (1, 0)", "0 -> (0, 0, 0)\n1 -> (1, 0, 0)"]
    batch = engine_backend.generate_batch(prompts, metas)
    singles = [engine_backend.generate(p, meta=m)
               for p, m in zip(prompts, metas)]
    assert [r.text for r in batch] == [r.text for r in singles]
    assert batch[0].text != batch[1].text  # per-domain fallback, not shared


def test_engine_backend_cache_identity_distinct_from_mock(engine_backend):
    """Engine cells must occupy different content addresses than mock cells
    (and than an engine with different decode knobs)."""
    mock = MockLLMBackend("OSS:120b")
    other = EngineBackend("OSS:120b", max_new_tokens=8)
    fps = {engine_backend.cache_fingerprint, mock.cache_fingerprint,
           other.cache_fingerprint}
    assert len(fps) == 3
    # stable across instances with the same knobs
    twin = EngineBackend("OSS:120b", max_new_tokens=4)
    assert twin.cache_fingerprint == engine_backend.cache_fingerprint


def test_engine_backend_runs_its_config_and_keys_on_widths(engine_backend):
    """The default is the smoke config; a published-width engine occupies
    its own content addresses, so a smoke artifact never serves it."""
    from repro.configs import get_config, get_smoke_config

    assert engine_backend.config == get_smoke_config("yi-6b")
    full = EngineBackend("OSS:120b", max_new_tokens=4,
                         config=get_config("yi-6b"))
    assert full.config.d_model == 4096 and full.config.n_layers == 32
    assert full.cache_fingerprint != engine_backend.cache_fingerprint
    assert "4096" in full.cache_fingerprint


def test_serve_cli_builds_the_stack_and_reports_its_device(tmp_path,
                                                           monkeypatch):
    """``build_map_server`` takes ``main()``'s parsed args: ``--backend
    engine`` without ``--smoke`` means published widths; the built async
    server answers on an ephemeral port and /healthz names the device."""
    import jax

    from repro.launch import serve
    from repro.serving import RemoteMappingService

    parser = serve.build_parser()
    full = parser.parse_args(["--serve-maps", "--backend", "engine",
                              "--arch", "yi-6b"])
    assert serve.engine_config(full).d_model == 4096
    smoke = parser.parse_args(["--serve-maps", "--backend", "engine",
                               "--arch", "yi-6b", "--smoke"])
    assert serve.engine_config(smoke).d_model == 64

    monkeypatch.setenv("REPRO_ARTIFACT_CACHE", str(tmp_path))
    args = parser.parse_args(["--serve-maps", "--port", "0",
                              "--n-validate", "2000"])
    stack = serve.build_map_server(args)
    try:
        client = RemoteMappingService(stack.url)
        assert client.healthy()
        health = client._call_json("/healthz", None)
        dev = jax.devices()
        assert health["device"] == {"platform": dev[0].platform,
                                    "kind": dev[0].device_kind,
                                    "count": len(dev)}
        res = client.derive("tri2d", "OSS:120b", 20)
        assert res.perfect
    finally:
        stack.close()
