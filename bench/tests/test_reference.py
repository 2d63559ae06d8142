"""The plain references against the program, on the CPU.

These are the only places where the benchmark's tests import the program:
to show that the references compute the same thing.  The references
themselves import nothing of it."""
import numpy as np
import pytest

from bench import common, work
from bench.reference import llama, maps

CONFIG = common.config("maps-serve")


@pytest.mark.parametrize("name", sorted(CONFIG["domains"]))
@pytest.mark.parametrize("start,n", [(0, 5000), (1 << 21, 3000),
                                     ((1 << 24) + 17, 2000)])
def test_maps_equal_the_programs_ground_truth(name, start, n):
    from repro.core.maps import np_map

    want = np_map(name, np.arange(start, start + n, dtype=np.int64))
    got = maps.coords(CONFIG["domains"][name], start, n)
    assert got.shape == want.shape == (n, maps.dim(CONFIG["domains"][name]))
    assert np.array_equal(got, want)


def test_control_breaks_the_int32_guarantee():
    ref = maps.coords(CONFIG["domains"]["cantor2d"], 0, 1 << 20)
    ctl = maps.narrowed(ref).astype(np.int32)
    assert maps.mismatches(ctl, ref) > 0
    assert maps.mismatches(ref.astype(np.int32), ref) == 0


def test_mismatches_counts_shape_and_dtype():
    ref = maps.coords(CONFIG["domains"]["tri2d"], 0, 10)
    assert maps.mismatches(ref.astype(np.int64), ref) == ref.size
    assert maps.mismatches(ref[:5].astype(np.int32), ref) == ref.size


def smoke_config():
    cfg = common.config("yi-6b")
    cfg.update(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, num_hidden_layers=3,
               vocab_size=256, torch_dtype="float32")
    return cfg


def test_llama_weights_are_the_programs():
    import jax

    from repro.configs import get_smoke_config
    from repro.models import transformer as T

    cfg = smoke_config()
    m = llama.dims(cfg)
    params = T.init_params(jax.random.PRNGKey(0), get_smoke_config("yi-6b"))
    _, _, keys = llama._keys(m["layers"], 0)
    for i in range(m["layers"]):
        w = llama._layer_weights(keys[i], m)
        layer = jax.tree.map(lambda t: t[i], params["layers"])
        assert np.array_equal(w["wq"], layer["attn"]["wq"])
        assert np.array_equal(w["wo"], layer["attn"]["wo"])
        assert np.array_equal(w["down"], layer["mlp"]["down"])


def test_llama_forward_matches_the_program():
    """Greedy tokens from the program's prefill and cached decode sit at
    the reference's best logit (float32 both: gap zero to rounding)."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke_config
    from repro.models import transformer as T

    pcfg = get_smoke_config("yi-6b").replace(max_seq=40)
    params = T.init_params(jax.random.PRNGKey(0), pcfg)
    prompt = jax.random.randint(jax.random.PRNGKey(5), (2, 8), 0, 256)
    logits, cache = T.prefill(params, pcfg, prompt)
    tok = jnp.argmax(logits[:, -1:], -1)
    served = [tok]
    for _ in range(15):
        logits, cache = T.decode_step(params, pcfg, tok, cache)
        tok = jnp.argmax(logits, -1)
        served.append(tok)
    served = np.asarray(jnp.concatenate(served, 1))
    gap, gap_ctl = llama.token_gaps(smoke_config(), np.asarray(prompt),
                                    served, control=True)
    assert gap.shape == served.shape
    assert gap.max() < 1e-4
    assert gap_ctl.max() > 10 * max(gap.max(), 1e-6)


def test_work_counts_match_the_program():
    """12,122 MB of yi-6b parameters (what the program's init allocates)
    and the smoke model's parameter count, from shapes alone."""
    import jax

    from repro.configs import get_smoke_config
    from repro.models import transformer as T
    from repro.models.common import count_params

    assert work.weight_bytes(common.config("yi-6b")) // 10**6 == 12122
    params = jax.eval_shape(lambda: T.init_params(
        jax.random.PRNGKey(0), get_smoke_config("yi-6b")))
    assert work.llama_params(smoke_config()) == count_params(params)


@pytest.mark.parametrize("name", sorted(CONFIG["domains"]))
def test_evaluate_bytes_use_the_domains_dim(name):
    from repro.core.domains import DOMAINS

    spec = CONFIG["domains"][name]
    assert maps.dim(spec) == DOMAINS[name].dim
    assert work.evaluate_bytes(1000, maps.dim(spec)) == 4000 * DOMAINS[name].dim
