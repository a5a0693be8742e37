"""Per-architecture smoke tests (required deliverable): reduced config of the
same family, one forward, asserting output shapes and no NaNs on CPU."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import lm

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = get_config(arch).reduced()
    params = lm.init_model(cfg, KEY)
    B, S = 2, 32
    kw = {}
    if cfg.is_encdec:
        kw["enc_embeds"] = jax.random.normal(KEY, (B, S, cfg.d_model), jnp.float32)
    P = int(S * cfg.prefix_len_fraction) if (cfg.prefix_embed and not cfg.is_encdec) else 0
    if P:
        kw["prefix_embeds"] = jax.random.normal(KEY, (B, P, cfg.d_model), jnp.float32)
    tokens = jax.random.randint(KEY, (B, S - P), 0, cfg.vocab_size)
    logits = lm.forward(params, cfg, tokens, **kw)
    assert logits.shape == (B, S, cfg.padded_vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


def test_param_counts_match_full_configs():
    """Full-config parameter counts should be in the advertised ballpark."""
    expect = {
        "starcoder2_15b": (13e9, 18e9),
        "starcoder2_3b": (2.5e9, 4e9),
        "deepseek_7b": (6e9, 8e9),
        "gemma2_9b": (8e9, 11e9),
        "arctic_480b": (420e9, 520e9),
        "dbrx_132b": (115e9, 145e9),
        "jamba_v0_1_52b": (45e9, 60e9),
        "xlstm_1_3b": (0.9e9, 1.8e9),
    }
    for arch, (lo, hi) in expect.items():
        n = lm.num_params(get_config(arch))
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B params outside [{lo/1e9}, {hi/1e9}]"


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_param_draw_matches_the_eager_expression(dtype):
    """init_params draws each leaf in one jitted program; its values must be
    the eager ``(normal * std).astype(dtype)`` bit for bit, so seeded runs
    keep their weights."""
    import numpy as np

    from repro.models.params import TSpec, _path_key, init_params

    tpl = {"w": TSpec((3, 64, 96), (None, None, None), init="fan_in"),
           "e": TSpec((257, 64), (None, None), std=0.02)}
    got = init_params(tpl, KEY, jnp.dtype(dtype))
    for path, std in ((("w",), 1.0 / np.sqrt(64)), (("e",), 0.02)):
        leaf = tpl[path[0]]
        k = jax.random.fold_in(KEY, _path_key((jax.tree_util.DictKey(path[0]),)))
        want = (jax.random.normal(k, leaf.shape, jnp.float32) * std).astype(dtype)
        assert np.array_equal(np.asarray(got[path[0]]), np.asarray(want))
