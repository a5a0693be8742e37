"""Per-architecture smoke tests (required deliverable): reduced config of the
same family, one forward (+ one train step for representatives), asserting
output shapes and no NaNs on CPU."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.data.pipeline import make_batch
from repro.launch.steps import make_train_step
from repro.models import lm
from repro.training import optimizer as opt

KEY = jax.random.PRNGKey(0)


def _batch_for(cfg, B=2, S=32):
    return make_batch(cfg, B, S, step=0)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shapes_and_finite(arch):
    cfg = get_config(arch).reduced()
    params = lm.init_model(cfg, KEY)
    batch = _batch_for(cfg)
    loss, metrics = jax.jit(lambda p, b: lm.loss_fn(p, cfg, b))(params, batch)
    assert jnp.isfinite(loss), f"{arch}: non-finite loss"
    logits = lm.forward(
        params, cfg, batch["tokens"],
        prefix_embeds=batch.get("prefix_embeds"),
        enc_embeds=batch.get("enc_embeds"),
    )
    S_total = batch["tokens"].shape[1] + (
        batch["prefix_embeds"].shape[1] if "prefix_embeds" in batch else 0
    )
    assert logits.shape == (2, S_total, cfg.padded_vocab)
    assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


@pytest.mark.parametrize("arch", ["starcoder2_3b", "jamba_v0_1_52b", "xlstm_1_3b"])
def test_train_step_no_nans(arch):
    cfg = get_config(arch).reduced()
    params = lm.init_model(cfg, KEY)
    state = (
        opt.adafactor_init(params)
        if cfg.optimizer == "adafactor"
        else opt.adamw_init(params)
    )
    step = jax.jit(make_train_step(cfg))
    batch = _batch_for(cfg)
    p2, s2, m = step(params, state, batch)
    assert jnp.isfinite(m["loss"])
    leaves = jax.tree.leaves(p2)
    assert all(bool(jnp.all(jnp.isfinite(l.astype(jnp.float32)))) for l in leaves)


@pytest.mark.parametrize("arch", ["starcoder2_3b", "gemma2_9b"])
def test_loss_decreases_over_steps(arch):
    cfg = get_config(arch).reduced()
    params = lm.init_model(cfg, KEY)
    state = opt.adamw_init(params)
    step = jax.jit(make_train_step(cfg, opt.AdamWConfig(lr=3e-3, weight_decay=0.0)))
    batch = _batch_for(cfg, B=4, S=32)
    losses = []
    for _ in range(8):
        params, state, m = step(params, state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3, losses


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
