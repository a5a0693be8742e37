"""The served weights and the reference's are the same numbers."""

import jax
import numpy as np
import pytest

from chipbench import families, harness, weights

SMALL = dict(name="small", family="dense", d_model=64, num_heads=4, num_kv_heads=2,
             head_dim=16, d_ff=96, vocab_size=512, num_superblocks=3, layers=[["attn", "mlp"]],
             gated_mlp=True, mlp_act="silu", rope_theta=10000.0, norm_eps=1e-6, dtype="bfloat16")
SEED = 2**35 + 77


def test_served_and_reference_weights_agree_bit_for_bit():
    served = weights.served_params(SMALL, SEED)
    flat = {"/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(served)}
    top = weights.top_f32(SMALL, SEED)
    for path, leaf in top.items():
        np.testing.assert_array_equal(np.asarray(flat[path], np.float32), np.asarray(leaf))
    for layer in range(SMALL["num_superblocks"]):
        one = weights.layer_f32(SMALL, SEED, layer)
        for path, leaf in one.items():
            got = np.asarray(flat[f"blocks/0/{path}"][layer], np.float32)
            np.testing.assert_array_equal(got, np.asarray(leaf))


def test_weights_depend_on_every_bit_of_the_seed():
    a = weights.top_f32(SMALL, SEED)["final_norm"]
    b = weights.top_f32(SMALL, SEED + 2**32)["final_norm"]
    c = weights.top_f32(SMALL, SEED)["final_norm"]
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(a, c)


def test_weights_have_the_layout_and_spread_asked_for():
    lay = weights.layout(SMALL)
    top = weights.top_f32(SMALL, 3)
    for path, leaf in top.items():
        shape, _, std = lay[path]
        assert leaf.shape == shape
        assert abs(float(np.std(leaf)) / std - 1) < 0.1


def test_seeded_tree_matches_the_program():
    # build_engine refuses a tree whose structure or shapes differ
    eng = harness.build_engine(SMALL, SEED)
    assert eng.cfg.num_layers == 3 and eng.cfg.gated_mlp


@pytest.mark.parametrize("name", harness.config_names())
def test_layout_is_the_programs_tree_at_published_widths(name):
    from repro.models import lm

    m = dict(harness.load_config(name)["model"], name=name)
    n = m["num_superblocks"]
    tree = weights.nest({path: jax.ShapeDtypeStruct((n, *shape) if stacked else shape, m["dtype"])
                         for path, (shape, stacked, _) in weights.layout(m).items()})
    want = lm.abstract_model(families.of(m).program_config(m))
    assert jax.tree.structure(tree) == jax.tree.structure(want)
    assert all(a.shape == b.shape and a.dtype == b.dtype
               for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(want)))
