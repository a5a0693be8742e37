"""The served path's Jamba pieces on the CPU at small sizes: the dropless
expert layer that holds a share of the experts (``moe.moe_dropless``), the
Mamba mixer's inner dt/B/C norms, and a Jamba-shaped model (Mamba, GQA
attention, experts) served by ``Engine`` through its slot caches."""

from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import lm
from repro.models import moe as M
from repro.models import ssm
from repro.models.params import init_params
from repro.serving.engine import Engine, Request, ServeConfig

KEY = jax.random.PRNGKey(3)
SUPERBLOCK = (LayerSpec("mamba", "moe"), LayerSpec("attn", "mlp"), LayerSpec("mamba", "mlp"))
JAMBA = ModelConfig(
    name="jamba-small", family="hybrid", d_model=32, num_heads=4, num_kv_heads=2, head_dim=8,
    d_ff=48, vocab_size=97, superblock=SUPERBLOCK, num_superblocks=2, rope=False,
    num_experts=8, num_experts_per_tok=2, moe_renormalize=False, mamba_d_state=4,
    mamba_dt_rank=4, mamba_inner_norms=True, capacity_factor=16.0, moe_group_size=64,
    seq_chunk=8, dtype="float32")


def _experts(cfg: ModelConfig, seed: int = 0) -> dict:
    return init_params(M.moe_template(cfg), jax.random.PRNGKey(seed), jnp.float32)


def _share(p: dict, lo: int, n: int) -> dict:
    return dict(p, **{k: p[k][lo:lo + n] for k in ("wi", "wg", "wo")})


# -- the dropless layer ---------------------------------------------------------


@pytest.mark.parametrize("renormalize", [True, False])
@pytest.mark.parametrize("tokens", [1, 2, 24], ids=["one_pair_each", "decode_pairs", "grouped"])
def test_held_halves_add_up_to_the_whole_layer(renormalize, tokens):
    """Two chips of a 2-way expert-parallel layer, each holding 4 of the 8
    experts: their parts of the output add up to the uncut layer's, and
    their counts to its."""
    whole = replace(JAMBA, moe_renormalize=renormalize)
    p = _experts(whole)
    x = jax.random.normal(KEY, (1, tokens, whole.d_model), jnp.float32)
    want, want_counts = M.moe_dropless(p, x, whole)
    parts = [M.moe_dropless(_share(p, lo, 4), x, replace(whole, expert_offset=lo, experts_held=4))
             for lo in (0, 4)]
    np.testing.assert_allclose(parts[0][0] + parts[1][0], want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(jnp.concatenate([parts[0][1], parts[1][1]], axis=-1),
                                  want_counts)
    assert want_counts.shape == (1, whole.num_experts)
    assert int(want_counts.sum()) == tokens * whole.num_experts_per_tok


def test_a_tokens_output_does_not_depend_on_its_batch():
    """No capacity, no drops: each token alone gives what it gives among
    all the others, however unevenly the batch routes."""
    cfg = replace(JAMBA, expert_offset=2, experts_held=4)
    p = _experts(cfg, seed=1)
    x = jax.random.normal(KEY, (2, 16, cfg.d_model), jnp.float32)
    x = x.at[:, 8:].set(x[0, 0])  # half the batch routes where token 0 does
    together, _ = M.moe_dropless(p, x, cfg)
    for b in range(2):
        for t in range(16):
            alone, _ = M.moe_dropless(p, x[b:b + 1, t:t + 1], cfg)
            np.testing.assert_allclose(together[b, t], alone[0, 0], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("renormalize", [True, False])
def test_dropless_equals_capacity_routing_with_room_for_every_token(renormalize):
    cfg = replace(JAMBA, moe_renormalize=renormalize)
    p = _experts(cfg, seed=2)
    x = jax.random.normal(KEY, (2, 12, cfg.d_model), jnp.float32)
    np.testing.assert_allclose(M.moe_dropless(p, x, cfg)[0], M.moe_apply(p, x, cfg),
                               rtol=1e-5, atol=1e-5)


def test_capacity_routing_refuses_a_share_of_the_experts():
    cfg = replace(JAMBA, experts_held=4)
    x = jnp.zeros((1, 4, cfg.d_model))
    with pytest.raises(ValueError, match="every expert"):
        M.moe_apply(_experts(cfg), x, cfg)


def test_template_holds_the_share_and_the_router_all_experts():
    t = M.moe_template(replace(JAMBA, expert_offset=4, experts_held=4))
    assert t["router"].shape == (32, 8)
    assert t["wi"].shape == t["wg"].shape == (4, 32, 48) and t["wo"].shape == (4, 48, 32)


# -- the Mamba mixer's inner norms ----------------------------------------------


def test_inner_norms_match_a_hand_written_step():
    """One decode step of Jamba's mixer from a zero state, written out with
    numpy: conv, x_proj, RMSNorms on dt, B and C, softplus, the state
    update, the skip and the gate."""
    cfg = JAMBA
    p = init_params(ssm.mamba_template(cfg), jax.random.PRNGKey(4), jnp.float32)
    p = {k: v + 0.1 * jax.random.normal(jax.random.PRNGKey(i), v.shape)
         for i, (k, v) in enumerate(sorted(p.items()))}  # norms and biases away from 0
    assert p["dt_norm"].shape == (cfg.mamba_dt_rank,) and p["B_norm"].shape == (4,)
    x = jax.random.normal(KEY, (1, 1, cfg.d_model), jnp.float32)
    cache = {"conv": jnp.zeros((1, cfg.mamba_d_conv - 1, cfg.mamba_d_inner)),
             "h": jnp.zeros((1, cfg.mamba_d_inner, cfg.mamba_d_state))}
    got, new = ssm.mamba_decode(p, x, cache, cfg)

    q = {k: np.asarray(v, np.float64) for k, v in p.items()}
    norm = lambda v, s: v / np.sqrt(np.mean(v * v) + cfg.norm_eps) * (1 + s)
    silu = lambda v: v / (1 + np.exp(-v))
    u, z = np.split(np.asarray(x[0, 0], np.float64) @ q["in_proj"], 2)
    uc = silu(u * q["conv_w"][-1] + q["conv_b"])  # the window's earlier inputs are 0
    r, n = cfg.mamba_dt_rank, cfg.mamba_d_state
    dbc = uc @ q["x_proj"]
    dt_in, B, C = norm(dbc[:r], q["dt_norm"]), norm(dbc[r:r + n], q["B_norm"]), norm(
        dbc[r + n:], q["C_norm"])
    dt = np.log1p(np.exp(dt_in @ q["dt_proj"] + q["dt_bias"]))
    h = (dt * uc)[:, None] * B[None, :]  # exp(dt A) times the zero state adds nothing
    y = (h @ C + uc * q["D"]) * silu(z)
    np.testing.assert_allclose(np.asarray(got[0, 0]), y @ q["out_proj"], rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(new["h"][0]), h, rtol=2e-4, atol=2e-6)


def test_dt_rank_comes_from_the_config():
    assert ssm.mamba_template(JAMBA)["dt_proj"].shape == (4, 64)
    derived = replace(JAMBA, mamba_dt_rank=0, mamba_inner_norms=False)
    assert derived.resolved_dt_rank == 2  # ceil(32 / 16)
    assert "dt_norm" not in ssm.mamba_template(derived)


# -- a Jamba-shaped model through Engine ----------------------------------------


def _full_logits(params, cfg, tokens):
    """Logits at every position of one pass over the whole sequence: the
    training path when every expert is held, else the dropless stack."""
    if cfg.held_experts == cfg.num_experts:
        return lm.forward(params, cfg, tokens)
    x, _ = lm._run_stack(params["blocks"], lm._embed(params, tokens, cfg), cfg,
                         mode="prefill")
    return lm._head(params, x, cfg)


@pytest.mark.parametrize("held", [0, 4], ids=["all_experts", "half_the_experts"])
def test_engine_prefill_then_decode_matches_one_pass(held):
    """Prefill into a slot of the engine's caches (KV of the attention
    layer beside Mamba's conv window and state), then decode through them:
    each step's logits are those of one pass over the whole sequence, to
    float32 rounding (2e-4), and the engine serves the same greedy tokens."""
    cfg = replace(JAMBA, expert_offset=4 if held else 0, experts_held=held)
    params = lm.init_model(cfg, KEY)
    prompt = np.asarray(jax.random.randint(KEY, (9,), 0, cfg.vocab_size), np.int32)
    engine = Engine(cfg, params, ServeConfig(slots=2, max_seq=24))
    assert engine.expert_tokens.shape == (2, cfg.held_experts)
    engine.submit(Request(rid=0, prompt=prompt, max_new_tokens=6))
    engine.drain()
    served = engine.completed[0].tokens_out
    seq = jnp.asarray(np.concatenate([prompt, served[:-1]]))[None]
    full = _full_logits(params, cfg, seq)[0]
    assert served == [int(t) for t in jnp.argmax(full[len(prompt) - 1:], axis=-1)]
    # both slots decode each step, in each of the two expert layers
    pairs = cfg.num_superblocks * 2 * cfg.num_experts_per_tok
    routed = int(engine.expert_tokens.sum())
    assert routed == pairs if not held else 0 < routed <= pairs

    logits, one = lm.prefill(params, cfg, jnp.asarray(prompt)[None])
    caches = jax.tree.map(lambda f, o: Engine._write_slot(f, o, 1, len(prompt)),
                          engine._zero_caches(2, 24), one)
    steps = [logits[0, 0]]
    for i, tok in enumerate(served[:-1]):
        toks = jnp.asarray([[0], [tok]], jnp.int32)
        logits, caches = lm.decode_step(params, cfg, toks, jnp.int32(len(prompt) + i), caches)
        steps.append(logits[1, 0])
        # each slot's row of an expert layer's counts holds its own token's pairs
        rows = caches[0]["routed"]
        assert rows.shape == (cfg.num_superblocks, 2, cfg.held_experts)
        assert 0 <= int(rows[:, 1].sum()) <= cfg.num_superblocks * cfg.num_experts_per_tok
        np.testing.assert_array_equal(lm.expert_tokens(cfg, caches), rows.sum(axis=1))
    np.testing.assert_allclose(jnp.stack(steps), full[len(prompt) - 1:], rtol=2e-4, atol=2e-4)


def test_dense_decode_reports_no_experts():
    cfg = replace(JAMBA, superblock=(LayerSpec("attn", "mlp"),), num_experts=0,
                  num_experts_per_tok=0)
    engine = Engine(cfg, lm.init_model(cfg, KEY), ServeConfig(slots=1, max_seq=16))
    engine.submit(Request(rid=0, prompt=np.arange(5, dtype=np.int32), max_new_tokens=3))
    engine.drain()
    assert engine.expert_tokens.shape == (0, 0)
    assert len(engine.completed[0].tokens_out) == 3
