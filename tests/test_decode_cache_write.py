"""The decode step writes its caches in place: one K/V row per attention
layer (the ring slot ``pos % W`` of a sliding-window layer), each recurrent
state whole, and nothing of the encoder's cross-attention K/V. Its logits
and caches equal those of a straightforward decode that slices each layer's
caches out of the stack, updates the slice and puts it back. ``Engine``
donates the caches to its decode program and keeps its warm-up off them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import LayerSpec, ModelConfig
from repro.models import attention as A
from repro.models import lm
from repro.models import ssm as SSM
from repro.models.layers import rms_norm, rope_apply
from repro.models.params import abstract_params
from repro.serving.engine import Engine, Request, ServeConfig

KEY = jax.random.PRNGKey(5)
JAMBA_SMALL = ModelConfig(
    name="jamba-small", family="hybrid", d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    d_ff=96, vocab_size=512, superblock=(LayerSpec("mamba", "moe"), LayerSpec("attn", "mlp")),
    num_superblocks=2, rope=False, num_experts=8, num_experts_per_tok=2, experts_held=4,
    moe_renormalize=False, mamba_d_state=16, mamba_dt_rank=8, mamba_inner_norms=True,
    remat="none", seq_chunk=8, dtype="float32")
MODELS = {
    "global": lambda: get_config("starcoder2_3b").reduced(seq_chunk=8),
    "local": lambda: get_config("gemma2_9b").reduced(seq_chunk=8),  # window 8
    "encdec": lambda: get_config("seamless_m4t_large_v2").reduced(seq_chunk=8),
    "jamba": lambda: JAMBA_SMALL,
}
B, S, POS = 2, 20, 13  # POS >= the window: the ring buffer has wrapped


def _random_caches(cfg: ModelConfig):
    """Caches of the template's shapes filled with random values, so that
    a row written or left alone shows."""
    tpl = abstract_params(lm.cache_template(cfg, B, S, enc_len=S if cfg.is_encdec else 0),
                          jnp.dtype(cfg.dtype))
    leaves, tree = jax.tree.flatten(tpl)
    keys = jax.random.split(KEY, len(leaves))
    out = [jax.random.randint(k, l.shape, 0, 3, l.dtype) if l.dtype == jnp.int32
           else jax.random.normal(k, l.shape, l.dtype) for k, l in zip(keys, leaves)]
    return jax.tree.unflatten(tree, out)


def _attend(p, x, c, pos, cfg: ModelConfig, *, local: bool):
    """One layer's attention on its own cache slice: write the token's row,
    then attend over every valid slot."""
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(B, 1, H, hd)
    k = (x @ p["wk"]).reshape(B, 1, K, hd)
    v = (x @ p["wv"]).reshape(B, 1, K, hd)
    if cfg.rope:
        q = rope_apply(q, pos[None], cfg.rope_theta)
        k = rope_apply(k, pos[None], cfg.rope_theta)
    slots = jnp.arange(c["k"].shape[1], dtype=jnp.int32)
    if local:
        W = cfg.window_size
        slot, valid = pos % W, pos - (pos - slots) % W >= 0
    else:
        slot, valid = pos, slots <= pos
    ks, vs = c["k"].at[:, slot].set(k[:, 0]), c["v"].at[:, slot].set(v[:, 0])
    out = A.mha_reference(q, ks, vs, causal=False, cap=cfg.attn_softcap, k_valid=valid)
    return out.reshape(B, 1, H * hd) @ p["wo"], {"k": ks, "v": vs}


def _stepwise_decode(params, cfg: ModelConfig, token, pos, caches):
    """The straightforward decode: per layer, slice its caches out of the
    stack, update the slice, and put it back."""
    x = lm._embed(params, token, cfg)
    new = [dict(c) for c in caches]
    for sb in range(cfg.num_superblocks):
        for i, spec in enumerate(cfg.superblock):
            p = jax.tree.map(lambda l: l[sb], params["blocks"][i])
            c = {k: v[sb] for k, v in new[i].items()}
            h = rms_norm(x, p["norm1"], cfg.norm_eps)
            if spec.mixer == "mamba":
                y, written = SSM.mamba_decode(p["mamba"], h, c, cfg)
            else:
                y, written = _attend(p["attn"], h, c, pos, cfg, local=spec.mixer == "attn_local")
            c.update(written)
            x = x + y
            if cfg.is_encdec:
                hc = rms_norm(x, p["norm_cross"], cfg.norm_eps)
                x = x + A.cross_attn_forward(p["cross"], hc, c["cross_k"], c["cross_v"], cfg)
            x, routed = lm._apply_ffn(spec, p, x, cfg, "decode")
            if routed is not None:
                c["routed"] = routed
            new[i] = {k: new[i][k].at[sb].set(v) for k, v in c.items()}
    return lm._head(params, x, cfg), tuple(new)


@pytest.fixture(scope="module", params=list(MODELS))
def decoded(request):
    cfg = MODELS[request.param]()
    params = lm.init_model(cfg, KEY)
    caches = _random_caches(cfg)
    token = jax.random.randint(KEY, (B, 1), 0, cfg.vocab_size)
    pos = jnp.int32(POS)
    got = jax.jit(lambda p, t, i, c: lm.decode_step(p, cfg, t, i, c))(params, token, pos, caches)
    want = _stepwise_decode(params, cfg, token, pos, caches)
    return cfg, caches, got, want


def test_kv_leaves_change_only_at_the_tokens_row(decoded):
    cfg, before, (_, after), _ = decoded
    checked = 0
    for i, spec in enumerate(cfg.superblock):
        if spec.mixer not in ("attn", "attn_local"):
            continue
        slot = POS % cfg.window_size if spec.mixer == "attn_local" else POS
        for name in ("k", "v"):
            old, new = np.asarray(before[i][name]), np.asarray(after[i][name])
            assert new.shape == old.shape
            rest = np.arange(old.shape[2]) != slot
            np.testing.assert_array_equal(new[:, :, rest], old[:, :, rest])
            assert not np.any(new[:, :, slot] == old[:, :, slot])
            checked += 1
    assert checked >= 2


def test_cross_caches_are_left_as_they_are(decoded):
    cfg, before, (_, after), _ = decoded
    cross = [(b[k], a[k]) for b, a in zip(before, after) for k in ("cross_k", "cross_v") if k in b]
    assert bool(cross) == cfg.is_encdec
    for old, new in cross:
        np.testing.assert_array_equal(new, old)


def test_matches_a_per_layer_slice_update_decode(decoded):
    _, _, (logits, caches), (want_logits, want_caches) = decoded
    assert float(jnp.max(jnp.abs(logits - want_logits))) < 2e-3
    assert jax.tree.structure(caches) == jax.tree.structure(want_caches)
    for got, want in zip(jax.tree.leaves(caches), jax.tree.leaves(want_caches)):
        assert got.dtype == want.dtype
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("model", ["global", "jamba"])
def test_engine_donates_its_caches_and_serves_the_greedy_tokens(model):
    """Warm-up leaves the engine's zero caches in place; two ticks (admit
    and decode, then decode) serve the tokens of a step-by-step greedy run
    of ``lm.prefill`` and ``lm.decode_step``, with no cold decode."""
    cfg = MODELS[model]()
    params = lm.init_model(cfg, KEY)
    prompt = np.asarray(jax.random.randint(KEY, (8,), 0, cfg.vocab_size), np.int32)
    eng = Engine(cfg, params, ServeConfig(slots=1, max_seq=32))
    eng.warmup([len(prompt)])
    for leaf in jax.tree.leaves(eng.caches):
        assert not leaf.is_deleted()
        assert not np.any(np.asarray(leaf))
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=8))
    eng.tick()
    eng.tick()
    assert [ev.compile for ev in eng.service_log] == [False] * 3

    logits, caches = lm.prefill(params, cfg, jnp.asarray(prompt)[None])
    caches = jax.tree.map(
        lambda c: jnp.pad(c, [(0, 0), (0, 0), (0, 32 - len(prompt)), (0, 0), (0, 0)])
        if c.ndim == 5 else c, caches)
    want = [int(jnp.argmax(logits[0, -1]))]
    for i in range(2):
        tok = jnp.asarray([[want[-1]]], jnp.int32)
        logits, caches = lm.decode_step(params, cfg, tok, jnp.int32(len(prompt) + i), caches)
        want.append(int(jnp.argmax(logits[0, 0])))
    assert eng.active[0].tokens_out == want
