"""Pallas kernel validation: interpret-mode execution vs pure-jnp oracles,
swept over shapes/dtypes (+ hypothesis for the pointwise kernels; a seeded
local fallback sweep keeps coverage when hypothesis is not installed)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _prop import given, settings, st

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_reference
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_reference
from repro.kernels.rmsnorm.ops import rmsnorm
from repro.kernels.rmsnorm.ref import rmsnorm_reference
from repro.kernels.ssm_scan.ops import ssm_scan
from repro.kernels.ssm_scan.ref import ssm_scan_reference

KEY = jax.random.PRNGKey(7)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


class TestFlashAttention:
    @pytest.mark.parametrize(
        "B,S,H,K,hd,causal,window,cap",
        [
            (2, 256, 4, 2, 64, True, 0, 0.0),  # GQA causal
            (1, 256, 4, 4, 128, True, 128, 0.0),  # MHA sliding window
            (2, 128, 8, 2, 64, True, 0, 50.0),  # softcap (gemma2)
            (1, 256, 2, 1, 64, False, 0, 0.0),  # bidirectional MQA
            (1, 192, 6, 3, 32, True, 64, 30.0),  # window + softcap, odd dims
        ],
    )
    def test_against_reference(self, B, S, H, K, hd, causal, window, cap):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (B, S, H, hd), jnp.float32)
        k = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32)
        v = jax.random.normal(ks[2], (B, S, K, hd), jnp.float32)
        out_k = flash_attention(
            q, k, v, causal=causal, window=window, softcap=cap,
            impl="interpret", blk_q=64, blk_k=64,
        )
        out_r = flash_attention(q, k, v, causal=causal, window=window, softcap=cap, impl="xla")
        np.testing.assert_allclose(out_k, out_r, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (1, 128, 4, 64)).astype(dtype)
        k = jax.random.normal(ks[1], (1, 128, 2, 64)).astype(dtype)
        v = jax.random.normal(ks[2], (1, 128, 2, 64)).astype(dtype)
        out_k = flash_attention(q, k, v, impl="interpret", blk_q=64, blk_k=64)
        out_r = flash_attention(q, k, v, impl="xla")
        assert out_k.dtype == dtype
        np.testing.assert_allclose(
            out_k.astype(jnp.float32), out_r.astype(jnp.float32), **tol(dtype)
        )

    def test_block_shape_invariance(self):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (1, 256, 4, 64), jnp.float32)
        k = jax.random.normal(ks[1], (1, 256, 2, 64), jnp.float32)
        v = jax.random.normal(ks[2], (1, 256, 2, 64), jnp.float32)
        outs = [
            flash_attention(q, k, v, impl="interpret", blk_q=bq, blk_k=bk)
            for bq, bk in [(32, 32), (64, 128), (128, 64), (256, 256)]
        ]
        for o in outs[1:]:
            np.testing.assert_allclose(o, outs[0], rtol=1e-5, atol=1e-5)


class TestDecodeAttention:
    @pytest.mark.parametrize(
        "B,S,H,K,hd,pos,cap",
        [
            (2, 512, 8, 2, 64, 511, 0.0),
            (1, 1024, 4, 4, 128, 700, 0.0),  # partially filled cache
            (2, 512, 6, 2, 64, 40, 50.0),  # softcap, short valid region
            (1, 256, 16, 8, 32, 255, 0.0),
        ],
    )
    def test_against_reference(self, B, S, H, K, hd, pos, cap):
        ks = jax.random.split(KEY, 3)
        q = jax.random.normal(ks[0], (B, 1, H, hd), jnp.float32)
        kc = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32)
        vc = jax.random.normal(ks[2], (B, S, K, hd), jnp.float32)
        o1 = decode_attention(q, kc, vc, jnp.int32(pos), softcap=cap, impl="interpret", blk_k=128)
        o2 = decode_attention(q, kc, vc, jnp.int32(pos), softcap=cap, impl="xla")
        np.testing.assert_allclose(o1, o2, rtol=1e-5, atol=1e-5)

    def test_garbage_past_pos_is_ignored(self):
        """Cache slots beyond `pos` must not affect the output."""
        ks = jax.random.split(KEY, 3)
        B, S, H, K, hd, pos = 1, 256, 4, 2, 64, 100
        q = jax.random.normal(ks[0], (B, 1, H, hd), jnp.float32)
        kc = jax.random.normal(ks[1], (B, S, K, hd), jnp.float32)
        vc = jax.random.normal(ks[2], (B, S, K, hd), jnp.float32)
        o1 = decode_attention(q, kc, vc, jnp.int32(pos), impl="interpret", blk_k=64)
        kc2 = kc.at[:, pos + 1 :].set(1e6)
        vc2 = vc.at[:, pos + 1 :].set(-1e6)
        o2 = decode_attention(q, kc2, vc2, jnp.int32(pos), impl="interpret", blk_k=64)
        np.testing.assert_allclose(o1, o2, rtol=1e-6, atol=1e-6)


class TestSsmScan:
    @pytest.mark.parametrize("B,T,D,N,bt,bd", [(2, 64, 128, 8, 16, 64), (1, 128, 256, 16, 32, 128)])
    def test_against_reference(self, B, T, D, N, bt, bd):
        ks = jax.random.split(KEY, 5)
        dt = jax.nn.softplus(jax.random.normal(ks[0], (B, T, D))) * 0.1
        Bc = jax.random.normal(ks[1], (B, T, N))
        Cc = jax.random.normal(ks[2], (B, T, N))
        u = jax.random.normal(ks[3], (B, T, D))
        A = -jnp.exp(jax.random.normal(ks[4], (D, N)) * 0.5)
        y1 = ssm_scan(dt, Bc, Cc, u, A, impl="interpret", blk_t=bt, blk_d=bd)
        y2, _ = ssm_scan_reference(dt, Bc, Cc, u, A)
        np.testing.assert_allclose(y1, y2, rtol=1e-5, atol=1e-5)

    def test_state_continuity_across_time_blocks(self):
        """The VMEM-resident state must carry across t-block grid steps:
        compare one big block vs many small blocks."""
        ks = jax.random.split(KEY, 5)
        B, T, D, N = 1, 64, 64, 4
        dt = jax.nn.softplus(jax.random.normal(ks[0], (B, T, D))) * 0.2
        Bc = jax.random.normal(ks[1], (B, T, N))
        Cc = jax.random.normal(ks[2], (B, T, N))
        u = jax.random.normal(ks[3], (B, T, D))
        A = -jnp.exp(jax.random.normal(ks[4], (D, N)) * 0.5)
        y_one = ssm_scan(dt, Bc, Cc, u, A, impl="interpret", blk_t=64, blk_d=64)
        y_many = ssm_scan(dt, Bc, Cc, u, A, impl="interpret", blk_t=8, blk_d=32)
        np.testing.assert_allclose(y_one, y_many, rtol=1e-5, atol=1e-5)


class TestLindleyScan:
    @pytest.mark.parametrize("B,T,bb,bt", [(16, 1024, 16, 256),  # bench shape
                                           (5, 37, 8, 16),  # pads both axes
                                           (3, 64, 3, 8)])  # many t-blocks
    def test_against_reference(self, B, T, bb, bt):
        """The time-major kernel, its padding and its clock carry across
        t-blocks reproduce the lax.scan recursion exactly."""
        from repro.kernels.lindley_scan.ops import lindley_scan

        rng = np.random.default_rng(B * T)
        arr = jnp.asarray(np.cumsum(rng.exponential(0.1, (B, T)), axis=1), jnp.float32)
        svc = jnp.asarray(rng.exponential(0.08, (B, T)), jnp.float32)
        out = lindley_scan(arr, svc, impl="interpret", blk_b=bb, blk_t=bt)
        assert jnp.array_equal(out, lindley_scan(arr, svc, impl="xla"))


class TestDecisionScan:
    @staticmethod
    def _costs(T, N, E1, seed=4):
        rng = np.random.default_rng(seed)
        c = jnp.asarray(rng.exponential(0.05, (T, N, E1)), jnp.float32)
        # saturated columns and exact ties must survive the kernel path
        c = c.at[3, :, E1 - 1].set(jnp.inf)
        c = c.at[5, 1 % N, :].set(0.07)
        return c

    @pytest.mark.parametrize("stagger,hysteresis", [(1, 0.0), (3, 0.0),
                                                    (3, 0.15), (2, 0.4)])
    def test_against_reference(self, stagger, hysteresis):
        from repro.kernels.decision_scan.ops import decision_scan

        T, N, E1 = 37, 13, 4
        costs = self._costs(T, N, E1)
        cohort = jnp.asarray(np.arange(N) % stagger, jnp.int32)
        ref = decision_scan(costs, cohort, hysteresis=hysteresis,
                            stagger=stagger, impl="xla")
        out = decision_scan(costs, cohort, hysteresis=hysteresis,
                            stagger=stagger, impl="interpret",
                            blk_n=8, blk_t=16)
        assert jnp.array_equal(ref, out)

    def test_choice_carry_across_time_blocks(self):
        """The VMEM-resident previous choice must persist across t-block grid
        steps — hysteresis makes any drop in the carry visible."""
        from repro.kernels.decision_scan.ops import decision_scan

        costs = self._costs(64, 8, 3, seed=9)
        cohort = jnp.asarray(np.arange(8) % 4, jnp.int32)
        one = decision_scan(costs, cohort, hysteresis=0.3, stagger=4,
                            impl="interpret", blk_n=8, blk_t=64)
        many = decision_scan(costs, cohort, hysteresis=0.3, stagger=4,
                             impl="interpret", blk_n=4, blk_t=8)
        assert jnp.array_equal(one, many)

    def test_reference_matches_cluster_decide_rule(self):
        """The oracle is pinned to the production decision rule: iterate
        ``repro.fleet.cluster._decide_vec`` by hand over the same tables."""
        from repro.fleet.cluster import _decide_vec
        from repro.jaxenv import x64
        from repro.kernels.decision_scan.ref import decision_scan_reference

        T, N = 25, 6
        with x64():
            costs = jnp.asarray(np.asarray(self._costs(T, N, 4)), jnp.float64)
            h, prev, manual = 0.15, jnp.full(N, -1, jnp.int32), []
            for t in range(T):
                prev = _decide_vec(costs[t, :, 0], costs[t, :, 1:], prev,
                                   jnp.float64(h), jnp.bool_(t >= 1))
                manual.append(np.asarray(prev))
            ref = decision_scan_reference(costs, jnp.zeros(N, jnp.int32),
                                          hysteresis=h, stagger=1)
        assert np.array_equal(np.stack(manual), np.asarray(ref))


class TestRmsNorm:
    @given(
        st.integers(1, 5),
        st.integers(1, 97),
        st.sampled_from([64, 128, 256]),
        st.sampled_from(["float32", "bfloat16"]),
    )
    @settings(max_examples=25, deadline=None)
    def test_hypothesis_sweep(self, b, rows, d, dtype):
        dt = jnp.dtype(dtype)
        x = (jax.random.normal(KEY, (b, rows, d)) * 3).astype(dt)
        sc = (jax.random.normal(jax.random.PRNGKey(9), (d,)) * 0.2).astype(dt)
        o1 = rmsnorm(x, sc, impl="interpret", blk_rows=32)
        o2 = rmsnorm_reference(x, sc)
        np.testing.assert_allclose(
            o1.astype(jnp.float32), o2.astype(jnp.float32), **tol(dt)
        )

    def test_matches_model_layer(self):
        from repro.models.layers import rms_norm

        x = jax.random.normal(KEY, (4, 16, 128), jnp.float32)
        sc = jax.random.normal(jax.random.PRNGKey(2), (128,)) * 0.1
        np.testing.assert_allclose(
            rmsnorm(x, sc, impl="interpret"), rms_norm(x, sc, 1e-6), rtol=1e-5, atol=1e-5
        )


class TestKernelsInsideModel:
    def test_flash_attention_agrees_with_model_attention(self):
        """The kernel path must agree with models.attention's chunked XLA path."""
        from repro.configs import get_config
        from repro.models import attention as A
        from repro.models.params import init_params

        cfg = get_config("gemma2_9b").reduced(
            seq_chunk=16, num_heads=4, num_kv_heads=2, head_dim=32, attn_softcap=50.0
        )
        p = init_params(A.attn_template(cfg), KEY, jnp.float32)
        B, S = 2, 64
        x = jax.random.normal(KEY, (B, S, cfg.d_model), jnp.float32) * 0.3
        y_model = A.attn_forward(p, x, cfg, causal=True, local=True)
        # reproduce with the kernel: project, rope, call flash, project out
        from repro.models.layers import rope_apply

        K, hd, H = cfg.num_kv_heads, cfg.resolved_head_dim, cfg.num_heads
        q = (x @ p["wq"]).reshape(B, S, H, hd)
        k = (x @ p["wk"]).reshape(B, S, K, hd)
        v = (x @ p["wv"]).reshape(B, S, K, hd)
        pos = jnp.arange(S, dtype=jnp.int32)
        q = rope_apply(q, pos, cfg.rope_theta)
        k = rope_apply(k, pos, cfg.rope_theta)
        o = flash_attention(
            q, k, v, causal=True, window=cfg.window_size, softcap=cfg.attn_softcap,
            impl="interpret", blk_q=32, blk_k=32,
        )
        y_kernel = o.reshape(B, S, H * hd) @ p["wo"]
        np.testing.assert_allclose(y_kernel, y_model, rtol=2e-4, atol=2e-4)
