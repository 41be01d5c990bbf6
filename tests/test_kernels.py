"""Pallas kernels vs their pure-jnp oracles (interpret=True on CPU),
swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import HealthCheck, given, settings, strategies as st

from repro.kernels.flash_attention import attention_ref, flash_attention
from repro.kernels.fused_update import (fused_apply_flat,
                                        fused_apply_flat_ref,
                                        fused_update_flat,
                                        fused_update_flat_ref,
                                        fused_weighted_apply_pallas,
                                        clamp_block_rows, kernel_interpret,
                                        resolve_kernel_mode)
from repro.kernels.fused_update.kernel import LANES
from repro.kernels.fused_update.ops import (DEFAULT_BLOCK_ROWS,
                                            MIN_BLOCK_ROWS,
                                            fused_momentum_gap_update_pallas)
from repro.kernels.ssd_scan import ssd_chunked_pallas, ssd_chunked_ref
from repro.models.ssm import ssd_chunked
from repro.optim.gap import fused_momentum_gap_update, fused_weighted_apply


class TestFusedUpdate:
    @pytest.mark.parametrize("n", [1, 100, 4096, 128 * 128 + 17, 777_777])
    @pytest.mark.parametrize("dtype", [jnp.float32])
    def test_matches_ref(self, n, dtype):
        k = jax.random.PRNGKey(n)
        t, v, g = (jax.random.normal(kk, (n,), dtype)
                   for kk in jax.random.split(k, 3))
        a = fused_update_flat(t, v, g, 0.01, 0.9, block_rows=128,
                              interpret=True)
        b = fused_update_flat_ref(t, v, g, 0.01, 0.9)
        for x, y in zip(a, b):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=3e-5, atol=3e-5)

    @pytest.mark.parametrize("eta,beta", [(0.1, 0.0), (0.01, 0.9),
                                          (1e-3, 0.99)])
    def test_hyperparam_sweep(self, eta, beta):
        k = jax.random.PRNGKey(0)
        t, v, g = (jax.random.normal(kk, (5000,))
                   for kk in jax.random.split(k, 3))
        a = fused_update_flat(t, v, g, eta, beta, block_rows=128,
                              interpret=True)
        b = fused_update_flat_ref(t, v, g, eta, beta)
        np.testing.assert_allclose(np.asarray(a[0]), np.asarray(b[0]),
                                   rtol=3e-5, atol=3e-5)

    def test_pytree_wrapper_matches_xla_fused(self):
        """kernels.fused_update.ops == optim.gap.fused_momentum_gap_update
        (the paper's Eq. 1 + Eq. 4 in one pass)."""
        k = jax.random.PRNGKey(1)
        ks = jax.random.split(k, 6)
        params = {"a": jax.random.normal(ks[0], (33, 7)),
                  "b": {"c": jax.random.normal(ks[1], (129,))}}
        v = {"a": jax.random.normal(ks[2], (33, 7)),
             "b": {"c": jax.random.normal(ks[3], (129,))}}
        g = {"a": jax.random.normal(ks[4], (33, 7)),
             "b": {"c": jax.random.normal(ks[5], (129,))}}
        p1, v1, gap1 = fused_momentum_gap_update(params, v, g, eta=0.05,
                                                 beta=0.9,
                                                 lag=jnp.int32(3))
        p2, v2, gap2 = fused_momentum_gap_update_pallas(
            params, v, g, eta=0.05, beta=0.9, lag=3, block_rows=128,
            interpret=True)
        for x, y in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=3e-5, atol=3e-5)
        assert float(gap1) == pytest.approx(float(gap2), rel=1e-4)


class TestFusedApply:
    """The server-push apply kernel (mix + momentum + sq-norm) vs its
    pure-jnp oracle."""

    @pytest.mark.parametrize("n", [1, 100, 4096, 128 * 128 + 17, 777_777])
    def test_matches_ref(self, n):
        k = jax.random.PRNGKey(n)
        cur, v, new = (jax.random.normal(kk, (n,))
                       for kk in jax.random.split(k, 3))
        a = fused_apply_flat(cur, v, new, 0.6, 1.0 / 0.01, 0.9,
                             block_rows=128, interpret=True)
        b = fused_apply_flat_ref(cur, v, new, 0.6, 1.0 / 0.01, 0.9)
        for x, y in zip(a, b):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=3e-5, atol=3e-5)

    @pytest.mark.parametrize("w,eta,beta", [
        (1.0, 0.1, 0.0),     # replace degenerates to w=1
        (0.6, 0.01, 0.9),
        (0.05, 1e-3, 0.99),
        (0.0, 0.05, 0.5),    # fully-stale push: model unchanged
    ])
    def test_knob_sweep(self, w, eta, beta):
        k = jax.random.PRNGKey(7)
        cur, v, new = (jax.random.normal(kk, (5000,))
                       for kk in jax.random.split(k, 3))
        a = fused_apply_flat(cur, v, new, w, 1.0 / eta, beta,
                             block_rows=128, interpret=True)
        b = fused_apply_flat_ref(cur, v, new, w, 1.0 / eta, beta)
        for x, y in zip(a, b):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=3e-5, atol=3e-5)

    def test_pytree_wrapper_matches_xla_fused(self):
        """fused_weighted_apply_pallas == optim.gap.fused_weighted_apply
        (the server apply contract) at rtol 1e-6."""
        k = jax.random.PRNGKey(1)
        ks = jax.random.split(k, 6)
        shape = {"a": (33, 7), "b": {"c": (129,)}}
        mk = lambda kk: {"a": jax.random.normal(kk[0], (33, 7)),
                         "b": {"c": jax.random.normal(kk[1], (129,))}}
        params, v, new = (mk(ks[2 * i:2 * i + 2]) for i in range(3))
        p1, v1, n1 = fused_weighted_apply(params, v, new, w=0.4, eta=0.05,
                                          beta=0.9)
        p2, v2, n2 = fused_weighted_apply_pallas(params, v, new, w=0.4,
                                                 eta=0.05, beta=0.9,
                                                 block_rows=128,
                                                 interpret=True)
        for x, y in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-6, atol=1e-7)
        for x, y in zip(jax.tree.leaves(v1), jax.tree.leaves(v2)):
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-6, atol=1e-6)
        assert float(n1) == pytest.approx(float(n2), rel=1e-5)

    def test_padding_contributes_nothing(self):
        """A size straddling a block boundary by one element: the padded
        lanes must add 0 to the norm (mixed/v' padding stays zero)."""
        n = 128 * 128 + 1
        k = jax.random.PRNGKey(n)
        cur, v, new = (jax.random.normal(kk, (n,))
                       for kk in jax.random.split(k, 3))
        _, _, sq = fused_apply_flat(cur, v, new, 0.3, 10.0, 0.9,
                                    block_rows=128, interpret=True)
        _, _, sq_ref = fused_apply_flat_ref(cur, v, new, 0.3, 10.0, 0.9)
        assert float(sq) == pytest.approx(float(sq_ref), rel=1e-5)


class TestBlockRowsClamp:
    """Satellite: block_rows auto-clamp for tiny params + empty guard
    (mirrors the topk k-clamp fix)."""

    def test_tiny_payload_shrinks_block(self):
        # a few hundred params should not pad to a 512 KiB block
        assert clamp_block_rows(300) == MIN_BLOCK_ROWS
        assert clamp_block_rows(LANES * MIN_BLOCK_ROWS) == MIN_BLOCK_ROWS

    def test_large_payload_keeps_requested_block(self):
        n = DEFAULT_BLOCK_ROWS * LANES * 4
        assert clamp_block_rows(n) == DEFAULT_BLOCK_ROWS

    def test_clamp_is_power_of_two_and_bounded(self):
        for n in (1, 7, 129, 1000, 10_000, 65_536, 10 ** 6):
            br = clamp_block_rows(n)
            assert MIN_BLOCK_ROWS <= br <= DEFAULT_BLOCK_ROWS
            assert br & (br - 1) == 0
            # pad waste bounded by one block
            rows = -(-n // LANES)
            padded_rows = -(-rows // br) * br
            assert padded_rows - rows < br or rows < MIN_BLOCK_ROWS

    def test_tiny_update_matches_ref(self):
        """The clamped path produces correct results for sub-block sizes."""
        for n in (1, 5, 129, 1025):
            k = jax.random.PRNGKey(n)
            t, v, g = (jax.random.normal(kk, (n,))
                       for kk in jax.random.split(k, 3))
            a = fused_update_flat(t, v, g, 0.01, 0.9, interpret=True)
            b = fused_update_flat_ref(t, v, g, 0.01, 0.9)
            for x, y in zip(a, b):
                np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                           rtol=3e-5, atol=3e-5)

    def test_empty_arrays_short_circuit(self):
        z = jnp.zeros((0,), jnp.float32)
        t, v, sq = fused_update_flat(z, z, z, 0.01, 0.9, interpret=True)
        assert t.shape == (0,) and v.shape == (0,) and float(sq) == 0.0
        m, v2, sq2 = fused_apply_flat(z, z, z, 0.5, 10.0, 0.9,
                                      interpret=True)
        assert m.shape == (0,) and v2.shape == (0,) and float(sq2) == 0.0

    def test_mode_dispatch(self):
        assert resolve_kernel_mode("pallas") == "pallas"
        assert resolve_kernel_mode("reference") == "reference"
        auto = resolve_kernel_mode("auto")
        on_tpu = jax.default_backend() == "tpu"
        assert auto == ("pallas" if on_tpu else "reference")
        assert kernel_interpret() == (jax.default_backend() == "cpu")
        with pytest.raises(ValueError, match="unknown kernel mode"):
            resolve_kernel_mode("bogus")


class TestFusedKernelProperties:
    """Hypothesis parity suite: both kernels (interpret mode) vs the
    optim/gap oracles over shapes x padding remainders x (eta, beta)."""

    @settings(max_examples=25, deadline=None,
              suppress_health_check=HealthCheck.all())
    @given(rows=st.integers(1, 40), rem=st.integers(0, LANES - 1),
           eta=st.floats(1e-4, 0.5), beta=st.floats(0.0, 0.99),
           seed=st.integers(0, 2 ** 16))
    def test_update_parity(self, rows, rem, eta, beta, seed):
        n = (rows - 1) * LANES + rem + 1   # spans rows, any lane remainder
        k = jax.random.PRNGKey(seed)
        t, v, g = (jax.random.normal(kk, (n,))
                   for kk in jax.random.split(k, 3))
        t2, v2, sq = fused_update_flat(t, v, g, eta, beta, interpret=True)
        tr, vr, sqr = fused_update_flat_ref(t, v, g, eta, beta)
        np.testing.assert_allclose(np.asarray(t2), np.asarray(tr),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(v2), np.asarray(vr),
                                   rtol=1e-6, atol=1e-6)
        assert float(sq) == pytest.approx(float(sqr), rel=1e-5, abs=1e-10)

    @settings(max_examples=25, deadline=None,
              suppress_health_check=HealthCheck.all())
    @given(rows=st.integers(1, 40), rem=st.integers(0, LANES - 1),
           w=st.floats(0.0, 1.0), eta=st.floats(1e-4, 0.5),
           beta=st.floats(0.0, 0.99), seed=st.integers(0, 2 ** 16))
    def test_apply_parity(self, rows, rem, w, eta, beta, seed):
        n = (rows - 1) * LANES + rem + 1   # spans rows, any lane remainder
        k = jax.random.PRNGKey(seed)
        cur, v, new = (jax.random.normal(kk, (n,))
                       for kk in jax.random.split(k, 3))
        inv_eta = 1.0 / eta
        m2, v2, sq = fused_apply_flat(cur, v, new, w, inv_eta, beta,
                                      interpret=True)
        mr, vr, sqr = fused_apply_flat_ref(cur, v, new, w, inv_eta, beta)
        np.testing.assert_allclose(np.asarray(m2), np.asarray(mr),
                                   rtol=1e-6, atol=1e-6)
        # v' suffers catastrophic cancellation scaled by inv_eta: a few
        # ulps of the LARGEST intermediate, not of the (near-zero) result
        # — so the absolute floor tracks the array scale
        v_scale = float(np.max(np.abs(np.asarray(vr)))) + 1.0
        np.testing.assert_allclose(np.asarray(v2), np.asarray(vr),
                                   rtol=1e-6, atol=1e-6 * v_scale)
        assert float(sq) == pytest.approx(float(sqr), rel=1e-5, abs=1e-10)


class TestFlashAttention:
    @pytest.mark.parametrize("B,H,KV,S,d", [
        (1, 4, 4, 256, 64),      # MHA
        (2, 8, 2, 256, 128),     # GQA 4:1
        (1, 4, 2, 384, 64),      # non-pow2 blocks count
        (1, 2, 1, 512, 32),      # MQA
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_causal_matches_ref(self, B, H, KV, S, d, dtype):
        k0 = jax.random.PRNGKey(B * H * S)
        ks = jax.random.split(k0, 3)
        q = jax.random.normal(ks[0], (B, H, S, d), dtype)
        k = jax.random.normal(ks[1], (B, KV, S, d), dtype)
        v = jax.random.normal(ks[2], (B, KV, S, d), dtype)
        out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                              interpret=True)
        ref = attention_ref(q, k, v, causal=True)
        tol = 2e-5 if dtype == jnp.float32 else 2e-2
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=tol, atol=tol)

    def test_non_causal(self):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (1, 2, 256, 64))
        k = jax.random.normal(ks[1], (1, 2, 256, 64))
        v = jax.random.normal(ks[2], (1, 2, 256, 64))
        out = flash_attention(q, k, v, causal=False, block_q=128,
                              block_k=128, interpret=True)
        ref = attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_matches_model_sdpa(self):
        """Kernel output == the model's XLA einsum attention (its oracle in
        the model stack)."""
        from repro.models.attention import _sdpa, causal_mask
        from repro.models.config import ModelConfig
        cfg = ModelConfig(name="t", family="dense", num_layers=1,
                          d_model=64, num_heads=4, num_kv_heads=2,
                          d_ff=128, vocab_size=64, head_dim=16)
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        B, S = 2, 256
        q = jax.random.normal(ks[0], (B, S, 4, 16))
        k = jax.random.normal(ks[1], (B, S, 2, 16))
        v = jax.random.normal(ks[2], (B, S, 2, 16))
        ref = _sdpa(q, k, v, causal_mask(S, S), cfg)
        out = flash_attention(q.transpose(0, 2, 1, 3),
                              k.transpose(0, 2, 1, 3),
                              v.transpose(0, 2, 1, 3), causal=True,
                              block_q=128, block_k=128, interpret=True)
        np.testing.assert_allclose(np.asarray(out.transpose(0, 2, 1, 3)),
                                   np.asarray(ref), rtol=2e-5, atol=2e-5)


class TestSSDScan:
    @pytest.mark.parametrize("B,S,nh,ph,s,chunk", [
        (2, 64, 4, 16, 16, 16),
        (1, 128, 2, 32, 64, 32),
        (2, 96, 3, 8, 24, 32),
        (1, 64, 8, 64, 128, 16),
    ])
    def test_matches_naive_recurrence(self, B, S, nh, ph, s, chunk):
        k0 = jax.random.PRNGKey(B + S + nh)
        ks = jax.random.split(k0, 5)
        X = jax.random.normal(ks[0], (B, S, nh, ph))
        dtv = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
        A = -jnp.exp(0.3 * jax.random.normal(ks[2], (nh,)))
        Bh = 0.5 * jax.random.normal(ks[3], (B, S, nh, s))
        Ch = 0.5 * jax.random.normal(ks[4], (B, S, nh, s))
        yr, fr = ssd_chunked_ref(X, dtv, A, Bh, Ch)
        yp, fp = ssd_chunked_pallas(X, dtv, A, Bh, Ch, chunk, interpret=True)
        np.testing.assert_allclose(np.asarray(yp), np.asarray(yr),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(fp), np.asarray(fr),
                                   rtol=1e-4, atol=1e-4)

    def test_model_xla_path_matches_naive(self):
        """models.ssm.ssd_chunked (the XLA default) == naive recurrence."""
        ks = jax.random.split(jax.random.PRNGKey(9), 5)
        X = jax.random.normal(ks[0], (2, 64, 4, 16))
        dtv = jax.nn.softplus(jax.random.normal(ks[1], (2, 64, 4)))
        A = -jnp.exp(0.3 * jax.random.normal(ks[2], (4,)))
        Bh = 0.5 * jax.random.normal(ks[3], (2, 64, 4, 16))
        Ch = 0.5 * jax.random.normal(ks[4], (2, 64, 4, 16))
        yr, fr = ssd_chunked_ref(X, dtv, A, Bh, Ch)
        yx, fx = ssd_chunked(X, dtv, A, Bh, Ch, 16)
        np.testing.assert_allclose(np.asarray(yx), np.asarray(yr),
                                   rtol=1e-4, atol=1e-4)

    def test_init_state_continuation(self):
        """Splitting a sequence across two calls with state carry == one call
        (prefill-continuation correctness)."""
        ks = jax.random.split(jax.random.PRNGKey(4), 5)
        B, S, nh, ph, s, chunk = 1, 64, 2, 8, 16, 16
        X = jax.random.normal(ks[0], (B, S, nh, ph))
        dtv = jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
        A = -jnp.exp(0.3 * jax.random.normal(ks[2], (nh,)))
        Bh = 0.5 * jax.random.normal(ks[3], (B, S, nh, s))
        Ch = 0.5 * jax.random.normal(ks[4], (B, S, nh, s))
        y_all, f_all = ssd_chunked_pallas(X, dtv, A, Bh, Ch, chunk,
                                          interpret=True)
        h = S // 2
        y1, f1 = ssd_chunked_pallas(X[:, :h], dtv[:, :h], A, Bh[:, :h],
                                    Ch[:, :h], chunk, interpret=True)
        y2, f2 = ssd_chunked_pallas(X[:, h:], dtv[:, h:], A, Bh[:, h:],
                                    Ch[:, h:], chunk, init_state=f1,
                                    interpret=True)
        np.testing.assert_allclose(np.asarray(y2), np.asarray(y_all[:, h:]),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(f2), np.asarray(f_all),
                                   rtol=1e-4, atol=1e-4)
