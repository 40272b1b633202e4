"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.coding import vandermonde_generator
from repro.kernels.mds_encode import skinny_gemm_pallas
from repro.kernels.ops import conv2d_subtask, mds_decode, mds_encode, ssd_chunk
from repro.kernels.ref import (
    conv2d_ref,
    mds_decode_ref,
    mds_encode_ref,
    ssd_chunk_ref,
)

TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


class TestMDSEncodeKernel:
    @pytest.mark.parametrize("n,k", [(3, 2), (10, 6), (16, 12), (16, 16)])
    @pytest.mark.parametrize("F", [64, 512, 1000, 4097])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, n, k, F, dtype):
        G = jnp.asarray(vandermonde_generator(n, k), dtype)
        x = (jax.random.normal(jax.random.PRNGKey(F + n), (k, F), jnp.float32)
             .astype(dtype))
        got = mds_encode(G, x, interpret=True)
        want = mds_encode_ref(G, x)
        assert got.shape == (n, F)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            **TOL[dtype])


class TestMDSDecodeKernel:
    @pytest.mark.parametrize("n,k", [(3, 2), (10, 6), (16, 12), (16, 16)])
    @pytest.mark.parametrize("F", [64, 512, 1000, 4097])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, n, k, F, dtype):
        # D = G_S^{-1} for the first-k subset: the real decode matrix shape
        G = vandermonde_generator(n, k)
        D = jnp.asarray(np.linalg.inv(G[:k]), dtype)
        y = (jax.random.normal(jax.random.PRNGKey(F + n), (k, F), jnp.float32)
             .astype(dtype))
        got = mds_decode(D, y, interpret=True)
        want = mds_decode_ref(D, y)
        assert got.shape == (k, F)
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            **TOL[dtype])

    def test_encode_then_decode_roundtrip(self):
        """Kernel pipeline = the paper's eq. 3 -> eq. 4 identity."""
        n, k, F = 10, 6, 777
        G = vandermonde_generator(n, k)
        x = jax.random.normal(jax.random.PRNGKey(0), (k, F), jnp.float32)
        coded = mds_encode(jnp.asarray(G, jnp.float32), x, interpret=True)
        subset = [0, 2, 3, 5, 7, 9]
        D = jnp.asarray(np.linalg.inv(G[subset]), jnp.float32)
        back = mds_decode(D, coded[jnp.asarray(subset)], interpret=True)
        np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                                   rtol=2e-3, atol=2e-3)


class TestPieceGemmKernel:
    """The tiled GEMM at shapes that take each tiling path: contraction
    cut into aligned blocks or padded, rows cut or padded, columns padded."""

    @pytest.mark.parametrize("m,b,f", [
        (3, 2048, 700),     # two 1024-deep contraction blocks, F padded
        (300, 256, 512),    # no aligned row block divides 300: rows padded
        (320, 1100, 600),   # 160-row blocks; contraction padded to 2048
        (171, 2048, 512),   # a prefill piece: one full-extent row block
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_float64_product(self, m, b, f, dtype):
        rng = np.random.default_rng(m + b + f)
        a = jnp.asarray(rng.standard_normal((m, b)), jnp.float32)
        x = jnp.asarray(rng.standard_normal((b, f)), dtype)
        got = skinny_gemm_pallas(a, x, interpret=True)
        want = (np.asarray(a.astype(dtype), np.float64)
                @ np.asarray(x, np.float64))
        assert got.shape == (m, f) and got.dtype == dtype
        # f32: accumulation error only; bf16: the output's own rounding
        bound = (2e-6 if dtype == jnp.float32 else 2 ** -8) * np.abs(want).max()
        assert np.abs(np.asarray(got, np.float64) - want).max() <= bound


class TestConv2dKernel:
    @pytest.mark.parametrize("ci,co,h,w,K,s", [
        (3, 8, 12, 12, 3, 1),
        (16, 32, 14, 20, 3, 1),
        (8, 7, 11, 17, 5, 2),    # c_out not a block multiple
        (4, 64, 9, 9, 1, 1),     # 1x1
        (32, 16, 8, 30, 3, 2),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_ref(self, ci, co, h, w, K, s, dtype):
        kx, kw = jax.random.split(jax.random.PRNGKey(ci * co))
        x = (jax.random.normal(kx, (ci, h, w), jnp.float32) * 0.5).astype(dtype)
        wts = (jax.random.normal(kw, (co, ci, K, K), jnp.float32)
               * (ci * K * K) ** -0.5).astype(dtype)
        got = conv2d_subtask(x, wts, s, interpret=True)
        want = conv2d_ref(x, wts, s)
        assert got.shape == want.shape
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(want, np.float32),
            **TOL[dtype])

    def test_worker_subtask_equals_coded_pipeline_piece(self):
        """The kernel computes exactly one CoCoI worker's subtask."""
        from repro.core.splitting import ConvSpec, plan_width_split

        spec = ConvSpec(c_in=8, c_out=16, h_in=12, w_in=26, kernel=3, stride=1)
        plan = plan_width_split(spec, 3)
        x = jax.random.normal(jax.random.PRNGKey(0),
                              (8, spec.h_in, spec.w_in), jnp.float32)
        w = jax.random.normal(jax.random.PRNGKey(1), (16, 8, 3, 3),
                              jnp.float32) * 0.1
        p = plan.parts[1]
        got = conv2d_subtask(x[:, :, p.a_i:p.b_i], w, 1, interpret=True)
        want = conv2d_ref(x, w, 1)[:, :, p.a_o:p.b_o]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


class TestSSDKernel:
    @pytest.mark.parametrize("B,L,H,P,N", [
        (1, 8, 2, 4, 4),
        (2, 16, 4, 8, 16),
        (3, 32, 8, 16, 8),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_sequential_scan(self, B, L, H, P, N, dtype):
        keys = jax.random.split(jax.random.PRNGKey(L * H), 5)
        x = (jax.random.normal(keys[0], (B, L, H, P), jnp.float32)).astype(dtype)
        dt = jax.nn.softplus(jax.random.normal(keys[1], (B, L, H), jnp.float32))
        A = -jnp.exp(jax.random.normal(keys[2], (H,), jnp.float32) * 0.3)
        Bm = (jax.random.normal(keys[3], (B, L, N), jnp.float32)).astype(dtype)
        Cm = (jax.random.normal(keys[4], (B, L, N), jnp.float32)).astype(dtype)
        h0 = jnp.zeros((B, H, P, N), jnp.float32)
        y, h1 = ssd_chunk(x, dt.astype(dtype), A, Bm, Cm, h0, interpret=True)
        y_ref = jnp.stack([
            ssd_chunk_ref(x[b], dt[b], A, Bm[b], Cm[b], h0[b])[0]
            for b in range(B)])
        h_ref = jnp.stack([
            ssd_chunk_ref(x[b], dt[b], A, Bm[b], Cm[b], h0[b])[1]
            for b in range(B)])
        tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else dict(
            rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(y, np.float32),
                                   np.asarray(y_ref, np.float32), **tol)
        np.testing.assert_allclose(np.asarray(h1), np.asarray(h_ref), **tol)

    def test_nonzero_initial_state(self):
        B, L, H, P, N = 1, 8, 2, 4, 4
        keys = jax.random.split(jax.random.PRNGKey(9), 6)
        x = jax.random.normal(keys[0], (B, L, H, P), jnp.float32)
        dt = jax.nn.softplus(jax.random.normal(keys[1], (B, L, H), jnp.float32))
        A = -jnp.exp(jax.random.normal(keys[2], (H,), jnp.float32) * 0.3)
        Bm = jax.random.normal(keys[3], (B, L, N), jnp.float32)
        Cm = jax.random.normal(keys[4], (B, L, N), jnp.float32)
        h0 = jax.random.normal(keys[5], (B, H, P, N), jnp.float32)
        y, h1 = ssd_chunk(x, dt, A, Bm, Cm, h0, interpret=True)
        y_ref, h_ref = ssd_chunk_ref(x[0], dt[0], A, Bm[0], Cm[0], h0[0])
        np.testing.assert_allclose(np.asarray(y[0]), np.asarray(y_ref),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(h1[0]), np.asarray(h_ref),
                                   rtol=1e-4, atol=1e-4)
