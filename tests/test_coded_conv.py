"""Coded execution == uncoded execution (the paper's §II-B.4 exactness
claim), for conv and the GEMM adaptation (the mesh backend's are in
tests/test_backend_equiv.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    ConvSpec,
    MDSCode,
    coded_conv2d,
    coded_matmul,
    conv2d,
    get_scheme,
    plan_width_split,
    scheme_names,
)


def _rand_conv(key, spec: ConvSpec):
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (spec.batch, spec.c_in, spec.h_in, spec.w_in),
                          jnp.float32)
    w = jax.random.normal(kw, (spec.c_out, spec.c_in, spec.kernel, spec.kernel),
                          jnp.float32) * (spec.c_in * spec.kernel ** 2) ** -0.5
    return x, w


CASES = [
    # (c_in, c_out, h_in, w_in, kernel, stride, n, k)
    (8, 16, 14, 16, 3, 1, 5, 3),
    (4, 8, 9, 23, 3, 2, 6, 4),   # non-divisible W_O -> master remainder
    (3, 7, 12, 12, 1, 1, 4, 2),  # 1x1 conv
    (8, 8, 20, 30, 5, 1, 10, 7),
    (2, 4, 7, 64, 7, 2, 16, 12),  # pod-width worker pool
]


@pytest.mark.parametrize("ci,co,h,w,ker,s,n,k", CASES)
def test_coded_conv_exact(ci, co, h, w, ker, s, n, k):
    spec = ConvSpec(c_in=ci, c_out=co, h_in=h, w_in=w, kernel=ker, stride=s)
    code = MDSCode(n, k)
    x, wts = _rand_conv(jax.random.PRNGKey(n * 17 + k), spec)
    ref = conv2d(x, wts, s)
    rng = np.random.default_rng(0)
    for _ in range(3):
        subset = sorted(rng.choice(n, size=k, replace=False).tolist())
        out = coded_conv2d(x, wts, code, spec, subset)
        assert out.shape == ref.shape
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=5e-3, atol=5e-3)


@given(n=st.integers(2, 10), data=st.data())
@settings(max_examples=15, deadline=None)
def test_coded_matmul_any_subset(n, data):
    k = data.draw(st.integers(1, n))
    t = data.draw(st.integers(k, 64))
    code = MDSCode(n, k)
    key = jax.random.PRNGKey(n * 31 + k)
    kx, kw = jax.random.split(key)
    x = jax.random.normal(kx, (t, 12), jnp.float32)
    w = jax.random.normal(kw, (12, 9), jnp.float32)
    rng = np.random.default_rng(k)
    subset = sorted(rng.choice(n, size=k, replace=False).tolist())
    out = coded_matmul(x, w, code, subset)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x @ w),
                               rtol=5e-3, atol=5e-3)


def test_straggler_insensitivity():
    """Any k-subset gives the SAME result — stragglers don't change the
    output, only who provides it (§II-B.4)."""
    spec = ConvSpec(c_in=4, c_out=4, h_in=10, w_in=18, kernel=3, stride=1)
    code = MDSCode(6, 4)
    x, w = _rand_conv(jax.random.PRNGKey(3), spec)
    outs = [coded_conv2d(x, w, code, spec, s)
            for s in ([0, 1, 2, 3], [2, 3, 4, 5], [0, 2, 4, 5])]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(o), np.asarray(outs[0]),
                                   rtol=5e-3, atol=5e-3)


def _transfer_case(name):
    cls = get_scheme(name)
    return cls.make(5, 3) if name in ("mds", "lt") else cls.make(4)


@pytest.mark.parametrize("path", ["functional", "executor"])
@pytest.mark.parametrize("op", ["conv2d", "matmul"])
@pytest.mark.parametrize("name", scheme_names())
def test_a_warmed_coded_op_transfers_nothing(name, op, path):
    """A warmed ``coded_conv2d``/``coded_matmul`` call's master-side encode
    and decode move nothing from the host: the windows are static and the
    coding matrices stay on the device.  Widths and token counts divide by
    k, so no remainder (master-local work, not coding) is sliced; worker
    threads run outside the guard, which holds the calling thread."""
    from repro.dist import CodedExecutor, DeterministicDelay, FakeClock

    code = _transfer_case(name)
    if op == "conv2d":
        spec = ConvSpec(c_in=3, c_out=4, h_in=6, w_in=14, kernel=3)
        x, w = _rand_conv(jax.random.PRNGKey(0), spec)
        call = lambda ex: coded_conv2d(x, w, code, spec, executor=ex)
        ref = conv2d(x, w, 1)
    else:
        x = jax.random.normal(jax.random.PRNGKey(0), (12, 8))
        w = jax.random.normal(jax.random.PRNGKey(1), (8, 5))
        call = lambda ex: coded_matmul(x, w, code, executor=ex)
        ref = x @ w
    ex = (CodedExecutor(code.n, clock=FakeClock(),
                        delay_model=DeterministicDelay(1.0))
          if path == "executor" else None)
    try:
        jax.block_until_ready(call(ex))
        with jax.transfer_guard_host_to_device("disallow"):
            y = jax.block_until_ready(call(ex))
    finally:
        if ex is not None:
            ex.close()
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=5e-3, atol=5e-3)
