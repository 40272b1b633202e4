"""ConvNeXt on the coded CNN path: grouped (depthwise) convs in the core,
biases after decode, the erf GELU, the compiled plan at 224 px, and the
master-side spans ``model.norm`` and ``model.dwconv``.

The model runs at a small size (32 px, widths 16-128, depths 1, 1, 2, 1);
``SMALL_CNN_PARAMS`` makes nearly every layer type-1 there, depthwise
convs of stages 1-2 included, so that the coded forward codes them."""
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.coded_conv import ACTIVATIONS, conv2d, run_segment
from repro.core.latency import SystemParams
from repro.core.netplan import LayerInfo, SegmentStep, compile_plan
from repro.core.planner import _sizes_continuous, straggling_index_R
from repro.core.schemes import commutes_elementwise, get_scheme
from repro.core.splitting import ConvSpec
from repro.dist import CodedExecutor, FaultPlan, RealClock
from repro.models import cnn
from repro.telemetry import trace
from repro.telemetry.trace import TraceRecorder

WIDTHS, DEPTHS, IMAGE = (16, 32, 64, 128), (1, 1, 2, 1), 32
N = 4


def _dense(spec: ConvSpec) -> ConvSpec:
    return ConvSpec(c_in=spec.c_in, c_out=spec.c_out, h_in=spec.h_in,
                    w_in=spec.w_in, kernel=spec.kernel, stride=spec.stride,
                    batch=spec.batch)


# ---------------------------------------------------------------------------
# grouped convs in the core
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("c, groups, batch", [(512, 512, 1), (64, 8, 2),
                                              (128, 1, 1)])
def test_grouped_flops_are_the_dense_count_over_the_groups(c, groups, batch):
    spec = ConvSpec(c_in=c, c_out=c, h_in=20, w_in=20, kernel=7,
                    batch=batch, groups=groups)
    dense = _dense(spec)
    assert spec.subtask_flops(5) * groups == dense.subtask_flops(5)
    assert spec.subtask_flops(spec.w_out) == \
        2 * batch * c * spec.h_out * spec.w_out * (c // groups) * 49
    # bytes do not change with the groups
    assert spec.recv_bytes(9) == dense.recv_bytes(9)
    assert spec.send_bytes(5) == dense.send_bytes(5)
    assert _sizes_continuous(spec, 4, 2.0).n_cmp * groups == pytest.approx(
        _sizes_continuous(dense, 4, 2.0).n_cmp)


def test_groups_must_divide_the_channels():
    with pytest.raises(ValueError, match="groups"):
        ConvSpec(c_in=6, c_out=6, h_in=8, w_in=8, kernel=3, groups=4)
    with pytest.raises(ValueError, match="groups"):
        ConvSpec(c_in=6, c_out=6, h_in=8, w_in=8, kernel=3, groups=0)


def test_depthwise_intensity_is_low_and_straggling_index_sees_groups():
    dw = ConvSpec(c_in=512, c_out=512, h_in=20, w_in=20, kernel=7,
                  groups=512)
    assert not cnn.is_type1(dw)
    assert cnn.is_type1(_dense(dw))  # as a dense conv it would be coded
    p = SystemParams()
    assert straggling_index_R(dw, p) != straggling_index_R(_dense(dw), p)


def test_conv2d_infers_the_groups_from_the_weight():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 8, 9, 9))
    w = jax.random.normal(jax.random.PRNGKey(1), (8, 2, 3, 3))
    ref = jax.lax.conv_general_dilated(
        x, w, (1, 1), "VALID", dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=4)
    np.testing.assert_array_equal(np.asarray(conv2d(x, w)), np.asarray(ref))


def test_gelu_is_the_erf_form():
    x = np.linspace(-6.0, 6.0, 2001, dtype=np.float32)
    erf = np.array([0.5 * v * (1.0 + math.erf(v / math.sqrt(2.0)))
                    for v in x.astype(np.float64)])
    np.testing.assert_allclose(np.asarray(ACTIVATIONS["gelu"](x)), erf,
                               rtol=1e-7, atol=1e-7)
    # the tanh approximation departs by far more
    assert np.max(np.abs(np.asarray(jax.nn.gelu(x)) - erf)) > 1e-4


def _scheme(name):
    return get_scheme(name).make(4, 2) if name in ("mds", "lt") \
        else get_scheme(name)(4)


def _depthwise(key, c=6, size=12, kernel=7, pad=3):
    kx, kw, kb = jax.random.split(key, 3)
    spec = ConvSpec(c_in=c, c_out=c, h_in=size + 2 * pad,
                    w_in=size + 2 * pad, kernel=kernel, groups=c)
    x = jax.random.normal(kx, (2, c, size + 2 * pad, size + 2 * pad))
    w = jax.random.normal(kw, (c, 1, kernel, kernel)) / kernel
    return spec, x, w, jax.random.normal(kb, (c,))


@pytest.mark.parametrize("scheme_name", ["mds", "replication", "uncoded"])
def test_a_coded_depthwise_layer_is_the_grouped_conv(scheme_name):
    spec, x, w, _ = _depthwise(jax.random.PRNGKey(2))
    ref = jax.lax.conv_general_dilated(
        x, w, (1, 1), "VALID", dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=spec.c_in, precision="highest")
    out = run_segment(x, [w], _scheme(scheme_name), [spec], [3], [None])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("scheme_name", ["replication", "uncoded"])
def test_interior_bias_runs_in_the_chain_under_selection(scheme_name):
    """A depthwise conv, its bias and GELU, then a 1x1 conv, fused into
    one segment: the interior bias and activation run in every piece."""
    dw, x, w, b = _depthwise(jax.random.PRNGKey(3))
    pw = ConvSpec(c_in=6, c_out=10, h_in=12, w_in=12, kernel=1)
    w2 = jax.random.normal(jax.random.PRNGKey(4), (10, 6, 1, 1))
    b2 = jax.random.normal(jax.random.PRNGKey(5), (10,))
    scheme = _scheme(scheme_name)
    assert commutes_elementwise(scheme)
    out = run_segment(x, [w, w2], scheme, [dw, pw], [3, 0], ["gelu", None],
                      biases=[b, b2])
    h = ACTIVATIONS["gelu"](conv2d(x, w) + b[:, None, None])
    ref = conv2d(h, w2)   # the last bias is the master's, after decode
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_a_linear_mix_refuses_an_interior_bias():
    dw, x, w, b = _depthwise(jax.random.PRNGKey(3))
    pw = ConvSpec(c_in=6, c_out=10, h_in=12, w_in=12, kernel=1)
    w2 = jnp.ones((10, 6, 1, 1))
    with pytest.raises(ValueError, match="linear mix"):
        run_segment(x, [w, w2], get_scheme("mds").make(4, 2), [dw, pw],
                    [3, 0], [None, None], biases=[b, None])


@pytest.mark.parametrize("bias", [False, True])
def test_a_bias_is_a_decode_point_for_linear_mixes(bias):
    """Two 1x1 convs with no activation between them: one segment under a
    linear mix, two when the first has a bias."""
    spec = ConvSpec(c_in=64, c_out=64, h_in=16, w_in=16, kernel=1)
    pair = [LayerInfo(f"c{j}", spec, True, act=None, pad=0,
                      bias=bias and j == 0) for j in range(2)]
    plan = compile_plan(pair, N, cnn.SMALL_CNN_PARAMS, "mds", dp=False)
    assert [s.depth for s in plan.segments] == ([1, 1] if bias else [2])


def test_the_pointwise_pair_fuses_under_selection():
    layers = cnn.convnext_conv_specs(IMAGE, WIDTHS, DEPTHS,
                                     cnn.SMALL_CNN_PARAMS)
    pair = [li for li in layers if li.name in ("s1b0pw1", "s1b0pw2")]
    for scheme_name, depths in (("mds", [1, 1]), ("replication", [2])):
        plan = compile_plan(pair, N, cnn.SMALL_CNN_PARAMS, scheme_name,
                            dp=False)
        assert [s.depth for s in plan.segments] == depths


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

def test_convnext_b_plan_at_224():
    """61 coded segments under the default SystemParams, each mds(4, 3) of
    one layer: every pointwise conv of stages 3-4 and the stage 3 -> 4
    downsample.  The stem, the depthwise convs, the stage-1/2 pointwise
    convs and the first two downsamples stay on the master."""
    layers = cnn.convnext_conv_specs(224)
    assert len(layers) == 1 + 3 + 3 * sum(cnn.CONVNEXT_B["depths"])
    coded = {li.name for li in layers if li.type1}
    assert coded == {"ds4"} | {f"s{s}b{b}pw{j}" for s, d in ((3, 27), (4, 3))
                               for b in range(d) for j in (1, 2)}
    # the mini plans the forward runs: each conv alone, each block's
    # pointwise pair together
    groups = [[li] for li in layers if not li.name.endswith("pw2")]
    for g, li in zip(groups, [li for li in layers
                               if not li.name.endswith("pw2")]):
        if li.name.endswith("pw1"):
            g.append(layers[layers.index(li) + 1])
    segments = [(s.n, s.k, s.depth, [li.name for li in p.layers[s.start:
                                                               s.stop]])
                for p in (compile_plan(g, N, SystemParams(), "mds")
                          for g in groups)
                for s in p.segments]
    assert len(segments) == 61
    assert {(n, k, d) for n, k, d, _ in segments} == {(4, 3, 1)}
    assert {names[0] for *_, names in segments} == coded


@pytest.fixture(scope="module")
def model():
    params = cnn.init_convnext(jax.random.PRNGKey(0), 10, IMAGE, WIDTHS,
                               DEPTHS)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, IMAGE, IMAGE))
    return params, x, cnn.convnext_forward(params, x)


def _rel(a, b):
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


@pytest.mark.parametrize("scheme_name", ["mds", "replication"])
def test_coded_forward_matches_the_uncoded(model, scheme_name):
    params, x, ref = model
    out = cnn.convnext_forward(params, x, scheme=scheme_name, n=N,
                               sys_params=cnn.SMALL_CNN_PARAMS)
    assert out.shape == (2, 10)
    assert _rel(out, ref) < 2e-5


def _traced_forward(params, x):
    with CodedExecutor(N, clock=RealClock(),
                       fault_plan=FaultPlan(straggler={1: 10.0})) as ex:
        fwd = dict(scheme="mds", n=N, sys_params=cnn.SMALL_CNN_PARAMS,
                   executor=ex)
        cnn.convnext_forward(params, x, **fwd).block_until_ready()
        rec = TraceRecorder()
        with trace.recording(rec):
            y = cnn.convnext_forward(params, x, **fwd)
            y.block_until_ready()
    time.sleep(0.05)  # a cancelled straggler folds its delay on waking
    return y, rec, trace.request_log.last(1)[0]


def test_pooled_forward_with_a_straggler_and_its_spans(model):
    params, x, ref = model
    y, rec, log = _traced_forward(params, x)
    assert _rel(y, ref) < 2e-5
    # every LayerNorm: stem, three downsamples, one a block, the head
    assert log.n("model.norm") == 1 + 3 + sum(DEPTHS) + 1
    layers = cnn.convnext_conv_specs(IMAGE, WIDTHS, DEPTHS,
                                     cnn.SMALL_CNN_PARAMS)
    local_dw = [li for li in layers if li.name.endswith("dw")
                and not li.type1]
    assert 0 < len(local_dw) < sum(DEPTHS)  # stage 1-2's are coded here
    assert log.n("model.dwconv") == len(local_dw)
    norms = rec.by_name("model.norm")
    assert sorted(s.args["channels"] for s in norms) == sorted(
        [16, 16, 32, 64, 16, 32, 64, 64, 128, 128])
    # siblings of model.local under the forward: the parts add up to it
    by_id = {s.args["span"]: s for s in rec.spans if "span" in s.args}
    for s in norms + rec.by_name("model.dwconv"):
        assert by_id[s.parent].name == "model.forward"
    parts = ("model.encode", "model.decode", "model.local", "model.remainder",
             "backend.run", "model.norm", "model.dwconv")
    total = sum(log.ms(p) for p in parts) + log.self_ms("model.forward")
    assert total == pytest.approx(log.ms("model.forward"), rel=1e-6)


def test_plan_codes_the_depthwise_layers_only_where_told(model):
    params, x, _ = model
    segs = 0
    layers = cnn.convnext_conv_specs(IMAGE, WIDTHS, DEPTHS,
                                     cnn.SMALL_CNN_PARAMS)
    for li in layers:
        if li.name.endswith("dw") and li.type1:
            plan = cnn._resolve_plan([li], None, "mds", None, N,
                                     cnn.SMALL_CNN_PARAMS)
            segs += sum(isinstance(s, SegmentStep) for s in plan.steps)
    assert segs == 2
    assert not any(li.type1 for li in cnn.convnext_conv_specs(
        IMAGE, WIDTHS, DEPTHS) if li.name.endswith("dw"))
