"""CNN workloads: the paper's (§V, App. A: VGG16, ResNet18, a small CNN)
and ConvNeXt (Liu et al., arXiv:2201.03545).

Three artefacts per network:

* ``*_conv_specs`` — the per-layer :class:`~repro.core.netplan.LayerInfo`
  list (padded-input geometry + activation/pad/pool structure) used by the
  latency model / planner / simulator / segment compiler, with the paper's
  type-1 / type-2 classification (App. A: a layer is type-1 iff
  distributed execution can accelerate it — VGG's early low-intensity
  convs and ResNet's 1x1 downsamples come out type-2).
* an init function building runnable conv + head parameters at any image
  size.
* a runnable forward whose conv stack executes through a compiled
  :class:`~repro.core.netplan.NetPlan` — coded *segments* with one
  encode at entry and one decode at exit (DESIGN.md §9) — under any
  registered coding scheme, functionally or on a ``repro.dist`` worker
  pool.

The type-1 threshold is derived from :class:`SystemParams` (the
compute-to-bandwidth cost ratio), not hard-coded: see :func:`is_type1`.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, List, Sequence

import jax
import jax.numpy as jnp

from ..core.coded_conv import ACTIVATIONS, add_bias, conv2d, run_segment
from ..core.latency import SystemParams
from ..core.netplan import (LayerInfo, LocalStep, NetPlan, SegmentStep,
                            compile_plan)
from ..core.schemes import CodingScheme, get_scheme
from ..core.splitting import ConvSpec
from ..telemetry.trace import span

__all__ = ["LayerInfo", "vgg16_conv_specs", "resnet18_conv_specs",
           "is_type1", "type1_threshold", "maxpool2d", "forward_plan",
           "init_cnn", "cnn_head_features",
           "init_small_cnn", "small_cnn_forward", "small_cnn_conv_specs",
           "small_cnn_layers", "SMALL_CNN_PARAMS",
           "init_vgg16", "vgg16_forward",
           "init_resnet18", "resnet18_forward",
           "convnext_conv_specs", "init_convnext", "convnext_forward"]


# ---------------------------------------------------------------------------
# type-1 / type-2 classification (App. A), threshold derived from params
# ---------------------------------------------------------------------------

def type1_threshold(params: SystemParams | None = None,
                    margin: float = 1.4) -> float:
    """Intensity (FLOP/byte) above which distributing a layer can pay.

    A subtask's round-trip charges its bytes at the per-byte transmission
    cost t_tr = theta_rec + 1/mu_rec and its FLOPs at the per-FLOP worker
    cost t_w = theta_cmp + 1/mu_cmp; distribution can only win when the
    compute a worker absorbs outweighs the transfer it adds, i.e. when
    FLOPs/byte exceeds t_tr / t_w — times a ``margin`` of headroom for
    the encode/decode GEMMs and the k-th-order-statistic inflation the
    ratio alone does not see.  Under the default ``SystemParams`` this
    evaluates to exactly the 200.0 FLOP/B the classification was
    previously hard-coded to (margin 1.4 x the 142.9 cost ratio), and it
    keeps VGG16's conv1 and ResNet18's 1x1 downsamples type-2 (App. A) —
    pinned by tests/test_netplan.py.
    """
    p = params if params is not None else SystemParams()
    t_tr = p.theta_rec + 1.0 / p.mu_rec
    t_w = p.theta_cmp + 1.0 / p.mu_cmp
    return margin * t_tr / t_w


def is_type1(spec: ConvSpec, params: SystemParams | None = None,
             min_intensity: float | None = None) -> bool:
    """Type-1 iff compute dominates transfer enough for distribution to pay.

    Intensity = subtask FLOPs per transferred byte at k=1, compared to
    :func:`type1_threshold` derived from ``params`` (``min_intensity``
    overrides the derived threshold for callers that pin one explicitly).
    """
    thresh = (min_intensity if min_intensity is not None
              else type1_threshold(params))
    flops = spec.subtask_flops(spec.w_out)
    bytes_ = spec.recv_bytes(spec.w_in) + spec.send_bytes(spec.w_out)
    return flops / bytes_ > thresh


# ---------------------------------------------------------------------------
# network definitions
# ---------------------------------------------------------------------------

def _spec(c_in, c_out, size, kernel=3, stride=1, pad=1) -> ConvSpec:
    return ConvSpec(c_in=c_in, c_out=c_out, h_in=size + 2 * pad,
                    w_in=size + 2 * pad, kernel=kernel, stride=stride)


def vgg16_conv_specs(image: int = 224,
                     params: SystemParams | None = None) -> List[LayerInfo]:
    cfg = [  # (name, c_in, c_out, spatial, pool after)
        ("conv1_1", 3, 64, image, 0), ("conv1_2", 64, 64, image, 2),
        ("conv2_1", 64, 128, image // 2, 0), ("conv2_2", 128, 128, image // 2, 2),
        ("conv3_1", 128, 256, image // 4, 0), ("conv3_2", 256, 256, image // 4, 0),
        ("conv3_3", 256, 256, image // 4, 2),
        ("conv4_1", 256, 512, image // 8, 0), ("conv4_2", 512, 512, image // 8, 0),
        ("conv4_3", 512, 512, image // 8, 2),
        ("conv5_1", 512, 512, image // 16, 0), ("conv5_2", 512, 512, image // 16, 0),
        ("conv5_3", 512, 512, image // 16, 2),
    ]
    out = []
    for name, ci, co, s, pool in cfg:
        spec = _spec(ci, co, s)
        out.append(LayerInfo(name, spec, is_type1(spec, params),
                             act="relu", pad=1, pool=pool))
    return out


def resnet18_conv_specs(image: int = 224,
                        params: SystemParams | None = None) -> List[LayerInfo]:
    out: List[LayerInfo] = []

    def add(name, ci, co, size, kernel=3, stride=1, pad=1, act="relu",
            pool=0, barrier=False):
        spec = ConvSpec(c_in=ci, c_out=co, h_in=size + 2 * pad,
                        w_in=size + 2 * pad, kernel=kernel, stride=stride)
        out.append(LayerInfo(name, spec, is_type1(spec, params), act=act,
                             pad=pad, pool=pool, barrier=barrier))

    # the stem pools, each block's second conv and every 1x1 downsample
    # end at a structural join (residual add): barrier stops the segment
    # compiler from fusing across what the flat layer list cannot express
    add("conv1", 3, 64, image, kernel=7, stride=2, pad=3, pool=2)
    s = image // 4  # after stride-2 conv + pool
    for b in range(2):  # layer1: 64 -> 64
        add(f"l1b{b}c1", 64, 64, s)
        add(f"l1b{b}c2", 64, 64, s, act=None, barrier=True)
    add("l2b0c1", 64, 128, s, stride=2)
    add("l2ds", 64, 128, s, kernel=1, stride=2, pad=0, act=None, barrier=True)
    s //= 2
    add("l2b0c2", 128, 128, s, act=None, barrier=True)
    add("l2b1c1", 128, 128, s)
    add("l2b1c2", 128, 128, s, act=None, barrier=True)
    add("l3b0c1", 128, 256, s, stride=2)
    add("l3ds", 128, 256, s, kernel=1, stride=2, pad=0, act=None, barrier=True)
    s //= 2
    add("l3b0c2", 256, 256, s, act=None, barrier=True)
    add("l3b1c1", 256, 256, s)
    add("l3b1c2", 256, 256, s, act=None, barrier=True)
    add("l4b0c1", 256, 512, s, stride=2)
    add("l4ds", 256, 512, s, kernel=1, stride=2, pad=0, act=None, barrier=True)
    s //= 2
    add("l4b0c2", 512, 512, s, act=None, barrier=True)
    add("l4b1c1", 512, 512, s)
    add("l4b1c2", 512, 512, s, act=None, barrier=True)
    return out


# ---------------------------------------------------------------------------
# runnable execution: a compiled NetPlan walked over real arrays
# ---------------------------------------------------------------------------

def maxpool2d(x: jax.Array, window: int, stride: int | None = None) -> jax.Array:
    """VALID max-pool over H and W (NCHW)."""
    stride = stride or window
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1, window, window),
        (1, 1, stride, stride), "VALID")


def _pad_hw(x: jax.Array, pad: int) -> jax.Array:
    if not pad:
        return x
    return jnp.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))


def _finish_layer(y: jax.Array, li: LayerInfo,
                  bias: jax.Array | None = None) -> jax.Array:
    y = add_bias(y, bias)
    if li.act is not None:
        y = ACTIVATIONS[li.act](y)
    if li.pool:
        y = maxpool2d(y, li.pool)
    return y


def forward_plan(plan: NetPlan, convs: Sequence[jax.Array], x: jax.Array,
                 *, subset=None, executor=None, assignment=None,
                 biases=None) -> jax.Array:
    """Run a conv stack through its compiled plan.

    Segments execute ``run_segment`` (one encode, resident chains, one
    decode; interior biases and activations inside the chains); the master
    applies each segment's final bias, activation and pooling post-decode,
    and runs LocalStep layers itself (``model.local`` spans, as is the
    segment's entry pad).  ``convs[i]`` is layer i's OIHW weight and
    ``biases[i]`` its bias (``biases`` None: no layer has one).
    """
    if biases is None:
        biases = [None] * len(plan.layers)
    for step in plan.steps:
        sub = plan.layers[step.start:step.stop]
        idx = range(step.start, step.stop)
        ws = [convs[i] for i in idx]
        bs = [biases[i] for i in idx]
        if isinstance(step, SegmentStep):
            with span("model.local"):
                xp = _pad_hw(x, sub[0].pad)
            y = run_segment(
                xp, ws, step.scheme,
                [li.spec for li in sub], [li.pad for li in sub],
                [li.act for li in sub], split=step.split, subset=subset,
                executor=executor, assignment=assignment, biases=bs)
            with span("model.local"):
                x = _finish_layer(y, sub[-1], bs[-1])
        else:
            with span("model.local"):
                x = _local_layers(sub, ws, x, bs)
    return x


def _local_layers(layers: Sequence[LayerInfo], convs, x: jax.Array,
                  biases=None) -> jax.Array:
    """Run conv layers (pad, conv, bias, activation, pool) on the master."""
    for i, (li, w) in enumerate(zip(layers, convs)):
        x = _finish_layer(conv2d(_pad_hw(x, li.pad), w, li.spec.stride), li,
                          None if biases is None else biases[i])
    return x


def _head(h: jax.Array, head: jax.Array) -> jax.Array:
    """Flatten and apply the linear head, on the master."""
    with span("model.local"):
        return h.reshape(h.shape[0], -1) @ head


def cnn_head_features(layers: Sequence[LayerInfo]) -> int:
    """Flattened feature count after the last conv layer (+ pools)."""
    h = w = None
    for li in layers:
        h, w = li.spec.h_out, li.spec.w_out
        if li.pool:
            h, w = h // li.pool, w // li.pool
    return layers[-1].spec.c_out * h * w


def init_cnn(key: jax.Array, layers: Sequence[LayerInfo],
             n_classes: int = 10) -> dict:
    """He-init conv weights + a linear head for any LayerInfo stack."""
    ks = jax.random.split(key, len(layers) + 1)
    convs = []
    for k, li in zip(ks, layers):
        s = li.spec
        w = jax.random.normal(k, (s.c_out, s.c_in, s.kernel, s.kernel),
                              jnp.float32)
        convs.append(w * (2.0 / (s.c_in * s.kernel ** 2)) ** 0.5)
    feat = cnn_head_features(layers)
    head = jax.random.normal(ks[-1], (feat, n_classes),
                             jnp.float32) * feat ** -0.5
    return {"convs": convs, "head": head}


@functools.lru_cache(maxsize=256)
def _compile_cached(layers: tuple, n: int, params: SystemParams,
                    scheme: str | None,
                    fixed: CodingScheme | None) -> NetPlan:
    """Every argument is a frozen dataclass / str, so repeated forwards of
    the same network (the serving loop, the per-block ResNet branches)
    reuse one compiled plan instead of re-running the cut DP per call."""
    if fixed is not None:
        return compile_plan(layers, n, params, fixed_scheme=fixed)
    return compile_plan(layers, n, params, scheme)


def _resolve_plan(layers: Sequence[LayerInfo], plan: NetPlan | None,
                  scheme, code: CodingScheme | None, n: int | None,
                  sys_params: SystemParams | None) -> NetPlan | None:
    """Shared forward-entry logic: an explicit plan wins; otherwise compile
    (and memoize) one from (scheme | code instance, n, params); None means
    run locally."""
    if plan is not None:
        return plan
    if code is None and scheme is None:
        return None
    params = sys_params if sys_params is not None else SystemParams()
    if code is not None:
        return _compile_cached(tuple(layers), code.n, params, None, code)
    if not isinstance(scheme, str):  # a scheme instance pins (n, k)
        return _compile_cached(tuple(layers), scheme.n, params, None, scheme)
    if n is None:
        raise ValueError("scheme given by name needs n= (worker count)")
    get_scheme(scheme)  # fail fast on unknown names
    return _compile_cached(tuple(layers), n, params, scheme, None)


# ---------------------------------------------------------------------------
# small runnable CNN (end-to-end coded inference on CPU)
# ---------------------------------------------------------------------------

_SMALL = [  # (c_in, c_out, stride) — VGG-ish, image 32
    (3, 32, 1), (32, 32, 1), (32, 64, 2), (64, 64, 1),
]

# The small CNN models an edge-LAN testbed (slow CPU compute, ~4 Gbps
# local link) rather than the paper's Pi-over-WiFi scale: its layers are
# only a few MFLOP, so under the WiFi-scale default SystemParams every
# one is type-2 and nothing would distribute.  Derived threshold: 2.0
# FLOP/B, which classifies all four layers type-1 — the same
# classification the old hard-coded min_intensity=10.0 produced.
SMALL_CNN_PARAMS = SystemParams(
    mu_cmp=2e8, theta_cmp=2e-9,     # ~0.14 GFLOP/s effective edge CPU
    mu_rec=5e8, theta_rec=8e-9,     # ~ 4 Gbps LAN
    mu_sen=5e8, theta_sen=8e-9,
)


def small_cnn_layers(image: int = 32,
                     params: SystemParams | None = None) -> List[LayerInfo]:
    params = params if params is not None else SMALL_CNN_PARAMS
    out, s = [], image
    for i, (ci, co, st) in enumerate(_SMALL):
        spec = ConvSpec(c_in=ci, c_out=co, h_in=s + 2, w_in=s + 2,
                        kernel=3, stride=st)
        out.append(LayerInfo(f"conv{i + 1}", spec, is_type1(spec, params),
                             act="relu", pad=1))
        s = s // st
    return out


def small_cnn_conv_specs(image: int = 32) -> List[ConvSpec]:
    return [li.spec for li in small_cnn_layers(image)]


def init_small_cnn(key: jax.Array, n_classes: int = 10, image: int = 32) -> dict:
    return init_cnn(key, small_cnn_layers(image), n_classes)


def small_cnn_forward(
    params: dict,
    x: jax.Array,
    code: CodingScheme | None = None,
    subset=None,
    *,
    scheme: str | CodingScheme | None = None,
    n: int | None = None,
    sys_params: SystemParams | None = None,
    plan: NetPlan | None = None,
    executor=None,
) -> jax.Array:
    """Forward pass through the compiled segment plan.

    ``code`` (kept for compatibility) pins one scheme instance — any
    registered :class:`CodingScheme`, not just MDS — for every segment;
    ``scheme``/``n`` compile a per-segment (n, k°) plan instead; ``plan``
    supplies a precompiled :class:`NetPlan` (the serving path compiles
    once and reuses).  No coding arguments -> plain local inference.
    ``subset`` (default: each scheme's ``default_subset``) picks the
    worker outputs decode consumes, emulating stragglers.
    """
    with span("model.forward", batch=x.shape[0]):
        layers = small_cnn_layers(image=x.shape[-1],
                                  params=sys_params or SMALL_CNN_PARAMS)
        plan = _resolve_plan(layers, plan, scheme, code, n,
                             sys_params or SMALL_CNN_PARAMS)
        if plan is None:
            with span("model.local"):
                h = _local_layers(layers, params["convs"], x)
        else:
            h = forward_plan(plan, params["convs"], x, subset=subset,
                             executor=executor)
        return _head(h, params["head"])


# ---------------------------------------------------------------------------
# runnable VGG16 / ResNet18
# ---------------------------------------------------------------------------

def init_vgg16(key: jax.Array, n_classes: int = 10, image: int = 32) -> dict:
    return init_cnn(key, vgg16_conv_specs(image), n_classes)


def vgg16_forward(
    params: dict,
    x: jax.Array,
    code: CodingScheme | None = None,
    subset=None,
    *,
    scheme: str | CodingScheme | None = None,
    n: int | None = None,
    sys_params: SystemParams | None = None,
    plan: NetPlan | None = None,
    executor=None,
) -> jax.Array:
    """Runnable VGG16: 13-conv stack through the compiled segment plan."""
    with span("model.forward", batch=x.shape[0]):
        layers = vgg16_conv_specs(image=x.shape[-1], params=sys_params)
        plan = _resolve_plan(layers, plan, scheme, code, n, sys_params)
        if plan is None:
            with span("model.local"):
                h = _local_layers(layers, params["convs"], x)
        else:
            h = forward_plan(plan, params["convs"], x, subset=subset,
                             executor=executor)
        return _head(h, params["head"])


def init_resnet18(key: jax.Array, n_classes: int = 10, image: int = 64) -> dict:
    return init_cnn(key, resnet18_conv_specs(image), n_classes)


def _resnet_blocks(layers: Sequence[LayerInfo]):
    """(c1_idx, c2_idx, ds_idx | None) triples of the 8 basic blocks."""
    blocks, i = [], 1
    while i < len(layers):
        if layers[i + 1].name.endswith("ds"):
            blocks.append((i, i + 2, i + 1))
            i += 3
        else:
            blocks.append((i, i + 1, None))
            i += 2
    return blocks


def resnet18_forward(
    params: dict,
    x: jax.Array,
    code: CodingScheme | None = None,
    subset=None,
    *,
    scheme: str | CodingScheme | None = None,
    n: int | None = None,
    sys_params: SystemParams | None = None,
    executor=None,
) -> jax.Array:
    """Runnable ResNet18 (basic blocks, bias/BN-free convs).

    Each residual branch's conv pair compiles as its own mini plan — the
    c1 -> c2 boundary carries a relu, so it fuses into one depth-2 segment
    under selection schemes and stays per-layer under linear mixes; the
    skip add and the following relu are master-side joins (barriers).
    """
    with span("model.forward", batch=x.shape[0]):
        layers = resnet18_conv_specs(image=x.shape[-1], params=sys_params)
        convs = params["convs"]

        def branch(idxs: Sequence[int], h: jax.Array) -> jax.Array:
            sub = [layers[i] for i in idxs]
            pln = _resolve_plan(sub, None, scheme, code, n, sys_params)
            if pln is None:
                with span("model.local"):
                    return _local_layers(sub, [convs[i] for i in idxs], h)
            return forward_plan(pln,
                                {i: convs[j] for i, j in enumerate(idxs)},
                                h, subset=subset, executor=executor)

        with span("model.local"):
            h = _local_layers(layers[:1], convs[:1], x)
        for c1, c2, ds in _resnet_blocks(layers):
            skip = h
            if ds is not None:
                with span("model.local"):
                    skip = conv2d(_pad_hw(h, layers[ds].pad), convs[ds],
                                  layers[ds].spec.stride)
            y = branch((c1, c2), h)
            with span("model.local"):
                h = jax.nn.relu(y + skip)
        return _head(h, params["head"])


# ---------------------------------------------------------------------------
# runnable ConvNeXt (Liu et al., arXiv:2201.03545)
# ---------------------------------------------------------------------------

CONVNEXT_B = {"widths": (128, 256, 512, 1024), "depths": (3, 3, 27, 3)}
CONVNEXT_NORM_EPS = 1e-6


def convnext_conv_specs(image: int = 224,
                        widths: Sequence[int] = CONVNEXT_B["widths"],
                        depths: Sequence[int] = CONVNEXT_B["depths"],
                        params: SystemParams | None = None
                        ) -> List[LayerInfo]:
    """Every conv of a ConvNeXt, in order; each has a bias.

    The stem is a 4x4 stride-4 conv; stages 2-4 open with a 2x2 stride-2
    downsample (``ds<s>``); a block (``s<s>b<b>``) is a 7x7 depthwise conv
    (``dw``, groups = C), then the pointwise convs ``pw1`` (C -> 4C, GELU)
    and ``pw2`` (4C -> C) as 1x1 convs.  A LayerNorm or the residual join
    follows every conv but ``pw1``: those are barriers, so only the
    pointwise pair may share a segment (under selection schemes; linear
    mixes decode at the GELU and the bias)."""
    out: List[LayerInfo] = []

    def add(name, ci, co, size, kernel=1, stride=1, pad=0, groups=1,
            act=None, barrier=True):
        spec = ConvSpec(c_in=ci, c_out=co, h_in=size + 2 * pad,
                        w_in=size + 2 * pad, kernel=kernel, stride=stride,
                        groups=groups)
        out.append(LayerInfo(name, spec, is_type1(spec, params), act=act,
                             pad=pad, barrier=barrier, bias=True))

    add("stem", 3, widths[0], image, kernel=4, stride=4)
    size = image // 4
    for s, (c, depth) in enumerate(zip(widths, depths), start=1):
        if s > 1:
            add(f"ds{s}", widths[s - 2], c, size, kernel=2, stride=2)
            size //= 2
        for b in range(depth):
            add(f"s{s}b{b}dw", c, c, size, kernel=7, pad=3, groups=c)
            add(f"s{s}b{b}pw1", c, 4 * c, size, act="gelu", barrier=False)
            add(f"s{s}b{b}pw2", 4 * c, c, size)
    return out


@jax.jit
def layer_norm(x: jax.Array, scale: jax.Array, shift: jax.Array
               ) -> jax.Array:
    """LayerNorm over axis 1 (the channels of NCHW, the features of (B, F)),
    eps 1e-6, then the per-channel affine; one program per call."""
    mean = x.mean(axis=1, keepdims=True)
    var = jnp.square(x - mean).mean(axis=1, keepdims=True)
    shape = (-1,) + (1,) * (x.ndim - 2)
    return ((x - mean) * jax.lax.rsqrt(var + CONVNEXT_NORM_EPS)
            * scale.reshape(shape) + shift.reshape(shape))


def _norm(x: jax.Array, p: dict) -> jax.Array:
    with span("model.norm", channels=x.shape[1]):
        return layer_norm(x, p["scale"], p["shift"])


def init_convnext(key: jax.Array, n_classes: int = 1000, image: int = 224,
                  widths: Sequence[int] = CONVNEXT_B["widths"],
                  depths: Sequence[int] = CONVNEXT_B["depths"]) -> dict:
    """Seeded weights in ``convnext_forward``'s layout: He-normal convs,
    biases and LayerNorm affines near 0 and 1, layer scales of order 1
    (not the training init of 1e-6, under which every block's branch
    would vanish), a head N(0, 1/F)."""
    layers = convnext_conv_specs(image, widths, depths)
    k_conv, k_rest = jax.random.split(key)
    convs = {}
    for k, li in zip(jax.random.split(k_conv, len(layers)), layers):
        s = li.spec
        fan_in = s.c_in // s.groups * s.kernel ** 2
        kw, kb = jax.random.split(k)
        convs[li.name] = {
            "w": jax.random.normal(kw, (s.c_out, s.c_in // s.groups,
                                        s.kernel, s.kernel), jnp.float32)
            * (2.0 / fan_in) ** 0.5,
            "b": 0.1 * jax.random.normal(kb, (s.c_out,), jnp.float32)}
    keys = iter(jax.random.split(k_rest, 3 * len(layers) + 3))

    def norm(c):
        return {"scale": 1.0 + 0.1 * jax.random.normal(next(keys), (c,)),
                "shift": 0.1 * jax.random.normal(next(keys), (c,))}

    stages = []
    for s, (c, depth) in enumerate(zip(widths, depths), start=1):
        blocks = [{"dw": convs[f"s{s}b{b}dw"], "norm": norm(c),
                   "pw1": convs[f"s{s}b{b}pw1"], "pw2": convs[f"s{s}b{b}pw2"],
                   "gamma": jax.random.uniform(next(keys), (c,),
                                               minval=0.5, maxval=1.5)}
                  for b in range(depth)]
        down = (None if s == 1 else
                dict(convs[f"ds{s}"], norm=norm(widths[s - 2])))
        stages.append({"down": down, "blocks": blocks})
    c = widths[-1]
    return {"stem": dict(convs["stem"], norm=norm(widths[0])),
            "stages": stages,
            "head": {"norm": norm(c),
                     "w": jax.random.normal(next(keys), (c, n_classes))
                     * c ** -0.5,
                     "b": 0.1 * jax.random.normal(next(keys), (n_classes,))}}


def convnext_forward(
    params: dict,
    x: jax.Array,
    code: CodingScheme | None = None,
    subset=None,
    *,
    scheme: str | CodingScheme | None = None,
    n: int | None = None,
    sys_params: SystemParams | None = None,
    executor=None,
) -> jax.Array:
    """Runnable ConvNeXt (``init_convnext``'s layout; the widths and depths
    are read from it).  Drop path is the identity at inference.

    Every conv runs through a mini plan of its own, as ResNet18's branches
    do, and each block's pointwise pair through one: the convs take the
    coded path wherever the plan codes them, and their biases are added
    after decode.  The LayerNorms (``model.norm``), the depthwise convs
    the plan keeps local (``model.dwconv``, with their pad and bias), the
    layer scale and residual add, the global average pool and the head
    (``model.local``) run on the master.
    """
    with span("model.forward", batch=x.shape[0]):
        stages = params["stages"]
        widths = [st["blocks"][0]["dw"]["w"].shape[0] for st in stages]
        depths = [len(st["blocks"]) for st in stages]
        layers = {li.name: li for li in convnext_conv_specs(
            x.shape[-1], widths, depths, sys_params)}

        def run(names, convs, h, local_span="model.local"):
            sub = [layers[name] for name in names]
            ws = [p["w"] for p in convs]
            bs = [p["b"] for p in convs]
            pln = _resolve_plan(sub, None, scheme, code, n, sys_params)
            if pln is None or not pln.segments:
                with span(local_span):
                    return _local_layers(sub, ws, h, bs)
            return forward_plan(pln, ws, h, biases=bs, subset=subset,
                                executor=executor)

        h = _norm(run(["stem"], [params["stem"]], x), params["stem"]["norm"])
        for s, st in enumerate(stages, start=1):
            if st["down"] is not None:
                h = run([f"ds{s}"], [st["down"]], _norm(h, st["down"]["norm"]))
            for b, blk in enumerate(st["blocks"]):
                y = run([f"s{s}b{b}dw"], [blk["dw"]], h,
                        local_span="model.dwconv")
                y = run([f"s{s}b{b}pw1", f"s{s}b{b}pw2"],
                        [blk["pw1"], blk["pw2"]], _norm(y, blk["norm"]))
                with span("model.local"):
                    h = h + blk["gamma"][:, None, None] * y
        head = params["head"]
        with span("model.local"):
            h = h.mean(axis=(2, 3))
        h = _norm(h, head["norm"])
        with span("model.local"):
            return h @ head["w"] + head["b"]
