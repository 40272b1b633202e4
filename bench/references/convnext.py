"""Plain float32 ConvNeXt reference (Liu et al., arXiv:2201.03545, and the
authors' ``models/convnext.py``).

A 4x4 stride-4 stem conv and a LayerNorm; four stages, stages 2-4 opened
by a LayerNorm and a 2x2 stride-2 conv; each block is a 7x7 depthwise conv
(pad 3), a LayerNorm over the channels, a 1x1 conv to four times the
width, the exact (erf) GELU, a 1x1 conv back, the layer scale and the
residual add.  The head is a global average pool, a LayerNorm and a linear
classifier.  Every conv and the classifier have a bias; drop path is the
identity at inference.  Nothing here imports the system under test.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.references.common import _apply, dense, he_normal


def _conv(x: jax.Array, w: jax.Array, b: jax.Array, stride: int, pad: int,
          mode: str) -> jax.Array:
    """Zero-padded NCHW conv by an OIHW weight, grouped where the weight's
    I is a fraction of the input's channels, plus the per-channel bias."""
    def op(a, k, precision):
        return jax.lax.conv_general_dilated(
            a, k, window_strides=(stride, stride),
            padding=((pad, pad), (pad, pad)),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=a.shape[1] // k.shape[1],
            precision=precision)
    return _apply(op, x, w, mode) + b[:, None, None]


def _layer_norm(x: jax.Array, params: dict, name: str, eps: float
                ) -> jax.Array:
    """LayerNorm over axis 1 (channels of NCHW, features of (B, F))."""
    u = jnp.mean(x, axis=1, keepdims=True)
    s = jnp.mean((x - u) ** 2, axis=1, keepdims=True)
    shape = (1, -1) + (1,) * (x.ndim - 2)
    return ((x - u) / jnp.sqrt(s + eps) * params[f"{name}.scale"].reshape(shape)
            + params[f"{name}.shift"].reshape(shape))


def _gelu(x: jax.Array) -> jax.Array:
    return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(2.0).astype(x.dtype)))


def _blocks(cfg: dict):
    """(stage, block, width, input size) of every block."""
    size = cfg["image"] // cfg["stem"]["stride"]
    for s, (c, depth) in enumerate(zip(cfg["widths"], cfg["depths"]),
                                   start=1):
        if s > 1:
            size //= cfg["downsample"]["stride"]
        for b in range(depth):
            yield s, b, c, size


def conv_layers(cfg: dict) -> list[dict]:
    """Every conv of the network, in order, with its input size.  A
    depthwise conv lists ``c_in`` 1, its weight's I: one input channel per
    output channel, so that a dense count of its shape is its true work."""
    stem, ds, r = cfg["stem"], cfg["downsample"], cfg["mlp_ratio"]
    out = [{"name": "stem", "c_in": cfg["in_channels"],
            "c_out": cfg["widths"][0], "kernel": stem["kernel"],
            "stride": stem["stride"], "pad": 0, "size": cfg["image"]}]
    for s, b, c, size in _blocks(cfg):
        if b == 0 and s > 1:
            out.append({"name": f"ds{s}", "c_in": cfg["widths"][s - 2],
                        "c_out": c, "kernel": ds["kernel"],
                        "stride": ds["stride"], "pad": 0,
                        "size": size * ds["stride"]})
        out += [{"name": f"s{s}b{b}dw", "c_in": 1, "c_out": c,
                 "kernel": cfg["kernel"], "stride": 1, "pad": cfg["pad"],
                 "size": size},
                {"name": f"s{s}b{b}pw1", "c_in": c, "c_out": r * c,
                 "kernel": 1, "stride": 1, "pad": 0, "size": size},
                {"name": f"s{s}b{b}pw2", "c_in": r * c, "c_out": c,
                 "kernel": 1, "stride": 1, "pad": 0, "size": size}]
    return out


def head_features(cfg: dict) -> int:
    return cfg["widths"][-1]


def _norms(cfg: dict) -> list[tuple[str, int]]:
    """(name, channels) of every LayerNorm."""
    w = cfg["widths"]
    out = [("stem.norm", w[0])]
    out += [(f"ds{s}.norm", w[s - 2]) for s in range(2, len(w) + 1)]
    out += [(f"s{s}b{b}.norm", c) for s, b, c, _ in _blocks(cfg)]
    return out + [("head.norm", w[-1])]


def init(cfg: dict, key: jax.Array) -> dict:
    """He-normal conv weights (OIHW) and N(0, 0.1^2) biases by conv name;
    LayerNorm scales 1 + N(0, 0.1^2) and shifts N(0, 0.1^2); layer scales
    U(0.5, 1.5) per channel; the head (F, classes) N(0, 1/F) and its bias
    N(0, 0.1^2)."""
    layers, norms = conv_layers(cfg), _norms(cfg)
    blocks = list(_blocks(cfg))
    keys = iter(jax.random.split(key, 2 * len(layers) + 2 * len(norms)
                                 + len(blocks) + 2))
    params = {}
    for li in layers:
        params[f"{li['name']}.w"] = he_normal(
            next(keys), (li["c_out"], li["c_in"], li["kernel"], li["kernel"]),
            li["c_in"] * li["kernel"] ** 2)
        params[f"{li['name']}.b"] = 0.1 * jax.random.normal(
            next(keys), (li["c_out"],), jnp.float32)
    for name, c in norms:
        params[f"{name}.scale"] = 1.0 + 0.1 * jax.random.normal(
            next(keys), (c,), jnp.float32)
        params[f"{name}.shift"] = 0.1 * jax.random.normal(
            next(keys), (c,), jnp.float32)
    for s, b, c, _ in blocks:
        params[f"s{s}b{b}.gamma"] = jax.random.uniform(
            next(keys), (c,), jnp.float32, 0.5, 1.5)
    feat = head_features(cfg)
    params["head.w"] = he_normal(next(keys), (feat, cfg["classes"]), 2 * feat)
    params["head.b"] = 0.1 * jax.random.normal(next(keys), (cfg["classes"],),
                                               jnp.float32)
    return params


def forward(cfg: dict, params: dict, x: jax.Array,
            mode: str = "highest") -> jax.Array:
    """Logits (B, classes) of images x (B, C, H, W)."""
    eps, p = cfg["norm_eps"], params
    stem, ds = cfg["stem"], cfg["downsample"]
    h = _conv(x, p["stem.w"], p["stem.b"], stem["stride"], 0, mode)
    h = _layer_norm(h, p, "stem.norm", eps)
    for s, b, _, _ in _blocks(cfg):
        if b == 0 and s > 1:
            h = _layer_norm(h, p, f"ds{s}.norm", eps)
            h = _conv(h, p[f"ds{s}.w"], p[f"ds{s}.b"], ds["stride"], 0, mode)
        blk = f"s{s}b{b}"
        y = _conv(h, p[f"{blk}dw.w"], p[f"{blk}dw.b"], 1, cfg["pad"], mode)
        y = _layer_norm(y, p, f"{blk}.norm", eps)
        y = _gelu(_conv(y, p[f"{blk}pw1.w"], p[f"{blk}pw1.b"], 1, 0, mode))
        y = _conv(y, p[f"{blk}pw2.w"], p[f"{blk}pw2.b"], 1, 0, mode)
        h = h + p[f"{blk}.gamma"][:, None, None] * y
    h = _layer_norm(jnp.mean(h, axis=(2, 3)), p, "head.norm", eps)
    return dense(h, p["head.w"], mode) + p["head.b"]
