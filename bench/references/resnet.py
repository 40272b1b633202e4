"""Plain float32 ResNet reference (He et al., arXiv:1512.03385, Table 1).

Basic blocks of two 3x3 convs; a block that changes width or stride
carries a 1x1 stride-2 projection on its skip path.  Every block ends in
ReLU(branch + skip).  The configuration file states the departures: no
batch norm, no biases, the stem's pool, and no global average pool (the
linear head reads the flattened C, H, W features).  Nothing here imports
the system under test.
"""
from __future__ import annotations

import jax

from bench.references.common import conv, he_normal, head, maxpool


def _block_layers(cfg: dict):
    """(name prefix, c_in, c_out, stride, input size) of every block."""
    stem = cfg["stem"]
    size = cfg["image"] // stem["stride"] // stem["pool"]["stride"]
    c_in = stem["c_out"]
    for s, stage in enumerate(cfg["stages"], start=1):
        for b in range(stage["blocks"]):
            stride = stage["stride"] if b == 0 else 1
            yield f"l{s}b{b}", c_in, stage["width"], stride, size
            c_in, size = stage["width"], size // stride


def conv_layers(cfg: dict) -> list[dict]:
    """Every conv of the network, in order, with its input size."""
    stem = cfg["stem"]
    out = [{"name": "conv1", "c_in": cfg["in_channels"], "c_out": stem["c_out"],
            "kernel": stem["kernel"], "stride": stem["stride"],
            "pad": stem["pad"], "size": cfg["image"]}]
    k, p = cfg["kernel"], cfg["pad"]
    for name, c_in, c_out, stride, size in _block_layers(cfg):
        out.append({"name": f"{name}c1", "c_in": c_in, "c_out": c_out,
                    "kernel": k, "stride": stride, "pad": p, "size": size})
        if stride != 1 or c_in != c_out:
            out.append({"name": f"l{name[1]}ds", "c_in": c_in, "c_out": c_out,
                        "kernel": 1, "stride": stride, "pad": 0,
                        "size": size})
        out.append({"name": f"{name}c2", "c_in": c_out, "c_out": c_out,
                    "kernel": k, "stride": 1, "pad": p,
                    "size": size // stride})
    return out


def head_features(cfg: dict) -> int:
    last = conv_layers(cfg)[-1]
    return last["c_out"] * last["size"] ** 2


def init(cfg: dict, key: jax.Array) -> dict:
    """He-normal conv weights (OIHW) by name, and the head (F, classes)."""
    layers = conv_layers(cfg)
    keys = jax.random.split(key, len(layers) + 1)
    params = {li["name"]: he_normal(k, (li["c_out"], li["c_in"], li["kernel"],
                                        li["kernel"]),
                                    li["c_in"] * li["kernel"] ** 2)
              for k, li in zip(keys, layers)}
    feat = head_features(cfg)
    params["head"] = he_normal(keys[-1], (feat, cfg["classes"]), 2 * feat)
    return params


def forward(cfg: dict, params: dict, x: jax.Array,
            mode: str = "highest") -> jax.Array:
    """Logits (B, classes) of images x (B, C, H, W)."""
    stem = cfg["stem"]
    x = jax.nn.relu(conv(x, params["conv1"], stem["stride"], stem["pad"],
                         mode))
    x = maxpool(x, stem["pool"]["window"], stem["pool"]["stride"])
    k, p = cfg["kernel"], cfg["pad"]
    for name, c_in, c_out, stride, _ in _block_layers(cfg):
        h = jax.nn.relu(conv(x, params[f"{name}c1"], stride, p, mode))
        h = conv(h, params[f"{name}c2"], 1, p, mode)
        skip = (conv(x, params[f"l{name[1]}ds"], stride, 0, mode)
                if stride != 1 or c_in != c_out else x)
        x = jax.nn.relu(h + skip)
    return head(x, params["head"], mode)
