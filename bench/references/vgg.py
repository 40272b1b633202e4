"""Plain float32 VGG reference (Simonyan & Zisserman, arXiv:1409.1556).

The configuration file gives the stages: each stage is a list of 3x3,
stride-1, pad-1 conv widths, every conv followed by ReLU, every stage by a
2x2 stride-2 max pool.  The classifier is one linear map of the flattened
C, H, W features.  No biases.  Nothing here imports the system under test.
"""
from __future__ import annotations

import jax

from bench.references.common import conv, he_normal, head, maxpool


def conv_layers(cfg: dict) -> list[dict]:
    """Every conv of the network, in order, with its input size."""
    out, c_in, size = [], cfg["in_channels"], cfg["image"]
    for s, widths in enumerate(cfg["stages"], start=1):
        for j, c_out in enumerate(widths, start=1):
            out.append({"name": f"conv{s}_{j}", "c_in": c_in, "c_out": c_out,
                        "kernel": cfg["kernel"], "stride": 1,
                        "pad": cfg["pad"], "size": size})
            c_in = c_out
        size //= cfg["pool"]["stride"]
    return out


def head_features(cfg: dict) -> int:
    size = cfg["image"] // cfg["pool"]["stride"] ** len(cfg["stages"])
    return cfg["stages"][-1][-1] * size * size


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
    pool = cfg["pool"]
    layers = iter(conv_layers(cfg))
    for widths in cfg["stages"]:
        for _ in widths:
            li = next(layers)
            x = jax.nn.relu(conv(x, params[li["name"]], li["stride"],
                                 li["pad"], mode))
        x = maxpool(x, pool["window"], pool["stride"])
    return head(x, params["head"], mode)
