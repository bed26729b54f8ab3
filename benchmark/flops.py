"""Operations a training step has to do, from the configuration's shapes.

Counts the convolution and dense products only: 2 x multiply-accumulates
forward, and twice that backward (one product for the weight gradient, one
for the input gradient; the first layer needs no input gradient).
Normalisation, pooling, activations, the loss and the update are not counted,
and neither is anything an implementation recomputes. ``layers`` is what a
reference module's ``layers(cfg)`` returns.
"""
from __future__ import annotations

import importlib


def layer_macs(layer: dict) -> int:
    """Multiply-accumulates of one sample's forward pass through ``layer``."""
    if layer["kind"] == "conv":
        return (layer["k"] * layer["k"] * layer["cin"] * layer["cout"]
                * layer["hout"] * layer["wout"])
    if layer["kind"] == "dense":
        return layer["nin"] * layer["nout"]
    raise ValueError(f"unknown layer kind {layer['kind']!r}")


def forward_flops_per_sample(layers: list) -> int:
    return sum(2 * layer_macs(l) for l in layers)


def train_flops_per_sample(layers: list) -> int:
    """Forward + weight-gradient + input-gradient products of one sample."""
    return sum(2 * layer_macs(l) * (2 if l.get("first") else 3)
               for l in layers)


def _layers_of(config: dict) -> list:
    """The products of a configuration file's network, as its plain
    reference lists them."""
    ref = importlib.import_module("reference." + config["reference"])
    return ref.layers(config["builder"]["kwargs"])


def train_flops_of(config: dict) -> int:
    """``train_flops_per_sample`` of a configuration file's network."""
    return train_flops_per_sample(_layers_of(config))


def train_flops_by_scope(config: dict) -> dict:
    """``train_flops_of`` split by the entries' ``scope``: the scope path a
    kernel with a roofline metric of its own is traced under
    (``"attn/core"``, ``"moe/experts"``), or None for an entry without the
    key, a dense product. The values add up to ``train_flops_of``, which
    ignores the key."""
    out = {}
    for l in _layers_of(config):
        scope = l.get("scope")
        out[scope] = out.get(scope, 0) + train_flops_per_sample([l])
    return out


def param_bytes(layers: list, itemsize: int = 4) -> int:
    total = 0
    for l in layers:
        if l["kind"] == "conv":
            total += l["k"] * l["k"] * l["cin"] * l["cout"]
        else:
            total += l["nin"] * l["nout"]
    return total * itemsize
