"""ResNet-50 (He et al., arXiv:1512.03385, Table 1, 50-layer column), plain.

conv -> batch norm -> ReLU; bottleneck blocks 1x1, 3x3, 1x1(x4) with the
stride on the first 1x1 (the paper's placement) and a projection shortcut on
each stage's first block; 7x7/2 stem, 3x3/2 max pool, global average pool,
1000-way softmax. Departures from the paper, all to meet the configuration
the benchmark states: 'SAME' padding in XLA's convention (the paper does not
say where the odd pixel goes), no weight decay, seeded He-normal weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import common as C

_FILTERS = (64, 128, 256, 512)


def _blocks(cfg):
    """(name, in_ch, filters, stride, projection, in_size) for every block."""
    size = C.conv_out(C.conv_out(cfg["image_size"], 2), 2)
    ch = 64
    for stage, n in enumerate(cfg.get("stage_blocks", (3, 4, 6, 3))):
        for block in range(n):
            stride = 2 if (stage > 0 and block == 0) else 1
            yield (f"s{stage}b{block}", ch, _FILTERS[stage], stride,
                   block == 0, size)
            size = C.conv_out(size, stride)
            ch = _FILTERS[stage] * 4


def _convs(cfg):
    """(name, k, cin, cout, stride, in_size) for every convolution."""
    yield "stem", 7, cfg.get("channels", 3), 64, 2, cfg["image_size"]
    for name, cin, f, stride, proj, size in _blocks(cfg):
        mid = C.conv_out(size, stride)
        yield f"{name}_a", 1, cin, f, stride, size
        yield f"{name}_b", 3, f, f, 1, mid
        yield f"{name}_c", 1, f, 4 * f, 1, mid
        if proj:
            yield f"{name}_proj", 1, cin, 4 * f, stride, size


def layers(cfg) -> list:
    """The products a step has to compute, for ``flops.py``."""
    out = []
    for i, (name, k, cin, cout, stride, size) in enumerate(_convs(cfg)):
        o = C.conv_out(size, stride)
        out.append({"kind": "conv", "name": name, "k": k, "cin": cin,
                    "cout": cout, "hout": o, "wout": o, "first": i == 0})
    out.append({"kind": "dense", "name": "fc", "nin": 2048,
                "nout": cfg["n_classes"], "first": False})
    return out


def init(seed: int, cfg) -> dict:
    """All weights on the device in one jitted call from the seed."""
    convs = list(_convs(cfg))

    def make(key):
        keys = jax.random.split(key, len(convs) + 1)
        p = {}
        for kk, (name, k, cin, cout, _, _) in zip(keys, convs):
            p[f"{name}_conv/W"] = C.he_normal(kk, (k, k, cin, cout))
            p[f"{name}_bn/gamma"] = jnp.ones((cout,), jnp.float32)
            p[f"{name}_bn/beta"] = jnp.zeros((cout,), jnp.float32)
        p["fc/W"] = C.xavier_normal(keys[-1], (2048, cfg["n_classes"]))
        p["fc/b"] = jnp.zeros((cfg["n_classes"],), jnp.float32)
        return p

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def make_loss_and_grad(cfg, precision: str = "float32", stage_dtype=None,
                       remat: bool = True):
    wrap = jax.checkpoint if remat else (lambda f: f)

    def cbn(p, name, x, stride, relu=True):
        y = C.conv(x, p[f"{name}_conv/W"], stride, precision)
        y = C.batch_norm(y, p[f"{name}_bn/gamma"], p[f"{name}_bn/beta"])
        return jax.nn.relu(y) if relu else y

    def block(p, x, name, stride, proj):
        y = cbn(p, f"{name}_a", x, stride)
        y = cbn(p, f"{name}_b", y, 1)
        y = cbn(p, f"{name}_c", y, 1, relu=False)
        sc = cbn(p, f"{name}_proj", x, stride, relu=False) if proj else x
        return jax.nn.relu(y + sc)

    def loss(p, x, y):
        h = cbn(p, "stem", C.staged(x, stage_dtype), 2)
        h = C.max_pool(h, 3, 2, "SAME")
        for name, _, _, stride, proj, _ in _blocks(cfg):
            sub = {k: v for k, v in p.items() if k.startswith(name + "_")}
            # one block's activations live at a time: batch norm needs the
            # whole batch, so the memory is cut by depth, not by rows
            h = wrap(
                lambda q, a, n=name, s=stride, pr=proj: block(q, a, n, s, pr)
            )(sub, h)
        h = jnp.mean(h, axis=(1, 2))
        return C.softmax_xent(C.dense(h, p["fc/W"], p["fc/b"], precision), y)

    return jax.jit(jax.value_and_grad(loss))
