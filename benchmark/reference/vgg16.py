"""VGG-16 (Simonyan & Zisserman, arXiv:1409.1556, Table 1, configuration D).

Thirteen 3x3 'same' convolutions with ReLU in five blocks (64, 128, 256, 512,
512), a 2x2/2 max pool after each, two 4096-wide ReLU layers and a softmax.
Departures: dropout is held out (the configuration states it), no weight
decay, seeded He-normal weights, NHWC flattening.

The keys are the program's layer indices (``MultiLayerNetwork`` keeps a list).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from . import common as C

_BLOCKS = ((64, 2), (128, 2), (256, 3), (512, 3), (512, 3))
ROW_BLOCK = 32


def _plan(cfg):
    """(index, kind, ...) in the order of the layer list, pools and dropout
    layers counted, so that an index is the program's."""
    i, size, ch = 0, cfg["image_size"], cfg.get("channels", 3)
    for f, n in _BLOCKS:
        for _ in range(n):
            yield i, "conv", ch, f, size
            ch = f
            i += 1
        yield i, "pool", None, None, size
        size //= 2
        i += 1
    nin = size * size * ch
    for _ in range(2):
        yield i, "dense", nin, 4096, None
        nin = 4096
        i += 2   # a dropout layer follows each
    yield i, "dense", nin, cfg["n_classes"], None


def layers(cfg) -> list:
    out = []
    for i, kind, a, b, size in _plan(cfg):
        if kind == "conv":
            out.append({"kind": "conv", "name": str(i), "k": 3, "cin": a,
                        "cout": b, "hout": size, "wout": size,
                        "first": i == 0})
        elif kind == "dense":
            out.append({"kind": "dense", "name": str(i), "nin": a, "nout": b,
                        "first": False})
    return out


def init(seed: int, cfg) -> dict:
    plan = [r for r in _plan(cfg) if r[1] != "pool"]

    def make(key):
        p = {}
        for kk, (i, kind, a, b, _) in zip(jax.random.split(key, len(plan)),
                                          plan):
            if kind == "conv":
                p[f"{i}/W"] = C.he_normal(kk, (3, 3, a, b))
            elif b == cfg["n_classes"]:
                p[f"{i}/W"] = C.xavier_normal(kk, (a, b))
            else:
                p[f"{i}/W"] = C.he_normal(kk, (a, b))
            p[f"{i}/b"] = jnp.zeros((b,), jnp.float32)
        return p

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def make_loss_and_grad(cfg, precision: str = "float32", stage_dtype=None):
    plan = list(_plan(cfg))
    last = plan[-1][0]

    def loss(p, x, y):
        h = C.staged(x, stage_dtype)
        for i, kind, _, _, _ in plan:
            if kind == "conv":
                h = jax.nn.relu(C.conv(h, p[f"{i}/W"], 1, precision)
                                + p[f"{i}/b"])
            elif kind == "pool":
                h = C.max_pool(h, 2, 2, "VALID")
            else:
                h = h.reshape(h.shape[0], -1)
                h = C.dense(h, p[f"{i}/W"], p[f"{i}/b"], precision)
                if i != last:
                    h = jax.nn.relu(h)
        return C.softmax_xent(h, y)

    def blocked(p, x, y):
        """The batch's mean loss and gradient, ROW_BLOCK rows at a time: no
        layer mixes rows, so the mean of the blocks' is the batch's."""
        n = x.shape[0]
        rb = ROW_BLOCK if n % ROW_BLOCK == 0 else n
        xs = x.reshape((n // rb, rb) + x.shape[1:])
        ys = y.reshape((n // rb, rb) + y.shape[1:])

        def body(acc, xy):
            l, g = jax.value_and_grad(loss)(p, *xy)
            return jax.tree_util.tree_map(jnp.add, acc, (l, g)), None

        zero = (jnp.float32(0), jax.tree_util.tree_map(jnp.zeros_like, p))
        (l, g), _ = lax.scan(body, zero, (xs, ys))
        k = n // rb
        return l / k, jax.tree_util.tree_map(lambda t: t / k, g)

    return jax.jit(blocked)
