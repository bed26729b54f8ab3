"""Plain float32 building blocks shared by the benchmark's reference models.

Straightforward ``jax.numpy`` at "highest" matmul precision, written from the
papers; nothing here imports the program under test. ``precision`` selects
the arithmetic of every convolution and dense product, forward and backward:

* ``"float32"``  — the reference proper;
* ``"bfloat16"`` — operands rounded to bfloat16 (what the configurations state);
* ``"int8"``     — operands rounded to 8-bit integers with one symmetric scale
  per tensor: the control of ``correct``, the nearest precision below
  bfloat16 that a v5e computes in (393 TOP/s int8; it has no fp8 unit);
* ``"float8"``   — operands rounded to float8_e4m3 with one scale per tensor.

NHWC activations, HWIO kernels. A parameter tree is a flat dict
``"<layer>/<param>" -> array`` whose keys name the program's leaves, so the
driver can place the same weights into the program without the reference
knowing the program.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

_DIMS = ("NHWC", "HWIO", "NHWC")
_HI = lax.Precision.HIGHEST
_E4M3_MAX = 448.0


def _round(t, precision: str):
    if precision == "float32":
        return t
    if precision == "bfloat16":
        return t.astype(jnp.bfloat16).astype(jnp.float32)
    if precision == "float8":
        scale = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / _E4M3_MAX
        return (t / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    if precision == "int8":
        scale = jnp.maximum(jnp.max(jnp.abs(t)), 1e-30) / 127.0
        return jnp.clip(jnp.round(t / scale), -127, 127) * scale
    raise ValueError(f"unknown precision {precision!r}")


def _product(fn, precision: str):
    """``fn(a, b)`` with both operands, and the cotangent on the way back,
    rounded to ``precision``; the arithmetic itself stays float32/highest."""
    if precision == "float32":
        return fn

    @jax.custom_vjp
    def f(a, b):
        return fn(_round(a, precision), _round(b, precision))

    def fwd(a, b):
        ra, rb = _round(a, precision), _round(b, precision)
        return fn(ra, rb), (ra, rb)

    def bwd(res, g):
        _, vjp = jax.vjp(fn, *res)
        return vjp(_round(g, precision))

    f.defvjp(fwd, bwd)
    return f


def conv(x, w, stride: int, precision: str):
    """'SAME' convolution (TensorFlow/XLA convention: the odd pad goes last)."""
    fn = functools.partial(lax.conv_general_dilated,
                           window_strides=(stride, stride), padding="SAME",
                           dimension_numbers=_DIMS, precision=_HI)
    return _product(lambda a, b: fn(a, b), precision)(x, w)


def dense(x, w, b, precision: str):
    return _product(lambda a, m: jnp.matmul(a, m, precision=_HI),
                    precision)(x, w) + b


def batch_norm(x, gamma, beta, eps: float = 1e-5):
    """Training-mode batch normalisation over (N, H, W): biased variance."""
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + eps) * gamma + beta


def max_pool(x, k: int, stride: int, padding: str):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, k, k, 1),
                             (1, stride, stride, 1), padding)


def softmax_xent(logits, onehot):
    """Mean over rows of the multi-class cross entropy."""
    return -jnp.mean(jnp.sum(onehot * jax.nn.log_softmax(logits, axis=-1),
                             axis=-1))


def he_normal(key, shape):
    fan_in = math.prod(shape[:-1])
    return jax.random.normal(key, shape, jnp.float32) * math.sqrt(2.0 / fan_in)


def xavier_normal(key, shape):
    fan_in, fan_out = math.prod(shape[:-1]), shape[-1]
    return jax.random.normal(key, shape, jnp.float32) * math.sqrt(
        2.0 / (fan_in + fan_out))


def conv_out(size: int, stride: int) -> int:
    return -(-size // stride)


def staged(x, stage_dtype):
    """The staging cast the traffic states, then back to float32."""
    if stage_dtype is None:
        return x.astype(jnp.float32)
    return x.astype(stage_dtype).astype(jnp.float32)


def nesterov(params, velocity, grads, lr: float, mu: float):
    """Nesterov momentum (Sutskever et al. 2013, as ND4J's Nesterovs applies
    it): v' = mu v - lr g;  p' = p + mu v' - lr g."""
    new_v = {k: mu * velocity[k] - lr * grads[k] for k in params}
    new_p = {k: params[k] + mu * new_v[k] - lr * grads[k] for k in params}
    return new_p, new_v


def leaf_norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def follow(loss_and_grad, params, batches, lr: float, mu: float):
    """Drive ``len(batches)`` optimizer steps from ``params`` and return what
    ``correct`` compares: each step's loss, and per leaf the norm of the
    velocity and of the parameters' change after the last step.

    ``loss_and_grad(params, x, y) -> (loss, grads)`` is one jitted program;
    the steps are separate calls so the peak is one step's."""
    step = jax.jit(lambda p, v, g: nesterov(p, v, g, lr, mu),
                   donate_argnums=(1,))
    p0 = params
    velocity = {k: jnp.zeros_like(v) for k, v in params.items()}
    losses = []
    for x, y in batches:
        loss, grads = loss_and_grad(params, x, y)
        params, velocity = step(params, velocity, grads)
        del grads
        losses.append(loss)
    norms = jax.jit(lambda a, b, v: (leaf_norms({k: a[k] - b[k] for k in a}),
                                     leaf_norms(v)))
    change, vel = norms(params, p0, velocity)
    return {"losses": [float(l) for l in losses],
            "velocity_norm": {k: float(v) for k, v in vel.items()},
            "change_norm": {k: float(v) for k, v in change.items()}}
