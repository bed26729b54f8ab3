"""Loss functions.

Parity with the reference's ``ILossFunction`` family (ND4J org.nd4j.linalg.lossfunctions,
used by output-layer configs, reference nn/conf/layers/OutputLayer.java). Each loss is a
pure function ``loss(labels, preout, activation, mask) -> scalar mean score`` where
``preout`` is the pre-activation output of the final layer; applying the activation inside
the loss lets us use numerically-stable fused forms (softmax+CE, sigmoid+BCE) — the
TPU-native equivalent of the reference's computeGradient analytic pairings.

Per-example scores (for masking and per-output weighting) are computed then mean-reduced
over batch; mask arrays broadcast over the output dim (reference BaseEvaluation masking).
"""
from __future__ import annotations

import os
from typing import Callable, Optional

import jax
import jax.numpy as jnp

Array = jax.Array


def _reduce(per_example: Array, mask: Optional[Array]) -> Array:
    """Mean over examples (per_example has trailing dim 1), honoring an optional
    {0,1} mask over the leading (batch[, time]) dims."""
    if mask is None:
        return jnp.mean(per_example)
    mask = mask.astype(per_example.dtype)
    while mask.ndim < per_example.ndim:
        mask = mask[..., None]
    return jnp.sum(per_example * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def mse(labels: Array, preout: Array, activation, mask=None) -> Array:
    out = activation(preout)
    per = jnp.sum((labels - out) ** 2, axis=-1, keepdims=True) / labels.shape[-1]
    return _reduce(per, mask)


def l2(labels: Array, preout: Array, activation, mask=None) -> Array:
    out = activation(preout)
    per = jnp.sum((labels - out) ** 2, axis=-1, keepdims=True)
    return _reduce(per, mask)


def mae(labels: Array, preout: Array, activation, mask=None) -> Array:
    out = activation(preout)
    per = jnp.sum(jnp.abs(labels - out), axis=-1, keepdims=True) / labels.shape[-1]
    return _reduce(per, mask)


def l1(labels: Array, preout: Array, activation, mask=None) -> Array:
    out = activation(preout)
    per = jnp.sum(jnp.abs(labels - out), axis=-1, keepdims=True)
    return _reduce(per, mask)


def mape(labels: Array, preout: Array, activation, mask=None) -> Array:
    out = activation(preout)
    per = 100.0 * jnp.sum(jnp.abs((labels - out) / jnp.where(labels == 0, 1e-8, labels)),
                          axis=-1, keepdims=True) / labels.shape[-1]
    return _reduce(per, mask)


def msle(labels: Array, preout: Array, activation, mask=None) -> Array:
    out = activation(preout)
    per = jnp.sum((jnp.log1p(jnp.maximum(labels, 0)) - jnp.log1p(jnp.maximum(out, -0.999999))) ** 2,
                  axis=-1, keepdims=True) / labels.shape[-1]
    return _reduce(per, mask)


def _is_softmax(activation) -> bool:
    return getattr(activation, "__name__", "") in ("softmax", "logsoftmax")


def _is_sigmoid(activation) -> bool:
    return getattr(activation, "__name__", "") == "sigmoid"


@jax.custom_vjp
def _fused_sm_xent_per(labels: Array, preout: Array) -> Array:
    """Per-row softmax cross entropy through the Pallas fused kernel: the
    forward computes loss AND dlogits in ONE pass over the logits
    (ops/pallas_kernels.softmax_cross_entropy), and the backward replays the
    saved gradient instead of re-deriving softmax from a stored log-softmax
    — the cuDNN softmax-loss pairing, TPU form. labels/preout: (N, C);
    returns (N, 1) f32."""
    from deeplearning4j_tpu.ops.pallas_kernels import softmax_cross_entropy

    loss, _ = softmax_cross_entropy(preout, labels,
                                    interpret=_xent_interpret())
    return loss[:, None]


def _xent_interpret() -> bool:
    """DL4J_XENT_INTERPRET=1 runs the fused kernel in interpret mode — the
    CPU test hook (loss code has no kwarg path down to the kernel), like
    DL4J_LSTM_INTERPRET. Never on by itself."""
    return os.environ.get("DL4J_XENT_INTERPRET") == "1"


def _fused_sm_xent_fwd(labels, preout):
    from deeplearning4j_tpu.ops.pallas_kernels import softmax_cross_entropy

    loss, grad = softmax_cross_entropy(preout, labels,
                                       interpret=_xent_interpret())
    return loss[:, None], grad


def _fused_sm_xent_bwd(grad, ct):
    # labels are data in LossMCXENT (reference semantics) — zero cotangent;
    # dpreout = ct * (softmax - labels), saved from the forward pass
    d = ct.astype(jnp.float32) * grad.astype(jnp.float32)
    return jnp.zeros_like(grad), d.astype(grad.dtype)


_fused_sm_xent_per.defvjp(_fused_sm_xent_fwd, _fused_sm_xent_bwd)


def _fused_xent_engaged(preout: Array) -> bool:
    """Engaged exactly when the other pallas kernels are (use_pallas()), or
    when a test asked for interpret mode; DL4J_FUSED_XENT=0 disables. Read
    at call time like every other kill switch in the tree. Whether the
    kernel itself then runs is the shape gate's call, counted on
    dl4j_pallas_dispatch_total (pallas_kernels.softmax_cross_entropy)."""
    if os.environ.get("DL4J_FUSED_XENT") == "0":
        return False
    if preout.dtype not in (jnp.float32, jnp.bfloat16):
        return False  # f64 gradient checks stay on the exact autodiff path
    from deeplearning4j_tpu.ops.pallas_kernels import use_pallas

    return _xent_interpret() or use_pallas()


@jax.custom_vjp
def sparse_softmax_xent(logits: Array, labels: Array) -> Array:
    """Per-row softmax cross entropy against integer class ids, with no
    one-hot of the classes anywhere: ``logits`` (..., C) in any float dtype,
    ``labels`` (...) integers -> (...) float32.

    The logits stay in the dtype they arrive in (bfloat16 under
    ``bfloat16_full``: 16,384 x 12,800 float32 logits would be 0.84 GB);
    the max, the sum of exponentials and the picked logit are float32
    reductions over them, and the backward writes ``(softmax - onehot) * ct``
    straight back in the logits' dtype from the saved log-sum-exp."""
    return _sparse_xent_fwd(logits, labels)[0]


def _sparse_xent_fwd(logits, labels):
    f32 = jnp.promote_types(logits.dtype, jnp.float32)
    m = jnp.max(logits, axis=-1, keepdims=True).astype(f32)
    z = jnp.sum(jnp.exp(logits.astype(f32) - m), axis=-1)
    lse = m[..., 0] + jnp.log(z)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - picked.astype(f32), (logits, labels, lse)


def _sparse_xent_bwd(res, ct):
    logits, labels, lse = res
    f32 = lse.dtype
    p = jnp.exp(logits.astype(f32) - lse[..., None])
    hit = labels[..., None] == jax.lax.broadcasted_iota(
        labels.dtype, logits.shape, logits.ndim - 1)
    d = (p - hit.astype(f32)) * ct[..., None].astype(f32)
    return d.astype(logits.dtype), None


sparse_softmax_xent.defvjp(_sparse_xent_fwd, _sparse_xent_bwd)


def _is_class_ids(labels: Array, preout: Array) -> bool:
    """Integer labels one rank below the logits: class ids, not one-hot."""
    return (jnp.issubdtype(labels.dtype, jnp.integer)
            and labels.ndim == preout.ndim - 1)


def mcxent(labels: Array, preout: Array, activation, mask=None) -> Array:
    """Multi-class cross entropy (reference LossMCXENT). Fused log-softmax when the
    output activation is softmax (the common OutputLayer pairing); on TPU the
    per-row loss+gradient ride the fused Pallas kernel via custom_vjp.
    Integer ``labels`` one rank below ``preout`` are class ids: the loss is
    :func:`sparse_softmax_xent` and no one-hot is built."""
    if _is_class_ids(labels, preout):
        if not _is_softmax(activation):
            raise ValueError("integer class-id labels need a softmax output "
                             "activation (mcxent over logits)")
        per = sparse_softmax_xent(preout, labels.astype(jnp.int32))
        return _reduce(per[..., None], mask)
    if _is_softmax(activation):
        if _fused_xent_engaged(preout):
            C = preout.shape[-1]
            # labels cast to the logits dtype BEFORE the custom_vjp call:
            # bwd's zero labels-cotangent must match the primal aval (int
            # one-hot labels would otherwise crash jax.grad)
            per = _fused_sm_xent_per(
                labels.reshape(-1, C).astype(preout.dtype),
                preout.reshape(-1, C))
            per = per.reshape(preout.shape[:-1] + (1,)).astype(preout.dtype)
            return _reduce(per, mask)
        logp = jax.nn.log_softmax(preout, axis=-1)
    else:
        out = activation(preout)
        logp = jnp.log(jnp.clip(out, 1e-10, 1.0))
    per = -jnp.sum(labels * logp, axis=-1, keepdims=True)
    return _reduce(per, mask)


def negativeloglikelihood(labels, preout, activation, mask=None) -> Array:
    return mcxent(labels, preout, activation, mask)


def xent(labels: Array, preout: Array, activation, mask=None) -> Array:
    """Binary cross entropy (reference LossBinaryXENT). Fused stable form for sigmoid."""
    if _is_sigmoid(activation):
        # log(sigmoid(x)) = -softplus(-x); log(1-sigmoid(x)) = -softplus(x)
        per = jnp.sum(labels * jax.nn.softplus(-preout) + (1 - labels) * jax.nn.softplus(preout),
                      axis=-1, keepdims=True)
    else:
        out = jnp.clip(activation(preout), 1e-10, 1 - 1e-10)
        per = -jnp.sum(labels * jnp.log(out) + (1 - labels) * jnp.log(1 - out),
                       axis=-1, keepdims=True)
    return _reduce(per, mask)


def hinge(labels: Array, preout: Array, activation, mask=None) -> Array:
    out = activation(preout)
    # labels in {-1, +1} or {0, 1} (mapped)
    y = jnp.where(labels <= 0, -1.0, 1.0)
    per = jnp.sum(jnp.maximum(0.0, 1.0 - y * out), axis=-1, keepdims=True)
    return _reduce(per, mask)


def squared_hinge(labels: Array, preout: Array, activation, mask=None) -> Array:
    out = activation(preout)
    y = jnp.where(labels <= 0, -1.0, 1.0)
    per = jnp.sum(jnp.maximum(0.0, 1.0 - y * out) ** 2, axis=-1, keepdims=True)
    return _reduce(per, mask)


def kl_divergence(labels: Array, preout: Array, activation, mask=None) -> Array:
    out = jnp.clip(activation(preout), 1e-10, 1.0)
    lbl = jnp.clip(labels, 1e-10, 1.0)
    per = jnp.sum(lbl * (jnp.log(lbl) - jnp.log(out)), axis=-1, keepdims=True)
    return _reduce(per, mask)


def poisson(labels: Array, preout: Array, activation, mask=None) -> Array:
    out = jnp.maximum(activation(preout), 1e-10)
    per = jnp.sum(out - labels * jnp.log(out), axis=-1, keepdims=True)
    return _reduce(per, mask)


def cosine_proximity(labels: Array, preout: Array, activation, mask=None) -> Array:
    out = activation(preout)
    ln = jnp.linalg.norm(labels, axis=-1, keepdims=True)
    on = jnp.linalg.norm(out, axis=-1, keepdims=True)
    per = -jnp.sum(labels * out, axis=-1, keepdims=True) / jnp.maximum(ln * on, 1e-10)
    return _reduce(per, mask)


LOSSES: dict[str, Callable] = {
    "mse": mse,
    "l2": l2,
    "mae": mae,
    "l1": l1,
    "mape": mape,
    "msle": msle,
    "mcxent": mcxent,
    "negativeloglikelihood": negativeloglikelihood,
    "xent": xent,
    "hinge": hinge,
    "squared_hinge": squared_hinge,
    "squaredhinge": squared_hinge,
    "kl_divergence": kl_divergence,
    "kld": kl_divergence,
    "reconstruction_crossentropy": xent,
    "poisson": poisson,
    "cosine_proximity": cosine_proximity,
}


def _f32_entry(fn: Callable) -> Callable:
    """Losses compute in at least float32. Under the full-bf16 activation
    policy the network hands the output layer bfloat16 pre-activations;
    log/exp/div in the loss are where reduced precision actually hurts (and
    the upcast is one elementwise op on (B, C) logits — free next to the
    savings upstream). Never downcasts: the float64 gradient-check path
    (nn/gradientcheck.py) flows through unchanged."""
    from deeplearning4j_tpu.common import at_least_f32

    def _upcast(a: Array) -> Array:
        if jnp.issubdtype(a.dtype, jnp.floating):
            return a.astype(at_least_f32(a.dtype))
        return a

    def wrapped(labels, preout, activation, mask=None):
        labels, preout = jnp.asarray(labels), jnp.asarray(preout)
        if (fn in (mcxent, negativeloglikelihood)
                and _is_class_ids(labels, preout)):
            # class ids: the loss reduces in float32 itself and never
            # materialises float32 logits (sparse_softmax_xent)
            return fn(labels, preout, activation, mask)
        return fn(_upcast(labels), _upcast(preout), activation, mask)
    return wrapped


def get_loss(name) -> Callable:
    if callable(name):
        return _f32_entry(name)
    key = str(name).lower()
    if key not in LOSSES:
        raise ValueError(f"Unknown loss '{name}'. Known: {sorted(LOSSES)}")
    return _f32_entry(LOSSES[key])
