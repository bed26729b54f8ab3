"""Global dtype / platform policy.

The reference selects a tensor backend via Maven profiles (reference pom.xml:123-150,
nd4j-native vs nd4j-cuda). Here the analogous knob is the JAX platform plus a dtype
policy: parameters are kept in ``param_dtype`` (float32 by default for exact updater
semantics) while matmul/conv compute may run in ``compute_dtype`` (bfloat16 on the MXU).

Reduction precision is a first-class policy axis (round-5 lesson: 23% of the
bf16 ResNet-50 step sat in f32 statistics/grad reduce fusions the policy never
asked for):

* ``reduction_dtype`` — accumulator/operand dtype of normalization-statistics
  reductions (batch-norm mean/var, dgamma/dbeta). ``None`` means "at least
  f32" (the safe classic recipe); an explicit ``bfloat16`` keeps the stat
  passes convert-free on bf16 activations.
* ``grad_accum_dtype`` — ``preferred_element_type`` for the dense/conv
  contractions. JAX's transpose rules propagate it into the weight-gradient
  contractions, so an explicit ``float32`` here pins f32 accumulation of
  dW/dx even when both operands are bf16 (Micikevicius et al. mixed-precision
  accumulate-wide discipline) without any post-hoc upcast-reduce. ``None``
  leaves XLA's operand-dtype inference in charge (the pre-round-6 behavior).
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax.numpy as jnp


@dataclasses.dataclass
class DtypePolicy:
    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.float32
    output_dtype: jnp.dtype = jnp.float32
    # None = derived defaults (see module docstring); both knobs are read at
    # trace time like every other field, so policy_key() must include them
    reduction_dtype: jnp.dtype | None = None
    grad_accum_dtype: jnp.dtype | None = None

    def stat_dtype(self, x_dtype) -> jnp.dtype:
        """Dtype for normalization-statistics reductions on an ``x_dtype``
        tensor: the explicit ``reduction_dtype`` if set, else at-least-f32
        (which also keeps the float64 gradient-check path undowncast)."""
        if self.reduction_dtype is not None:
            # never downcast the f64 gradcheck path: a bf16 reduction policy
            # applies to bf16/f32 activations, not to x64 verification runs
            if jnp.finfo(x_dtype).bits > jnp.finfo(self.reduction_dtype).bits \
                    and jnp.finfo(x_dtype).bits > 32:
                return jnp.dtype(x_dtype)
            return jnp.dtype(self.reduction_dtype)
        return at_least_f32(x_dtype)


_POLICY = DtypePolicy()


def get_policy() -> DtypePolicy:
    return _POLICY


def _dtype_name(d) -> str | None:
    return None if d is None else jnp.dtype(d).name


def policy_key() -> tuple:
    """Hashable identity of the active policy. Networks key their compiled-
    program caches on this: the policy is read at trace time, so a cached
    program silently pins whatever policy was active at first call unless the
    cache key includes it."""
    return (jnp.dtype(_POLICY.param_dtype).name,
            jnp.dtype(_POLICY.compute_dtype).name,
            jnp.dtype(_POLICY.output_dtype).name,
            _dtype_name(_POLICY.reduction_dtype),
            _dtype_name(_POLICY.grad_accum_dtype))


def effective_policy_key(conf_dtype: str | None) -> tuple:
    """The cache key under which a traced program's dtypes are decided.

    A config-declared dtype (GlobalConf.dtype, applied via wrap_with_policy)
    pins the program regardless of the ambient global policy — so such
    programs must NOT be invalidated or re-keyed when the global policy
    changes. Every compiled-program cache in the framework keys on this one
    helper so the rule can't diverge between sites."""
    return (conf_dtype,) if conf_dtype else (None,) + policy_key()


_UNSET = object()


def set_policy(param_dtype=None, compute_dtype=None, output_dtype=None,
               reduction_dtype=_UNSET, grad_accum_dtype=_UNSET) -> DtypePolicy:
    """Update the global policy. The three storage/compute dtypes keep their
    current value when None (they are never legitimately None); the two
    reduction knobs use an explicit unset sentinel because None IS a
    meaningful value for them ("derive the default")."""
    global _POLICY
    _POLICY = DtypePolicy(
        param_dtype=param_dtype or _POLICY.param_dtype,
        compute_dtype=compute_dtype or _POLICY.compute_dtype,
        output_dtype=output_dtype or _POLICY.output_dtype,
        reduction_dtype=(_POLICY.reduction_dtype if reduction_dtype is _UNSET
                         else reduction_dtype),
        grad_accum_dtype=(_POLICY.grad_accum_dtype if grad_accum_dtype is _UNSET
                          else grad_accum_dtype),
    )
    return _POLICY


def accum_dtype(operand_dtype) -> jnp.dtype | None:
    """``preferred_element_type`` for policy-routed contractions (dense/conv
    forward ops — JAX transpose rules carry it into the weight-grad
    contractions). Returns the policy's ``grad_accum_dtype`` only when it
    WIDENS the operands; already-wide operands (plain f32 runs, the f64
    gradient-check path) return None and lower exactly as before."""
    g = _POLICY.grad_accum_dtype
    if g is None:
        return None
    if jnp.finfo(operand_dtype).bits >= jnp.finfo(g).bits:
        return None
    return jnp.dtype(g)


def at_least_f32(dtype) -> jnp.dtype:
    """The dtype to run precision-critical reductions (norm statistics, loss
    entry points) in: float32 when activations flow as bf16/f16, otherwise the
    incoming dtype unchanged (the float64 gradient-check path must not be
    downcast)."""
    return dtype if jnp.finfo(dtype).bits >= 32 else jnp.dtype(jnp.float32)


def bf16_matmul_policy() -> DtypePolicy:
    """bfloat16 compute on the MXU, float32 params/outputs."""
    return set_policy(compute_dtype=jnp.bfloat16)


_NAMED_POLICIES = {
    "float32": DtypePolicy(),
    "bfloat16": DtypePolicy(compute_dtype=jnp.bfloat16),
    "bfloat16_full": DtypePolicy(compute_dtype=jnp.bfloat16,
                                 output_dtype=jnp.bfloat16),
    # the flagship training recipe: bf16 storage/IO AND bf16 single-pass
    # statistics (no standalone f32 upcast-reduce fusions), with weight-grad
    # contractions pinned to f32 accumulation so updater numerics hold
    "bfloat16_flagship": DtypePolicy(compute_dtype=jnp.bfloat16,
                                     output_dtype=jnp.bfloat16,
                                     reduction_dtype=jnp.bfloat16,
                                     grad_accum_dtype=jnp.float32),
}


def resolve_policy(name: str) -> DtypePolicy:
    """Named policy for the config DSL's ``dtype`` field."""
    key = str(name).lower()
    if key not in _NAMED_POLICIES:
        raise ValueError(f"Unknown dtype policy '{name}'. "
                         f"Known: {sorted(_NAMED_POLICIES)}")
    return _NAMED_POLICIES[key]


@contextlib.contextmanager
def override_policy(name: str):
    """Temporarily install a named policy. Wrapped around function BODIES that
    jit traces (wrap_with_policy): tracing runs the body under the override,
    baking the dtype into the compiled program; execution never re-enters the
    Python body, so the global policy is untouched at run time."""
    global _POLICY
    saved = _POLICY
    _POLICY = resolve_policy(name)
    try:
        yield
    finally:
        _POLICY = saved


def wrap_with_policy(fn, name: str | None):
    """Make ``fn`` trace under the named policy (no-op when name is None)."""
    if not name:
        return fn

    def wrapped(*args, **kwargs):
        with override_policy(name):
            return fn(*args, **kwargs)
    # jax.jit names the compiled module after the function it is given: keep
    # ``fn``'s name, so a profile shows the program and not "jit_wrapped"
    wrapped.__name__ = getattr(fn, "__name__", wrapped.__name__)
    return wrapped


def full_bf16_policy() -> DtypePolicy:
    """bfloat16 compute AND activations; float32 params, optimizer state and
    norm statistics.

    Halves activation HBM traffic vs :func:`bf16_matmul_policy` (each layer
    otherwise materializes its output back to float32). Precision-critical
    reductions stay float32 regardless of this policy: batch-norm/layer-norm
    statistics and every registered loss upcast internally (custom callable
    losses are wrapped the same way by ``ops.losses.get_loss``), and gradients
    follow the float32 param dtype, so updater semantics are unchanged.
    VariationalAutoencoder's encoder/decoder matmuls use raw float32 params
    and stay float32 under any policy; AutoEncoder/RBM route through the
    shared dense kernel and follow the policy like every other layer.
    For bf16 statistics too (the measured flagship recipe), use
    :func:`flagship_bf16_policy` / the ``"bfloat16_flagship"`` named policy.
    """
    return set_policy(compute_dtype=jnp.bfloat16, output_dtype=jnp.bfloat16,
                      reduction_dtype=None, grad_accum_dtype=None)


def flagship_bf16_policy() -> DtypePolicy:
    """The measured flagship training recipe (``"bfloat16_flagship"``):
    everything :func:`full_bf16_policy` does, PLUS bf16 single-pass
    normalization statistics (kills the standalone f32 upcast-reduce fusions
    — 23% of ResNet-50 bf16 device time in the r5 profile) and f32-pinned
    weight-gradient accumulation via ``preferred_element_type``."""
    return set_policy(compute_dtype=jnp.bfloat16, output_dtype=jnp.bfloat16,
                      reduction_dtype=jnp.bfloat16,
                      grad_accum_dtype=jnp.float32)
