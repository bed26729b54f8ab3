"""Expert parallelism: GShard-style all_to_all MoE dispatch over a mesh axis.

Tokens are sharded over the ``expert`` mesh axis (it doubles as a data axis,
the standard EP layout); experts are sharded over the same axis. Each shard
routes its local tokens, packs them into per-expert capacity buffers with a
one-hot dispatch tensor, all_to_alls the buffers so every device receives the
tokens bound for ITS experts from every shard, applies its local experts'
FFNs, all_to_alls back, and combines with the gate weights. Exactly matches
the dense MoELayer math whenever no expert overflows its capacity
(capacity_factor sizes the buffers; overflowing tokens are dropped, as in
GShard/Switch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh
from deeplearning4j_tpu.parallel.partition import (
    pspec as P, named_sharding as _named_sharding,
)
from deeplearning4j_tpu.observability.names import COLLECTIVE_BYTES_PER_STEP
from deeplearning4j_tpu.observability.metrics import (
    global_registry as _obs_registry,
)

# trace-time traffic gauge (see parallel/ring_attention.py: the local bodies
# run inside jit traces, so traffic is sized from static shapes per trace)
_collective_per_step = _obs_registry().gauge(
    COLLECTIVE_BYTES_PER_STEP,
    "bytes one executed step moves through a traced collective, from "
    "static shapes at trace time, by op and site")

Array = jax.Array


def _moe_local(router_params, expert_params, x, rng, *, layer,
               axis_name: str, capacity: int, train: bool,
               mean_axes=None):
    """Per-shard body. x: [Bl, T, F] local tokens; expert_params hold this
    shard's experts on the leading axis [E_local, ...]. Returns (y, aux)
    where aux is the GLOBAL Switch load-balance term E * sum_e f_e * P_e
    (token fractions / router probs pmean-ed over the shards — every shard
    holds the same token count, so the pmean of local means is the global
    mean), matching MoELayer._balance_term on the full batch. ``rng``
    (replicated) is folded per shard so router_noise jitters each shard's
    routing independently at train time, like the dense path's jitter —
    same distribution, different draws than single-device."""
    N = lax.psum(1, axis_name)
    E_local = expert_params["W1"].shape[0]
    E = N * E_local
    Bl, T, F = x.shape
    S = Bl * T
    x2d = x.reshape(S, F)

    mean_axes = mean_axes or (axis_name,)
    rng_local = rng
    if rng is not None:
        for ax in mean_axes:
            rng_local = jax.random.fold_in(rng_local, lax.axis_index(ax))
    eidx, gate, probs = layer.route(router_params, x2d, train=train,
                                    rng=rng_local)
    frac = lax.pmean(jnp.mean(jax.nn.one_hot(eidx, E, dtype=jnp.float32),
                              axis=0), mean_axes)
    p_mean = lax.pmean(jnp.mean(probs.astype(jnp.float32), axis=0), mean_axes)
    aux = E * jnp.sum(frac * p_mean)
    # routing/position arithmetic is exact int32/float32 bookkeeping: under
    # the full-bf16 activation policy x2d.dtype can only count to 256 before
    # cumsum slots collide and tokens silently overwrite each other
    sel = jax.nn.one_hot(eidx, E, dtype=jnp.float32)            # [S, E]
    # position of each token within its expert's capacity buffer
    pos = (jnp.cumsum(sel, axis=0) - 1.0) * sel                 # [S, E]
    in_cap = sel * (pos < capacity)
    pos_oh = (jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                             dtype=jnp.float32)
              * in_cap[..., None]).astype(x2d.dtype)            # [S, E, C]
    # pack: [E, C, F] buffers of this shard's tokens per destination expert
    buf = jnp.einsum("sec,sf->ecf", pos_oh, x2d)
    # exchange: every device gets its experts' buffers from every shard.
    # [E, C, F] -> [N, E_local, C, F]; all_to_all over the leading shard axis.
    buf = buf.reshape(N, E_local, capacity, F)
    buf = lax.all_to_all(buf, axis_name, split_axis=0, concat_axis=0,
                         tiled=False)                           # [N, El, C, F]
    # apply local experts to tokens from all shards
    buf = buf.transpose(1, 0, 2, 3).reshape(E_local, N * capacity, F)
    out = layer.expert_ffn(expert_params, buf)                  # [El, N*C, F]
    out = out.reshape(E_local, N, capacity, F).transpose(1, 0, 2, 3)
    out = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                         tiled=False)                           # [N=E grouping back]
    out = out.reshape(E, capacity, F)
    # combine: gather each token's result from its (expert, slot) and gate it
    # (gate cast so the f32 router bookkeeping can't promote the activations)
    y = jnp.einsum("sec,ecf->sf", pos_oh, out) * gate[:, None].astype(out.dtype)
    return y.astype(x2d.dtype).reshape(Bl, T, F), aux


def expert_parallel_ffn(layer, params: dict, x: Array, mesh: Mesh,
                        axis_name: str, capacity_factor: float = 2.0,
                        train: bool = False, rng=None,
                        seq_axis: str = None):
    """Trace-safe GShard dispatch: the in-jit target MoELayer.apply uses when
    an active ParallelContext declares an expert axis (parallel/context.py).

    x: [B, T, F] (or [S, F], treated as T=1) with B divisible by the axis
    size. Returns (y, aux) — y WITHOUT the layer's output activation (callers
    apply it exactly where their dense path does), aux the global Switch
    load-balance term. Under jit, GSPMD reshards operands to the shard_map
    in_specs, so this composes with the data-parallel wrapper step where the
    data axis doubles as the expert axis (the standard EP layout).
    """
    n = mesh.shape[axis_name]
    squeeze = x.ndim == 2
    if squeeze:
        x = x[:, None, :]
    B, T, F = x.shape
    if B % n:
        raise ValueError(f"batch {B} not divisible by expert axis size {n}")
    # composing with sequence parallelism: shard T over the seq axis too so
    # sp shards route disjoint token slices instead of all-gathering the
    # full sequence and redundantly recomputing the FFN on every sp shard
    if seq_axis is not None and (seq_axis == axis_name
                                 or T % mesh.shape[seq_axis]):
        seq_axis = None
    n_seq = mesh.shape[seq_axis] if seq_axis else 1
    x_spec = P(axis_name, seq_axis) if seq_axis else P(axis_name)
    mean_axes = (axis_name,) + ((seq_axis,) if seq_axis else ())
    capacity = max(1, int(capacity_factor * (B // n) * (T // n_seq)
                          / layer.n_experts))
    # two all-to-alls (dispatch + return) on per-shard [N, E_local, C, F]
    # capacity buffers, across all n shards
    _collective_per_step.labels(op="all_to_all", site="moe_dispatch").set(
        2 * n * n * (layer.n_experts // n) * capacity * F
        * jnp.dtype(x.dtype).itemsize)
    router = {"Wg": params["Wg"]}
    experts = {k: params[k] for k in ("W1", "b1", "W2", "b2")}
    # router noise needs an rng; without one the routing is deterministic,
    # so a placeholder key + train=False keeps the operand list static
    if rng is None:
        rng, train = jax.random.PRNGKey(0), False
    fn = jax.shard_map(
        functools.partial(_moe_local, layer=layer, axis_name=axis_name,
                          capacity=capacity, train=train,
                          mean_axes=mean_axes),
        mesh=mesh,
        in_specs=({"Wg": P()}, {k: P(axis_name) for k in experts},
                  x_spec, P()),
        out_specs=(x_spec, P()),
    )
    y, aux = fn(router, experts, x, rng)
    if squeeze:
        y = y[:, 0, :]
    return y, aux


class ExpertParallelMoE:
    """Run a MoELayer's parameters expert-parallel over ``axis_name``."""

    def __init__(self, layer, mesh: Mesh, axis_name: str = "expert",
                 capacity_factor: float = 2.0):
        self.layer = layer
        self.mesh = mesh
        self.axis_name = axis_name
        self.capacity_factor = capacity_factor
        n = mesh.shape[axis_name]
        if layer.n_experts % n:
            raise ValueError(f"{layer.n_experts} experts not divisible by "
                             f"mesh axis size {n}")

    def __call__(self, params: dict, x: Array) -> Array:
        """x: [B, T, F] with B divisible by the axis size. Returns [B, T, F]."""
        router = jax.device_put({"Wg": params["Wg"]},
                                {"Wg": _named_sharding(self.mesh, P())})
        experts = jax.device_put(
            {k: params[k] for k in ("W1", "b1", "W2", "b2")},
            {k: _named_sharding(self.mesh, P(self.axis_name))
             for k in ("W1", "b1", "W2", "b2")})
        x = jax.device_put(x, _named_sharding(self.mesh, P(self.axis_name)))
        y, _ = expert_parallel_ffn(self.layer, {**router, **experts}, x,
                                   self.mesh, self.axis_name,
                                   self.capacity_factor)
        # same epilogue as the dense MoELayer.apply (activation after combine)
        return self.layer.act_fn()(y)
