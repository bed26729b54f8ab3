"""Mixture-of-Experts layers: the language models' top-k dropless expert
products and the top-1 (Switch-style) layer.

**Top-k, dropless** (``grouped_expert_ffn``, called by ``DecoderBlock``'s
``ffn="moe"`` for the experts one chip of an expert-parallel group holds):
the (token, choice) pairs routed here are sorted by expert into a row buffer
a quarter of all pairs long (all of them when one expert draws more), the
three gated feed-forward products run grouped over the held experts
(megablox ``gmm`` on a TPU, ``jax.lax.ragged_dot`` elsewhere), the pair's
weight multiplies the down projection's input, and the buffer's rows are
summed into their tokens' rows. Both trips between tokens and buffer touch
the buffer's rows only: the way in is a gather of that many rows
(``_take_token_rows``), the way out their sum by token
(``_sum_token_rows``: on a TPU one gather into token order and a 0/1
selector's grouped product over tiles of tokens, elsewhere a segment sum),
and each is the other's cotangent.

**Top-1** (``MoELayer``, ``MoETransformerBlock``). Absent in the reference;
part of the TPU-native parallelism surface (expert parallelism — SURVEY.md
§2.4 note). The layer itself is mesh-agnostic: the dense ``apply`` computes
the routed FFN on one device (every expert evaluated via batched einsum —
fine at test scale), while ``parallel/moe.py::ExpertParallelMoE`` runs the
same parameters across an ``expert`` mesh axis with all_to_all
dispatch/combine (GShard-style) and matches the dense math exactly when no
tokens overflow capacity.

Params: "Wg" [F, E] router; experts batched on the leading axis —
"W1" [E, F, H], "b1" [E, H], "W2" [E, H, F], "b2" [E, F].
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.common import accum_dtype, at_least_f32, get_policy
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.base import FeedForwardLayer
from deeplearning4j_tpu.nn.conf.serde import register_config


#: tokens a group of the return trips' grouped product sums into (the rows of
#: its 0/1 selector), and the buffer rows one of its steps reads
TOKEN_TILE = 256


def _buffer_plan(token, n_routed, pair_held, by_tiles: bool):
    """What both trips between the tokens and a row buffer need, ``(token,
    n_routed, tiles)``: the buffer's i-th row is ``token[i]``'s for ``i <
    n_routed`` and no pair's from there on. ``tiles`` is None, or with
    ``by_tiles`` (the TPU kernel; S a multiple of ``TOKEN_TILE``) ``(by_token,
    sizes)``: the rows' order by token with the rows past ``n_routed`` last,
    and how many live rows each tile of ``TOKEN_TILE`` tokens has, from
    ``pair_held`` [k, S] (which pairs are routed here: all of them are in
    the buffer)."""
    if not by_tiles:
        return token, n_routed, None
    k, S = pair_held.shape
    live = jnp.arange(token.shape[0]) < n_routed
    by_token = jnp.argsort(jnp.where(live, token, S)).astype(jnp.int32)
    sizes = jnp.sum(pair_held.reshape(k, -1, TOKEN_TILE), axis=(0, 2),
                    dtype=jnp.int32)
    return token, n_routed, (by_token, sizes)


def _sum_rows_by_token(rows, plan, S: int, interpret: bool = False):
    """Both return trips out of the row buffer: ``y[s] = sum of rows[i]``
    over the buffer's rows ``i < n_routed`` with ``token[i] == s``, [S, F] in
    ``rows``' dtype, summed in float32. It touches the buffer's M rows, never
    all ``S * k`` pairs. Rows from ``n_routed`` on may hold anything (a
    grouped product leaves them unwritten): they are selected away, never
    multiplied.

    Without ``tiles`` in the plan: XLA's segment sum (off the TPU, in a
    partitioned jit; a TPU's scatter-add takes 120 ns a 4 KB row). With
    them, one gather puts the rows in token order, then a tile's tokens are
    a 0/1 selector's product with the tile's rows: a grouped product whose
    groups are the token tiles (megablox ``tgmm``, which masks a step's rows
    outside its group and visits no row past the groups' sum)."""
    token, n_routed, tiles = plan
    if tiles is None:
        live = jnp.arange(rows.shape[0])[:, None] < n_routed
        return jax.ops.segment_sum(
            jnp.where(live, rows.astype(at_least_f32(rows.dtype)), 0), token,
            num_segments=S).astype(rows.dtype)
    import importlib

    # the kernels' own module: the package's attribute ``gmm`` is the
    # differentiable function, which hides the module of that name
    tgmm = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm").tgmm
    by_token, sizes = tiles
    at = token[by_token] % TOKEN_TILE
    select = (at[None, :] == jnp.arange(TOKEN_TILE, dtype=at.dtype)[:, None]
              ).astype(rows.dtype)
    y = tgmm(select, rows[by_token], sizes, rows.dtype,
             (TOKEN_TILE, TOKEN_TILE, min(rows.shape[1], 2048)),
             interpret=interpret)
    return y.reshape(S, rows.shape[1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _take_token_rows(x2d, plan, interpret: bool = False):
    """The way into the row buffer, ``rows[i] = x2d[token[i]]``: a gather of
    M rows. Its cotangent is the way out, which drops the rows from
    ``n_routed`` on."""
    return x2d[plan[0]]


def _take_fwd(x2d, plan, interpret):
    return x2d[plan[0]], (plan, x2d.shape[0])


def _take_bwd(interpret, res, g):
    plan, S = res
    return _sum_rows_by_token(g, plan, S, interpret), None


_take_token_rows.defvjp(_take_fwd, _take_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _sum_token_rows(rows, plan, S: int, interpret: bool = False):
    """The way out of the row buffer, every token's sum of its rows
    (``_sum_rows_by_token``). Its cotangent is the way in: a token's row to
    each of its buffer rows (a row that carries no pair gets some token's
    too: no grouped product reads it)."""
    return _sum_rows_by_token(rows, plan, S, interpret)


def _sum_fwd(rows, plan, S, interpret):
    return _sum_rows_by_token(rows, plan, S, interpret), plan[0]


def _sum_bwd(S, interpret, token, g):
    return g[token], None


_sum_token_rows.defvjp(_sum_fwd, _sum_bwd)


#: row tile of the grouped products: a group's rows are computed in whole
#: tiles, so ``computed rows`` counts up to one tile of padding a group
GROUP_ROW_TILE = 512


def _kernel_engaged(m: int, dtype) -> bool:
    """Whether the grouped products of an ``m``-row buffer run as the Pallas
    kernel: on a TPU, outside a partitioned jit, bfloat16 or float32, and a
    row count the tile divides."""
    from deeplearning4j_tpu.ops.pallas_kernels import use_pallas

    return (use_pallas() and m % GROUP_ROW_TILE == 0
            and dtype in (jnp.bfloat16, jnp.float32))


def _grouped_matmul(lhs, rhs, group_sizes, kernel: bool):
    """``lhs[rows of group g] @ rhs[g]`` for every group: [M, K] x [G, K, N]
    -> [M, N], rows sorted by group, float32 accumulation, output in
    ``lhs``'s dtype. ``kernel``: the Pallas grouped product (JAX's megablox
    ``gmm``: it visits only the row tiles that hold a group's rows, and its
    backward is two more grouped products); else ``jax.lax.ragged_dot``.
    Rows past ``sum(group_sizes)`` are not computed by either: the kernel
    leaves them unwritten, ``ragged_dot`` zero."""
    if kernel:
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        # tiles sized for the 16 MiB of scoped VMEM: two buffers each of
        # an lhs, an rhs and an out tile, and the float32 accumulator
        k, n = lhs.shape[1], rhs.shape[-1]
        tk = 1024 if lhs.dtype.itemsize == 2 else 512
        return gmm(lhs, rhs, group_sizes, lhs.dtype,
                   (GROUP_ROW_TILE, min(k, tk), min(n, 1024)))
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=accum_dtype(lhs.dtype)
                              ).astype(lhs.dtype)


def _tiled_rows(group_sizes):
    """Rows the kernel runs over, padding included: every row tile a
    non-empty group touches, counted once per group that touches it (as the
    kernel visits them). Equal to the routed rows only where every group
    starts and ends on a tile boundary."""
    ends = jnp.cumsum(group_sizes)
    starts = ends - group_sizes
    t = GROUP_ROW_TILE
    tiles = jnp.where(group_sizes > 0, (ends + t - 1) // t - starts // t, 0)
    return jnp.sum(tiles) * t


def _usual_bound(pairs: int, eighths: int = 2) -> int:
    """The smaller of the dispatch buffer's two static sizes: ``eighths``
    eighths of all pairs in whole row tiles (by default a quarter: with an
    even router and an eighth of the experts held, twice what is routed
    here), or all of them where that is less than a tile."""
    share = -(-pairs * eighths // (8 * GROUP_ROW_TILE)) * GROUP_ROW_TILE
    return share if GROUP_ROW_TILE <= share < pairs else pairs


def grouped_expert_ffn(x2d, choice, weight, w_gate, w_up, w_down,
                       first_held: int = 0, usual_eighths: int = 2):
    """The routed experts' part of a top-k expert layer for the experts held
    here, dropless: ``y[t] = sum over t's choices e held here of
    weight[t, e] * E_e(x[t])``, ``E_e`` a gated SiLU feed-forward
    ``(silu(x Wg_e) * (x Wu_e)) Wd_e``, or with ``w_gate`` None a squared
    ReLU ``relu(x Wu_e)^2 Wd_e`` (two grouped products, not three).

    ``x2d`` [S, F]; ``choice`` [S, k] int32 expert ids over ALL experts;
    ``weight`` [S, k]; ``w_gate``/``w_up`` [G, F, H] and ``w_down`` [G, H, F]
    hold experts ``first_held .. first_held + G - 1``. A choice outside that
    range adds nothing here (another chip's part). Returns ``(y [S, F],
    rows)`` with ``rows`` int32 [3]: the (token, choice) pairs routed here,
    the rows the grouped products ran over (padding included), the largest
    expert's rows.

    **Sort and group.** The ``S * k`` pairs (numbered choice-major: pair
    ``j * S + s`` is token s's j-th choice) are sorted by expert, pairs for
    absent experts last; each of the buffer's rows takes its token's
    activations (``_take_token_rows``), the three products run grouped over
    the G experts (``_grouped_matmul``), the gated activation is weighted by
    its pair's weight in float32 before its one rounding (the down
    projection is linear in its rows), and the rows are summed into their
    tokens' rows (``_sum_token_rows``). Nothing here is ``S * k`` rows by F:
    the trips between tokens and buffer run over the buffer's rows.

    **No pair is dropped whatever the imbalance.** The row buffer has one of
    two static sizes, chosen on the device from the count of pairs routed
    here (``lax.cond``): ``_usual_bound`` (``usual_eighths`` eighths of all
    pairs, a quarter by default) where they fit in it, else all ``S * k``
    rows, which is every pair there is (one expert may take them all). The
    full buffer is several times slower (its trips run over every row), so
    a model whose router sends a layer more than twice an even share asks
    for a larger usual one: m experts that every token chooses are m eighths
    of all pairs at 8 choices a token. Rows past the routed count belong to no
    expert held here: the grouped products do not compute them (a kernel
    leaves them unwritten), the way out selects them away, their cotangent
    is dropped on the way back, their weight is 0 and takes no gradient, and
    they are not counted as work (``rows[1]`` stops at the last group's last
    tile)."""
    S, k = choice.shape
    G = w_up.shape[0]
    pairs = S * k
    pol = get_policy()
    with jax.named_scope("moe/dispatch"):
        # choice-major pairs: pair j * S + s is token s's j-th choice
        local = choice.T.reshape(pairs) - first_held
        key = jnp.where((local >= 0) & (local < G), local, G).astype(jnp.int32)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        group_sizes = jnp.sum(
            key[:, None] == jnp.arange(G, dtype=jnp.int32)[None, :],
            axis=0, dtype=jnp.int32)
        n_routed = jnp.sum(group_sizes)

    def run(bound: int):
        """The layer over a buffer of ``bound`` rows (>= the routed count)."""
        from deeplearning4j_tpu.ops.pallas_kernels import _note_dispatch

        kernel = _kernel_engaged(bound, jnp.dtype(pol.compute_dtype))
        _note_dispatch("grouped_matmul", kernel)
        by_tiles = kernel and S % TOKEN_TILE == 0
        _note_dispatch("moe_combine", by_tiles)

        def layer(x2d, weight, wg, wu, wd):
            with jax.named_scope("moe/dispatch"):
                head = order[:bound]
                plan = _buffer_plan(head % S, n_routed,
                                    (key < G).reshape(k, S), by_tiles)
                rows = _take_token_rows(x2d, plan)
                # a row's weight, float32; 0 where the row carries no pair,
                # so that nothing comes back to that pair's weight
                w = jnp.where(jnp.arange(bound) < n_routed,
                              weight.T.reshape(pairs)[head], 0)[:, None]
            with jax.named_scope("moe/experts"):
                rows = rows.astype(pol.compute_dtype)
                gate = (None if wg is None
                        else _grouped_matmul(rows, wg, group_sizes, kernel))
                up = _grouped_matmul(rows, wu, group_sizes, kernel)
                # the down projection is linear in its rows, so the pair's
                # weight goes in here, in float32 before the one rounding
                f32 = at_least_f32(up.dtype)
                if gate is None:
                    act = jax.nn.relu(up.astype(f32))
                    act = act * act
                else:
                    act = jax.nn.silu(gate.astype(f32)) * up.astype(f32)
                act = (act * w.astype(f32)).astype(up.dtype)
                out = _grouped_matmul(act, wd, group_sizes, kernel).astype(
                    pol.output_dtype)
            with jax.named_scope("moe/dispatch"):
                y = _sum_token_rows(out, plan, S)
            return y, _tiled_rows(group_sizes) if kernel else n_routed

        return layer

    operands = (x2d, weight) + tuple(
        None if w is None else w.astype(pol.compute_dtype)
        for w in (w_gate, w_up, w_down))
    usual = _usual_bound(pairs, usual_eighths)
    if usual < pairs:
        # each branch is rematerialised: the cond's backward then needs the
        # operands alone, not both branches' intermediates (the full
        # buffer's would be written as zeros whenever the usual one ran)
        y, computed = jax.lax.cond(
            n_routed <= usual, jax.checkpoint(run(usual)),
            jax.checkpoint(run(pairs)), *operands)
    else:
        y, computed = run(pairs)(*operands)
    stats = jnp.stack([n_routed, computed,
                       jnp.max(group_sizes)]).astype(jnp.int32)
    return y, stats


@register_config("MoE")
@dataclasses.dataclass
class MoELayer(FeedForwardLayer):
    n_experts: int = 4
    expert_hidden: int = 0          # 0 -> 4 * width
    router_noise: float = 0.0       # jitter stddev at train time
    #: Switch-transformer auxiliary load-balance loss weight, added to the
    #: training objective (without it top-1 routing collapses onto one
    #: expert). The term rides the layer-state pytree as "aux_loss" and is
    #: summed by loss_fn/graph_loss.
    aux_loss_weight: float = 0.01

    def init_state(self, itype: InputType) -> dict:
        return {"aux_loss": jnp.zeros((), jnp.float32)}

    def set_n_in(self, itype: InputType) -> None:
        if not self.n_in:
            self.n_in = itype.size if itype.kind == "recurrent" else itype.flat_size()
        if not self.n_out:
            self.n_out = self.n_in

    def _hidden(self) -> int:
        return self.expert_hidden or 4 * self.n_out

    def init_params(self, key, itype: InputType) -> dict:
        E, F, H = self.n_experts, self.n_in, self._hidden()
        kg, k1, k2 = jax.random.split(key, 3)
        w1 = jax.vmap(lambda k: self._init_w(k, (F, H)))(
            jax.random.split(k1, E))
        w2 = jax.vmap(lambda k: self._init_w(k, (H, F)))(
            jax.random.split(k2, E))
        return {"Wg": self._init_w(kg, (F, E)),
                "W1": w1, "b1": jnp.zeros((E, H), jnp.float32),
                "W2": w2, "b2": jnp.zeros((E, F), jnp.float32)}

    def regularizable_params(self):
        return ("W1", "W2")

    def output_type(self, itype: InputType) -> InputType:
        if itype is not None and itype.kind == "recurrent":
            return InputType.recurrent(self.n_out, itype.timesteps)
        return InputType.feed_forward(self.n_out)

    def route(self, params, x2d, *, train=False, rng=None):
        """Top-1 router: returns (expert_index [S], gate [S], probs [S, E])."""
        logits = x2d @ params["Wg"]
        if train and self.router_noise > 0 and rng is not None:
            logits = logits + self.router_noise * jax.random.normal(
                rng, logits.shape)
        probs = jax.nn.softmax(logits, axis=-1)
        eidx = jnp.argmax(probs, axis=-1)
        gate = jnp.max(probs, axis=-1)
        return eidx, gate, probs

    def expert_ffn(self, params, buf):
        """Apply every expert to its token buffer: buf [E, C, F] -> [E, C, F]."""
        pol = get_policy()
        h = (jnp.einsum("ecf,efh->ech", buf.astype(pol.compute_dtype),
                        params["W1"].astype(pol.compute_dtype),
                        preferred_element_type=accum_dtype(pol.compute_dtype))
             .astype(pol.output_dtype) + params["b1"][:, None].astype(pol.output_dtype))
        h = jax.nn.relu(h)
        return (jnp.einsum("ech,ehf->ecf", h.astype(pol.compute_dtype),
                           params["W2"].astype(pol.compute_dtype),
                           preferred_element_type=accum_dtype(pol.compute_dtype))
                .astype(pol.output_dtype)
                + params["b2"][:, None].astype(pol.output_dtype))

    def moe_ffn_2d(self, params, x2d, *, train=False, rng=None):
        """Core top-1 expert FFN on flattened tokens: (y2d, aux_term).

        ONE implementation shared by MoELayer.apply and MoETransformerBlock's
        residual sublayer (dense evaluation: every expert on every token,
        select by routing — exact, and XLA-friendly on a single chip; the
        sparse dispatch lives in parallel/moe.ExpertParallelMoE)."""
        pol = get_policy()
        eidx, gate, probs = self.route(params, x2d, train=train, rng=rng)
        # load-balance term from THIS routing decision (same rng/noise the
        # tokens were actually dispatched with)
        aux = self._balance_term(eidx, probs)
        h = (jnp.einsum("sf,efh->esh", x2d.astype(pol.compute_dtype),
                        params["W1"].astype(pol.compute_dtype),
                        preferred_element_type=accum_dtype(pol.compute_dtype))
             .astype(pol.output_dtype) + params["b1"][:, None].astype(pol.output_dtype))
        h = jax.nn.relu(h)
        y_all = (jnp.einsum("esh,ehf->esf", h.astype(pol.compute_dtype),
                            params["W2"].astype(pol.compute_dtype),
                            preferred_element_type=accum_dtype(pol.compute_dtype))
                 .astype(pol.output_dtype)
                 + params["b2"][:, None].astype(pol.output_dtype))  # [E, S, F]
        sel = jax.nn.one_hot(eidx, self.n_experts, dtype=y_all.dtype)  # [S, E]
        y = jnp.einsum("se,esf->sf", sel, y_all) * gate[:, None].astype(y_all.dtype)
        return y, aux

    def _ep_context(self):
        """Active expert-parallel context, if a trainer published one while
        tracing (parallel/context.py). None -> dense single-device path."""
        from deeplearning4j_tpu.parallel import context as pctx
        ctx = pctx.current()
        if ctx is not None and ctx.expert_axis is not None \
                and self.n_experts % ctx.mesh.shape[ctx.expert_axis] == 0:
            return ctx
        return None

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        shape = x.shape
        ctx = self._ep_context()
        if ctx is not None:
            from deeplearning4j_tpu.parallel.moe import expert_parallel_ffn
            y, aux = expert_parallel_ffn(self, params, x, ctx.mesh,
                                         ctx.expert_axis,
                                         ctx.capacity_factor,
                                         train=train, rng=rng,
                                         seq_axis=ctx.seq_axis)
            new_state = {"aux_loss": aux if train else jnp.zeros_like(aux)}
            return self.act_fn()(y.reshape(shape)), new_state
        x2d = x.reshape(-1, shape[-1])
        y, aux = self.moe_ffn_2d(params, x2d, train=train, rng=rng)
        # aux keeps its natural dtype (f32 in training, f64 under the
        # gradient checker — a forced f32 cast would truncate the f64 path
        # and make numeric-vs-analytic gradients disagree)
        new_state = {"aux_loss": aux if train else jnp.zeros_like(aux)}
        return self.act_fn()(y.reshape(shape)), new_state

    def _balance_term(self, eidx, probs) -> jax.Array:
        """Switch-transformer balance term E * sum_e f_e * P_e from a routing
        decision — the ONE formula both training (apply) and
        load_balance_loss optimize."""
        frac = jnp.mean(jax.nn.one_hot(eidx, self.n_experts), axis=0)
        return self.n_experts * jnp.sum(frac * jnp.mean(probs, axis=0))

    def load_balance_loss(self, params, x2d) -> jax.Array:
        """Switch-transformer auxiliary loss: E * sum_e f_e * P_e."""
        eidx, _, probs = self.route(params, x2d)
        return self._balance_term(eidx, probs)


@register_config("MoETransformerBlock")
@dataclasses.dataclass
class MoETransformerBlock(MoELayer):
    """Switch-transformer block: pre-LN residual attention, then a pre-LN
    residual top-1 MoE FFN (Fedus et al.; the dense-FFN analog is
    TransformerBlock). Publishes the load-balance term like MoELayer.

    Params: ln1/ln2 scale+bias, fused Wqkv + Wo/bo attention projections,
    and MoELayer's router/expert tensors.
    """

    n_heads: int = 4
    causal: bool = True
    #: residual-stream blocks take no output nonlinearity by default; an
    #: explicit non-identity default here keeps bake_layer_defaults from
    #: filling None with the global activation (sigmoid) and squashing the
    #: residual stream. A user-set activation is still honored in apply().
    activation: Optional[str] = "identity"

    def init_params(self, key, itype: InputType) -> dict:
        F = self.n_out
        if F % self.n_heads:
            raise ValueError(f"width {F} not divisible by heads {self.n_heads}")
        k_attn, k_moe = jax.random.split(key)
        ka, kb = jax.random.split(k_attn)
        params = MoELayer.init_params(self, k_moe, itype)
        params.update({
            "ln1_g": jnp.ones((F,), jnp.float32),
            "ln1_b": jnp.zeros((F,), jnp.float32),
            "Wqkv": self._init_w(ka, (F, 3 * F)),
            "Wo": self._init_w(kb, (F, F)),
            "bo": jnp.zeros((F,), jnp.float32),
            "ln2_g": jnp.ones((F,), jnp.float32),
            "ln2_b": jnp.zeros((F,), jnp.float32),
        })
        return params

    def regularizable_params(self):
        return ("Wqkv", "Wo", "W1", "W2")

    def output_type(self, itype: InputType) -> InputType:
        return InputType.recurrent(self.n_out, itype.timesteps)

    def apply(self, params, state, x, *, train=False, rng=None, mask=None):
        from deeplearning4j_tpu.nn.conf.layers.attention import (
            TransformerBlock, attend)

        pol = get_policy()
        B, T, F = x.shape
        H = self.n_heads
        D = F // H
        h = TransformerBlock._ln(x, params["ln1_g"], params["ln1_b"])
        qkv = jnp.matmul(h.astype(pol.compute_dtype),
                         params["Wqkv"].astype(pol.compute_dtype),
                         preferred_element_type=accum_dtype(pol.compute_dtype))
        q, k, v = jnp.split(qkv.astype(pol.output_dtype), 3, axis=-1)
        q, k, v = (a.reshape(B, T, H, D) for a in (q, k, v))
        o = attend(q, k, v, self.causal, mask)
        att = jnp.matmul(o.reshape(B, T, F).astype(pol.compute_dtype),
                         params["Wo"].astype(pol.compute_dtype),
                         preferred_element_type=accum_dtype(pol.compute_dtype))
        x = x + att.astype(pol.output_dtype) + params["bo"].astype(pol.output_dtype)

        h = TransformerBlock._ln(x, params["ln2_g"], params["ln2_b"])
        ctx = self._ep_context()
        if ctx is not None:
            from deeplearning4j_tpu.parallel.moe import expert_parallel_ffn
            y, aux = expert_parallel_ffn(self, params, h, ctx.mesh,
                                         ctx.expert_axis,
                                         ctx.capacity_factor,
                                         train=train, rng=rng,
                                         seq_axis=ctx.seq_axis)
        else:
            y2d, aux = self.moe_ffn_2d(params, h.reshape(-1, F), train=train,
                                       rng=rng)
            y = y2d.reshape(B, T, F)
        new_state = {"aux_loss": aux if train else jnp.zeros_like(aux)}
        # honor a user-configured activation on the block output (default is
        # identity — the standard residual-stream semantics)
        return self.act_fn()(x + y.reshape(B, T, F)), new_state
