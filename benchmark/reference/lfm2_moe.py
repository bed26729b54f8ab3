"""LFM2-24B-A2B (LiquidAI; ``model_type: "lfm2_moe"``, ``config.json`` of
huggingface.co/LiquidAI/LFM2-24B-A2B), one chip's share, in plain float32
``jax.numpy`` at "highest" precision.

Per sequence of T token ids (statistics, softmaxes, sigmoids, the taps and
the router float32), ``N(u; g) = u * rsqrt(mean(u^2) + eps) * g``:

* ``x = Emb[ids]`` (unscaled); the blocks; ``N``; ``logits = h Wh``
  (untied); mean cross entropy over integer labels. No auxiliary loss.
* Block: ``h = x + A(N(x; g1))``, ``y = h + F(N(h; g2))``.
* ``A(u)`` of a ``"conv"`` layer: ``[Bg | Cg | X] = u W_in`` (``W_in`` [F,
  3F], split in that order), ``V = Bg * X``, ``Z_t = sum_{j < L} w_j *
  V_{t-L+1+j}`` with ``V_s = 0`` for ``s < 0`` and ``w`` [L, F] one filter a
  channel (PyTorch's cross-correlation with padding ``L - 1`` cut to the
  first T outputs: ``w_{L-1}`` weighs the token itself), ``A = (Cg * Z)
  W_out``; no activation, no bias.
* ``A(u)`` of a ``"full_attention"`` layer: ``q = rope(N(u Wq -> [T, H,
  D]; gq))``, ``k = rope(N(u Wk -> [T, G, D]; gk))`` (the norm over each
  head's D values, one scale vector for all heads), ``v = u Wv -> [T, G,
  D]``; ``rope`` over all D dimensions, halves paired ``(x[i], x[i +
  D/2])``; query head h uses key/value head ``h // (H / G)``; causal
  softmax of ``q . k / sqrt(D)``; ``A = o Wo``; no output gate.
* ``F``, the first ``n_dense_layers``: ``(silu(u Wg) * (u Wu)) Wd``.
  Elsewhere ``s = sigmoid(u Wr)`` over all router outputs; the
  ``experts_per_token`` largest of ``s + b`` chosen (``b`` the selection
  bias, no gradient); weights ``w_e = s_e / (sum of the chosen s + 1e-6) *
  routed_scaling_factor``; ``F = sum over chosen e held here of w_e
  E_e(u)``, ``E_e`` SwiGLUs; no shared expert.
* After every step, outside the gradient, the bias moves as Trinity-Mini's
  (``trinity_mini.next_bias``): ``d = load_balance_coeff * sign(mean(c) -
  c)``, ``b <- b + d - mean(d)`` with ``c`` the step's (token, choice) pairs
  on each output over this chip's tokens.

Departures from the source, all stated by the configuration: the **share of
experts** (the sum runs over the chosen experts whose id lies in
``experts_held``; every held expert is evaluated densely on every token and
weighted where chosen, else 0), the **sliced vocabulary** (``vocab_rows``),
the **depth** (``n_layers``, ``layer_types``, ``n_dense_layers``) and
``assumed`` (initialisation normal(0.02) with norm scales 1, Adam as
``trinity_mini.follow`` applies it, the bias's rule and rate, the untied
head, the per-head q/k norms, the convolution's layout as recalled from the
published ``modeling_lfm2.py``, not checked here). The program renormalises
with 1e-20 where this follows the source's 1e-6: below bfloat16's resolution.

Computed one sequence at a time (no layer mixes sequences), each block under
``jax.checkpoint``. The attention core runs over blocks of ``QUERY_BLOCK``
queries and one key/value group at a time (a group's scores are ``H / G x
block x T`` float32; one head's ``[T, T]`` would be 4.3 GB at 32,768), each
block checkpointed; the feed-forward runs over chunks of ``TOKEN_CHUNK``
tokens. Adam's moments live on the host between steps (``follow``). Keys
are the program's leaf names, ``"<layer index>/<param>"``. Nothing here
imports the program under test.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from . import common as C
# ``follow`` (Adam, and the bias moved by ``trinity_mini.next_bias`` after
# every step over ``make_loss_and_grad``'s ``with_loads``) is this model's too
from .trinity_mini import (  # noqa: F401
    _mm, _rms, _rope, _swiglu, follow, visible_pairs)

_HI = lax.Precision.HIGHEST
CONV, FULL = "conv", "full_attention"
_DEFAULTS = dict(
    n_layers=40, layer_types=None, n_dense_layers=2, experts_held=None,
    vocab_rows=65536, hidden_size=2048, n_heads=32, n_kv_heads=8,
    head_dim=64, conv_kernel=3, intermediate_size=11776,
    moe_intermediate_size=1536, n_router_outputs=64, experts_per_token=4,
    routed_scaling_factor=1.0, load_balance_coeff=0.001, rms_norm_eps=1e-5,
    rope_theta=1e6, seq_len=32768)
INIT_STD = 0.02
#: queries a block of the attention core holds
QUERY_BLOCK = 512
#: tokens a chunk of the feed-forward holds
TOKEN_CHUNK = 4096


def published_layer_types(n_layers: int) -> list:
    """Full attention at layer 2 and every fourth after it."""
    return [FULL if i >= 2 and (i - 2) % 4 == 0 else CONV
            for i in range(n_layers)]


def _cfg(cfg) -> dict:
    c = dict(_DEFAULTS, **{k: v for k, v in cfg.items() if k in _DEFAULTS})
    if c["layer_types"] is None:
        c["layer_types"] = published_layer_types(c["n_layers"])
    if len(c["layer_types"]) != c["n_layers"]:
        raise ValueError("layer_types does not name every layer held")
    first, end = c["experts_held"] or (0, c["n_router_outputs"])
    c["first_held"], c["n_held"] = int(first), int(end) - int(first)
    return c


def _kind(c, i):
    """The mixer of block ``i`` (1-based)."""
    return c["layer_types"][i - 1]


# ------------------------------------------------------------ what it costs
def layers(cfg) -> list:
    """Every product a step requires, as ``dense`` entries whose ``nin *
    nout`` is the multiply-accumulates of one sample, a sequence of
    ``seq_len`` tokens: a convolution's two projections and its taps and
    gates (``L`` multiply-adds and two products a channel and token, under
    ``attn/conv``), attention's projections and its scores and values at the
    causal pairs (``attn/core``), the dense feed-forward, the router, routed
    experts at ``experts_per_token * held / router outputs`` of an expert a
    token (``moe/experts``), the head. (The embedding is a lookup.)"""
    c = _cfg(cfg)
    T, F, H, G, D = (c["seq_len"], c["hidden_size"], c["n_heads"],
                     c["n_kv_heads"], c["head_dim"])
    He = c["moe_intermediate_size"]
    routed_rows = T * c["experts_per_token"] * c["n_held"]
    if routed_rows % c["n_router_outputs"]:
        raise ValueError("the expected routed rows of a sequence are not whole")
    out = []

    def add(name, nin, nout, scope=None):
        entry = {"kind": "dense", "name": name, "nin": nin, "nout": nout,
                 "first": False}
        if scope:   # a kernel with a roofline metric of its own runs it
            entry["scope"] = scope
        out.append(entry)

    for i in range(1, c["n_layers"] + 1):
        if _kind(c, i) == CONV:
            add(f"{i}/W_in", T * F, 3 * F)
            add(f"{i}/conv", T * F, c["conv_kernel"] + 1, "attn/conv")
            add(f"{i}/W_out", T * F, F)
        else:
            add(f"{i}/Wq", T * F, H * D)
            add(f"{i}/Wk", T * F, G * D)
            add(f"{i}/Wv", T * F, G * D)
            add(f"{i}/core", H * visible_pairs(T, None), 2 * D, "attn/core")
            add(f"{i}/Wo", T * H * D, F)
        if i <= c["n_dense_layers"]:
            add(f"{i}/ffn", T * F, 3 * c["intermediate_size"])
        else:
            add(f"{i}/Wr", T * F, c["n_router_outputs"])
            add(f"{i}/routed", routed_rows // c["n_router_outputs"] * F,
                3 * He, "moe/experts")
    add(f"{c['n_layers'] + 2}/W", T * F, c["vocab_rows"])
    return out


# ------------------------------------------------------------------ weights
def _shapes(c) -> dict:
    F, H, G, D = (c["hidden_size"], c["n_heads"], c["n_kv_heads"],
                  c["head_dim"])
    He, held, I = (c["moe_intermediate_size"], c["n_held"],
                   c["intermediate_size"])
    s = {"0/W": (c["vocab_rows"], F)}
    for i in range(1, c["n_layers"] + 1):
        s.update({f"{i}/norm1_g": (F,), f"{i}/norm2_g": (F,)})
        if _kind(c, i) == CONV:
            s.update({f"{i}/W_in": (F, 3 * F),
                      f"{i}/conv_w": (c["conv_kernel"], F),
                      f"{i}/W_out": (F, F)})
        else:
            s.update({f"{i}/Wq": (F, H * D), f"{i}/Wk": (F, G * D),
                      f"{i}/Wv": (F, G * D), f"{i}/q_norm_g": (D,),
                      f"{i}/k_norm_g": (D,), f"{i}/Wo": (H * D, F)})
        if i <= c["n_dense_layers"]:
            s.update({f"{i}/Wg": (F, I), f"{i}/Wu": (F, I), f"{i}/Wd": (I, F)})
        else:
            s.update({f"{i}/Wr": (F, c["n_router_outputs"]),
                      f"{i}/Eg": (held, F, He), f"{i}/Eu": (held, F, He),
                      f"{i}/Ed": (held, He, F)})
    s[f"{c['n_layers'] + 1}/g"] = (F,)
    s[f"{c['n_layers'] + 2}/W"] = (F, c["vocab_rows"])
    return s


def init(seed: int, cfg) -> dict:
    shapes = _shapes(_cfg(cfg))

    def make(key):
        p = {}
        for kk, (name, shape) in zip(jax.random.split(key, len(shapes)),
                                     shapes.items()):
            if len(shape) == 1:
                p[name] = jnp.ones(shape, jnp.float32)
            else:
                p[name] = INIT_STD * jax.random.normal(kk, shape, jnp.float32)
        return p

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def init_bias(cfg) -> dict:
    """The routers' biases before the first step: ``{layer index: zeros}``."""
    c = _cfg(cfg)
    return {i: jnp.zeros((c["n_router_outputs"],), jnp.float32)
            for i in range(c["n_dense_layers"] + 1, c["n_layers"] + 1)}


# --------------------------------------------------------------------- math
def _rows_of(t: int, block: int) -> int:
    return block if t % block == 0 else t


def gated_conv(bcx, w):
    """``Cg * Z`` from ``[Bg | Cg | X]`` [T, 3F] and the taps ``w`` [L, F]:
    the gates and the causal depthwise convolution, float32."""
    T, L = bcx.shape[0], w.shape[0]
    b, cg, x = jnp.split(bcx, 3, axis=-1)
    v = jnp.concatenate([jnp.zeros((L - 1, b.shape[1]), b.dtype), b * x])
    z = sum(w[j] * v[j:j + T] for j in range(L))     # v[j + t] = V_{t-L+1+j}
    return cg * z


def short_conv(p, i, u, c, precision):
    bcx = _mm(u, p[f"{i}/W_in"], precision)
    return _mm(gated_conv(bcx, p[f"{i}/conv_w"]), p[f"{i}/W_out"], precision)


def attention(p, i, u, c, precision):
    T = u.shape[0]
    H, G, D = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    eps, theta, per = c["rms_norm_eps"], c["rope_theta"], H // G
    q = _rope(_rms(_mm(u, p[f"{i}/Wq"], precision).reshape(T, H, D),
                   p[f"{i}/q_norm_g"], eps), theta)
    k = _rope(_rms(_mm(u, p[f"{i}/Wk"], precision).reshape(T, G, D),
                   p[f"{i}/k_norm_g"], eps), theta)
    v = _mm(u, p[f"{i}/Wv"], precision).reshape(T, G, D)
    dot = C._product(lambda a, b: jnp.einsum("qhd,kd->hqk", a, b,
                                             precision=_HI), precision)
    mix = C._product(lambda a, b: jnp.einsum("hqk,kd->qhd", a, b,
                                             precision=_HI), precision)
    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)        # [G, T, D]
    blk = _rows_of(T, QUERY_BLOCK)

    def rows(args):
        """One block of queries, every head, a key/value group at a time."""
        qb, first = args                                     # [blk, H, D]
        seen = (first + jnp.arange(blk))[:, None] >= jnp.arange(T)[None, :]

        def group(g):
            qg = lax.dynamic_slice_in_dim(qb, g * per, per, axis=1)
            s = jnp.where(seen, dot(qg, k[g]) * D ** -0.5, -jnp.inf)
            return mix(jax.nn.softmax(s, axis=-1), v[g])     # [blk, per, D]

        o = lax.map(jax.checkpoint(group), jnp.arange(G))    # [G, blk, per, D]
        return o.transpose(1, 0, 2, 3).reshape(blk, H * D)

    n = T // blk
    o = lax.map(jax.checkpoint(rows), (q.reshape(n, blk, H, D),
                                       jnp.arange(0, T, blk)))
    return _mm(o.reshape(T, H * D), p[f"{i}/Wo"], precision)


def _chunked(fn, *arrays):
    """``fn`` over chunks of ``TOKEN_CHUNK`` rows of ``arrays`` (each [T,
    ...]), each chunk checkpointed: -> [T, F]."""
    T = arrays[0].shape[0]
    n = T // _rows_of(T, TOKEN_CHUNK)
    out = lax.map(jax.checkpoint(lambda a: fn(*a)),
                  tuple(a.reshape(n, -1, *a.shape[1:]) for a in arrays))
    return out.reshape(T, -1)


def route(u, wr, bias, k, scale):
    """-> (choice [T, k], weight [T, k], scores [T, E]), float32
    throughout: the k largest of ``sigmoid(u Wr) + bias``, weighted by their
    scores over their sum + 1e-6, times ``scale``."""
    scores = jax.nn.sigmoid(jnp.matmul(u, wr, precision=_HI))
    _, choice = lax.top_k(scores + bias, k)
    weight = jnp.take_along_axis(scores, choice, axis=-1)
    weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-6) * scale
    return choice, weight, scores


def expert_layer(p, i, u, c, precision, bias=None):
    """-> (F(u) over the experts held here, the (token, choice) pairs on
    each router output [E], those that fell on an expert held here)."""
    E = c["n_router_outputs"]
    bias = jnp.zeros((E,), jnp.float32) if bias is None else bias
    choice, weight, _ = route(u, p[f"{i}/Wr"], lax.stop_gradient(bias),
                              c["experts_per_token"],
                              c["routed_scaling_factor"])
    w = jnp.stack([jnp.sum(jnp.where(choice == c["first_held"] + e, weight,
                                     0.0), axis=-1)
                   for e in range(c["n_held"])], axis=-1)     # [T, held]

    def chunk(uc, wc):
        y = jnp.zeros_like(uc)
        for e in range(c["n_held"]):
            y = y + wc[:, e:e + 1] * _swiglu(
                uc, p[f"{i}/Eg"][e], p[f"{i}/Eu"][e], p[f"{i}/Ed"][e],
                precision)
        return y

    load = jnp.zeros((E,), jnp.float32).at[choice.reshape(-1)].add(1.0)
    here = ((choice >= c["first_held"])
            & (choice < c["first_held"] + c["n_held"]))
    return _chunked(chunk, u, w), load, jnp.sum(here)


def _block(p, i, x, c, precision, bias):
    eps = c["rms_norm_eps"]
    mixer = short_conv if _kind(c, i) == CONV else attention
    h = x + mixer(p, i, _rms(x, p[f"{i}/norm1_g"], eps), c, precision)
    u = _rms(h, p[f"{i}/norm2_g"], eps)
    if i <= c["n_dense_layers"]:
        f = _chunked(lambda uc: _swiglu(uc, p[f"{i}/Wg"], p[f"{i}/Wu"],
                                        p[f"{i}/Wd"], precision), u)
        return h + f, None, None
    f, load, rows = expert_layer(p, i, u, c, precision, bias)
    return h + f, load, rows


def sequence_logits(p, ids, c, precision="float32", biases=None):
    """-> (logits [T, vocab_rows], loads [expert layers, E], rows routed
    here per expert layer)."""
    x = p["0/W"][ids]
    loads, rows = [], []
    for i in range(1, c["n_layers"] + 1):
        b = None if biases is None else biases.get(i)
        x, load, r = jax.checkpoint(
            lambda pp, xx, bb, _i=i: _block(pp, _i, xx, c, precision, bb))(
                {k: v for k, v in p.items() if k.startswith(f"{i}/")}, x, b)
        if r is not None:
            loads.append(load)
            rows.append(r)
    n = c["n_layers"]
    h = _rms(x, p[f"{n + 1}/g"], c["rms_norm_eps"])
    return (_mm(h, p[f"{n + 2}/W"], precision), jnp.stack(loads),
            jnp.stack(rows))


def _sequence_loss(p, ids, labels, biases, c, precision):
    logits, loads, rows = sequence_logits(p, ids, c, precision, biases)
    logp = jax.nn.log_softmax(logits, axis=-1)
    xent = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
    return xent, (rows, loads)


def make_loss_and_grad(cfg, precision: str = "float32", stage_dtype=None):
    """``(params, ids [B, T], labels [B, T]) -> (loss, grads, rows)``: the
    batch's mean loss and gradient over its sequences, one sequence at a
    time, and per expert layer the (token, choice) pairs of the batch that
    fell on an expert held here. ``.with_loads(params, ids, labels,
    biases)`` takes the routers' biases (``{layer index: [E]}``, None:
    zeros) and also returns the batch's pairs on every router output
    ``[expert layers, E]``, what ``follow``'s bias step reads.
    ``stage_dtype`` does not touch integer ids."""
    c = _cfg(cfg)
    one = jax.jit(jax.value_and_grad(
        lambda p, x, y, b: _sequence_loss(p, x, y, b, c, precision),
        has_aux=True))
    add = jax.jit(lambda acc, new: jax.tree_util.tree_map(jnp.add, acc, new),
                  donate_argnums=(0,))
    mean = jax.jit(lambda lg, n: jax.tree_util.tree_map(lambda t: t / n, lg),
                   donate_argnums=(0,))

    def with_loads(params, x, y, biases=None):
        x, y = jnp.asarray(x, jnp.int32), jnp.asarray(y, jnp.int32)
        biases = init_bias(cfg) if biases is None else biases
        acc = None
        for b in range(x.shape[0]):
            (loss, counts), grads = one(params, x[b], y[b], biases)
            new = ((loss, grads), counts)
            acc = new if acc is None else add(acc, new)
        (loss, grads), (rows, loads) = acc
        loss, grads = mean((loss, grads), jnp.float32(x.shape[0]))
        return loss, grads, rows, loads

    def loss_and_grad(params, x, y):
        return with_loads(params, x, y)[:3]

    loss_and_grad.with_loads = with_loads
    loss_and_grad.cfg = c
    return loss_and_grad
