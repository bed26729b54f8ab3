"""Keye-VL-2.0-30B-A3B's language model (Kwai-Keye; ``model_type:
"KeyeVL2"``, ``config.json`` of huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B),
one chip's share, in plain float32 ``jax.numpy`` at "highest" precision.

Per sequence of T token ids (statistics, softmaxes, the router and the index
scores float32), ``N(u; g) = u * rsqrt(mean(u^2) + eps) * g``:

* ``x = Emb[ids]`` (unscaled); ``n_layers`` alike blocks; ``N``; ``logits =
  h Wh`` (untied). Block: ``h = x + A(N(x; g1))``, ``y = h + F(N(h; g2))``.
* ``A(u)``: ``q = rope(N(u Wq -> [T, H, D]; gq))``, ``k = rope(N(u Wk -> [T,
  G, D]; gk))`` (the norm over each head's D values, one scale vector for all
  heads), ``v = u Wv -> [T, G, D]``; ``rope`` over all D dimensions, halves
  paired ``(x[i], x[i + D/2])``, theta 1e7; query head h uses key/value head
  ``h // (H / G)``; no biases, no output gate.
* Indexer, on ``stop_gradient(u)``: ``qI = rope(u WqI) -> [T, J, E]``, ``kI =
  rope(N(u WkI; gI)) -> [T, E]`` (one key head), ``w = (u Ww) * J^-0.5 *
  E^-0.5 -> [T, J]``, ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for
  ``s <= t``.
* Selection ``S_t``: the ``min(t + 1, topk)`` keys ``s <= t`` with the
  largest ``I[t, s]``; equal scores: the lower key (the k-th largest value
  from ``lax.top_k``, the keys above it, and of those equal to it the first
  as many as are missing). No gradient.
* Core: ``o[t, h] = sum_{s in S_t} softmax_{s in S_t}(q[t, h] . k[s, h //
  (H/G)] / sqrt(D)) v[s, h // (H/G)]``; ``A(u) = o Wo``.
* Indexer's loss, one a layer: ``p[t, s] = stop_gradient(mean_h P[t, h,
  s])`` over ``S_t``; ``L_I = mean_t KL(p[t, .] || softmax_{s in S_t} I[t,
  .])``. Its gradient reaches ``WqI``, ``WkI``, ``Ww`` and ``gI`` alone.
* ``F``: ``P = softmax(u Wr)`` over all router outputs, the
  ``experts_per_token`` largest chosen, weights their probabilities divided
  by their sum; ``F = sum over chosen e held here of w_e E_e(u)``, ``E_e``
  SwiGLUs; no shared expert. Balance term per sequence: ``sum_e f_e P_e``
  with ``f_e`` the share of the sequence's choices on e times the number of
  outputs and ``P_e`` the mean probability of e.
* Loss of a sequence: mean cross entropy + ``aux_loss_weight`` x the sum of
  the layers' balance terms + ``index_loss_weight`` x the sum of the layers'
  ``L_I``.

Departures from the source, all stated by the configuration: the **share of
experts** (the sum runs over the chosen experts whose id lies in
``experts_held``; what absent experts would add is left out), the **sliced
vocabulary** (``vocab_rows``), the **depth** (``n_layers``), and ``assumed``
(initialisation, Adam, and every point above that the catalog's row does not
give).

Computed one sequence at a time, each block under ``jax.checkpoint``. The
selection of a layer is made first, outside the gradient, over blocks of
``QUERY_BLOCK`` queries (a block's per-head index scores are ``J x block x
T`` float32) and kept as a ``[T, T]`` boolean; the core and the indexer's
loss then run over the same blocks of queries, a query head at a time, each
block checkpointed. Adam's moments live on the host between steps. Keys are
the program's leaf names, ``"<layer index>/<param>"``. Nothing here imports
the program under test.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import common as C

_HI = lax.Precision.HIGHEST
_DEFAULTS = dict(
    n_layers=48, experts_held=None, vocab_rows=151936, hidden_size=2048,
    n_heads=32, n_kv_heads=4, head_dim=128, index_n_heads=16,
    index_head_dim=64, index_topk=2048, index_loss_weight=1.0,
    moe_intermediate_size=768, n_router_outputs=128, experts_per_token=8,
    norm_topk_prob=True, aux_loss_weight=0.001, rms_norm_eps=1e-6,
    rope_theta=1e7, seq_len=16384)
INIT_STD = 0.02
#: queries a block of the selection, the core and the indexer's loss holds
QUERY_BLOCK = 256


def _cfg(cfg) -> dict:
    c = dict(_DEFAULTS, **{k: v for k, v in cfg.items() if k in _DEFAULTS})
    first, end = c["experts_held"] or (0, c["n_router_outputs"])
    c["first_held"], c["n_held"] = int(first), int(end) - int(first)
    return c


def selected_pairs(seq: int, topk: int) -> int:
    """(query, key) pairs a sequence's selection keeps: ``min(t + 1, topk)``
    a query (the count of a causal mask cut to a ``topk``-key window)."""
    k = min(topk, seq)
    return k * (k + 1) // 2 + (seq - k) * k


# ------------------------------------------------------------ what it costs
def layers(cfg) -> list:
    """Every product a step requires, as ``dense`` entries whose ``nin *
    nout`` is the multiply-accumulates of one sample, a sequence of
    ``seq_len`` tokens: the projections, the cores' scores and values at the
    SELECTED pairs, the indexer's projections, its scores (forward at every
    causal pair, gradients at the selected pairs only: an entry at the
    selected pairs, which ``flops.py`` counts three times, and the unselected
    pairs' forward as a ``first`` entry at half their multiply-accumulates,
    which it counts twice), the router, routed experts at
    ``experts_per_token * held / router outputs`` of an expert a token, the
    head. (The embedding is a lookup; the selection requires no product.)"""
    c = _cfg(cfg)
    T, F, H, G, D = (c["seq_len"], c["hidden_size"], c["n_heads"],
                     c["n_kv_heads"], c["head_dim"])
    J, E, He = c["index_n_heads"], c["index_head_dim"], c["moe_intermediate_size"]
    routed_rows = T * c["experts_per_token"] * c["n_held"]
    if routed_rows % c["n_router_outputs"] or (J * E) % 2:
        raise ValueError("the expected routed rows of a sequence, or half an "
                         "index score's products, are not whole")
    chosen = selected_pairs(T, c["index_topk"])
    causal = T * (T + 1) // 2
    out = []

    def add(name, nin, nout, scope=None, first=False):
        entry = {"kind": "dense", "name": name, "nin": nin, "nout": nout,
                 "first": first}
        if scope:   # a kernel with a roofline metric of its own runs it
            entry["scope"] = scope
        out.append(entry)

    for i in range(1, c["n_layers"] + 1):
        add(f"{i}/Wq", T * F, H * D)
        add(f"{i}/Wk", T * F, G * D)
        add(f"{i}/Wv", T * F, G * D)
        add(f"{i}/WqI", T * F, J * E)
        add(f"{i}/WkI", T * F, E)
        add(f"{i}/Ww", T * F, J)
        add(f"{i}/index", chosen, J * E, "attn/indexer")
        add(f"{i}/index_unselected", causal - chosen, J * E // 2,
            "attn/indexer", first=True)
        add(f"{i}/core", H * chosen, 2 * D, "attn/core")
        add(f"{i}/Wo", T * H * D, F)
        add(f"{i}/Wr", T * F, c["n_router_outputs"])
        add(f"{i}/routed", routed_rows // c["n_router_outputs"] * F, 3 * He,
            "moe/experts")
    add(f"{c['n_layers'] + 2}/W", T * F, c["vocab_rows"])
    return out


# ------------------------------------------------------------------ weights
def _shapes(c) -> dict:
    F, H, G, D = (c["hidden_size"], c["n_heads"], c["n_kv_heads"],
                  c["head_dim"])
    J, E = c["index_n_heads"], c["index_head_dim"]
    He, held = c["moe_intermediate_size"], c["n_held"]
    s = {"0/W": (c["vocab_rows"], F)}
    for i in range(1, c["n_layers"] + 1):
        s.update({f"{i}/norm1_g": (F,), f"{i}/norm2_g": (F,),
                  f"{i}/Wq": (F, H * D), f"{i}/Wk": (F, G * D),
                  f"{i}/Wv": (F, G * D), f"{i}/q_norm_g": (D,),
                  f"{i}/k_norm_g": (D,), f"{i}/Wo": (H * D, F),
                  f"{i}/WqI": (F, J * E), f"{i}/WkI": (F, E),
                  f"{i}/Ww": (F, J), f"{i}/kI_norm_g": (E,),
                  f"{i}/Wr": (F, c["n_router_outputs"]),
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


# --------------------------------------------------------------------- math
def _mm(a, w, precision):
    return C._product(lambda x, m: jnp.matmul(x, m, precision=_HI),
                      precision)(a, w)


def _rms(u, g, eps):
    return u * lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps) * g


def _swiglu(u, wg, wu, wd, precision):
    return _mm(jax.nn.silu(_mm(u, wg, precision)) * _mm(u, wu, precision),
               wd, precision)


def _rope(x, theta):
    """x [T, heads, D]: ``x * cos + rotate_half(x) * sin`` with the angles
    ``t * theta^(-2i/D)`` laid out twice, so ``(x[i], x[i + D/2])`` turn
    together."""
    T, D = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, D, 2, dtype=np.float64) / D)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    emb = np.concatenate([ang, ang], axis=-1)
    cos = jnp.asarray(np.cos(emb), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(emb), jnp.float32)[:, None, :]
    half = D // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def _dot(precision):
    return C._product(lambda a, b: jnp.einsum("qd,kd->qk", a, b,
                                              precision=_HI), precision)


def _block_of(t: int) -> int:
    return QUERY_BLOCK if t % QUERY_BLOCK == 0 else t


def indexer(p, i, u, c, precision):
    """-> (qI [T, J, E], kI [T, E], w [T, J]) from the block's normed input;
    the caller stops the gradient into ``u``."""
    T = u.shape[0]
    J, E = c["index_n_heads"], c["index_head_dim"]
    qi = _rope(_mm(u, p[f"{i}/WqI"], precision).reshape(T, J, E),
               c["rope_theta"])
    ki = _rope(_rms(_mm(u, p[f"{i}/WkI"], precision),
                    p[f"{i}/kI_norm_g"], c["rms_norm_eps"])[:, None, :],
               c["rope_theta"])[:, 0, :]
    w = _mm(u, p[f"{i}/Ww"], precision) * J ** -0.5 * E ** -0.5
    return qi, ki, w


def index_scores(qi, ki, w, precision):
    """``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` for the queries of
    ``qi`` [rows, J, E] against every key: [rows, T]."""
    dot = _dot(precision)
    s = jax.vmap(lambda qj: dot(qj, ki), in_axes=1)(qi)      # [J, rows, T]
    return jnp.einsum("jts,tj->ts", jnp.maximum(s, 0.0), w, precision=_HI)


def select_rows(scores, first: int, topk: int):
    """The selection of the queries ``first, first + 1, ...`` whose index
    scores against every key are ``scores`` [rows, T]: a boolean [rows, T]
    with exactly ``min(t + 1, topk)`` keys a query, all at ``s <= t``."""
    rows, T = scores.shape
    t = first + jnp.arange(rows)
    causal = jnp.arange(T)[None, :] <= t[:, None]
    x = jnp.where(causal, scores, -jnp.inf)
    want = jnp.minimum(t + 1, topk)
    top = lax.top_k(x, min(topk, T))[0]
    kth = jnp.take_along_axis(top, (want - 1)[:, None], axis=-1)
    above = x > kth
    level = jnp.logical_and(x == kth, causal)
    missing = want - jnp.sum(above, axis=-1)
    return jnp.logical_or(above, jnp.logical_and(
        level, jnp.cumsum(level, axis=-1) <= missing[:, None]))


def selection(p, i, u, c, precision="float32"):
    """``S`` of block ``i`` for the normed input ``u``: boolean [T, T]."""
    T = u.shape[0]
    qi, ki, w = indexer(p, i, u, c, precision)
    blk = _block_of(T)

    def rows(args):
        q, ww, first = args
        return select_rows(index_scores(q, ki, ww, precision), first,
                           c["index_topk"])

    return lax.map(rows, (qi.reshape(T // blk, blk, *qi.shape[1:]),
                          w.reshape(T // blk, blk, -1),
                          jnp.arange(0, T, blk))).reshape(T, T)


def attention(p, i, u, chosen, c, precision):
    """-> (A(u) [T, F], L_I): the core over ``chosen`` [T, T] and the
    indexer's loss against the core's own probabilities."""
    T = u.shape[0]
    H, G, D = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    q = _rope(_rms(_mm(u, p[f"{i}/Wq"], precision).reshape(T, H, D),
                   p[f"{i}/q_norm_g"], eps), theta)
    k = _rope(_rms(_mm(u, p[f"{i}/Wk"], precision).reshape(T, G, D),
                   p[f"{i}/k_norm_g"], eps), theta)
    v = _mm(u, p[f"{i}/Wv"], precision).reshape(T, G, D)
    qi, ki, w = indexer(p, i, lax.stop_gradient(u), c, precision)
    dot = _dot(precision)
    mix = C._product(lambda a, b: jnp.einsum("qk,kd->qd", a, b,
                                             precision=_HI), precision)
    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)        # [G, T, D]
    blk = _block_of(T)

    def rows(args):
        """One block of queries: every head's output over the chosen keys,
        and the block's sum of ``KL(p || softmax_chosen I)``."""
        qb, qib, wb, seen = args

        def head(mean_p, hq):
            qh, g = hq
            s = jnp.where(seen, dot(qh, k[g]) * D ** -0.5, -jnp.inf)
            prob = jax.nn.softmax(s, axis=-1)
            return mean_p + lax.stop_gradient(prob) / H, mix(prob, v[g])

        mean_p, o = lax.scan(jax.checkpoint(head), jnp.zeros(seen.shape),
                             (qb.transpose(1, 0, 2),
                              jnp.arange(H) // (H // G)))
        logq = jax.nn.log_softmax(
            jnp.where(seen, index_scores(qib, ki, wb, precision), -jnp.inf),
            axis=-1)
        live = jnp.logical_and(seen, mean_p > 0)
        kl = jnp.sum(jnp.where(
            live, mean_p * (jnp.log(jnp.where(live, mean_p, 1.0))
                            - jnp.where(live, logq, 0.0)), 0.0))
        return o.transpose(1, 0, 2).reshape(blk, H * D), kl

    n = T // blk
    o, kl = lax.map(jax.checkpoint(rows),
                    (q.reshape(n, blk, H, D), qi.reshape(n, blk, *qi.shape[1:]),
                     w.reshape(n, blk, -1), chosen.reshape(n, blk, T)))
    return _mm(o.reshape(T, H * D), p[f"{i}/Wo"], precision), jnp.sum(kl) / T


def route(u, wr, k, renorm=True):
    """-> (choice [T, k], weight [T, k], probs [T, E]), float32 throughout:
    the k largest of ``softmax(u Wr)``, weighted by their probabilities
    divided by their sum."""
    probs = jax.nn.softmax(jnp.matmul(u, wr, precision=_HI), axis=-1)
    weight, choice = lax.top_k(probs, k)
    if renorm:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    return choice, weight, probs


def _seq_aux(choice, probs, c):
    """The sequence-wise balance term for one sequence, before its weight."""
    E, (T, k) = c["n_router_outputs"], choice.shape
    count = jnp.zeros((E,), jnp.float32).at[choice.reshape(-1)].add(1.0)
    return jnp.sum(count / (T * k / E) * jnp.mean(probs, axis=0))


def expert_layer(p, i, u, c, precision):
    """-> (F(u) over the experts held here, the balance term, the (token,
    choice) pairs that fell on an expert held here)."""
    choice, weight, probs = route(u, p[f"{i}/Wr"], c["experts_per_token"],
                                  c["norm_topk_prob"])
    y = jnp.zeros_like(u)
    for e in range(c["n_held"]):
        w_e = jnp.sum(jnp.where(choice == c["first_held"] + e, weight, 0.0),
                      axis=-1)
        y = y + w_e[:, None] * _swiglu(u, p[f"{i}/Eg"][e], p[f"{i}/Eu"][e],
                                       p[f"{i}/Ed"][e], precision)
    here = ((choice >= c["first_held"])
            & (choice < c["first_held"] + c["n_held"]))
    return y, _seq_aux(choice, probs, c), jnp.sum(here)


def _block(p, i, x, chosen, c, precision):
    eps = c["rms_norm_eps"]
    a, index_loss = attention(p, i, _rms(x, p[f"{i}/norm1_g"], eps), chosen,
                              c, precision)
    h = x + a
    f, aux, rows = expert_layer(p, i, _rms(h, p[f"{i}/norm2_g"], eps), c,
                                precision)
    return h + f, aux, index_loss, rows


def sequence_logits(p, ids, c, precision="float32", select=None):
    """-> (logits [T, vocab_rows], the sum of the layers' balance terms, the
    sum of their indexer losses, rows routed here per layer, the layers'
    selections [n_layers, T, T]). ``select(i, S)`` may replace block i's
    selection (a planted fault)."""
    x = p["0/W"][ids]
    aux, index_loss, rows, chosen = jnp.float32(0), jnp.float32(0), [], []
    for i in range(1, c["n_layers"] + 1):
        mine = {k: v for k, v in p.items() if k.startswith(f"{i}/")}
        s = lax.stop_gradient(selection(
            mine, i, _rms(x, p[f"{i}/norm1_g"], c["rms_norm_eps"]), c,
            precision))
        if select is not None:
            s = select(i, s)
        x, a, l, r = jax.checkpoint(
            lambda pp, xx, ss, _i=i: _block(pp, _i, xx, ss, c, precision))(
                mine, x, s)
        aux, index_loss = aux + a, index_loss + l
        rows.append(r)
        chosen.append(s)
    n = c["n_layers"]
    h = _rms(x, p[f"{n + 1}/g"], c["rms_norm_eps"])
    return (_mm(h, p[f"{n + 2}/W"], precision), aux, index_loss,
            jnp.stack(rows), chosen)


def _sequence_loss(p, ids, labels, c, precision, select=None):
    logits, aux, index_loss, rows, _ = sequence_logits(p, ids, c, precision,
                                                       select)
    logp = jax.nn.log_softmax(logits, axis=-1)
    xent = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
    return (xent + c["aux_loss_weight"] * aux
            + c["index_loss_weight"] * index_loss), rows


def make_loss_and_grad(cfg, precision: str = "float32", stage_dtype=None,
                       select=None):
    """``(params, ids [B, T], labels [B, T]) -> (loss, grads, rows)``: the
    batch's mean loss and gradient over its sequences, one sequence at a
    time, and per layer the (token, choice) pairs of the batch that fell on
    an expert held here. ``stage_dtype`` does not touch integer ids.
    ``select`` is ``sequence_logits``'s (the probes plant a fault with it)."""
    c = _cfg(cfg)
    one = jax.jit(jax.value_and_grad(
        lambda p, x, y: _sequence_loss(p, x, y, c, precision, select),
        has_aux=True))
    add = jax.jit(lambda acc, new: jax.tree_util.tree_map(jnp.add, acc, new),
                  donate_argnums=(0,))
    mean = jax.jit(lambda lg, n: jax.tree_util.tree_map(lambda t: t / n, lg),
                   donate_argnums=(0,))

    def loss_and_grad(params, x, y):
        x, y = jnp.asarray(x, jnp.int32), jnp.asarray(y, jnp.int32)
        acc = None
        for b in range(x.shape[0]):
            (loss, rows), grads = one(params, x[b], y[b])
            new = ((loss, grads), rows)
            acc = new if acc is None else add(acc, new)
        (loss, grads), rows = acc
        loss, grads = mean((loss, grads), jnp.float32(x.shape[0]))
        return loss, grads, rows

    return loss_and_grad


# ------------------------------------------------------------------ follower
def follow(loss_and_grad, params, batches, lr: float, beta1: float = 0.9,
           beta2: float = 0.999, epsilon: float = 1e-8):
    """Drive ``len(batches)`` Adam steps from ``params`` and return what
    ``correct`` compares: each step's loss, and per leaf the norm of Adam's
    first moment (``velocity_norm``) and of the parameters' change after the
    last step; also ``routed_rows``, per layer the pairs routed to the
    experts held here over all the steps. The moments and the starting
    parameters are kept on the host and visit the device leaf by leaf."""
    @jax.jit
    def leaf_step(p, m, v, g, t):
        m = beta1 * m + (1 - beta1) * g
        v = beta2 * v + (1 - beta2) * g * g
        alpha = lr * jnp.sqrt(1 - beta2 ** t) / (1 - beta1 ** t)
        return p - alpha * m / (jnp.sqrt(v) + epsilon), m, v

    norm = jax.jit(lambda a: jnp.sqrt(jnp.sum(jnp.square(a))))
    start = {k: np.asarray(v) for k, v in params.items()}
    m_host = {k: np.zeros(v.shape, np.float32) for k, v in start.items()}
    v_host = {k: np.zeros(v.shape, np.float32) for k, v in start.items()}
    losses, rows = [], []
    for step, (x, y) in enumerate(batches):
        loss, grads, routed = loss_and_grad(params, x, y)
        losses.append(float(loss))
        rows.append(np.asarray(routed))
        t = jnp.float32(step + 1)
        new = {}
        for k in list(params):
            new[k], m, v = leaf_step(params.pop(k), m_host[k], v_host[k],
                                     grads.pop(k), t)
            m_host[k], v_host[k] = np.asarray(m), np.asarray(v)
        params = new
    return {"losses": losses,
            "routed_rows": np.sum(rows, axis=0).tolist(),
            "velocity_norm": {k: float(norm(m)) for k, m in m_host.items()},
            "change_norm": {k: float(norm(params[k] - start[k]))
                            for k in params}}
