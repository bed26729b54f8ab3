"""Trinity-Mini (Arcee; ``model_type: "afmoe"``, ``config.json`` of
huggingface.co/arcee-ai/Trinity-Mini), one chip's share, in plain float32
``jax.numpy`` at "highest" precision.

Per sequence of T token ids (statistics, softmaxes, sigmoids and the router
float32), ``N(u; g) = u * rsqrt(mean(u^2) + eps) * g``:

* ``x = Emb[ids] * sqrt(F)``; ``n_layers`` blocks; ``N``; ``logits = h Wh``
  (untied); mean cross entropy over integer labels. No auxiliary loss.
* Block: ``h = x + N2(A(N1(x)))``, ``y = h + N4(Fn(N3(h)))``: four norms with
  scales of their own (``norm1_g``, ``post1_g``, ``norm2_g``, ``post2_g``).
* ``A(u)``: ``q = u Wq -> [T, H, D]``, ``k = u Wk -> [T, G, D]``, ``v = u Wv
  -> [T, G, D]``, ``z = u Wz -> [T, H D]``, no biases. ``q = N(q; gq)``,
  ``k = N(k; gk)`` over each head's D values, one scale vector for all heads.
  Sliding layers only: rotary embedding on all D dimensions of q and k,
  theta 10,000, no scaling, halves paired ``(x[i], x[i + D/2])``; full layers:
  none. Query head h uses key/value head ``h // (H / G)``. Scores ``q . k /
  sqrt(D)``; key j is visible to query i where ``j <= i``, and on a sliding
  layer ``i - j < W``; softmax; ``o = P v``; ``A = (o * sigmoid(z)) Wo``.
* ``Fn``, dense layers: ``(silu(u Wg) * (u Wu)) Wd``. Expert layers: ``s =
  sigmoid(u Wr)`` over all router outputs; the ``experts_per_token`` largest
  of ``s + b`` chosen (one group); weights ``w_e = s_e`` of the chosen
  (without b), ``w <- w / (sum w + 1e-20) * route_scale``; ``Fn = S(u) + sum
  over chosen e held here of w_e E_e(u)``, ``S`` and ``E_e`` SwiGLUs.
* After every step, outside the gradient: ``c_e`` = the step's (token,
  choice) pairs on output e, all outputs, over this chip's tokens; ``d =
  load_balance_coeff * sign(mean(c) - c)``; ``b <- b + d - mean(d)``. ``b``
  starts at 0, takes no gradient and has no optimizer state.

Departures from the source, all stated by the configuration:

* **share of experts**: the sum runs over the chosen experts whose id lies in
  ``experts_held``; every held expert is evaluated densely on every token and
  weighted where chosen, else 0 (no sort, no kernel). What absent experts
  would add is left out and the partial result goes on to the next block.
  The bias follows this chip's tokens' loads alone (a deployment sums ``c``
  over the chips that share the layer first).
* **sliced vocabulary** (``vocab_rows``), **depth** (``n_layers``,
  ``n_dense_layers``, ``layer_types``).
* ``assumed``: initialisation (normal, std 0.02, norm scales 1), Adam in the
  order of Kingma & Ba's section 2 note, as ``deepseek_v2_lite.py`` applies
  it, and every point above that the catalog's row does not give (recalled
  from the published ``modeling_afmoe.py``, not checked here).

Computed one sequence at a time (no layer mixes sequences), each block under
``jax.checkpoint``, the scores of one query head at a time (``lax.map``
over the heads, each reading its key/value head in place and checkpointed:
32 heads x 8,192^2 x 4 B would be 8.6 GB whole); Adam's moments live on the
host between steps. Keys are the
program's leaf names, ``"<layer index>/<param>"``. Nothing here imports the
program under test.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import common as C

_HI = lax.Precision.HIGHEST
SLIDING, FULL = "sliding_attention", "full_attention"
_DEFAULTS = dict(
    n_layers=32, experts_held=None, vocab_rows=200192, hidden_size=2048,
    n_heads=32, n_kv_heads=4, head_dim=128, sliding_window=2048,
    layer_types=None, global_attn_every_n_layers=4, intermediate_size=6144,
    moe_intermediate_size=1024, n_router_outputs=128, experts_per_token=8,
    n_shared_experts=1, n_dense_layers=2, route_scale=2.826,
    load_balance_coeff=0.001, rms_norm_eps=1e-5, rope_theta=10000.0,
    mup_enabled=True, seq_len=8192)
INIT_STD = 0.02


def _cfg(cfg) -> dict:
    c = dict(_DEFAULTS, **{k: v for k, v in cfg.items() if k in _DEFAULTS})
    if c["layer_types"] is None:
        every = c["global_attn_every_n_layers"]
        c["layer_types"] = [FULL if (i + 1) % every == 0 else SLIDING
                            for i in range(c["n_layers"])]
    if len(c["layer_types"]) != c["n_layers"]:
        raise ValueError("layer_types does not name every layer held")
    first, end = c["experts_held"] or (0, c["n_router_outputs"])
    c["first_held"], c["n_held"] = int(first), int(end) - int(first)
    return c


def _window(c, i):
    """The keys a query of block ``i`` (1-based) sees, counting itself."""
    return c["sliding_window"] if c["layer_types"][i - 1] == SLIDING else None


def visible_pairs(seq: int, window) -> int:
    """(query, key) pairs a causal mask cut to ``window`` leaves visible."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


# ------------------------------------------------------------ what it costs
def layers(cfg) -> list:
    """Every product a step requires, as ``dense`` entries whose ``nin *
    nout`` is the multiply-accumulates of one sample, a sequence of
    ``seq_len`` tokens: projections and the gate, attention scores and values
    at each layer's own visible pairs, the router, the shared expert, routed
    experts at ``experts_per_token * held / router outputs`` of an expert a
    token, the head. (The embedding is a lookup.)"""
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
        add(f"{i}/Wq", T * F, H * D)
        add(f"{i}/Wk", T * F, G * D)
        add(f"{i}/Wv", T * F, G * D)
        add(f"{i}/Wz", T * F, H * D)
        add(f"{i}/core", H * visible_pairs(T, _window(c, i)), 2 * D,
            "attn/core")
        add(f"{i}/Wo", T * H * D, F)
        if i <= c["n_dense_layers"]:
            add(f"{i}/ffn", T * F, 3 * c["intermediate_size"])
        else:
            add(f"{i}/Wr", T * F, c["n_router_outputs"])
            add(f"{i}/shared", T * F, 3 * c["n_shared_experts"] * He)
            add(f"{i}/routed", routed_rows // c["n_router_outputs"] * F,
                3 * He, "moe/experts")
    add(f"{c['n_layers'] + 2}/W", T * F, c["vocab_rows"])
    return out


# ------------------------------------------------------------------ weights
def _shapes(c) -> dict:
    F, H, G, D = (c["hidden_size"], c["n_heads"], c["n_kv_heads"],
                  c["head_dim"])
    He, held = c["moe_intermediate_size"], c["n_held"]
    s = {"0/W": (c["vocab_rows"], F)}
    for i in range(1, c["n_layers"] + 1):
        s.update({f"{i}/norm1_g": (F,), f"{i}/norm2_g": (F,),
                  f"{i}/post1_g": (F,), f"{i}/post2_g": (F,),
                  f"{i}/Wq": (F, H * D), f"{i}/Wk": (F, G * D),
                  f"{i}/Wv": (F, G * D), f"{i}/Wz": (F, H * D),
                  f"{i}/q_norm_g": (D,), f"{i}/k_norm_g": (D,),
                  f"{i}/Wo": (H * D, F)})
        if i <= c["n_dense_layers"]:
            I = c["intermediate_size"]
            s.update({f"{i}/Wg": (F, I), f"{i}/Wu": (F, I), f"{i}/Wd": (I, F)})
        else:
            Hs = c["n_shared_experts"] * He
            s.update({f"{i}/Wr": (F, c["n_router_outputs"]),
                      f"{i}/Eg": (held, F, He), f"{i}/Eu": (held, F, He),
                      f"{i}/Ed": (held, He, F), f"{i}/Sg": (F, Hs),
                      f"{i}/Su": (F, Hs), f"{i}/Sd": (Hs, F)})
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


def attention(p, i, u, c, precision):
    T = u.shape[0]
    H, G, D = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    eps, window = c["rms_norm_eps"], _window(c, i)
    q = _rms(_mm(u, p[f"{i}/Wq"], precision).reshape(T, H, D),
             p[f"{i}/q_norm_g"], eps)
    k = _rms(_mm(u, p[f"{i}/Wk"], precision).reshape(T, G, D),
             p[f"{i}/k_norm_g"], eps)
    v = _mm(u, p[f"{i}/Wv"], precision).reshape(T, G, D)
    z = _mm(u, p[f"{i}/Wz"], precision)
    if window is not None:
        q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    ahead = np.arange(T)[:, None] - np.arange(T)[None, :]
    seen = ahead >= 0
    if window is not None:
        seen &= ahead < window
    seen = jnp.asarray(seen)
    dot = C._product(lambda a, b: jnp.einsum("qd,kd->qk", a, b,
                                             precision=_HI), precision)
    mix = C._product(lambda a, b: jnp.einsum("qk,kd->qd", a, b,
                                             precision=_HI), precision)
    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)     # [G, T, D]

    def head(args):
        """One query head against the key/value head it reads; its [T, T]
        scores are made again on the way back, never kept."""
        qh, g = args
        s = dot(qh, k[g]) * D ** -0.5
        prob = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return mix(prob, v[g])

    o = lax.map(jax.checkpoint(head),
                (q.transpose(1, 0, 2), jnp.arange(H) // (H // G)))
    o = o.transpose(1, 0, 2).reshape(T, H * D)
    return _mm(o * jax.nn.sigmoid(z), p[f"{i}/Wo"], precision)


def route(u, wr, bias, k, route_scale):
    """-> (choice [T, k], weight [T, k], scores [T, E]), float32
    throughout: the k largest of ``sigmoid(u Wr) + bias``, weighted by their
    scores renormalised to ``route_scale``."""
    scores = jax.nn.sigmoid(jnp.matmul(u, wr, precision=_HI))
    _, choice = lax.top_k(scores + bias, k)
    weight = jnp.take_along_axis(scores, choice, axis=-1)
    weight = weight / (jnp.sum(weight, axis=-1, keepdims=True)
                       + 1e-20) * route_scale
    return choice, weight, scores


def expert_layer(p, i, u, c, precision, bias=None, shared=True):
    """-> (Fn(u) over the experts held here [+ the shared expert], the
    (token, choice) pairs on each router output [E], those that fell on an
    expert held here)."""
    E = c["n_router_outputs"]
    bias = jnp.zeros((E,), jnp.float32) if bias is None else bias
    choice, weight, _ = route(u, p[f"{i}/Wr"], lax.stop_gradient(bias),
                              c["experts_per_token"], c["route_scale"])
    y = jnp.zeros_like(u)
    for e in range(c["n_held"]):
        w_e = jnp.sum(jnp.where(choice == c["first_held"] + e, weight, 0.0),
                      axis=-1)
        y = y + w_e[:, None] * _swiglu(u, p[f"{i}/Eg"][e], p[f"{i}/Eu"][e],
                                       p[f"{i}/Ed"][e], precision)
    if shared:
        y = y + _swiglu(u, p[f"{i}/Sg"], p[f"{i}/Su"], p[f"{i}/Sd"],
                        precision)
    load = jnp.zeros((E,), jnp.float32).at[choice.reshape(-1)].add(1.0)
    here = ((choice >= c["first_held"])
            & (choice < c["first_held"] + c["n_held"]))
    return y, load, jnp.sum(here)


def next_bias(bias, load, coeff):
    """The bias after a step whose choices put ``load`` pairs on each
    output."""
    d = coeff * jnp.sign(jnp.mean(load) - load)
    return bias + d - jnp.mean(d)


def _block(p, i, x, c, precision, bias):
    eps = c["rms_norm_eps"]
    a = attention(p, i, _rms(x, p[f"{i}/norm1_g"], eps), c, precision)
    h = x + _rms(a, p[f"{i}/post1_g"], eps)
    u = _rms(h, p[f"{i}/norm2_g"], eps)
    if i <= c["n_dense_layers"]:
        f = _swiglu(u, p[f"{i}/Wg"], p[f"{i}/Wu"], p[f"{i}/Wd"], precision)
        return h + _rms(f, p[f"{i}/post2_g"], eps), None, None
    f, load, rows = expert_layer(p, i, u, c, precision, bias)
    return h + _rms(f, p[f"{i}/post2_g"], eps), load, rows


def sequence_logits(p, ids, c, precision="float32", biases=None):
    """-> (logits [T, vocab_rows], loads [expert layers, E], rows routed
    here per expert layer)."""
    x = p["0/W"][ids]
    if c["mup_enabled"]:
        x = x * math.sqrt(c["hidden_size"])
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
    fell on an expert held here. ``.with_loads(params, ids, labels, biases)``
    takes the routers' biases (``{layer index: [E]}``, None: zeros) and also
    returns the batch's pairs on every router output ``[expert layers, E]``,
    what the bias update reads. ``stage_dtype`` does not touch integer
    ids."""
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


# ------------------------------------------------------------------ follower
def follow(loss_and_grad, params, batches, lr: float, beta1: float = 0.9,
           beta2: float = 0.999, epsilon: float = 1e-8):
    """Drive ``len(batches)`` Adam steps from ``params``, the routers' biases
    from 0 and moved after every step, and return what ``correct`` compares:
    each step's loss, and per leaf the norm of Adam's first moment
    (``velocity_norm``) and of the parameters' change after the last step;
    also ``routed_rows``, per expert layer the pairs routed to the experts
    held here over all the steps, and ``router_bias``, per expert layer the
    bias after the last step. The moments and the starting parameters are
    kept on the host and visit the device leaf by leaf."""
    @jax.jit
    def leaf_step(p, m, v, g, t):
        m = beta1 * m + (1 - beta1) * g
        v = beta2 * v + (1 - beta2) * g * g
        alpha = lr * jnp.sqrt(1 - beta2 ** t) / (1 - beta1 ** t)
        return p - alpha * m / (jnp.sqrt(v) + epsilon), m, v

    c = loss_and_grad.cfg
    norm = jax.jit(lambda a: jnp.sqrt(jnp.sum(jnp.square(a))))
    start = {k: np.asarray(v) for k, v in params.items()}
    m_host = {k: np.zeros(v.shape, np.float32) for k, v in start.items()}
    v_host = {k: np.zeros(v.shape, np.float32) for k, v in start.items()}
    biases = init_bias(c)
    losses, rows = [], []
    for step, (x, y) in enumerate(batches):
        loss, grads, routed, loads = loss_and_grad.with_loads(
            params, x, y, biases)
        losses.append(float(loss))
        rows.append(np.asarray(routed))
        biases = {i: next_bias(b, loads[j], c["load_balance_coeff"])
                  for j, (i, b) in enumerate(sorted(biases.items()))}
        t = jnp.float32(step + 1)
        new = {}
        for k in list(params):
            new[k], m, v = leaf_step(params.pop(k), m_host[k], v_host[k],
                                     grads.pop(k), t)
            m_host[k], v_host[k] = np.asarray(m), np.asarray(v)
        params = new
    return {"losses": losses,
            "routed_rows": np.sum(rows, axis=0).tolist(),
            "router_bias": {str(i): np.asarray(b).tolist()
                            for i, b in sorted(biases.items())},
            "velocity_norm": {k: float(norm(m)) for k, m in m_host.items()},
            "change_norm": {k: float(norm(params[k] - start[k]))
                            for k in params}}
