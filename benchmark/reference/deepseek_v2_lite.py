"""DeepSeek-V2-Lite (arXiv:2405.04434; ``config.json`` and
``modeling_deepseek.py`` of huggingface.co/deepseek-ai/DeepSeek-V2-Lite), one
chip's share, in plain float32 ``jax.numpy`` at "highest" precision.

Per sequence ``x`` of T token ids (statistics and softmaxes float32):

* embedding lookup; ``n_layers`` pre-norm blocks ``h = x + MLA(RMSNorm(x))``,
  ``y = h + F(RMSNorm(h))``; ``RMSNorm(u) = u * rsqrt(mean(u^2) + eps) * g``;
  a last RMSNorm; ``logits = h Wh``; mean cross entropy over integer labels.
* MLA: ``q = u Wq -> [T, H, dn + dr]``; ``u Wkva -> [T, r + dr]`` split into
  the latent ``c`` and one rotary key ``k_pe`` for all heads; ``RMSNorm(c)
  Wkvb -> [T, H, dn + dv]`` split into ``k_nope | v``; RoPE with YaRN
  frequencies on ``q_pe`` and ``k_pe``, pairs ``(x[2i], x[2i+1])`` rotated and
  laid out as the source's ``apply_rotary_pos_emb`` leaves them; scores
  ``(q_nope . k_nope + q_pe . k_pe) * (dn + dr)^-0.5 * m^2`` with ``m = 0.1 *
  mscale_all_dim * ln(factor) + 1``; causal softmax; ``P v``; ``Wo``.
* ``F``: SwiGLU ``(silu(u Wg) * (u Wu)) Wd`` in the first ``first_k_dense``
  blocks; in the others ``sum_e p_e E_e(u) + S(u)``: ``p = softmax(u Wr)``
  over all router outputs, the ``experts_per_token`` largest (greedy, one
  group), weight ``p_e`` not renormalised, ``E_e`` a SwiGLU, ``S`` one SwiGLU
  as wide as the shared experts together. The sequence-wise auxiliary loss
  is the source's ``seq_aux`` branch, over all router outputs.

Departures from the source, all stated by the configuration:

* **share of experts**: the sum runs over the chosen experts whose id lies in
  ``experts_held``; every held expert is evaluated densely on every token and
  weighted by the token's probability for it where chosen, else 0 (no sort,
  no kernel). What absent experts would add is left out and the partial
  result goes on to the next block.
* **sliced vocabulary** (``vocab_rows``) and **depth** (``n_layers``).
* ``assumed``: auxiliary-loss weight, initialisation (normal, std 0.02, norm
  scales 1), Adam in the order of Kingma & Ba's section 2 note
  (``alpha_t = lr * sqrt(1 - b2^t) / (1 - b1^t)``, ``p -= alpha_t * m /
  (sqrt(v) + eps)``), which is how the program's updater applies it.
* YaRN's factor on cos and sin, ``mscale / mscale_all_dim``, is 1 here.

Computed one sequence at a time (no layer mixes sequences), each block under
``jax.checkpoint``; Adam's moments live on the host between steps, so the
device holds the parameters, two gradient trees and one sequence's
activations. Keys are the program's leaf names, ``"<layer index>/<param>"``.
Nothing here imports the program under test.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import common as C

_HI = lax.Precision.HIGHEST
_YARN = {"type": "yarn", "factor": 40, "original_max_position_embeddings": 4096,
         "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
         "mscale_all_dim": 0.707}
_DEFAULTS = dict(
    n_layers=27, experts_held=None, vocab_rows=102400, hidden_size=2048,
    n_heads=16, kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
    v_head_dim=128, intermediate_size=10944, moe_intermediate_size=1408,
    n_router_outputs=64, experts_per_token=6, n_shared_experts=2,
    first_k_dense=1, rms_norm_eps=1e-6, rope_theta=10000.0,
    rope_scaling=None, aux_loss_weight=0.001, seq_len=4096)
INIT_STD = 0.02


def _cfg(cfg) -> dict:
    c = dict(_DEFAULTS, **{k: v for k, v in cfg.items() if k in _DEFAULTS})
    c["rope_scaling"] = dict(c["rope_scaling"] or _YARN)
    first, end = c["experts_held"] or (0, c["n_router_outputs"])
    c["first_held"], c["n_held"] = int(first), int(end) - int(first)
    return c


# ------------------------------------------------------------ what it costs
def layers(cfg) -> list:
    """Every product a step requires, as ``dense`` entries whose ``nin *
    nout`` is the multiply-accumulates of one sample, a sequence of
    ``seq_len`` tokens: projections, attention scores and values at the
    causal half, the router, shared experts, routed experts at
    ``experts_per_token * held / router outputs`` of an expert a token, the
    head. (The embedding is a lookup.)"""
    c = _cfg(cfg)
    T, F, H = c["seq_len"], c["hidden_size"], c["n_heads"]
    dn, dr, dv, r = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"], c["kv_lora_rank"])
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
        add(f"{i}/Wq", T * F, H * (dn + dr))
        add(f"{i}/Wkva", T * F, r + dr)
        add(f"{i}/Wkvb", T * r, H * (dn + dv))
        add(f"{i}/core", H * (T * (T + 1) // 2), dn + dr + dv,
            "attn/core")
        add(f"{i}/Wo", T * H * dv, F)
        if i <= c["first_k_dense"]:
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
    F, H = c["hidden_size"], c["n_heads"]
    dn, dr, dv, r = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                     c["v_head_dim"], c["kv_lora_rank"])
    He, G = c["moe_intermediate_size"], c["n_held"]
    s = {"0/W": (c["vocab_rows"], F)}
    for i in range(1, c["n_layers"] + 1):
        s.update({f"{i}/norm1_g": (F,), f"{i}/norm2_g": (F,),
                  f"{i}/Wq": (F, H * (dn + dr)), f"{i}/Wkva": (F, r + dr),
                  f"{i}/kv_norm_g": (r,), f"{i}/Wkvb": (r, H * (dn + dv)),
                  f"{i}/Wo": (H * dv, F)})
        if i <= c["first_k_dense"]:
            I = c["intermediate_size"]
            s.update({f"{i}/Wg": (F, I), f"{i}/Wu": (F, I), f"{i}/Wd": (I, F)})
        else:
            Hs = c["n_shared_experts"] * He
            s.update({f"{i}/Wr": (F, c["n_router_outputs"]),
                      f"{i}/Eg": (G, F, He), f"{i}/Eu": (G, F, He),
                      f"{i}/Ed": (G, He, F), f"{i}/Sg": (F, Hs),
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


# --------------------------------------------------------------------- math
def _mm(a, w, precision):
    return C._product(lambda x, m: jnp.matmul(x, m, precision=_HI),
                      precision)(a, w)


def _rms(u, g, eps):
    return u * lax.rsqrt(jnp.mean(u * u, axis=-1, keepdims=True) + eps) * g


def _swiglu(u, wg, wu, wd, precision):
    return _mm(jax.nn.silu(_mm(u, wg, precision)) * _mm(u, wu, precision),
               wd, precision)


def _yarn_inv_freq(dim, theta, sc):
    """The source's ``DeepseekV2YarnRotaryEmbedding._set_cos_sin_cache``."""
    pos = np.arange(0, dim, 2, dtype=np.float64) / dim
    extra = 1.0 / theta ** pos
    inter = 1.0 / (sc["factor"] * theta ** pos)
    orig = sc["original_max_position_embeddings"]

    def correction(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction(sc["beta_fast"])), 0)
    high = min(math.ceil(correction(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask


def _rope(x, inv_freq):
    """x [T, H, D]: de-interleave to (evens | odds), then ``x * cos +
    rotate_half(x) * sin``, as the source's ``apply_rotary_pos_emb``."""
    T = x.shape[0]
    ang = np.arange(T, dtype=np.float64)[:, None] * inv_freq[None, :]
    emb = np.concatenate([ang, ang], axis=-1)
    cos = jnp.asarray(np.cos(emb), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(emb), jnp.float32)[:, None, :]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    half = x.shape[-1] // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def _mla(p, i, u, c, precision):
    T = u.shape[0]
    H, dn, dr, dv, r = (c["n_heads"], c["qk_nope_head_dim"],
                        c["qk_rope_head_dim"], c["v_head_dim"],
                        c["kv_lora_rank"])
    sc = c["rope_scaling"]
    q = _mm(u, p[f"{i}/Wq"], precision).reshape(T, H, dn + dr)
    kva = _mm(u, p[f"{i}/Wkva"], precision)
    latent = _rms(kva[:, :r], p[f"{i}/kv_norm_g"], c["rms_norm_eps"])
    kv = _mm(latent, p[f"{i}/Wkvb"], precision).reshape(T, H, dn + dv)
    freq = _yarn_inv_freq(dr, c["rope_theta"], sc)
    q_pe = _rope(q[..., dn:], freq)
    k_pe = _rope(kva[:, r:].reshape(T, 1, dr), freq)
    m = 0.1 * sc["mscale_all_dim"] * math.log(sc["factor"]) + 1.0
    scale = (dn + dr) ** -0.5 * m * m
    dot = C._product(lambda a, b: jnp.einsum("qhd,khd->hqk", a, b,
                                             precision=_HI), precision)
    s = (dot(q[..., :dn], kv[..., :dn])
         + dot(q_pe, jnp.broadcast_to(k_pe, (T, H, dr)))) * scale
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None], s, -jnp.inf)
    prob = jax.nn.softmax(s, axis=-1)
    o = C._product(lambda a, b: jnp.einsum("hqk,khd->qhd", a, b,
                                           precision=_HI),
                   precision)(prob, kv[..., dn:])
    return _mm(o.reshape(T, H * dv), p[f"{i}/Wo"], precision)


def route(u, wr, k):
    """-> (choice [T, k], weight [T, k], probs [T, E]), float32 throughout."""
    probs = jax.nn.softmax(jnp.matmul(u, wr, precision=_HI), axis=-1)
    weight, choice = lax.top_k(probs, k)
    return choice, weight, probs


def _seq_aux(choice, probs, c):
    """The source's ``seq_aux`` branch for one sequence, before its weight."""
    E, (T, k) = c["n_router_outputs"], choice.shape
    count = jnp.zeros((E,), jnp.float32).at[choice.reshape(-1)].add(1.0)
    return jnp.sum(count / (T * k / E) * jnp.mean(probs, axis=0))


def expert_layer(p, i, u, c, precision, shared=True):
    """-> (F(u) over the experts held here [+ the shared expert], aux, the
    (token, choice) pairs that fell on an expert held here)."""
    choice, weight, probs = route(u, p[f"{i}/Wr"], c["experts_per_token"])
    y = jnp.zeros_like(u)
    for e in range(c["n_held"]):
        w_e = jnp.sum(jnp.where(choice == c["first_held"] + e, weight, 0.0),
                      axis=-1)
        y = y + w_e[:, None] * _swiglu(u, p[f"{i}/Eg"][e], p[f"{i}/Eu"][e],
                                       p[f"{i}/Ed"][e], precision)
    if shared:
        y = y + _swiglu(u, p[f"{i}/Sg"], p[f"{i}/Su"], p[f"{i}/Sd"],
                        precision)
    here = ((choice >= c["first_held"])
            & (choice < c["first_held"] + c["n_held"]))
    return y, _seq_aux(choice, probs, c), jnp.sum(here)


def _block(p, i, x, c, precision):
    eps = c["rms_norm_eps"]
    h = x + _mla(p, i, _rms(x, p[f"{i}/norm1_g"], eps), c, precision)
    u = _rms(h, p[f"{i}/norm2_g"], eps)
    if i <= c["first_k_dense"]:
        return h + _swiglu(u, p[f"{i}/Wg"], p[f"{i}/Wu"], p[f"{i}/Wd"],
                           precision), jnp.float32(0), None
    f, aux, rows = expert_layer(p, i, u, c, precision)
    return h + f, aux, rows


def _sequence_loss(p, ids, labels, c, precision):
    """-> (loss, rows routed here per expert layer [n expert layers])."""
    x = p["0/W"][ids]
    aux, rows = jnp.float32(0), []
    for i in range(1, c["n_layers"] + 1):
        x, a, r = jax.checkpoint(
            lambda pp, xx, _i=i: _block(pp, _i, xx, c, precision))(
                {k: v for k, v in p.items() if k.startswith(f"{i}/")}, x)
        aux = aux + a
        if r is not None:
            rows.append(r)
    n = c["n_layers"]
    h = _rms(x, p[f"{n + 1}/g"], c["rms_norm_eps"])
    logits = _mm(h, p[f"{n + 2}/W"], precision)
    logp = jax.nn.log_softmax(logits, axis=-1)
    xent = -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=-1))
    return xent + c["aux_loss_weight"] * aux, jnp.stack(rows)


def make_loss_and_grad(cfg, precision: str = "float32", stage_dtype=None):
    """``(params, ids [B, T], labels [B, T]) -> (loss, grads, rows)``: the
    batch's mean loss and gradient over its sequences, one sequence at a
    time, and per expert layer the (token, choice) pairs of the batch that
    fell on an expert held here. ``stage_dtype`` does not touch integer
    ids."""
    c = _cfg(cfg)
    one = jax.jit(jax.value_and_grad(
        lambda p, x, y: _sequence_loss(p, x, y, c, precision), has_aux=True))
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
    last step; also ``routed_rows``, per expert layer the pairs routed to the
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
