"""Nemotron-3-Nano-30B-A3B (NVIDIA; ``model_type: "nemotron_h"``,
``config.json`` of huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16),
one chip's share, in plain float32 ``jax.numpy`` at "highest" precision.

Per sequence of T token ids (statistics, softmaxes, sigmoids, the taps, the
step sizes, the decays and the state float32), ``N(u; g) = u * rsqrt(mean(u^2)
+ eps) * g``:

* ``x = Emb[ids]`` (unscaled); the blocks; ``N``; ``logits = h Wh``
  (untied); mean cross entropy over integer labels. No auxiliary loss.
* Block ``i``, one branch named by the letter ``pattern[i - 1]``: ``y = x +
  P(N(x; g))``, ``g`` the block's ``norm1_g`` before a mixer (``M``, ``*``)
  and ``norm2_g`` before the experts (``E``).
* ``M``, a Mamba-2 mixer, ``d = H P`` (``ssm_heads`` x ``ssm_head_dim``), ``G``
  groups of ``N``-wide B and C: ``[z | xBC | dt] = u W_in`` (``W_in`` [F, d
  + (d + 2 G N) + H], split in that order); ``xBC' = silu(b_c + sum_{j<L}
  w_j * xBC_{t-L+1+j})`` with ``xBC_s = 0`` for ``s < 0`` (``w`` [L, d + 2 G
  N] one filter a channel; ``w_{L-1}`` weighs the token itself); ``[x | B |
  C] = xBC'`` split ``d / G N / G N``, ``x`` as [T, H, P], ``B``, ``C`` as
  [T, G, N], head h on group ``h // (H / G)``; ``dt = softplus(dt +
  dt_bias)`` (no clamp), ``A = -exp(A_log)``; **the recurrence as written**:
  ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`` (``S`` [P, N] a head, ``S_{-1}
  = 0``), ``y_t = S_t C_t + D x_t``; ``y * silu(z)`` RMS-normed over each
  of ``G`` groups of ``d / G`` channels, times ``ssm_norm_g``; ``P = y
  W_out``.
* ``E``: ``s = sigmoid(u Wr)`` over all router outputs; the
  ``experts_per_token`` largest of ``s + b`` chosen (``b`` the selection
  bias, no gradient); weights ``w_e = s_e / (sum of the chosen s + 1e-20) *
  routed_scaling_factor``; ``P = sum over chosen e held here of w_e
  relu(u U_e)^2 D_e + relu(u U_s)^2 D_s``, the last the shared expert.
* ``*``: ``q = u Wq -> [T, H, D]``, ``k = u Wk``, ``v = u Wv -> [T, G, D]``;
  query head h reads key/value head ``h // (H / G)``; causal softmax of ``q .
  k / sqrt(D)``; ``P = o Wo``. No rotary embedding, no q/k norm, no gate.
* After every step, outside the gradient, the bias moves as Trinity-Mini's
  (``trinity_mini.next_bias``): ``d = load_balance_coeff * sign(mean(c) -
  c)``, ``b <- b + d - mean(d)`` with ``c`` the step's (token, choice) pairs
  on each output over this chip's tokens.

Departures from the source, all stated by the configuration: the **share of
experts** (the sum runs over the chosen experts whose id lies in
``experts_held``; every held expert is evaluated densely on every token and
weighted where chosen, else 0; the shared expert counts whole), the **sliced
vocabulary** (``vocab_rows``), the **depth** (``pattern``: the layers held)
and ``assumed`` (initialisation, Adam, the bias's rule and rate, the untied
head, the mixer's layout and the attention's lack of a rotary embedding, as
recalled from the published ``modeling_nemotron_h.py``, not checked here).

Computed one sequence at a time (no layer mixes sequences), each block under
``jax.checkpoint``. The recurrence runs a token at a time (``lax.scan``)
within blocks of ``SCAN_BLOCK`` tokens, the state carried from block to
block, each block checkpointed: the backward keeps one [H, P, N] state a
block and not one a token (one a token would be 34 GB a layer at 16,384). In
the ``precision`` of a control the scan's operands ``x``, ``B`` and ``C``
and their cotangents are rounded once, a tensor each, as every other
product's are; the per-token arithmetic stays float32.
The attention core runs over blocks of ``QUERY_BLOCK`` queries, one
key/value group at a time, each checkpointed; the experts over chunks of
``lfm2_moe.TOKEN_CHUNK`` tokens. Adam's moments live on the host between steps
(``follow``). Keys are the program's leaf names, ``"<layer index>/<param>"``.
Nothing here imports the program under test.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from costs_ssd import chunk_pairs

from . import common as C
from .lfm2_moe import QUERY_BLOCK, _chunked, _rows_of
from .trinity_mini import _mm, _rms, next_bias, route, visible_pairs

_HI = lax.Precision.HIGHEST
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
_DEFAULTS = dict(
    pattern=None, experts_held=None, vocab_rows=131072, hidden_size=2688,
    n_heads=32, n_kv_heads=2, head_dim=128, ssm_heads=64, ssm_head_dim=64,
    ssm_state=128, ssm_groups=8, ssm_chunk=128, conv_kernel=4,
    moe_intermediate_size=1856, shared_intermediate_size=3712,
    n_router_outputs=128, experts_per_token=6, routed_scaling_factor=2.5,
    load_balance_coeff=0.001, rms_norm_eps=1e-5, seq_len=16384)
INIT_STD = 0.02
#: tokens a checkpointed block of the recurrence holds
SCAN_BLOCK = 64


def _cfg(cfg) -> dict:
    c = dict(_DEFAULTS, **{k: v for k, v in cfg.items() if k in _DEFAULTS})
    if c["pattern"] is None:
        c["pattern"] = PUBLISHED_PATTERN
    if set(c["pattern"]) - {MAMBA, EXPERTS, ATTENTION}:
        raise ValueError(f"pattern {c['pattern']!r}")
    first, end = c["experts_held"] or (0, c["n_router_outputs"])
    c["first_held"], c["n_held"] = int(first), int(end) - int(first)
    c["n_layers"] = len(c["pattern"])
    return c


def _kind(c, i):
    """The letter of block ``i`` (1-based)."""
    return c["pattern"][i - 1]


def _widths(c):
    """``(d, conv channels)`` of a Mamba-2 mixer."""
    d = c["ssm_heads"] * c["ssm_head_dim"]
    return d, d + 2 * c["ssm_groups"] * c["ssm_state"]


# ------------------------------------------------------------ what it costs
def layers(cfg) -> list:
    """Every product a step requires, as ``dense`` entries whose ``nin *
    nout`` is the multiply-accumulates of one sample, a sequence of
    ``seq_len`` tokens: a Mamba-2 mixer's two projections and the chunked
    scan's four products at ``ssm_chunk`` (``C B^T`` and its mix with the
    inputs at the causal pairs of each chunk, the chunks' states and the
    carried states' part, under ``attn/ssd``); attention's projections and
    its scores and values at the causal pairs (``attn/core``); the router,
    the shared expert, routed experts at ``experts_per_token * held / router
    outputs`` of an expert a token (``moe/experts``), two products each; the
    head. (The embedding is a lookup.)"""
    c = _cfg(cfg)
    T, F, H, G, D = (c["seq_len"], c["hidden_size"], c["n_heads"],
                     c["n_kv_heads"], c["head_dim"])
    Hm, P, Gm, N = (c["ssm_heads"], c["ssm_head_dim"], c["ssm_groups"],
                    c["ssm_state"])
    d, conv_dim = _widths(c)
    He, Hs = c["moe_intermediate_size"], c["shared_intermediate_size"]
    routed_rows = T * c["experts_per_token"] * c["n_held"]
    if routed_rows % c["n_router_outputs"]:
        raise ValueError("the expected routed rows of a sequence are not whole")
    pairs = chunk_pairs(T, c["ssm_chunk"])
    out = []

    def add(name, nin, nout, scope=None):
        entry = {"kind": "dense", "name": name, "nin": nin, "nout": nout,
                 "first": False}
        if scope:   # a kernel with a roofline metric of its own runs it
            entry["scope"] = scope
        out.append(entry)

    for i in range(1, c["n_layers"] + 1):
        kind = _kind(c, i)
        if kind == MAMBA:
            add(f"{i}/W_in", T * F, d + conv_dim + Hm)
            add(f"{i}/ssd_scores", Gm * pairs, N, "attn/ssd")
            add(f"{i}/ssd_mix", Hm * pairs, P, "attn/ssd")
            add(f"{i}/ssd_states", T * Hm, P * N, "attn/ssd")
            add(f"{i}/ssd_out", T * Hm, P * N, "attn/ssd")
            add(f"{i}/W_out", T * d, F)
        elif kind == ATTENTION:
            add(f"{i}/Wq", T * F, H * D)
            add(f"{i}/Wk", T * F, G * D)
            add(f"{i}/Wv", T * F, G * D)
            add(f"{i}/core", H * visible_pairs(T, None), 2 * D, "attn/core")
            add(f"{i}/Wo", T * H * D, F)
        else:
            add(f"{i}/Wr", T * F, c["n_router_outputs"])
            add(f"{i}/shared", T * F, 2 * Hs)
            add(f"{i}/routed", routed_rows // c["n_router_outputs"] * F,
                2 * He, "moe/experts")
    add(f"{c['n_layers'] + 2}/W", T * F, c["vocab_rows"])
    return out


# ------------------------------------------------------------------ weights
def _shapes(c) -> dict:
    F, H, G, D = (c["hidden_size"], c["n_heads"], c["n_kv_heads"],
                  c["head_dim"])
    He, Hs, held = (c["moe_intermediate_size"], c["shared_intermediate_size"],
                    c["n_held"])
    Hm = c["ssm_heads"]
    d, conv_dim = _widths(c)
    s = {"0/W": (c["vocab_rows"], F)}
    for i in range(1, c["n_layers"] + 1):
        kind = _kind(c, i)
        if kind == MAMBA:
            s.update({f"{i}/norm1_g": (F,), f"{i}/W_in": (F, d + conv_dim + Hm),
                      f"{i}/conv_w": (c["conv_kernel"], conv_dim),
                      f"{i}/conv_b": (conv_dim,), f"{i}/dt_bias": (Hm,),
                      f"{i}/A_log": (Hm,), f"{i}/D": (Hm,),
                      f"{i}/ssm_norm_g": (d,), f"{i}/W_out": (d, F)})
        elif kind == ATTENTION:
            s.update({f"{i}/norm1_g": (F,), f"{i}/Wq": (F, H * D),
                      f"{i}/Wk": (F, G * D), f"{i}/Wv": (F, G * D),
                      f"{i}/Wo": (H * D, F)})
        else:
            s.update({f"{i}/norm2_g": (F,),
                      f"{i}/Wr": (F, c["n_router_outputs"]),
                      f"{i}/Eu": (held, F, He), f"{i}/Ed": (held, He, F),
                      f"{i}/Su": (F, Hs), f"{i}/Sd": (Hs, F)})
    s[f"{c['n_layers'] + 1}/g"] = (F,)
    s[f"{c['n_layers'] + 2}/W"] = (F, c["vocab_rows"])
    return s


def _leaf(name, shape, key):
    """One leaf's starting value: the taps' bias 0; ``dt_bias`` softplus^-1
    of a step drawn log-uniform in [0.001, 0.1] and floored at 1e-4;
    ``A_log = log U[1, 16]``; ``D``, norm scales 1; matrices (the taps too)
    normal(0.02)."""
    leaf = name.split("/", 1)[1]
    if leaf == "conv_b":
        return jnp.zeros(shape, jnp.float32)
    if leaf == "dt_bias":
        lo, hi = np.log(1e-3), np.log(1e-1)
        dt = jnp.maximum(jnp.exp(jax.random.uniform(key, shape) * (hi - lo)
                                 + lo), 1e-4)
        return dt + jnp.log(-jnp.expm1(-dt))
    if leaf == "A_log":
        return jnp.log(jax.random.uniform(key, shape, minval=1.0,
                                          maxval=16.0))
    if len(shape) == 1:
        return jnp.ones(shape, jnp.float32)
    return INIT_STD * jax.random.normal(key, shape, jnp.float32)


def init(seed: int, cfg) -> dict:
    shapes = _shapes(_cfg(cfg))

    def make(key):
        return {name: _leaf(name, shape, kk) for kk, (name, shape) in
                zip(jax.random.split(key, len(shapes)), shapes.items())}

    return jax.jit(make)(jax.random.PRNGKey(seed % (2 ** 31)))


def init_bias(cfg) -> dict:
    """The routers' biases before the first step: ``{layer index: zeros}``
    for every ``E`` block."""
    c = _cfg(cfg)
    return {i: jnp.zeros((c["n_router_outputs"],), jnp.float32)
            for i in range(1, c["n_layers"] + 1) if _kind(c, i) == EXPERTS}


# --------------------------------------------------------------------- math
def recurrence(x, dt, a, b, cc, dd):
    """``y [T, H, P]`` of ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t b_t^T``,
    ``y_t = S_t cc_t + dd x_t`` from x [T, H, P], dt [T, H], a [H], b, cc
    [T, G, N], dd [H]: a token at a time, in checkpointed blocks of
    ``SCAN_BLOCK`` tokens with the state carried between them."""
    T, H, P = x.shape
    G, N = b.shape[-2:]
    per = H // G

    def token(s, inputs):
        xt, dtt, bt, ct = inputs
        bh, ch = jnp.repeat(bt, per, axis=0), jnp.repeat(ct, per, axis=0)
        s = (jnp.exp(dtt * a)[:, None, None] * s
             + (dtt[:, None] * xt)[:, :, None] * bh[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, ch, precision=_HI) + dd[:, None] * xt

    blk = _rows_of(T, SCAN_BLOCK)
    n = T // blk
    _, y = lax.scan(jax.checkpoint(lambda s, inputs: lax.scan(token, s, inputs)),
                    jnp.zeros((H, P, N), jnp.float32),
                    (x.reshape(n, blk, H, P), dt.reshape(n, blk, H),
                     b.reshape(n, blk, G, N), cc.reshape(n, blk, G, N)))
    return y.reshape(T, H, P)


def _rounded(t, precision):
    """``t``, and its cotangent on the way back, rounded to ``precision``:
    the scan's operands in a control (``common._product``'s rule for a
    product's operands, here for the per-token products of the
    recurrence)."""
    if precision == "float32":
        return t

    @jax.custom_vjp
    def f(t):
        return C._round(t, precision)

    f.defvjp(lambda t: (C._round(t, precision), None),
             lambda _, g: (C._round(g, precision),))
    return f(t)


def mamba(p, i, u, c, precision):
    T = u.shape[0]
    H, P, G, N, L = (c["ssm_heads"], c["ssm_head_dim"], c["ssm_groups"],
                     c["ssm_state"], c["conv_kernel"])
    d, conv_dim = _widths(c)
    zxd = _mm(u, p[f"{i}/W_in"], precision)
    z, xbc, dt = jnp.split(zxd, [d, d + conv_dim], axis=-1)
    v = jnp.concatenate([jnp.zeros((L - 1, conv_dim), xbc.dtype), xbc])
    w = p[f"{i}/conv_w"]
    xbc = jax.nn.silu(p[f"{i}/conv_b"]
                      + sum(w[j] * v[j:j + T] for j in range(L)))
    x, b, cc = jnp.split(xbc, [d, d + G * N], axis=-1)
    dt = jax.nn.softplus(dt + p[f"{i}/dt_bias"])
    x, b, cc = (_rounded(t, precision) for t in (x, b, cc))
    y = recurrence(x.reshape(T, H, P), dt, -jnp.exp(p[f"{i}/A_log"]),
                   b.reshape(T, G, N), cc.reshape(T, G, N), p[f"{i}/D"])
    y = (y.reshape(T, d) * jax.nn.silu(z)).reshape(T, G, d // G)
    y = _rms(y, p[f"{i}/ssm_norm_g"].reshape(G, d // G), c["rms_norm_eps"])
    return _mm(y.reshape(T, d), p[f"{i}/W_out"], precision)


def attention(p, i, u, c, precision):
    """Causal grouped attention over blocks of ``QUERY_BLOCK`` queries, a
    key/value group at a time (a group's scores are ``H / G x block x T``
    float32), each block checkpointed."""
    T = u.shape[0]
    H, G, D = c["n_heads"], c["n_kv_heads"], c["head_dim"]
    per = H // G
    q = _mm(u, p[f"{i}/Wq"], precision).reshape(T, H, D)
    k = _mm(u, p[f"{i}/Wk"], precision).reshape(T, G, D).transpose(1, 0, 2)
    v = _mm(u, p[f"{i}/Wv"], precision).reshape(T, G, D).transpose(1, 0, 2)
    dot = C._product(lambda a, b: jnp.einsum("qhd,kd->hqk", a, b,
                                             precision=_HI), precision)
    mix = C._product(lambda a, b: jnp.einsum("hqk,kd->qhd", a, b,
                                             precision=_HI), precision)
    blk = _rows_of(T, QUERY_BLOCK)

    def rows(args):
        qb, first = args                                     # [blk, H, D]
        seen = (first + jnp.arange(blk))[:, None] >= jnp.arange(T)[None, :]

        def group(g):
            qg = lax.dynamic_slice_in_dim(qb, g * per, per, axis=1)
            s = jnp.where(seen, dot(qg, k[g]) * D ** -0.5, -jnp.inf)
            return mix(jax.nn.softmax(s, axis=-1), v[g])     # [blk, per, D]

        o = lax.map(jax.checkpoint(group), jnp.arange(G))
        return o.transpose(1, 0, 2, 3).reshape(blk, H * D)

    n = T // blk
    o = lax.map(jax.checkpoint(rows), (q.reshape(n, blk, H, D),
                                       jnp.arange(0, T, blk)))
    return _mm(o.reshape(T, H * D), p[f"{i}/Wo"], precision)


def _relu2(u, wu, wd, precision):
    return _mm(jnp.square(jax.nn.relu(_mm(u, wu, precision))), wd, precision)


def expert_layer(p, i, u, c, precision, bias=None):
    """-> (P(u): the experts held here and the shared expert, the (token,
    choice) pairs on each router output [E], those that fell on an expert
    held here)."""
    E = c["n_router_outputs"]
    bias = jnp.zeros((E,), jnp.float32) if bias is None else bias
    choice, weight, _ = route(u, p[f"{i}/Wr"], lax.stop_gradient(bias),
                              c["experts_per_token"],
                              c["routed_scaling_factor"])
    w = jnp.stack([jnp.sum(jnp.where(choice == c["first_held"] + e, weight,
                                     0.0), axis=-1)
                   for e in range(c["n_held"])], axis=-1)     # [T, held]

    def chunk(uc, wc):
        y = _relu2(uc, p[f"{i}/Su"], p[f"{i}/Sd"], precision)
        for e in range(c["n_held"]):
            y = y + wc[:, e:e + 1] * _relu2(uc, p[f"{i}/Eu"][e],
                                            p[f"{i}/Ed"][e], precision)
        return y

    load = jnp.zeros((E,), jnp.float32).at[choice.reshape(-1)].add(1.0)
    here = ((choice >= c["first_held"])
            & (choice < c["first_held"] + c["n_held"]))
    return _chunked(chunk, u, w), load, jnp.sum(here)


def _block(p, i, x, c, precision, bias):
    eps, kind = c["rms_norm_eps"], _kind(c, i)
    if kind == EXPERTS:
        f, load, rows = expert_layer(p, i, _rms(x, p[f"{i}/norm2_g"], eps), c,
                                     precision, bias)
        return x + f, load, rows
    mixer = mamba if kind == MAMBA else attention
    return x + mixer(p, i, _rms(x, p[f"{i}/norm1_g"], eps), c,
                     precision), None, None


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
