"""DeepSeek-V2-Lite through the normal path against the benchmark's plain
reference (``benchmark/reference/deepseek_v2_lite.py``, which imports nothing
of the program), at a tiny size on the CPU with the reference's seeded
weights. Float32 policy on both sides, so what is left between them is the
order of float32 sums: tolerances are a few 1e-5 relative, far below what
any change of the mathematics would move.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from reference import deepseek_v2_lite as ref  # noqa: E402

from deeplearning4j_tpu.datasets.dataset import DataSet  # noqa: E402
from deeplearning4j_tpu.models.deepseek_v2 import deepseek_v2_lite  # noqa: E402
from deeplearning4j_tpu.nn.conf.layers import DecoderBlock  # noqa: E402
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork  # noqa: E402
from deeplearning4j_tpu.observability.metrics import global_registry  # noqa: E402

#: 1 dense + 2 expert layers; 16 router outputs of which 4 are held; ids up
#: to 600, so most lie above 256
TINY = dict(n_layers=3, experts_held=[4, 8], vocab_rows=600, hidden_size=32,
            n_heads=2, kv_lora_rank=16, qk_nope_head_dim=8,
            qk_rope_head_dim=4, v_head_dim=8, intermediate_size=48,
            moe_intermediate_size=16, n_router_outputs=16,
            experts_per_token=3, n_shared_experts=2, seq_len=16,
            aux_loss_weight=0.01, learning_rate=1e-3)
RTOL = 5e-5


def _net(weights, cfg=TINY, policy="float32", **attrs):
    """The program's network holding the reference's ``weights``."""
    conf = deepseek_v2_lite(**cfg)
    conf.global_conf.dtype = policy
    net = MultiLayerNetwork(conf).init()
    placed = []
    for i, sub in enumerate(net.params_list):
        for name in sub:
            assert sub[name].shape == weights[f"{i}/{name}"].shape
            sub[name] = jnp.array(weights[f"{i}/{name}"])
            placed.append(f"{i}/{name}")
    assert sorted(placed) == sorted(weights)
    for k, v in attrs.items():
        setattr(net, k, v)
    return net


def _batches(n, cfg=TINY, batch=2, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        ids = rng.integers(0, cfg["vocab_rows"],
                           (batch, cfg["seq_len"] + 1)).astype(np.int32)
        out.append((ids[:, :-1].copy(), ids[:, 1:].copy()))
    return out


def _close(got, want, rtol=RTOL, what=""):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(np.abs(want).max(), 1e-12)
    assert np.abs(got - want).max() <= rtol * scale, (
        what, np.abs(got - want).max() / scale)


# (a) ---------------------------------------------------------------------
def test_loss_and_every_gradient_match_the_reference():
    weights = ref.init(3, TINY)
    net = _net(weights)
    (x, y), = _batches(1)
    grads, loss = net.gradient_and_score(x, y)
    want_loss, want, _ = ref.make_loss_and_grad(TINY)(ref.init(3, TINY), x, y)
    _close(loss, want_loss, what="loss")
    for i, sub in enumerate(grads):
        for name, g in sub.items():
            _close(g, want[f"{i}/{name}"], what=f"{i}/{name}")
    assert sum(len(s) for s in grads) == len(want)


# (b) ---------------------------------------------------------------------
def test_fit_iterator_with_adam_follows_the_reference():
    k = 4
    batches = _batches(k)
    net = _net(ref.init(5, TINY), dispatch_ksteps=k)
    losses = []

    class Rec:
        def iteration_done(self, n, it):
            losses.append(float(n.score_value))

    net.set_listeners(Rec())
    before = _moe_counters()
    net.fit_iterator([DataSet(x, y) for x, y in batches])
    want = ref.follow(ref.make_loss_and_grad(TINY), ref.init(5, TINY),
                      batches, TINY["learning_rate"])
    _close(losses, want["losses"], what="losses")
    for i, sub in enumerate(net.updater_state):
        for name, st in sub.items():
            m = float(jnp.sqrt(jnp.sum(jnp.square(st["m"]))))
            # Adam's first steps divide by sqrt(v) ~ |g|: a leaf's update is
            # its gradient's sign pattern, and round-off in a gradient near
            # nought moves it; the moments' norms are compared, not signs
            _close(m, want["velocity_norm"][f"{i}/{name}"], rtol=2e-4,
                   what=f"m of {i}/{name}")
    # the program's routed-rows counter is the reference's count, per layer
    after = _moe_counters()
    routed = [after[f"dl4j_moe_routed_rows_total/{layer}"]
              - before.get(f"dl4j_moe_routed_rows_total/{layer}", 0)
              for layer in ("2", "3")]
    assert routed == want["routed_rows"]
    tokens = after["dl4j_moe_tokens_total/"] - before.get(
        "dl4j_moe_tokens_total/", 0)
    assert tokens == k * 2 * TINY["seq_len"]
    # the busiest expert's rows, summed over the same steps: between the
    # mean expert's rows and all of them
    held = TINY["experts_held"][1] - TINY["experts_held"][0]
    for layer, rows in zip(("2", "3"), routed):
        key = f"dl4j_moe_expert_rows_max_total/{layer}"
        busiest = after[key] - before.get(key, 0)
        assert rows / held <= busiest <= rows


def _moe_counters():
    out = {}
    for name, fam in global_registry().snapshot().items():
        if name.startswith("dl4j_moe_"):
            for s in fam["series"]:
                out[f"{name}/{s['labels'].get('layer', '')}"] = s["value"]
    return out


# (c) ---------------------------------------------------------------------
def _expert_block(held, cfg=TINY):
    return DecoderBlock(
        n_in=cfg["hidden_size"], n_out=cfg["hidden_size"], ffn="moe",
        attention="mla", n_heads=cfg["n_heads"], kv_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
        n_experts=cfg["n_router_outputs"],
        experts_per_token=cfg["experts_per_token"],
        expert_hidden=cfg["moe_intermediate_size"],
        shared_hidden=2 * cfg["moe_intermediate_size"], experts_held=held)


def _layer_params(weights, i, first=None, end=None):
    p = {k.split("/", 1)[1]: v for k, v in weights.items()
         if k.startswith(f"{i}/")}
    if first is not None:
        p.update({n: p[n][first:end] for n in ("Eg", "Eu", "Ed")})
    return p


def test_eight_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    whole = dict(TINY, experts_held=None)
    weights = ref.init(11, whole)           # all 16 experts' weights
    E, k = whole["n_router_outputs"], whole["experts_per_token"]
    u = jax.random.normal(jax.random.PRNGKey(2), (24, whole["hidden_size"]))
    want, _, rows = ref.expert_layer(weights, 2, u, ref._cfg(whole),
                                     "float32")
    assert int(rows) == 24 * k
    total = _expert_block(None).shared_part(_layer_params(weights, 2), u)
    seen = 0
    for first in range(0, E, E // 8):
        held = [first, first + E // 8]
        layer = _expert_block(held)
        params = _layer_params(weights, 2, *held)
        choice, weight, _ = layer.route(params, u[None])
        part, stats = layer.routed_part(params, u, choice[0], weight[0])
        total = total + part
        seen += int(stats[0])
    assert seen == 24 * k                   # every pair was some share's
    _close(total, want, what="sum of shares")


# (d) ---------------------------------------------------------------------
def test_no_pair_is_dropped_when_one_held_expert_takes_most_of_them():
    c = ref._cfg(TINY)
    weights = dict(ref.init(13, TINY))
    # positive activations and a large positive router column: every token's
    # largest router output is expert 5, which is held here
    u = jnp.abs(jax.random.normal(jax.random.PRNGKey(4),
                                  (40, TINY["hidden_size"]))) + 0.5
    weights["2/Wr"] = weights["2/Wr"].at[:, 5].set(3.0)
    layer = _expert_block(TINY["experts_held"])
    params = _layer_params(weights, 2)
    choice, weight, _ = layer.route(params, u[None])
    got, stats = layer.routed_part(params, u, choice[0], weight[0])
    routed, _, largest = (int(s) for s in stats)
    assert largest == 40 and largest > routed / 2
    want, _, rows = ref.expert_layer(weights, 2, u, c, "float32",
                                     shared=False)
    assert routed == int(rows)
    _close(got, want, what="dropless under skew")


@pytest.mark.parametrize("held,fits", [([4, 5], True), ([4, 12], False)])
def test_both_buffer_sizes_give_the_dense_result(monkeypatch, held, fits):
    """The dispatch buffer has two static sizes, chosen on the device by the
    count routed here: with an 8-row tile and 120 pairs the usual size is 32
    rows. One held expert of 16 stays inside it; eight do not, and take the
    full buffer. Values and gradients equal the dense evaluation's."""
    from deeplearning4j_tpu.nn.conf.layers import moe

    monkeypatch.setattr(moe, "GROUP_ROW_TILE", 8)
    cfg = dict(TINY, experts_held=held)
    c = ref._cfg(cfg)
    weights = ref.init(19, cfg)
    u = jax.random.normal(jax.random.PRNGKey(8), (40, TINY["hidden_size"]))
    assert moe._usual_bound(40 * 3) == 32
    layer = _expert_block(held)
    params = _layer_params(weights, 2)
    choice, weight, _ = layer.route(params, u[None])

    def got(p, x):
        return layer.routed_part(p, x, choice[0], weight[0])

    def want(p, x):
        """Every held expert on every token, weighted where chosen (the
        choices and weights held fixed, as ``got`` holds them)."""
        y = 0
        for e in range(held[1] - held[0]):
            h = (jax.nn.silu(x @ p["Eg"][e]) * (x @ p["Eu"][e])) @ p["Ed"][e]
            y = y + h * jnp.sum(jnp.where(choice[0] == held[0] + e,
                                          weight[0], 0), axis=1)[:, None]
        return y

    y, stats = got(params, u)
    assert (int(stats[0]) <= 32) == fits
    _close(y, want(params, u), what="value")
    _close(y, ref.expert_layer(weights, 2, u, c, "float32", shared=False)[0],
           what="value against the reference")
    experts = {n: params[n] for n in ("Eg", "Eu", "Ed")}
    g = jax.grad(lambda p, x: jnp.sum(jnp.sin(got(dict(params, **p), x)[0])),
                 argnums=(0, 1))(experts, u)
    w = jax.grad(lambda p, x: jnp.sum(jnp.sin(want(p, x))),
                 argnums=(0, 1))(experts, u)
    for a, b in zip(jax.tree_util.tree_leaves(g), jax.tree_util.tree_leaves(w)):
        _close(a, b, what="gradient")


def _choices(case, S=32, k=3, E=16):
    """``choice`` [S, k] over E experts of which 4..7 are held, the buffer's
    rows and the routed count the case must give."""
    rng = np.random.default_rng(5)
    even = np.argsort(rng.random((S, E)), axis=1)[:, :k]
    away = 8 + np.argsort(rng.random((S, 8)), axis=1)[:, :k]
    if case == "even":                      # about a quarter held
        return even, 32, None
    if case == "all-on-one-expert":         # the full-size buffer
        return np.full((S, k), 5), S * k, S * k
    if case == "none-here":
        return away, 32, 0
    if case == "one-token-all-local":       # token 9 alone, all k here
        away[9] = [4, 6, 7]
        return away, 32, 3
    # the routed count one short of / one past a tile of 8 rows: tokens
    # 0..4 with all k here, token 5 with none or two more
    n = {"tile-minus-one": 15, "tile-plus-one": 17}[case]
    for t in range(n // k):
        away[t] = [4, 5, 6]
    away[n // k, :n % k] = 7
    return away, 32, n


CASES = ["even", "all-on-one-expert", "none-here", "one-token-all-local",
         "tile-minus-one", "tile-plus-one"]


@pytest.mark.parametrize("path", ["xla", "kernel"])
@pytest.mark.parametrize("case", CASES)
def test_rows_sum_into_their_tokens_as_the_dense_sum(monkeypatch, case, path):
    """The dispatch's way out (``_sum_token_rows``) and way in
    (``_take_token_rows``), each the other's cotangent, against a dense
    float32 sum over a one-hot of the rows' tokens. Rows past the routed
    count hold NaN on entry, as a grouped product may leave them, and reach
    no result. ``kernel``: the token-order gather and megablox's ``tgmm`` in
    interpret mode, at tiles of 8."""
    from deeplearning4j_tpu.nn.conf.layers import moe

    monkeypatch.setattr(moe, "TOKEN_TILE", 8)
    choice, bound, n = _choices(case)
    (S, k), F = choice.shape, 128
    # the buffer as grouped_expert_ffn lays it out: pairs numbered
    # choice-major, sorted by held expert, the first ``bound``
    local = jnp.asarray(choice.T.reshape(S * k) - 4, jnp.int32)
    key = jnp.where((local >= 0) & (local < 4), local, 4)
    token = (jnp.argsort(key, stable=True)[:bound] % S).astype(jnp.int32)
    pair_held = (key < 4).reshape(k, S)
    n_routed = jnp.sum(pair_held).astype(jnp.int32)
    assert int(n_routed) <= bound and (n is None or int(n_routed) == n)
    live = (jnp.arange(bound) < n_routed)[:, None]
    rows = jnp.where(live, jax.random.normal(jax.random.PRNGKey(3),
                                             (bound, F)), jnp.nan)
    kernel = path == "kernel"
    plan = moe._buffer_plan(token, n_routed, pair_held, kernel)
    onehot = (live & (token[:, None] == jnp.arange(S)[None, :])).astype(
        jnp.float32)
    want = jnp.einsum("is,if->sf", onehot, jnp.where(live, rows, 0),
                      precision="highest")

    def way_out(rows):
        return moe._sum_token_rows(rows, plan, S, kernel)

    def way_in(x):
        return moe._take_token_rows(x, plan, kernel)

    y, pull = jax.vjp(way_out, rows)
    assert bool(jnp.all(jnp.isfinite(y)))
    _close(y, want, what="the way out")
    x = jax.random.normal(jax.random.PRNGKey(7), (S, F))
    _close(jnp.where(live, pull(x)[0], 0), jnp.where(live, x[token], 0),
           what="the way out's cotangent")
    taken, pull = jax.vjp(way_in, x)
    _close(taken, x[token], what="the way in")
    dx, = pull(rows)                    # NaN past the count
    assert bool(jnp.all(jnp.isfinite(dx)))
    _close(dx, want, what="the way in's cotangent")


@pytest.mark.parametrize("case", CASES)
def test_expert_layer_and_its_gradients_equal_the_dense_sum(monkeypatch,
                                                           case):
    """``grouped_expert_ffn`` over the same routings against every held
    expert evaluated on every token: the value and the gradients by the
    tokens' rows, by the pairs' weights (0 for a pair routed elsewhere) and
    by an expert's weights."""
    from deeplearning4j_tpu import common
    from deeplearning4j_tpu.nn.conf.layers import moe

    monkeypatch.setattr(moe, "GROUP_ROW_TILE", 8)
    choice, bound, n = _choices(case)
    (S, k), F, H, G = choice.shape, 16, 24, 4
    assert moe._usual_bound(S * k) == 24 or bound == S * k
    choice = jnp.asarray(choice, jnp.int32)
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    x = jax.random.normal(ks[0], (S, F))
    weight = jax.random.uniform(ks[1], (S, k)) + 0.5
    wg, wu = (jax.random.normal(kk, (G, F, H)) / 4 for kk in ks[2:4])
    wd = jax.random.normal(ks[4], (G, H, F)) / 4

    def got(x, weight, wg):
        with common.override_policy("float32"):
            return moe.grouped_expert_ffn(x, choice, weight, wg, wu, wd, 4)

    def want(x, weight, wg):
        y = 0
        for e in range(G):
            h = (jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e]
            y = y + h * jnp.sum(jnp.where(choice == 4 + e, weight, 0),
                                axis=1)[:, None]
        return y

    y, stats = got(x, weight, wg)
    assert n is None or int(stats[0]) == n
    _close(y, want(x, weight, wg), what="value")
    g = jax.grad(lambda *a: jnp.sum(jnp.sin(got(*a)[0])),
                 argnums=(0, 1, 2))(x, weight, wg)
    e = jax.grad(lambda *a: jnp.sum(jnp.sin(want(*a))),
                 argnums=(0, 1, 2))(x, weight, wg)
    for a, b, what in zip(g, e, ("rows", "weights", "an expert's weights")):
        assert bool(jnp.all(jnp.isfinite(a)))
        _close(a, b, what="gradient by the " + what)
    held = (choice >= 4) & (choice < 8)
    assert not bool(jnp.any(jnp.where(held, 0, g[1])))


# (e) ---------------------------------------------------------------------
def test_integer_ids_above_256_reach_the_step_unchanged(monkeypatch):
    batches = _batches(4)
    assert max(x.max() for x, _ in batches) > 256
    net = _net(ref.init(7, TINY), dispatch_ksteps=4,
               stage_dtype=jnp.bfloat16)
    seen = []
    run = type(net)._run_steps

    def spy(self, kind, n, xs, ys, **kw):
        seen.append((np.asarray(xs), np.asarray(ys)))
        return run(self, kind, n, xs, ys, **kw)

    monkeypatch.setattr(type(net), "_run_steps", spy)
    net.fit_iterator([DataSet(x, y) for x, y in batches])
    (xs, ys), = seen
    assert xs.dtype == np.int32 and ys.dtype == np.int32
    assert np.array_equal(xs, np.stack([x for x, _ in batches]))
    assert np.array_equal(ys, np.stack([y for _, y in batches]))


def test_integer_labels_give_the_one_hot_loss():
    net = _net(ref.init(7, TINY))
    (x, y), = _batches(1)
    onehot = np.eye(TINY["vocab_rows"], dtype=np.float32)[y]
    _, with_ids = net.gradient_and_score(x, y)
    _, with_onehot = net.gradient_and_score(x, onehot)
    _close(with_ids, with_onehot, rtol=1e-6, what="loss")


# (f) ---------------------------------------------------------------------
def test_latent_attention_is_per_head_attention_over_192_wide_keys():
    """``attention_part`` against attention written out head by head with
    the keys concatenated (no-rotation part | the shared rotary key)."""
    weights = ref.init(17, TINY)
    layer = _expert_block(None)
    layer.rope_theta = 10000.0
    layer.rope_scaling = dict(ref._YARN)
    p = _layer_params(weights, 1)
    T, F = TINY["seq_len"], TINY["hidden_size"]
    H, dn, dr, dv, r = 2, 8, 4, 8, 16
    u = jax.random.normal(jax.random.PRNGKey(6), (1, T, F))
    got = layer.attention_part(p, u)[0]

    c = ref._cfg(TINY)
    freq = ref._yarn_inv_freq(dr, 10000.0, c["rope_scaling"])
    q = (u[0] @ p["Wq"]).reshape(T, H, dn + dr)
    kva = u[0] @ p["Wkva"]
    latent = ref._rms(kva[:, :r], p["kv_norm_g"], 1e-6)
    kv = (latent @ p["Wkvb"]).reshape(T, H, dn + dv)
    k_pe = ref._rope(kva[:, r:].reshape(T, 1, dr), freq)
    q_pe = ref._rope(q[..., dn:], freq)
    m = 0.1 * 0.707 * np.log(40) + 1
    heads = []
    for h in range(H):
        qh = jnp.concatenate([q[:, h, :dn], q_pe[:, h]], axis=-1)
        kh = jnp.concatenate([kv[:, h, :dn], k_pe[:, 0]], axis=-1)
        s = qh @ kh.T * ((dn + dr) ** -0.5 * m * m)
        s = jnp.where(np.tril(np.ones((T, T), bool)), s, -np.inf)
        heads.append(jax.nn.softmax(s, axis=-1) @ kv[:, h, dn:])
    want = jnp.concatenate(heads, axis=-1) @ p["Wo"]
    _close(got, want, what="latent attention")


@pytest.mark.parametrize("field,value", [("norm", "batch"),
                                         ("attention", "linear"),
                                         ("ffn", "relu")])
def test_unknown_block_part_is_refused_by_name(field, value):
    with pytest.raises(ValueError, match=field):
        DecoderBlock(n_in=8, n_out=8, **{field: value})
