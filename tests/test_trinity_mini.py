"""Trinity-Mini through the normal path against the benchmark's plain
reference (``benchmark/reference/trinity_mini.py``, which imports nothing of
the program), at a tiny size on the CPU with the reference's seeded weights.
Float32 policy on both sides, so what is left between them is the order of
float32 sums: tolerances are a few 1e-5 relative, far below what any change
of the mathematics would move.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from reference import trinity_mini as ref  # noqa: E402

from deeplearning4j_tpu.datasets.dataset import DataSet  # noqa: E402
from deeplearning4j_tpu.models import trinity_mini  # noqa: E402
from deeplearning4j_tpu.nn.conf.layers import DecoderBlock  # noqa: E402
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork  # noqa: E402
from deeplearning4j_tpu.observability.metrics import global_registry  # noqa: E402

#: 1 dense + 4 expert layers (sliding x 4, full); a window of 6 in 16
#: positions; 16 router outputs of which 4 are held; 4 query heads over 2
TINY = dict(n_layers=5, n_dense_layers=1, experts_held=[4, 8], vocab_rows=600,
            hidden_size=32, n_heads=4, n_kv_heads=2, head_dim=8,
            sliding_window=6,
            layer_types=["sliding_attention"] * 4 + ["full_attention"],
            intermediate_size=48, moe_intermediate_size=16,
            n_router_outputs=16, experts_per_token=3, seq_len=16,
            learning_rate=1e-3, load_balance_coeff=0.001)
RTOL = 5e-5


def _net(weights, cfg=TINY, **attrs):
    """The program's network holding the reference's ``weights``."""
    conf = trinity_mini(**cfg)
    conf.global_conf.dtype = "float32"
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


def _counters(prefix):
    out = {}
    for name, fam in global_registry().snapshot().items():
        if name.startswith(prefix):
            for s in fam["series"]:
                out[f"{name}/{s['labels'].get('layer', '')}"] = s["value"]
    return out


# (a) ---------------------------------------------------------------------
def test_logits_loss_and_every_gradient_match_the_reference():
    weights = ref.init(3, TINY)
    net = _net(weights)
    (x, y), = _batches(1)
    c = ref._cfg(TINY)
    for b in range(2):
        logits, _, _ = ref.sequence_logits(weights, jnp.asarray(x[b]), c)
        _close(jnp.log(net.output(x[b:b + 1])[0]),
               jax.nn.log_softmax(logits, axis=-1), what="log-probabilities")
    grads, loss = net.gradient_and_score(x, y)
    want_loss, want, _ = ref.make_loss_and_grad(TINY)(ref.init(3, TINY), x, y)
    _close(loss, want_loss, what="loss")
    for i, sub in enumerate(grads):
        for name, g in sub.items():
            _close(g, want[f"{i}/{name}"], what=f"{i}/{name}")
    assert sum(len(s) for s in grads) == len(want)


# (b) ---------------------------------------------------------------------
def test_fit_iterator_follows_the_reference_and_its_bias_entry_for_entry():
    k = 3
    batches = _batches(k)
    net = _net(ref.init(5, TINY), dispatch_ksteps=k)
    losses = []

    class Rec:
        def iteration_done(self, n, it):
            losses.append(float(n.score_value))

    net.set_listeners(Rec())
    moe, attn = _counters("dl4j_moe_"), _counters("dl4j_attn_")
    net.fit_iterator([DataSet(x, y) for x, y in batches])
    want = ref.follow(ref.make_loss_and_grad(TINY), ref.init(5, TINY),
                      batches, TINY["learning_rate"])
    _close(losses, want["losses"], what="losses")
    for i, sub in enumerate(net.updater_state):
        for name, st in sub.items():
            m = float(jnp.sqrt(jnp.sum(jnp.square(st["m"]))))
            _close(m, want["velocity_norm"][f"{i}/{name}"], rtol=2e-4,
                   what=f"m of {i}/{name}")
    # the bias after three steps: multiples of the step's rate, so equal
    # entry for entry or a choice differed somewhere
    for i in (2, 3, 4, 5):
        got = np.asarray(net.state_list[i]["router_bias"])
        assert np.abs(got).max() > 0 and abs(got.mean()) < 1e-8
        np.testing.assert_allclose(got, want["router_bias"][str(i)],
                                   rtol=0, atol=1e-8)
        assert "aux_loss" not in net.state_list[i]
    assert net.state_list[1] == {}
    after = _counters("dl4j_moe_")
    routed = [after[f"dl4j_moe_routed_rows_total/{i}"]
              - moe.get(f"dl4j_moe_routed_rows_total/{i}", 0)
              for i in (2, 3, 4, 5)]
    assert routed == want["routed_rows"]
    # on the CPU the XLA math computes every block's whole square; the mask
    # leaves the window's band (81 of 256 a head) or the causal half (136)
    after = _counters("dl4j_attn_")
    heads = k * 2 * TINY["n_heads"]
    for i in (1, 2, 3, 4, 5):
        seen = {kind: after[f"dl4j_attn_score_entries_{kind}_total/{i}"]
                - attn.get(f"dl4j_attn_score_entries_{kind}_total/{i}", 0)
                for kind in ("computed", "visible")}
        assert seen == {"computed": heads * 256,
                        "visible": heads * (136 if i == 5 else 81)}


def test_the_bias_moves_against_the_load_and_stays_centred():
    layer = _block(None)
    choice = jnp.array([[0, 1, 2], [0, 1, 3], [0, 4, 5], [0, 1, 2]])
    b = layer.next_bias(jnp.zeros((16,)), choice)
    load = np.bincount(np.asarray(choice).ravel(), minlength=16)
    assert abs(float(b.sum())) < 1e-7
    # 12 pairs over 16 outputs: a mean of 0.75; one pair or more is over it
    step = np.where(load > 0.75, -1.0, 1.0) * 0.001
    np.testing.assert_allclose(b, step - step.mean(), atol=1e-9)
    # evaluation leaves the bias where it is
    p = {k.split("/", 1)[1]: v for k, v in ref.init(1, TINY).items()
         if k.startswith("2/")}
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, 32))
    state = {"router_bias": b, "moe_rows": jnp.zeros((3,), jnp.int32)}
    _, after = layer.apply(p, state, x, train=False)
    assert np.array_equal(after["router_bias"], b)
    _, after = layer.apply(p, state, x, train=True)
    assert not np.array_equal(after["router_bias"], b)


# (c) ---------------------------------------------------------------------
def _block(held, cfg=TINY, window=6):
    return DecoderBlock(
        n_in=cfg["hidden_size"], n_out=cfg["hidden_size"], norm_eps=1e-5,
        norm_placement="sandwich", attention="gqa", n_heads=cfg["n_heads"],
        n_kv_heads=cfg["n_kv_heads"], head_dim=cfg["head_dim"], window=window,
        rope_theta=10000.0 if window else None, ffn="moe",
        router="sigmoid_bias", n_experts=cfg["n_router_outputs"],
        experts_per_token=cfg["experts_per_token"],
        expert_hidden=cfg["moe_intermediate_size"],
        shared_hidden=cfg["moe_intermediate_size"], experts_held=held,
        route_scale=2.826, bias_update_rate=cfg["load_balance_coeff"])


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
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (E,))
    want, load, rows = ref.expert_layer(weights, 2, u, ref._cfg(whole),
                                        "float32", bias)
    assert int(rows) == 24 * k == int(load.sum())
    total = _block(None).shared_part(_layer_params(weights, 2), u)
    seen = 0
    for first in range(0, E, E // 8):
        held = [first, first + E // 8]
        layer = _block(held)
        params = _layer_params(weights, 2, *held)
        choice, weight, _ = layer.route(params, u[None], bias)
        # the chosen weights add up to the route's scale for every token
        _close(weight[0].sum(-1), np.full(24, 2.826), what="renormalised")
        part, stats = layer.routed_part(params, u, choice[0], weight[0])
        total = total + part
        seen += int(stats[0])
    assert seen == 24 * k                   # every pair was some share's
    _close(total, want, what="sum of shares")


def test_the_bias_chooses_and_weighs_nothing():
    weights = ref.init(13, TINY)
    layer, p = _block(TINY["experts_held"]), _layer_params(weights, 2)
    u = jax.random.normal(jax.random.PRNGKey(4), (1, 40, 32))
    plain, w0, scores = layer.route(p, u, jnp.zeros((16,)))
    pushed, w1, _ = layer.route(p, u, jnp.zeros((16,)).at[9].set(10.0))
    assert (np.asarray(pushed) == 9).any(axis=-1).all()
    assert not (np.asarray(plain) == 9).any(axis=-1).all()
    picked = jnp.take_along_axis(scores, pushed, axis=-1)
    _close(w1, picked / picked.sum(-1, keepdims=True) * 2.826,
           what="weights are the scores, without the bias")


# (d) ---------------------------------------------------------------------
@pytest.mark.parametrize("window", [6, None])
def test_gated_grouped_attention_is_per_head_attention(window):
    """``attention_part`` against attention written out head by head."""
    weights = ref.init(17, TINY)
    layer, p = _block(None, window=window), _layer_params(weights, 2)
    T, F, H, G, D = 16, 32, 4, 2, 8
    u = jax.random.normal(jax.random.PRNGKey(6), (1, T, F))
    got = layer.attention_part(p, u)[0]
    q = ref._rms((u[0] @ p["Wq"]).reshape(T, H, D), p["q_norm_g"], 1e-5)
    k = ref._rms((u[0] @ p["Wk"]).reshape(T, G, D), p["k_norm_g"], 1e-5)
    v = (u[0] @ p["Wv"]).reshape(T, G, D)
    if window:
        q, k = ref._rope(q, 10000.0), ref._rope(k, 10000.0)
    ahead = np.arange(T)[:, None] - np.arange(T)[None, :]
    seen = (ahead >= 0) & (ahead < (window or T))
    heads = []
    for h in range(H):
        s = q[:, h] @ k[:, h // 2].T * D ** -0.5
        heads.append(jax.nn.softmax(jnp.where(seen, s, -np.inf), axis=-1)
                     @ v[:, h // 2])
    o = jnp.concatenate(heads, axis=-1) * jax.nn.sigmoid(u[0] @ p["Wz"])
    _close(got, o @ p["Wo"], what="gated grouped attention")


def test_rotation_in_halves_keeps_pairs_where_they_were():
    from deeplearning4j_tpu.nn.conf.layers.attention import (
        apply_rope, rope_inv_freq)

    x = jax.random.normal(jax.random.PRNGKey(8), (2, 16, 3, 8))
    got = apply_rope(x, rope_inv_freq(8, 10000.0, None), halves=True)
    for b in range(2):
        _close(got[b], ref._rope(x[b], 10000.0), what="halves")
    # the interleaved form holds the same pairs, laid out first | second
    inter = apply_rope(x[..., jnp.array([0, 4, 1, 5, 2, 6, 3, 7])],
                       rope_inv_freq(8, 10000.0, None))
    _close(inter, got, what="the same rotation of the same pairs")


def test_embedding_scale_and_sandwich_norms_are_fields():
    from deeplearning4j_tpu.nn.conf.layers import EmbeddingLayer

    conf = trinity_mini(**TINY)
    emb = conf.layers[0]
    assert isinstance(emb, EmbeddingLayer)
    assert emb.output_scale == pytest.approx(32 ** 0.5)
    assert trinity_mini(**dict(TINY, mup_enabled=False)).layers[
        0].output_scale == 1.0
    blocks = [l for l in conf.layers if isinstance(l, DecoderBlock)]
    assert [b.window for b in blocks] == [6, 6, 6, 6, None]
    assert [b.rope_theta for b in blocks] == [10000.0] * 4 + [None]
    assert [b.ffn for b in blocks] == ["swiglu"] + ["moe"] * 4
    assert {b.norm_placement for b in blocks} == {"sandwich"}
    # the published pattern: every fourth layer full
    kinds = [b.window for b in trinity_mini(
        **dict(TINY, n_layers=8, layer_types=None)).layers[1:9]]
    assert kinds == [6, 6, 6, None, 6, 6, 6, None]
    with pytest.raises(ValueError, match="layer_types"):
        trinity_mini(**dict(TINY, n_layers=4))


@pytest.mark.parametrize("field,value", [("router", "hash"),
                                         ("norm_placement", "post")])
def test_unknown_block_part_is_refused_by_name(field, value):
    with pytest.raises(ValueError, match=field):
        DecoderBlock(n_in=8, n_out=8, **{field: value})


def test_published_defaults():
    import inspect

    d = {k: v.default for k, v in
         inspect.signature(trinity_mini).parameters.items()}
    assert (d["n_layers"], d["vocab_rows"], d["hidden_size"], d["n_heads"],
            d["n_kv_heads"], d["head_dim"], d["sliding_window"],
            d["intermediate_size"], d["moe_intermediate_size"],
            d["n_router_outputs"], d["experts_per_token"],
            d["n_shared_experts"], d["n_dense_layers"], d["route_scale"],
            d["load_balance_coeff"], d["rms_norm_eps"], d["rope_theta"]) == (
        32, 200192, 2048, 32, 4, 128, 2048, 6144, 1024, 128, 8, 1, 2, 2.826,
        0.001, 1e-5, 10000.0)
