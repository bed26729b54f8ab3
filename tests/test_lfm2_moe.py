"""LFM2-24B-A2B through the normal path against the benchmark's plain
reference (``benchmark/reference/lfm2_moe.py``, which imports nothing of the
program), at a tiny size on the CPU with the reference's seeded weights: a
short convolution with the dense feed-forward, a grouped-attention block
and a second short convolution, both with experts. Float32 policy on both
sides leaves the order of float32 sums between them: tolerances are a few
1e-5 relative, far below what any change of the mathematics would move.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from reference import lfm2_moe as ref  # noqa: E402

from deeplearning4j_tpu import common  # noqa: E402
from deeplearning4j_tpu.datasets.dataset import DataSet  # noqa: E402
from deeplearning4j_tpu.models import lfm2_moe  # noqa: E402
from deeplearning4j_tpu.nn.conf.layers import DecoderBlock  # noqa: E402
from deeplearning4j_tpu.nn.conf.layers.decoder import short_conv  # noqa: E402
from deeplearning4j_tpu.observability.names import (  # noqa: E402
    SHORT_CONV_TOKENS_TOTAL)
from test_keye_vl2 import _kept_bytes  # noqa: E402
from test_trinity_mini import _batches, _close, _counters  # noqa: E402

#: [conv (dense), full attention, conv]; 16 router outputs of which 4 are
#: held; 4 query heads over 2 of 16; 32 positions
TINY = dict(n_layers=3, layer_types=["conv", "full_attention", "conv"],
            n_dense_layers=1, experts_held=[4, 8], vocab_rows=300,
            hidden_size=64, n_heads=4, n_kv_heads=2, head_dim=16,
            intermediate_size=96, moe_intermediate_size=32,
            n_router_outputs=16, experts_per_token=4, seq_len=32,
            learning_rate=1e-3, load_balance_coeff=0.001)


def _net(weights, cfg=TINY, policy="float32", **attrs):
    """The program's network holding the reference's ``weights``."""
    conf = lfm2_moe(**cfg)
    conf.global_conf.dtype = policy
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    net = MultiLayerNetwork(conf).init()
    placed = []
    for i, sub in enumerate(net.params_list):
        for name in sub:
            assert sub[name].shape == weights[f"{i}/{name}"].shape, (i, name)
            sub[name] = jnp.array(weights[f"{i}/{name}"])
            placed.append(f"{i}/{name}")
    assert sorted(placed) == sorted(weights)
    for k, v in attrs.items():
        setattr(net, k, v)
    return net


def _params(weights, i, first=None, end=None):
    p = {k.split("/", 1)[1]: v for k, v in weights.items()
         if k.startswith(f"{i}/")}
    if first is not None:
        p.update({n: p[n][first:end] for n in ("Eg", "Eu", "Ed")})
    return p


# (a) ---------------------------------------------------------------------
def test_logits_loss_and_every_gradient_match_the_reference():
    weights = ref.init(3, TINY)
    net = _net(weights)
    (x, y), = _batches(1, TINY)
    c = ref._cfg(TINY)
    for b in range(2):
        logits = ref.sequence_logits(weights, jnp.asarray(x[b]), c)[0]
        _close(jnp.log(net.output(x[b:b + 1])[0]),
               jax.nn.log_softmax(logits, axis=-1), what="log-probabilities")
    grads, loss = net.gradient_and_score(x, y)
    want_loss, want, _ = ref.make_loss_and_grad(TINY)(ref.init(3, TINY), x, y)
    _close(loss, want_loss, what="loss")
    for i, sub in enumerate(grads):
        for name, g in sub.items():
            assert np.abs(np.asarray(want[f"{i}/{name}"])).max() > 0
            _close(g, want[f"{i}/{name}"], what=f"{i}/{name}")
    assert sum(len(s) for s in grads) == len(want)
    assert {"W_in", "conv_w", "W_out"} <= set(grads[1]) and "Wq" in grads[2]


def test_bfloat16_policy_stays_near_the_reference():
    """``bfloat16_full``: the loss within 1 %; a leaf's gradient norm within
    15 % where the reference's is not under a tenth of the median leaf's."""
    weights = ref.init(3, TINY)
    (x, y), = _batches(1, TINY)
    grads, loss = _net(weights, policy="bfloat16_full").gradient_and_score(
        x, y)
    want_loss, want, _ = ref.make_loss_and_grad(TINY)(ref.init(3, TINY), x, y)
    _close(loss, want_loss, rtol=1e-2, what="loss")
    norms = {k: float(jnp.linalg.norm(v)) for k, v in want.items()}
    floor = 0.1 * float(np.median(list(norms.values())))
    checked = 0
    for i, sub in enumerate(grads):
        for name, g in sub.items():
            r = norms[f"{i}/{name}"]
            if r >= floor:
                got = float(jnp.linalg.norm(g.astype(jnp.float32)))
                assert abs(got - r) <= 0.15 * r, (i, name, got, r)
                checked += 1
    assert checked >= 15


# (b) ---------------------------------------------------------------------
def test_fit_iterator_follows_the_reference_and_its_bias_entry_for_entry():
    k = 3
    batches = _batches(k, TINY)
    net = _net(ref.init(5, TINY), dispatch_ksteps=k)
    losses = []

    class Rec:
        def iteration_done(self, n, it):
            losses.append(float(n.score_value))

    net.set_listeners(Rec())
    before = {**_counters("dl4j_moe_"), **_counters("dl4j_attn_"),
              **_counters("dl4j_short_conv_")}
    kept = _kept_bytes()
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
    for i in (2, 3):
        got = np.asarray(net.state_list[i]["router_bias"])
        assert np.abs(got).max() > 0 and abs(got.mean()) < 1e-8
        np.testing.assert_allclose(got, want["router_bias"][str(i)],
                                   rtol=0, atol=1e-8)
    assert net.state_list[1] == {}
    after = {**_counters("dl4j_moe_"), **_counters("dl4j_attn_"),
             **_counters("dl4j_short_conv_")}

    def seen(name, i):
        return after.get(f"{name}/{i}", 0) - before.get(f"{name}/{i}", 0)

    assert [seen("dl4j_moe_routed_rows_total", i)
            for i in (2, 3)] == want["routed_rows"]
    # steps x batch x positions through each convolution; the attention
    # block alone books score entries (the XLA core of this CPU computes
    # its whole square of 32 x 32 a head and leaves the causal 528 visible)
    tokens = k * 2 * 32
    assert [seen("dl4j_short_conv_tokens_total", i)
            for i in (1, 2, 3)] == [tokens, 0, tokens]
    heads = k * 2 * TINY["n_heads"]
    for kind, per_head in (("computed", 1024), ("visible", 528)):
        assert [seen(f"dl4j_attn_score_entries_{kind}_total", i)
                for i in (1, 2, 3)] == [0, heads * per_head, 0]
    # under gradient checkpointing a convolution keeps nothing besides its
    # input, and the XLA core of this CPU keeps nothing either
    assert net.conf.global_conf.gradient_checkpointing
    assert {n: v - kept.get(n, 0) for n, v in _kept_bytes().items()} == {
        "attn_core_out": 0, "attn_core_lse": 0}


def test_a_convolution_books_tokens_and_keeps_nothing():
    block = DecoderBlock(n_in=8, n_out=8, attention="short_conv")
    attn = DecoderBlock(n_in=8, n_out=8, attention="gqa", n_heads=2,
                        n_kv_heads=1, head_dim=4)
    assert block.step_counts(3, 32, jnp.bfloat16) == {
        SHORT_CONV_TOKENS_TOTAL: 96}
    assert SHORT_CONV_TOKENS_TOTAL not in attn.step_counts(3, 32,
                                                            jnp.bfloat16)
    assert block.attn_score_entries(3, 32, jnp.bfloat16) == (0, 0)
    assert block.remat_kept_bytes(3, 32, jnp.bfloat16) == {}
    assert attn.remat_kept_bytes(3, 32, jnp.bfloat16)


# (c) ---------------------------------------------------------------------
def _conv_operands(T=20, F=8, L=3, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (2, T, F)),
            jax.random.normal(ks[1], (F, 3 * F)) * 0.3,
            jax.random.normal(ks[2], (L, F)),
            jax.random.normal(ks[3], (F, F)) * 0.3)


def test_the_gated_convolution_is_the_equation_token_by_token():
    u, w_in, w, w_out = _conv_operands()
    with common.override_policy("float32"):
        got = np.asarray(short_conv(u, w_in, w, w_out))
    bcx = np.asarray(u) @ np.asarray(w_in)
    b, c, x = np.split(bcx, 3, axis=-1)
    v, w = b * x, np.asarray(w)
    T, L = u.shape[1], w.shape[0]
    want = np.zeros_like(got)
    for t in range(T):
        z = sum(w[j] * v[:, t - L + 1 + j] for j in range(L)
                if t - L + 1 + j >= 0)
        want[:, t] = (c[:, t] * z) @ np.asarray(w_out)
    _close(got, want, what="per-token loop")
    # PyTorch's depthwise cross-correlation with padding L - 1, first T out
    conv = jax.lax.conv_general_dilated(
        jnp.asarray(v), w[:, None, :], window_strides=(1,),
        padding=[(L - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=v.shape[-1],
        precision=jax.lax.Precision.HIGHEST)
    _close(got, (c * np.asarray(conv)) @ np.asarray(w_out), what="lax conv")
    # and the reference's
    for s in range(2):
        _close(got[s], np.asarray(ref.gated_conv(jnp.asarray(bcx[s]), w))
               @ np.asarray(w_out), what="reference")


@pytest.mark.parametrize("t", [0, 7, 18])
def test_the_gated_convolution_is_causal(t):
    u, w_in, w, w_out = _conv_operands(seed=t)
    later = u.at[:, t + 1:].set(jax.random.normal(jax.random.PRNGKey(99),
                                                  u[:, t + 1:].shape))
    with common.override_policy("float32"):
        a, b = short_conv(u, w_in, w, w_out), short_conv(later, w_in, w,
                                                         w_out)
    assert np.array_equal(a[:, :t + 1], b[:, :t + 1])
    assert not np.array_equal(a[:, t + 1:], b[:, t + 1:])


# (d) ---------------------------------------------------------------------
def _expert_block(held, cfg=TINY):
    return lfm2_moe(**dict(cfg, experts_held=held)).layers[3]


def test_eight_shares_add_up_to_the_uncut_layer():
    """No shared expert: the eight shares' parts alone make the layer."""
    whole = dict(TINY, experts_held=None)
    weights = ref.init(11, whole)           # all 16 experts' weights
    E, k = whole["n_router_outputs"], whole["experts_per_token"]
    u = jax.random.normal(jax.random.PRNGKey(2), (24, whole["hidden_size"]))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (E,))
    want, load, rows = ref.expert_layer(weights, 3, u, ref._cfg(whole),
                                        "float32", bias)
    assert int(rows) == 24 * k == int(load.sum())
    total, seen = jnp.zeros_like(u), 0
    for first in range(0, E, E // 8):
        held = [first, first + E // 8]
        layer = _expert_block(held)
        assert layer.shared_hidden == 0 and layer.attention == "short_conv"
        params = _params(weights, 3, *held)
        choice, weight, _ = layer.route(params, u[None], bias)
        _close(weight[0].sum(-1), np.ones(24), rtol=1e-5, what="renormalised")
        part, stats = layer.routed_part(params, u, choice[0], weight[0])
        total = total + part
        seen += int(stats[0])
    assert seen == 24 * k                   # every pair was some share's
    _close(total, want, what="sum of shares")


# (e) ---------------------------------------------------------------------
def test_published_defaults_and_the_blocks_fields():
    import inspect

    d = {k: v.default for k, v in
         inspect.signature(lfm2_moe).parameters.items()}
    assert (d["n_layers"], d["n_dense_layers"], d["vocab_rows"],
            d["hidden_size"], d["n_heads"], d["n_kv_heads"], d["head_dim"],
            d["conv_kernel"], d["intermediate_size"],
            d["moe_intermediate_size"], d["n_router_outputs"],
            d["experts_per_token"], d["routed_scaling_factor"],
            d["rms_norm_eps"], d["rope_theta"], d["seq_len"]) == (
        40, 2, 65536, 2048, 32, 8, 64, 3, 11776, 1536, 64, 4, 1.0, 1e-5, 1e6,
        32768)
    blocks = [l for l in lfm2_moe(n_layers=40, **{
        k: v for k, v in TINY.items()
        if k not in ("n_layers", "layer_types", "n_dense_layers")}).layers
        if isinstance(l, DecoderBlock)]
    kinds = [b.attention for b in blocks]
    assert kinds.count("short_conv") == 30 and kinds.count("gqa") == 10
    assert [i for i, k in enumerate(kinds) if k == "gqa"] == list(
        range(2, 40, 4))
    assert [b.ffn for b in blocks[:3]] == ["swiglu", "swiglu", "moe"]
    for b in blocks:
        assert (b.norm_placement, b.norm_eps) == ("pre", 1e-5)
        if b.attention == "gqa":
            assert (b.output_gate, b.window, b.rope_theta) == (False, None,
                                                               1e6)
        else:
            assert b.conv_kernel == 3
        if b.ffn == "moe":
            assert (b.router, b.shared_hidden, b.route_scale,
                    b.bias_update_rate) == ("sigmoid_bias", 0, 1.0, 0.001)
    conf = lfm2_moe(**TINY)
    assert conf.layers[0].output_scale == 1.0
    # the count the configuration states: 486.06 M at five layers of the cut
    cut = dict(n_layers=5, layer_types=["conv", "full_attention", "conv",
                                        "conv", "conv"],
               n_dense_layers=1, experts_held=[0, 8], vocab_rows=8192)
    n = sum(int(np.prod(s)) for s in ref._shapes(ref._cfg(cut)).values())
    assert round(n / 1e6, 2) == 486.06
    with pytest.raises(ValueError, match="layer_types"):
        lfm2_moe(**dict(TINY, n_layers=4))


# (f) ---------------------------------------------------------------------
@pytest.mark.parametrize("fields,match", [
    (dict(window=4), "short convolution"),
    (dict(conv_kernel=0), "short convolution"),
    (dict(index_heads=2, index_dim=4, index_topk=2), "indexer"),
    (dict(attention="long_conv"), "attention")])
def test_a_short_convolution_takes_no_window_or_indexer(fields, match):
    with pytest.raises(ValueError, match=match):
        DecoderBlock(n_in=8, n_out=8, **{"attention": "short_conv",
                                         **fields})


def test_older_blocks_keep_their_bits():
    """A Trinity-Mini and a DeepSeek-V2-Lite block: outputs, state and every
    gradient equal, bit for bit, to what PR 34's code gave."""
    sys.path.insert(0, os.path.join(ROOT, "tests", "golden"))
    import make_decoder_blocks

    want = np.load(os.path.join(ROOT, "tests", "golden",
                                "decoder_blocks_pr34.npz"))
    got = make_decoder_blocks.run()
    assert sorted(got) == sorted(want.files)
    for name in want.files:
        assert np.array_equal(got[name], want[name]), name
