"""Keye-VL-2.0-30B-A3B's language model through the normal path against the
benchmark's plain reference (``benchmark/reference/keye_vl2.py``, which
imports nothing of the program), at a tiny size on the CPU with the
reference's seeded weights, ``index_topk`` smaller than the sequence so that
the selection bites. Float32 policy on both sides leaves the order of
float32 sums between them: tolerances are a few 1e-5 relative, far below
what any change of the mathematics would move. Under ``bfloat16_full`` the
program rounds every product's operands to 8 bits of mantissa and a few
selections flip at the margin: the loose tolerances there are what 16
positions' worth of such rounding reads, an order below a missing term.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from reference import keye_vl2 as ref  # noqa: E402

from deeplearning4j_tpu import common  # noqa: E402
from deeplearning4j_tpu.datasets.dataset import DataSet  # noqa: E402
from deeplearning4j_tpu.models import keye_vl2_lm  # noqa: E402
from deeplearning4j_tpu.nn.conf.layers import DecoderBlock  # noqa: E402
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork  # noqa: E402
from deeplearning4j_tpu.observability.metrics import global_registry  # noqa: E402
from deeplearning4j_tpu.ops import indexer  # noqa: E402
from test_trinity_mini import (  # noqa: E402  (the sibling model's helpers)
    _batches as _trinity_batches, _close as _close_to, _counters)

#: 2 layers; 5 keys a query in 16 positions; 16 router outputs of which 4 are
#: held; 4 query heads over 2; 3 index heads of 8 over one key head
TINY = dict(n_layers=2, experts_held=[4, 8], vocab_rows=300, hidden_size=32,
            n_heads=4, n_kv_heads=2, head_dim=8, index_n_heads=3,
            index_head_dim=8, index_topk=5, moe_intermediate_size=16,
            n_router_outputs=16, experts_per_token=3, seq_len=16,
            learning_rate=1e-3)
RTOL = 5e-5
INDEXER = ("WqI", "WkI", "Ww", "kI_norm_g")


def _net(weights, cfg=TINY, policy="float32", **attrs):
    """The program's network holding the reference's ``weights``."""
    conf = keye_vl2_lm(**cfg)
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
    return _trinity_batches(n, cfg, batch, seed)


def _close(got, want, rtol=RTOL, what=""):
    _close_to(got, want, rtol, what)


# (a) ---------------------------------------------------------------------
def test_logits_loss_and_every_gradient_match_the_reference():
    weights = ref.init(3, TINY)
    net = _net(weights)
    (x, y), = _batches(1)
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


def test_bfloat16_policy_stays_near_the_reference():
    """``bfloat16_full``: the loss within 1 % (it is ln 300 plus two small
    terms); a leaf's gradient norm within 15 % where the reference's is not
    under a tenth of the median leaf's (small leaves are rounding)."""
    weights = ref.init(3, TINY)
    (x, y), = _batches(1)
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
    assert checked >= 20


# (b) ---------------------------------------------------------------------
def _program_selection(net, weights, x):
    """Block 1's selection as the program makes it, from its own parts."""
    layer = net.conf.layers[1]
    emb = jnp.asarray(weights["0/W"])[x]
    with common.override_policy("float32"):
        u = layer._norm(net.params_list[1], "norm1", emb)
        qi, ki, w = layer._index_part(net.params_list[1], u)
        scores = indexer.index_scores(qi, ki, w)
        return scores, indexer.select_topk(scores, layer.index_topk)[0]


def test_every_query_selects_exactly_its_keys_and_the_reference_agrees():
    weights = ref.init(7, TINY)
    net = _net(weights)
    (x, _), = _batches(1, seed=4)
    scores, select = _program_selection(net, weights, x)
    select = np.asarray(select)
    T, k = TINY["seq_len"], TINY["index_topk"]
    assert (select.sum(-1) == np.minimum(np.arange(T) + 1, k)).all()
    assert not select[:, ~np.tril(np.ones((T, T), bool))].any()
    c = ref._cfg(TINY)
    for b in range(2):
        u = ref._rms(weights["0/W"][x[b]], weights["1/norm1_g"],
                     c["rms_norm_eps"])
        want = np.asarray(ref.selection(weights, 1, u, c))
        assert (want.sum(-1) == np.minimum(np.arange(T) + 1, k)).all()
        # where no two causal scores of a row tie, the choices are the same
        s = np.where(np.tril(np.ones((T, T), bool)), np.asarray(scores[b]),
                     np.nan)
        for t in range(T):
            row = s[t, :t + 1]
            if len(np.unique(row)) == len(row):
                assert np.array_equal(select[b, t] > 0, want[t]), (b, t)


# (c) ---------------------------------------------------------------------
def _grads_at(weight, seed=9):
    net = _net(ref.init(seed, TINY), cfg=dict(TINY, index_loss_weight=weight))
    (x, y), = _batches(1, seed=2)
    grads, loss = net.gradient_and_score(x, y)
    return grads, float(loss)


def test_the_indexers_loss_reaches_the_indexer_alone():
    """With ``index_loss_weight`` 0 the indexer's leaves get a zero
    gradient (nothing else reaches them: the selection passes none); and
    the trunk's gradients do not move when the weight does, to the last
    bit."""
    off, loss_off = _grads_at(0.0)
    on, loss_on = _grads_at(1.0)
    twice, loss_twice = _grads_at(2.0)
    assert loss_on > loss_off and loss_twice - loss_on == pytest.approx(
        loss_on - loss_off, rel=1e-4)
    for i in (1, 2):
        for name in INDEXER:
            assert not np.asarray(off[i][name]).any(), (i, name)
            assert np.abs(np.asarray(on[i][name])).max() > 0, (i, name)
            _close(twice[i][name], 2 * np.asarray(on[i][name]),
                   what=f"{i}/{name} scales with the weight")
    for i, sub in enumerate(on):
        for name, g in sub.items():
            if name not in INDEXER:
                assert np.array_equal(g, off[i][name]), (i, name)
                assert np.array_equal(g, twice[i][name]), (i, name)


# (d) ---------------------------------------------------------------------
def _block(held, cfg=TINY):
    return DecoderBlock(
        n_in=cfg["hidden_size"], n_out=cfg["hidden_size"], attention="gqa",
        n_heads=cfg["n_heads"], n_kv_heads=cfg["n_kv_heads"],
        head_dim=cfg["head_dim"], output_gate=False, rope_theta=1e7,
        index_heads=cfg["index_n_heads"], index_dim=cfg["index_head_dim"],
        index_topk=cfg["index_topk"], ffn="moe", router="softmax",
        router_renorm=True, n_experts=cfg["n_router_outputs"],
        experts_per_token=cfg["experts_per_token"],
        expert_hidden=cfg["moe_intermediate_size"], experts_held=held)


def _layer_params(weights, i, first=None, end=None):
    p = {k.split("/", 1)[1]: v for k, v in weights.items()
         if k.startswith(f"{i}/")}
    if first is not None:
        p.update({n: p[n][first:end] for n in ("Eg", "Eu", "Ed")})
    return p


def test_eight_shares_add_up_to_the_uncut_layer():
    whole = dict(TINY, experts_held=None)
    weights = ref.init(11, whole)           # all 16 experts' weights
    E, k = whole["n_router_outputs"], whole["experts_per_token"]
    u = jax.random.normal(jax.random.PRNGKey(2), (24, whole["hidden_size"]))
    want, _, rows = ref.expert_layer(weights, 2, u, ref._cfg(whole),
                                     "float32")
    assert int(rows) == 24 * k
    total, seen = jnp.zeros_like(u), 0
    for first in range(0, E, E // 8):
        held = [first, first + E // 8]
        layer = _block(held)
        params = _layer_params(weights, 2, *held)
        choice, weight, _ = layer.route(params, u[None])
        # the chosen probabilities are divided by their sum
        _close(weight[0].sum(-1), np.ones(24), what="renormalised")
        part, stats = layer.routed_part(params, u, choice[0], weight[0])
        total = total + part
        seen += int(stats[0])
    assert seen == 24 * k                   # every pair was some share's
    _close(total, want, what="sum of shares")


def test_renormalisation_is_a_switch_on_the_softmax_router():
    weights = ref.init(13, TINY)
    p = _layer_params(weights, 2)
    u = jax.random.normal(jax.random.PRNGKey(4), (1, 40, 32))
    layer = _block(TINY["experts_held"])
    choice, weight, probs = layer.route(p, u)
    plain = DecoderBlock(n_in=32, n_out=32, ffn="moe", n_experts=16,
                         experts_per_token=3, expert_hidden=16)
    assert plain.router_renorm is False
    choice0, weight0, _ = plain.route(p, u)
    assert np.array_equal(choice, choice0)
    _close(weight0, jnp.take_along_axis(probs, choice, axis=-1),
           what="not renormalised by default")
    _close(weight, weight0 / weight0.sum(-1, keepdims=True),
           what="divided by their sum")


def _kept_bytes():
    fam = global_registry().snapshot().get("dl4j_remat_kept_bytes_total")
    return ({s["labels"]["name"]: s["value"] for s in fam["series"]}
            if fam else {})


# (e) ---------------------------------------------------------------------
def test_fit_iterator_follows_the_reference_and_books_the_pairs():
    k = 3
    batches = _batches(k)
    net = _net(ref.init(5, TINY), dispatch_ksteps=k)
    losses = []

    class Rec:
        def iteration_done(self, n, it):
            losses.append(float(n.score_value))

    net.set_listeners(Rec())
    moe, attn = _counters("dl4j_moe_"), _counters("dl4j_attn_")
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
    for i in (1, 2):
        assert set(net.state_list[i]) == {"aux_loss", "index_loss",
                                          "moe_rows"}
    after = _counters("dl4j_moe_")
    routed = [after[f"dl4j_moe_routed_rows_total/{i}"]
              - moe.get(f"dl4j_moe_routed_rows_total/{i}", 0) for i in (1, 2)]
    assert routed == want["routed_rows"]
    # on the CPU the XLA math computes a block's whole square of 256; the
    # selection leaves 15 + 11 * 5 = 70 pairs a head; the indexer scores
    # the 136 causal pairs of a sequence
    after = _counters("dl4j_attn_")
    seqs = k * 2

    def seen(name, i):
        return after[f"{name}/{i}"] - attn.get(f"{name}/{i}", 0)

    for i in (1, 2):
        assert seen("dl4j_attn_score_entries_computed_total", i) == (
            seqs * TINY["n_heads"] * 256)
        assert seen("dl4j_attn_score_entries_visible_total", i) == (
            seqs * TINY["n_heads"] * 70)
        assert seen("dl4j_attn_index_pairs_scored_total", i) == seqs * 136
        assert seen("dl4j_attn_pairs_selected_total", i) == seqs * 70
    # the model sets ``gradient_checkpointing``: its two blocks keep their
    # int8 selections of 16 x 16 and the 16 float32 log-sum-exps beside
    # them a sequence; the XLA core of this CPU keeps nothing
    assert net.conf.global_conf.gradient_checkpointing
    assert {n: v - kept.get(n, 0) for n, v in _kept_bytes().items()} == {
        "attn_select": 2 * seqs * 256, "attn_select_lse": 2 * seqs * 64,
        "attn_core_out": 0, "attn_core_lse": 0}


# (f) ---------------------------------------------------------------------
def test_blocks_without_the_new_fields_give_the_parents_bits():
    """A Trinity-Mini and a DeepSeek-V2-Lite block, the new fields at their
    defaults: outputs, state and every gradient equal, bit for bit, to what
    PR 34's code gave (``tests/golden/make_decoder_blocks.py``, run there)."""
    sys.path.insert(0, os.path.join(ROOT, "tests", "golden"))
    import make_decoder_blocks

    want = np.load(os.path.join(ROOT, "tests", "golden",
                                "decoder_blocks_pr34.npz"))
    got = make_decoder_blocks.run()
    assert sorted(got) == sorted(want.files) and len(got) > 60
    for name in want.files:
        assert np.array_equal(got[name], want[name]), name


def test_an_indexer_needs_its_attention_and_sizes():
    with pytest.raises(ValueError, match="indexer"):
        DecoderBlock(n_in=8, n_out=8, attention="mla", index_heads=2,
                     index_dim=4, index_topk=2)
    with pytest.raises(ValueError, match="indexer"):
        DecoderBlock(n_in=8, n_out=8, attention="gqa", window=4,
                     index_heads=2, index_dim=4, index_topk=2)
    with pytest.raises(ValueError, match="indexer"):
        DecoderBlock(n_in=8, n_out=8, attention="gqa", index_heads=2)


def test_selection_runs_on_one_device_only():
    from deeplearning4j_tpu.nn.conf.layers.attention import attend

    q = jnp.zeros((1, 16, 2, 8))
    with pytest.raises(NotImplementedError, match="selection"):
        attend(q, q, q, True, mask=jnp.ones((1, 16)),
               select=jnp.ones((1, 16, 16), jnp.int8))


def test_published_defaults_and_the_blocks_fields():
    import inspect

    d = {k: v.default for k, v in
         inspect.signature(keye_vl2_lm).parameters.items()}
    assert (d["n_layers"], d["vocab_rows"], d["hidden_size"], d["n_heads"],
            d["n_kv_heads"], d["head_dim"], d["index_n_heads"],
            d["index_head_dim"], d["index_topk"], d["moe_intermediate_size"],
            d["n_router_outputs"], d["experts_per_token"],
            d["norm_topk_prob"], d["rms_norm_eps"], d["rope_theta"]) == (
        48, 151936, 2048, 32, 4, 128, 16, 64, 2048, 768, 128, 8, True, 1e-6,
        1e7)
    blocks = [l for l in keye_vl2_lm(**TINY).layers
              if isinstance(l, DecoderBlock)]
    assert len(blocks) == 2
    for b in blocks:
        assert (b.attention, b.output_gate, b.window, b.router,
                b.router_renorm, b.shared_hidden, b.norm_placement) == (
            "gqa", False, None, "softmax", True, 0, "pre")
        assert (b.index_heads, b.index_dim, b.index_topk) == (3, 8, 5)
        # a usual dispatch buffer of three eighths of all pairs (the other
        # models' blocks keep the quarter): 49,152 rows of the cell's 131,072
        assert b.dispatch_eighths == 3
    from deeplearning4j_tpu.nn.conf.layers import moe

    assert DecoderBlock(n_in=8, n_out=8).dispatch_eighths == 2
    assert (moe._usual_bound(131072), moe._usual_bound(131072, 3)) == (
        32768, 49152)
    assert keye_vl2_lm(**TINY).layers[0].output_scale == 1.0
    # the count the configuration states: 562.3 M at five layers of the cut
    cut = dict(n_layers=5, experts_held=[0, 16], vocab_rows=18992)
    n = sum(int(np.prod(s)) for s in ref._shapes(ref._cfg(cut)).values())
    assert round(n / 1e6, 1) == 562.3
