"""Nemotron-3-Nano-30B-A3B through the normal path against the benchmark's
plain reference (``benchmark/reference/nemotron_h.py``, which imports nothing
of the program and runs the state-space recurrence a token at a time), at a
tiny size on the CPU with the reference's seeded weights: the pattern
``EM*M`` (an expert layer, a Mamba-2 mixer, attention, a second mixer) over
40 tokens, which a chunk of 8 does not divide into whole chunks but for the
last. Float32 policy on both sides leaves the order of float32 sums between
them: tolerances are a few 1e-5 relative, far below what any change of the
mathematics would move.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from reference import nemotron_h as ref  # noqa: E402

from deeplearning4j_tpu import common  # noqa: E402
from deeplearning4j_tpu.datasets.dataset import DataSet  # noqa: E402
from deeplearning4j_tpu.models import nemotron_h  # noqa: E402
from deeplearning4j_tpu.models.nemotron_h import PUBLISHED_PATTERN  # noqa: E402
from deeplearning4j_tpu.nn.conf.inputs import InputType  # noqa: E402
from deeplearning4j_tpu.nn.conf.layers import DecoderBlock  # noqa: E402
from deeplearning4j_tpu.nn.conf.layers.moe import grouped_expert_ffn  # noqa: E402
from deeplearning4j_tpu.observability.names import SSM_TOKENS_TOTAL  # noqa: E402
from deeplearning4j_tpu.ops.ssd import ssd_scan  # noqa: E402
from test_keye_vl2 import _kept_bytes  # noqa: E402
from test_trinity_mini import _batches, _close, _counters  # noqa: E402

#: [experts, Mamba-2, attention, Mamba-2]; 16 router outputs of which 4 are
#: held, 2 a token, and a shared expert; 4 mixer heads of 8 over 2 groups of
#: a 16-wide state in chunks of 8; 2 query heads over 1 of 16; 40 positions
TINY = dict(pattern="EM*M", experts_held=[4, 8], vocab_rows=300,
            hidden_size=64, n_heads=2, n_kv_heads=1, head_dim=16,
            ssm_heads=4, ssm_head_dim=8, ssm_state=16, ssm_groups=2,
            ssm_chunk=8, moe_intermediate_size=32,
            shared_intermediate_size=48, n_router_outputs=16,
            experts_per_token=2, seq_len=40, learning_rate=1e-3,
            load_balance_coeff=0.001)


def _net(weights, cfg=TINY, policy="float32", **attrs):
    """The program's network holding the reference's ``weights``."""
    conf = nemotron_h(**cfg)
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
    assert {"W_in", "conv_w", "conv_b", "dt_bias", "A_log", "D",
            "ssm_norm_g", "W_out"} <= set(grads[2])
    assert {"Eu", "Ed", "Su", "Sd"} <= set(grads[1]) and "Eg" not in grads[1]


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
    prefixes = ("dl4j_moe_", "dl4j_attn_", "dl4j_ssm_", "dl4j_short_conv_")
    before = {k: v for p in prefixes for k, v in _counters(p).items()}
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
    got = np.asarray(net.state_list[1]["router_bias"])
    assert np.abs(got).max() > 0 and abs(got.mean()) < 1e-8
    np.testing.assert_allclose(got, want["router_bias"]["1"], rtol=0,
                               atol=1e-8)
    assert net.state_list[2] == net.state_list[3] == {}
    after = {k: v for p in prefixes for k, v in _counters(p).items()}

    def seen(name, i):
        return after.get(f"{name}/{i}", 0) - before.get(f"{name}/{i}", 0)

    assert [seen("dl4j_moe_routed_rows_total", 1)] == want["routed_rows"]
    # steps x batch x positions through each scan; the attention block
    # alone books score entries (the XLA core of this CPU computes its whole
    # square of 40 x 40 a head and leaves the causal 820 visible); nothing
    # is booked for a block without a mixer, nor as a short convolution
    assert [seen("dl4j_ssm_tokens_total", i)
            for i in (1, 2, 3, 4)] == [0, k * 2 * 40, 0, k * 2 * 40]
    heads = k * 2 * TINY["n_heads"]
    for kind, per_head in (("computed", 1600), ("visible", 820)):
        assert [seen(f"dl4j_attn_score_entries_{kind}_total", i)
                for i in (1, 2, 3, 4)] == [0, 0, heads * per_head, 0]
        assert f"dl4j_attn_score_entries_{kind}_total/2" not in after
    assert not any(seen("dl4j_short_conv_tokens_total", i)
                   for i in (1, 2, 3, 4))
    # under gradient checkpointing a Mamba-2 mixer keeps nothing besides its
    # input, and the XLA core of this CPU keeps nothing either
    assert net.conf.global_conf.gradient_checkpointing
    assert {n: v - kept.get(n, 0) for n, v in _kept_bytes().items()} == {
        "attn_core_out": 0, "attn_core_lse": 0}


def test_a_mixer_books_tokens_and_keeps_nothing():
    mixer = DecoderBlock(n_in=8, n_out=8, attention="mamba2", ffn="none",
                         ssm_heads=2, ssm_head_dim=4, ssm_state=4)
    experts = DecoderBlock(n_in=8, n_out=8, attention="none", ffn="moe",
                           n_experts=4, expert_hidden=4)
    attn = DecoderBlock(n_in=8, n_out=8, attention="gqa", n_heads=2,
                        n_kv_heads=1, head_dim=4, ffn="none")
    assert mixer.step_counts(3, 32, jnp.bfloat16) == {SSM_TOKENS_TOTAL: 96}
    assert SSM_TOKENS_TOTAL not in attn.step_counts(3, 32, jnp.bfloat16)
    for block in (mixer, experts):
        assert block.attn_score_entries(3, 32, jnp.bfloat16) == (0, 0)
        assert block.remat_kept_bytes(3, 32, jnp.bfloat16) == {}
    assert attn.remat_kept_bytes(3, 32, jnp.bfloat16)


# (c) ---------------------------------------------------------------------
def _scan_operands(T=21, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    Bt, H, P, G, N = 2, 4, 3, 2, 5
    return (jax.random.normal(ks[0], (Bt, T, H, P)),
            jax.nn.softplus(jax.random.normal(ks[1], (Bt, T, H))),
            -jnp.exp(jax.random.normal(ks[2], (H,))),
            jax.random.normal(ks[3], (Bt, T, G, N)),
            jax.random.normal(ks[4], (Bt, T, G, N)),
            jax.random.normal(ks[5], (H,)))


def _token_loop(x, dt, A, B, C, D):
    """The recurrence as written, one token and one head at a time."""
    x, dt, A, B, C, D = map(np.asarray, (x, dt, A, B, C, D))
    Bt, T, H, P = x.shape
    per = H // B.shape[2]
    y = np.zeros((Bt, T, H, P))
    for b in range(Bt):
        S = np.zeros((H, P, B.shape[-1]))
        for t in range(T):
            for h in range(H):
                g = h // per
                S[h] = (np.exp(dt[b, t, h] * A[h]) * S[h]
                        + dt[b, t, h] * np.outer(x[b, t, h], B[b, t, g]))
                y[b, t, h] = S[h] @ C[b, t, g] + D[h] * x[b, t, h]
    return y


@pytest.mark.parametrize("chunk", [1, 4, 8, 32])
def test_the_chunked_scan_is_the_recurrence_token_by_token(chunk):
    ops = _scan_operands()
    _close(ssd_scan(*ops, chunk), _token_loop(*ops), what="per-token loop")
    # and the reference's blocked recurrence, a sequence at a time
    for b in range(2):
        _close(ssd_scan(*ops, chunk)[b],
               ref.recurrence(ops[0][b], ops[1][b], ops[2], ops[3][b],
                              ops[4][b], ops[5]), what="reference")


def test_the_chunked_scan_has_the_recurrences_gradients():
    ops = _scan_operands()
    probe = jax.random.normal(jax.random.PRNGKey(7), ops[0].shape)

    def loss(scan, *a):
        return jnp.sum(jnp.sin(scan(*a)) * probe)

    want = jax.grad(lambda *a: loss(
        lambda x, dt, A, B, C, D: jnp.stack([
            ref.recurrence(x[b], dt[b], A, B[b], C[b], D) for b in range(2)]),
        *a), argnums=tuple(range(6)))(*ops)
    for chunk in (3, 8):
        got = jax.grad(lambda *a: loss(lambda *o: ssd_scan(*o, chunk), *a),
                       argnums=tuple(range(6)))(*ops)
        for name, g, w in zip("x dt A B C D".split(), got, want):
            _close(g, w, what=f"d{name} at chunk {chunk}")


@pytest.mark.parametrize("t", [0, 7, 8, 19])
def test_the_scan_is_causal(t):
    ops = _scan_operands(seed=t)
    later = tuple(a.at[:, t + 1:].set(jax.random.normal(
        jax.random.PRNGKey(99), a[:, t + 1:].shape)) if a.ndim > 1 else a
        for a in ops)
    a, b = ssd_scan(*ops, 8), ssd_scan(*later, 8)
    assert np.array_equal(a[:, :t + 1], b[:, :t + 1])
    assert not np.array_equal(a[:, t + 1:], b[:, t + 1:])


@pytest.mark.parametrize("t", [0, 13, 38])
def test_the_mixer_is_causal(t):
    """The whole Mamba-2 block: taps, scan, gate and norm."""
    block = nemotron_h(**TINY).layers[2]
    assert block.attention == "mamba2"
    with common.override_policy("float32"):
        p = block.init_params(jax.random.PRNGKey(1), InputType.recurrent(64, 40))
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 40, 64))
        later = x.at[:, t + 1:].set(jax.random.normal(
            jax.random.PRNGKey(3), x[:, t + 1:].shape))
        a, b = block.apply(p, {}, x)[0], block.apply(p, {}, later)[0]
    assert np.array_equal(a[:, :t + 1], b[:, :t + 1])
    assert not np.array_equal(a[:, t + 1:], b[:, t + 1:])


# (d) ---------------------------------------------------------------------
def test_squared_relu_experts_are_a_dense_loop():
    S, F, He, E, k, G = 24, 16, 8, 8, 2, 4
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(ks[0], (S, F))
    choice = jax.random.randint(ks[1], (S, k), 0, E)
    weight = jax.random.uniform(ks[2], (S, k))
    wu = jax.random.normal(ks[3], (G, F, He)) * 0.3
    wd = jax.random.normal(ks[4], (G, He, F)) * 0.3
    with common.override_policy("float32"):
        got, rows = grouped_expert_ffn(x, choice, weight, None, wu, wd, 2)
    want = np.zeros((S, F))
    for s in range(S):
        for j in range(k):
            e = int(choice[s, j]) - 2
            if 0 <= e < G:
                h = np.maximum(np.asarray(x[s]) @ np.asarray(wu[e]), 0) ** 2
                want[s] += float(weight[s, j]) * h @ np.asarray(wd[e])
    _close(got, want, what="dense loop")
    here = (np.asarray(choice) >= 2) & (np.asarray(choice) < 2 + G)
    assert int(rows[0]) == int(here.sum())


def test_sixteen_shares_add_up_to_the_uncut_layer():
    """The routed parts of every share, and the shared expert once, make
    the uncut layer (16 router outputs, a share of 1 expert each)."""
    whole = dict(TINY, experts_held=None)
    weights = ref.init(11, whole)           # all 16 experts' weights
    E, k = whole["n_router_outputs"], whole["experts_per_token"]
    u = jax.random.normal(jax.random.PRNGKey(2), (24, whole["hidden_size"]))
    bias = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (E,))
    want, load, rows = ref.expert_layer(weights, 1, u, ref._cfg(whole),
                                        "float32", bias)
    assert int(rows) == 24 * k == int(load.sum())
    p = {n.split("/", 1)[1]: v for n, v in weights.items()
         if n.startswith("1/")}
    total, seen = jnp.zeros_like(u), 0
    with common.override_policy("float32"):
        for first in range(E):
            layer = nemotron_h(**dict(TINY, experts_held=[first, first + 1])
                               ).layers[1]
            assert layer.expert_act == "relu2" and layer.shared_hidden
            share = dict(p, Eu=p["Eu"][first:first + 1],
                         Ed=p["Ed"][first:first + 1])
            choice, weight, _ = layer.route(share, u[None], bias)
            _close(weight[0].sum(-1), np.full(24, 2.5), rtol=1e-5,
                   what="renormalised to the scale")
            part, stats = layer.routed_part(share, u, choice[0], weight[0])
            total = total + part
            seen += int(stats[0])
        total = total + layer.shared_part(p, u)
    assert seen == 24 * k                   # every pair was some share's
    _close(total, want, what="sum of shares and the shared expert")


# (e) ---------------------------------------------------------------------
def test_single_branch_blocks_hold_nothing_of_the_absent_branch():
    conf = nemotron_h(**TINY)
    itype = InputType.recurrent(64, 40)
    kinds = {"E": {"norm2_g", "Wr", "Eu", "Ed", "Su", "Sd"},
             "M": {"norm1_g", "W_in", "conv_w", "conv_b", "dt_bias", "A_log",
                   "D", "ssm_norm_g", "W_out"},
             "*": {"norm1_g", "Wq", "Wk", "Wv", "Wo"}}
    for letter, block in zip(TINY["pattern"], conf.layers[1:5]):
        params = block.init_params(jax.random.PRNGKey(0), itype)
        assert set(params) == kinds[letter], letter
        state = block.init_state(itype)
        assert set(state) == ({"router_bias", "moe_rows"} if letter == "E"
                              else set())
    mixer = conf.layers[2]
    p = mixer.init_params(jax.random.PRNGKey(0), itype)
    assert p["W_in"].shape == (64, 2 * 32 + 2 * 2 * 16 + 4)
    assert p["conv_w"].shape == (4, 32 + 2 * 2 * 16)
    dt = jax.nn.softplus(p["dt_bias"])
    assert bool(jnp.all((dt >= 1e-3 * 0.999) & (dt <= 0.1 * 1.001)))
    assert bool(jnp.all((jnp.exp(p["A_log"]) >= 1) & (jnp.exp(p["A_log"])
                                                       <= 16)))
    assert np.array_equal(p["D"], np.ones(4)) and not np.any(p["conv_b"])


def test_published_defaults_and_pattern():
    import inspect

    d = {k: v.default for k, v in
         inspect.signature(nemotron_h).parameters.items()}
    assert (d["pattern"], d["vocab_rows"], d["hidden_size"], d["n_heads"],
            d["n_kv_heads"], d["head_dim"], d["ssm_heads"], d["ssm_head_dim"],
            d["ssm_state"], d["ssm_groups"], d["ssm_chunk"], d["conv_kernel"],
            d["moe_intermediate_size"], d["shared_intermediate_size"],
            d["n_router_outputs"], d["experts_per_token"],
            d["routed_scaling_factor"], d["rms_norm_eps"]) == (
        None, 131072, 2688, 32, 2, 128, 64, 64, 128, 8, 128, 4, 1856, 3712,
        128, 6, 2.5, 1e-5)
    assert len(PUBLISHED_PATTERN) == 52
    blocks = [l for l in nemotron_h(**{
        k: v for k, v in TINY.items() if k != "pattern"}).layers
        if isinstance(l, DecoderBlock)]
    kinds = [(b.attention, b.ffn) for b in blocks]
    assert kinds.count(("mamba2", "none")) == 23
    assert kinds.count(("none", "moe")) == 23
    assert kinds.count(("gqa", "none")) == 6
    assert [i for i, k in enumerate(kinds) if k[0] == "gqa"] == [
        5, 12, 19, 26, 33, 42]
    for b in blocks:
        assert (b.norm_placement, b.norm_eps) == ("pre", 1e-5)
        if b.attention == "gqa":
            assert (b.qk_norm, b.output_gate, b.window, b.rope_theta) == (
                False, False, None, None)
        if b.ffn == "moe":
            assert (b.router, b.expert_act, b.route_scale,
                    b.bias_update_rate) == ("sigmoid_bias", "relu2", 2.5,
                                            0.001)
    with pytest.raises(ValueError, match="pattern"):
        nemotron_h(**dict(TINY, pattern="EMX*"))


# (f) ---------------------------------------------------------------------
_MAMBA = dict(attention="mamba2", ffn="none", ssm_heads=4, ssm_head_dim=4,
              ssm_state=4, ssm_groups=2)


@pytest.mark.parametrize("fields,match", [
    (dict(_MAMBA, window=4), "Mamba-2"),
    (dict(_MAMBA, index_heads=2, index_dim=4, index_topk=2), "indexer"),
    (dict(_MAMBA, rope_theta=1e4), "Mamba-2"),
    (dict(_MAMBA, ssm_groups=3), "Mamba-2"),
    (dict(_MAMBA, ssm_heads=0), "Mamba-2"),
    (dict(attention="none", ffn="none"), "mixer or a feed-forward"),
    (dict(attention="none", ffn="swiglu", norm_placement="sandwich"),
     "single-branch"),
    (dict(attention="gqa", ffn="none", norm_placement="sandwich"),
     "single-branch"),
    (dict(attention="mamba3"), "attention"),
    (dict(ffn="geglu"), "ffn"),
    (dict(ffn="moe", expert_act="gelu"), "expert_act")])
def test_the_new_kinds_refuse_what_they_cannot_be(fields, match):
    with pytest.raises(ValueError, match=match):
        DecoderBlock(n_in=8, n_out=8, **fields)


def test_a_grouped_attention_without_qk_norms_has_no_scales():
    kw = dict(n_in=16, n_out=16, attention="gqa", n_heads=4, n_kv_heads=1,
              head_dim=4, ffn="none")
    itype = InputType.recurrent(16, 8)
    normed = DecoderBlock(**kw).init_params(jax.random.PRNGKey(0), itype)
    plain = DecoderBlock(qk_norm=False, **kw).init_params(
        jax.random.PRNGKey(0), itype)
    assert set(normed) - set(plain) == {"q_norm_g", "k_norm_g"}
