"""Equivalence suite for the three-variant recurrent engine (ops/lstm.py).

The scan variant is the oracle: fused and pallas (interpret mode on CPU) must
reproduce its forward within 1e-5 relative in f32 and its gradients through
their own backward paths (autodiff through the fused scan, the hand-derived
custom VJP for the kernel). Dispatch-gate selection is pinned per env
override, and the serving seam is held to a bitwise contract: a T-step
rnnTimeStep loop equals one fused-scan forward exactly in f32.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (GravesBidirectionalLSTM,
                                               GravesLSTM, LSTM,
                                               RnnOutputLayer)
from deeplearning4j_tpu.nn.gradientcheck import check_gradients
from deeplearning4j_tpu.nn.multilayer import (MultiLayerNetwork,
                                              make_multistep_train_step)
from deeplearning4j_tpu.ops import lstm as eng
from deeplearning4j_tpu.ops.activations import get_activation

B, T, F, H = 3, 7, 5, 6
ACT, GATE = get_activation("tanh"), get_activation("sigmoid")


def _params(peephole: bool, seed: int = 0, n_in: int = F, hidden: int = H):
    rng = np.random.default_rng(seed)
    p = {"W": jnp.asarray(rng.normal(0, 0.3, (n_in, 4 * hidden)), jnp.float32),
         "RW": jnp.asarray(rng.normal(0, 0.3, (hidden, 4 * hidden)),
                           jnp.float32),
         "b": jnp.asarray(rng.normal(0, 0.1, (4 * hidden,)), jnp.float32)}
    if peephole:
        for k in ("pI", "pF", "pO"):
            p[k] = jnp.asarray(rng.normal(0, 0.2, (hidden,)), jnp.float32)
    return p


def _inputs(seed: int = 0, batch: int = B, seq: int = T, n_in: int = F,
            masked: bool = True):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(0, 1, (batch, seq, n_in)), jnp.float32)
    mask = (jnp.asarray((rng.random((batch, seq)) > 0.3)
                        .astype(np.float32)) if masked else None)
    return x, mask


def _run(impl, p, x, mask, peephole, h0=None, c0=None):
    z = jnp.zeros((x.shape[0], p["RW"].shape[0]), jnp.float32)
    return eng.lstm_sequence(p, x, ACT, GATE,
                             z if h0 is None else h0,
                             z if c0 is None else c0,
                             peephole, mask, impl=impl,
                             interpret=(impl == "pallas"))


# --------------------------------------------------------- forward vs oracle
@pytest.mark.parametrize("impl", ["fused", "pallas"])
@pytest.mark.parametrize("peephole", [False, True])
@pytest.mark.parametrize("masked", [False, True])
def test_forward_matches_scan_oracle(impl, peephole, masked):
    p = _params(peephole)
    x, mask = _inputs(masked=masked)
    ys0, (h0, c0) = _run("scan", p, x, mask, peephole)
    ys1, (h1, c1) = _run(impl, p, x, mask, peephole)
    np.testing.assert_allclose(ys1, ys0, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(h1, h0, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(c1, c0, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("seq", [1, 8, 16, 33])
def test_pallas_block_padding_all_seq_lengths(seq):
    """Any T is serviceable: the engine pads to a block multiple with zero
    mask, the kernel freezes state on the pad, the engine trims the pad."""
    p = _params(True, seed=3)
    x, mask = _inputs(seed=3, seq=seq)
    ys0, (h0, c0) = _run("scan", p, x, mask, True)
    ys1, (h1, c1) = _run("pallas", p, x, mask, True)
    assert ys1.shape == ys0.shape
    np.testing.assert_allclose(ys1, ys0, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(h1, h0, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(c1, c0, rtol=1e-5, atol=1e-6)


# -------------------------------------------------------- gradients vs oracle
@pytest.mark.parametrize("impl", ["fused", "pallas"])
@pytest.mark.parametrize("peephole", [False, True])
@pytest.mark.parametrize("masked", [False, True])
def test_grad_matches_scan_oracle(impl, peephole, masked):
    """d(params), d(x), and d(h0, c0) — the initial-state cotangents are what
    TBPTT chunk boundaries hand backward, so they get checked too."""
    p = _params(peephole, seed=1)
    x, mask = _inputs(seed=1)
    rng = np.random.default_rng(9)
    h0 = jnp.asarray(rng.normal(0, 1, (B, H)), jnp.float32)
    c0 = jnp.asarray(rng.normal(0, 1, (B, H)), jnp.float32)

    def grads(which):
        def loss(p_, x_, h0_, c0_):
            ys, (h, c) = _run(which, p_, x_, mask, peephole, h0_, c0_)
            return (jnp.sum(jnp.cos(ys)) + jnp.sum(h * h)
                    + jnp.sum(jnp.sin(c)))
        return jax.grad(loss, argnums=(0, 1, 2, 3))(p, x, h0, c0)

    g0, g1 = grads("scan"), grads(impl)
    for k in g0[0]:
        np.testing.assert_allclose(g1[0][k], g0[0][k], rtol=1e-4, atol=1e-5,
                                   err_msg=f"d{k}")
    for a, b, name in ((g1[1], g0[1], "dx"), (g1[2], g0[2], "dh0"),
                      (g1[3], g0[3], "dc0")):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5, err_msg=name)


def test_pallas_custom_vjp_gradientcheck(monkeypatch):
    """Numeric-vs-analytic check THROUGH the kernel's hand-derived backward:
    check_gradients swaps in an all-f64 policy, and the kernel's compute
    dtype promotes with the operands, so the interpret-mode run really is
    checked at f64 resolution."""
    monkeypatch.setenv(eng.IMPL_ENV, "pallas")
    monkeypatch.setenv("DL4J_LSTM_INTERPRET", "1")
    net = MultiLayerNetwork(
        NeuralNetConfiguration.builder().seed(7).list()
        .layer(GravesLSTM(n_in=4, n_out=5, activation="tanh"))
        .layer(RnnOutputLayer(n_in=5, n_out=3, loss="mcxent",
                              activation="softmax"))
        .build())
    net.init()
    rng = np.random.default_rng(5)
    x = rng.normal(size=(2, 6, 4)).astype(np.float32)
    ids = rng.integers(0, 3, (2, 6))
    y = np.eye(3, dtype=np.float32)[ids]
    assert check_gradients(net, x, y, subset=60, verbose=True)


# ----------------------------------------------------------- layer-level path
@pytest.mark.parametrize("impl", ["fused", "pallas"])
def test_bidirectional_layer_matches_scan(impl, monkeypatch):
    layer = GravesBidirectionalLSTM(n_in=F, n_out=H, activation="tanh")
    params = layer.init_params(jax.random.PRNGKey(0), InputType.recurrent(F))
    x, mask = _inputs(seed=2)

    def run(which):
        monkeypatch.setenv(eng.IMPL_ENV, which)
        monkeypatch.setenv("DL4J_LSTM_INTERPRET",
                           "1" if which == "pallas" else "0")
        ys, _ = layer.apply(params, {}, x, mask=mask)
        return ys

    np.testing.assert_allclose(run(impl), run("scan"), rtol=1e-5, atol=1e-6)


def test_rnn_time_step_loop_bitwise_equals_fused_forward(monkeypatch):
    """The serving seam's contract (ISSUE 6 satellite): T single-step
    apply_streaming calls reproduce one fused-scan forward BITWISE in f32 —
    both paths run the identical per-step cell primitives, so streaming
    inference cannot drift from training numerics."""
    monkeypatch.setenv(eng.IMPL_ENV, "fused")
    layer = LSTM(n_in=F, n_out=H, activation="tanh")
    params = layer.init_params(jax.random.PRNGKey(1), InputType.recurrent(F))
    x, _ = _inputs(seed=4, masked=False)
    full, _ = layer.apply(params, {}, x)
    state = {}
    steps = []
    for t in range(T):
        yt, state = layer.apply_streaming(params, state, x[:, t:t + 1])
        steps.append(yt)
    loop = jnp.concatenate(steps, axis=1)
    assert np.array_equal(np.asarray(full), np.asarray(loop))


@pytest.mark.parametrize("impl", ["scan", "fused", "pallas"])
def test_multistep_kgroup_training_matches_oracle(impl, monkeypatch):
    """K-step fused-dispatch training (the bench/fit hot path) reaches the
    same losses and parameters under every variant — the dispatch decision
    holds for the whole K-group trace, fwd AND bwd."""
    from deeplearning4j_tpu.models.char_rnn import char_rnn_lstm

    def train(which):
        monkeypatch.setenv(eng.IMPL_ENV, which)
        monkeypatch.setenv("DL4J_LSTM_INTERPRET",
                           "1" if which == "pallas" else "0")
        conf = char_rnn_lstm(vocab_size=8, hidden=6, layers=1,
                             tbptt_length=5)
        conf.backprop_type = "Standard"
        net = MultiLayerNetwork(conf).init()
        multi = make_multistep_train_step(conf)
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 8, (3, 2, 5))  # [K, B, T]
        xs = jnp.asarray(np.eye(8, dtype=np.float32)[ids])
        params, states, upd, loss = multi(
            net.params_list, net.state_list, net.updater_state, xs, xs,
            jax.random.PRNGKey(0), jnp.int32(0))
        return params, loss

    p0, l0 = train("scan")
    p1, l1 = train(impl)
    np.testing.assert_allclose(l1, l0, rtol=1e-4, atol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p0)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------- dispatch gate
class TestDispatchGate:
    def test_default_is_fused_on_cpu(self, monkeypatch):
        monkeypatch.delenv(eng.IMPL_ENV, raising=False)
        assert eng.resolve_impl(H, T, B, F) == ("fused", None)

    @pytest.mark.parametrize("forced", ["scan", "fused"])
    def test_env_forces_variant(self, forced, monkeypatch):
        monkeypatch.setenv(eng.IMPL_ENV, forced)
        assert eng.resolve_impl(1024, 1024, 64, 256) == (forced, None)

    def test_forced_pallas_on_cpu_raises(self, monkeypatch):
        """A request for the kernel never runs something else: without a
        TPU (and without a test's interpret mode) it is an error."""
        monkeypatch.setenv(eng.IMPL_ENV, "pallas")
        with pytest.raises(ValueError, match="no TPU"):
            eng.resolve_impl(1024, 1024, 64, 256)

    def test_forced_pallas_engages_under_interpret(self):
        sel, bt = eng.resolve_impl(H, T, B, F, impl="pallas", interpret=True)
        assert sel == "pallas" and bt in eng.BLOCK_CHOICES

    def test_auto_thresholds_hidden_and_seq(self, monkeypatch):
        monkeypatch.setenv("DL4J_LSTM_PALLAS_MIN_HIDDEN", "8")
        monkeypatch.setenv("DL4J_LSTM_PALLAS_MIN_SEQ", "8")
        sel, bt = eng.resolve_impl(8, 16, 2, 4, impl="auto", interpret=True)
        assert sel == "pallas" and bt is not None
        assert eng.resolve_impl(4, 16, 2, 4, impl="auto",
                                interpret=True)[0] == "fused"  # hidden below
        assert eng.resolve_impl(8, 4, 2, 4, impl="auto",
                                interpret=True)[0] == "fused"  # seq below

    def test_block_autotune_prefers_least_padding(self):
        # T=16: blocks 16 and 8 pad nothing, 32 pads 16 -> largest no-pad
        # block wins
        assert eng.resolve_impl(H, 16, B, F, impl="pallas",
                                interpret=True)[1] == 16
        # T=64: all divide; largest block wins
        assert eng.resolve_impl(H, 64, B, F, impl="pallas",
                                interpret=True)[1] == 32

    def test_block_env_override(self, monkeypatch):
        monkeypatch.setenv("DL4J_LSTM_BLOCK", "16")
        assert eng.resolve_impl(H, 64, B, F, impl="pallas",
                                interpret=True)[1] == 16

    def test_vmem_budget_rules_out_pallas(self, monkeypatch):
        """The (hidden, seq, batch)-keyed feasibility half of the gate:
        hidden=1024 f32 puts W+dW alone at ~67MB, over any real budget."""
        monkeypatch.setenv("DL4J_LSTM_VMEM_BUDGET", str(1024))
        with pytest.raises(ValueError, match="VMEM"):
            eng.resolve_impl(8, 16, 2, 4, impl="pallas", interpret=True)
        # the same refusal under auto is a choice, not an error
        monkeypatch.setenv("DL4J_LSTM_PALLAS_MIN_HIDDEN", "8")
        assert eng.resolve_impl(8, 16, 2, 4, impl="auto",
                                interpret=True) == ("fused", None)

    def test_nonstandard_activation_rules_out_pallas(self):
        for kw in ({"act_name": "relu"}, {"gate_name": "hardsigmoid"}):
            with pytest.raises(ValueError, match="tanh/sigmoid"):
                eng.resolve_impl(H, 16, B, F, impl="pallas", interpret=True,
                                 **kw)
            assert eng.resolve_impl(H, 16, B, F, impl="auto", interpret=True,
                                    **kw) == ("fused", None)

    def test_unknown_impl_raises(self):
        with pytest.raises(ValueError):
            eng.resolve_impl(H, T, B, F, impl="cudnn")

    def test_dispatch_counter_increments(self):
        from deeplearning4j_tpu.observability.metrics import global_registry
        p = _params(False)
        x, _ = _inputs(masked=False)
        _run("fused", p, x, None, False)
        text = global_registry().prometheus_text()
        assert 'dl4j_lstm_dispatch_total{impl="fused",requested="fused"}' \
            in text
