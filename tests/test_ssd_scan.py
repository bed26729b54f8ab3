"""The Mamba-2 chunked scan's Pallas kernels (``ops/ssd.py``) against the
statement they are held to, ``_ssd_scan_xla``, on the CPU in interpret mode:
the forward and the gradients with respect to every operand, at float32 to
float32's rounding and at bfloat16 within the statement's own distance from
a float32 run; and where ``ssd.ssd_scan`` sends a call, and what a Mamba-2
block's gradient then holds, through the scan's kernels or through the
whole core's (``ssd.mamba_core``, its own tests in ``test_mamba_core.py``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from deeplearning4j_tpu import common
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import DecoderBlock
from deeplearning4j_tpu.observability.metrics import global_registry
from deeplearning4j_tpu.observability.names import PALLAS_DISPATCH_TOTAL
from deeplearning4j_tpu.ops import pallas_kernels as pk
from deeplearning4j_tpu.ops import ssd

#: (Bt, T, G, K heads a group, P, N, chunk, A = 0)
CASES = {
    "ragged": (1, 21, 1, 2, 8, 16, 8, False),
    "batch": (3, 32, 1, 2, 8, 16, 8, False),
    "heads-share-a-slab": (1, 24, 1, 4, 64, 16, 8, False),
    "groups": (2, 24, 2, 2, 8, 8, 8, False),
    "one-chunk-each": (6, 8, 1, 2, 8, 16, 8, False),
    "no-decay": (2, 20, 1, 2, 8, 16, 8, True),
    "many-chunks": (1, 72, 1, 2, 16, 8, 8, False),
    "one-head-a-group": (2, 20, 2, 1, 8, 16, 8, False),
    "a-slab-a-head": (1, 24, 1, 2, 128, 16, 8, False),
    "four-heads-a-slab": (1, 24, 1, 4, 32, 16, 8, False),
}
NAMES = ("y", "x", "dt", "A", "B", "C", "D")


def _operands(Bt, T, G, K, P, N, zero_decay, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    H = G * K
    A = -jnp.exp(jax.random.normal(ks[2], (H,)))
    return (jax.random.normal(ks[0], (Bt, T, H, P)).astype(dtype),
            jax.nn.softplus(jax.random.normal(ks[1], (Bt, T, H))),
            A * 0 if zero_decay else A,
            jax.random.normal(ks[3], (Bt, T, G, N)).astype(dtype),
            jax.random.normal(ks[4], (Bt, T, G, N)).astype(dtype),
            jax.random.normal(ks[5], (H,)))


def _value_and_grads(scan, ops, chunk, probe):
    """y and the gradients of ``sum(sin(y) * probe)`` w.r.t. all six."""
    def loss(*a):
        y = scan(*a, chunk)
        return jnp.sum(jnp.sin(y) * probe), y

    (_, y), grads = jax.value_and_grad(loss, argnums=tuple(range(6)),
                                       has_aux=True)(*ops)
    return [np.asarray(v, np.float64) for v in (y, *grads)]


def _kernels(*a):
    return ssd._ssd_scan_kernels(*a, interpret=True)


def _gap(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernels_match_the_statement_forward_and_backward(case, dtype):
    Bt, T, G, K, P, N, chunk, zero_decay = CASES[case]
    dtype = jnp.dtype(dtype)
    ops = _operands(Bt, T, G, K, P, N, zero_decay, dtype)
    probe = jax.random.normal(jax.random.PRNGKey(7), (Bt, T, G * K, P))
    got = _value_and_grads(_kernels, ops, chunk, probe)
    xla = _value_and_grads(ssd._ssd_scan_xla, ops, chunk, probe)
    if dtype == jnp.float32:
        for name, g, w in zip(NAMES, got, xla):
            assert _gap(g, w) < 2e-5, (name, _gap(g, w))
        return
    # bfloat16: each against the statement run in float32 on the same
    # (rounded) operands; the kernels round no operand more than it does.
    # Both share the forward's rounded products, which set most of the gap;
    # within two bfloat16 roundings (2**-7) which of the two lies nearer is
    # the noise of where each rounds and sums (A's few values cancel most)
    exact = _value_and_grads(ssd._ssd_scan_xla, tuple(
        a.astype(jnp.float32) for a in ops), chunk, probe)
    for name, g, x, w in zip(NAMES, got, xla, exact):
        assert _gap(g, w) <= max(1.5 * _gap(x, w), 2.0 ** -7), (
            name, _gap(g, w), _gap(x, w))


def _engaged(kernel):
    text = global_registry().prometheus_text()
    return {e: _read(text, kernel, e) for e in ("true", "false")}


def _read(text, kernel, engaged):
    for line in text.splitlines():
        if (line.startswith(PALLAS_DISPATCH_TOTAL) and f'kernel="{kernel}"'
                in line and f'engaged="{engaged}"' in line):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


@pytest.mark.parametrize("K, P, hs", [(1, 8, 1), (2, 128, 1), (2, 64, 2),
                                      (8, 64, 2), (4, 32, 4), (3, 16, 3)])
def test_heads_per_slab(K, P, hs):
    assert ssd._heads_per_slab(K, P) == hs


def test_the_cpu_books_the_fallback_and_runs_the_statement():
    ops = _operands(2, 21, 1, 2, 8, 16, False, jnp.float32)
    before = [_engaged(k) for k in ("ssd_scan", "ssd_scan_bwd")]
    y = jax.jit(lambda *a: ssd.ssd_scan(*a, 8))(*ops)
    for kernel, was in zip(("ssd_scan", "ssd_scan_bwd"), before):
        after = _engaged(kernel)
        assert after["false"] == was["false"] + 1
        assert after["true"] == was["true"]
    assert np.array_equal(y, jax.jit(
        lambda *a: ssd._ssd_scan_xla(*a, 8))(*ops))


def _eqns(jaxpr, out=None):
    """Every equation of ``jaxpr``, nested programs included."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        out.append(eqn)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _eqns(sub, out)
    return out


@pytest.mark.parametrize("fused", [False, True])
def test_a_mamba2_blocks_gradient_runs_the_kernels_and_no_scan_over_chunks(
        monkeypatch, fused):
    """With the kernels engaged (interpret mode). Where a group's lanes are
    too narrow for the whole core's kernels (``fused`` False: 16 lanes), a
    block's gradient holds the scan's forward kernel, the forward that also
    writes the state entering each chunk and the backward kernel, and no
    scan but the map over the 2 groups: none over the 5 chunks. Where they
    engage (128 lanes, chunks of 128 tokens), it holds the core's forward
    that also writes its residuals and the core's backward, and no scan at
    all: none over the groups nor over the 2 chunks."""
    real = pl.pallas_call
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    monkeypatch.setattr(pl, "pallas_call",
                        lambda *a, **kw: real(*a, **{**kw, "interpret": True}))
    B, T, F, G, chunk = 2, 40, 32, 2, 8
    P, N = (64, 128) if fused else (8, 16)
    if fused:                  # a chunk of 128 tokens, the last one partial
        T, chunk = 136, 128
    block = DecoderBlock(n_in=F, n_out=F, attention="mamba2", ffn="none",
                         ssm_heads=4, ssm_head_dim=P, ssm_state=N,
                         ssm_groups=G, ssm_chunk=chunk)
    kernels = (("mamba_core", "mamba_core_bwd") if fused
               else ("ssd_scan", "ssd_scan_bwd"))
    with common.override_policy("float32"):
        p = block.init_params(jax.random.PRNGKey(1), InputType.recurrent(F, T))
        x = jax.random.normal(jax.random.PRNGKey(2), (B, T, F))

        def loss(p, x):
            return jnp.sum(jnp.sin(block.apply(p, {}, x)[0]))

        before = [_engaged(k) for k in kernels]
        eqns = _eqns(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
            p, x).jaxpr)
        fwd, bwd = (_engaged(k) for k in kernels)
        assert fwd["true"] > before[0]["true"] and fwd["false"] == before[0][
            "false"]
        assert bwd["true"] > before[1]["true"]
        scans = [e.params["length"] for e in eqns
                 if e.primitive.name == "scan"]
        calls = [tuple(v.aval.shape for v in e.outvars) for e in eqns
                 if e.primitive.name == "pallas_call"]
        alone = [tuple(v.aval.shape for v in e.outvars) for e in _eqns(
            jax.make_jaxpr(loss)(p, x).jaxpr)
            if e.primitive.name == "pallas_call"]
        if fused:
            W, n = 2 * P, -(-T // chunk)
            assert scans == []
            # y, the float32 y before the gate and the states entering each
            # chunk; then the backward's seven, its first the cotangent of
            # W_in's output whole, tokens minor
            assert calls[0] == ((B, n * chunk, G * W), (B, n * chunk, G * W),
                                (B, G, n, N, W))
            assert len(calls) == 2 and len(calls[1]) == 7
            assert calls[1][0] == (B, 2 * G * W + 2 * G * N + 4, n * chunk)
            # a forward with no backward writes y alone
            assert alone == [((B, n * chunk, G * W),)]
        else:
            assert scans and set(scans) == {G}
            rows, H = B, 4 // G
            # y and the states entering each chunk, then the backward's seven
            assert ((rows, T, H * 8), (rows, T // chunk, 16, H * 8)) in calls
            assert any(len(c) == 7 and c[0] == (rows, T, H * 8) for c in calls)
            # a forward with no backward writes y alone
            assert alone == [((rows, T, H * 8),)]
        # and the gradient the kernels give is the statement's
        got = jax.grad(loss, argnums=(0, 1))(p, x)
        monkeypatch.setattr(ssd, "ssd_scan", ssd._ssd_scan_xla)
        monkeypatch.setattr(ssd, "_core_ok", lambda *a: False)
        want = jax.grad(loss, argnums=(0, 1))(p, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert _gap(np.asarray(g), np.asarray(w)) < 5e-5
