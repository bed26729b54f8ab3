"""The Mamba-2 chunked scan (``ops/ssd.py::ssd_scan``, the statement the
whole core's kernels are held to) against the recurrence as written, a
token at a time (``benchmark/reference/nemotron_h.py::recurrence``): the
forward and the gradients with respect to every operand, at float32 to
float32's rounding, and at bfloat16 within what its roundings of the
products' operands and cotangents leave, of the recurrence run in float32
on the same rounded operands; and what a Mamba-2 block's gradient holds
where the whole core's kernels (``ssd.mamba_core``, its own tests in
``test_mamba_core.py``) engage."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from reference import nemotron_h as ref  # noqa: E402

from deeplearning4j_tpu import common  # noqa: E402
from deeplearning4j_tpu.nn.conf.inputs import InputType  # noqa: E402
from deeplearning4j_tpu.nn.conf.layers import DecoderBlock  # noqa: E402
from deeplearning4j_tpu.observability.metrics import global_registry  # noqa: E402
from deeplearning4j_tpu.observability.names import PALLAS_DISPATCH_TOTAL  # noqa: E402
from deeplearning4j_tpu.ops import pallas_kernels as pk  # noqa: E402
from deeplearning4j_tpu.ops import ssd  # noqa: E402

#: (Bt, T, G, K heads a group, P, N, chunk, A = 0)
CASES = {
    "ragged": (1, 21, 1, 2, 8, 16, 8, False),
    "batch": (3, 32, 1, 2, 8, 16, 8, False),
    "groups": (2, 24, 2, 2, 8, 8, 8, False),
    "one-chunk-each": (6, 8, 1, 2, 8, 16, 8, False),
    "no-decay": (2, 20, 1, 2, 8, 16, 8, True),
    "many-chunks": (1, 72, 1, 2, 16, 8, 8, False),
    "one-head-a-group": (2, 20, 2, 1, 8, 16, 8, False),
    "chunk-of-one": (2, 12, 1, 2, 8, 8, 1, False),
    "chunk-past-the-sequence": (2, 13, 1, 2, 8, 8, 32, False),
    "ragged-groups": (2, 27, 3, 2, 4, 8, 5, False),
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


def _recurrence(x, dt, A, B, C, D):
    """The reference's recurrence, a sequence at a time."""
    return jnp.stack([ref.recurrence(x[b], dt[b], A, B[b], C[b], D)
                      for b in range(x.shape[0])])


def _value_and_grads(scan, ops, probe):
    """y and the gradients of ``sum(sin(y) * probe)`` w.r.t. all six."""
    def loss(*a):
        y = scan(*a)
        return jnp.sum(jnp.sin(y) * probe), y

    (_, y), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(6)), has_aux=True))(*ops)
    return [np.asarray(v, np.float64) for v in (y, *grads)]


def _gap(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_statement_is_the_recurrence_forward_and_backward(case, dtype):
    Bt, T, G, K, P, N, chunk, zero_decay = CASES[case]
    ops = _operands(Bt, T, G, K, P, N, zero_decay, jnp.dtype(dtype))
    probe = jax.random.normal(jax.random.PRNGKey(7), (Bt, T, G * K, P))
    got = _value_and_grads(lambda *a: ssd.ssd_scan(*a, chunk), ops, probe)
    # against the recurrence in float32 on the same (rounded) operands. At
    # bfloat16 the forward rounds its products' operands once, within two
    # bfloat16 roundings (2**-7); the backward hands its cotangents to
    # bfloat16 products too, and a gradient that sums many of them, with
    # cancellation, lies up to 2**-4 of its largest entry from the
    # recurrence's at these shapes: bounded at 2**-3, where a wrong term
    # moves it by its whole size
    want = _value_and_grads(_recurrence, tuple(
        a.astype(jnp.float32) for a in ops), probe)
    for name, g, w in zip(NAMES, got, want):
        bound = (2e-5 if dtype == "float32"
                 else 2.0 ** -7 if name == "y" else 2.0 ** -3)
        assert _gap(g, w) < bound, (name, _gap(g, w))


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


def test_a_mamba2_blocks_gradient_runs_the_kernels_and_no_scan_over_chunks(
        monkeypatch):
    """With the whole core's kernels engaged (interpret mode; 128 lanes,
    chunks of 128 tokens): a block's gradient holds the core's forward that
    also writes its residuals and the core's backward, and no scan at all:
    none over the groups nor over the 2 chunks."""
    real = pl.pallas_call
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    monkeypatch.setattr(pl, "pallas_call",
                        lambda *a, **kw: real(*a, **{**kw, "interpret": True}))
    B, F, G, P, N = 2, 32, 2, 64, 128
    T, chunk = 136, 128                  # a chunk of 128 tokens, the last partial
    block = DecoderBlock(n_in=F, n_out=F, attention="mamba2", ffn="none",
                         ssm_heads=4, ssm_head_dim=P, ssm_state=N,
                         ssm_groups=G, ssm_chunk=chunk)
    kernels = ("mamba_core", "mamba_core_bwd")
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
        W, n = 2 * P, -(-T // chunk)
        assert scans == []
        # y, the float32 y before the gate and the states entering each
        # chunk; then the backward's seven, its first the cotangent of W_in's
        # output whole, tokens minor
        assert calls[0] == ((B, n * chunk, G * W), (B, n * chunk, G * W),
                            (B, G, n, N, W))
        assert len(calls) == 2 and len(calls[1]) == 7
        assert calls[1][0] == (B, 2 * G * W + 2 * G * N + 4, n * chunk)
        # a forward with no backward writes y alone
        assert alone == [((B, n * chunk, G * W),)]
        # and the gradient the kernels give is the statement's
        got = jax.grad(loss, argnums=(0, 1))(p, x)
        monkeypatch.setattr(ssd, "_core_ok", lambda *a: False)
        want = jax.grad(loss, argnums=(0, 1))(p, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert _gap(np.asarray(g), np.asarray(w)) < 5e-5
