"""A Mamba-2 mixer's whole core as Pallas kernels (``ssd.mamba_core``: the
taps, the steps, the chunked scan, the gate and the group's norm, from
``W_in``'s output to the normed ``y``) against the composition they are held
to, ``DecoderBlock._mamba_groups`` (its scan the statement, ``ssd_scan``),
in interpret mode: the forward and the gradients with respect to ``W_in``'s
output and every leaf of the core, in float32 and in bfloat16, at lengths
with a partial last chunk and with chunks shorter than the taps' reach
spans; and where ``DecoderBlock`` sends the core on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import common
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import DecoderBlock
from deeplearning4j_tpu.observability.metrics import global_registry
from deeplearning4j_tpu.observability.names import PALLAS_DISPATCH_TOTAL
from deeplearning4j_tpu.ops import ssd

#: (Bt, T, G, K heads a group, P, N, chunk, taps)
CASES = {
    # 2.5 chunks: the last one partial, every boundary inside the taps
    "ragged": (2, 40, 2, 2, 8, 16, 16, 4),
    # a last chunk of 2 tokens, fewer than the 3 the taps reach back
    "last-chunk-under-the-taps": (1, 34, 1, 2, 8, 16, 16, 4),
    # whole chunks (no padding), two taps
    "whole-chunks": (1, 48, 2, 2, 8, 16, 16, 2),
    # two slabs of two 64-wide heads a group, as Nemotron's
    "two-slabs": (1, 32, 1, 4, 64, 16, 16, 4),
    # chunks of 128 tokens, as the gate admits on a TPU; the last of 44
    "chunks-of-128": (1, 300, 1, 2, 8, 16, 128, 4),
}
LEAVES = ("conv_w", "conv_b", "dt_bias", "A_log", "D", "ssm_norm_g")
POLICY = {"float32": "float32", "bfloat16": "bfloat16_full"}


def _block(G, K, P, N, chunk, taps, F=32):
    return DecoderBlock(n_in=F, n_out=F, attention="mamba2", ffn="none",
                        ssm_heads=G * K, ssm_head_dim=P, ssm_state=N,
                        ssm_groups=G, ssm_chunk=chunk, conv_kernel=taps)


def _operands(block, Bt, T, dtype, seed=0):
    """``W_in``'s output and the core's leaves, the ones a fresh block
    holds as 0 or 1 (the taps' bias, D, the norm's scale) drawn too."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    p = block.init_params(ks[0], InputType.recurrent(block.n_in, T))
    p = {n: p[n] for n in LEAVES}
    p["conv_w"] = 0.5 * jax.random.normal(ks[1], p["conv_w"].shape)
    p["conv_b"] = 0.3 * jax.random.normal(ks[2], p["conv_b"].shape)
    p["D"] = jax.random.normal(ks[3], p["D"].shape)
    p["ssm_norm_g"] = 1 + 0.2 * jax.random.normal(ks[4], p["ssm_norm_g"].shape)
    H, G, N = block.ssm_heads, block.ssm_groups, block.ssm_state
    width = 2 * H * block.ssm_head_dim + 2 * G * N + H
    zxd = jax.random.normal(ks[5], (Bt, T, width)).astype(dtype)
    return zxd, p


def _core(block):
    return ssd.Core(block.ssm_groups, block.ssm_heads // block.ssm_groups,
                    block.ssm_head_dim, block.ssm_state, block.ssm_chunk,
                    block.conv_kernel, block.norm_eps,
                    common.get_policy().compute_dtype)


def _composition(block):
    return lambda zxd, p: block._mamba_groups(p, zxd)


def _value_and_grads(core_fn, zxd, p, probe):
    """y and the gradients of ``sum(sin(y) * probe)`` w.r.t. ``zxd`` and
    each leaf."""
    def loss(zxd, *leaves):
        y = core_fn(zxd, dict(zip(LEAVES, leaves)))
        return jnp.sum(jnp.sin(y.astype(jnp.float32)) * probe), y

    (_, y), grads = jax.value_and_grad(loss, argnums=tuple(range(7)),
                                       has_aux=True)(
        zxd, *(p[n] for n in LEAVES))
    return [np.asarray(v, np.float64) for v in (y, *grads)]


def _gap(got, want):
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize("dtype", sorted(POLICY))
@pytest.mark.parametrize("case", sorted(CASES))
def test_fused_core_matches_the_composition_forward_and_backward(case, dtype):
    Bt, T, G, K, P, N, chunk, taps = CASES[case]
    block = _block(G, K, P, N, chunk, taps)
    probe = jax.random.normal(jax.random.PRNGKey(7), (Bt, T, G * K * P))
    with common.override_policy("float32"):
        zxd, p = _operands(block, Bt, T, jnp.float32)
    with common.override_policy(POLICY[dtype]):
        zxd_d = zxd.astype(common.get_policy().output_dtype)
        kernels = _value_and_grads(lambda z, q: ssd.mamba_core(
            z, *(q[n] for n in LEAVES), _core(block), interpret=True),
            zxd_d, p, probe)
        composed = _value_and_grads(_composition(block), zxd_d, p, probe)
    # the composition's own distance from a float32 run on the same
    # (rounded) operands sets the room
    with common.override_policy("float32"):
        exact = _value_and_grads(_composition(block),
                                 zxd_d.astype(jnp.float32), p, probe)
    for name, g, c, e in zip(("y", "zxd") + LEAVES, kernels, composed,
                             exact):
        assert _gap(g, c) <= 3 * _gap(c, e) + 1e-3, (
            name, _gap(g, c), _gap(c, e))


def _engaged(kernel):
    text = global_registry().prometheus_text()
    out = {}
    for engaged in ("true", "false"):
        out[engaged] = 0.0
        for line in text.splitlines():
            if (line.startswith(PALLAS_DISPATCH_TOTAL)
                    and f'kernel="{kernel}"' in line
                    and f'engaged="{engaged}"' in line):
                out[engaged] = float(line.rsplit(" ", 1)[1])
    return out


def test_the_cpu_books_the_fallback_and_runs_the_composition():
    block = _block(1, 2, 64, 128, 16, 4)          # widths the gate admits
    with common.override_policy("float32"):
        zxd, p = _operands(block, 1, 40, jnp.float32)
        before = [_engaged(k) for k in ("mamba_core", "mamba_core_bwd")]
        y = jax.jit(lambda z, q: block._mamba_part(
            {**q, "W_in": jnp.eye(z.shape[-1]), "W_out": jnp.eye(128)},
            z))(zxd, p)
        for kernel, was in zip(("mamba_core", "mamba_core_bwd"), before):
            after = _engaged(kernel)
            assert after["false"] == was["false"] + 1
            assert after["true"] == was["true"]
        want = jax.jit(_composition(block))(zxd, p)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-6,
                               atol=1e-6)


@pytest.mark.parametrize("G, K, P, N, chunk, taps, admitted", [
    (8, 8, 64, 128, 128, 4, True),        # Nemotron-3-Nano's core
    (2, 4, 32, 128, 256, 4, True),
    (2, 2, 8, 16, 16, 4, False),          # a group of 16 lanes
    (8, 8, 64, 64, 128, 4, False),        # B and C of 64 lanes
    (8, 8, 64, 128, 64, 4, False),        # a chunk of 64 tokens
    (8, 8, 64, 128, 120, 4, False),       # a chunk of no whole halo block
    (8, 8, 64, 128, 128, 18, False),      # taps past the halo
])
def test_the_gate_reads_the_geometry(monkeypatch, G, K, P, N, chunk, taps,
                                     admitted):
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    core = ssd.Core(G, K, P, N, chunk, taps, 1e-5, jnp.bfloat16)
    zxd = jnp.zeros((1, 256, 2 * G * K * P + 2 * G * N + G * K),
                    jnp.bfloat16)
    assert ssd._core_ok(zxd, core) is admitted
