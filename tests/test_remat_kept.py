"""What a checkpointed decoder block keeps (``ops/remat.py``): the flash
core's output and log-sum-exp and an indexer's selection, by name, so that
the recomputed forward runs neither kernel a second time. The kernels run in
interpret mode on the CPU at a tiny size (256 positions, 128-square tiles);
the gates are told they see a TPU, as ``tests/test_tpu_aot_compile.py``
tells them.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import DecoderBlock
from deeplearning4j_tpu.ops import indexer, remat
from deeplearning4j_tpu.ops import pallas_kernels as pk

B, T, F, H, D = 1, 256, 32, 4, 8
COMMON = dict(n_in=F, n_out=F, n_heads=H, rope_theta=1e4, ffn="swiglu",
              ffn_hidden=64)
BLOCKS = {
    "indexer": dict(attention="gqa", n_kv_heads=2, head_dim=D,
                    output_gate=False, index_heads=3, index_dim=8,
                    index_topk=40),
    "window": dict(attention="gqa", n_kv_heads=2, head_dim=D, window=96),
    "latent": dict(attention="mla", kv_rank=16, qk_nope_dim=8, qk_rope_dim=4,
                   v_dim=D),
}


@pytest.fixture
def kernels(monkeypatch):
    """Every Pallas kernel a block reaches engages, in interpret mode."""
    real = pl.pallas_call
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    monkeypatch.setattr(pk, "_MIN_SEQ", 128)
    monkeypatch.setattr(pk, "_PBWD_MIN_SEQ", 128)
    monkeypatch.setattr(pk, "_TILE_SIZES", (128,))
    monkeypatch.setattr(indexer, "_tiles", lambda t: (128, 128))
    monkeypatch.setattr(indexer, "_SELECT_CHUNK", 128)
    monkeypatch.setattr(indexer, "_SELECT_BLOCK_BYTES", 64 * T * 4)
    monkeypatch.setattr(
        pl, "pallas_call",
        lambda *a, **kw: real(*a, **{**kw, "interpret": True}))


def _case(kind, seed=0):
    layer = DecoderBlock(**COMMON, **BLOCKS[kind])
    itype = InputType.recurrent(F, T)
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    params = [layer.init_params(k, itype) for k in keys[:2]]
    x = jax.random.normal(keys[2], (B, T, F), jnp.float32)
    return layer, layer.init_state(itype), params, x


def _loss(layer, state, wrap):
    """Two blocks, each through ``wrap``, as ``multilayer.loss_fn`` runs
    them: a sum over the output plus every block's indexer term."""
    def loss(params, x):
        h, extra = x, 0.0
        for p in params:
            h, ns = wrap(lambda p_, h_: layer.apply(p_, state, h_,
                                                    train=True))(p, h)
            extra = extra + ns.get("index_loss", 0.0)
        return jnp.sum(jnp.sin(h)) + extra
    return loss


def _pallas_calls(jaxpr, out=None):
    """The output shapes of every ``pallas_call`` in ``jaxpr``, nested
    programs included."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(tuple((v.aval.dtype.name, v.aval.shape)
                             for v in eqn.outvars))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _pallas_calls(sub, out)
    return out


def _counts(kind, wrap):
    layer, state, params, x = _case(kind)
    calls = _pallas_calls(jax.make_jaxpr(jax.grad(_loss(layer, state, wrap)))(
        params, x).jaxpr)
    # the forward core writes (out [B * H, T, Dv], lse [B * H, T, 1]); the
    # selection writes the int8 matrix first
    core = [c for c in calls if len(c) == 2 and c[0][1] == (B * H, T, D)
            and c[1][1] == (B * H, T, 1)]
    select = [c for c in calls if c[0] == ("int8", (B, T, T))]
    return len(core), len(select)


# (a) ---------------------------------------------------------------------
@pytest.mark.parametrize("kind", sorted(BLOCKS))
def test_the_recomputed_forward_runs_no_core_and_no_selection(kernels, kind):
    """Two layers: one forward core (and selection) each under the policy,
    two each under a bare ``jax.checkpoint`` (so this fails if the tags
    come off), one each where nothing is checkpointed."""
    selects = 2 if kind == "indexer" else 0
    assert _counts(kind, remat.checkpoint_layer) == (2, selects)
    assert _counts(kind, jax.checkpoint) == (4, 2 * selects)
    assert _counts(kind, lambda f: f) == (2, selects)


# (b) ---------------------------------------------------------------------
@pytest.mark.parametrize("kind", sorted(BLOCKS))
def test_loss_and_every_gradient_keep_their_bits(kernels, kind):
    layer, state, params, x = _case(kind, seed=1)

    def run(wrap):
        return jax.jit(jax.value_and_grad(_loss(layer, state, wrap),
                                          argnums=(0, 1)))(params, x)

    want, want_g = run(jax.checkpoint)
    got, got_g = run(remat.checkpoint_layer)
    assert np.isfinite(float(want)) and float(got) == float(want)
    leaves, names = jax.tree_util.tree_flatten(want_g)
    assert len(leaves) > 10 and names == jax.tree_util.tree_structure(got_g)
    for g, w in zip(jax.tree_util.tree_leaves(got_g), leaves):
        assert np.asarray(w).any()
        assert np.array_equal(np.asarray(g), np.asarray(w))


# (c) ---------------------------------------------------------------------
def test_an_indexer_block_keeps_four_names_and_no_score_matrix(kernels):
    layer, state, params, x = _case("indexer", seed=2)

    def f(p, h):
        y, ns = remat.checkpoint_layer(
            lambda p_, h_: layer.apply(p_, state, h_, train=True))(p, h)
        return jnp.sum(y) + ns["index_loss"]

    # the list behind ``jax.ad_checkpoint.print_saved_residuals``
    from jax._src.ad_checkpoint import saved_residuals

    def kept(fn):
        return sorted(
            (aval.dtype.name, aval.shape) for aval, why in saved_residuals(
                fn, params[0], x)
            if "from the argument" not in why and "constant" not in why)

    # the four names' values (a kept float passes through a
    # ``reduce_precision`` that changes no bit: JAX's barrier against the
    # compiler making it again) and nothing else: neither the float32 index
    # scores nor the loss's gradient of them, both [T, T]
    assert kept(f) == sorted([
        ("int8", (B, T, T)), ("float32", (B, T)),           # the selection
        ("float32", (B, T, H, D)), ("float32", (B * H, T))])    # the core
    assert [why for _, why in saved_residuals(f, params[0], x)
            if "named" in why][0].startswith(f"named '{remat.SELECT}'")

    # a bare checkpoint keeps the layer's arguments alone
    def bare(p, h):
        y, ns = jax.checkpoint(
            lambda p_, h_: layer.apply(p_, state, h_, train=True))(p, h)
        return jnp.sum(y) + ns["index_loss"]

    assert kept(bare) == []


# (d) ---------------------------------------------------------------------
def test_a_block_reports_the_bytes_its_shapes_give(monkeypatch):
    """Keye-VL-2.0-30B-A3B's block at one sequence of 16,384 in bfloat16:
    268 MB of selection, 134 MB of output, 2 MB of log-sum-exp; without the
    flash kernels (this CPU) the core keeps nothing."""
    layer = DecoderBlock(n_in=2048, n_out=2048, attention="gqa", n_heads=32,
                         n_kv_heads=4, head_dim=128, index_heads=16,
                         index_dim=64, index_topk=2048, ffn="swiglu",
                         ffn_hidden=64)
    select = {remat.SELECT: 16384 * 16384, remat.SELECT_LSE: 16384 * 4}
    assert layer.remat_kept_bytes(1, 16384, jnp.bfloat16) == {
        remat.CORE_OUT: 0, remat.CORE_LSE: 0, **select}
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)
    assert layer.remat_kept_bytes(1, 16384, jnp.bfloat16) == {
        remat.CORE_OUT: 16384 * 32 * 128 * 2, remat.CORE_LSE: 32 * 16384 * 4,
        **select}
    latent = DecoderBlock(**{**COMMON, **BLOCKS["latent"], "v_dim": 128})
    assert latent.remat_kept_bytes(4, 4096, jnp.bfloat16) == {
        remat.CORE_OUT: 4 * 4096 * H * 128 * 2, remat.CORE_LSE: 4 * H * 4096 * 4}
    # under the length the forward kernel engages at, nothing
    assert set(latent.remat_kept_bytes(4, 512, jnp.bfloat16).values()) == {0}


def test_the_four_names_are_the_policys():
    assert remat.KEPT == (remat.CORE_OUT, remat.CORE_LSE, remat.SELECT,
                          remat.SELECT_LSE) and len(set(remat.KEPT)) == 4
