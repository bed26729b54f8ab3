"""Attention/transformer layers + pipeline parallelism equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.layers import (
    SelfAttentionLayer, TransformerBlock,
)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.parallel.mesh import build_mesh
from deeplearning4j_tpu.parallel.pipeline import (
    PipelineParallel, stack_block_params, unstack_block_params,
)


def test_self_attention_layer_causal_matches_reference():
    from deeplearning4j_tpu.parallel.ring_attention import attention_reference
    lyr = SelfAttentionLayer(n_in=16, n_out=16, n_heads=4, causal=True,
                             activation="identity")
    params = lyr.init_params(jax.random.PRNGKey(0),
                             InputType.recurrent(16, 8))
    x = jnp.asarray(np.random.default_rng(0).normal(size=(2, 8, 16)),
                    jnp.float32)
    out, _ = lyr.apply(params, {}, x)
    assert out.shape == (2, 8, 16)
    # manual recomputation through the reference attention math
    qkv = x @ params["Wqkv"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    o = attention_reference(q.reshape(2, 8, 4, 4), k.reshape(2, 8, 4, 4),
                            v.reshape(2, 8, 4, 4), causal=True)
    expect = o.reshape(2, 8, 16) @ params["Wo"] + params["b"]
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=2e-4, atol=2e-5)


def test_transformer_block_gradcheck_smoke():
    blk = TransformerBlock(n_in=8, n_out=8, n_heads=2, ffn_multiplier=2)
    params = blk.init_params(jax.random.PRNGKey(1), InputType.recurrent(8, 4))
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, 4, 8)),
                    jnp.float32)

    def loss(p):
        y, _ = blk.apply(p, {}, x)
        return jnp.sum(y ** 2)

    g = jax.grad(loss)(params)
    for k, v in g.items():
        assert np.all(np.isfinite(np.asarray(v))), k
    # central-difference numeric check on a couple of scalar params
    eps = 1e-2
    for name in ("ln1_g", "b1"):
        plus = dict(params)
        plus[name] = params[name].at[0].add(eps)
        minus = dict(params)
        minus[name] = params[name].at[0].add(-eps)
        num = (loss(plus) - loss(minus)) / (2 * eps)
        np.testing.assert_allclose(float(num), float(g[name][0]),
                                   rtol=5e-2, atol=1e-2)


def test_pipeline_matches_sequential():
    blk = TransformerBlock(n_in=8, n_out=8, n_heads=2, ffn_multiplier=2,
                           causal=True)
    n_blocks = 4
    keys = jax.random.split(jax.random.PRNGKey(2), n_blocks)
    plist = [blk.init_params(k, InputType.recurrent(8, 4)) for k in keys]
    stacked = stack_block_params(plist)
    assert len(unstack_block_params(stacked)) == n_blocks

    mesh = build_mesh({"stage": 4})
    block_fn = lambda p, x: blk.apply(p, {}, x)[0]
    pipe = PipelineParallel(mesh, block_fn, n_blocks, n_microbatches=4)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(8, 4, 8)),
                    jnp.float32)
    got = pipe(stacked, x)
    expect = pipe.reference_forward(stacked, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-4, atol=2e-5)


def test_pipeline_multiple_blocks_per_stage():
    blk = TransformerBlock(n_in=8, n_out=8, n_heads=2, ffn_multiplier=2)
    n_blocks = 8
    keys = jax.random.split(jax.random.PRNGKey(3), n_blocks)
    stacked = stack_block_params(
        [blk.init_params(k, InputType.recurrent(8, 4)) for k in keys])
    mesh = build_mesh({"stage": 4})
    block_fn = lambda p, x: blk.apply(p, {}, x)[0]
    pipe = PipelineParallel(mesh, block_fn, n_blocks, n_microbatches=2)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(4, 4, 8)),
                    jnp.float32)
    np.testing.assert_allclose(np.asarray(pipe(stacked, x)),
                               np.asarray(pipe.reference_forward(stacked, x)),
                               rtol=2e-4, atol=2e-5)


def test_pipeline_is_differentiable():
    blk = TransformerBlock(n_in=8, n_out=8, n_heads=2, ffn_multiplier=2)
    n_blocks = 4
    keys = jax.random.split(jax.random.PRNGKey(4), n_blocks)
    stacked = stack_block_params(
        [blk.init_params(k, InputType.recurrent(8, 4)) for k in keys])
    mesh = build_mesh({"stage": 4})
    block_fn = lambda p, x: blk.apply(p, {}, x)[0]
    pipe = PipelineParallel(mesh, block_fn, n_blocks, n_microbatches=4)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(4, 4, 8)),
                    jnp.float32)

    def loss_pipe(p):
        return jnp.sum(pipe(p, x) ** 2)

    def loss_seq(p):
        return jnp.sum(pipe.reference_forward(p, x) ** 2)

    gp = jax.grad(loss_pipe)(stacked)
    gs = jax.grad(loss_seq)(stacked)
    for k in gs:
        np.testing.assert_allclose(np.asarray(gp[k]), np.asarray(gs[k]),
                                   rtol=5e-3, atol=5e-4)


def test_transformer_lm_end_to_end():
    from deeplearning4j_tpu.models.transformer import transformer_lm
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    conf = transformer_lm(vocab_size=12, width=16, n_layers=2, n_heads=2,
                          max_len=8)
    net = MultiLayerNetwork(conf).init()
    rng = np.random.default_rng(5)
    # learnable task: next token = current token (shifted identity)
    ids = np.tile(np.arange(8) % 12, (16, 1))
    x = np.eye(12, dtype=np.float32)[ids]
    first = None
    for i in range(15):
        net.fit(x, x)
        if first is None:
            first = net.score_value
    assert net.score_value < first
    # config serde round trip includes the new layer types
    from deeplearning4j_tpu.nn.conf.multilayer import MultiLayerConfiguration
    back = MultiLayerConfiguration.from_json(conf.to_json())
    assert type(back.layers[1]).__name__ == "TransformerBlock"


def test_self_attention_mask_excludes_padded_keys():
    from deeplearning4j_tpu.ops.pallas_kernels import masked_attention
    from deeplearning4j_tpu.parallel.ring_attention import attention_reference
    lyr = SelfAttentionLayer(n_in=8, n_out=8, n_heads=2, causal=False,
                             activation="identity")
    params = lyr.init_params(jax.random.PRNGKey(7),
                             InputType.recurrent(8, 6))
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.normal(size=(1, 6, 8)), jnp.float32)
    mask = jnp.asarray([[1, 1, 1, 1, 0, 0]], jnp.float32)
    out_m, _ = lyr.apply(params, {}, x, mask=mask)
    # oracle: run unmasked attention on the truncated (real-only) sequence
    out_trunc, _ = lyr.apply(params, {}, x[:, :4])
    np.testing.assert_allclose(np.asarray(out_m[:, :4]),
                               np.asarray(out_trunc), rtol=1e-4, atol=1e-5)
    # direct masked_attention helper agrees with truncation too
    q = jnp.asarray(rng.normal(size=(1, 6, 2, 4)), jnp.float32)
    got = masked_attention(q, q, q, mask)
    ref = attention_reference(q[:, :4], q[:, :4], q[:, :4])
    np.testing.assert_allclose(np.asarray(got[:, :4]), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_embedding_int_ids_not_mistaken_for_onehot():
    from deeplearning4j_tpu.nn.conf.layers import EmbeddingLayer
    lyr = EmbeddingLayer(n_in=4, n_out=3)
    params = lyr.init_params(jax.random.PRNGKey(0), InputType.feed_forward(4))
    ids = jnp.asarray([[0, 3, 2, 1]], jnp.int32)  # T == n_in collision
    out, _ = lyr.apply(params, {}, ids)
    expect = params["W"][jnp.asarray([0, 3, 2, 1])] + params["b"]
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(expect))


@pytest.mark.parametrize("kv_heads,window", [(4, 6), (2, None), (1, 5)])
def test_attend_passes_a_window_and_grouped_heads_to_the_core(kv_heads,
                                                               window):
    """``attend`` hands a window and fewer key/value heads to the
    single-device core (the XLA math on the CPU) and refuses them, by name,
    under a key mask."""
    from deeplearning4j_tpu.nn.conf.layers.attention import attend
    from deeplearning4j_tpu.parallel.ring_attention import attention_reference

    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, 16, 4, 8))
    k, v = (jax.random.normal(kk, (2, 16, kv_heads, 8)) for kk in ks[1:])
    got = attend(q, k, v, True, window=window)
    rep = 4 // kv_heads
    want = attention_reference(q, jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2),
                               True, None, window)
    np.testing.assert_allclose(got, want, atol=1e-5)
    if window is not None:
        # position 15 sees the last `window` keys only
        far = k.at[:, :16 - window].set(100.0)
        np.testing.assert_allclose(attend(q, far, v, True, window=window)[:, -1],
                                   got[:, -1], atol=1e-5)
    with pytest.raises(NotImplementedError, match="window.*fewer key/value"):
        attend(q, k, v, True, mask=jnp.ones((2, 16)), window=window)
