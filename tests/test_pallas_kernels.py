"""Pallas kernels == XLA reference math, in interpret mode on CPU (the
reference's backend-equivalence pattern: CuDNNGradientChecks compares the
accelerated helper path against the built-in path)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops.pallas_kernels import (
    _attention_xla, flash_attention, softmax_cross_entropy,
)
from deeplearning4j_tpu.parallel.ring_attention import attention_reference


def _qkv(B=2, T=128, H=4, D=32, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(B, T, H, D)).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_reference(causal):
    q, k, v = _qkv()
    expect = attention_reference(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal, True)  # interpret mode
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-4, atol=2e-5)


def test_flash_attention_gradient_flows():
    q, k, v = _qkv(B=1, T=64, H=2, D=16, seed=2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


def test_flash_attention_rejects_ragged_blocks():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 130, 2, 16)).astype(np.float32))
    from deeplearning4j_tpu.ops.pallas_kernels import _flash_forward

    with pytest.raises(ValueError):
        _flash_forward(q, q, q, False)


def test_softmax_xent_matches_xla():
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(256, 10)).astype(np.float32))
    labels_idx = rng.integers(0, 10, 256)
    labels = jnp.asarray(np.eye(10, dtype=np.float32)[labels_idx])
    loss_p, grad_p = softmax_cross_entropy(logits, labels, interpret=True)
    # XLA reference
    logp = jax.nn.log_softmax(logits, axis=-1)
    loss_x = -jnp.sum(labels * logp, axis=-1)
    grad_x = jnp.exp(logp) - labels
    np.testing.assert_allclose(np.asarray(loss_p), np.asarray(loss_x),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(grad_p), np.asarray(grad_x),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("T", [64, 130])  # 130: not a block multiple
def test_chunked_backward_matches_reference(causal, T):
    q, k, v = _qkv(B=1, T=T, H=2, D=16, seed=3)
    g = jnp.ones_like(q)
    from deeplearning4j_tpu.ops.pallas_kernels import _attention_bwd_chunked
    got = _attention_bwd_chunked(q, k, v, g, causal, blk_q=32)
    _, vjp = jax.vjp(lambda a, b, c: attention_reference(a, b, c, causal),
                     q, k, v)
    expect = vjp(g)
    for a, b in zip(got, expect):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


def test_flash_attention_non_tileable_falls_back():
    # Public entry must not error on ragged sequence lengths even when the
    # pallas path is selected (interpret=True routes it): T=130 falls back.
    q, k, v = _qkv(B=1, T=130, H=2, D=16, seed=4)
    got = flash_attention(q, k, v, False, True)
    expect = attention_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_backward_matches_reference(causal):
    """The tiled pallas backward (dQ + dK/dV kernels from the saved forward
    logsumexp) must match autodiff of the reference math, with multiple
    q- and k-blocks in flight (blk 32 over T=128 -> 4x4 block grid)."""
    from deeplearning4j_tpu.ops.pallas_kernels import (
        _flash_backward, _flash_forward)
    q, k, v = _qkv(B=2, T=128, H=2, D=32, seed=5)
    rng = np.random.default_rng(6)
    g = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))
    out, lse = _flash_forward(q, k, v, causal, blk_q=32, blk_k=32,
                              interpret=True)
    got = _flash_backward(q, k, v, out, lse, g, causal, blk_q=32, blk_k=32,
                          interpret=True)
    _, vjp = jax.vjp(lambda a, b, c: attention_reference(a, b, c, causal),
                     q, k, v)
    expect = vjp(g)
    for name, a, b in zip(("dq", "dk", "dv"), got, expect):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5, err_msg=name)


def test_pallas_backward_cross_attention_lengths():
    """Tq != Tk (cross-attention shapes) through the pallas backward."""
    from deeplearning4j_tpu.ops.pallas_kernels import (
        _flash_backward, _flash_forward)
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(1, 64, 2, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(1, 128, 2, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(1, 128, 2, 16)).astype(np.float32))
    g = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))
    out, lse = _flash_forward(q, k, v, False, blk_q=32, blk_k=32,
                              interpret=True)
    got = _flash_backward(q, k, v, out, lse, g, False, blk_q=32, blk_k=32,
                          interpret=True)
    _, vjp = jax.vjp(lambda a, b, c: attention_reference(a, b, c, False),
                     q, k, v)
    expect = vjp(g)
    for name, a, b in zip(("dq", "dk", "dv"), got, expect):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5, err_msg=name)


def _latent_operands(T, seed, B=2, H=2, Dk=24, Dv=16):
    """q, k (Dk wide), v and a cotangent (Dv wide): latent attention's
    192/128 scaled down by 8."""
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(B, T, H, d)).astype(np.float32))
                 for d in (Dk, Dk, Dv, Dv))


def _tail_mask(B, T):
    """A padded tail in the first sequence, every key of the last masked."""
    mask = np.ones((B, T), np.float32)
    mask[0, T - T // 3:] = 0.0
    mask[-1, :] = 0.0
    return jnp.asarray(mask)


def _causal_plan(plan, T, seed, B=2, scale=None):
    """A causal core under ``plan``, 4 query heads over 2 key/value heads:
    ``grouped`` alone, a 40-key ``window`` over them, or a random
    ``select``ion of keys that holds each query's own -> (q, k, v, g, the
    kernels' keywords, the plain math's forward)."""
    from deeplearning4j_tpu.ops.pallas_kernels import _selected_attention_xla

    q, k, v, g = _latent_operands(T, seed, B=B, H=4)
    k, v = k[:, :, ::2], v[:, :, ::2]
    if plan == "select":
        rng = np.random.default_rng(seed)
        pick = np.tril(rng.random((B, T, T)) < 0.3) | np.eye(T, dtype=bool)
        select = jnp.asarray(pick.astype(np.int8))
        return q, k, v, g, {"select": select}, (
            lambda a, b, c: _selected_attention_xla(a, b, c, select,
                                                    scale)[0])
    window = 40 if plan == "window" else None
    return q, k, v, g, {"window": window}, (
        lambda a, b, c: _masked_f32(a, b, c, window, scale))


def _tiles_id(tiles):
    return f"q{tiles[0]}k{tiles[1]}"


_FUSED_TILES = ((64, 32), (32, 32), (32, 64))


@pytest.mark.parametrize("causal,key_mask,plan,tiles", [
    pytest.param(c, m, "plain", t, id="-".join((
        "causal" if c else "full", "keymask" if m else "nomask",
        _tiles_id(t))))
    for c in (False, True) for m in (False, True) for t in _FUSED_TILES] + [
    pytest.param(True, False, p, t, id=f"causal-{p}-{_tiles_id(t)}")
    for p in ("grouped", "window", "select") for t in _FUSED_TILES])
def test_fused_backward_matches_pair_and_reference(causal, key_mask, plan,
                                                   tiles):
    """ONE backward kernel (dQ for the whole head in VMEM beside dK/dV's
    accumulators) gives the dQ + dK/dV pair's gradients bit for bit and
    what the plain math gives (`_attention_bwd_chunked`; under a key mask
    the XLA masked attention's gradient; grouped heads, a window or a
    selection against their own statements), at Dk != Dv, with the query
    tile larger than, equal to and smaller than the key tile. A causal one
    kernel writes dQ one key block at a time, 2 or 4 of them a head here:
    a block written before its last tile would differ from the pair's."""
    from deeplearning4j_tpu.ops.pallas_kernels import (
        _attention_bwd_chunked, _flash_backward, _flash_forward,
        _masked_attention_xla)
    T, scale = 128, 24 ** -0.5
    if plan == "plain":
        q, k, v, g = _latent_operands(T, seed=11)
        km = _tail_mask(q.shape[0], T) if key_mask else None
        kw = {"key_mask": km}
    else:
        q, k, v, g, kw, ref = _causal_plan(plan, T, seed=11)
    bq, bk = tiles
    out, lse = _flash_forward(q, k, v, causal, blk_q=bq, blk_k=bk,
                              interpret=True, **kw)
    fused, pair = (
        _flash_backward(q, k, v, out, lse, g, causal, blk_q=bq, blk_k=bk,
                        interpret=True, fused=f, **kw)
        for f in (True, False))
    if plan != "plain":
        expect = jax.vjp(ref, q, k, v)[1](g)
    elif key_mask:
        _, vjp = jax.vjp(
            lambda a, b, c: _masked_attention_xla(a, b, c, km, causal),
            q, k, v)
        expect = vjp(g)
    else:
        expect = _attention_bwd_chunked(q, k, v, g, causal, blk_q=32,
                                        scale=scale)
    for name, a, b, c in zip(("dq", "dk", "dv"), fused, pair, expect):
        assert np.all(np.isfinite(np.asarray(a))), name
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=5e-4, atol=5e-5, err_msg=name)


_OFFSET_TILES = ((48, 32), (32, 48), (16, 96), (96, 16), (24, 16))


@pytest.mark.parametrize("plan,tiles", [
    pytest.param("plain", t, id=_tiles_id(t)) for t in _OFFSET_TILES] + [
    pytest.param(p, t, id=f"{p}-{_tiles_id(t)}")
    for p in ("grouped", "window", "select")
    for t in ((48, 32), (32, 48), (24, 16))])
def test_diagonal_crosses_tiles_at_every_offset(plan, tiles):
    """Only a tile the diagonal crosses is masked, a tile wholly below it
    takes the body without the mask and a dead one is skipped: with a query
    tile that is no multiple of the key tile (and the reverse) the diagonal
    enters tiles at every offset, and forward and both backwards still give
    the masked reference's result, with grouped heads, a window or a
    selection too; the one kernel, which writes dQ a key block at a time
    (up to 6 a head here), gives the pair's gradients bit for bit."""
    from deeplearning4j_tpu.ops.pallas_kernels import (
        _causal_block_crossed, _causal_block_live, _flash_backward,
        _flash_forward)
    T, scale = 96, 0.17
    if plan == "plain":
        q, k, v, g = _latent_operands(T, seed=12, B=1)
        kw = {}
        forward = lambda a, b, c: attention_reference(a, b, c, True, scale)
    else:
        q, k, v, g, kw, forward = _causal_plan(plan, T, seed=12, B=1,
                                               scale=scale)
    bq, bk = tiles
    # the predicates against the positions they stand for
    for qi in range(T // bq):
        for kj in range(T // bk):
            qs, ks = range(qi * bq, (qi + 1) * bq), range(kj * bk,
                                                          (kj + 1) * bk)
            assert _causal_block_live(qi, kj, bq, bk) == (ks[0] <= qs[-1])
            assert _causal_block_crossed(qi, kj, bq, bk) == (ks[-1] > qs[0])
    out, lse = _flash_forward(q, k, v, True, blk_q=bq, blk_k=bk,
                              interpret=True, scale=scale, **kw)
    ref, vjp = jax.vjp(forward, q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)
    grads = []
    for fused in (True, False):
        got = _flash_backward(q, k, v, out, lse, g, True, blk_q=bq, blk_k=bk,
                              interpret=True, scale=scale, fused=fused, **kw)
        grads.append(got)
        for name, a, b in zip(("dq", "dk", "dv"), got, vjp(g)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=5e-4, atol=5e-5,
                err_msg=f"{name} fused={fused}")
    for name, a, b in zip(("dq", "dk", "dv"), *grads):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


@pytest.mark.parametrize("key_mask", [False, True], ids=["nomask", "keymask"])
def test_square_diagonal_tile_runs_as_three_quarters(key_mask, monkeypatch):
    """From `_QUARTERED_FROM` rows a square tile on the diagonal is computed
    as its two masked quarters and the plain one below them; the dead
    quarter is left out. Forward and both backwards give what the whole
    masked tile gives, and the reference."""
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    T = 128
    q, k, v, g = _latent_operands(T, seed=15)
    km = _tail_mask(q.shape[0], T) if key_mask else None
    masks = []
    real = pk._causal_mask
    monkeypatch.setattr(pk, "_causal_mask",
                        lambda *a, **kw: masks.append(1) or real(*a, **kw))

    def run():
        out, lse = pk._flash_forward(q, k, v, True, blk_q=64, blk_k=64,
                                     interpret=True, key_mask=km)
        grads = [pk._flash_backward(q, k, v, out, lse, g, True, blk_q=64,
                                    blk_k=64, interpret=True, key_mask=km,
                                    fused=f) for f in (True, False)]
        return [out, *grads[0], *grads[1]]

    whole = run()
    assert len(masks) == 4          # forward, fused, dQ, dK/dV: one each
    monkeypatch.setattr(pk, "_QUARTERED_FROM", 64)
    del masks[:]
    quartered = run()
    assert len(masks) == 8          # two masked quarters a kernel
    for a, b in zip(quartered, whole):
        assert np.all(np.isfinite(np.asarray(a)))
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    if not key_mask:
        ref, vjp = jax.vjp(
            lambda a, b, c: attention_reference(a, b, c, True), q, k, v)
        for a, b in zip(quartered[:4], (ref, *vjp(g))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=5e-4, atol=5e-5)


def test_tiles_and_backward_follow_the_shape():
    """No switch chooses the tiles or the backward: the operands' shape
    does. The language-model cells' cores (4,096 x 192/128, 8,192 x 128,
    16,384 x 128 and 32,768 x 64, bfloat16, causal) take 1,024-square tiles
    and the one-kernel backward, which writes a causal head's dQ one key
    block at a time; so do 16,384 x 192/128 and 32,768 x 128. At 32,768 x
    192/128 and 65,536 x 64 the one kernel's program passes what a kernel
    may ask for and the pair stays, as it does without the causal mask from
    16,384 x 192/128, where a head's whole dQ is written at once; lengths
    that a tile does not divide keep a smaller standard one."""
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    bf16 = jnp.bfloat16
    for backward in (False, True):
        assert pk._flash_tiles(4096, 4096, 192, 128, bf16,
                               backward=backward) == (1024, 1024)
    assert pk._flash_tiles(32768, 32768, 64, 64, bf16,
                           backward=True) == (1024, 1024)
    for causal in (False, True):
        assert pk._fused_bwd_fits(4096, 4096, 192, 128, bf16, causal)
        assert pk._fused_bwd_fits(4096, 4096, 64, 64, jnp.float32, causal)
        assert pk._fused_bwd_fits(8192, 8192, 128, 128, bf16, causal)
        assert pk._fused_bwd_fits(16384, 16384, 64, 64, bf16, causal)
        assert pk._fused_bwd_fits(16384, 16384, 128, 128, bf16, causal)
        for t, dk, dv in ((16384, 192, 128), (32768, 64, 64),
                          (32768, 128, 128)):
            assert pk._fused_bwd_fits(t, t, dk, dv, bf16, causal) == causal
        assert not pk._fused_bwd_fits(32768, 32768, 192, 128, bf16, causal)
        assert not pk._fused_bwd_fits(65536, 65536, 64, 64, bf16, causal)
    # explicit tiles are counted as given: a 128-row query tile leaves room
    # for a head's whole dQ where the 1,024-square one does not
    assert pk._fused_bwd_fits(16384, 16384, 192, 128, bf16, False, 128, 512)
    # a causal mask over unequal lengths leaves rows final only at the end
    assert not pk._dq_by_key_block(True, 4096, 8192)
    assert pk._dq_by_key_block(True, 8192, 8192)
    assert not pk._dq_by_key_block(False, 8192, 8192)
    # the widest tile that divides, down to one a sequence: measured
    # faster than four a side at T = 1,024 and 2,048 (PERF.md §6, PR 31)
    assert pk._flash_tiles(1024, 1024, 64, 64, bf16) == (1024, 1024)
    assert pk._flash_tiles(2048, 2048, 64, 64, bf16,
                           backward=True) == (1024, 1024)
    # float32 operands: the 1,024-square backward passes the ceiling
    assert pk._flash_tiles(4096, 4096, 192, 128, jnp.float32,
                           backward=True) == (512, 512)
    assert pk._flash_tiles(1280, 3200, 64, 64, bf16) == (256, 128)
    assert pk._flash_tiles(64, 2048, 64, 64, bf16) == (64, 1024)
    assert pk._flash_tiles(2048, 1000, 64, 64, bf16) is None
    # an explicit size is taken as given, capped at the sequence
    assert pk._flash_tiles(4096, 256, 64, 64, bf16, 128, 512) == (
        128, 256)
    # the count grows with the tile and with a head's dQ: the float32
    # accumulator over Tq rows, and the double-buffered output block over
    # Tq rows, or over blk_k where a causal head's dQ leaves a key block at
    # a time (lanes: 192 pads to 256)
    small = pk._flash_vmem_bytes(128, 512, 192, 128, 2, True)
    assert small < pk._flash_vmem_bytes(512, 512, 192, 128, 2, True)
    assert pk._flash_vmem_bytes(512, 512, 192, 128, 2, True, dq_rows=4096) \
        == pk._flash_vmem_bytes(512, 512, 192, 128, 2, True) \
        + (4096 - 512) * 256 * 8
    assert pk._flash_vmem_bytes(512, 512, 192, 128, 2, True, dq_rows=4096,
                                dq_out_rows=512) \
        == pk._flash_vmem_bytes(512, 512, 192, 128, 2, True) \
        + (4096 - 512) * 256 * 4
    # LFM2's core: 24 MiB of tiles, 16 of accumulator, half of output block
    assert pk._flash_vmem_bytes(1024, 1024, 64, 64, 2, True, dq_rows=32768,
                                dq_out_rows=1024) == 40.5 * 2 ** 20


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("widths", [(64, 64), (128, 128), (192, 128),
                                    (256, 256)], ids=str)
def test_one_kernel_backward_plans_within_tiles_plus_dq(widths, dtype):
    """``_VMEM_CEILING`` holds the tiles alone; whatever shape takes the
    one-kernel backward, causal or not, plans for at most ``_VMEM_CEILING +
    _VMEM_BUDGET`` with a head's whole dQ accumulator and its output block,
    and asks the compiler for under half a core's 128 MiB."""
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    dk, dv = widths
    itemsize = jnp.dtype(dtype).itemsize
    for causal in (False, True):
        fused = 0
        for t in (1024, 1280, 2048, 4096, 8192, 16384, 32768):
            bq, bk = pk._flash_tiles(t, t, dk, dv, dtype, backward=True)
            tiles = pk._flash_vmem_bytes(bq, bk, dk, dv, itemsize, True)
            assert tiles <= pk._VMEM_CEILING
            if pk._fused_bwd_fits(t, t, dk, dv, dtype, causal):
                fused += 1
                need = pk._flash_vmem_bytes(
                    bq, bk, dk, dv, itemsize, True, dq_rows=t,
                    dq_out_rows=bk if causal else None)
                limit = pk._flash_params(("parallel",),
                                         need).vmem_limit_bytes
                assert limit is None or need < limit <= 64 << 20
        assert fused  # some length of every width takes the one kernel


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("widths", [(64, 64), (128, 128), (192, 128),
                                    (256, 256)], ids=str)
@pytest.mark.parametrize("t", [4096, 8192, 16384, 32768])
def test_one_kernel_gate_is_its_whole_programs_count(t, widths, dtype):
    """The gate stated as the invariant: a shape takes the one-kernel
    backward exactly where its program at the backward's tiles, a head's
    whole dQ accumulator and dQ's output block included (a key block's rows
    under a causal mask, the whole head's without), counts within
    ``_VMEM_CEILING + _VMEM_BUDGET``."""
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    dk, dv = widths
    bq, bk = pk._flash_tiles(t, t, dk, dv, dtype, backward=True)
    for causal in (False, True):
        need = pk._flash_vmem_bytes(
            bq, bk, dk, dv, jnp.dtype(dtype).itemsize, True, dq_rows=t,
            dq_out_rows=bk if causal else None)
        assert pk._fused_bwd_fits(t, t, dk, dv, dtype, causal) == (
            need <= pk._VMEM_CEILING + pk._VMEM_BUDGET)


def _dispatch_counts():
    from deeplearning4j_tpu.observability.metrics import global_registry
    snap = global_registry().snapshot().get(
        "dl4j_pallas_dispatch_total", {"series": []})
    return {(s["labels"]["kernel"], s["labels"]["engaged"]): s["value"]
            for s in snap["series"]}


def _count_delta(before, kernel):
    now = _dispatch_counts()
    return tuple(now.get((kernel, e), 0) - before.get((kernel, e), 0)
                 for e in ("true", "false"))


@pytest.mark.parametrize("fits", [True, False], ids=["fused", "pair"])
def test_traced_gradient_notes_which_backward_it_holds(fits, monkeypatch):
    """A traced gradient of flash_attention notes
    `flash_attention_bwd_fused` engaged or not beside `flash_attention_bwd`:
    a run says which backward its program holds."""
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    if not fits:     # a gate that refuses every shape
        monkeypatch.setattr(pk, "_fused_bwd_fits", lambda *a, **kw: False)
    q, k, v, g = _latent_operands(64, seed=13, B=1)
    before = _dispatch_counts()
    grads = jax.grad(lambda a, b, c: jnp.sum(
        pk.flash_attention(a, b, c, True, True, True) * g), argnums=(0, 1, 2))(
            q, k, v)
    assert _count_delta(before, "flash_attention_bwd") == (1, 0)
    assert _count_delta(before, "flash_attention_bwd_fused") == (
        (1, 0) if fits else (0, 1))
    _, vjp = jax.vjp(lambda a, b, c: attention_reference(a, b, c, True),
                     q, k, v)
    for a, b in zip(grads, vjp(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5)


def test_store_replays_the_backward_a_loaded_program_holds():
    """A program loaded from the executable store is never traced: the
    store replays the notes of the trace it serialized, the fused backward's
    among them, so a warm process counts what it runs."""
    from deeplearning4j_tpu.nn import compile_cache as cc
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    q, k, v, g = _latent_operands(64, seed=14, B=1)

    def grad_fn(a, b, c):
        return jax.grad(lambda *t: jnp.sum(
            pk.flash_attention(*t, True, True, True) * g))(a, b, c)

    outs = []
    for round_ in ("cold", "warm"):
        before = _dispatch_counts()
        prog = cc.build_program("flash_grad", jax.jit(grad_fn))
        outs.append(np.asarray(prog(q, k, v)))
        assert prog.cache_hit is (round_ == "warm"), round_
        for kernel in ("flash_attention", "flash_attention_bwd",
                       "flash_attention_bwd_fused"):
            assert _count_delta(before, kernel) == (1, 0), (round_, kernel)
    np.testing.assert_array_equal(outs[0], outs[1])


@pytest.mark.parametrize("causal", [False, True])
def test_masked_attention_pallas_matches_xla(causal):
    """masked_attention's tiled pallas path (interpret=True) == the XLA
    reference math, forward and gradients, including fully-masked rows."""
    from deeplearning4j_tpu.ops.pallas_kernels import (
        _masked_attention_xla, masked_attention)
    q, k, v = _qkv(B=2, T=64, H=2, D=16, seed=8)
    rng = np.random.default_rng(9)
    mask = np.ones((2, 64), np.float32)
    mask[0, 40:] = 0.0           # padded tail
    mask[1, :] = 0.0             # one sequence fully masked
    mask = jnp.asarray(mask)

    expect = _masked_attention_xla(q, k, v, mask, causal)
    got = masked_attention(q, k, v, mask, causal, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect),
                               rtol=2e-4, atol=2e-5)

    g = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))

    def loss_p(q, k, v):
        return jnp.sum(masked_attention(q, k, v, mask, causal, True) * g)

    def loss_x(q, k, v):
        return jnp.sum(_masked_attention_xla(q, k, v, mask, causal) * g)

    gp = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss_x, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), gp, gx):
        assert np.all(np.isfinite(np.asarray(a))), name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-4, atol=5e-5, err_msg=name)


def test_fused_xent_loss_path_matches_xla():
    """mcxent through the fused Pallas softmax-xent custom_vjp (asked for via
    DL4J_XENT_INTERPRET=1, interpret on CPU) must match the XLA autodiff path in
    value AND gradient, including masked time-series input — this is the
    production wiring of ops/pallas_kernels.softmax_cross_entropy."""
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.ops import losses

    rng = np.random.default_rng(0)
    cases = [
        (rng.normal(size=(8, 5)).astype(np.float32),
         np.eye(5, dtype=np.float32)[rng.integers(0, 5, 8)], None),
        # integer one-hot labels: the fused path must cast, not crash
        (rng.normal(size=(8, 5)).astype(np.float32),
         np.eye(5, dtype=np.int32)[rng.integers(0, 5, 8)], None),
        (rng.normal(size=(4, 6, 3)).astype(np.float32),
         np.eye(3, dtype=np.float32)[rng.integers(0, 3, (4, 6))],
         (rng.uniform(size=(4, 6)) > 0.3).astype(np.float32)),
    ]
    act = jax.nn.softmax
    for preout, labels, mask in cases:
        preout, labels = jnp.asarray(preout), jnp.asarray(labels)
        m = jnp.asarray(mask) if mask is not None else None

        def run():
            f = lambda p: losses.mcxent(labels, p, act, m)
            return float(f(preout)), np.asarray(jax.grad(f)(preout))

        try:
            os.environ["DL4J_FUSED_XENT"] = "0"
            v_xla, g_xla = run()
            del os.environ["DL4J_FUSED_XENT"]
            os.environ["DL4J_XENT_INTERPRET"] = "1"
            v_fused, g_fused = run()
        finally:
            os.environ.pop("DL4J_FUSED_XENT", None)
            os.environ.pop("DL4J_XENT_INTERPRET", None)
        assert abs(v_xla - v_fused) < 1e-5, (v_xla, v_fused)
        np.testing.assert_allclose(g_fused, g_xla, rtol=1e-4, atol=1e-6)


def test_fused_xent_falls_back_under_shard_map():
    """Inside a shard_map trace the fused kernel must yield to the XLA math
    (the vma checker rejects the pallas_call there — this crashed
    ParallelWrapper local-SGD until round 4). Forced engagement + an
    explicit shard_map reproduce the original failure path."""
    import os

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    from deeplearning4j_tpu.ops import losses
    from deeplearning4j_tpu.parallel.mesh import build_mesh

    mesh = build_mesh({"data": 8})
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(16, 5)).astype(np.float32))
    y = jnp.asarray(np.eye(5, dtype=np.float32)[rng.integers(0, 5, 16)])

    def local_loss(xx, yy):
        return losses.mcxent(yy, xx, jax.nn.softmax)[None]

    try:
        os.environ["DL4J_XENT_INTERPRET"] = "1"
        per_shard = jax.jit(shard_map(
            local_loss, mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=P("data")))(x, y)
        del os.environ["DL4J_XENT_INTERPRET"]
        os.environ["DL4J_FUSED_XENT"] = "0"
        expect = jax.jit(shard_map(
            local_loss, mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=P("data")))(x, y)
    finally:
        os.environ.pop("DL4J_FUSED_XENT", None)
        os.environ.pop("DL4J_XENT_INTERPRET", None)
    np.testing.assert_allclose(np.asarray(per_shard), np.asarray(expect),
                               rtol=1e-5)


def test_flash_attention_falls_back_under_checked_shard_map():
    """flash_attention inside a check_vma=True shard_map must fall back to
    the XLA math (same crash class as the xent kernel); inside ulysses'
    check_vma=False shard_map the pallas kernel still engages (covered by
    test_ulysses_pallas_interpret_matches_reference)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from deeplearning4j_tpu.ops import pallas_kernels as pk
    from deeplearning4j_tpu.parallel.mesh import build_mesh

    mesh = build_mesh({"data": 4})
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(4, 16, 2, 8)).astype(np.float32))
               for _ in range(3))

    def local(qq, kk, vv):
        # interpret=True would normally force the pallas path; the vma guard
        # must override it here
        return pk.flash_attention(qq, kk, vv, True, interpret=True)

    got = jax.jit(shard_map(local, mesh=mesh,
                            in_specs=(P("data"), P("data"), P("data")),
                            out_specs=P("data")))(q, k, v)
    want = pk._attention_xla(q, k, v, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_fused_xent_integrations_bf16_and_lbfgs():
    """Force-engaged fused xent must train under the bfloat16_full policy
    and through the LBFGS solver path (integration seams where the
    custom_vjp meets dtype policies and jitted while_loop optimizers)."""
    import os

    import numpy as np

    from deeplearning4j_tpu import NeuralNetConfiguration, MultiLayerNetwork
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer

    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 6)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 32)]
    try:
        os.environ["DL4J_XENT_INTERPRET"] = "1"
        conf = (NeuralNetConfiguration.builder().seed(0).learning_rate(0.1)
                .dtype("bfloat16_full")
                .list()
                .layer(DenseLayer(n_in=6, n_out=16, activation="tanh"))
                .layer(OutputLayer(n_in=16, n_out=3, loss="mcxent",
                                   activation="softmax"))
                .build())
        net = MultiLayerNetwork(conf).init()
        net.fit(x, y)
        s0 = net.score_value
        for _ in range(20):
            net.fit(x, y)
        assert net.score_value < s0

        conf2 = (NeuralNetConfiguration.builder().seed(1).learning_rate(0.5)
                 .optimization_algo("lbfgs")
                 .list()
                 .layer(DenseLayer(n_in=6, n_out=16, activation="tanh"))
                 .layer(OutputLayer(n_in=16, n_out=3, loss="mcxent",
                                    activation="softmax"))
                 .build())
        net2 = MultiLayerNetwork(conf2).init()
        net2.fit(x, y)
        s0 = net2.score_value
        for _ in range(5):
            net2.fit(x, y)
        assert net2.score_value <= s0
    finally:
        os.environ.pop("DL4J_XENT_INTERPRET", None)


def test_pick_blk_divisor_fallback():
    """The tiles prefer 512 rows; _pick_blk must fall back to smaller
    standard tiles for 128-divisible-but-not-512-divisible lengths instead
    of silently dropping to the O(T^2) XLA path."""
    from deeplearning4j_tpu.ops.pallas_kernels import _pick_blk, _tileable

    assert _pick_blk(2048, 512) == 512
    assert _pick_blk(1280, 512) == 256
    assert _pick_blk(3200, 512) == 128
    assert _pick_blk(1000, 512) is None       # not 128-divisible
    assert _pick_blk(64, 512) == 64           # short seq: one block
    assert _tileable(1280, 3200)
    assert not _tileable(2048, 1000)


def test_min_seq_gates_pallas_dispatch(monkeypatch):
    """Production dispatch engages the flash kernel only at/above
    DL4J_FLASH_MIN_SEQ (short sequences measured faster on the fused XLA
    path in-model); interpret mode bypasses the gate so CPU tests keep
    exercising the kernel."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.ops import pallas_kernels as pk

    q = jnp.zeros((1, 256, 2, 8), jnp.float32)
    qlong = jnp.zeros((1, 2048, 2, 8), jnp.float32)
    monkeypatch.setattr(pk, "use_pallas", lambda: True)
    assert not pk._pallas_ok(q, q, interpret=False)       # 256 < 1024
    assert pk._pallas_ok(qlong, qlong, interpret=False)   # 2048 >= 1024
    assert pk._pallas_ok(q, q, interpret=True)            # tests bypass

    # the tiled backward has its own, higher threshold
    assert not pk._pallas_bwd_enabled(2048)
    assert pk._pallas_bwd_enabled(4096)
    monkeypatch.setenv("DL4J_FLASH_PALLAS_BWD", "1")
    assert pk._pallas_bwd_enabled(64)                     # explicit override


def test_force_pallas_bypasses_length_gate_not_hard_constraints(monkeypatch):
    """force_pallas is the per-call opt-in for workloads whose measured
    crossover differs from _MIN_SEQ: it must bypass the length heuristic on
    both flash and masked entry points, and must NEVER override the
    vma-checked shard_map guard (pallas_call is rejected there outright)."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    from deeplearning4j_tpu.parallel.mesh import build_mesh

    rng = np.random.default_rng(0)
    # T=64: tileable, but far below _MIN_SEQ (1024)
    q, k, v = (jnp.asarray(rng.normal(size=(4, 64, 2, 8)).astype(np.float32))
               for _ in range(3))

    calls = []

    def fake_forward(qq, kk, vv, causal, interpret=False, key_mask=None,
                     scale=None, window=None):
        calls.append(1)
        if key_mask is not None:
            return pk._masked_attention_xla(qq, kk, vv, key_mask, causal), None
        return pk._attention_xla(qq, kk, vv, causal), None

    # pretend the TPU kernel path is available so the length heuristic (not
    # hardware support) is what decides
    monkeypatch.setattr(pk, "use_pallas", lambda: True)
    monkeypatch.setattr(pk, "_flash_forward", fake_forward)

    out = pk.flash_attention(q, k, v, False)
    assert not calls, "short sequence must stay on the XLA path by default"
    forced = pk.flash_attention(q, k, v, False, force_pallas=True)
    assert calls, "force_pallas did not bypass the _MIN_SEQ gate"
    np.testing.assert_allclose(np.asarray(forced), np.asarray(out),
                               rtol=1e-5, atol=1e-6)

    # masked entry point shares the one dispatch predicate
    km = jnp.ones((4, 64), jnp.float32)
    calls.clear()
    pk.masked_attention(q, k, v, km, False)
    assert not calls
    pk.masked_attention(q, k, v, km, False, force_pallas=True)
    assert calls

    # hard constraint wins over force: inside a CHECKED shard_map the kernel
    # must still fall back (engaging would crash on the vma checker, not
    # merely run slow)
    mesh = build_mesh({"data": 4})
    calls.clear()
    got = jax.jit(shard_map(
        lambda a, b, c: pk.flash_attention(a, b, c, False, force_pallas=True),
        mesh=mesh, in_specs=(P("data"), P("data"), P("data")),
        out_specs=P("data")))(q, k, v)
    assert not calls, "force_pallas must not override the checked-shard_map guard"
    np.testing.assert_allclose(np.asarray(got), np.asarray(out),
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------ window and grouped heads
def _masked_f32(q, k, v, window, scale=None):
    """Causal attention cut to ``window`` keys, head h reading key/value
    head ``h // (H // G)``: masked float32 math, one head at a time."""
    B, T, H, D = q.shape
    G = k.shape[2]
    ahead = np.arange(T)[:, None] - np.arange(T)[None, :]
    seen = (ahead >= 0) & (ahead < (window or T))
    heads = []
    for h in range(H):
        s = jnp.einsum("bqd,bkd->bqk", q[:, :, h], k[:, :, h // (H // G)],
                       precision="highest") * (scale or D ** -0.5)
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        heads.append(jnp.einsum("bqk,bkd->bqd", p, v[:, :, h // (H // G)],
                                precision="highest"))
    return jnp.stack(heads, axis=2)


@pytest.mark.parametrize("T,W,H,G,bq,bk,quartered", [
    (160, 48, 4, 1, 32, 32, True),     # T no multiple of W, W none of a tile
    (128, 64, 4, 4, 32, 32, True),     # W two tiles: the edge corner to corner
    (128, 40, 8, 2, 64, 32, False),    # oblong tiles, grouped 4 to 1
    (128, 50, 4, 2, 32, 64, False),
    (128, 48, 4, 1, 32, 32, False),    # square, crossed tiles whole
    (128, None, 4, 2, 32, 32, True),   # grouped, no window
    (128, 200, 4, 4, 32, 32, True),    # a window wider than the sequence
    (96, 1, 2, 1, 32, 32, True),       # a query sees itself alone
])
def test_windowed_grouped_core_matches_masked_float32(monkeypatch, T, W, H, G,
                                                      bq, bk, quartered):
    """Forward, the one-kernel backward, the dQ + dK/dV pair and the chunked
    XLA backward, in interpret mode, against masked float32 math."""
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_QUARTERED_FROM", 32 if quartered else 512)
    ks = jax.random.split(jax.random.PRNGKey(T + (W or 0)), 4)
    q, g = (jax.random.normal(kk, (2, T, H, 16)) for kk in ks[:2])
    k, v = (jax.random.normal(kk, (2, T, G, 16)) for kk in ks[2:])
    want, vjp = jax.vjp(lambda *a: _masked_f32(*a, W), q, k, v)
    want_g = vjp(g)
    out, lse = pk._flash_forward(q, k, v, True, blk_q=bq, blk_k=bk,
                                 interpret=True, window=W)
    np.testing.assert_allclose(out, want, atol=2e-5)
    for fused in (True, False):
        got = pk._flash_backward(q, k, v, out, lse, g, True, blk_q=bq,
                                 blk_k=bk, interpret=True, fused=fused,
                                 window=W)
        for a, b in zip(got, want_g):
            assert a.shape == b.shape
            np.testing.assert_allclose(a, b, atol=5e-5)
    for a, b in zip(pk._attention_bwd_chunked(q, k, v, g, True, blk_q=32,
                                              window=W), want_g):
        np.testing.assert_allclose(a, b, atol=5e-5)
    np.testing.assert_allclose(pk._attention_xla(q, k, v, True, None, W),
                               want, atol=2e-5)
    # the plan computes every visible entry and no tile outside the band
    computed, visible = pk.flash_score_entries(T, 16, 16, q.dtype, W, bq, bk,
                                               engaged=True)
    w = min(W or T, T)
    assert visible == sum(min(r + 1, w) for r in range(T))
    edge = (bq + bk) * T if not quartered else (bq + bk) * T // 2
    assert visible <= computed <= visible + 2 * edge


def test_flash_attention_takes_a_window_and_groups_under_grad():
    """The public entry point, interpret mode: value and all three gradients,
    dK and dV in the key/value heads' own shape."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 16))
    k, v = (jax.random.normal(kk, (1, 256, 2, 16)) for kk in ks[1:])

    def loss(f):
        return lambda q, k, v: jnp.sum(jnp.sin(f(q, k, v)))

    got = jax.grad(loss(lambda *a: flash_attention(*a, True, True, True, None,
                                                   96)), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda *a: _masked_f32(*a, 96)), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=5e-5)


@pytest.mark.parametrize("kwargs,what", [
    (dict(causal=False, window=8), "causal"),
    (dict(causal=True, window=0), "window"),
    (dict(causal=True, window=8, key_mask=True), "key mask"),
    (dict(causal=True, kv_heads=3), "heads"),
])
def test_window_and_groups_refuse_what_they_cannot_do(kwargs, what):
    from deeplearning4j_tpu.ops.pallas_kernels import _flash_forward

    q = jnp.zeros((1, 64, 4, 16))
    kv = jnp.zeros((1, 64, kwargs.get("kv_heads", 4), 16))
    km = jnp.ones((1, 64)) if kwargs.get("key_mask") else None
    with pytest.raises(ValueError, match=what):
        _flash_forward(q, kv, kv, kwargs["causal"], blk_q=32, blk_k=32,
                       interpret=True, key_mask=km,
                       window=kwargs.get("window"))


def test_no_window_and_equal_heads_touch_none_of_the_new_plan(monkeypatch):
    """``window=None`` with as many key/value heads as query heads traces
    the program it traced before either existed: none of the window's plan
    is reached, and the result is the same to the bit as with the new
    helpers made to raise."""
    from deeplearning4j_tpu.ops import pallas_kernels as pk

    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q, k, v = (jax.random.normal(kk, (1, 128, 2, 16)) for kk in ks)

    def run():
        return jax.grad(lambda *a: jnp.sum(jnp.sin(pk.flash_attention(
            *a, True, True, True, 0.3))), (0, 1, 2))(q, k, v)

    def text():
        return str(jax.make_jaxpr(lambda *a: pk.flash_attention(
            *a, True, True, True, 0.3))(q, k, v))

    before, jaxpr = run(), text()

    def boom(*a, **kw):
        raise AssertionError("the window's plan was reached")

    for name in ("_tile_state", "_first_live", "_window_steps", "_repeat_kv"):
        monkeypatch.setattr(pk, name, boom)
    after = run()
    for a, b in zip(before, after):
        assert np.array_equal(a, b)
    assert text() == jaxpr


# ------------------------------------------- a learned selection of keys
# (ops/indexer.py and flash_attention(select=...)): the kernels in interpret
# mode against the XLA statement of the same math. Float32 operands at
# "highest" precision on both sides, so what is left is the order of float32
# sums: 1e-5 relative, far below what a wrong mask or a missing term moves.
from deeplearning4j_tpu.ops import pallas_kernels as pk  # noqa: E402


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles small enough that 512 positions are four query tiles by two
    key tiles, several row blocks and four chunks of the selection."""
    from deeplearning4j_tpu.ops import indexer

    monkeypatch.setattr(indexer, "_tiles", lambda t: (128, 256))
    monkeypatch.setattr(indexer, "_SELECT_CHUNK", 128)
    monkeypatch.setattr(indexer, "_SELECT_BLOCK_BYTES", 64 * 512 * 4)
    monkeypatch.setattr(pk, "_TILE_SIZES", (128,))
    return indexer


def _selection_case(seed=0, B=2, T=512, H=4, G=2, D=32, J=3, E=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    n = jax.random.normal
    return dict(q=n(ks[0], (B, T, H, D)), k=n(ks[1], (B, T, G, D)),
                v=n(ks[2], (B, T, G, D)), qi=n(ks[3], (B, T, J, E)),
                ki=n(ks[4], (B, T, E)), w=0.1 * n(ks[5], (B, T, J)))


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


def test_index_scores_kernel_matches_xla_below_the_diagonal(small_tiles):
    ix, c = small_tiles, _selection_case()
    with jax.default_matmul_precision("highest"):
        want = ix.index_scores_xla(c["qi"], c["ki"], c["w"])
        got = ix.index_scores(c["qi"], c["ki"], c["w"], interpret=True)
    causal = np.tril(np.ones((512, 512), bool))
    assert _rel(np.where(causal, got, 0), np.where(causal, want, 0)) < 1e-5


@pytest.mark.parametrize("ties", [False, True])
def test_selection_is_exact_causal_and_breaks_ties_to_the_lower_key(
        small_tiles, ties):
    """Exactly ``min(t + 1, topk)`` keys a query, none above the diagonal
    (which holds NaN here: it is never read), and the XLA statement's
    choice entry for entry, also where a quarter-step rounding makes
    thousands of scores equal."""
    ix, c = small_tiles, _selection_case(1)
    scores = ix.index_scores_xla(c["qi"], c["ki"], c["w"])
    if ties:
        scores = jnp.round(scores * 4) / 4
    want, want_lse = ix.select_topk_xla(scores, 70)
    causal = np.tril(np.ones((512, 512), bool))
    got, lse = ix.select_topk(jnp.where(causal, scores, jnp.nan), 70,
                              interpret=True)
    got = np.asarray(got)
    assert got.dtype == np.int8 and set(np.unique(got)) == {0, 1}
    assert (got.sum(-1) == np.minimum(np.arange(512) + 1, 70)).all()
    assert not got[:, ~causal].any()
    assert np.array_equal(got, np.asarray(want))
    assert np.abs(np.asarray(lse) - np.asarray(want_lse)).max() < 1e-5


@pytest.mark.parametrize("fused", [True, False])
def test_core_over_a_selection_matches_xla_forward_and_backward(
        small_tiles, fused):
    """Grouped heads, four tiles a side; the one-kernel backward and the
    dQ + dK/dV pair, which reads the selection transposed."""
    ix, c = small_tiles, _selection_case(2)
    select, _ = ix.select_topk_xla(
        ix.index_scores_xla(c["qi"], c["ki"], c["w"]), 70)
    with jax.default_matmul_precision("highest"):
        def run(core):
            def f(q, k, v):
                out, lse = core(q, k, v)
                return jnp.sum(jnp.sin(out)), (out, lse)
            return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)(
                c["q"], c["k"], c["v"])

        (_, (want, want_lse)), want_g = run(
            lambda q, k, v: pk._selected_attention_xla(q, k, v, select))
        (_, (got, lse)), got_g = run(lambda q, k, v: pk.flash_attention(
            q, k, v, True, True, True, select=select, with_lse=True))
        if not fused:
            got_g = pk._flash_backward(
                c["q"], c["k"], c["v"], got, lse, jnp.cos(got), True,
                interpret=True, fused=False, select=select)
    assert _rel(got, want) < 1e-5 and _rel(lse, want_lse) < 1e-5
    for g, w in zip(got_g, want_g):
        assert _rel(g, w) < 2e-5


def test_indexer_loss_kernels_match_xla_and_reach_the_indexer_alone(
        small_tiles):
    ix, c = small_tiles, _selection_case(3)
    scale = 32 ** -0.5
    with jax.default_matmul_precision("highest"):
        scores = ix.index_scores_xla(c["qi"], c["ki"], c["w"])
        select, lse_i = ix.select_topk_xla(scores, 70)
        _, lse = pk._selected_attention_xla(c["q"], c["k"], c["v"], select)
        want, want_g = jax.value_and_grad(
            lambda qi, ki, w: ix.index_kl_xla(qi, ki, w, select, c["q"],
                                              c["k"], lse, scale),
            argnums=(0, 1, 2))(c["qi"], c["ki"], c["w"])

        def loss(qi, ki, w, q, k, lse):
            return ix.index_kl(qi, ki, w, scores, select, lse_i, q, k, lse,
                               scale, interpret=True)

        got, got_g = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4, 5))(
            c["qi"], c["ki"], c["w"], c["q"], c["k"], lse)
    assert abs(float(got) - float(want)) < 1e-5 * float(want) > 0
    for g, w in zip(got_g[:3], want_g):
        assert _rel(g, w) < 2e-5
    # the core's operands are constants of this loss
    assert all(not np.asarray(g).any() for g in got_g[3:])


def test_a_selection_refuses_what_it_cannot_stand_for():
    q = jnp.zeros((1, 128, 2, 8))
    select = jnp.ones((1, 128, 128), jnp.int8)
    with pytest.raises(ValueError, match="selection"):
        pk.flash_attention(q, q, q, False, select=select)
    with pytest.raises(ValueError, match="selection"):
        pk.flash_attention(q, q, q, True, window=16, select=select)
    with pytest.raises(ValueError, match="selection"):
        pk.flash_attention(q, q, q, True, select=select.astype(jnp.int32))
    with pytest.raises(ValueError, match="with_lse"):
        pk.flash_attention(q, q, q, True, with_lse=True)
