"""The TPU compiler's verdict on every Pallas kernel, without a TPU.

The suite runs on a forced-CPU mesh, where the kernels only ever execute in
interpret mode — which cannot see what Mosaic refuses (an unaligned slice of
a packed dtype, a gather, a block that overflows scoped VMEM). libtpu is
installed, though, and compiles for a *described* ``v5e:2x2`` topology. Each
case below either compiles a kernel at a real shape and finds the
``tpu_custom_call`` in the program, or asserts that the kernel's own gate
refuses the shape — so a default path that does not lower is caught here,
at no chip time. Nothing runs; a compile that passes is not a chip run.
"""
import contextlib
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp

import jax
import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.ops import indexer
from deeplearning4j_tpu.ops import lstm as lstm_engine
from deeplearning4j_tpu.ops import paged_attention, quant, ssd
from deeplearning4j_tpu.ops import pallas_kernels as pk

BF16, F32 = jnp.bfloat16, jnp.float32


@pytest.fixture(scope="module")
def chip():
    """One described (not attached) v5e device, persistent cache off: a
    compile for a described chip is written to the cache but cannot be read
    back without one, and the next run would warn about it."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu on this host
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(autouse=True)
def _gates_see_a_tpu(monkeypatch):
    """The gates ask for the default device's platform, which is this
    host's CPU; the question here is what they decide on the chip."""
    monkeypatch.setattr(pk, "_on_tpu", lambda: True)


def _n_kernels(chip, f, *shapes):
    """Compile ``f`` for the described chip; -> number of Pallas kernels in
    the program."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    return jax.jit(f).lower(*args).compile().as_text().count(
        '"tpu_custom_call"')


# ------------------------------------------------------------------- cases
def _flash(T, dtype, grad):
    qkv = [((2, T, 8, 64), dtype)] * 3          # B*H = 16, D = 64

    def fwd(q, k, v):
        return pk.flash_attention(q, k, v, True)

    def bwd(q, k, v):
        # the loss value keeps the forward alive where only the chunked
        # XLA backward follows it
        return jax.value_and_grad(lambda *a: fwd(*a).astype(F32).sum(),
                                  argnums=(0, 1, 2))(q, k, v)

    # forward kernel; under grad it is joined from _PBWD_MIN_SEQ by the one
    # backward kernel where its program, a head's whole dQ accumulator and
    # one key block of dQ's output included, fits what a kernel may ask for
    # (T = 16,384 at 64 wide: 32.5 MiB in bfloat16, 18 in float32), else by
    # the dq + dkv pair
    want = 1
    if grad and T >= pk._PBWD_MIN_SEQ:
        want = 2 if pk._fused_bwd_fits(T, T, 64, 64, dtype, True) else 3
    return (bwd if grad else fwd), qkv, want


def _masked(grad):
    T = 4096
    shapes = [((2, T, 8, 64), BF16)] * 3 + [((2, T), F32)]

    def fwd(q, k, v, m):
        return pk.masked_attention(q, k, v, m)

    def bwd(q, k, v, m):
        return jax.grad(lambda *a: fwd(*a, m).astype(F32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    return (bwd if grad else fwd), shapes, (2 if grad else 1)


def _xent(N, C, dtype):
    if pk._xent_rows(N, C, dtype) is None:
        return None
    return (lambda a, b: pk.softmax_cross_entropy(a, b),
            [((N, C), dtype)] * 2, 1)


def _lstm(dtype, grad, B=32, T=56, F=64, H=512):
    """char-RNN layer 0 at hidden 512: TBPTT 50 padded to the 8-step block,
    GravesLSTM peepholes on."""
    sel, bt = lstm_engine.resolve_impl(H, T, B, F, dtype=dtype, impl="auto")
    if sel != "pallas":
        return None
    shapes = [((T, B, F), dtype), ((F + H, 4 * H), dtype), ((1, 4 * H), dtype),
              ((3, H), dtype), ((B, H), dtype), ((B, H), dtype),
              ((T, B, 1), dtype)]

    def fwd(*a):
        return lstm_engine._pallas_lstm(bt, True, False, *a)

    def bwd(*a):
        return jax.grad(lambda *d: fwd(*d, *a[4:])[0].astype(F32).sum(),
                        argnums=(0, 1, 2, 3))(*a[:4])

    return (bwd if grad else fwd), shapes, (2 if grad else 1)


def _paged():
    # 16 slots x 2048-token ceiling in 16-token pages, 8 heads of 64
    pool, table = ((2049, 16, 8, 64), BF16), ((16, 128), jnp.int32)
    return (lambda p, t: paged_attention.paged_gather(p, t),
            [pool, table], 1)


def _int8():
    # one decode step's FFN matmul at width 512: [slots, 512] x [512, 2048]
    leaf = quant.QuantizedLeaf(jnp.zeros((512, 2048), jnp.int8),
                               jnp.ones((2048,), F32))
    assert quant._pallas_int8_ok(jnp.zeros((16, 512)), leaf, False)
    return (lambda x, q, s: quant._int8_matmul_pallas(x, q, s),
            [((16, 512), F32), ((512, 2048), jnp.int8), ((2048,), F32)], 1)


def _latent(grad, T=4096):
    """Latent attention's core at DeepSeek-V2-Lite's widths: 192-wide
    queries and keys, 128-wide values, 4 sequences of 4,096, 16 heads: the
    forward at the tiles the shape chooses, and under grad the ONE backward
    kernel (its program, a head's 4,096 x 192 dQ accumulator and one key
    block of dQ's output included, counts 31 MiB; at 16,384, one sequence,
    43). At 32,768 it counts 59, past what a kernel may ask for, and the dq
    + dkv pair stays: the case that guards the pair."""
    shapes = [((max(1, 16384 // T), T, 16, d), BF16) for d in (192, 192, 128)]

    def fwd(q, k, v):
        return pk.flash_attention(q, k, v, True, False, False, 0.1147)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(F32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    assert pk._fused_bwd_fits(T, T, 192, 128, BF16, True) == (T <= 16384)
    return (bwd if grad else fwd), shapes, (
        (2 if T <= 16384 else 3) if grad else 1)


def _windowed(grad, window=2048, T=8192):
    """The grouped core at Trinity-Mini's widths: 2 sequences of 8,192, 32
    query heads over 4 key/value heads, 128 wide; a 2,048-key window on the
    sliding layers, none on the full ones. Forward, and under grad the ONE
    backward kernel (its program, a head's 8,192 x 128 dQ accumulator and
    one key block of dQ's output included, counts 28.5 MiB)."""
    shapes = [((2, T, h, 128), BF16) for h in (32, 4, 4)]

    def fwd(q, k, v):
        return pk.flash_attention(q, k, v, True, False, False, None, window)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(F32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    assert pk._fused_bwd_fits(T, T, 128, 128, BF16, True)
    return (bwd if grad else fwd), shapes, (2 if grad else 1)


def _lfm2_core(grad, T=32768):
    """The attention core of LFM2-24B-A2B (``benchmark/configs/
    lfm2-24b-a2b-ep8.json``): one sequence of 32,768, 32 query heads over 8
    key/value heads of 64, causal. Under grad the ONE backward kernel: its
    program, a head's 32,768 x 64 float32 dQ accumulator and one 1,024-row
    key block of dQ's output beside the tiles, counts 40.5 MiB and asks for
    54.6 (the whole head's output block would make it 56, past the 46 a
    kernel may plan for)."""
    shapes = [((1, T, h, 64), BF16) for h in (32, 8, 8)]

    def fwd(q, k, v):
        return pk.flash_attention(q, k, v, True)

    def bwd(q, k, v):
        return jax.grad(lambda *a: fwd(*a).astype(F32).sum(),
                        argnums=(0, 1, 2))(q, k, v)

    assert pk._fused_bwd_fits(T, T, 64, 64, BF16, True)
    return (bwd if grad else fwd), shapes, (2 if grad else 1)


def _ssd(part, T=16384):
    """The Mamba-2 mixer of Nemotron-3-Nano-30B-A3B (``benchmark/configs/
    nemotron-3-nano-30b-a3b-ep16.json``): one sequence of 16,384, 64 heads
    of 64 over 8 groups' 128-wide state, chunks of 128, a group's 8 heads a
    kernel's program. ``block``: the whole mixer of a block under grad,
    whose core from ``W_in``'s output to the normed ``y`` is one kernel
    each way (``ssd.mamba_core``): the forward that also writes its
    residuals, and the backward; ``block-fwd``: the mixer's forward alone,
    the core's forward kernel."""
    K, P, N, chunk = 8, 64, 128, 128
    assert ssd._vmem_bytes(chunk, K, P, N, BF16, True) <= pk._VMEM_BUDGET
    f, shapes = _mamba_block(T, grad=part == "block")
    return f, shapes, 2 if part == "block" else 1


def _mamba_block(T=16384, F=2688, scopes=(), grad=True):
    """Grad (or, not ``grad``, the forward) of one Nemotron Mamba-2 mixer
    (``attention_part``) at the published widths under ``bfloat16_full``,
    inside the named ``scopes`` a block's step opens around it: -> (f,
    shapes)."""
    from deeplearning4j_tpu import common
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import DecoderBlock

    layer = DecoderBlock(n_in=F, n_out=F, attention="mamba2", ffn="none",
                         ssm_heads=64, ssm_head_dim=64, ssm_state=128,
                         ssm_groups=8, ssm_chunk=128, conv_kernel=4)
    params = jax.eval_shape(lambda: layer.init_params(
        jax.random.PRNGKey(0), InputType.recurrent(F, T)))
    names = sorted(params)

    def f(u, *leaves):
        def loss(u, p):
            with contextlib.ExitStack() as scoped:
                for name in scopes:
                    scoped.enter_context(jax.named_scope(name))
                with common.override_policy("bfloat16_full"):
                    return layer.attention_part(p, u).astype(F32).sum()
        if not grad:
            return loss(u, dict(zip(names, leaves)))
        return jax.grad(loss, argnums=(0, 1))(u, dict(zip(names, leaves)))

    return f, [((1, T, F), BF16)] + [(params[n].shape, F32) for n in names]


def _grouped(grad, policy="bfloat16_full", k=6, H=1408, G=8, eighths=2):
    """The dropless expert dispatch at DeepSeek-V2-Lite's widths: 16,384
    tokens x 6 choices over 8 held experts of 64, experts 2048 x 1408; or
    at Trinity-Mini's (``benchmark/configs/trinity-mini-ep8.json``): 8
    choices over 16 held of 128, experts 2048 x 1024; or at
    Keye-VL-2.0-30B-A3B's: experts 2048 x 768 and a usual buffer of three
    eighths of all pairs (``DecoderBlock.dispatch_eighths``)."""
    from deeplearning4j_tpu import common
    from deeplearning4j_tpu.nn.conf.layers.moe import grouped_expert_ffn

    S, F = 16384, 2048
    shapes = [((S, F), BF16), ((S, k), jnp.int32), ((S, k), F32),
              ((G, F, H), BF16), ((G, F, H), BF16), ((G, H, F), BF16)]

    def fwd(x, c, w, g, u, d):
        with common.override_policy(policy):
            return grouped_expert_ffn(x, c, w, g, u, d, 0, eighths)[0]

    def bwd(x, c, w, g, u, d):
        return jax.grad(lambda *a: fwd(a[0], c, *a[1:]).astype(F32).sum(),
                        argnums=(0, 1, 2, 3, 4))(x, w, g, u, d)

    # in both of the buffer's sizes (the cond's two branches). Forward: the
    # three grouped products and the way out's selector product. Under grad
    # of a sum: the gate and up products again (no cotangent needs the down
    # product's output, the pair's weight having gone into its input), two
    # more for each of the three, and the way in's cotangent, which is the
    # selector product again (the way out's own cotangent is a gather)
    return (bwd if grad else fwd), shapes, (18 if grad else 8)


def _indexer(part, grad=False, T=16384, topk=2048):
    """The sparse-attention indexer and the core over its selection at
    Keye-VL-2.0-30B-A3B's widths (``benchmark/configs/
    keye-vl2-30b-a3b-ep8.json``): one sequence of 16,384, 32 query heads over
    4 key/value heads of 128, 16 index heads of 64 over one key head, 2,048
    keys a query. ``scores``: one kernel; ``select``: one (128 rows of all
    16,384 keys in VMEM, the keys' scratch beside them); ``core``: the
    forward with the selection's int8 tile, under grad the ONE backward
    kernel reading it transposed (its program, a head's 16,384 x 128 dQ
    accumulator and one key block of dQ's output included, counts 32.5 MiB
    and asks for 44.6); ``kl``: the
    kernel that sums the heads' probabilities into a tile, under grad its
    forward and the two of the index scores' backward."""
    B, H, G, D, J, E = 1, 32, 4, 128, 16, 64
    qkv = [((B, T, h, D), BF16) for h in (H, G, G)]
    idx = [((B, T, J, E), BF16), ((B, T, E), BF16), ((B, T, J), F32)]
    if part == "scores":
        return (lambda a, b, c: indexer.index_scores(a, b, c)), idx, 1
    if part == "select":
        return (lambda s: indexer.select_topk(s, topk)), [((B, T, T), F32)], 1
    if part == "core":
        def fwd(q, k, v, s):
            return pk.flash_attention(q, k, v, True, select=s, with_lse=True)

        def bwd(q, k, v, s):
            return jax.grad(lambda *a: fwd(*a, s)[0].astype(F32).sum(),
                            argnums=(0, 1, 2))(q, k, v)

        # Keye's shape takes the one kernel: Mosaic is asked for 44.6 MiB
        assert pk._fused_bwd_fits(T, T, D, D, BF16, True)
        want = 2 if grad else 1
        return (bwd if grad else fwd), qkv + [((B, T, T), jnp.int8)], want

    if part == "block":
        from deeplearning4j_tpu import common
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.conf.layers import DecoderBlock

        # the whole attention of a block under grad, as the step program
        # differentiates it: no kernel may be asked for a JVP of its own
        # (the scores and the selection carry no gradient; the core and the
        # indexer's loss bring theirs): 4 forward kernels, the core's one
        # backward kernel and the index scores' backward pair
        layer = DecoderBlock(
            n_in=2048, n_out=2048, attention="gqa", n_heads=H, n_kv_heads=G,
            head_dim=D, output_gate=False, rope_theta=1e7, index_heads=J,
            index_dim=E, index_topk=topk, ffn="moe", n_experts=128,
            experts_per_token=8, expert_hidden=768, experts_held=[0, 16])
        params = jax.eval_shape(lambda: layer.init_params(
            jax.random.PRNGKey(0), InputType.recurrent(2048, T)))
        names = sorted(params)

        def f(u, *leaves):
            def loss(u, p):
                with common.override_policy("bfloat16_full"):
                    a, index_loss = layer.attention_part(p, u)
                return a.astype(F32).sum() + index_loss
            return jax.grad(loss, argnums=(0, 1))(u, dict(zip(names, leaves)))

        shapes = [((B, T, 2048), BF16)] + [
            (params[n].shape, F32) for n in names]
        return f, shapes, 7

    def kl(qi, ki, w, scores, s, lse_i, q, k, lse):
        return indexer.index_kl(qi, ki, w, scores, s, lse_i, q, k, lse,
                                D ** -0.5)

    shapes = idx + [((B, T, T), F32), ((B, T, T), jnp.int8), ((B, T), F32),
                    qkv[0], qkv[1], ((B * H, T), F32)]
    if grad:
        return (lambda *a: jax.grad(kl, argnums=(0, 1, 2))(*a)), shapes, 3
    return kl, shapes, 1


CASES = {
    "indexer-scores-T16384-bfloat16": (_indexer, ("scores",)),
    "indexer-select-T16384-k2048": (_indexer, ("select",)),
    "indexer-core-fwd-T16384-bfloat16": (_indexer, ("core",)),
    "indexer-core-grad-T16384-bfloat16": (_indexer, ("core", True)),
    "indexer-kl-fwd-T16384-bfloat16": (_indexer, ("kl",)),
    "indexer-kl-grad-T16384-bfloat16": (_indexer, ("kl", True)),
    "indexer-core-grad-T4096-bfloat16": (_indexer, ("core", True, 4096)),
    "indexer-block-grad-T16384-bfloat16": (_indexer, ("block",)),
    "ssd-block-grad-T16384-bfloat16": (_ssd, ("block",)),
    "ssd-block-fwd-T16384-bfloat16": (_ssd, ("block-fwd",)),
    "latent-fwd-T4096-bfloat16": (_latent, (False,)),
    "latent-grad-T4096-bfloat16": (_latent, (True,)),
    "latent-grad-T16384-bfloat16": (_latent, (True, 16384)),
    "latent-grad-T32768-bfloat16": (_latent, (True, 32768)),
    "lfm2-core-grad-T32768-bfloat16": (_lfm2_core, (True,)),
    "window-fwd-T8192-W2048-bfloat16": (_windowed, (False,)),
    "window-grad-T8192-W2048-bfloat16": (_windowed, (True,)),
    "window-grad-T8192-full-bfloat16": (_windowed, (True, None)),
    "window-grad-T4096-W1536-bfloat16": (_windowed, (True, 1536, 4096)),
    "grouped-fwd-S16384-bfloat16": (_grouped, (False,)),
    "grouped-grad-S16384-bfloat16": (_grouped, (True,)),
    "grouped-grad-S16384-float32": (_grouped, (True, "float32")),
    "grouped-grad-S16384-k8-G16-bfloat16": (_grouped, (True, "bfloat16_full",
                                                       8, 1024, 16)),
    "grouped-grad-S16384-k8-G16-H768-3eighths-bfloat16": (
        _grouped, (True, "bfloat16_full", 8, 768, 16, 3)),
    **{f"flash-{'grad' if g else 'fwd'}-T{T}-{jnp.dtype(d).name}":
       (_flash, (T, d, g))
       for g in (False, True)
       for T, d in ((1024, BF16), (4096, BF16), (16384, BF16), (16384, F32))},
    "masked-fwd-T4096-bfloat16": (_masked, (False,)),
    "masked-grad-T4096-bfloat16": (_masked, (True,)),
    **{f"xent-N{N}-C{C}-{jnp.dtype(d).name}": (_xent, (N, C, d))
       for N, C, d in ((128, 10, F32), (4096, 1000, BF16),
                       (4096, 32000, BF16), (2048, 50257, F32),
                       (2048, 50257, BF16))},
    **{f"lstm-{'grad' if g else 'fwd'}-H512-{jnp.dtype(d).name}":
       (_lstm, (d, g)) for g in (False, True) for d in (BF16, F32)},
    "paged-gather": (_paged, ()),
    "int8-matmul": (_int8, ()),
}

#: shapes the gates refuse, with the reason each gate gives from the shape
REFUSED = {
    "xent-N2048-C50257-bfloat16",   # a 16-row bf16 block of 50,304 lanes
    "lstm-fwd-H512-float32",        # W + f32 dW alone are 9.4 MiB
    "lstm-grad-H512-float32",
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e_or_gate_refuses(name, chip):
    build, args = CASES[name]
    case = build(*args)
    if name in REFUSED:
        assert case is None, f"{name}: the gate was expected to refuse"
        return
    assert case is not None, f"{name}: the gate refused a shape it admitted"
    f, shapes, want = case
    assert _n_kernels(chip, f, *shapes) == want


def test_ssd_kernels_lie_under_the_scan_scope(chip):
    """Every Mosaic kernel of a Mamba-2 mixer's gradient, forward and
    backward (the whole core's two, ``ssd.mamba_core``), carries an op name
    under ``attn/ssd/.../scan``, the scope ``benchmark/costs_ssd.py`` reads
    the chunked scan's time from: with the scan, the kernels hold the taps,
    the steps, the gate and the norm."""
    import re
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark"))
    import costs_ssd

    f, shapes = _mamba_block(T=1024, scopes=("layer", "attn"))
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    text = jax.jit(f).lower(*args).compile().as_text()
    names = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines() if '"tpu_custom_call"' in line]
    assert len(names) == 2, names
    assert all(re.search(costs_ssd.SCAN_SCOPE, "/" + n) for n in names), names
    assert any("transpose(" in n for n in names), names


def test_usual_dispatch_buffer_holds_no_array_of_every_pair(chip):
    """At Trinity-Mini's shape, forward and backward: outside the full-size
    branch of the dispatch's ``cond`` (whose buffer is ``S * k`` rows by
    definition) the program defines no array of ``S * k`` rows by F; both
    trips between tokens and buffer run over the buffer's rows."""
    import re

    fwd, shapes, _ = _grouped(False, "bfloat16_full", 8, 1024, 16)

    def f(x, c, *rest):                 # a loss that needs the forward's y
        return jax.grad(lambda x, *a: (fwd(x, c, *a).astype(F32) ** 2).sum(),
                        argnums=(0, 1, 2, 3, 4))(x, *rest)

    hlo = jax.jit(f).lower(*[jax.ShapeDtypeStruct(s, d, sharding=chip)
                             for s, d in shapes]).compile().as_text()
    bodies, name = {}, None
    for line in hlo.split("\n"):
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif name:
            bodies[name].append(line)
    every_pair = re.compile(r"= \(?\w+\[(?:131072,2048|8,16384,2048)\]")

    def arrays_under(comp, seen):
        if comp in seen or comp not in bodies:
            return 0
        seen.add(comp)
        called = {c.strip().lstrip("%") for line in bodies[comp]
                  for group in re.findall(
                      r"(?:calls|to_apply|body|condition|branch_computations)"
                      r"=\{?([^}\s]+(?:, [^}\s]+)*)", line)
                  for c in group.rstrip(",").split(",")}
        return (sum(1 for line in bodies[comp] if every_pair.search(line))
                + sum(arrays_under(c, seen) for c in called))

    conds = [line for body in bodies.values() for line in body
             if " conditional(" in line]
    assert conds
    inside = set()
    for line in conds:
        full, usual = (c.strip().lstrip("%") for c in re.search(
            r"branch_computations=\{([^}]*)\}", line).group(1).split(","))
        assert arrays_under(full, inside) > 0      # the reader sees them
        assert arrays_under(usual, inside) == 0
    assert sum(1 for comp, body in bodies.items() if comp not in inside
               for line in body if every_pair.search(line)) == 0


def test_lstm_refusal_names_the_shape():
    why = lstm_engine.pallas_refusal(512, 56, 32, 64, dtype=F32)
    assert why is not None and "VMEM" in why and "hidden 512" in why
    assert lstm_engine.pallas_refusal(512, 56, 32, 64, dtype=BF16) is None
    with pytest.raises(ValueError, match="VMEM"):
        lstm_engine.resolve_impl(512, 56, 32, 64, dtype=F32, impl="pallas")


def test_partitioned_jit_leaves_kernels_to_xla_and_compiles_for_four_chips(chip):
    """GSPMD cannot partition a Mosaic kernel — the TPU lowering refuses the
    whole program — so under compile_seam's "jit" strategy over more than one
    device every gate says no, unless a shard_map body holds the call. The
    sync-DP step of a classifier (whose loss is the fused xent kernel on one
    chip) must then compile for four described chips: an all-reduce, no
    kernel."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    assert pk.use_pallas()
    with pk.partitioned_trace(4):
        assert "GSPMD" in pk.pallas_unavailable()
        assert "GSPMD" in lstm_engine.pallas_refusal(512, 56, 32, 64,
                                                     dtype=BF16)
    from jax.experimental import topologies
    mesh = Mesh(np.array(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices), ("data",))
    seen = {}

    def probe(x):
        with pk.partitioned_trace(4):
            seen["jit"] = pk.use_pallas()

            def body(y):
                seen["shard_map"] = pk.use_pallas()
                return y

            return jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                                 out_specs=P("data"))(x)

    jax.jit(probe).lower(jax.ShapeDtypeStruct(
        (8,), F32, sharding=NamedSharding(mesh, P("data"))))
    assert seen == {"jit": False, "shard_map": True}

    net = MultiLayerNetwork(
        NeuralNetConfiguration.builder().seed(1).list()
        .layer(DenseLayer(n_in=256, n_out=256, activation="relu"))
        .layer(OutputLayer(n_in=256, n_out=1000, loss="mcxent",
                           activation="softmax")).build()).init()
    pw = ParallelWrapper(net, mesh=mesh)
    pw._drop_stale_programs()
    step = pw._make_sync_step()
    repl, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))

    def abstract(tree, sharding):
        return jax.tree_util.tree_map(
            lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype,
                                           sharding=sharding), tree)

    hlo = step.fn._jitted.lower(
        abstract(net.params_list, repl), abstract(net.state_list, repl),
        abstract(net.updater_state, repl),
        jax.ShapeDtypeStruct((512, 256), F32, sharding=split),
        jax.ShapeDtypeStruct((512, 1000), F32, sharding=split),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=repl),
    ).compile().as_text()
    assert '"tpu_custom_call"' not in hlo
    assert " all-reduce(" in hlo or " all-reduce-start(" in hlo
