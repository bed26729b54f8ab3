"""flops.py against a hand count per stage, and against XLA's own count."""
import jax
import jax.numpy as jnp
import pytest

import flops
from reference import resnet50, vgg16

FULL = {"n_classes": 1000, "image_size": 224}


def _resnet_stage(cin, f, out, blocks):
    """Multiply-accumulates of one stage at output size ``out`` (the stride
    sits on the first 1x1, so every product of the stage is at ``out``)."""
    px = out * out
    first = (cin * f + 9 * f * f + f * 4 * f + cin * 4 * f) * px
    rest = (4 * f * f + 9 * f * f + f * 4 * f) * px
    return first + (blocks - 1) * rest


RESNET_BY_HAND = {
    "stem": 7 * 7 * 3 * 64 * 112 * 112,
    "s0": _resnet_stage(64, 64, 56, 3),
    "s1": _resnet_stage(256, 128, 28, 4),
    "s2": _resnet_stage(512, 256, 14, 6),
    "s3": _resnet_stage(1024, 512, 7, 3),
    "fc": 2048 * 1000,
}

VGG_BY_HAND = {
    "block1": 9 * (3 * 64 + 64 * 64) * 224 * 224,
    "block2": 9 * (64 * 128 + 128 * 128) * 112 * 112,
    "block3": 9 * (128 * 256 + 2 * 256 * 256) * 56 * 56,
    "block4": 9 * (256 * 512 + 2 * 512 * 512) * 28 * 28,
    "block5": 9 * (3 * 512 * 512) * 14 * 14,
    "dense": 25088 * 4096 + 4096 * 4096 + 4096 * 1000,
}


def test_resnet50_hand_count():
    layers = resnet50.layers(FULL)
    got = {}
    for l in layers:
        key = l["name"] if l["name"] in ("stem", "fc") else l["name"][:2]
        got[key] = got.get(key, 0) + flops.layer_macs(l)
    assert got == RESNET_BY_HAND
    assert RESNET_BY_HAND["s0"] == 667_942_912
    macs = sum(RESNET_BY_HAND.values())
    assert flops.forward_flops_per_sample(layers) == 2 * macs
    # backward: two more products each, but no input gradient for the stem
    assert flops.train_flops_per_sample(layers) == (
        6 * macs - 2 * RESNET_BY_HAND["stem"])
    # the published 25,557,032 less batch-norm scales and shifts and the fc bias
    assert flops.param_bytes(layers) // 4 == 25_557_032 - 2 * 26_560 - 1000


def test_vgg16_hand_count():
    layers = vgg16.layers(FULL)
    macs = sum(VGG_BY_HAND.values())
    assert macs == 15_470_264_320
    assert flops.forward_flops_per_sample(layers) == 2 * macs
    first = 9 * 3 * 64 * 224 * 224
    assert flops.train_flops_per_sample(layers) == 6 * macs - 2 * first
    by_block = {}
    bounds = ((2, "block1"), (5, "block2"), (9, "block3"), (13, "block4"),
              (17, "block5"), (99, "dense"))
    for l in layers:
        key = next(name for hi, name in bounds if int(l["name"]) < hi)
        by_block[key] = by_block.get(key, 0) + flops.layer_macs(l)
    assert by_block == VGG_BY_HAND


@pytest.mark.parametrize("mod,kw", [(resnet50, {"remat": False}), (vgg16, {})])
def test_against_xla_cost_analysis(mod, kw):
    """XLA's count of one step body lowered on the CPU at batch 2: the same
    order, within 10% (XLA skips the padded border of a convolution and adds
    the elementwise work)."""
    batch = 2
    p = jax.eval_shape(lambda: mod.init(1, FULL))
    low = mod.make_loss_and_grad(FULL, **kw).lower(
        p, jax.ShapeDtypeStruct((batch, 224, 224, 3), jnp.float32),
        jax.ShapeDtypeStruct((batch, 1000), jnp.float32))
    need = flops.train_flops_per_sample(mod.layers(FULL)) * batch
    assert 0.9 < low.cost_analysis()["flops"] / need < 1.1
