"""Operations and bytes of the kernels a language-model cell adds, from
shapes and counts alone (the products' 2 x multiply-accumulates; recomputed
work is not counted). ``least_seconds`` is the roofline: the larger of
operations over the chip's peak and bytes over its memory bandwidth."""
from __future__ import annotations


def grouped_ffn(rows: float, width: int, hidden: int, experts: int,
                itemsize: int = 2) -> tuple:
    """``(flops, bytes)`` of ``rows`` (token, choice) pairs through a gated
    feed-forward over ``experts`` held experts, forward and backward: three
    grouped products ``rows x width x hidden``, each with two more for its
    gradients. Bytes: every product reads its row operand and writes its
    result once, and reads (forward, input gradient) or writes (weight
    gradient) the experts' weights once."""
    products = 3 * 3
    flops = products * 2.0 * rows * width * hidden
    per_product = rows * (width + hidden) + experts * width * hidden
    return flops, products * per_product * itemsize


def attention_core(batch: int, heads: int, seq: int, qk: int, v: int,
                   itemsize: int = 2) -> tuple:
    """``(flops, bytes)`` of causal attention's core for ``batch``
    sequences, forward and backward: scores and values at the causal half
    (``seq (seq + 1) / 2`` pairs a head), ``qk + v`` wide, and twice that
    for the gradients. Bytes: queries, keys, values and outputs read or
    written once forward, and they and their gradients once backward."""
    pairs = seq * (seq + 1) // 2
    flops = 3 * 2.0 * batch * heads * pairs * (qk + v)
    tensors = batch * heads * seq * (2 * qk + 2 * v)
    return flops, 3 * tensors * itemsize


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


class Share(float):
    """A percentage that keeps the two numbers it was divided from, named,
    numerator first: ``Share(least_s=0.131, device_s=0.242)`` is 54.1... and
    its ``operands`` say of what. ``run.py`` prints them where a share of a
    roofline or of the peak reads over 100: one of the two was counted
    wrong, and the reader that divided them knows which they were."""

    def __new__(cls, **operands):
        numerator, denominator = operands.values()
        self = super().__new__(cls, 100.0 * numerator / denominator)
        self.operands = operands
        return self
