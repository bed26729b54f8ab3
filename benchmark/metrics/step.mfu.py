"""The whole step's share of the chip's peak: the operations the forward and
backward products require per sample (flops.py, from the configuration's
shapes) x samples per second of this run / (chips x peak bf16 FLOP/s)."""
import flops
from costs import Share


def read(ctx):
    need = flops.train_flops_of(ctx["cell"]["config"])
    rate = ctx["window"]["samples"] / ctx["window"]["elapsed_s"]
    peak = ctx["peak"]["bf16_flops_per_s"] * ctx["device"]["count"]
    return Share(required_flops_per_s=need * rate, peak_flops_per_s=peak)
