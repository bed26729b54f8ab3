"""Convolution and dense products against the chip's peak: the FLOPs they
require (flops.py) for the steps of the reduced slice / peak bf16 FLOP/s,
over the device time of the trace events that carry them (a convolution or
dot, bare or at the root of an output fusion). Compute-bound at these shapes
(hundreds of FLOPs per byte), so the bound is the FLOP peak. Reads the same
work whatever implements it."""
import flops
from costs import Share


def read(ctx):
    t = ctx["trace"]
    tr = ctx["cell"]["traffic"]
    samples = t["dispatches"] * int(tr["dispatch_ksteps"]) * int(tr["batch"])
    if not t["matmul_s"] or not samples:
        return None
    need = flops.train_flops_of(ctx["cell"]["config"])
    chips = ctx["device"]["count"]
    least = need * samples / (ctx["peak"]["bf16_flops_per_s"] * chips)
    return Share(least_s=least, matmul_s=t["matmul_s"])
