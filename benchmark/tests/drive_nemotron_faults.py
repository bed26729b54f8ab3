"""``drive_faults.py`` for a cell whose mixers are Mamba-2 state-space scans:
one untraced run on the CPU (no look for a chip) with one of
``nemotron_faults.FAULTS`` planted in the program under the cell's own driver, so
that ``correct`` has to come out false through the cell's own readings,
comparison and limits.

    python drive_nemotron_faults.py <benchmark dir> <cell> <seed> <seconds> <fault>
"""
import argparse
import os
import sys

bench, cell, seed, seconds, fault = sys.argv[1:6]
here = os.path.dirname(os.path.abspath(__file__))
repo = os.path.dirname(os.path.dirname(here))
sys.path[:0] = [repo, bench, here]

import run  # noqa: E402  (the copy under test)
import nemotron_faults  # noqa: E402


def find(chips, platform="cpu"):
    import jax

    return jax.devices()[:chips], {"bf16_flops_per_s": 1e12,
                                   "hbm_bytes_per_s": 1e11}


args = argparse.Namespace(workload=cell, seed=int(seed),
                          seconds=float(seconds), trace=0)
sys.exit(run.run(args, find=find,
                 driver_cls=nemotron_faults.broken(run.load_cell(cell), fault)))
