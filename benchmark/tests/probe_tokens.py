"""``probe.py`` for a cell of any driver: reads, on the chip and in one
process, what ``correct``'s limits are set from. For each seed the program's
gaps against the reference, the control's (the reference computed in a lower
precision, put in the program's place) and each fault's (planted in the
reference put in the program's place). The driver and its ``make_pool`` come
from the cell's traffic file. One JSON line per reading on standard output.

    python3 benchmark/tests/probe_tokens.py --workload <cell> --seeds 1,2 \
        [--controls int8] [--faults half_batch,shard_alone] [--lr 1e-4,1e-3]
        [--no-program]

``--no-program`` reads the controls and the faults alone, reference against
reference, on one chip whatever the cell asks for: a four-chip cell's sound
readings come from its own runs. ``shard_alone`` is what one chip of a
data-parallel step would hold had the chips exchanged nothing: every chip's
shard of a batch repeats the first chip's, so the gradient and the batch
statistics are one shard's.
"""
import argparse
import copy
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH]

import compare  # noqa: E402
import run  # noqa: E402
from probe import half_batch  # noqa: E402


def shard_alone(pool, chips):
    out = []
    for ds in pool:
        ds = copy.deepcopy(ds)
        n = ds.features.shape[0] // chips
        for i in range(1, chips):
            ds.features[i * n:(i + 1) * n] = ds.features[:n]
            ds.labels[i * n:(i + 1) * n] = ds.labels[:n]
        out.append(ds)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="int8")
    ap.add_argument("--faults", default="")
    ap.add_argument("--lr", default="")
    ap.add_argument("--no-program", action="store_true")
    ap.add_argument("--cpu", action="store_true")
    a = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(os.path.dirname(BENCH), ".jax_cache"))
    if not a.cpu:
        run.find_devices(1)
    base = run.load_cell(a.workload)
    drivers = importlib.import_module("drivers." + base["traffic"]["driver"])
    faults = {"half_batch": half_batch,
              "shard_alone": lambda pool: shard_alone(pool, base["chips"])}
    for lr in [float(x) for x in a.lr.split(",") if x] or [None]:
        cell = copy.deepcopy(base)
        if lr is not None:
            cell["config"]["builder"]["kwargs"]["learning_rate"] = lr
            cell["config"]["updater"]["learning_rate"] = lr
        for seed in [int(x) for x in a.seeds.split(",")]:
            d = drivers.Driver(cell, seed, run.Tools)
            t0 = time.perf_counter()
            if a.no_program:
                from deeplearning4j_tpu.datasets.dataset import DataSet

                d.pool = drivers.make_pool(seed, d.traffic, d.kwargs, DataSet)
                d.routed_first = {}
                prog = None
            else:
                d.setup()
                prog = d.readings
            d.release()
            ref = d.reference()
            row = {"cell": a.workload, "seed": seed, "lr": lr,
                   "ref_losses": ref["losses"]}

            def emit(kind, reading):
                g = compare.gaps(reading, ref)
                print(json.dumps(dict(
                    row, kind=kind, losses=reading["losses"],
                    **{k: v[0] for k, v in g.items()},
                    at={k: v[1] for k, v in g.items()},
                    seconds=round(time.perf_counter() - t0, 1))), flush=True)

            if prog is not None:
                emit("program", prog)
            for c in [x for x in a.controls.split(",") if x]:
                emit("control:" + c, d.reference(precision=c))
            sound = d.pool
            for f in [x for x in a.faults.split(",") if x]:
                d.pool = faults[f](sound)
                emit("fault:" + f, d.reference())
                d.pool = sound


if __name__ == "__main__":
    main()
