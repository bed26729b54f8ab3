"""``probe_tokens.py`` for a cell whose attention selects its keys: reads, on
the chip and in one process, what ``correct``'s limits are set from, and how
far the program's selection is the reference's. For each seed one JSON line
per reading on standard output:

* ``program``: the program's gaps against the float32 reference;
* ``control:int8``: the reference at int8 in the program's place;
* ``fault:<name>`` for each of ``keye_faults.FAULTS`` asked for, planted in
  the reference put in the program's place (no second program is compiled);
* ``selection``: per decoder block, the share of the reference's selected
  pairs that the program (its own forward, in its own precision) selects
  too, for the first sequence of the first batch.

    python3 benchmark/tests/probe_select.py --workload <cell> --seeds 1,2 \
        [--controls int8] [--faults half_batch,dense_core,no_index_loss]
        [--no-program] [--no-selection] [--cpu]
"""
import argparse
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH, HERE]

import compare  # noqa: E402
import keye_faults  # noqa: E402
import run  # noqa: E402


def selection_agreement(d, weights):
    """``[share]`` per decoder block for the first sequence of the pool: the
    program's blocks applied one after another from its embedding, each
    block's selection made from its own normed input as ``_gqa_part`` makes
    it, against the reference's selections of the same sequence."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import common
    from deeplearning4j_tpu.ops import indexer

    ids = jnp.asarray(d.pool[0].features[:1], jnp.int32)
    net = d.build(weights)
    conf = net.conf
    c = d.ref._cfg(d.kwargs)
    want = jax.jit(lambda p, x: jnp.stack(
        d.ref.sequence_logits(p, x, c)[4]))(weights, ids[0])
    shares = []
    with common.override_policy(conf.global_conf.dtype):
        h = jax.jit(lambda p, x: conf.layers[0].apply(p, {}, x)[0])(
            net.params_list[0], ids)
        for i, layer in enumerate(conf.layers):
            if not getattr(layer, "index_heads", 0):
                continue

            def block(p, st, h, layer=layer):
                u = layer._norm(p, "norm1", h)
                qi, ki, w = layer._index_part(p, u)
                sel, _ = indexer.select_topk(
                    indexer.index_scores(qi, ki, w), layer.index_topk)
                return sel, layer.apply(p, st, h)[0]

            sel, h = jax.jit(block)(net.params_list[i], net.state_list[i], h)
            both = jnp.sum(jnp.logical_and(sel[0] > 0, want[len(shares)]))
            shares.append(float(both) / float(jnp.sum(want[len(shares)])))
    return shares


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="int8")
    ap.add_argument("--faults", default="half_batch,dense_core,no_index_loss")
    ap.add_argument("--no-program", action="store_true")
    ap.add_argument("--no-selection", action="store_true")
    ap.add_argument("--cpu", action="store_true")
    a = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(os.path.dirname(BENCH), ".jax_cache"))
    if not a.cpu:
        run.find_devices(1)
    cell = run.load_cell(a.workload)
    drivers = importlib.import_module("drivers." + cell["traffic"]["driver"])
    for seed in [int(x) for x in a.seeds.split(",")]:
        d = drivers.Driver(cell, seed, run.Tools)
        t0 = time.perf_counter()
        row = {"cell": a.workload, "seed": seed}
        if a.no_program:
            from deeplearning4j_tpu.datasets.dataset import DataSet

            d.pool = drivers.make_pool(seed, d.traffic, d.kwargs, DataSet)
            d.routed_first, prog = {}, None
        else:
            d.setup()
            prog = d.readings
        d.release()
        if not a.no_selection:
            shares = selection_agreement(d, d.ref.init(seed, d.kwargs))
            d.release()
            print(json.dumps(dict(row, kind="selection", agree=shares,
                                  seconds=round(time.perf_counter() - t0, 1))),
                  flush=True)
        ref = d.reference()

        def emit(kind, reading):
            g = compare.gaps(reading, ref)
            print(json.dumps(dict(
                row, kind=kind, losses=reading["losses"],
                ref_losses=ref["losses"],
                **{k: v[0] for k, v in g.items()},
                at={k: v[1] for k, v in g.items()},
                seconds=round(time.perf_counter() - t0, 1))), flush=True)

        if prog is not None:
            emit("program", prog)
        for c in [x for x in a.controls.split(",") if x]:
            emit("control:" + c, d.reference(precision=c))
        sound, make = d.pool, d.ref.make_loss_and_grad
        for f in [x for x in a.faults.split(",") if x]:
            if f == "half_batch":
                d.pool = keye_faults.half_repeated(sound)
            else:
                d.ref.make_loss_and_grad = (
                    lambda kw, precision, stage, f=f:
                    keye_faults.faulty_reference(make, kw, f, precision))
            try:
                emit("fault:" + f, d.reference())
            finally:
                d.pool, d.ref.make_loss_and_grad = sound, make


if __name__ == "__main__":
    main()
