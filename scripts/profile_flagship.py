"""Capture + summarize an XPlane trace of a flagship K-step training program.

Thin CLI over the framework's trace engine: capture goes through the
process-global ``TraceSession`` (deeplearning4j_tpu/observability/profiler.py
— single locked owner of ``jax.profiler``), parsing/attribution through the
stdlib XPlane parser (observability/xplane.py). This script's only jobs are
(1) the exact-program guarantee — build the SAME (jitted fn, args)
bench.py times, via ``bench.flagship_setup`` + the same multistep builders
and donation — and (2) argument plumbing.

Usage (on the chip machine, e.g. `chiprun -- python3 scripts/profile_flagship.py ...`):
    python scripts/profile_flagship.py --model resnet50 --batch 128 --ksteps 8
    python scripts/profile_flagship.py --model transformer --bf16-act
The raw trace stays in --logdir (default scripts/profiles/<model>/) for
TensorBoard/xprof; the printed summary (also written as attribution.json
next to the trace) is self-contained.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_program(model: str, batch: int, ksteps: int):
    """The same (jitted fn, args) bench.py times for this config — model,
    data, and jit construction come from bench.flagship_setup and the same
    make_*_multistep_train_step + donation, so the profiled program IS the
    benchmarked one."""
    import jax
    import jax.numpy as jnp

    from bench import flagship_setup

    conf, xs, ys, graph = flagship_setup(model, batch, ksteps)
    if graph:
        from deeplearning4j_tpu.nn.graph_network import (
            ComputationGraph, make_graph_multistep_train_step)
        net = ComputationGraph(conf).init()
        multi = jax.jit(make_graph_multistep_train_step(conf),
                        donate_argnums=(0, 1, 2))
    else:
        from deeplearning4j_tpu.nn.multilayer import (
            MultiLayerNetwork, make_multistep_train_step)
        net = MultiLayerNetwork(conf).init()
        multi = jax.jit(make_multistep_train_step(conf),
                        donate_argnums=(0, 1, 2))
    args = (net.params_list, net.state_list, net.updater_state, xs, ys,
            jax.random.PRNGKey(0), jnp.int32(0))
    return multi, args


def capture(model: str, batch: int, ksteps: int, logdir: str,
            warmup: int = 2, traced_dispatches: int = 2) -> str:
    import jax

    from deeplearning4j_tpu.observability.profiler import global_trace_session

    fn, args = build_program(model, batch, ksteps)
    params, states, upd = args[0], args[1], args[2]
    rest = args[3:]
    t0 = time.time()
    for _ in range(warmup):
        params, states, upd, loss = fn(params, states, upd, *rest)
    _sync = float(np.asarray(jax.tree_util.tree_leaves(loss)[0]).ravel()[-1])
    print(f"warmup done ({time.time() - t0:.1f}s, loss={_sync:.4f}); tracing...",
          file=sys.stderr)
    session = global_trace_session()
    if session.start("script", logdir=logdir) is None:
        raise SystemExit("trace engine busy: another capture owns the "
                         "process-global profiler")
    for _ in range(traced_dispatches):
        params, states, upd, loss = fn(params, states, upd, *rest)
    float(np.asarray(jax.tree_util.tree_leaves(loss)[0]).ravel()[-1])
    session.stop(summarize=False)  # main() prints the summary itself
    return logdir


def summarize(logdir: str, top: int = 25) -> dict:
    """Per-op self-time table of the newest trace under ``logdir`` (the
    engine's stdlib parser; kept as a function so existing callers and
    --summarize-only share one path)."""
    from deeplearning4j_tpu.observability.xplane import summarize as _summ

    return _summ(logdir, top=top)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50",
                    choices=["resnet50", "transformer", "moe", "lenet"])
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--ksteps", type=int, default=8)
    ap.add_argument("--logdir", default=None)
    ap.add_argument("--bf16-act", action="store_true")
    ap.add_argument("--summarize-only", metavar="DIR",
                    help="skip capture; just parse an existing trace dir")
    args = ap.parse_args()

    if args.summarize_only:
        print(json.dumps(summarize(args.summarize_only), indent=1))
        return

    # same dtype setup as bench.py's default / --bf16-act modes
    from deeplearning4j_tpu.common import bf16_matmul_policy, full_bf16_policy
    (full_bf16_policy if args.bf16_act else bf16_matmul_policy)()
    batch = args.batch or {"resnet50": 128, "transformer": 16,
                           "moe": 16, "lenet": 128}[args.model]
    logdir = args.logdir or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "profiles", args.model)
    capture(args.model, batch, args.ksteps, logdir)
    print(json.dumps(summarize(logdir), indent=1))


if __name__ == "__main__":
    main()
