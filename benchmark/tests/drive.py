"""Drives the rest of a run on the CPU: skips the harness's look for a chip
(``run.run``'s ``find`` seam) and, where asked, breaks the timed path
underneath (``driver_cls`` seam) or stands in a recorded reduction for the
device trace a CPU cannot give.

    python drive.py <benchmark dir> <cell> <seed> <seconds> <trace> [fault]
"""
import argparse
import os
import sys

bench, cell, seed, seconds, trace = sys.argv[1:6]
fault = sys.argv[6] if len(sys.argv) > 6 else None
repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [repo, bench]

import run  # noqa: E402  (the copy under test)


def find(chips, platform="cpu"):
    import jax

    return jax.devices()[:chips], {"bf16_flops_per_s": 1e12,
                                   "hbm_bytes_per_s": 1e11}


def broken(fault):
    from drivers.train_fit import Driver

    class StateUnchanged(Driver):
        """A step that returns its state unchanged."""

        def fit(self, iterator):
            import jax
            import jax.numpy as jnp

            keep = jax.tree_util.tree_map(
                jnp.copy, (self.net.params_list, self.net.updater_state))
            super().fit(iterator)
            self.net.params_list, self.net.updater_state = keep

    class HalfBatch(Driver):
        """Half of the batch left out, the mean taken over the rest: the
        second half of every batch repeats the first."""

        def fit(self, iterator):
            for ds in iterator.pool:
                n = ds.features.shape[0] // 2
                ds.features[n:] = ds.features[:n]
                ds.labels[n:] = ds.labels[:n]
            super().fit(iterator)

        def reference(self, *a, **kw):
            self.pool = self._sound
            return super().reference(*a, **kw)

        def setup(self):
            from deeplearning4j_tpu.datasets.dataset import DataSet
            from drivers.train_fit import make_pool

            self._sound = make_pool(self.seed, self.traffic, self.kwargs,
                                    DataSet)
            super().setup()

    class LossAltered(Driver):
        """An answer altered where it is produced: the recorded score."""

        def setup(self):
            super().setup()
            self.readings["losses"] = [l * 1.5 for l in self.readings["losses"]]

    return {"state_unchanged": StateUnchanged, "half_batch": HalfBatch,
            "loss_altered": LossAltered}[fault]


if trace == "1":
    import trace_reduce

    canned = trace_reduce.reduce_profile(
        __import__("jax").profiler.ProfileData.from_text_proto(
            open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "data", "small_trace.textproto")).read()))
    trace_reduce.reduce = lambda path, limit_s=None: canned

    class NoProfile:
        """The CPU has no device plane to profile; the canned reduction of
        the recorded trace stands in."""

        def __init__(self, out_dir, delay, seconds):
            self.seconds = seconds

        start = join = lambda self: None
        xplane = lambda self: ""

    run.TraceSlice = NoProfile

args = argparse.Namespace(workload=cell, seed=int(seed),
                          seconds=float(seconds), trace=int(trace))
sys.exit(run.run(args, find=find,
                 driver_cls=broken(fault) if fault else None))
