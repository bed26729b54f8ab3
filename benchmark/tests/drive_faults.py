"""``drive.py``'s planted faults for a cell of any driver: one untraced run on
the CPU (no look for a chip) with the timed path broken underneath the
driver the cell's traffic names, so that ``correct`` has to come out false
through the cell's own readings (Adam's ``m`` for ``train_tokens``, the
wrapper's state for ``train_dp``), comparison and limits.

    python drive_faults.py <benchmark dir> <cell> <seed> <seconds> <fault>

``state_unchanged``, ``half_batch`` and ``loss_altered`` are ``drive.py``'s;
``shard_alone`` is what a chip of a data-parallel step would hold had nothing
been exchanged: every chip's shard of a batch repeats the first chip's, so
the gradient and the batch statistics are those of one shard.
"""
import argparse
import importlib
import os
import sys

bench, cell, seed, seconds, fault = sys.argv[1:6]
repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [repo, bench]

import run  # noqa: E402  (the copy under test)


def find(chips, platform="cpu"):
    import jax

    return jax.devices()[:chips], {"bf16_flops_per_s": 1e12,
                                   "hbm_bytes_per_s": 1e11}


def repeat_first(pool, parts):
    """Every ``1/parts`` of each batch overwritten with the first."""
    for ds in pool:
        n = ds.features.shape[0] // parts
        for i in range(1, parts):
            ds.features[i * n:(i + 1) * n] = ds.features[:n]
            ds.labels[i * n:(i + 1) * n] = ds.labels[:n]


def broken(fault, cell_name):
    loaded = run.load_cell(cell_name)
    drivers = importlib.import_module("drivers." + loaded["traffic"]["driver"])
    Driver = drivers.Driver

    class StateUnchanged(Driver):
        """A step that returns its state unchanged."""

        def fit(self, iterator):
            import jax
            import jax.numpy as jnp

            keep = jax.tree_util.tree_map(
                jnp.copy, (self.net.params_list, self.net.updater_state))
            super().fit(iterator)
            self.net.params_list, self.net.updater_state = keep

    class PartOfBatch(Driver):
        """Part of every batch left out, the mean taken over the rest; the
        reference follows the sound pool."""

        parts = {"half_batch": 2,
                 "shard_alone": int(loaded["chips"])}.get(fault)

        def fit(self, iterator):
            repeat_first(iterator.pool, self.parts)
            super().fit(iterator)

        def reference(self, *a, **kw):
            from deeplearning4j_tpu.datasets.dataset import DataSet

            self.pool = drivers.make_pool(self.seed, self.traffic,
                                          self.kwargs, DataSet)
            return super().reference(*a, **kw)

    class LossAltered(Driver):
        """An answer altered where it is produced: the recorded score."""

        def setup(self):
            super().setup()
            self.readings["losses"] = [l * 1.5 for l in self.readings["losses"]]

    return {"state_unchanged": StateUnchanged, "half_batch": PartOfBatch,
            "shard_alone": PartOfBatch, "loss_altered": LossAltered}[fault]


args = argparse.Namespace(workload=cell, seed=int(seed),
                          seconds=float(seconds), trace=0)
sys.exit(run.run(args, find=find, driver_cls=broken(fault, cell)))
