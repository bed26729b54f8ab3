"""Traffic generator ``train_tokens``: ``train_fit``'s closed loop for a
language model. One trainer drives ``<network>.fit_iterator`` with a cycled
pool of host batches of **integer token ids** ``[batch, seq_len]`` (int32,
uniform over the vocabulary rows the configuration holds, from the seed) and
integer labels, the ids shifted by one. Parameters from the traffic file:

  batch, seq_len, pool, dispatch_ksteps, prefetch_depth, stage_dtype,
  trace_seconds, trace_periods

A sample is one sequence. As in ``train_fit`` the first dispatch is driven
in set-up and is what ``correct`` compares; here the optimizer is Adam, so
``velocity_norm`` is read from its first moment ``m``, and the reference
follows with its own Adam (``reference/<file>.follow``). The expert layers'
routed rows of that dispatch, by the program's counter and by the
reference's count, go to standard error side by side.

Three things differ because a dispatch here runs for seconds, not tenths:

* the profile of a traced run lasts ``trace_seconds`` or ``trace_periods``
  dispatches, whichever is longer. ``trace_reduce`` leaves the first
  execution it sees out and cuts the slice to whole periods of the step
  program, and ``fit.dispatch_ms_p50`` needs two starts after that one: the
  execution the profile began in and two whole ones must end inside it, up
  to three periods. Set-up times the first dispatch on the device (the wait
  for its scores, after the call that dispatched it has returned) and, where
  ``trace_periods`` of those outlast ``trace_seconds``, writes the longer
  time into the cell's traffic, where ``run.py`` reads the profile's length
  once set-up is done (it caps it at 0.6 of ``--seconds``);

* the benchmark's weights are kept on the host once the program holds its
  copies (the parameters' change is taken leaf by leaf against them), so
  that they do not lie on the device beside the first dispatch;
* the pool stops handing out groups once those handed out will, at the
  device's pace, finish after ``--seconds`` (``_PacedPool``): the fit loop's
  producer runs some five groups ahead of the device, which at 6 s a group
  stretched a 30 s window to 61.
"""
from __future__ import annotations

import importlib
import time

import numpy as np

from drivers import train_fit
from drivers.train_fit import _CycledPool, _leaves, _LossRecorder


def make_pool(seed: int, traffic: dict, cfg: dict, dataset_cls):
    rng = np.random.default_rng(seed)
    pool = []
    for _ in range(traffic["pool"]):
        ids = rng.integers(0, cfg["vocab_rows"],
                           (traffic["batch"], traffic["seq_len"] + 1),
                           dtype=np.int32)
        pool.append(dataset_cls(np.ascontiguousarray(ids[:, :-1]),
                                np.ascontiguousarray(ids[:, 1:])))
    return pool


class _PacedPool(_CycledPool):
    """``_CycledPool`` whose timed run ends by the device's pace. The fit
    loop's producer pulls groups well ahead of the device (two queued, one
    in hand, two dispatched), so at seconds a group the last group pulled
    before ``seconds`` on the clock finishes long after it. ``dispatched()``
    is the number of groups the fit loop has dispatched; it dispatches a
    group when the one two back has finished, so ``dispatched() - 2`` groups
    are known to be done by now, which gives the pace. The pool stops
    before a group when the groups already handed out will, at that pace,
    take ``seconds`` or more (and at ``seconds`` on the clock whatever the
    pace)."""

    def __init__(self, pool, k, seconds, dispatched):
        super().__init__(pool, k, seconds=seconds)
        self.dispatched = dispatched

    def __iter__(self):
        self.t_first = time.perf_counter()
        while True:
            if self.yielded % self.k == 0 and self.yielded:
                now = time.perf_counter() - self.t_first
                done = self.dispatched() - 2
                if now >= self.seconds or (
                        done >= 1 and self.yielded // self.k * now / done
                        >= self.seconds):
                    return
            ds = self.pool[self.yielded % len(self.pool)]
            self.yielded += 1
            yield ds


class _TimedLossRecorder(_LossRecorder):
    """``_LossRecorder`` that also adds up how long the reads waited. The
    first read returns when the dispatch's program has run; the call that
    dispatched it (compile or load included) had returned before, so
    ``waited`` is one dispatch on the device."""

    waited = 0.0

    def iteration_done(self, net, iteration):
        t0 = time.perf_counter()
        super().iteration_done(net, iteration)
        self.waited += time.perf_counter() - t0


def _routed_rows() -> dict:
    """``{layer: rows}`` of the program's routed-rows counter; empty where
    the program has none."""
    from deeplearning4j_tpu.observability.metrics import global_registry

    fam = global_registry().snapshot().get("dl4j_moe_routed_rows_total")
    return ({s["labels"]["layer"]: int(s["value"]) for s in fam["series"]}
            if fam else {})


class Driver(train_fit.Driver):
    def setup(self):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.datasets.dataset import DataSet

        log = self.tools.log
        if int(self.kwargs["seq_len"]) != int(self.traffic["seq_len"]):
            raise ValueError("the traffic's seq_len is not the configuration's")
        # a program without the configuration's builder fails here, at once,
        # before 2.5 GB of weights are made
        mod, fn = self.config["builder"]["function"].rsplit(".", 1)
        getattr(importlib.import_module(mod), fn)
        t0 = time.perf_counter()
        weights = self.ref.init(self.seed, self.kwargs)
        self.net = self.build(weights)
        start = {k: np.asarray(v) for k, v in weights.items()}
        del weights
        log(f"built and placed weights in {time.perf_counter() - t0:.1f}s: "
            f"{sum(w.size for w in start.values()) / 1e6:.1f}M parameters")
        self.pool = make_pool(self.seed, self.traffic, self.kwargs, DataSet)

        t0 = time.perf_counter()
        rec = _TimedLossRecorder()
        self.net.set_listeners(rec)
        self.fit(_CycledPool(self.pool, self.k, n=self.k))
        self.net.set_listeners()
        self._lengthen_profile(rec.waited)
        after = _leaves(self.net.params_list)
        moment = {k: v["m"] for k, v in _leaves(self.net.updater_state).items()}
        norm = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(
            a.astype(jnp.float32) - b))))
        self.readings = {
            "losses": rec.losses,
            "change_norm": {k: float(norm(after[k], start[k]))
                            for k in after},
            "velocity_norm": {k: float(norm(m, jnp.float32(0)))
                              for k, m in moment.items()}}
        self.routed_first = _routed_rows()
        del start, after, moment
        log(f"first dispatch ({self.k} steps) in "
            f"{time.perf_counter() - t0:.1f}s, losses "
            + " ".join(f"{l:.4f}" for l in rec.losses))

    def _lengthen_profile(self, dispatch_s: float) -> None:
        """``trace_seconds`` of the cell's traffic, as ``run.py`` will read
        it, raised to ``trace_periods`` dispatches of ``dispatch_s`` each."""
        stated = float(self.traffic.get("trace_seconds", 3.0))
        need = float(self.traffic.get("trace_periods", 0.0)) * dispatch_s
        self.tools.log(
            f"a dispatch ran {dispatch_s:.3f}s on the device: a traced run's "
            f"profile lasts {max(stated, need):.2f}s (trace_seconds {stated}, "
            f"trace_periods {self.traffic.get('trace_periods')})")
        if need > stated:
            self.traffic["trace_seconds"] = need

    def window(self, seconds: float) -> dict:
        """``train_fit``'s window over a ``_PacedPool``."""
        first = self.net.iteration
        it = _PacedPool(self.pool, self.k, seconds,
                        lambda: (self.net.iteration - first) // self.k)
        self.fit(it)
        score = float(self.net.score_value)   # the host read that ends it
        t1 = time.perf_counter()
        batch = int(self.traffic["batch"])
        elapsed = t1 - it.t_first
        ok = bool(np.isfinite(score))
        return {"t_start": it.t_first, "t_end": t1, "elapsed_s": elapsed,
                "steps": it.yielded, "dispatches": it.yielded // self.k,
                "samples": it.yielded * batch, "final_score": score,
                "attempted": it.yielded, "failed": 0 if ok else it.yielded,
                "end_to_end": {
                    "train_samples_per_s": it.yielded * batch / elapsed}}

    def reference(self, precision: str = "float32") -> dict:
        fn = self.ref.make_loss_and_grad(self.kwargs, precision, None)
        params = self.ref.init(self.seed, self.kwargs)
        batches = [(self.pool[i % len(self.pool)].features,
                    self.pool[i % len(self.pool)].labels)
                   for i in range(self.k)]
        u = self.config["updater"]
        out = self.ref.follow(fn, params, batches, u["learning_rate"],
                              u["beta1"], u["beta2"], u["epsilon"])
        self.tools.log(
            f"rows routed to the experts held, first dispatch, by expert "
            f"layer: program {sorted(self.routed_first.items())}, reference "
            f"{out['routed_rows']}")
        return out
