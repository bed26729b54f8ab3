"""Traffic generator ``train_fit``: one trainer, closed loop, host-fed.

Drives ``<network>.fit_iterator`` (the call a trainer writes) with a
``DataSetIterator`` that cycles a pool of distinct float32 host batches made
from the seed. Parameters come from the traffic file:

  batch, pool, dispatch_ksteps, prefetch_depth, stage_dtype, trace_seconds

The first dispatch (K steps, through the same call and feed as the window)
is driven during set-up with a recording listener; its losses, and the
optimizer state and parameter change after it, are what ``correct`` compares
with the plain reference. The same network object then runs the window.
"""
from __future__ import annotations

import gc
import importlib
import time

import numpy as np


class _CycledPool:
    """``DataSetIterator`` over ``pool``, cycled. ``n`` batches, or until the
    first multiple of ``k`` after ``seconds`` have passed on the wall clock
    (so the window holds one program shape and no ragged last group)."""

    def __init__(self, pool, k, n=None, seconds=None):
        self.pool, self.k, self.n, self.seconds = pool, k, n, seconds
        self.yielded = 0
        self.t_first = None

    def reset(self):
        pass

    def batch_size(self):
        return self.pool[0].num_examples()

    def __iter__(self):
        self.t_first = time.perf_counter()
        while True:
            if self.n is not None:
                if self.yielded >= self.n:
                    return
            elif (self.yielded % self.k == 0 and self.yielded
                  and time.perf_counter() - self.t_first >= self.seconds):
                return
            ds = self.pool[self.yielded % len(self.pool)]
            self.yielded += 1
            yield ds


class _LossRecorder:
    """Listener of the first dispatch only: reads each step's score."""

    def __init__(self):
        self.losses = []

    def iteration_done(self, net, iteration):
        self.losses.append(float(net.score_value))


def _leaves(tree):
    """The program's parameter containers, flattened to the reference's
    ``"<layer>/<param>"`` keys (a dict of dicts, or a list of dicts)."""
    items = tree.items() if isinstance(tree, dict) else enumerate(tree)
    return {f"{name}/{p}": v for name, sub in items for p, v in sub.items()}


def _jit_with_aux(fn):
    """``jax.jit(fn)()`` for an ``fn`` that returns ``(arrays, static)``: the
    static part (shapes) comes back as it was built at trace time."""
    import jax

    box = {}

    def arrays():
        out, box["aux"] = fn()
        return out

    return jax.jit(arrays)(), box["aux"]


def make_pool(seed: int, traffic: dict, cfg: dict, dataset_cls):
    rng = np.random.default_rng(seed)
    size, ch = cfg["image_size"], cfg.get("channels", 3)
    pool = []
    for _ in range(traffic["pool"]):
        x = rng.standard_normal((traffic["batch"], size, size, ch), np.float32)
        y = np.zeros((traffic["batch"], cfg["n_classes"]), np.float32)
        y[np.arange(traffic["batch"]),
          rng.integers(0, cfg["n_classes"], traffic["batch"])] = 1.0
        pool.append(dataset_cls(x, y))
    return pool


class Driver:
    def __init__(self, cell: dict, seed: int, tools):
        self.cell, self.seed, self.tools = cell, seed, tools
        self.config, self.traffic = cell["config"], cell["traffic"]
        self.kwargs = dict(self.config["builder"]["kwargs"])
        self.ref = importlib.import_module(
            "reference." + self.config["reference"])
        self.k = int(self.traffic["dispatch_ksteps"])
        self.net = None

    # ------------------------------------------------------------- set-up
    def build(self, weights: dict):
        """The network as the configuration states it, holding ``weights``.

        The program's own ``init()`` draws every leaf eagerly (a small
        compile each, ~20 s for ResNet-50 on the chip) only to be overwritten
        by the benchmark's weights, so it is traced into one jitted call that
        returns the layer states, the updater's state and the RNG key; the
        parameters' containers are filled from ``weights`` by leaf name. The
        train step donates its parameters, so the program gets copies."""
        import jax
        import jax.numpy as jnp

        mod, fn = self.config["builder"]["function"].rsplit(".", 1)
        kw = dict(self.kwargs)
        if "stage_blocks" in kw:
            kw["stage_blocks"] = tuple(kw["stage_blocks"])
        conf = getattr(importlib.import_module(mod), fn)(**kw)
        conf.global_conf.dtype = self.config["dtype_policy"]
        nmod, ncls = self.config["network"].rsplit(".", 1)
        cls = getattr(importlib.import_module(nmod), ncls)

        def shell():
            n = cls(conf).init()
            shapes = jax.tree_util.tree_map(lambda a: a.shape, n.params_list)
            return (n.state_list, n.updater_state, n._rng), shapes

        (states, upd, rng), shapes = _jit_with_aux(shell)
        net = cls(conf)
        items = shapes.items() if isinstance(shapes, dict) else enumerate(shapes)
        placed, params = set(), ({} if isinstance(shapes, dict) else [])
        for name, sub in items:
            leaf = {}
            for p, shape in sub.items():
                w = weights[f"{name}/{p}"]
                if tuple(w.shape) != tuple(shape):
                    raise ValueError(f"leaf {name}/{p}: reference {w.shape}, "
                                     f"program {shape}")
                leaf[p] = jnp.copy(w)
                placed.add(f"{name}/{p}")
            if isinstance(params, dict):
                params[name] = leaf
            else:
                params.append(leaf)
        if placed != set(weights):
            raise ValueError(f"leaves not in the program: "
                             f"{sorted(set(weights) - placed)[:5]}")
        net.params_list, net.state_list = params, states
        net.updater_state, net._rng = upd, rng
        net.dispatch_ksteps = self.k
        net.prefetch_depth = int(self.traffic["prefetch_depth"])
        sd = self.traffic.get("stage_dtype")
        net.stage_dtype = getattr(jnp, sd) if sd else None
        return net

    def setup(self):
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.datasets.dataset import DataSet

        log = self.tools.log
        t0 = time.perf_counter()
        weights = self.ref.init(self.seed, self.kwargs)
        self.net = self.build(weights)
        log(f"built and placed weights in {time.perf_counter() - t0:.1f}s: "
            f"{sum(w.size for w in weights.values()) / 1e6:.1f}M parameters")
        t0 = time.perf_counter()
        self.pool = make_pool(self.seed, self.traffic, self.kwargs, DataSet)
        log(f"pool of {len(self.pool)} host batches in "
            f"{time.perf_counter() - t0:.1f}s")

        # the first dispatch: compiles (or loads) the one K-step program the
        # window uses, and is what the reference follows
        t0 = time.perf_counter()
        rec = _LossRecorder()
        self.net.set_listeners(rec)
        self.fit(_CycledPool(self.pool, self.k, n=self.k))
        self.net.set_listeners()
        after = _leaves(self.net.params_list)
        vel = {k: v["v"] for k, v in _leaves(self.net.updater_state).items()}

        def norms(a, b, v):
            sq = lambda t: jnp.sqrt(jnp.sum(jnp.square(t.astype(jnp.float32))))
            return ({k: sq(a[k] - b[k]) for k in a},
                    {k: sq(v[k]) for k in v})

        change, vnorm = jax.jit(norms)(after, weights, vel)
        self.readings = {
            "losses": rec.losses,
            "change_norm": {k: float(v) for k, v in change.items()},
            "velocity_norm": {k: float(v) for k, v in vnorm.items()}}
        del weights, after, vel
        log(f"first dispatch ({self.k} steps) in "
            f"{time.perf_counter() - t0:.1f}s, losses "
            + " ".join(f"{l:.4f}" for l in rec.losses))

    def fit(self, iterator):
        self.net.fit_iterator(iterator)

    # ------------------------------------------------------------- window
    def window(self, seconds: float) -> dict:
        it = _CycledPool(self.pool, self.k, seconds=seconds)
        t0 = time.perf_counter()
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

    # ----------------------------------------------------- after the window
    def release(self):
        """Free the program's state so the reference has the device."""
        self.net = None
        gc.collect()

    def reference(self, precision: str = "float32") -> dict:
        """The plain reference over the first dispatch, made from the seed."""
        import jax.numpy as jnp

        from reference import common

        sd = self.traffic.get("stage_dtype")
        fn = self.ref.make_loss_and_grad(
            self.kwargs, precision, getattr(jnp, sd) if sd else None)
        params = self.ref.init(self.seed, self.kwargs)
        batches = [(self.pool[i % len(self.pool)].features,
                    self.pool[i % len(self.pool)].labels)
                   for i in range(self.k)]
        u = self.config["updater"]
        return common.follow(fn, params, batches, u["learning_rate"],
                             u["momentum"])
