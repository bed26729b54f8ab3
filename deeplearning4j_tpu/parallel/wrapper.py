"""ParallelWrapper: data-parallel training over a device mesh.

Reference: deeplearning4j-scaleout ParallelWrapper.java:44 — clones the model into one
trainer thread per device, round-robin feeds minibatches, averages params every
``averaging_frequency`` iterations via Nd4j.averageAndPropagate (:179) and optionally
averages updater state (:198-212).

TPU-native redesign — no threads, no clones, no explicit averaging transport:

* averaging_frequency == 1 (synchronous DP): ONE jit-compiled train step whose batch
  input is sharded over the 'data' mesh axis and whose params are replicated. The loss
  is the global-batch mean, so autodiff's gradients are automatically all-reduced by
  XLA (psum over ICI) — bitwise the same math as lockstep parameter averaging every
  iteration, with the collective fused into the step.

* averaging_frequency == N > 1 (local SGD, the reference's actual semantics): params
  carry a leading per-replica axis sharded over 'data'; a shard_map train step updates
  each replica locally from its shard of the batch, and every N iterations a psum-mean
  resynchronizes params (and optionally updater state) across replicas.

The same wrapper covers the Spark ParameterAveragingTrainingMaster use-case
(SURVEY.md §2.4): multi-host, the mesh just spans hosts via jax.distributed and the
collectives ride DCN.
"""
from __future__ import annotations

import functools
import time as _time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from deeplearning4j_tpu.datasets.iterators import AsyncDataSetIterator
from deeplearning4j_tpu.nn.multilayer import (
    LoopOwner, _t_staging, book_fit_call,
)
from deeplearning4j_tpu.observability.flight_recorder import (
    dump_on_unhandled as _dump_on_unhandled,
)
from deeplearning4j_tpu.observability.names import COLLECTIVE_BYTES_TOTAL
from deeplearning4j_tpu.observability.metrics import (
    global_registry as _obs_registry, tree_nbytes as _tree_nbytes,
)
from deeplearning4j_tpu.parallel.mesh import data_parallel_mesh
from deeplearning4j_tpu.parallel.compile_seam import compile_step
from deeplearning4j_tpu.parallel.partition import (
    pspec as P, named_sharding as _named_sharding, match_partition_rules,
    rules_for,
)

# the collective counter sizes DP traffic host-side per dispatch (the
# gradient psum moves ~param bytes per step; traced collectives inside
# ring/ulysses/moe report trace-time per-step gauges instead); step-time
# attribution is the networks' loop's (``LazyScore._book_steps``)
_collective_bytes = _obs_registry().counter(
    COLLECTIVE_BYTES_TOTAL,
    "bytes moved by host-dispatched collectives, by op and site")


class ParallelWrapperBuilder:
    """Mirrors reference ParallelWrapper.Builder (:483+)."""

    def __init__(self, model):
        self._model = model
        self._workers: Optional[int] = None
        self._prefetch = 2
        self._avg_freq = 1
        self._average_updaters = True
        self._report_score = False
        self._mesh: Optional[Mesh] = None
        self._seq_axis: Optional[str] = None
        self._seq_mode = "ulysses"
        self._expert_axis: Optional[str] = None
        self._capacity_factor = 2.0
        self._zero1 = False
        self._fsdp = False
        self._sharding: Optional[str] = None

    def workers(self, n: int) -> "ParallelWrapperBuilder":
        self._workers = n
        return self

    def prefetch_buffer(self, n: int) -> "ParallelWrapperBuilder":
        self._prefetch = n
        return self

    def averaging_frequency(self, n: int) -> "ParallelWrapperBuilder":
        self._avg_freq = max(1, n)
        return self

    def average_updaters(self, flag: bool) -> "ParallelWrapperBuilder":
        self._average_updaters = flag
        return self

    def report_score_after_averaging(self, flag: bool) -> "ParallelWrapperBuilder":
        self._report_score = flag
        return self

    def mesh(self, mesh: Mesh) -> "ParallelWrapperBuilder":
        self._mesh = mesh
        return self

    def sequence_parallel(self, axis: str = "sp",
                          mode: str = "ulysses") -> "ParallelWrapperBuilder":
        """Run the net's attention layers sequence-parallel over the mesh
        axis ``axis`` (Ulysses all_to_all or "ring" ppermute) — long-context
        training from a plain transformer config, no model changes."""
        self._seq_axis = axis
        self._seq_mode = mode
        return self

    def expert_parallel(self, axis: str = "data",
                        capacity_factor: float = 2.0) -> "ParallelWrapperBuilder":
        """Route the net's MoE layers through GShard all_to_all dispatch over
        ``axis`` (default: the data axis doubles as the expert axis — the
        standard EP layout)."""
        self._expert_axis = axis
        self._capacity_factor = capacity_factor
        return self

    def shard_parameters(self, flag: bool = True) -> "ParallelWrapperBuilder":
        """FSDP / ZeRO-3: shard the parameters themselves over the data
        axis — per-device parameter memory drops by the axis size; XLA
        all-gathers each weight just-in-time and reduce-scatters its
        gradient. Usually combined with .shard_optimizer_state(). Same
        memory-feature caveats as ZeRO-1 apply."""
        self._fsdp = flag
        return self

    def shard_optimizer_state(self, flag: bool = True) -> "ParallelWrapperBuilder":
        """ZeRO-1: shard updater state (Adam moments etc.) over the data
        axis — per-device optimizer memory drops by the axis size; XLA
        inserts the gather around the parameter update. This is a MEMORY
        feature: training math is exactly unchanged (tested), and GSPMD may
        log involuntary-remat warnings where the sharding propagates through
        reshapes in the backward pass — a compile-time layout fallback on
        small tensors, not a correctness issue. Profile before assuming a
        throughput effect either way."""
        self._zero1 = flag
        return self

    def sharding(self, rule_set: str) -> "ParallelWrapperBuilder":
        """Pick a partition-rule set by name (parallel/partition.py):

        * ``"dp"`` — replicate params, shard the batch (the default).
        * ``"dp_tp"`` — Megatron tensor parallelism over the 'model' mesh
          axis on top of data parallelism (mesh must carry both axes).
        * ``"zero3"`` — params AND optimizer state sharded over 'data'
          (equivalent to .shard_parameters() + .shard_optimizer_state()).

        This is the config-choice face of the engine: the mesh shape plus a
        rule-set name replaces hand-wired sharding code paths."""
        self._sharding = rule_set
        return self

    def build(self) -> "ParallelWrapper":
        return ParallelWrapper(self._model, workers=self._workers,
                               prefetch=self._prefetch,
                               averaging_frequency=self._avg_freq,
                               average_updaters=self._average_updaters,
                               report_score=self._report_score, mesh=self._mesh,
                               sequence_parallel_axis=self._seq_axis,
                               sequence_parallel_mode=self._seq_mode,
                               expert_parallel_axis=self._expert_axis,
                               capacity_factor=self._capacity_factor,
                               shard_optimizer_state=self._zero1,
                               shard_parameters=self._fsdp,
                               sharding=self._sharding)


class ParallelWrapper:
    """Data-parallel ``fit`` over the devices of a mesh (module docstring).

    The synchronous loop (``averaging_frequency`` 1) is the networks' own
    staged loop (``nn.multilayer.LazyScore._fit_epoch``), run with the
    wrapper as its ``LoopOwner`` (``_fit_sync``): each batch is cast to the
    model's ``stage_dtype`` straight into a reused host slot, every device is
    sent its shard of the slot without waiting (laid out per ``_batch_spec``;
    nothing lands whole on one device), a group is dispatched through the
    wrapper's program once the step of the group two before it has finished,
    and every dispatch is booked as the networks' are (``fit.dispatch`` with
    the all-reduce's ``collective_bytes``, ``fit.listeners``).

    The slots (a ``HostGroupRing`` of ``prefetch + 2``) live as long as the
    wrapper, not one ``fit`` call: a trainer's epochs are several ``fit``
    calls, and the first touch of a slot's pages costs about a second a GB.
    The wrapper therefore holds up to ``prefetch + 2`` groups of host memory,
    each ``K`` global batches of the staged dtype; the ring starts anew when
    the batch's shape or dtype changes, and goes with the wrapper. Device
    memory is not held between fits (the ring is drained when ``fit``
    returns)."""

    def __init__(self, model, workers: Optional[int] = None, prefetch: int = 2,
                 averaging_frequency: int = 1, average_updaters: bool = True,
                 report_score: bool = False, mesh: Optional[Mesh] = None,
                 sequence_parallel_axis: Optional[str] = None,
                 sequence_parallel_mode: str = "ulysses",
                 expert_parallel_axis: Optional[str] = None,
                 capacity_factor: float = 2.0,
                 shard_optimizer_state: bool = False,
                 shard_parameters: bool = False,
                 sharding: Optional[str] = None):
        self.model = model
        self.mesh = mesh or data_parallel_mesh(workers)
        self.n_workers = self.mesh.shape["data"]
        self.seq_axis = sequence_parallel_axis
        self.seq_mode = sequence_parallel_mode
        self.expert_axis = expert_parallel_axis
        self.capacity_factor = capacity_factor
        self.zero1 = shard_optimizer_state
        self.fsdp = shard_parameters
        if sharding not in (None, "dp", "dp_tp", "zero3"):
            raise ValueError(f"unknown sharding rule set {sharding!r}; "
                             "expected 'dp', 'dp_tp', or 'zero3'")
        self.rule_set = sharding
        if sharding == "zero3":
            # zero3 = the full decomposition: params AND optimizer state
            # sharded over 'data'; the flags below drive the spec trees
            self.zero1 = self.fsdp = True
        if sharding == "dp_tp":
            if "model" not in self.mesh.shape:
                raise ValueError("sharding('dp_tp') needs a mesh with a "
                                 "'model' axis, e.g. build_mesh({'data': 4, "
                                 "'model': 2})")
            if averaging_frequency != 1:
                raise ValueError("sharding('dp_tp') requires "
                                 "averaging_frequency == 1 (synchronous DP)")
        if (self.zero1 or self.fsdp) and averaging_frequency != 1:
            raise ValueError("shard_optimizer_state/shard_parameters "
                             "(ZeRO/FSDP) require averaging_frequency == 1 "
                             "(synchronous DP)")
        if (self.seq_axis or self.expert_axis) and averaging_frequency != 1:
            # the local-SGD step is itself a shard_map over 'data'; nesting
            # the SP/EP shard_maps inside it is not supported
            raise ValueError("sequence/expert parallelism requires "
                             "averaging_frequency == 1 (synchronous DP)")
        if self.seq_axis:
            # requested SP must engage or fail loudly (same principle as EP
            # below): without attention layers the context changes nothing
            layers = list(getattr(model.conf, "layers", []) or [])
            for v in getattr(model.conf, "vertices", {}).values():
                if getattr(v, "layer", None) is not None:
                    layers.append(v.layer)
            attn = [l for l in layers
                    if hasattr(l, "n_heads") and hasattr(l, "causal")]
            if not attn:
                raise ValueError("sequence_parallel() requested but the "
                                 "model has no attention layers")
            n = self.mesh.shape[self.seq_axis]
            if self.seq_mode == "ulysses":
                bad = [l.n_heads for l in attn if l.n_heads % n]
                if bad:
                    raise ValueError(
                        f"sequence_parallel('{self.seq_axis}', ulysses) with "
                        f"axis size {n}: head counts {bad} are not divisible "
                        "by it (use mode='ring' or adjust heads)")
        if self.expert_axis:
            # requested EP must engage or fail loudly — the layer-side
            # dispatch falls back to dense when expert counts don't divide
            # the axis, which must never happen silently for an explicit
            # .expert_parallel() request (ulysses raises on the analogous
            # heads-divisibility violation)
            n = self.mesh.shape[self.expert_axis]
            layers = list(getattr(model.conf, "layers", []) or [])
            for v in getattr(model.conf, "vertices", {}).values():
                if getattr(v, "layer", None) is not None:
                    layers.append(v.layer)
            moe_layers = [l for l in layers if hasattr(l, "n_experts")]
            bad = [l.n_experts for l in moe_layers if l.n_experts % n]
            if bad:
                raise ValueError(
                    f"expert_parallel('{self.expert_axis}') with axis size "
                    f"{n}: expert counts {bad} are not divisible by it")
            if not moe_layers:
                raise ValueError("expert_parallel() requested but the model "
                                 "has no MoE layers")
        self.prefetch = prefetch
        self.averaging_frequency = averaging_frequency
        self.average_updaters = average_updaters
        self.report_score = report_score
        self._sync_step = None
        self._sync_multi = None
        self._local_step = None
        self._avg_fn = None
        self._local = None  # stacked per-replica (params, states, upd) for local-SGD
        # the synchronous loop's host slots for staged K-step groups (class
        # docstring)
        self._host_ring = None
        # dtype policy the cached jitted programs were traced under; they are
        # rebuilt when it changes (the policy is read at trace time)
        self._traced_policy = None

    def _drop_stale_programs(self) -> None:
        from deeplearning4j_tpu import common
        eff = common.effective_policy_key(
            getattr(self.model.conf.global_conf, "dtype", None))
        if self._traced_policy != eff:
            self._traced_policy = eff
            self._sync_step = self._sync_multi = None
            self._local_step = self._avg_fn = None

    @staticmethod
    def builder(model) -> ParallelWrapperBuilder:
        return ParallelWrapperBuilder(model)

    def _trace_ctx(self):
        """Context the jitted step's Python body is traced under: publishes
        the mesh + axis roles so attention/MoE layers dispatch their
        sequence-/expert-parallel paths (parallel/context.py)."""
        if self.seq_axis or self.expert_axis:
            from deeplearning4j_tpu.parallel import context as pctx
            return pctx.parallel_context(
                self.mesh, seq_axis=self.seq_axis, seq_mode=self.seq_mode,
                expert_axis=self.expert_axis,
                capacity_factor=self.capacity_factor, data_axis="data")
        import contextlib
        return contextlib.nullcontext()

    def _batch_spec(self, arr) -> P:
        """Leading dim over 'data'; with sequence parallelism active, the
        time axis of [B, T, ...] batches is additionally sharded over the
        sequence axis so long sequences never materialize unsharded.

        Divisibility is validated HERE, at staging, so a bad sequence length
        raises with the axis and length named instead of surfacing as an
        opaque device_put/sharding failure deep inside jit dispatch."""
        if self.seq_axis and getattr(arr, "ndim", 0) == 3:
            n = self.mesh.shape[self.seq_axis]
            t = arr.shape[1]
            if t % n:
                raise ValueError(
                    f"sequence_parallel('{self.seq_axis}'): sequence length "
                    f"{t} (axis 1 of a batch shaped {tuple(arr.shape)}) is "
                    f"not divisible by the '{self.seq_axis}' mesh axis size "
                    f"{n}; pad or re-bucket the batch")
            return P("data", self.seq_axis)
        return P("data")

    def _rule_label(self) -> str:
        """Rule-set name for telemetry + CompileTracker attribution."""
        if self.rule_set:
            return self.rule_set
        if self.fsdp or self.zero1:
            return "zero3"
        return "dp"

    def _matched_specs(self, rules, tree, what: str):
        """Run the partition-rule engine over a param-shaped pytree; an
        explicit sharding request that would shard NOTHING raises (same
        engage-or-fail principle as the expert_parallel validation —
        indivisible leaves demote to replicated per-leaf, but a fully
        replicated result means the request silently did nothing)."""
        specs = match_partition_rules(rules, tree, mesh=self.mesh,
                                      conf=self.model.conf)
        leaves = jax.tree_util.tree_leaves(tree)
        if leaves and all(s == P() for s in jax.tree_util.tree_leaves(specs)):
            raise ValueError(
                f"{what}: no dimension is divisible by the mesh axis; "
                f"nothing would shard")
        return specs

    def _spec_trees(self):
        """(param_specs, upd_specs) from the rule engine — either a P()
        prefix (replicated) or full spec pytrees. dp_tp applies the Megatron
        column/row rules to params AND their optimizer moments; fsdp/zero1/
        zero3 apply the first-divisible-dim ZeRO scan over 'data' (GSPMD
        all-gathers each weight just-in-time and reduce-scatters its
        gradient — per-device memory drops n_workers-fold)."""
        net = self.model
        if self.rule_set == "dp_tp":
            rules = rules_for("dp_tp")
            par = self._matched_specs(rules, net.params_list,
                                      "sharding('dp_tp')")
            upd = match_partition_rules(rules, net.updater_state,
                                        mesh=self.mesh, conf=net.conf)
            return par, upd
        par, upd = P(), P()
        if self.fsdp:
            par = self._matched_specs(rules_for("zero3"), net.params_list,
                                      "shard_parameters()")
        if self.zero1:
            upd = self._matched_specs(rules_for("zero3"), net.updater_state,
                                      "shard_optimizer_state()")
        return par, upd

    # ------------------------------------------------------------------ public API
    @_dump_on_unhandled("ParallelWrapper.fit")
    def fit(self, iterator, epochs: int = 1) -> None:
        """Reference fit(DataSetIterator):322. Batches are sharded over the mesh;
        each global batch must be divisible by the number of workers."""
        t0_ns = _time.time_ns()
        sync = self.averaging_frequency == 1
        try:
            if self.prefetch:
                iterator = AsyncDataSetIterator(iterator,
                                                queue_size=self.prefetch)
            if sync:
                self._fit_sync(iterator, epochs)
            else:
                self._fit_local_sgd(iterator, epochs)
        finally:
            book_fit_call("wrapper_sync" if sync else "wrapper_local_sgd",
                          max(1, self.model.dispatch_ksteps) if sync else 1,
                          epochs, t0_ns)

    # ------------------------------------------------------- synchronous DP (freq=1)
    def _make_sync_step(self):
        net = self.model
        mesh = self.mesh
        base = type(net)._step_builder(net.conf)

        def step(params, states, upd, x, y, rng, it):
            with self._trace_ctx():
                return base(params, states, upd, x, y, rng, it)

        # batch in_shardings are left to the staged arrays' committed
        # shardings (_stage picks P('data') or P('data', seq_axis) per rank).
        # The cross-replica gradient psum GSPMD inserts for the sharded-batch
        # mean loss inherits the cotangent dtype: under a grad_accum_dtype
        # policy the weight-grad contractions emit wide (f32) cotangents
        # (preferred_element_type routing in the layers), so the DP reduce
        # itself accumulates wide — no extra plumbing needed here.
        par_sp, upd_sp = self._spec_trees()
        return compile_step(
            "ParallelWrapper.sync_step", step, mesh=mesh,
            rule_set=self._rule_label(),
            in_specs=(par_sp, P(), upd_sp, None, None, P(), P()),
            out_specs=(par_sp, P(), upd_sp, P()),
            strategy="jit", cache_key=self._traced_policy,
            params=net.params_list, param_specs=par_sp,
            conf=net.conf)

    def _make_sync_multistep(self):
        """K-step scanned train step with the stacked batch axis sharded over
        'data' (stack axis unsharded): one host dispatch drives K synchronous
        DP steps, so dispatch latency amortizes exactly as in the single-chip
        fast path (the networks' fit_iterator)."""
        net = self.model
        mesh = self.mesh
        base = type(net)._multistep_builder(net.conf)

        def multi(params, states, upd, xs, ys, rng, it0):
            with self._trace_ctx():
                return base(params, states, upd, xs, ys, rng, it0)

        par_sp, upd_sp = self._spec_trees()
        return compile_step(
            "ParallelWrapper.sync_multistep", multi, mesh=mesh,
            rule_set=self._rule_label(),
            in_specs=(par_sp, P(), upd_sp, None, None, P(), P()),
            out_specs=(par_sp, P(), upd_sp, P()),
            strategy="jit", cache_key=self._traced_policy, conf=net.conf)

    def _stage(self, arr, spec: P):
        """Host batch -> device array laid out for the jit's in_shardings.

        Single-process: a plain transfer (the jit places it). Multi-process
        (jax.distributed cluster): every process holds the same global batch
        from its iterator, and each contributes only its addressable shards
        via make_array_from_callback — the cross-host equivalent of the
        reference's Spark executors each taking their partition of the RDD
        (ParameterAveragingTrainingMaster.executeTraining:344)."""
        arr = np.asarray(arr)
        sharding = _named_sharding(self.mesh, spec)
        if jax.process_count() == 1:
            return jax.device_put(jnp.asarray(arr), sharding)
        return jax.make_array_from_callback(arr.shape, sharding,
                                            lambda idx: arr[idx])

    def _place_state(self) -> None:
        """Lay the model's parameters, layer states and updater state out as
        the sync step's out_specs leave them. A network comes from ``init()``
        on one device; dispatched from there, the step is compiled for that
        placement, its outputs come back laid out over the mesh, and the
        second dispatch compiles the same step again for those. Placed up
        front the step compiles once; on a later ``fit`` the state is where
        it belongs already and this moves nothing. Single process only: a
        cluster's processes hand their state over as the jit takes it."""
        if jax.process_count() != 1:
            return
        net = self.model

        def place(tree, specs):
            if isinstance(specs, P):
                return jax.device_put(tree, _named_sharding(self.mesh, specs))
            return jax.tree_util.tree_map(
                lambda sp, sub: place(sub, sp), specs, tree,
                is_leaf=lambda sp: isinstance(sp, P))

        par_sp, upd_sp = self._spec_trees()
        net.params_list = place(net.params_list, par_sp)
        net.state_list = place(net.state_list, P())
        net.updater_state = place(net.updater_state, upd_sp)

    def _fit_sync(self, iterator, epochs: int) -> None:
        """The networks' staged loop (``LazyScore._fit_epoch``), handed what
        is the wrapper's own: the programs compiled over the mesh, how a
        batch and a group are laid out over it, the ring, the prefetch depth
        and the gradient all-reduce's accounting. What the sharded step does
        not implement (masks, iterations>1, TBPTT, a solver) falls back to
        the model's own per-batch path, unsharded: correctness over
        parallelism for those batches."""
        from deeplearning4j_tpu.datasets.prefetch import HostGroupRing

        net = self.model
        self._drop_stale_programs()
        if self._sync_step is None:
            self._sync_step = self._make_sync_step()
            self._sync_multi = self._make_sync_multistep()
        self._place_state()
        if self._host_ring is None:
            self._host_ring = HostGroupRing(self.prefetch + 2, "wrapper_sync")
        # DP gradient psum moves ~param bytes per executed train step; sized
        # host-side here because the collective itself is inside the jit
        param_bytes = _tree_nbytes(net.params_list)
        psum_bytes = _collective_bytes.labels(op="psum_grad",
                                              site="wrapper_sync")

        def note_steps(n):
            psum_bytes.inc(param_bytes * n)
            return {"collective_bytes": param_bytes * n}

        owner = LoopOwner(
            path="wrapper_sync", depth=self.prefetch, ring=self._host_ring,
            put=lambda a: self._stage(a, self._batch_spec(a)),
            # a stacked (K, B, ...) group: the batch spec shifted one axis right
            group_sharding=lambda leaf: _named_sharding(
                self.mesh, P(None, *self._batch_spec(leaf))),
            programs={
                "train_step": (self._sync_step, "ParallelWrapper.sync_step"),
                "multistep": (self._sync_multi,
                              "ParallelWrapper.sync_multistep")},
            note_steps=note_steps)
        try:
            for _ in range(epochs):
                if hasattr(iterator, "reset"):
                    iterator.reset()
                net._fit_epoch(iterator, max(1, net.dispatch_ksteps), owner)
        finally:
            # the ring outlives the fit; the device memory of the groups last
            # put from it must not
            self._host_ring.drain()

    # --------------------------------------------------- local SGD (freq=N>1)
    def _make_local_sgd_fns(self):
        """shard_map local step over stacked per-replica params + psum-mean averager
        (reference averaging loop ParallelWrapper.java:179-212)."""
        net = self.model
        mesh = self.mesh
        # multi-IO supported: a graph's xs/ys arrive as lists of arrays; the
        # shard_map in_specs below are pytree prefixes so P("data") applies
        # to every input/label leaf (reference ParallelWrapper handles
        # MultiDataSet fit the same way, ParallelWrapper.java:117)
        base = type(net)._step_builder(net.conf)
        stacked = P("data")
        repl = P()

        def local_step(params, states, upd, x, y, rng, it):
            # inside shard_map: leading axis is this replica's slice (size 1); drop it
            sq = functools.partial(jax.tree_util.tree_map, lambda a: a[0])
            ex = functools.partial(jax.tree_util.tree_map, lambda a: a[None])
            p, s, u = sq(params), sq(states), sq(upd)
            rng_local = jax.random.fold_in(rng, jax.lax.axis_index("data"))
            p2, s2, u2, loss = base(p, s, u, x, y, rng_local, it)
            return ex(p2), ex(s2), ex(u2), jax.lax.pmean(loss, "data")

        # check_vma=False through the seam: the vma checker rejects
        # pallas_call, so a checked body would silently downgrade flash/LSTM
        # kernels to XLA math inside every local step — the outputs are made
        # replicated by the body's own pmean, so unchecked is safe (the
        # ulysses precedent, parallel/ring_attention.py)
        local = compile_step(
            "ParallelWrapper.local_sgd_step", local_step, mesh=mesh,
            rule_set=self._rule_label(),
            in_specs=(stacked, stacked, stacked, stacked, stacked, repl,
                      repl),
            out_specs=(stacked, stacked, stacked, repl),
            strategy="shard_map", check_vma=False,
            cache_key=self._traced_policy, conf=net.conf)

        def average(params, upd, states):
            from deeplearning4j_tpu import common

            def mean_bcast(a):
                # cross-replica averaging follows the policy's grad-accum
                # dtype when that widens the leaf (bf16 replicas average in
                # f32); already-wide leaves average in their own dtype
                wide = common.accum_dtype(a.dtype)
                m = jnp.mean(a.astype(wide) if wide is not None else a,
                             axis=0, keepdims=True)
                return jnp.broadcast_to(m.astype(a.dtype), a.shape)
            avg = jax.tree_util.tree_map(mean_bcast, params)
            if self.average_updaters:
                upd = jax.tree_util.tree_map(mean_bcast, upd)
            # model state (batchnorm running stats) is averaged too — the reference
            # keeps BN stats inside params, which averageAndPropagate averages
            states = jax.tree_util.tree_map(mean_bcast, states)
            return avg, upd, states

        avg_fn = compile_step(
            "ParallelWrapper.average", average, mesh=mesh,
            rule_set=self._rule_label(), strategy="jit",
            cache_key=self._traced_policy, conf=net.conf)
        return local, avg_fn

    def _fit_local_sgd(self, iterator, epochs: int) -> None:
        net = self.model
        D = self.n_workers
        self._drop_stale_programs()
        if self._local_step is None:
            self._local_step, self._avg_fn = self._make_local_sgd_fns()
        stack = functools.partial(
            jax.tree_util.tree_map,
            lambda a: jnp.broadcast_to(a[None], (D,) + a.shape))
        sharding = _named_sharding(self.mesh, P("data"))
        params = jax.device_put(stack(net.params_list), sharding) \
            if jax.tree_util.tree_leaves(net.params_list) else net.params_list
        states = stack(net.state_list)
        upd = stack(net.updater_state)
        batch_sh = _named_sharding(self.mesh, P("data"))
        # each psum-mean resync moves ~per-replica param bytes across the ring
        avg_bytes = _collective_bytes.labels(op="parameter_average",
                                             site="wrapper_local_sgd")
        param_bytes = _tree_nbytes(net.params_list)

        def stage(ds):
            # producer thread: sharded non-blocking transfer of the next
            # batch while the current local step runs
            xs, ys, _, _ = net._batch_of(ds)
            return jax.tree_util.tree_map(
                lambda a: jax.device_put(a, batch_sh), (xs, ys))

        from deeplearning4j_tpu.datasets.prefetch import DevicePrefetcher

        since_avg = 0
        for _ in range(epochs):
            if hasattr(iterator, "reset"):
                iterator.reset()
            pf = DevicePrefetcher(iterator, stage, depth=self.prefetch,
                                  path="wrapper_local_sgd",
                                  wait_series=_t_staging)
            for x, y in pf:
                net.last_batch_size = int(
                    jax.tree_util.tree_leaves(x)[0].shape[0])
                t0, t0_ns = _time.perf_counter(), _time.time_ns()
                params, states, upd, loss = self._local_step(
                    params, states, upd, x, y, net._next_rng(),
                    jnp.int32(net.iteration))
                dt, t1_ns = _time.perf_counter() - t0, _time.time_ns()
                # the replicas' params live in this loop until its end: a
                # listener sees the same model before and after an averaging
                net._book_steps("ParallelWrapper.local_step", 1, [loss],
                                t0_ns, t1_ns, dt)
                since_avg += 1
                if since_avg >= self.averaging_frequency:
                    params, upd, states = self._avg_fn(params, upd, states)
                    avg_bytes.inc(param_bytes)
                    since_avg = 0
        # final sync + unstack back into the model
        params, upd, states = self._avg_fn(params, upd, states)
        unstack = functools.partial(jax.tree_util.tree_map, lambda a: a[0])
        net.params_list = unstack(params)
        net.state_list = unstack(states)
        net.updater_state = unstack(upd)
