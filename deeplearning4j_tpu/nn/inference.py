"""Non-donated compiled inference: the serving seam.

Training dispatches donate params/states/updater into XLA
(``LazyScore._program`` jits with ``donate_argnums=(0, 1, 2)``) — the
buffers are consumed in place, which is exactly right for a fit loop and
exactly wrong for serving, where the same parameters must survive millions
of forward passes. :func:`make_predict_fn` pins a **snapshot** of a
network's parameters/states (real buffer copies, like ``clone()``) to a
compiled forward program jitted WITHOUT donation, so:

- serving a request can never invalidate the source network's buffers, and
  training the source network can never invalidate the serving snapshot;
- the compiled program is policy-keyed and compile-tracked through the same
  ``LazyScore._jit`` cache as every other program, so recompiles show up in
  ``dl4j_jit_compile_total`` and the recompile-storm detector;
- per padded-batch-bucket compiles are the ONLY compiles: a steady-state
  server replays cached executables (the MicroBatcher's contract).

``sharding="dp_tp"`` + ``mesh=`` routes the pin through the partition-rule
engine instead of a single device: the snapshot is ``device_put`` per the
same rules that shard training (``parallel/partition.py``), cutting resident
bytes per device by the shard factor, and the program compiles through the
``parallel/compile_seam`` jit-with-shardings path.

**The serving equality contract.** Distributed *compute* (true Megatron-style
tensor parallelism) makes GSPMD insert partial-sum all-reduces that reorder
f32 accumulation — ~1-ulp accurate, never bitwise (the training suite's
dp_tp equivalence test uses atol=1e-4 for exactly this reason). Serving
instead shards params **at rest** and gathers **at use**: the first act
inside the jitted program is ``with_sharding_constraint(params,
replicated)`` — an exact all-gather layout change, no arithmetic — so there
is no cross-device arithmetic at all, and each device computes its slice of
the batch with the single-device program's own operations. What that makes
equal to what:

- **bitwise**: the sharded output equals the single-device program applied
  to each device's slice of the batch (same shapes, same kernels) — hence
  to the whole batch wherever the data axis does not split it (batches it
  does not divide dispatch replicated);
- **to float32 rounding** (a few ulp; the tests hold it to rtol 4e-6): the
  sharded output against the single-device program on the *whole* batch.
  XLA picks a matmul kernel by the operand's batch extent, so one row
  computed in a batch of 1 and in a batch of 4 may round differently. The
  CPU backend of JAX 0.4 happened to be batch-invariant and this read
  bitwise; JAX 0.9.0's is not (a batch of 4 split over ``data=4`` drifts by
  1 ulp). The same holds for the MicroBatcher's padded dispatch against a
  request served alone. Whether a TPU's kernels are batch-invariant is
  something ``chip_smoke.py``'s serve phase reports, not something promised.

The win is resident bytes (serve models bigger than one HBM) and data-axis
batch scale-out, not distributed matmuls; do not "optimize" the gather away.

The reference serves via ``KerasModelEndpoint``/``output()`` with no
donation concept; here the seam must be explicit because the fit path's
donation is what makes TPU training fast.
"""
from __future__ import annotations

import functools
import threading
from typing import Any, Optional

import jax
import jax.numpy as jnp

#: the program name every serving forward compiles under — load tests and
#: the compile-cache-bounded test filter CompileTracker events on it
PREDICT_PROGRAM_NAME = "serve_predict"


def _copy_tree(tree):
    """Real buffer copies, not aliases (same contract as clone())."""
    return jax.tree_util.tree_map(lambda a: jnp.array(a), tree)


def _with_dequant(fn):
    """Wrap a forward so its first act is reconstituting dense params from
    the int8 snapshot — inside the jit, so the arguments stay int8."""
    @functools.wraps(fn)
    def wrapped(params, *rest, **kw):
        from deeplearning4j_tpu.ops.quant import dequantize_tree
        return fn(dequantize_tree(params), *rest, **kw)
    return wrapped


#: serving DtypePolicy values make_predict_fn accepts: None/"bf16" serve the
#: pinned snapshot at the network's policy dtype; "int8" additionally
#: quantizes large matrix leaves (ops/quant.py) so the resident params are
#: 8-bit and the dequant runs inside the compiled program
QUANT_MODES = (None, "bf16", "int8")


class PredictFn:
    """A compiled, non-donated, snapshot-pinned forward pass.

    Callable: ``predict_fn(*inputs) -> jnp array`` where each input carries
    a leading batch axis (multi-input ComputationGraphs take one positional
    array per declared graph input). Thread-safe — concurrent calls share
    one compiled program per abstract input shape (jax's jit cache handles
    the rest); the pinned buffers are never donated so calls cannot race on
    buffer liveness.

    ``quant="int8"`` is the opt-in serving DtypePolicy: per-channel scales
    are calibrated at pin time over the snapshot (ops/quant.py), the pinned
    tree holds int8 codes (4x resident-bytes cut vs f32), and the jitted
    program dequantizes lazily so XLA fuses the cast into each consumer.

    ``sharding="dp_tp"`` + ``mesh=`` pins the snapshot sharded per the
    partition rules (params live split across the mesh; int8 composes — the
    codes shard, and the gather moves int8 bytes) and compiles through the
    compile seam. Outputs are fully replicated and bitwise-equal to the
    single-device program on each device's slice of the batch (see the
    module docstring for the equality contract and why the params are
    gathered at use rather than compute-sharded). ``device=`` instead pins
    the snapshot onto one specific device — the ReplicaSet's per-replica
    placement on a multi-chip host.
    """

    def __init__(self, net, name: str = PREDICT_PROGRAM_NAME,
                 quant: Optional[str] = None,
                 sharding: Optional[str] = None,
                 mesh=None, device=None,
                 fingerprint: Optional[str] = None):
        net._require_init()
        if quant not in QUANT_MODES:
            raise ValueError(f"quant must be one of {QUANT_MODES}, "
                             f"got {quant!r}")
        if sharding is not None and mesh is None:
            raise ValueError("sharding requires a mesh (parallel.build_mesh)")
        if sharding is not None and device is not None:
            raise ValueError("pass sharding+mesh OR device, not both")
        self._net = net
        self._name = name
        #: executable-cache identity: the undecorated program name (no
        #: @version / ~replica), so hot swaps and replica spawns warm-hit
        self._fingerprint = fingerprint or name
        self.quant = quant if quant == "int8" else None
        self.sharding = sharding
        self.mesh = mesh
        self.device = device
        # snapshot at pin time: a later fit() on `net` donates ITS buffers,
        # not these copies, and a hot-swap replaces this object wholesale
        self._params = _copy_tree(net.params_list)
        self._states = _copy_tree(net.state_list)
        if self.quant == "int8":
            from deeplearning4j_tpu.ops.quant import quantize_tree
            self._params = quantize_tree(self._params)
        self._graph = type(net).__name__ == "ComputationGraph"
        if self._graph:
            self._n_in = len(net.conf.network_inputs)
            self._single_out = len(net.conf.network_outputs) == 1
            fn = net._output_pure
        else:
            self._n_in = 1
            fn = functools.partial(net._output_pure, train=False)
        if self.quant == "int8":
            fn = _with_dequant(fn)
        self.param_specs = None
        if sharding is not None:
            self._fn = self._compile_sharded(net, name, fn)
        else:
            if device is not None:
                self._params = jax.device_put(self._params, device)
                self._states = jax.device_put(self._states, device)
            # LazyScore._jit: policy-keyed, compile-tracked, NO donate argnums
            self._fn = net._jit(name, fn, fingerprint=self._fingerprint)
        self._lock = threading.Lock()
        self.calls = 0  #: dispatches served (host-side, informational)

    def _compile_sharded(self, net, name, fn):
        """Pin the snapshot sharded-at-rest and compile the gathered-at-use
        program through the compile seam (records the per-device bytes
        gauge for this rule set)."""
        from deeplearning4j_tpu import common
        from deeplearning4j_tpu.parallel import compile_seam, partition
        mesh = self.mesh
        specs = partition.match_partition_rules(
            partition.rules_for(self.sharding), self._params,
            mesh=mesh, conf=getattr(net, "conf", None))
        self.param_specs = specs
        self._params = partition.device_put(self._params, mesh, specs)
        self._states = partition.device_put(self._states, mesh,
                                            partition.pspec())
        gather = partition.tree_shardings(
            mesh, jax.tree_util.tree_map(lambda _: partition.pspec(), specs))

        @functools.wraps(fn)
        def gathered(params, *rest, **kw):
            # exact all-gather (layout change, no arithmetic): every device
            # then runs the single-device operations on its slice of the
            # batch, which is what the equality contract rests on (int8
            # codes gather as int8 — 4x cheaper on the wire than f32)
            return fn(jax.lax.with_sharding_constraint(params, gather),
                      *rest, **kw)

        conf_dtype = getattr(getattr(getattr(net, "conf", None),
                                     "global_conf", None), "dtype", None)
        step = compile_seam.compile_step(
            f"{type(net).__name__}.{name}",
            common.wrap_with_policy(gathered, conf_dtype),
            mesh=mesh, rule_set=self.sharding,
            # batch entries stay None: __call__ stages each input with
            # batch_spec() and jit inherits the committed placement
            in_specs=(specs, partition.pspec(), None),
            out_specs=partition.pspec(),
            cache_key=common.effective_policy_key(conf_dtype),
            params=self._params, param_specs=specs,
            conf=getattr(net, "conf", None),
            fingerprint=f"{type(net).__name__}.{self._fingerprint}")
        return step

    @property
    def name(self) -> str:
        return self._name

    @property
    def cache_hit(self) -> Optional[bool]:
        """Whether this program's LAST executable resolve came from the
        persistent compile cache (None before any resolve, or with the
        cache disabled). The batcher stamps it on dispatch trace spans so
        a slow first request is attributable to a cold compile."""
        # CompiledStep (sharded) wraps the CachedProgram as .fn
        target = getattr(self._fn, "fn", self._fn)
        return getattr(target, "cache_hit", None)

    @property
    def n_inputs(self) -> int:
        """Positional input arrays one call takes (1 for sequential nets)."""
        return self._n_in

    @property
    def param_bytes(self) -> int:
        """Resident bytes of the pinned params (int8 shows the 4x cut)."""
        from deeplearning4j_tpu.ops.quant import tree_param_bytes
        return tree_param_bytes(self._params)

    @property
    def per_device_param_bytes(self) -> Optional[int]:
        """Resident param bytes on ONE device of the mesh when sharded
        (= param_bytes / shard factor, the tensor-parallel serving win);
        None for unsharded pins."""
        if self.sharding is None:
            return None
        from deeplearning4j_tpu.parallel import partition
        return partition.per_device_bytes(self._params, self.param_specs,
                                          self.mesh)

    def params_snapshot(self):
        """The pinned parameter pytree (tests assert bit-stability).
        Under quant="int8" the matrix leaves are QuantizedLeaf records."""
        return self._params

    def _stage(self, x):
        x = jnp.asarray(x)
        if self.mesh is not None:
            from deeplearning4j_tpu.parallel import partition
            return partition.device_put(
                x, self.mesh, partition.batch_spec(self.mesh, x.shape[0]))
        if self.device is not None:
            return jax.device_put(x, self.device)
        return x

    def __call__(self, *xs) -> Any:
        if len(xs) != self._n_in:
            raise ValueError(f"model takes {self._n_in} input(s), "
                             f"got {len(xs)}")
        staged = [self._stage(x) for x in xs]
        if self._graph:
            outs, _ = self._fn(self._params, self._states, staged)
            out = outs[0] if self._single_out else outs
        else:
            out, _ = self._fn(self._params, self._states, staged[0])
        with self._lock:
            self.calls += 1
        return out

    def warm(self, *xs) -> None:
        """Pre-resolve the compiled program for these example inputs
        (AOT through the executable cache when available — no dispatch;
        one real dispatch otherwise). Registry/replica warmup calls this
        per micro-batch bucket before the pin goes live."""
        if len(xs) != self._n_in:
            raise ValueError(f"model takes {self._n_in} input(s), "
                             f"got {len(xs)}")
        staged = [self._stage(x) for x in xs]
        inputs = staged if self._graph else staged[0]
        # CompiledStep (sharded) wraps the program as .fn
        target = getattr(self._fn, "fn", self._fn)
        warm = getattr(target, "warm", None)
        if warm is not None:
            warm(self._params, self._states, inputs)
        else:
            self._fn(self._params, self._states, inputs)


def make_predict_fn(net, name: str = PREDICT_PROGRAM_NAME,
                    version: Optional[str] = None,
                    quant: Optional[str] = None,
                    sharding: Optional[str] = None,
                    mesh=None, device=None,
                    replica: Optional[int] = None) -> PredictFn:
    """Pin a non-donated compiled forward for serving.

    ``version`` only decorates the program name (``serve_predict@v2``) so a
    hot-swapped model's compiles are attributable in the compile tracker;
    omit it for the plain serving program. ``quant="int8"`` opts this pin
    into the int8 serving DtypePolicy (the program name gains ``+int8`` so
    quantized compiles stay attributable too). ``replica`` likewise only
    decorates the name (``~r0``) so each ReplicaSet member's per-bucket
    compiles count separately. ``sharding``/``mesh``/``device`` choose the
    pin placement — see :class:`PredictFn`.
    """
    # cache identity keeps the quant marker (different program) but sheds
    # version/replica decoration (same program) — that is what lets a hot
    # swap or replica respawn load the previous pin's executables
    fingerprint = f"{name}+int8" if quant == "int8" else name
    if version:
        name = f"{name}@{version}"
    if quant == "int8":
        name = f"{name}+int8"
    if replica is not None:
        name = f"{name}~r{replica}"
    return PredictFn(net, name=name, quant=quant,
                     sharding=sharding, mesh=mesh, device=device,
                     fingerprint=fingerprint)
