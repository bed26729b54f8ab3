"""ONE compile seam for every parallel fit path (SNIPPETS.md [3] pattern).

Every step function that runs on a mesh compiles through
:func:`compile_step`, which takes the step fn + the PartitionSpec trees from
``partition.py`` + the mesh and chooses the strategy:

* ``"jit"`` — GSPMD: ``jax.jit`` with ``in_shardings``/``out_shardings``
  built from the spec trees (``None`` entries inherit the committed
  placement of staged arrays — the batch positions). XLA inserts the
  collectives the layouts imply; this is the sync-DP / TP / ZeRO path. The
  body is traced under ``pallas_kernels.partitioned_trace(mesh.size)``:
  GSPMD cannot partition a Mosaic kernel (the TPU lowering refuses the
  program), so over more than one device the kernels take their XLA math
  unless a shard_map body inside the step holds them.
* ``"shard_map"`` — per-device SPMD bodies (local-SGD, Spark-style
  parameter averaging): ``jax.shard_map`` under an outer jit.
  ``check_vma`` defaults to **False** here: the vma checker rejects
  ``pallas_call``, so a checked body silently downgrades every flash/LSTM
  kernel to XLA math (round-5 advisor finding; ulysses set the precedent).
  Bodies whose outputs are made replicated by their own psum/pmean are safe
  unchecked — pass ``check_vma=True`` only to keep the checker on a body
  that wants the audit and doesn't carry kernels.

The seam preserves what the fit paths already had: buffer donation
(``donate_argnums``), dtype-policy cache keys (the ``cache_key``
pass-through), and CompileTracker registration — with the rule-set name
folded into the cache key so recompiles are attributed per rule set. It
also records the chosen specs (``dl4j_sharding_spec_total``) and, when
given the parameter tree, the per-device sharded-param-bytes gauge.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import jax

from deeplearning4j_tpu.observability.compile_tracker import global_tracker
from deeplearning4j_tpu.ops.pallas_kernels import partitioned_trace
from deeplearning4j_tpu.parallel import partition


@dataclasses.dataclass
class CompiledStep:
    """A compiled, tracker-wrapped step plus the layout that produced it —
    callers read ``in_specs``/``out_specs`` for telemetry and staging."""
    fn: Callable
    name: str
    rule_set: str
    strategy: str
    mesh: Any
    in_specs: Any
    out_specs: Any
    check_vma: bool = True

    def __call__(self, *args, **kwargs):
        return self.fn(*args, **kwargs)


def _sharding_entries(mesh, specs):
    """Per-argument spec entries -> per-argument NamedSharding trees for
    jit; ``None`` entries stay None (inherit the staged placement)."""
    if specs is None:
        return None
    return tuple(partition.tree_shardings(mesh, s) for s in specs)


def compile_step(name: str, step_fn: Callable, *, mesh, rule_set: str,
                 in_specs: Optional[Sequence] = None,
                 out_specs: Any = None,
                 strategy: str = "jit",
                 check_vma: bool = False,
                 donate_argnums: Tuple[int, ...] = (),
                 cache_key: Any = None,
                 params=None, param_specs=None,
                 conf=None, fingerprint: Optional[str] = None) -> CompiledStep:
    """Compile ``step_fn`` for ``mesh`` under the given spec trees.

    ``cache_key`` flows into CompileTracker.wrap with ``rule_set`` prepended,
    so a recompile storm shows which rule set is churning. ``params`` +
    ``param_specs`` (optional) feed the per-device sharded-param-bytes
    gauge for this rule set.

    ``conf`` (the model configuration, when the caller has one) and
    ``fingerprint`` (identity override when ``name`` carries per-instance
    decoration) key the persistent executable cache; sharding strategy,
    spec trees, and donation are folded in so layout changes invalidate.
    """
    if strategy == "shard_map":
        body = jax.shard_map(step_fn, mesh=mesh, in_specs=tuple(in_specs),
                             out_specs=out_specs, check_vma=check_vma)
        fitted = jax.jit(body, donate_argnums=donate_argnums)
    elif strategy == "jit":
        kw = {}
        in_sh = _sharding_entries(mesh, in_specs)
        if in_sh is not None:
            kw["in_shardings"] = in_sh
        out_sh = partition.tree_shardings(mesh, out_specs) \
            if out_specs is not None else None
        if out_sh is not None:
            kw["out_shardings"] = out_sh
        @functools.wraps(step_fn)
        def traced(*args, **kwargs):
            with partitioned_trace(mesh.size):
                return step_fn(*args, **kwargs)

        fitted = jax.jit(traced, donate_argnums=donate_argnums, **kw)
    else:
        raise ValueError(f"unknown compile strategy {strategy!r}; "
                         f"expected 'jit' or 'shard_map'")

    partition.record_specs(rule_set, in_specs, out_specs)
    if params is not None and param_specs is not None:
        partition.record_param_bytes(rule_set, params, param_specs, mesh)

    key = cache_key if isinstance(cache_key, tuple) else (cache_key,)
    from deeplearning4j_tpu.nn import compile_cache as _cc

    # fingerprint material: NOT the cache_key (callers fold process-local
    # ids into it for in-memory keying); the global dtype policy stands in
    # for it — conf-pinned dtypes are covered by the conf hash
    try:
        from deeplearning4j_tpu import common
        policy = common.policy_key()
    except Exception:
        policy = None
    tracked = _cc.build_program(
        name, fitted, cache_key=(rule_set,) + key,
        fingerprint=fingerprint or name, conf=conf,
        extra=(rule_set, strategy, repr(in_specs), repr(out_specs),
               tuple(donate_argnums), repr(policy)))
    return CompiledStep(fn=tracked, name=name, rule_set=rule_set,
                        strategy=strategy, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, check_vma=check_vma)
