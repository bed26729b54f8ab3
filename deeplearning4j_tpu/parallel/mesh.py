"""Device-mesh construction and sharding rules.

This is the TPU-native replacement for the reference's entire distribution transport
stack (SURVEY.md §2.4): Spark RDD tree-aggregation, the Aeron parameter server, and
in-process P2P parameter averaging all become XLA collectives over a
``jax.sharding.Mesh`` — psum over ICI inside a slice, DCN across slices via
jax.distributed. Axis conventions:

  data  — data parallelism (ParallelWrapper / ParameterAveragingTrainingMaster)
  model — tensor parallelism (new TPU-native capability, absent in reference)
  seq   — sequence/context parallelism for long sequences (ring attention)

Multi-host: call ``init_distributed()`` (jax.distributed.initialize) before building
the mesh; jax.devices() then spans all hosts and the same code scales out — the
replacement for the reference's Spark cluster setup.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None) -> None:
    """Multi-host init (replaces Spark driver/executor RPC + Aeron media driver,
    reference ParameterServerParallelWrapper.java:159-161)."""
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes, process_id=process_id)


def build_mesh(axes: dict[str, int], devices: Optional[Sequence] = None) -> Mesh:
    """Build a named mesh, e.g. build_mesh({"data": 4, "model": 2})."""
    devices = list(devices if devices is not None else jax.devices())
    sizes = list(axes.values())
    total = int(np.prod(sizes))
    if total > len(devices):
        raise ValueError(f"Mesh needs {total} devices, have {len(devices)}")
    arr = np.array(devices[:total]).reshape(sizes)
    return Mesh(arr, tuple(axes.keys()))


def build_hybrid_mesh(ici_axes: dict[str, int],
                      dcn_axes: dict[str, int]) -> Mesh:
    """Multi-slice mesh: per-axis size = ici_size * dcn_size, with device
    placement chosen so the ``dcn_axes`` multiplier spans slices (DCN) and the
    ``ici_axes`` factor stays inside a slice (ICI).

    This encodes the scaling rule the reference never needed (its Spark tree-
    aggregate treated all links alike, SURVEY.md §2.4): collective-heavy axes
    (tensor/sequence parallel) must ride ICI, so give them dcn multiplier 1;
    bandwidth-light axes (data parallel gradient all-reduce, expert all_to_all
    at low frequency) may span slices. Keys of ``dcn_axes`` must be a subset
    of ``ici_axes`` (missing keys mean multiplier 1).

    On a single slice/process (including the CPU test mesh) this degrades to
    a plain mesh with the same axis names and product sizes, so code written
    against it runs unchanged from one chip to multi-slice.
    """
    dcn = {k: int(dcn_axes.get(k, 1)) for k in ici_axes}
    unknown = set(dcn_axes) - set(ici_axes)
    if unknown:
        raise ValueError(f"dcn_axes {sorted(unknown)} not present in ici_axes "
                         f"{sorted(ici_axes)}")
    n_slices = len({getattr(d, "slice_index", 0) for d in jax.devices()})
    if n_slices > 1 and any(v > 1 for v in dcn.values()):
        # real multi-slice topology: misconfigurations must raise loudly —
        # a silent fallback here could lay a collective-heavy axis across
        # DCN, the exact failure this helper exists to prevent
        from jax.experimental import mesh_utils
        devs = mesh_utils.create_hybrid_device_mesh(
            mesh_shape=tuple(ici_axes.values()),
            dcn_mesh_shape=tuple(dcn.values()))
        return Mesh(devs, tuple(ici_axes.keys()))
    return build_mesh({k: ici_axes[k] * dcn[k] for k in ici_axes})


def data_parallel_mesh(n: Optional[int] = None,
                       devices: Optional[Sequence] = None) -> Mesh:
    devices = list(devices if devices is not None else jax.devices())
    n = n or len(devices)
    return build_mesh({"data": n}, devices)


# --------------------------------------------------------------------- shardings
# Layout DECISIONS live in parallel/partition.py (the rule engine); these are
# thin delegates kept for API stability. The old per-param Megatron rules
# (``param_pspec``) are now the engine's ``dp_tp`` rule set.
def batch_sharding(mesh: Mesh):
    """Shard leading (batch) dim over 'data'."""
    from deeplearning4j_tpu.parallel import partition
    return partition.named_sharding(mesh, partition.pspec("data"))


def replicated(mesh: Mesh):
    from deeplearning4j_tpu.parallel import partition
    return partition.named_sharding(mesh)


def shard_params_for_tp(params_tree, conf, mesh: Mesh, model_axis: str = "model"):
    """Apply tensor-parallel shardings to a params pytree (list- or
    dict-style) via the ``dp_tp`` partition rules — Megatron column/row
    splits for dense/attention/MoE weights; indivisible or tiny leaves stay
    replicated. XLA GSPMD inserts the all-gathers/reduce-scatters the
    shardings imply — nothing manual."""
    from deeplearning4j_tpu.parallel import partition
    specs = partition.match_partition_rules(
        partition.dp_tp_rules(model_axis), params_tree, mesh=mesh, conf=conf)
    return partition.device_put(params_tree, mesh, specs)
