"""K-step batch grouping for fused-dispatch training loops.

The state machine under the one staged fit loop
(``nn.multilayer.LazyScore._fit_epoch_staged``, which both networks'
``fit_iterator`` and ``ParallelWrapper``'s synchronous loop run): accumulate
up to ``k`` same-shape host-staged minibatches, emit them as a group for one
stacked (K, B, ...) device dispatch, and route batches the caller declines
(masked, ragged tail) to the per-batch fallback.
"""
from __future__ import annotations

from typing import Callable, Iterable, Iterator, Tuple

import jax


def _shape_key(batch) -> list:
    return [a.shape for a in jax.tree_util.tree_leaves(batch)]


def k_step_groups(iterator: Iterable, k: int,
                  to_batch: Callable) -> Iterator[Tuple[str, object]]:
    """Yield ``("group", [batch, ...])`` (1 <= len <= k, identical shapes) or
    ``("single", ds)`` for datasets ``to_batch`` declines.

    ``to_batch(ds)`` returns a pytree of host (numpy) arrays to include the
    dataset in fused dispatch, or None to route it to the caller's per-batch
    fallback (masked batches, unsupported layouts). A shape change (e.g. the
    ragged final batch of an epoch) flushes the pending group first so groups
    always stack cleanly.
    """
    pending: list = []
    for ds in iterator:
        batch = to_batch(ds)
        if batch is None:
            if pending:
                yield "group", pending
                pending = []
            yield "single", ds
            continue
        if pending and _shape_key(batch) != _shape_key(pending[-1]):
            yield "group", pending
            pending = []
        pending.append(batch)
        if len(pending) == k:
            yield "group", pending
            pending = []
    if pending:
        yield "group", pending
