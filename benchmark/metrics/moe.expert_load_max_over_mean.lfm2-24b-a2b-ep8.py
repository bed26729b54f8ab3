"""``moe.expert_load_max_over_mean`` for the cell of ``lfm2-24b-a2b-ep8``: the accepted reader of
``metrics/moe.expert_load_max_over_mean.py``, under a name of its own because a cell added after
PR 34 may edit no file the benchmark has (benchmark/README.md)."""
import costs_sparse

read = costs_sparse.accepted_reader("moe.expert_load_max_over_mean")
