"""``moe.expert_load_max_over_mean`` for the cell of ``nemotron-3-nano-30b-a3b-ep16``: the accepted reader of
``metrics/moe.expert_load_max_over_mean.py``, under a name of its own because a cell may edit no
file the benchmark has (benchmark/README.md)."""
import costs_sparse

read = costs_sparse.accepted_reader("moe.expert_load_max_over_mean")
