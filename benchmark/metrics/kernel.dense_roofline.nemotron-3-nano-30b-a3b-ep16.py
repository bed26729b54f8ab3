"""``kernel.dense_roofline`` for the cell of ``nemotron-3-nano-30b-a3b-ep16``: the accepted reader of
``metrics/kernel.dense_roofline.py``, under a name of its own because a cell may edit no
file the benchmark has (benchmark/README.md)."""
import costs_sparse

read = costs_sparse.accepted_reader("kernel.dense_roofline")
