"""``kernel.dense_roofline`` for the cell of ``lfm2-24b-a2b-ep8``: the accepted reader of
``metrics/kernel.dense_roofline.py``, under a name of its own because a cell added after
PR 34 may edit no file the benchmark has (benchmark/README.md)."""
import costs_sparse

read = costs_sparse.accepted_reader("kernel.dense_roofline")
