"""Operations and bytes of an attention whose keys are chosen per query by a
learned indexer, from shapes and counts alone (``costs.py``'s rules: the
products' 2 x multiply-accumulates, forward and two gradient products;
recomputed work, masked entries and the selection itself, which requires no
product, are not counted). ``costs.least_seconds`` turns a pair into the
roofline's time. Also the two things every reader of a cell that came after
PR 34 needs: the accepted reader of another metric's file (a later cell may
edit no file, so it brings a metric of its own name over the same reader),
and the block's kwargs."""
from __future__ import annotations

import importlib.util
import os

import costs_window

METRICS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "metrics")
INDEXER = r"/attn/indexer(/|$)"
SELECT = r"/attn/indexer/select(/|$)"


def selected_pairs(seq: int, topk: int) -> int:
    """(query, key) pairs a sequence's selection keeps: query t keeps
    ``min(t + 1, topk)`` keys, the count of a causal mask cut to a
    ``topk``-key window."""
    return costs_window.visible_pairs(seq, topk)


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def selected_core(batch: int, heads: int, kv_heads: int, seq: int, width: int,
                  topk: int, itemsize: int = 2) -> tuple:
    """``(flops, bytes)`` of one block's core over the selection for
    ``batch`` sequences, forward and backward: scores and values at the
    selected pairs of every query head, ``width`` wide each, and twice that
    for the gradients. Bytes: queries and outputs per query head, keys and
    values per key/value head, read or written once forward, and they and
    their gradients once backward."""
    flops = 3 * 2.0 * batch * heads * selected_pairs(seq, topk) * 2 * width
    tensors = batch * seq * width * (2 * heads + 2 * kv_heads)
    return flops, 3 * tensors * itemsize


def index_scores(batch: int, heads: int, width: int, seq: int, topk: int,
                 itemsize: int = 2) -> tuple:
    """``(flops, bytes)`` of one block's index scores for ``batch``
    sequences: ``heads`` products ``width`` wide at every causal pair
    forward, and the two gradient products at the selected pairs alone (the
    loss reaches no other). Bytes: the queries (``heads x width`` a
    position), the one key head and the weights, read once forward, and they
    and their gradients once backward."""
    flops = 2.0 * batch * heads * width * (
        causal_pairs(seq) + 2 * selected_pairs(seq, topk))
    tensors = batch * seq * (heads * width + width + heads)
    return flops, 3 * tensors * itemsize


def accepted_reader(name: str):
    """``read`` of the accepted metric ``metrics/<name>.py``: what a later
    cell's metric of its own name calls, so that it reads what the cells
    before it read."""
    spec = importlib.util.spec_from_file_location(
        "accepted_" + name.replace(".", "_").replace("-", "_"),
        os.path.join(METRICS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def indexer_kwargs(ctx: dict):
    """The configuration's builder kwargs where it names an indexer, else
    None (a reader then has nothing to read)."""
    kw = ctx["cell"]["config"]["builder"]["kwargs"]
    return kw if kw.get("index_n_heads") and kw.get("index_topk") else None
