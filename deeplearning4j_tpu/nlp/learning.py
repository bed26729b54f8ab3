"""Batched embedding-training kernels.

Reference: models/embeddings/learning/impl/elements/{SkipGram,CBOW}.java — the
reference queues AggregateSkipGram ops and executes the batch natively
(SkipGram.java:168-178). TPU-native equivalent: ONE jit-compiled step per batch
of training pairs, with gathers + scatter-adds over the embedding matrices.
Hierarchical softmax (:225) and negative sampling (:258) both supported; CBOW
and PV-DM reuse the same kernel with multi-token inputs (masked mean).

Update convention matches classic word2vec (and the reference): for a pair the
input vector is h = mean(syn0[ctx]) (single token for skip-gram), outputs are
the target word's Huffman path (syn1) and/or sampled negatives (syn1neg);
g = (label - sigmoid(h·v)) * lr; each input token receives the full
accumulated gradient (no 1/n scaling on the backward, as in word2vec C).
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


class PairBatch(NamedTuple):
    """One padded batch of training pairs (host-assembled, device-consumed)."""

    ctx: Array        # (B, W) int32 input-token indices
    ctx_mask: Array   # (B, W) float32 — 1 for real input tokens
    target: Array     # (B,) int32 target-word indices
    points: Array     # (B, L) int32 Huffman inner-node indices (HS)
    codes: Array      # (B, L) float32 Huffman branch codes (HS)
    code_mask: Array  # (B, L) float32 — 1 for real code positions
    pair_mask: Array  # (B,) float32 — 1 for real (non-padding) pairs
    update_dest: Array  # (B, W) int32 where input-gradients are scattered


#: vocab-size ceiling for the dense one-hot-matmul update path (auto mode).
#: A (rows, V) one-hot times (rows, D) update is exact scatter-add math on
#: the MXU — but it rewrites the WHOLE V x D table per chunk, so its HBM
#: traffic is O(V*D) regardless of how few rows changed. Calibrated
#: 2026-07-31 on a v5e, record not kept; re-derive in a cell: scatter won at
#: every measured vocab (V=2048 and V=10k), so the dense path is OFF by
#: default (ceiling 0) and remains an explicit opt-in via
#: DL4J_W2V_DENSE=1 for dtypes/shapes where a future chip's scatter unit is
#: the bottleneck.
DENSE_UPDATE_MAX_VOCAB = int(os.environ.get("DL4J_W2V_DENSE_MAX_VOCAB", "0"))


def resolve_dense_update(n_words: int) -> bool:
    """THE auto heuristic for the dense one-hot-matmul update path, shared
    with bench.py's A/B labeling: DL4J_W2V_DENSE=0/1 forces it; otherwise
    dense iff the vocab fits the ceiling AND there is an MXU (on CPU a
    one-hot matmul is orders of magnitude slower than scatter)."""
    env = os.environ.get("DL4J_W2V_DENSE")
    if env is not None:
        return env == "1"
    return (n_words <= DENSE_UPDATE_MAX_VOCAB
            and jax.default_backend() not in ("cpu",))


def _scatter_add(table, idx_flat, upd_flat, dense: bool):
    """table[idx] += upd with identical semantics on both paths: duplicate
    indices accumulate, out-of-range indices are dropped (one_hot yields a
    zero row exactly where scatter mode="drop" skips). precision=HIGHEST
    keeps the MXU pass float32-exact — without it TPU einsum rounds the
    updates to bfloat16 and the two paths diverge numerically."""
    if dense:
        oh = jax.nn.one_hot(idx_flat, table.shape[0], dtype=upd_flat.dtype)
        return table + jnp.einsum("nv,nd->vd", oh, upd_flat,
                                  precision=jax.lax.Precision.HIGHEST)
    return table.at[idx_flat].add(upd_flat, mode="drop")


def make_train_step(use_hs: bool, negative: int, chunk: int = 64,
                    dense_update: Optional[bool] = None):
    """Returns jitted step(syn0, syn1, syn1neg, cum_table, batch, lr, key).

    The batch is applied in sequential sub-chunks of ``chunk`` pairs via
    ``lax.scan`` inside the one compiled step: frequent rows (e.g. the Huffman
    root, in nearly every pair) would otherwise receive hundreds of colliding
    scatter-adds computed from one stale snapshot and diverge; chunking bounds
    the staleness to ``chunk`` pairs while keeping a single device dispatch
    (word2vec's update semantics are fully online, one pair at a time).

    ``dense_update`` routes the embedding-table updates through one-hot
    matmuls (MXU) instead of XLA scatter; None = auto via
    resolve_dense_update (an explicit argument always wins over the
    DL4J_W2V_DENSE env override so A/B twins stay distinct).
    DL4J_W2V_CHUNK=N overrides the chunk size at build time."""
    chunk = int(os.environ.get("DL4J_W2V_CHUNK", chunk))

    def apply_chunk(syn0, syn1, syn1neg, cum_table, batch: PairBatch, lr, key):
        B, W = batch.ctx.shape
        d = syn0.shape[1]
        dense = (dense_update if dense_update is not None
                 else resolve_dense_update(syn0.shape[0]))
        ctx_vecs = syn0[batch.ctx]                        # (B, W, D)
        cmask = batch.ctx_mask[..., None]                 # (B, W, 1)
        counts = jnp.maximum(jnp.sum(batch.ctx_mask, 1, keepdims=True), 1.0)
        h = jnp.sum(ctx_vecs * cmask, axis=1) / counts    # (B, D) masked mean
        neu1e = jnp.zeros((B, d), syn0.dtype)             # input-gradient accum

        if use_hs:
            p_vecs = syn1[batch.points]                   # (B, L, D)
            f = jax.nn.sigmoid(jnp.einsum("bd,bld->bl", h, p_vecs))
            # word2vec label = 1 - code
            g = ((1.0 - batch.codes - f) * lr
                 * batch.code_mask * batch.pair_mask[:, None])  # (B, L)
            neu1e = neu1e + jnp.einsum("bl,bld->bd", g, p_vecs)
            dsyn1 = jnp.einsum("bl,bd->bld", g, h)
            syn1 = _scatter_add(syn1, batch.points.reshape(-1),
                                dsyn1.reshape(-1, d), dense)

        if negative > 0:
            k = negative
            u = jax.random.uniform(key, (B, k))
            negs = jnp.searchsorted(cum_table, u).astype(jnp.int32)  # (B, k)
            tgts = jnp.concatenate([batch.target[:, None], negs], axis=1)  # (B,1+k)
            labels = jnp.concatenate(
                [jnp.ones((B, 1)), jnp.zeros((B, k))], axis=1)
            # sampled negative == true target ⇒ skip (word2vec: continue)
            valid = jnp.concatenate(
                [jnp.ones((B, 1), bool), negs != batch.target[:, None]], axis=1)
            n_vecs = syn1neg[tgts]                        # (B, 1+k, D)
            f = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", h, n_vecs))
            g = ((labels - f) * lr * valid
                 * batch.pair_mask[:, None])              # (B, 1+k)
            neu1e = neu1e + jnp.einsum("bk,bkd->bd", g, n_vecs)
            dneg = jnp.einsum("bk,bd->bkd", g, h)
            syn1neg = _scatter_add(syn1neg, tgts.reshape(-1),
                                   dneg.reshape(-1, d), dense)

        # scatter the accumulated input gradient to every real input token
        upd = (neu1e[:, None, :] * cmask
               * batch.pair_mask[:, None, None])          # (B, W, D)
        syn0 = _scatter_add(syn0, batch.update_dest.reshape(-1),
                            upd.reshape(-1, d), dense)
        return syn0, syn1, syn1neg

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(syn0, syn1, syn1neg, cum_table, batch: PairBatch, lr, key):
        B = batch.ctx.shape[0]
        S = min(chunk, B)
        if B % S != 0:  # lint: recompile-hazard-ok (trace-time chunk sizing; B is the fixed accumulator size, static under jit)
            S = B
        C = B // S
        chunked = jax.tree_util.tree_map(
            lambda a: a.reshape((C, S) + a.shape[1:]), batch)
        keys = jax.random.split(key, C)

        def body(carry, xs):
            s0, s1, sn = carry
            b, k = xs
            s0, s1, sn = apply_chunk(s0, s1, sn, cum_table, b, lr, k)
            return (s0, s1, sn), None

        (syn0, syn1, syn1neg), _ = jax.lax.scan(
            body, (syn0, syn1, syn1neg), (chunked, keys))
        return syn0, syn1, syn1neg

    return step


class BatchAccumulator:
    """Host-side pair accumulator producing fixed-shape PairBatches (replaces the
    reference's Aggregate op queue; fixed shapes keep one compiled step)."""

    def __init__(self, batch_size: int, window_width: int, code_length: int,
                 n_words: int):
        self.B = batch_size
        self.W = window_width
        self.L = code_length
        self.n_words = n_words
        self._rows: list = []

    def add(self, ctx_indices, target_idx: int, points, codes,
            update_dest=None) -> Optional[PairBatch]:
        self._rows.append((ctx_indices, target_idx, points, codes,
                           update_dest if update_dest is not None else ctx_indices))
        if len(self._rows) >= self.B:
            return self.flush()
        return None

    def flush(self) -> Optional[PairBatch]:
        if not self._rows:
            return None
        B, W, L = self.B, self.W, self.L
        ctx = np.zeros((B, W), np.int32)
        cmask = np.zeros((B, W), np.float32)
        tgt = np.zeros((B,), np.int32)
        pts = np.zeros((B, L), np.int32)
        codes = np.zeros((B, L), np.float32)
        pmask = np.zeros((B, L), np.float32)
        pair_mask = np.zeros((B,), np.float32)
        dest = np.full((B, W), self.n_words, np.int32)  # OOB ⇒ dropped by scatter
        for i, (c, t, p, cd, ud) in enumerate(self._rows):
            nc = min(len(c), W)
            ctx[i, :nc] = c[:nc]
            cmask[i, :nc] = 1.0
            dest[i, :nc] = ud[:nc]
            tgt[i] = t
            npts = min(len(p), L)
            pts[i, :npts] = p[:npts]
            codes[i, :npts] = cd[:npts]
            pmask[i, :npts] = 1.0
            pair_mask[i] = 1.0
        self._rows = []
        return PairBatch(jnp.asarray(ctx), jnp.asarray(cmask), jnp.asarray(tgt),
                         jnp.asarray(pts), jnp.asarray(codes), jnp.asarray(pmask),
                         jnp.asarray(pair_mask), jnp.asarray(dest))
