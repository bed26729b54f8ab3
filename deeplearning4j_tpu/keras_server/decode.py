"""Continuous (iteration-level) batching for autoregressive decode.

The MicroBatcher (batcher.py) coalesces independent one-shot forwards —
right for classify/score traffic, wrong for generation: under
request-level batching a batch runs until its LONGEST sequence finishes,
so one long session holds every slot hostage and steady-state occupancy
collapses. This engine batches at the **iteration** level instead (the
ORCA scheduling model): ONE persistent decode step compiled per capacity
bucket runs every iteration over a fixed-capacity slot tensor; sessions
are admitted into free slots BETWEEN steps and evicted the step their
sequence ends, so the device batch stays full while individual sessions
churn.

What makes admission cheap is the state layout, grown from
``StreamSessions``' parked-state idiom into preallocated device-resident
**per-slot state blocks**:

- transformer, ``kv="dense"``: a KV cache ``[cap, max_context, heads,
  head_dim]`` per block, written at the slot's position each step and
  attention-masked to ``j <= position`` — a freed slot's stale keys are
  unreachable by construction, so admission never touches the cache;
- transformer, ``kv="paged"``: the SAME logical cache resolved through a
  per-slot page table over one fixed physical page pool
  ``[n_pages + 1, page_size, heads, head_dim]`` per block (paging.py).
  A slot consumes pages only for tokens it has written, so session count
  decouples from the context ceiling; sessions whose prompts share a
  prefix map the same physical pages copy-on-write (fork-on-write inside
  the compiled step), and refcounted pages return to the free list on
  eviction. The step scatters this iteration's k/v through the table,
  gathers the logical view back (ops/paged_attention.py), and runs the
  IDENTICAL masked attention math — the dense program is the bitwise
  oracle at every capacity bucket;
- LSTM (the PR 6 recurrent engine): ``h``/``c`` blocks ``[cap, hidden]``
  per layer, zeroed INSIDE the compiled step for slots flagged ``fresh``
  — admission is a host-side slot write, never a recompile.

Prompt prefill feeds prompt tokens one per step through the SAME compiled
program (teacher forcing; emitted tokens are discarded until the last
prompt token is consumed), so prompt length is not a compile axis: the
only compiles are the capacity buckets (powers of two, grown on demand),
pinned by tests/test_decode.py as ``compile count == bucket count``.

**Speculative decoding** (``draft_net=``): the teacher-forcing prefill
path generalizes to a T-token verify program — the same per-token math
unrolled ``spec_tokens + 1`` times in one dispatch. A small draft model
pinned alongside (same ModelRegistry) proposes ``spec_tokens`` tokens
per round; the target verifies all of them in ONE dispatch and accepts
the longest argmax-agreeing prefix, rolling its position back past the
first mismatch (rejected writes sit at ``j > position`` — stale by the
same masking invariant that free slot reuse relies on). Because
acceptance compares greedy argmax to greedy argmax, the emitted stream
is bitwise identical to plain greedy decode at ANY acceptance rate; the
win is dispatch amortization (and, on real hardware, HBM read reuse).

``mode="static"`` runs the SAME compiled step but only admits when every
slot has drained — the request-level baseline for the A/B in
scripts/serve_load.jsonl. Because per-slot math is row-independent (the
padding property test_serving.py pins for the MLP path), a
session's token stream is bitwise identical under either schedule.

Sampling is greedy argmax on device: deterministic, so continuous-vs-
static equality is exact, and the int8-vs-bf16 drift bound (ops/quant.py)
is measurable on the returned per-token distributions.
"""
from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.common import get_policy
from deeplearning4j_tpu.nn.conf.layers.attention import TransformerBlock
from deeplearning4j_tpu.nn.conf.layers.feedforward import EmbeddingLayer
from deeplearning4j_tpu.nn.conf.layers.recurrent import (
    GravesBidirectionalLSTM, LSTM, RnnOutputLayer,
)
from deeplearning4j_tpu.observability import names as _n
from deeplearning4j_tpu.observability.compile_tracker import (
    global_tracker as _compile_tracker,
)
from deeplearning4j_tpu.observability.flight_recorder import (
    global_recorder as _flight_recorder,
)
from deeplearning4j_tpu.observability.metrics import global_registry
from deeplearning4j_tpu.observability.profiler import (
    note_dispatch as _profile_note_dispatch,
)
from deeplearning4j_tpu.observability.tracing import (
    NOOP_SPAN, global_trace_store, start_span,
)
from deeplearning4j_tpu.observability.watchdog import beat as _wd_beat
from deeplearning4j_tpu.ops.paged_attention import paged_gather
from deeplearning4j_tpu.ops.quant import (
    dequantize_tree, gather_rows, quantize_tree, quantized_matmul,
    tree_param_bytes,
)

from .admission import RejectedError
from .paging import (TRASH_PAGE, PagePool, alloc_dense_kv, alloc_page_pool)

#: the compiled-program name of the persistent step — the compile tracker
#: records one event per capacity bucket under it (tests filter on this);
#: paged / draft / verify variants suffix it, so the filter still matches
DECODE_PROGRAM_NAME = "decode_step"

DECODE_MODES = ("continuous", "static")
DECODE_KV = ("dense", "paged")

#: occupancy fraction at which the engine starts background-compiling the
#: NEXT capacity bucket (continuous mode; growth would otherwise compile
#: synchronously mid-step the moment backlog arrives)
_PREWARM_OCCUPANCY = 0.75

log = logging.getLogger(__name__)


def _copy_tree(tree):
    return jax.tree_util.tree_map(lambda a: jnp.array(a), tree)


def _streaming_lstm(layer) -> bool:
    return isinstance(layer, LSTM) and not isinstance(
        layer, GravesBidirectionalLSTM)


class DecodeSession:
    """One generation request: a prompt plus a token budget.

    The engine appends generated tokens (and their host timestamps) as they
    materialize; ``result()`` blocks until eviction. ``t_sched`` is the
    OFFERED arrival time when the caller runs an open-loop schedule — TTFT
    is measured from it so a backed-up engine cannot hide queueing delay
    (no coordinated omission).
    """

    _next_sid = [0]
    _sid_lock = threading.Lock()

    def __init__(self, prompt, max_new_tokens: int,
                 t_sched: Optional[float] = None, stream=None):
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        if not prompt:
            raise ValueError("prompt must contain at least one token id")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        with self._sid_lock:
            self._next_sid[0] += 1
            self.sid = self._next_sid[0]
        self.prompt = prompt
        self.max_new_tokens = int(max_new_tokens)
        self.stream = stream
        self.tokens: List[int] = []        #: generated token ids
        self.token_times: List[float] = []  #: host perf_counter per token
        self.probs: List[np.ndarray] = []   #: per-token dists (opt-in)
        self.t_submit = time.perf_counter()
        self.t_sched = self.t_submit if t_sched is None else float(t_sched)
        self.t_first: Optional[float] = None
        self.t_done: Optional[float] = None
        self.evict_reason: Optional[str] = None
        self.done = threading.Event()
        # engine-internal slot bookkeeping
        self._prompt_idx = 0
        #: spec-decode stream history: every input token the target has
        #: consumed or will consume next (prompt + accepted emissions)
        self._hist: List[int] = []
        # request-trace spans, owned across threads via the session object
        # (contextvars do not follow the pump thread); all no-ops when
        # tracing is disabled
        self._span = NOOP_SPAN   #: decode.queue — submit -> admit
        self._span_phase = None  #: decode.prefill, then decode.decode
        self._span_park = None   #: open page-starvation episode
        #: per-session spec-decode tallies (stamped on the decode span)
        self._spec_proposed = 0
        self._spec_accepted = 0

    @property
    def ttft_s(self) -> Optional[float]:
        return None if self.t_first is None else self.t_first - self.t_sched

    def result(self, timeout: Optional[float] = None) -> List[int]:
        if not self.done.wait(timeout):
            raise TimeoutError(
                f"session {self.sid} not finished within {timeout}s")
        return self.tokens


# --------------------------------------------------------------- step builders
def _build_lstm_step(conf, quant: Optional[str], vocab: int):
    """Per-iteration step for LSTM stacks: one-hot the slot tokens, thread
    ``{h, c}`` slot blocks through ``apply_streaming`` (the PR 6 engine),
    zeroing state for ``fresh`` slots inside the program."""
    layers = conf.layers

    def step(params_list, state_list, blocks, tokens, fresh, positions):
        if quant == "int8":
            params_list = dequantize_tree(params_list)
        h = jax.nn.one_hot(tokens, vocab, dtype=jnp.float32)[:, None, :]
        new_blocks = []
        for i, layer in enumerate(layers):
            pp = conf.preprocessor(i)
            if pp is not None:
                h = pp.pre_process(h)
            if _streaming_lstm(layer):
                st = {
                    "h": jnp.where(fresh[:, None], 0.0, blocks[i]["h"]),
                    "c": jnp.where(fresh[:, None], 0.0, blocks[i]["c"]),
                }
                h, rs = layer.apply_streaming(params_list[i], st, h)
                new_blocks.append(rs)
            else:
                h, _ = layer.apply(params_list[i], state_list[i], h,
                                   train=False, rng=None)
                new_blocks.append(blocks[i])
        probs = h[:, -1, :]
        return jnp.argmax(probs, axis=-1).astype(jnp.int32), probs, new_blocks

    return step


class _DenseKV:
    """Dense cache adapter: write this step's k/v at each slot's position
    (the ``jnp.where`` one-hot row update), read back the stored block.
    THE oracle layout — the paged adapter must be bitwise-equal to it."""

    def __init__(self, blocks):
        self.blocks = list(blocks)

    def write_read(self, i, k, v, positions):
        K, V = self.blocks[i]["k"], self.blocks[i]["v"]
        tmax = K.shape[1]
        at_pos = (jnp.arange(tmax)[None, :]
                  == positions[:, None])[..., None, None]
        K = jnp.where(at_pos, k[:, None], K)
        V = jnp.where(at_pos, v[:, None], V)
        self.blocks[i] = {"k": K, "v": V}
        return K, V


class _PagedKV:
    """Paged cache adapter: scatter this step's k/v into the physical pool
    through the slot's page-table row, then gather the logical
    ``[cap, max_context, H, D]`` view back for attention. Positions at or
    past the context ceiling (including the parking sentinel) redirect
    the write to the trash page; the gathered garbage beyond a slot's
    mapped pages sits at ``j > position`` where the mask never looks —
    identical values to the dense block wherever the mask CAN look, which
    is what makes the two layouts bitwise-interchangeable."""

    def __init__(self, blocks, table, page_size):
        self.blocks = list(blocks)
        self.table = table
        self.page_size = page_size

    def write_read(self, i, k, v, positions):
        ps = self.page_size
        pool_k, pool_v = self.blocks[i]["k"], self.blocks[i]["v"]
        cap, P = self.table.shape
        in_range = positions < P * ps
        pidx = jnp.clip(positions // ps, 0, P - 1)
        rows = self.table[jnp.arange(cap), pidx]
        # active slots own their write page exclusively (the CoW planner's
        # invariant), so scatter indices never collide except on trash —
        # where every colliding row carries identical (garbage) values
        wp = jnp.where(in_range, rows, TRASH_PAGE)
        off = jnp.where(in_range, positions % ps, 0)
        pool_k = pool_k.at[wp, off].set(k)
        pool_v = pool_v.at[wp, off].set(v)
        self.blocks[i] = {"k": pool_k, "v": pool_v}
        K = paged_gather(pool_k, self.table)
        V = paged_gather(pool_v, self.table)
        return K, V


def _fork_pages(blocks, fork_src, fork_dst):
    """Apply this iteration's copy-on-write forks: one gather+scatter per
    pool copies page ``fork_src[c]`` onto ``fork_dst[c]`` for every slot
    (non-forking slots carry trash→trash, a self-copy of garbage). Runs
    BEFORE any write so a forked slot's history is in place when its
    write lands in the fresh page."""
    out = []
    for b in blocks:
        if b and "k" in b:
            out.append({"k": b["k"].at[fork_dst].set(b["k"][fork_src]),
                        "v": b["v"].at[fork_dst].set(b["v"][fork_src])})
        else:
            out.append(b)
    return out


def _tf_validate(conf):
    for i in range(len(conf.layers)):
        if conf.preprocessor(i) is not None:
            raise ValueError(
                "decode does not support preprocessors in transformer "
                "stacks; got one before layer "
                f"{i} ({type(conf.layers[i]).__name__})")


def _tf_forward(layers, params_list, tokens, positions, kv):
    """ONE token through the transformer stack for every slot — the shared
    core of the single-token step, the paged step and the T-token verify
    program. Keeping the math in one function is what makes the
    paged-vs-dense and spec-vs-greedy bitwise contracts hold by
    construction: every variant runs these exact ops, only
    ``kv.write_read`` differs (and it is pure data movement)."""
    pol = get_policy()
    od, cd = pol.output_dtype, pol.compute_dtype
    cap = tokens.shape[0]
    x = None
    for i, layer in enumerate(layers):
        p = params_list[i]
        if isinstance(layer, EmbeddingLayer):
            x = (gather_rows(p["W"], tokens) + p["b"]).astype(od)
            x = layer.act_fn()(x)
        elif isinstance(layer, TransformerBlock):
            F = layer.n_out
            H = layer.n_heads
            D = F // H
            h = TransformerBlock._ln(x, p["ln1_g"], p["ln1_b"])
            qkv = quantized_matmul(h.astype(cd), p["Wqkv"],
                                   compute_dtype=cd)
            q, k, v = jnp.split(qkv.astype(od), 3, axis=-1)
            q = q.reshape(cap, H, D)
            k = k.reshape(cap, H, D)
            v = v.reshape(cap, H, D)
            K, V = kv.write_read(i, k, v, positions)
            tmax = K.shape[1]
            # a freed slot's stale cache rows sit at j > position of the
            # next tenant, so masking to j <= position doubles as the
            # admission reset — no cache zeroing on slot reuse
            valid = (jnp.arange(tmax)[None, None, :]
                     <= positions[:, None, None])
            s = jnp.einsum("chd,cthd->cht", q.astype(jnp.float32),
                           K.astype(jnp.float32)) / jnp.sqrt(
                               jnp.float32(D))
            s = jnp.where(valid, s, jnp.float32(-1e30))
            w = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("cht,cthd->chd", w,
                           V.astype(jnp.float32)).reshape(cap, F)
            att = quantized_matmul(o.astype(cd), p["Wo"],
                                   compute_dtype=cd)
            x = x + att.astype(od) + p["bo"].astype(od)
            h = TransformerBlock._ln(x, p["ln2_g"], p["ln2_b"])
            h = quantized_matmul(h.astype(cd), p["W1"], compute_dtype=cd)
            h = jax.nn.gelu(h.astype(od) + p["b1"].astype(od))
            h = quantized_matmul(h.astype(cd), p["W2"], compute_dtype=cd)
            x = x + h.astype(od) + p["b2"].astype(od)
        elif isinstance(layer, RnnOutputLayer):
            logits = quantized_matmul(x.astype(cd), p["W"],
                                      compute_dtype=cd)
            x = layer.act_fn()(logits.astype(od) + p["b"].astype(od))
        else:
            raise ValueError(
                f"decode cannot stream layer {type(layer).__name__}")
    return jnp.argmax(x, axis=-1).astype(jnp.int32), x


def _build_transformer_step(conf, quant: Optional[str], vocab: int,
                            page_size: Optional[int] = None):
    """Per-iteration step for decoder-only transformer stacks: embed the
    slot tokens, write this step's k/v into each block's cache at the
    slot position, attend the single query over ``j <= position``, finish
    with the time-distributed output head. Matmuls that dominate the step
    route through :func:`ops.quant.quantized_matmul` so the int8 policy is
    dequant-free where the Pallas path allows. ``page_size`` switches the
    cache layout to the paged plane (extra table/fork args)."""
    layers = conf.layers
    _tf_validate(conf)

    if page_size is None:
        def step(params_list, state_list, blocks, tokens, fresh, positions):
            kv = _DenseKV(blocks)
            tok, probs = _tf_forward(layers, params_list, tokens,
                                     positions, kv)
            return tok, probs, kv.blocks
    else:
        def step(params_list, state_list, blocks, tokens, fresh, positions,
                 table, fork_src, fork_dst):
            blocks = _fork_pages(blocks, fork_src, fork_dst)
            kv = _PagedKV(blocks, table, page_size)
            tok, probs = _tf_forward(layers, params_list, tokens,
                                     positions, kv)
            return tok, probs, kv.blocks

    return step


def _build_transformer_verify(conf, quant: Optional[str], vocab: int,
                              T: int, page_size: Optional[int] = None):
    """The T-token spec-decode verify program: the single-token core
    unrolled T times in ONE dispatch (teacher forcing over the proposed
    tokens — PR 11's prefill path as a batched program). Token t writes
    KV at ``position + t`` and emits the argmax continuation, so the
    per-position outputs are the same ops in the same order as T separate
    single-token dispatches — bitwise equality with plain greedy decode
    is by construction, acceptance only decides which outputs count."""
    layers = conf.layers
    _tf_validate(conf)

    def _unroll(params_list, blocks, tokens, positions, kv):
        outs, prbs = [], []
        for t in range(T):
            tok, pr = _tf_forward(layers, params_list, tokens[:, t],
                                  positions + t, kv)
            outs.append(tok)
            prbs.append(pr)
        return jnp.stack(outs, axis=1), jnp.stack(prbs, axis=1)

    if page_size is None:
        def step(params_list, state_list, blocks, tokens, fresh, positions):
            kv = _DenseKV(blocks)
            outs, prbs = _unroll(params_list, blocks, tokens, positions, kv)
            return outs, prbs, kv.blocks
    else:
        def step(params_list, state_list, blocks, tokens, fresh, positions,
                 table, fork_src, fork_dst):
            blocks = _fork_pages(blocks, fork_src, fork_dst)
            kv = _PagedKV(blocks, table, page_size)
            outs, prbs = _unroll(params_list, blocks, tokens, positions, kv)
            return outs, prbs, kv.blocks

    return step


class DecodeEngine:
    """Persistent decode loop with slot-level admission/eviction.

    ``submit()`` queues a session; one daemon pump thread admits, steps and
    evicts. ``mode="continuous"`` admits into any free slot between steps;
    ``mode="static"`` (the request-level baseline) admits only when the
    whole batch has drained. ``quant="int8"`` pins the engine's parameter
    snapshot under the int8 serving DtypePolicy (ops/quant.py).

    ``kv="paged"`` (transformer only) swaps the dense per-slot KV blocks
    for the paged memory plane: ``n_pages`` physical pages of
    ``page_size`` tokens each, shared copy-on-write across sessions with
    equal prompt prefixes. ``draft_net`` (transformer only) enables
    speculative decoding: ``spec_tokens`` proposals per round from the
    draft, verified by the target in one multi-token dispatch.
    """

    def __init__(self, net, *, max_context: int = 128, min_slots: int = 2,
                 max_slots: int = 16, eos_id: Optional[int] = None,
                 mode: str = "continuous", quant: Optional[str] = None,
                 capture_probs: bool = False, max_queue: int = 4096,
                 metrics=None, kv: str = "dense", page_size: int = 16,
                 n_pages: Optional[int] = None, draft_net=None,
                 spec_tokens: int = 3):
        if mode not in DECODE_MODES:
            raise ValueError(f"mode must be one of {DECODE_MODES}, "
                             f"got {mode!r}")
        if kv not in DECODE_KV:
            raise ValueError(f"kv must be one of {DECODE_KV}, got {kv!r}")
        if not (1 <= min_slots <= max_slots):
            raise ValueError("need 1 <= min_slots <= max_slots")
        net._require_init()
        conf = net.conf
        out = conf.layers[-1]
        if not isinstance(out, RnnOutputLayer):
            raise ValueError(
                "decode needs a time-distributed output head "
                f"(RnnOutputLayer), got {type(out).__name__}")
        self.vocab = int(out.n_out)
        first = conf.layers[0]
        if int(first.n_in) != self.vocab:
            raise ValueError(
                f"decode feeds outputs back as inputs: first-layer n_in "
                f"{first.n_in} must equal output vocab {self.vocab}")
        has_tf = any(isinstance(l, TransformerBlock) for l in conf.layers)
        has_lstm = any(_streaming_lstm(l) for l in conf.layers)
        if has_tf and has_lstm:
            raise ValueError("decode supports pure-LSTM or pure-transformer "
                             "stacks, not a mix")
        if not (has_tf or has_lstm):
            raise ValueError(
                "decode needs a stateful sequence model (LSTM stack or "
                "TransformerBlock stack)")
        if any(isinstance(l, GravesBidirectionalLSTM) for l in conf.layers):
            raise ValueError("bidirectional LSTMs cannot stream "
                             "(the backward pass needs the full sequence)")
        self.kind = "transformer" if has_tf else "lstm"
        self.mode = mode
        self.max_context = int(max_context)
        self.min_slots = int(min_slots)
        self.max_slots = int(max_slots)
        self.eos_id = eos_id
        self.capture_probs = bool(capture_probs)
        self.quant = "int8" if quant == "int8" else None
        self.kv = kv
        self.page_size = int(page_size)
        self._net = net
        self._conf = conf
        # ---- paged memory plane ----
        self._pool: Optional[PagePool] = None
        if kv == "paged":
            if self.kind != "transformer":
                raise ValueError(
                    "kv='paged' needs a transformer stack (LSTM state is "
                    "h/c vectors, not a KV cache)")
            if self.page_size < 1 or self.max_context % self.page_size:
                raise ValueError(
                    f"max_context {self.max_context} must be a multiple of "
                    f"page_size {self.page_size}")
            self._pages_per_slot = self.max_context // self.page_size
            if n_pages is None:
                # capacity parity with the dense layout at max_slots
                n_pages = self.max_slots * self._pages_per_slot
            if int(n_pages) < 1:
                raise ValueError("n_pages must be >= 1")
            self._n_pages = int(n_pages)
            self._pool = PagePool(self._n_pages, self.page_size)
        # ---- speculative decoding ----
        self._spec_draft = None
        self.spec_tokens = int(spec_tokens)
        if draft_net is not None:
            if self.kind != "transformer":
                raise ValueError("speculative decoding needs a transformer "
                                 "target (the verify program is the "
                                 "teacher-forcing prefill path)")
            draft_net._require_init()
            dconf = draft_net.conf
            dout = dconf.layers[-1]
            if not isinstance(dout, RnnOutputLayer) \
                    or int(dout.n_out) != self.vocab:
                raise ValueError(
                    "draft model must share the target's vocab "
                    f"({self.vocab}) and end in an RnnOutputLayer")
            if not any(isinstance(l, TransformerBlock)
                       for l in dconf.layers):
                raise ValueError("draft model must be a transformer stack")
            if self.spec_tokens < 1:
                raise ValueError("spec_tokens must be >= 1")
            self._spec_draft = draft_net
            self._draft_conf = dconf
        # pinned snapshot, exactly like PredictFn: a later fit() on `net`
        # donates its own buffers, never these
        self._params = _copy_tree(net.params_list)
        self._states = _copy_tree(net.state_list)
        if self.quant == "int8":
            self._params = quantize_tree(self._params)
        ps_arg = self.page_size if kv == "paged" else None
        extra = (("kv", self.kv, "page_size", self.page_size,
                  "n_pages", self._n_pages) if kv == "paged" else ())
        suffix = ("+int8" if self.quant else "") \
            + (":paged" if kv == "paged" else "")
        # blocks (arg 2) are donated: the step updates every slot cache in
        # place instead of allocating a second copy of the KV blocks
        self._step = self._draft_step = self._verify_step = None
        if self._spec_draft is None:
            builder = (_build_lstm_step if self.kind == "lstm"
                       else _build_transformer_step)
            if self.kind == "lstm":
                fn = builder(conf, self.quant, self.vocab)
            else:
                fn = builder(conf, self.quant, self.vocab, page_size=ps_arg)
            self._step = net._jit(DECODE_PROGRAM_NAME + suffix, fn,
                                  donate=(2,), extra=extra)
        else:
            self._verify_T = self.spec_tokens + 1
            self._verify_step = net._jit(
                DECODE_PROGRAM_NAME + suffix + f":verify{self._verify_T}",
                _build_transformer_verify(conf, self.quant, self.vocab,
                                          self._verify_T, page_size=ps_arg),
                donate=(2,), extra=extra + ("spec", self._verify_T))
            self._draft_params = _copy_tree(draft_net.params_list)
            self._draft_states = _copy_tree(draft_net.state_list)
            self._draft_step = draft_net._jit(
                DECODE_PROGRAM_NAME + ":draft",
                _build_transformer_step(self._draft_conf, None, self.vocab),
                donate=(2,))
        m = metrics or global_registry()
        self._g_occupancy = m.gauge(
            _n.SERVE_SLOT_OCCUPANCY,
            "active decode slots / slot capacity of the last step")
        self._h_growth_stall = m.histogram(
            _n.SERVE_BUCKET_GROWTH_STALL_SECONDS,
            "first-step dispatch time of each new capacity bucket (the "
            "live-traffic stall growth causes; pre-warmed buckets show "
            "steady-state step time here)")
        self._h_ttft = m.histogram(
            _n.SERVE_TTFT_SECONDS,
            "offered-arrival to first generated token")
        self._c_tokens = m.counter(
            _n.SERVE_TOKENS_TOTAL, "generated tokens streamed to sessions")
        self._c_evictions = m.counter(
            _n.SERVE_EVICTIONS_TOTAL, "slot evictions by reason")
        self._g_pages = m.gauge(
            _n.DECODE_PAGES_IN_USE,
            "physical KV pages currently mapped by live slots")
        self._g_share = m.gauge(
            _n.DECODE_PREFIX_SHARE_RATIO,
            "prompt tokens served from shared prefix pages / prompt "
            "tokens admitted (cumulative)")
        self._g_accept = m.gauge(
            _n.DECODE_SPEC_ACCEPTANCE,
            "spec-decode proposals accepted / proposals offered "
            "(cumulative)")
        self._c_spec = m.counter(
            _n.DECODE_SPEC_TOKENS_TOTAL,
            "spec-decode draft proposals by verify outcome")
        self._c_copy = m.counter(
            _n.DECODE_STATE_COPY_BYTES_TOTAL,
            "host bytes copied moving per-slot decode state across "
            "capacity buckets (device block moves are a single on-device "
            "scatter and do not count)")
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queue: deque = deque()
        self.max_queue = int(max_queue)
        self._closed = False
        self._cap = 0
        self._slots: List[Optional[DecodeSession]] = []
        self._tokens_h = np.zeros((0,), np.int32)
        self._pos_h = np.zeros((0,), np.int32)
        self._fresh_h = np.zeros((0,), bool)
        self._table_h = np.zeros((0, 0), np.int32)
        self._fork_src_h = np.zeros((0,), np.int32)
        self._fork_dst_h = np.zeros((0,), np.int32)
        self._park_h = np.zeros((0,), bool)
        self._dpos_h = np.zeros((0,), np.int32)
        self._blocks = None
        self._draft_blocks = None
        self._copy_bytes = 0
        self._grow_to(self.min_slots)
        self._steps = 0
        self._generated = 0
        self._evicted = 0
        self._occupancy_sum = 0.0
        self._peak_active = 0
        self._shared_tokens = 0
        self._prompt_tokens = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._buckets: set = set()
        #: capacity buckets a background pre-warm has been started for
        self._warming: set = set()
        self._thread = threading.Thread(
            target=self._loop, name="serve-decode-engine", daemon=True)
        self._thread.start()

    # ------------------------------------------------------------- slot state
    def _zero_blocks(self, cap: int):
        """Preallocated per-slot state blocks for one capacity bucket.
        Paged pools are capacity-INdependent: every bucket shares the one
        physical pool, so this allocates fresh pools only for pre-warm
        probes (the live pool rides ``self._blocks``)."""
        blocks = []
        for layer in self._conf.layers:
            if self.kind == "lstm" and _streaming_lstm(layer):
                h = int(layer.n_out)
                blocks.append(
                    {"h": jnp.zeros((cap, h), jnp.float32),
                     "c": jnp.zeros((cap, h), jnp.float32)})
            elif self.kind == "transformer" \
                    and isinstance(layer, TransformerBlock):
                hd = int(layer.n_out) // int(layer.n_heads)
                if self.kv == "paged":
                    blocks.append(alloc_page_pool(
                        self._n_pages, self.page_size,
                        int(layer.n_heads), hd))
                else:
                    blocks.append(alloc_dense_kv(
                        cap, self.max_context, int(layer.n_heads), hd))
            else:
                blocks.append({})
        return blocks

    def _zero_draft_blocks(self, cap: int):
        blocks = []
        for layer in self._draft_conf.layers:
            if isinstance(layer, TransformerBlock):
                hd = int(layer.n_out) // int(layer.n_heads)
                blocks.append(alloc_dense_kv(
                    cap, self.max_context, int(layer.n_heads), hd))
            else:
                blocks.append({})
        return blocks

    #: requires-lock: _cond
    def _grow_to(self, cap: int) -> None:
        """Move to a larger capacity bucket. Dense blocks move with ONE
        device-side scatter per leaf (``.at[:old].set`` — never a host
        round-trip per slot); the paged pool is capacity-independent and
        moves nothing. What the host DOES copy (slot arrays, page tables)
        is billed to ``dl4j_decode_state_copy_bytes_total``."""
        old = self._cap
        self._slots += [None] * (cap - old)
        copied = 0
        for name_ in ("_tokens_h", "_pos_h", "_fresh_h", "_fork_src_h",
                      "_fork_dst_h", "_park_h", "_dpos_h"):
            a = getattr(self, name_)
            grown = np.zeros((cap,), a.dtype)
            grown[:old] = a
            copied += a.nbytes
            setattr(self, name_, grown)
        if self._pool is not None:
            t = np.full((cap, self._pages_per_slot), TRASH_PAGE, np.int32)
            if old:
                t[:old] = self._table_h
            copied += self._table_h.nbytes
            self._table_h = t
            if self._blocks is None:
                self._blocks = self._zero_blocks(cap)
        else:
            new_blocks = self._zero_blocks(cap)
            if self._blocks is not None and old:
                new_blocks = jax.tree_util.tree_map(
                    lambda z, a: z.at[:old].set(a), new_blocks, self._blocks)
            self._blocks = new_blocks
        if self._spec_draft is not None:
            new_draft = self._zero_draft_blocks(cap)
            if self._draft_blocks is not None and old:
                new_draft = jax.tree_util.tree_map(
                    lambda z, a: z.at[:old].set(a), new_draft,
                    self._draft_blocks)
            self._draft_blocks = new_draft
        self._cap = cap
        self._copy_bytes += copied
        self._c_copy.inc(copied)

    # --------------------------------------------------------------- producer
    def submit(self, prompt, max_new_tokens: int = 32,
               t_sched: Optional[float] = None,
               stream=None) -> DecodeSession:
        """Queue one generation session; returns immediately."""
        sess = DecodeSession(prompt, max_new_tokens, t_sched=t_sched,
                             stream=stream)
        # parented under the ambient span (the HTTP handler's root) on
        # THIS thread; the pump finishes it cross-thread via the session
        sess._span = start_span("decode.queue", sid=sess.sid,
                                prompt_len=len(sess.prompt),
                                max_new=sess.max_new_tokens)
        try:
            bad = [t for t in sess.prompt if not 0 <= t < self.vocab]
            if bad:
                raise ValueError(f"prompt token ids {bad} outside vocab "
                                 f"[0, {self.vocab})")
            if self._pool is not None:
                span = min(len(sess.prompt) + sess.max_new_tokens,
                           self.max_context)
                worst = -(-span // self.page_size)
                if worst > self._n_pages:
                    # the session can NEVER fit this pool — fail fast with
                    # the 429 the HTTP layer already maps, not a
                    # mid-decode OOM
                    raise RejectedError(worst, self._n_pages, 60.0)
            with self._cond:
                if self._closed:
                    raise RuntimeError("DecodeEngine is closed")
                if len(self._queue) >= self.max_queue:
                    # Retry-After: the backlog drains roughly a session
                    # per slot per active session's remaining budget; 1s
                    # is the honest coarse answer at this layer
                    raise RejectedError(len(self._queue), self.max_queue,
                                        1.0)
                self._queue.append(sess)
                self._cond.notify()
        except RejectedError:
            sess._span.set_status("rejected").finish()
            raise
        except Exception:
            sess._span.set_status("error").finish()
            raise
        return sess

    # ----------------------------------------------------------------- pump
    def _active_count(self) -> int:
        return sum(1 for s in self._slots if s is not None)

    #: requires-lock: _cond
    def _admit_locked(self) -> None:
        """Under the lock: move queued sessions into free slots.

        Continuous mode admits whenever a slot is free; static mode admits
        only into a fully-drained batch (the request-level baseline). Both
        grow the capacity bucket (a new compile, power-of-two) when demand
        outruns the current one. Paged engines additionally gate on free
        pages (FIFO — no head-of-line bypass) and map any registered
        prefix pages copy-on-write before the first step."""
        active = self._active_count()
        if self.mode == "static" and active:
            return
        while self._queue and active >= self._cap \
                and self._cap < self.max_slots:
            self._grow_to(min(self._cap * 2, self.max_slots))
        for i in range(self._cap):
            if not self._queue:
                break
            if self._slots[i] is not None:
                continue
            sess = self._queue[0]
            skip = 0
            if self._pool is not None:
                pids, covered = self._pool.match_prompt(sess.prompt)
                ps = self.page_size
                fresh_pages = (-(-len(sess.prompt) // ps)) - len(pids) \
                    + (1 if covered % ps else 0)
                if self._pool.free_pages < fresh_pages + 1:
                    break
                for k, pid in enumerate(pids):
                    self._pool.incref(pid)
                    self._table_h[i, k] = pid
                skip = min(covered, len(sess.prompt) - 1)
                self._shared_tokens += skip
                self._prompt_tokens += len(sess.prompt)
            self._queue.popleft()
            self._slots[i] = sess
            self._tokens_h[i] = sess.prompt[skip]
            self._pos_h[i] = skip
            self._fresh_h[i] = True
            sess._prompt_idx = skip
            sess._span.set_attr(slot=i, skip=skip)
            sess._span.finish()
            sess._span_phase = start_span(
                "decode.prefill", parent=self._span_parent(sess),
                sid=sess.sid, prompt_len=len(sess.prompt), skip=skip)
            if self._spec_draft is not None:
                sess._hist = list(sess.prompt)
                self._dpos_h[i] = 0
            active += 1
        if self._prompt_tokens:
            self._g_share.set(self._shared_tokens / self._prompt_tokens)
        self._peak_active = max(self._peak_active, active)

    #: requires-lock: _cond
    def _release_pages_locked(self, i: int) -> None:
        row = self._table_h[i]
        for pid in {int(x) for x in row.tolist()} - {TRASH_PAGE}:
            self._pool.decref(pid)
        row[:] = TRASH_PAGE

    @staticmethod
    def _span_parent(sess):
        """The session's queue span as a parent, or None so a real span
        never parents under the no-op singleton's empty trace id."""
        return sess._span if sess._span is not NOOP_SPAN else None

    #: requires-lock: _cond
    def _trace_evict_locked(self, sess, reason: str) -> None:
        """Close the session's open spans at eviction: preemption emits an
        instant ``decode.preempt`` span so the victim's trace names why it
        ended mid-stream, step errors flip the phase span's status (the
        tail sampler then always keeps the trace)."""
        if sess._span_park is not None:
            sess._span_park.set_attr(evicted=True)
            sess._span_park.finish()
            sess._span_park = None
        if reason == "pool_exhausted":
            start_span("decode.preempt", parent=self._span_parent(sess),
                       sid=sess.sid).finish()
        sp = sess._span_phase
        if sp is not None:
            if reason == "error":
                sp.set_status("error")
            sp.set_attr(reason=reason, tokens=len(sess.tokens))
            if sess._spec_proposed:
                sp.set_attr(spec_proposed=sess._spec_proposed,
                            spec_accepted=sess._spec_accepted)
            sp.finish()
            sess._span_phase = None
        sess._span.finish()  # idempotent; covers never-admitted paths

    #: requires-lock: _cond
    def _evict_locked(self, i: int, reason: str) -> None:
        sess = self._slots[i]
        self._slots[i] = None
        if self._pool is not None:
            self._release_pages_locked(i)
        self._evicted += 1
        self._c_evictions.labels(reason=reason).inc()
        self._trace_evict_locked(sess, reason)
        sess.evict_reason = reason
        sess.t_done = time.perf_counter()
        sess.done.set()

    # -------------------------------------------------------- page planning
    #: requires-lock: _cond
    def _map_window_locked(self, i: int, window: int) -> bool:
        """Ensure slot ``i`` owns pages for its next ``window`` write
        positions: allocate unmapped pages, copy-on-write-fork shared
        ones. False = exhaustion (caller parks or preempts); partial
        allocations stay mapped — they are owned, a retry reuses them."""
        pool, ps = self._pool, self.page_size
        pos = int(self._pos_h[i])
        for t in range(window):
            q = pos + t
            if q >= self.max_context:
                break  # clamped to the trash page in-step
            k = q // ps
            pid = int(self._table_h[i, k])
            if pid == TRASH_PAGE:
                npid = pool.alloc()
                if npid is None:
                    return False
                self._table_h[i, k] = npid
            elif pool.refcount(pid) > 1:
                npid = pool.alloc()
                if npid is None:
                    return False
                if q % ps:
                    # mid-page: earlier offsets hold this slot's live
                    # history — device-copies src→dst inside the step.
                    # Only the FIRST window page can be shared (sharing
                    # covers written positions only), so the single
                    # fork-per-slot register never collides; park if a
                    # second copy somehow arises rather than lose one.
                    if int(self._fork_dst_h[i]) != TRASH_PAGE:
                        pool.decref(npid)
                        return False
                    self._fork_src_h[i] = pid
                    self._fork_dst_h[i] = npid
                pool.decref(pid)
                self._table_h[i, k] = npid
        return True

    #: requires-lock: _cond
    def _plan_pages_locked(self, window: int) -> None:
        """Map every active slot's write window; on total exhaustion (no
        slot can move) preempt the YOUNGEST tenant so the rest make
        progress — pool pressure degrades to parking, never to OOM."""
        self._fork_src_h[:] = TRASH_PAGE
        self._fork_dst_h[:] = TRASH_PAGE
        self._park_h[:] = False
        pending = [i for i in range(self._cap)
                   if self._slots[i] is not None]
        any_live = False
        while True:
            still = []
            for i in pending:
                if self._map_window_locked(i, window):
                    any_live = True
                else:
                    still.append(i)
            if any_live or not still:
                for i in still:
                    self._park_h[i] = True
                break
            victim = max(still, key=lambda i: self._slots[i].sid)
            self._evict_locked(victim, "pool_exhausted")
            pending = [i for i in still if i != victim]
            if not pending:
                break
        # park-episode spans: one span per contiguous starved stretch, so
        # a trace shows exactly when pool pressure stalled the session
        for i in range(self._cap):
            sess = self._slots[i]
            if sess is None:
                continue
            if self._park_h[i]:
                if sess._span_park is None:
                    sess._span_park = start_span(
                        "decode.park", parent=self._span_parent(sess),
                        sid=sess.sid, reason="pool_exhausted")
            elif sess._span_park is not None:
                sess._span_park.finish()
                sess._span_park = None
        self._g_pages.set(self._pool.pages_in_use)

    #: requires-lock: _cond
    def _register_prefix_locked(self, i: int, sess, lo: int,
                                hi: int) -> None:
        """Publish the prompt pages slot ``i`` finished writing in
        ``[lo, hi)`` so later sessions can map them copy-on-write.
        Generated positions are never registered — sharing is a prompt
        (system-prefix) property."""
        ps = self.page_size
        for q in range(lo, min(hi, len(sess.prompt))):
            self._pool.register(sess.prompt[:q + 1],
                                int(self._table_h[i, q // ps]))

    def _note_first_token(self, sess, ttft: float) -> None:
        """Prefill -> decode phase flip on the session's trace, plus the
        TTFT exemplar so a burning TTFT SLO can name this trace."""
        sp = sess._span_phase
        if sp is None:
            return
        sp.set_attr(ttft_s=round(ttft, 6))
        sp.finish()
        sess._span_phase = start_span(
            "decode.decode", parent=self._span_parent(sess), sid=sess.sid)
        if sp.trace_id:
            global_trace_store().put_exemplar(
                _n.SERVE_TTFT_SECONDS, ttft, sp.trace_id)

    def _pump_once(self) -> bool:
        """One admit/step/bookkeep iteration; False when idle-and-closed."""
        if self._spec_draft is not None:
            return self._pump_once_spec()
        return self._pump_once_single()

    def _pump_once_single(self) -> bool:
        with self._cond:
            while True:
                self._admit_locked()
                if self._active_count():
                    break
                if self._closed and not self._queue:
                    return False
                self._cond.wait(0.05)
            cap = self._cap
            if self._pool is not None:
                self._plan_pages_locked(1)
            active = [(i, self._slots[i]) for i in range(cap)
                      if self._slots[i] is not None]
            if not active:
                return True  # planning preempted the whole batch
            parked = self._park_h.copy() if self._pool is not None else None
            tokens = jnp.asarray(self._tokens_h)
            fresh = jnp.asarray(self._fresh_h)
            pos_np = self._pos_h.copy()
            if parked is not None:
                # parked slots write the trash page and advance nothing:
                # the sentinel position clamps their scatter out of range
                pos_np[parked] = self.max_context
            positions = jnp.asarray(pos_np)
            paged_args = ()
            if self._pool is not None:
                paged_args = (jnp.asarray(self._table_h),
                              jnp.asarray(self._fork_src_h),
                              jnp.asarray(self._fork_dst_h))
            blocks = self._blocks
            growing = cap not in self._buckets
        t0 = time.perf_counter()
        try:
            next_tok, probs, new_blocks = self._step(
                self._params, self._states, blocks, tokens, fresh,
                positions, *paged_args)
            next_h = np.asarray(next_tok)  # lint: host-sync-in-hot-loop-ok (the emitted token drives admission/eviction and feeds back as the next input; the sync IS the iteration boundary)
            probs_h = np.asarray(probs) if self.capture_probs else None
        except Exception as e:
            if growing:
                # evict-all is not the only signal a failed growth leaves:
                # this event names the bucket that never came up
                _flight_recorder().record(
                    "decode_bucket_growth_failed", cap=cap, mode=self.mode,
                    error=repr(e))
            _flight_recorder().dump(
                reason="decode-step-error",
                extra={"cap": cap, "mode": self.mode, "error": repr(e)})
            with self._cond:
                for i, sess in active:
                    self._evict_locked(i, "error")
            raise
        dt = time.perf_counter() - t0
        if growing:
            # first step at a new capacity: with a cold cache this dispatch
            # carries the XLA compile (the stall); warm it is step-sized
            self._h_growth_stall.labels(bucket=str(cap)).observe(dt)
        now = time.perf_counter()
        prewarm_cap = None
        with self._cond:
            self._blocks = new_blocks
            self._steps += 1
            self._buckets.add(cap)
            occupancy = len(active) / cap
            if (self.mode == "continuous" and cap < self.max_slots
                    and occupancy >= _PREWARM_OCCUPANCY):
                nxt = min(cap * 2, self.max_slots)
                if nxt not in self._buckets and nxt not in self._warming:
                    self._warming.add(nxt)
                    prewarm_cap = nxt
            self._occupancy_sum += occupancy
            n_steps = self._steps
            for i, sess in active:
                if parked is not None and parked[i]:
                    continue  # wrote trash; retry when pages free up
                p0 = int(self._pos_h[i])
                self._fresh_h[i] = False
                self._pos_h[i] += 1
                if self._pool is not None:
                    self._register_prefix_locked(i, sess, p0, p0 + 1)
                prefilling = sess._prompt_idx < len(sess.prompt) - 1
                if prefilling:
                    sess._prompt_idx += 1
                    self._tokens_h[i] = sess.prompt[sess._prompt_idx]
                else:
                    tok = int(next_h[i])
                    sess.tokens.append(tok)
                    sess.token_times.append(now)
                    if probs_h is not None:
                        sess.probs.append(probs_h[i].copy())
                    if sess.t_first is None:
                        sess.t_first = now
                        self._h_ttft.observe(now - sess.t_sched)
                        self._note_first_token(sess, now - sess.t_sched)
                    self._generated += 1
                    self._c_tokens.inc()
                    if sess.stream is not None:
                        sess.stream(sess.sid, tok, now)
                    if self.eos_id is not None and tok == self.eos_id:
                        self._evict_locked(i, "eos")
                        continue
                    if len(sess.tokens) >= sess.max_new_tokens:
                        self._evict_locked(i, "max_tokens")
                        continue
                    self._tokens_h[i] = tok
                if self.kind == "transformer" \
                        and self._pos_h[i] >= self.max_context:
                    self._evict_locked(i, "context")
        self._g_occupancy.set(occupancy)
        # a decode iteration advances the step clock like a fit/serve
        # dispatch: bucket-growth compiles are expected, steady-state
        # compiles are what the storm detector must catch
        _compile_tracker().note_step()
        _profile_note_dispatch(dt)
        _wd_beat(n_steps)
        if prewarm_cap is not None:
            threading.Thread(
                target=self._prewarm, args=(prewarm_cap,),
                name="serve-decode-prewarm", daemon=True).start()
        return True

    # ------------------------------------------------------------ spec pump
    def _pump_once_spec(self) -> bool:
        """One speculative round: γ draft proposals, one T-token verify
        dispatch, accept the longest argmax-agreeing prefix. Prefill rides
        the same round — prompt tokens are guaranteed-accept inputs — so
        the worst case (acceptance 0) degrades to exactly the plain
        engine's one token per dispatch, never below."""
        gamma = self.spec_tokens
        T = self._verify_T
        with self._cond:
            while True:
                self._admit_locked()
                if self._active_count():
                    break
                if self._closed and not self._queue:
                    return False
                self._cond.wait(0.05)
            cap = self._cap
            if self._pool is not None:
                self._plan_pages_locked(T)
            active = [(i, self._slots[i]) for i in range(cap)
                      if self._slots[i] is not None]
            if not active:
                return True
            parked = (self._park_h.copy() if self._pool is not None
                      else np.zeros((cap,), bool))
            paged_args = ()
            if self._pool is not None:
                paged_args = (jnp.asarray(self._table_h),
                              jnp.asarray(self._fork_src_h),
                              jnp.asarray(self._fork_dst_h))
            d0 = self._dpos_h.copy()
            base_pos = self._pos_h.copy()
            fresh = jnp.asarray(self._fresh_h)
            growing = cap not in self._buckets
        live = [(i, s) for i, s in active if not parked[i]]
        t0 = time.perf_counter()
        try:
            # ---- draft phase: γ single-token dispatches ----
            props = {i: {} for i, _ in active}   # stream index -> proposal
            dins = {i: [] for i, _ in active}    # tokens the draft consumed
            dcur = d0.copy()
            zeros_b = jnp.zeros((cap,), bool)
            for _ in range(gamma):
                dtok = np.zeros((cap,), np.int32)
                for i, s in live:
                    c = int(dcur[i])
                    tok = s._hist[c] if c < len(s._hist) else props[i][c]
                    dtok[i] = tok
                    dins[i].append(tok)
                dpos = dcur.copy()
                dpos[parked] = self.max_context
                # lint: lockguard-ok (KV blocks are pump-thread-confined: only the single pump thread touches them; _grow_to's locked writes run on that same thread)
                dout, _, self._draft_blocks = self._draft_step(
                    self._draft_params, self._draft_states,
                    self._draft_blocks, jnp.asarray(dtok), zeros_b,
                    jnp.asarray(dpos))
                dout_h = np.asarray(dout)  # lint: host-sync-in-hot-loop-ok (the proposal feeds the draft's own next input; the sync is the draft's iteration boundary)
                for i, s in live:
                    c = int(dcur[i])
                    if c + 1 >= len(s._hist):
                        props[i][c + 1] = int(dout_h[i])
                    dcur[i] = c + 1
            # ---- verify phase: one T-token dispatch ----
            vtok = np.zeros((cap, T), np.int32)
            trusted = {}
            for i, s in live:
                p = int(base_pos[i])
                row = []
                for t in range(T):
                    sidx = p + t
                    if sidx < len(s._hist):
                        vtok[i, t] = s._hist[sidx]
                        row.append(True)
                    elif sidx in props[i]:
                        vtok[i, t] = props[i][sidx]
                        row.append(False)
                    else:
                        # draft still catching up: pad (always rejected —
                        # the write rolls back behind the position mask)
                        vtok[i, t] = s._hist[-1]
                        row.append(None)
                trusted[i] = row
            vpos = base_pos.copy()
            vpos[parked] = self.max_context
            # lint: lockguard-ok (KV blocks are pump-thread-confined: only the single pump thread touches them; _grow_to's locked writes run on that same thread)
            outs, vprobs, self._blocks = self._verify_step(
                self._params, self._states, self._blocks,
                jnp.asarray(vtok), fresh, jnp.asarray(vpos), *paged_args)
            outs_h = np.asarray(outs)  # lint: host-sync-in-hot-loop-ok (accept/reject drives eviction and the next round's inputs; the sync IS the round boundary)
            vprobs_h = np.asarray(vprobs) if self.capture_probs else None
        except Exception as e:
            if growing:
                _flight_recorder().record(
                    "decode_bucket_growth_failed", cap=cap, mode=self.mode,
                    error=repr(e))
            _flight_recorder().dump(
                reason="decode-step-error",
                extra={"cap": cap, "mode": self.mode, "error": repr(e)})
            with self._cond:
                for i, sess in active:
                    self._evict_locked(i, "error")
            raise
        dt = time.perf_counter() - t0
        if growing:
            self._h_growth_stall.labels(bucket=str(cap)).observe(dt)
        now = time.perf_counter()
        prewarm_cap = None
        with self._cond:
            self._steps += 1
            self._buckets.add(cap)
            occupancy = len(active) / cap
            if (self.mode == "continuous" and cap < self.max_slots
                    and occupancy >= _PREWARM_OCCUPANCY):
                nxt = min(cap * 2, self.max_slots)
                if nxt not in self._buckets and nxt not in self._warming:
                    self._warming.add(nxt)
                    prewarm_cap = nxt
            self._occupancy_sum += occupancy
            n_steps = self._steps
            for i, s in live:
                p = int(base_pos[i])
                h = s._hist
                row = trusted[i]
                # writes past the context ceiling landed in trash: they
                # can never be accepted, the slot evicts at the ceiling
                max_ok = min(T, self.max_context - p)
                n_ok = max_ok
                # a proposal counts as judged only up to the first reject
                # (everything behind a reject was never on trial), so an
                # identical-weights draft reads acceptance == 1.0 exactly
                proposed = accepted = 0
                for t in range(1, max_ok):
                    if row[t] is True:
                        continue
                    if row[t] is False:
                        proposed += 1
                        if int(vtok[i, t]) == int(outs_h[i, t - 1]):
                            accepted += 1
                            continue
                    n_ok = t
                    break
                evict = None
                for t in range(n_ok):
                    sidx = p + t + 1
                    if sidx < len(h):
                        continue  # teacher-forced prefill output
                    tok = int(outs_h[i, t])
                    h.append(tok)
                    s.tokens.append(tok)
                    s.token_times.append(now)
                    if vprobs_h is not None:
                        s.probs.append(vprobs_h[i, t].copy())
                    if s.t_first is None:
                        s.t_first = now
                        self._h_ttft.observe(now - s.t_sched)
                        self._note_first_token(s, now - s.t_sched)
                    self._generated += 1
                    self._c_tokens.inc()
                    if s.stream is not None:
                        s.stream(s.sid, tok, now)
                    if self.eos_id is not None and tok == self.eos_id:
                        evict = "eos"
                        n_ok = t + 1
                        break
                    if len(s.tokens) >= s.max_new_tokens:
                        evict = "max_tokens"
                        n_ok = t + 1
                        break
                new_p = p + n_ok
                self._spec_proposed += proposed
                self._spec_accepted += accepted
                s._spec_proposed += proposed
                s._spec_accepted += accepted
                if proposed:
                    self._c_spec.labels(outcome="proposed").inc(proposed)
                    self._c_spec.labels(outcome="accepted").inc(accepted)
                # draft keeps KV only for inputs that match the (now
                # settled) true stream; the rest rolls back behind its
                # position mask exactly like the target's rejects
                dvalid = 0
                c0 = int(d0[i])
                for j, tok in enumerate(dins[i]):
                    if c0 + j < len(h) and tok == h[c0 + j]:
                        dvalid += 1
                    else:
                        break
                self._dpos_h[i] = c0 + dvalid
                self._fresh_h[i] = False
                self._pos_h[i] = new_p
                if self._pool is not None and evict is None:
                    self._register_prefix_locked(i, s, p, new_p)
                s._prompt_idx = min(new_p, len(s.prompt) - 1)
                if evict is not None:
                    self._evict_locked(i, evict)
                    continue
                if new_p >= self.max_context:
                    self._evict_locked(i, "context")
                    continue
                self._tokens_h[i] = h[new_p]
            if self._spec_proposed:
                self._g_accept.set(
                    self._spec_accepted / self._spec_proposed)
        self._g_occupancy.set(occupancy)
        _compile_tracker().note_step()
        _profile_note_dispatch(dt)
        _wd_beat(n_steps)
        if prewarm_cap is not None:
            threading.Thread(
                target=self._prewarm, args=(prewarm_cap,),
                name="serve-decode-prewarm", daemon=True).start()
        return True

    def _prewarm_calls(self, cap: int):
        """(program, example-inputs) pairs that cover one capacity bucket
        (single step, or draft + verify for spec engines)."""
        zi = jnp.zeros((cap,), jnp.int32)
        zb = jnp.zeros((cap,), bool)
        paged_args = ()
        if self._pool is not None:
            paged_args = (jnp.zeros((cap, self._pages_per_slot), jnp.int32),
                          zi, zi)
        calls = []
        if self._spec_draft is None:
            calls.append((self._step,
                          (self._params, self._states,
                           self._zero_blocks(cap), zi, zb, zi) + paged_args))
        else:
            calls.append((self._draft_step,
                          (self._draft_params, self._draft_states,
                           self._zero_draft_blocks(cap), zi, zb, zi)))
            vt = jnp.zeros((cap, self._verify_T), jnp.int32)
            calls.append((self._verify_step,
                          (self._params, self._states,
                           self._zero_blocks(cap), vt, zb, zi) + paged_args))
        return calls

    def _prewarm(self, cap: int) -> None:
        """Background-compile the next capacity bucket's step program so
        growth under load does not stall live traffic. Resolves the same
        per-signature entry the pump would, so a concurrent synchronous
        growth dedups on the program's own lock — never a double compile."""
        from deeplearning4j_tpu.nn import compile_cache

        t0 = time.perf_counter()
        try:
            for prog, inputs in self._prewarm_calls(cap):
                warm = getattr(prog, "warm", None)
                if warm is not None:
                    warm(*jax.tree_util.tree_map(
                        lambda a: jax.ShapeDtypeStruct(
                            tuple(a.shape), a.dtype)
                        if hasattr(a, "shape") and hasattr(a, "dtype")
                        else a, inputs))
                else:
                    # kill-switch path (plain jit): one zero step at the
                    # next capacity populates jit's own dispatch cache; the
                    # donated blocks are this thread's private zeros
                    prog(*inputs)
            compile_cache.observe_warmup("decode", time.perf_counter() - t0)
        except Exception as e:
            log.debug("decode pre-warm of bucket %d failed: %r", cap, e)
            with self._lock:
                self._warming.discard(cap)

    def _loop(self) -> None:
        while True:
            try:
                if not self._pump_once():
                    return
            except Exception:
                # sessions in flight were failed by _pump_once; keep serving
                continue

    # ---------------------------------------------------------------- control
    def stats(self) -> dict:
        with self._lock:
            out = {
                "mode": self.mode,
                "kind": self.kind,
                "quant": self.quant,
                "kv": self.kv,
                "capacity": self._cap,
                "max_slots": self.max_slots,
                "buckets": sorted(self._buckets),
                "bucket_count": len(self._buckets),
                "steps": self._steps,
                "tokens": self._generated,
                "evictions": self._evicted,
                "queue_depth": len(self._queue),
                "active": self._active_count(),
                "peak_active": self._peak_active,
                "mean_occupancy": (self._occupancy_sum / self._steps
                                   if self._steps else 0.0),
                "param_bytes": tree_param_bytes(self._params),
                "state_copy_bytes": self._copy_bytes,
            }
            if self._pool is not None:
                out["page_size"] = self.page_size
                out["pool_pages"] = self._n_pages
                out["pages_in_use"] = self._pool.pages_in_use
                out["pages_free"] = self._pool.free_pages
                out["prefix_entries"] = self._pool.prefix_entries
                out["prefix_share_ratio"] = (
                    self._shared_tokens / self._prompt_tokens
                    if self._prompt_tokens else 0.0)
            if self._spec_draft is not None:
                out["spec_tokens"] = self.spec_tokens
                out["spec_proposed"] = self._spec_proposed
                out["spec_accepted"] = self._spec_accepted
                out["spec_acceptance"] = (
                    self._spec_accepted / self._spec_proposed
                    if self._spec_proposed else 0.0)
                out["draft_param_bytes"] = tree_param_bytes(
                    self._draft_params)
            return out

    def state_bytes(self) -> int:
        """Device-resident bytes of the slot state blocks (the number the
        churn regression pins: sessions come and go, this does not grow).
        Paged engines count the fixed pool plus page tables — the
        capacity-independent footprint the ≥2x sessions-per-chip
        acceptance test compares against the dense layout."""
        with self._lock:
            total = tree_param_bytes(self._blocks)
            if self._pool is not None:
                total += self._table_h.nbytes
            if self._draft_blocks is not None:
                total += tree_param_bytes(self._draft_blocks)
            return total

    def idle(self) -> bool:
        """No queued or active sessions — a hot-swapped-away version's
        engine is safe to retire exactly when this is True."""
        with self._lock:
            return not self._queue and not self._active_count()

    def drain(self, timeout_s: float = 60.0) -> None:
        """Block until every queued/active session has finished."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            with self._lock:
                if not self._queue and not self._active_count():
                    return
            time.sleep(0.002)
        raise TimeoutError("decode engine did not drain in time")

    def close(self, timeout_s: float = 30.0) -> None:
        """Stop accepting sessions; the pump drains what is queued first."""
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._cond.notify_all()
        self._thread.join(timeout_s)
