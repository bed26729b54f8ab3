"""Paged KV-cache memory plane + speculative decoding: ISSUE-16 acceptance.

Contracts pinned here:
- page-table attention is BITWISE identical to the dense masked oracle
  (tokens AND probability rows) at every capacity bucket and for session
  lengths that end mid-page — the gather indirection is pure layout;
- copy-on-write prefix sharing engages (shared tokens > 0) without
  touching the math: a fork mid-page diverges correctly and never
  corrupts the donor session's stream;
- page refcounts never leak: 1k churned sessions leave pool bytes flat
  (the device arrays the engine holds, after a garbage collection), every
  page back on the free list and the prefix registry empty;
- speculative decode emits the EXACT greedy stream at every acceptance
  rate — identical draft (acceptance == 1.0 by construction), a real
  partial-acceptance draft, and a sign-flipped near-zero draft;
- a session that can never fit the pool is refused at submit with the
  RejectedError the HTTP layer maps to 429 — pool pressure degrades to
  preemption/parking, never to OOM;
- the paged engine admits >= 2x the dense session count at EQUAL state
  bytes (the ISSUE-16 headline ratio);
- capacity growth no longer round-trips KV blocks through the host: the
  bytes billed to dl4j_decode_state_copy_bytes_total are the small host
  scheduling arrays, orders of magnitude under the device blocks.
"""
import gc
import os
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.keras_server import RejectedError
from deeplearning4j_tpu.keras_server.decode import DecodeEngine
from deeplearning4j_tpu.keras_server.paging import TRASH_PAGE, PagePool
from deeplearning4j_tpu.models.transformer import transformer_lm
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability import names
from deeplearning4j_tpu.ops.paged_attention import paged_gather

V = 24


def _tf_net(seed=5, width=32):
    return MultiLayerNetwork(
        transformer_lm(vocab_size=V, width=width, n_layers=2, n_heads=2,
                       max_len=64, seed=seed)).init()


def _workload(n, rng=None, lo=2, hi=9):
    rng = rng or np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(0, V,
                                          size=int(rng.integers(1, 5)))))
               for _ in range(n)]
    budgets = [int(rng.integers(lo, hi)) for _ in range(n)]
    return prompts, budgets


def _run(eng, prompts, budgets):
    sessions = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
    for s in sessions:
        s.result(timeout=300)
    return sessions


def _device_bytes_held_by(root) -> int:
    """Bytes of every live device array reachable from ``root`` through
    instances and containers: an engine's blocks, pool and tables, and
    whatever a leak would leave hanging on it (a session kept, a cache that
    grows). Not the process's ``jax.live_arrays()``: other tests' threads in
    the same worker, and the engine thread's own temporaries of its last
    step, come and go between two readings."""
    skip = (type, types.ModuleType, types.FunctionType,
            types.BuiltinFunctionType, types.CodeType, types.FrameType)
    seen, stack, total = set(), [root], 0
    while stack:
        obj = stack.pop()
        if id(obj) in seen or isinstance(obj, skip):
            continue
        seen.add(id(obj))
        if isinstance(obj, jax.Array):
            total += 0 if obj.is_deleted() else obj.nbytes
        else:
            stack.extend(gc.get_referents(obj))
    return total


# ----------------------------------------------- paged == dense, bitwise
@pytest.mark.parametrize("cap", [2, 4, 8])
def test_paged_vs_dense_bitwise_per_capacity(cap):
    """Same sessions, same tokens AND probability rows bit-for-bit whether
    KV lives in dense per-slot blocks or gathered pages, at every capacity
    bucket. The workload's prompt+budget spans deliberately straddle page
    boundaries (page_size=8, sessions end mid-page)."""
    net = _tf_net()
    prompts, budgets = _workload(12, np.random.default_rng(cap), lo=3,
                                 hi=14)
    dense = DecodeEngine(net, max_context=64, min_slots=cap, max_slots=cap,
                         capture_probs=True)
    paged = DecodeEngine(net, max_context=64, min_slots=cap, max_slots=cap,
                         capture_probs=True, kv="paged", page_size=8)
    try:
        ds = _run(dense, prompts, budgets)
        ps = _run(paged, prompts, budgets)
    finally:
        dense.close()
        paged.close()
    for d, p in zip(ds, ps):
        assert d.tokens == p.tokens
        for dp, pp in zip(d.probs, p.probs):
            assert np.array_equal(dp, pp)
    st = paged.stats()
    assert st["kv"] == "paged" and st["pages_in_use"] == 0


def test_odd_session_tails_park_on_trash_page():
    """Sessions whose final position lands mid-page read only written
    offsets: the j <= position mask never selects a row past the write
    head, so the page's uninitialised tail is unobservable (bitwise check
    against dense is the proof; the trash page absorbs suppressed
    writes)."""
    net = _tf_net(seed=3)
    # one-token prompts + budgets chosen so totals hit every residue
    # class mod page_size=4
    prompts = [[t % V] for t in range(8)]
    budgets = [2 + (t % 4) for t in range(8)]
    dense = DecodeEngine(net, max_context=64, min_slots=4, max_slots=4)
    paged = DecodeEngine(net, max_context=64, min_slots=4, max_slots=4,
                         kv="paged", page_size=4)
    try:
        ds = _run(dense, prompts, budgets)
        ps = _run(paged, prompts, budgets)
    finally:
        dense.close()
        paged.close()
    assert [d.tokens for d in ds] == [p.tokens for p in ps]


# --------------------------------------------------- copy-on-write forks
def test_cow_fork_mid_page_diverges_without_corrupting_donor():
    """B maps A's registered prompt pages copy-on-write, then forks
    mid-page where its prompt diverges. Both streams must equal the
    dense oracle — the fork copies A's earlier offsets device-side, and
    A's own pages are untouched by B's writes."""
    net = _tf_net(seed=7)
    pa = [1, 2, 3, 4, 5, 6, 7, 8, 2, 3, 9]          # 11 tokens, ps=8
    pb = pa[:6] + [11, 12]                          # diverges mid-page
    dense = DecodeEngine(net, max_context=64, min_slots=2, max_slots=2)
    paged = DecodeEngine(net, max_context=64, min_slots=2, max_slots=2,
                         kv="paged", page_size=8)
    try:
        da = dense.submit(pa, 16)
        db = dense.submit(pb, 10)
        da.result(timeout=300)
        db.result(timeout=300)
        a = paged.submit(pa, 16)
        # wait until A has written (and registered) its prompt pages so
        # B's admission can actually map them copy-on-write
        deadline = time.time() + 60
        while len(a.tokens) < 2 and time.time() < deadline:
            time.sleep(0.01)
        assert len(a.tokens) >= 2
        b = paged.submit(pb, 10)
        a.result(timeout=300)
        b.result(timeout=300)
    finally:
        st = paged.stats()
        dense.close()
        paged.close()
    assert a.tokens == da.tokens
    assert b.tokens == db.tokens
    # sharing genuinely engaged: B skipped re-prefilling the common prefix
    assert st["prefix_share_ratio"] > 0.0


def test_page_boundary_share_remaps_without_fork():
    """A shared page whose boundary coincides with the divergence point
    needs no fork at all — the follower keeps the whole page by
    reference and allocates fresh pages from the boundary on. Bitwise
    equality with dense is the contract either way."""
    net = _tf_net(seed=9)
    pa = [4, 5, 6, 7, 8, 9, 10, 11, 1]              # first page exactly full
    pb = pa[:8] + [13]                              # diverges ON the boundary
    dense = DecodeEngine(net, max_context=64, min_slots=2, max_slots=2)
    paged = DecodeEngine(net, max_context=64, min_slots=2, max_slots=2,
                         kv="paged", page_size=8)
    try:
        da = dense.submit(pa, 12).result(timeout=300)
        db = dense.submit(pb, 12).result(timeout=300)
        a = paged.submit(pa, 12)
        deadline = time.time() + 60
        while len(a.tokens) < 2 and time.time() < deadline:
            time.sleep(0.01)
        b = paged.submit(pb, 12)
        ta = a.result(timeout=300)
        tb = b.result(timeout=300)
    finally:
        dense.close()
        paged.close()
    assert ta == da and tb == db


# ------------------------------------------------------- refcount hygiene
def test_pool_refcounts_drain_after_1k_session_churn():
    """1000 churned sessions leave the pool exactly where it started:
    zero pages in use, the full free list back, the prefix registry
    empty, and device-resident bytes flat — the physical pool is the
    ONLY decode memory and it never grows."""
    net = _tf_net(seed=5)
    eng = DecodeEngine(net, max_context=64, min_slots=8, max_slots=8,
                       kv="paged", page_size=8)
    rng = np.random.default_rng(1)
    try:
        # warm wave: compile + allocate everything once
        prompts, budgets = _workload(8, rng, lo=2, hi=4)
        _run(eng, prompts, budgets)
        baseline_state = eng.state_bytes()
        gc.collect()
        baseline_held = _device_bytes_held_by(eng)
        assert baseline_held >= baseline_state > 0
        prompts = [[int(rng.integers(0, V))] for _ in range(1000)]
        budgets = [2] * 1000
        _run(eng, prompts, budgets)
        st = eng.stats()
        assert eng.state_bytes() == baseline_state
        gc.collect()
        grown = _device_bytes_held_by(eng) - baseline_held
        assert grown <= 0, f"device bytes grew by {grown} after 1k sessions"
    finally:
        eng.close()
    assert st["pages_in_use"] == 0
    assert st["pages_free"] == st["pool_pages"]
    assert st["prefix_entries"] == 0


def test_pagepool_decref_drops_prefix_keys():
    pool = PagePool(4, 8)
    pid = pool.alloc()
    pool.register((1, 2, 3), pid)
    pids, covered = pool.match_prompt([1, 2, 3, 4])
    assert pids == [pid] and covered == 3
    pool.decref(pid)
    assert pool.free_pages == 4
    assert pool.prefix_entries == 0
    assert pool.match_prompt([1, 2, 3, 4])[1] == 0
    assert pid != TRASH_PAGE


# -------------------------------------------------- speculative decoding
def _spec_ab(draft_net, seed=5, n=8):
    net = _tf_net(seed=seed)
    prompts, budgets = _workload(n, np.random.default_rng(17), lo=4,
                                 hi=12)
    greedy = DecodeEngine(net, max_context=64, min_slots=4, max_slots=4)
    spec = DecodeEngine(net, max_context=64, min_slots=4, max_slots=4,
                        draft_net=draft_net, spec_tokens=3)
    try:
        gs = _run(greedy, prompts, budgets)
        ss = _run(spec, prompts, budgets)
        st = spec.stats()
    finally:
        greedy.close()
        spec.close()
    assert [g.tokens for g in gs] == [s.tokens for s in ss]
    assert st["spec_proposed"] > 0
    return st["spec_acceptance"]


def test_spec_identical_draft_acceptance_exactly_one():
    """A draft with the target's own weights proposes the target's own
    argmaxes: every judged proposal is accepted, and — the real
    contract — the emitted stream is still bit-for-bit greedy."""
    acc = _spec_ab(_tf_net(seed=5))
    assert acc == 1.0


def test_spec_partial_acceptance_bitwise_greedy():
    """A genuinely different (smaller, differently-seeded) draft is
    right only sometimes; rejected suffixes roll back behind the
    position mask and the stream is STILL exactly greedy."""
    acc = _spec_ab(_tf_net(seed=9, width=16))
    assert 0.0 < acc < 1.0


def test_spec_near_zero_acceptance_bitwise_greedy():
    """Sign-flipping every draft parameter makes its argmax essentially
    uncorrelated with the target's (~1/V agreement): verification falls
    back to one guaranteed token per round and the stream is STILL
    exactly greedy — the speedup degrades, never the math."""
    draft = _tf_net(seed=5)
    draft.set_params(-draft.params())
    acc = _spec_ab(draft)
    assert acc < 0.35


def test_spec_on_paged_kv_bitwise_greedy():
    """The two planes compose: spec-decode on the paged memory plane
    still emits the dense greedy stream bit-for-bit."""
    net = _tf_net(seed=5)
    prompts, budgets = _workload(8, np.random.default_rng(23), lo=3,
                                 hi=10)
    greedy = DecodeEngine(net, max_context=64, min_slots=4, max_slots=4)
    both = DecodeEngine(net, max_context=64, min_slots=4, max_slots=4,
                        kv="paged", page_size=8,
                        draft_net=_tf_net(seed=9, width=16), spec_tokens=3)
    try:
        gs = _run(greedy, prompts, budgets)
        bs = _run(both, prompts, budgets)
    finally:
        greedy.close()
        both.close()
    assert [g.tokens for g in gs] == [b.tokens for b in bs]


# ------------------------------------------------------ admission control
def test_never_fit_session_rejected_429_not_oom():
    """A session whose worst-case span needs more pages than the pool
    HAS is refused at submit with the RejectedError the HTTP layer maps
    to 429 — it must not be admitted only to OOM mid-decode."""
    net = _tf_net(seed=5)
    eng = DecodeEngine(net, max_context=64, min_slots=2, max_slots=2,
                       kv="paged", page_size=16, n_pages=2)
    try:
        with pytest.raises(RejectedError) as ei:
            eng.submit(list(range(20)), 20)  # span 40 -> 3 pages > 2
        assert ei.value.limit == 2 and ei.value.pending == 3
        assert ei.value.retry_after_s > 0
        # a session that fits completes normally on the same tiny pool
        toks = eng.submit([1, 2, 3], 8).result(timeout=300)
        assert len(toks) == 8
    finally:
        eng.close()


def test_tiny_pool_overload_degrades_to_preemption_not_oom():
    """Oversubscribing a pool with individually-fitting sessions must
    finish every session (preemption/parking reorders work, never
    crashes) and drain the pool."""
    net = _tf_net(seed=5)
    eng = DecodeEngine(net, max_context=64, min_slots=4, max_slots=4,
                       kv="paged", page_size=8, n_pages=6)
    prompts, budgets = _workload(12, np.random.default_rng(3), lo=2,
                                 hi=6)
    try:
        sessions = _run(eng, prompts, budgets)
        st = eng.stats()
    finally:
        eng.close()
    assert all(s.done.is_set() for s in sessions)
    assert st["pages_in_use"] == 0


# ----------------------------------------- headline: 2x sessions, = bytes
def test_paged_admits_2x_sessions_at_equal_state_bytes():
    """THE ISSUE-16 ratio: size the paged pool to the dense engine's
    exact KV bytes (n_pages = slots * pages_per_ctx - 1; the +1 trash
    page balances the ledger) and the paged engine holds 2x the
    concurrent sessions, emitting the identical streams."""
    net = _tf_net(seed=5)
    prompts, budgets = _workload(16, np.random.default_rng(11), lo=4,
                                 hi=9)
    dense = DecodeEngine(net, max_context=64, min_slots=4, max_slots=4)
    paged = DecodeEngine(net, max_context=64, min_slots=8, max_slots=8,
                         kv="paged", page_size=16,
                         n_pages=4 * (64 // 16) - 1)
    try:
        ds = _run(dense, prompts, budgets)
        ps = _run(paged, prompts, budgets)
        dst, pst = dense.stats(), paged.stats()
        dbytes, pbytes = dense.state_bytes(), paged.state_bytes()
    finally:
        dense.close()
        paged.close()
    assert [d.tokens for d in ds] == [p.tokens for p in ps]
    # equal memory: the paged plane pays only the tiny host page table
    # on top of the identical device pool bytes
    assert pbytes <= int(dbytes * 1.02)
    assert pst["peak_active"] >= 2 * dst["peak_active"]


# ------------------------------------------------------- growth copy path
def test_grow_copy_bytes_billed_and_small():
    """Capacity growth copies slot state device-side; only the small
    host scheduling arrays still round-trip, and THOSE bytes are billed
    to dl4j_decode_state_copy_bytes_total — far under the device blocks
    a host KV round-trip would have cost."""
    net = _tf_net(seed=5)
    for kv in ("dense", "paged"):
        eng = DecodeEngine(net, max_context=64, min_slots=2, max_slots=8,
                           kv=kv, page_size=16)
        try:
            assert eng.stats()["state_copy_bytes"] == 0
            prompts, budgets = _workload(12, np.random.default_rng(5))
            _run(eng, prompts, budgets)
            st = eng.stats()
            copied, blocks = st["state_copy_bytes"], eng.state_bytes()
        finally:
            eng.close()
        assert copied > 0, f"{kv}: growth billed nothing"
        assert copied < blocks // 10, \
            f"{kv}: {copied}B copied vs {blocks}B blocks — KV is " \
            "round-tripping through the host again"


# ------------------------------------------------------------ ops + names
def test_paged_gather_pallas_interpret_matches_xla(monkeypatch):
    rng = np.random.default_rng(2)
    pool = jnp.asarray(rng.standard_normal((9, 4, 2, 8)), jnp.float32)
    table = jnp.asarray(rng.integers(0, 9, size=(3, 5)), jnp.int32)
    ref = np.asarray(paged_gather(pool, table, impl="xla"))
    monkeypatch.setenv("DL4J_PAGED_GATHER_IMPL", "pallas")
    monkeypatch.setenv("DL4J_PAGED_GATHER_INTERPRET", "1")
    got = np.asarray(paged_gather(pool, table))
    assert got.shape == (3, 20, 2, 8)
    assert np.array_equal(ref, got)


def test_page_metric_names_registered():
    for name in (names.DECODE_PAGES_IN_USE,
                 names.DECODE_PREFIX_SHARE_RATIO,
                 names.DECODE_SPEC_ACCEPTANCE,
                 names.DECODE_SPEC_TOKENS_TOTAL,
                 names.DECODE_STATE_COPY_BYTES_TOTAL):
        assert name in names.ALL_METRIC_NAMES
        assert name.startswith("dl4j_decode_")
