"""Warm-start compile plane: the ISSUE-15 acceptance set.

Contracts pinned here:
- cache-hit executables are BITWISE identical to fresh compiles, for the
  donated train step, the serving predict program, and the decode engine's
  per-bucket step (deserialize_and_load must change nothing about math);
- torn / truncated / version-mismatched entries are quarantined and fall
  back to a normal compile — never an error, always a correct result, and
  the flight recorder keeps the trail;
- entries written by one process warm-start another (the elastic-respawn
  and replica-spawn payoff);
- ModelRegistry warmup builds every micro-batch bucket program
  (log2(max_batch)+1 of them) BEFORE the active pointer moves, and serving
  those bucket sizes afterwards compiles nothing new;
- the ``DL4J_COMPILE_CACHE=0`` kill switch restores the exact plain
  ``tracker.wrap(jax.jit(...))`` path: no disk entries, no CachedProgram;
- the store itself prunes oldest-first to its byte bound.

The autouse conftest fixture points ``JAX_COMPILATION_CACHE_DIR`` at a
per-test tmp dir (the store is its ``executables/``), so every test starts
cold and cross-test poisoning is impossible.
"""
import glob
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.keras_server import ModelRegistry
from deeplearning4j_tpu.keras_server.decode import (
    DECODE_PROGRAM_NAME, DecodeEngine,
)
from deeplearning4j_tpu.models.char_rnn import char_rnn_lstm
from deeplearning4j_tpu.nn import compile_cache as cc
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.inference import PREDICT_PROGRAM_NAME
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability.compile_tracker import global_tracker
from deeplearning4j_tpu.observability.flight_recorder import global_recorder

N_IN, N_OUT = 12, 3
V = 24


def _mlp(seed=3):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).learning_rate(0.1).updater("adam")
            .weight_init("xavier")
            .list()
            .layer(DenseLayer(n_in=N_IN, n_out=16, activation="relu"))
            .layer(OutputLayer(n_in=16, n_out=N_OUT, loss="mcxent",
                               activation="softmax"))
            .build())
    return MultiLayerNetwork(conf).init()


def _xy(n=16, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, N_IN)).astype(np.float32)
    y = np.eye(N_OUT, dtype=np.float32)[rng.integers(0, N_OUT, size=n)]
    return x, y


def _cache_files():
    return sorted(glob.glob(os.path.join(cc.cache_dir(), "*.xc")))


def _events_since(n0):
    return global_tracker().snapshot_events()[n0:]


def _n_events():
    return len(global_tracker().snapshot_events())


# ------------------------------------------------------- bitwise identity
def test_train_and_predict_cache_hit_bitwise_equal(monkeypatch):
    """A net resolved entirely from disk entries trains and predicts
    bit-for-bit like both the cold (populating) run and the kill-switch
    plain-jit run."""
    x, y = _xy()
    xq, _ = _xy(n=5, seed=9)

    monkeypatch.setenv("DL4J_COMPILE_CACHE", "0")
    ref = _mlp()
    ref.fit(x, y, epochs=3)
    ref_out = np.asarray(ref.output(xq))
    assert _cache_files() == []

    monkeypatch.setenv("DL4J_COMPILE_CACHE", "1")
    cold = _mlp()
    cold.fit(x, y, epochs=3)
    cold_out = np.asarray(cold.output(xq))
    assert _cache_files(), "cold run must persist executables"

    n0 = _n_events()
    warm = _mlp()
    warm.fit(x, y, epochs=3)
    warm_out = np.asarray(warm.output(xq))
    ev = _events_since(n0)
    assert ev and all(e.get("cache_hit") for e in ev), \
        f"identical net must resolve every program from disk: {ev}"

    np.testing.assert_array_equal(np.asarray(warm.params()),
                                  np.asarray(cold.params()))
    np.testing.assert_array_equal(np.asarray(warm.params()),
                                  np.asarray(ref.params()))
    np.testing.assert_array_equal(warm_out, cold_out)
    np.testing.assert_array_equal(warm_out, ref_out)


def test_decode_bucket_cache_hit_bitwise_equal(monkeypatch):
    """Greedy decode through deserialized per-bucket step executables
    emits the same token streams as the plain-jit engine."""
    rng = np.random.default_rng(4)
    prompts = [list(map(int, rng.integers(0, V, size=3))) for _ in range(6)]
    budgets = [4, 5, 6, 4, 5, 6]

    def run():
        net = MultiLayerNetwork(
            char_rnn_lstm(vocab_size=V, hidden=16, seed=11)).init()
        eng = DecodeEngine(net, min_slots=2, max_slots=4)
        try:
            sessions = [eng.submit(p, b) for p, b in zip(prompts, budgets)]
            return [s.result(timeout=300) for s in sessions]
        finally:
            eng.close()

    monkeypatch.setenv("DL4J_COMPILE_CACHE", "0")
    ref = run()
    monkeypatch.setenv("DL4J_COMPILE_CACHE", "1")
    cold = run()          # populates the store
    n0 = _n_events()
    warm = run()          # resolves every bucket step from disk
    decode_ev = [e for e in _events_since(n0)
                 if DECODE_PROGRAM_NAME in e.get("fn", "")]
    assert decode_ev and all(e.get("cache_hit") for e in decode_ev)
    assert warm == cold == ref


# ------------------------------------------------------ corruption = miss
@pytest.mark.parametrize("corrupt", ["truncate", "bad-magic", "bit-flip"])
def test_corrupt_entry_falls_back_to_fresh_compile(corrupt):
    xq, _ = _xy(n=4, seed=2)
    good = np.asarray(_mlp().output(xq))
    files = _cache_files()
    assert files
    for path in files:
        raw = open(path, "rb").read()
        if corrupt == "truncate":
            raw = raw[:10]
        elif corrupt == "bad-magic":
            raw = b"NOTDL4J!" + raw[8:]
        else:
            raw = raw[:-1] + bytes([raw[-1] ^ 0xFF])
        open(path, "wb").write(raw)

    n0, r0 = _n_events(), len(global_recorder().snapshot())
    out = np.asarray(_mlp().output(xq))
    np.testing.assert_array_equal(out, good)
    ev = [e for e in _events_since(n0)
          if "output" in e.get("fn", "")]
    assert ev and not any(e.get("cache_hit") for e in ev), \
        "corrupt entries must read as misses, not hits"
    falls = [e for e in global_recorder().snapshot()[r0:]
             if e.get("kind") == "compile_cache_fallback"]
    assert falls, "quarantine must leave a flight-recorder trail"
    # the ring's compile record of that fallback is the span of a miss: no
    # store_read of an entry that was not returned, the three parts of a
    # fresh compile under it
    spans = [e for e in global_recorder().snapshot()[r0:]
             if e.get("name", "").startswith("compile.")]
    assert [e["name"] for e in spans] == [
        "compile.lower", "compile.backend", "compile.store_write",
        "compile.resolve"]
    assert spans[-1]["kind"] == "compile" and spans[-1]["hit"] is False
    # the quarantined bytes are gone: the fresh compile re-persisted a
    # valid entry (magic + digest check out) at the same fingerprint
    import hashlib
    for path in files:
        raw = open(path, "rb").read()
        assert raw.startswith(cc.MAGIC)
        body = raw[len(cc.MAGIC) + 32:]
        assert hashlib.sha256(body).digest() == raw[len(cc.MAGIC):
                                                    len(cc.MAGIC) + 32]


# ------------------------------------------------------- cross-process
def test_cross_process_reuse(tmp_path):
    """An entry serialized by a child process warm-starts this one — the
    mechanism behind elastic respawn and replica-spawn warm recovery."""
    out_npy = str(tmp_path / "child_out.npy")
    child = textwrap.dedent(f"""
        import numpy as np
        from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        conf = (NeuralNetConfiguration.builder()
                .seed(3).learning_rate(0.1).updater("adam")
                .weight_init("xavier")
                .list()
                .layer(DenseLayer(n_in={N_IN}, n_out=16, activation="relu"))
                .layer(OutputLayer(n_in=16, n_out={N_OUT}, loss="mcxent",
                                   activation="softmax"))
                .build())
        net = MultiLayerNetwork(conf).init()
        out = np.asarray(net.output(np.zeros((4, {N_IN}), np.float32)))
        np.save({out_npy!r}, out)
    """)
    # the child inherits JAX_PLATFORMS / XLA_FLAGS / JAX_COMPILATION_CACHE_DIR
    # from this process, so its backend key matches ours
    subprocess.run([sys.executable, "-c", child], check=True, timeout=300)
    assert _cache_files(), "child must have persisted its executable"

    n0 = _n_events()
    mine = np.asarray(_mlp().output(np.zeros((4, N_IN), np.float32)))
    ev = [e for e in _events_since(n0) if "output" in e.get("fn", "")]
    assert ev and all(e.get("cache_hit") for e in ev), \
        "parent must load the child's entry instead of compiling"
    np.testing.assert_array_equal(mine, np.load(out_npy))


# ---------------------------------------------------------------- warmup
def test_registry_warmup_builds_all_buckets_before_swap(monkeypatch):
    assert ModelRegistry.warmup_buckets(8) == [1, 2, 4, 8]
    assert ModelRegistry.warmup_buckets(6) == [1, 2, 4, 6]

    reg = ModelRegistry(warmup_max_batch=8)
    seen = {}
    orig = ModelRegistry._warmup

    def spy(self, pf, net, example=None):
        seen["active_at_warmup"] = self._active.get("m")
        n0 = _n_events()
        orig(self, pf, net, example)
        seen["events"] = [e for e in _events_since(n0)
                          if PREDICT_PROGRAM_NAME in e.get("fn", "")]

    monkeypatch.setattr(ModelRegistry, "_warmup", spy)

    reg.register("m", _mlp())
    assert seen["active_at_warmup"] is None, \
        "v1 warmup must run before the pointer first moves"
    assert len(seen["events"]) == 4, \
        "warmup must build exactly log2(max_batch)+1 bucket programs"

    reg.register("m", _mlp())
    assert seen["active_at_warmup"] == "v1", \
        "v2 warmup must run while v1 still serves"
    assert len(seen["events"]) == 4
    assert all(e.get("cache_hit") for e in seen["events"]), \
        "hot swap of a structurally identical model must warm-hit v1's " \
        "entries (fingerprints ignore the @version decoration)"
    assert reg.active("m").version == "v2"

    # every bucket the micro-batcher can form is already resident
    n0 = _n_events()
    pf = reg.active("m").predict_fn
    for b in (1, 2, 4, 8):
        pf(np.zeros((b, N_IN), np.float32))
    assert [e for e in _events_since(n0)
            if PREDICT_PROGRAM_NAME in e.get("fn", "")] == []


def test_warmup_skipped_when_example_underivable():
    """Recurrent first layers have no (1, n_in) shape to derive — warmup
    degrades to a no-op instead of guessing wrong."""
    net = MultiLayerNetwork(
        char_rnn_lstm(vocab_size=V, hidden=16, seed=1)).init()
    reg = ModelRegistry(warmup_max_batch=4)
    n0 = _n_events()
    reg.register("rnn", net)
    assert [e for e in _events_since(n0)
            if PREDICT_PROGRAM_NAME in e.get("fn", "")] == []


# ------------------------------------------------------------ kill switch
def test_kill_switch_restores_plain_path(monkeypatch):
    monkeypatch.setenv("DL4J_COMPILE_CACHE", "0")
    prog = cc.build_program("t", jax.jit(lambda a: a + 1))
    assert not isinstance(prog, cc.CachedProgram)

    x, y = _xy()
    net = _mlp()
    n0 = _n_events()
    net.fit(x, y, epochs=1)
    net.output(x)
    ev = _events_since(n0)
    assert ev and not any(e.get("cache_hit") for e in ev)
    assert _cache_files() == [], "kill switch must never touch disk"


# ------------------------------------------------------------- the store
def test_store_prunes_oldest_to_byte_bound(tmp_path):
    store = cc.CompileCache(str(tmp_path / "s"), max_bytes=4096)
    for i in range(6):
        store.put(f"{i:064x}", os.urandom(1024), None, None, {"i": i})
        os.utime(store.entry_path(f"{i:064x}"), (1000 + i, 1000 + i))
    store._prune()
    left = sorted(glob.glob(os.path.join(str(tmp_path / "s"), "*.xc")))
    total = sum(os.path.getsize(p) for p in left)
    assert total <= 4096
    assert store.entry_path(f"{5:064x}") in left, \
        "prune must evict oldest-mtime first"
    assert store.entry_path(f"{0:064x}") not in left


def test_epoch_env_salts_fingerprint(monkeypatch):
    prog = cc.CachedProgram("t", jax.jit(lambda a: a + 1))
    sig = (("f32[2]",), ())
    a = prog._fp_hex(sig)
    monkeypatch.setenv("DL4J_COMPILE_CACHE_EPOCH", "2")
    b = prog._fp_hex(sig)
    assert a != b, "EPOCH must invalidate without deleting files"


# ------------------------------------------------------------- placement
def test_cache_dir_resolution(monkeypatch, tmp_path):
    """One knob, JAX's own: with JAX_COMPILATION_CACHE_DIR set the store is
    its ``executables/`` and JAX's cache is left where the variable put it;
    unset, both sit under one fixed directory in the checkout — derived from
    the package's path, never from tempfile, a pid or a time."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    jax_dir_before = jax.config.jax_compilation_cache_dir

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "c"))
    monkeypatch.setenv("DL4J_COMPILE_CACHE_DIR", str(tmp_path / "gone"))
    assert cc.cache_root() == str(tmp_path / "c")
    assert cc.cache_dir() == str(tmp_path / "c" / "executables")
    assert cc.global_cache().directory == cc.cache_dir()
    # nothing in code repointed JAX's own cache
    assert jax.config.jax_compilation_cache_dir == jax_dir_before

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert cc.cache_root() == os.path.join(repo, ".jax_cache")
        assert cc.cache_dir() == os.path.join(repo, ".jax_cache",
                                              "executables")
        assert cc.cache_root() == cc.cache_root()  # a fixed path
        assert jax.config.jax_compilation_cache_dir == cc.cache_root()
    finally:
        jax.config.update("jax_compilation_cache_dir", jax_dir_before)
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    src = open(cc.__file__).read()
    assert "gettempdir" not in src and "mkdtemp" not in src \
        and "getpid" not in src


def test_loaded_executable_runs_on_the_devices_it_was_compiled_for():
    """An entry records its device assignment and is loaded onto exactly
    those devices (the default, every local device, is what broke every warm
    start on JAX 0.9); a placement on another device is a sibling entry."""
    import jax.numpy as jnp

    d0, d3 = jax.devices()[0], jax.devices()[3]
    x = np.arange(8, dtype=np.float32)
    outs = {}
    for round_ in ("cold", "warm"):
        n0 = _n_events()
        for dev in (d0, d3):
            prog = cc.build_program("placed", jax.jit(lambda a: a * 2 + 1))
            out = prog(jax.device_put(jnp.asarray(x), dev))
            assert out.devices() == {dev}
            outs[round_, dev.id] = np.asarray(out)
        hits = [e.get("cache_hit") for e in _events_since(n0)
                if e.get("fn") == "placed"]
        assert hits == [round_ == "warm"] * 2, (round_, hits)
    assert len(_cache_files()) == 2      # one entry per device
    for dev in (d0, d3):
        np.testing.assert_array_equal(outs["cold", dev.id],
                                      outs["warm", dev.id])
