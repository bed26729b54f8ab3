"""Native C++ runtime: IDX/CIFAR parsing, async prefetch loader, CSV reader,
stats codec wire-format equivalence with the Python encoder."""
import struct

import numpy as np
import pytest

from deeplearning4j_tpu import nativert
from deeplearning4j_tpu.ui.stats import StatsReport

pytestmark = pytest.mark.skipif(
    not nativert.native_available(),
    reason=f"native runtime not built: {nativert.load_error()}")


def _write_idx(path, arr):
    arr = np.asarray(arr, np.uint8)
    with open(path, "wb") as f:
        f.write(struct.pack(">i", 0x0800 | arr.ndim))
        for d in arr.shape:
            f.write(struct.pack(">i", d))
        f.write(arr.tobytes())


def test_idx_roundtrip(tmp_path):
    arr = np.arange(2 * 5 * 4, dtype=np.uint8).reshape(2, 5, 4)
    p = tmp_path / "t.idx"
    _write_idx(p, arr)
    out = nativert.read_idx(str(p))
    assert out.shape == (2, 5, 4)
    np.testing.assert_array_equal(out, arr)


def test_idx_bad_file(tmp_path):
    p = tmp_path / "bad.idx"
    p.write_bytes(b"\x00\x01\x02")
    assert nativert.read_idx(str(p)) is None


def test_loader_ordered_batches():
    n, feat, ncls, batch = 12, 6, 3, 4
    feats = np.arange(n * feat, dtype=np.uint8).reshape(n, feat)
    labels = (np.arange(n) % ncls).astype(np.uint8)
    ld = nativert.AsyncNativeLoader.from_arrays(
        feats, labels, ncls, batch, shuffle=False, normalize=False)
    batches = list(ld)
    assert len(batches) == 3
    x0, y0 = batches[0]
    np.testing.assert_allclose(x0, feats[:4].astype(np.float32))
    np.testing.assert_array_equal(np.argmax(y0, axis=1), labels[:4])
    assert y0.sum() == batch  # one-hot
    # epoch exhausted; reset restarts
    assert ld.next() is None
    ld.reset()
    assert len(list(ld)) == 3
    ld.close()


def test_loader_shuffle_covers_all():
    n, feat, batch = 16, 2, 4
    feats = np.repeat(np.arange(n, dtype=np.uint8)[:, None], feat, axis=1)
    labels = np.zeros(n, np.uint8)
    ld = nativert.AsyncNativeLoader.from_arrays(
        feats, labels, 2, batch, shuffle=True, seed=7, normalize=False)
    seen = sorted(int(x[0]) for xb, _ in ld for x in xb)
    assert seen == list(range(n))
    ld.close()


def test_mnist_loader_from_idx_files(tmp_path):
    imgs = np.random.default_rng(0).integers(0, 256, (10, 28, 28)).astype(np.uint8)
    lbls = (np.arange(10) % 10).astype(np.uint8)
    _write_idx(tmp_path / "img.idx", imgs)
    _write_idx(tmp_path / "lbl.idx", lbls)
    ld = nativert.AsyncNativeLoader.mnist(
        str(tmp_path / "img.idx"), str(tmp_path / "lbl.idx"), batch=5,
        shuffle=False)
    assert ld.num_examples == 10 and ld.feature_size == 784
    x, y = ld.next()
    np.testing.assert_allclose(
        x, imgs[:5].reshape(5, -1).astype(np.float32) / 255.0, atol=1e-6)
    np.testing.assert_array_equal(np.argmax(y, axis=1), lbls[:5])
    ld.close()


def test_cifar_loader(tmp_path):
    # CIFAR-10 binary: [label u8][3072 pixels u8] per record
    rng = np.random.default_rng(1)
    n = 6
    recs = bytearray()
    labels = []
    for i in range(n):
        lab = int(rng.integers(0, 10))
        labels.append(lab)
        recs.append(lab)
        recs += rng.integers(0, 256, 3072).astype(np.uint8).tobytes()
    p = tmp_path / "data_batch_1.bin"
    p.write_bytes(bytes(recs))
    ld = nativert.AsyncNativeLoader.cifar([str(p)], batch=3, shuffle=False)
    assert ld.num_examples == n and ld.feature_size == 3072
    _, y = ld.next()
    np.testing.assert_array_equal(np.argmax(y, axis=1), labels[:3])
    ld.close()


def test_csv_reader(tmp_path):
    p = tmp_path / "d.csv"
    p.write_text("# header\n1.5,2,3\n4,5.25,6\n7,8,9\n")
    out = nativert.read_csv_numeric(str(p), skip_lines=1)
    np.testing.assert_allclose(
        out, [[1.5, 2, 3], [4, 5.25, 6], [7, 8, 9]])


def _sample_report():
    r = StatsReport("sess-1", "worker-0", 1234567890123)
    r.iteration = 42
    r.score = 0.125
    r.iteration_time_ms = 3.5
    r.samples_per_sec = 1000.25
    r.mem_rss_bytes = 1 << 30
    r.device_mem_bytes = 2 << 30
    r.param_stats["layer0_W"] = (0.5, [1, 2, 3, 4], (-1.0, 1.0))
    r.gradient_stats["layer0_W"] = (0.01, [4, 3, 2, 1], (-0.1, 0.1))
    r.update_stats["layer0_b"] = (0.001, [7], (0.0, 0.002))
    return r


def test_stats_codec_matches_python(monkeypatch):
    r = _sample_report()
    native_bytes = r.encode()
    monkeypatch.setenv("DL4J_TPU_DISABLE_NATIVE", "1")
    python_bytes = r.encode()
    assert native_bytes == python_bytes


def test_stats_codec_decode_roundtrip():
    r = _sample_report()
    d = StatsReport.decode(r.encode())
    assert d.session_id == "sess-1" and d.worker_id == "worker-0"
    assert d.iteration == 42 and d.score == 0.125
    assert d.param_stats["layer0_W"] == (0.5, [1, 2, 3, 4], (-1.0, 1.0))
    assert d.update_stats["layer0_b"] == (0.001, [7], (0.0, 0.002))


def test_csv_trailing_delim_and_whitespace_fields(tmp_path):
    p = tmp_path / "e.csv"
    p.write_text("1,2,\n4,5,6\n")   # trailing empty field on row 1
    out = nativert.read_csv_numeric(str(p))
    np.testing.assert_allclose(out, [[1, 2, 0], [4, 5, 6]])
    p2 = tmp_path / "w.csv"
    p2.write_text("1, \n2,3\n")     # whitespace field must not eat next line
    out2 = nativert.read_csv_numeric(str(p2))
    np.testing.assert_allclose(out2, [[1, 0], [2, 3]])


def test_loader_use_after_close_raises():
    feats = np.zeros((4, 2), np.uint8)
    ld = nativert.AsyncNativeLoader.from_arrays(
        feats, np.zeros(4, np.uint8), 2, 2, shuffle=False)
    ld.close()
    with pytest.raises(ValueError):
        ld.next()
    with pytest.raises(ValueError):
        ld.reset()


def _python_counts(path, common):
    from collections import Counter
    from deeplearning4j_tpu.nlp.tokenization import CommonPreprocessor
    pre = CommonPreprocessor() if common else None
    c = Counter()
    with open(path) as f:
        for line in f:
            for tok in line.split():
                if pre is not None:
                    tok = pre.pre_process(tok)
                if tok:
                    c[tok] += 1
    return dict(c)


@pytest.mark.parametrize("common", [False, True])
def test_vocab_counter_matches_python(tmp_path, common):
    """Native parallel token counts == the Python tokenizer pipeline
    (reference VocabConstructor.java parallel count phase)."""
    p = tmp_path / "corpus.txt"
    text = ("The quick brown fox, jumps over the lazy dog!\n"
            "the quick RED fox; and the dog sleeps.\n" * 50)
    p.write_text(text)
    got = nativert.count_tokens_file(str(p), common_preprocess=common,
                                     nthreads=3)
    assert got is not None
    assert dict(got) == _python_counts(str(p), common)
    # deterministic ordering: count desc, then word asc
    counts = [c for _, c in got]
    assert counts == sorted(counts, reverse=True)
    for (w1, c1), (w2, c2) in zip(got, got[1:]):
        if c1 == c2:
            assert w1 < w2


def test_vocab_counter_separator_chars_match_python(tmp_path):
    """\x1c-\x1f are whitespace for str.split(); the native scan must agree."""
    p = tmp_path / "corpus.txt"
    p.write_bytes(b"a\x1cb a\x1db c\x1fd\n")
    got = nativert.count_tokens_file(str(p))
    assert got is not None
    assert dict(got) == _python_counts(str(p), False)


def test_vocab_counter_rejects_non_ascii(tmp_path):
    p = tmp_path / "corpus.txt"
    p.write_bytes("caf\xc3\xa9 au lait".encode("latin-1"))
    assert nativert.count_tokens_file(str(p)) is None


def test_vocab_constructor_native_equals_python(tmp_path):
    """VocabConstructor.build_from_file: native fast path == forced-Python
    fallback, including Huffman codes."""
    from deeplearning4j_tpu.nlp.tokenization import (
        CommonPreprocessor, DefaultTokenizerFactory)
    from deeplearning4j_tpu.nlp.vocab import VocabConstructor

    p = tmp_path / "corpus.txt"
    p.write_text("one two two three three three four four four four\n" * 20)
    tf = DefaultTokenizerFactory()
    tf.set_token_pre_processor(CommonPreprocessor())
    vc = VocabConstructor(min_word_frequency=1)
    native = vc.build_from_file(str(p), tf)

    class _NotDefault(DefaultTokenizerFactory):
        pass  # subclass => native path declines, Python pipeline runs

    tf2 = _NotDefault()
    tf2.set_token_pre_processor(CommonPreprocessor())
    python = vc.build_from_file(str(p), tf2)

    assert native.words() == python.words()
    for w in native.words():
        nw, pw = native.word_for(w), python.word_for(w)
        assert nw.count == pw.count
        assert nw.code == pw.code and nw.points == pw.points


def test_vocab_from_file_specials_always_present(tmp_path):
    """Specials absent from the corpus still enter the vocab, matching
    build_vocab's caller-side injection, on BOTH the native and Python
    paths."""
    from deeplearning4j_tpu.nlp.tokenization import DefaultTokenizerFactory
    from deeplearning4j_tpu.nlp.vocab import VocabConstructor

    p = tmp_path / "corpus.txt"
    p.write_text("alpha beta beta gamma\n" * 5)
    vc = VocabConstructor(min_word_frequency=1, special=("<UNK>",))
    native = vc.build_from_file(str(p))

    class _NotDefault(DefaultTokenizerFactory):
        pass

    python = vc.build_from_file(str(p), _NotDefault())
    assert "<UNK>" in native and "<UNK>" in python
    assert native.words() == python.words()
    for w in native.words():
        assert native.word_for(w).count == python.word_for(w).count
