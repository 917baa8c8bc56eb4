"""Native (C++) corpus processor: parity with the Python pipeline.

The shared library must produce token-for-token identical ids and vocabulary
to ``data.lm_text`` on ASCII corpora — then the trainer can use either path
interchangeably.
"""

import numpy as np
import pytest

from pipe_tpu.data import lm_text
from pipe_tpu.data.native import (NativeCorpus, native_available,
                                  process_corpus)

pytestmark = pytest.mark.skipif(not native_available(),
                                reason="no C++ toolchain")

SAMPLE = """The quick brown Fox jumps over the lazy dog.
Pack my box with five dozen liquor jugs!
(Hello, world); "quotes" and it's colons: done?

Repeated words repeated WORDS repeated.
"""


def python_reference(text):
    lines = text.splitlines()
    vocab = lm_text.Vocab(map(lm_text.basic_english_tokenize, lines))
    ids = lm_text.data_process(lines, vocab)
    return ids, [vocab.lookup_token(i) for i in range(len(vocab))]


def test_ids_and_vocab_parity():
    c = NativeCorpus.from_text(SAMPLE)
    exp_ids, exp_vocab = python_reference(SAMPLE)
    np.testing.assert_array_equal(c.ids(), exp_ids)
    assert c.vocab_list() == exp_vocab


def test_file_roundtrip(tmp_path):
    p = tmp_path / "c.txt"
    p.write_text(SAMPLE)
    ids, vocab = process_corpus(path=str(p))
    exp_ids, exp_vocab = python_reference(SAMPLE)
    np.testing.assert_array_equal(ids, exp_ids)
    assert vocab == exp_vocab


def test_lookup_and_unk():
    c = NativeCorpus.from_text("alpha beta gamma alpha")
    assert c.lookup("alpha") == 1  # 0 is <unk>
    assert c.lookup("never-seen") == 0
    assert c.token(0) == "<unk>"
    assert c.vocab_size == 4


def test_large_corpus_matches_and_is_fast():
    lines = lm_text.synthetic_corpus(120_000, 500, seed=9)
    text = "\n".join(lines)
    import time
    t0 = time.perf_counter()
    c = NativeCorpus.from_text(text)
    native_ids = c.ids()
    native_t = time.perf_counter() - t0

    t0 = time.perf_counter()
    exp_ids, _ = python_reference(text)
    python_t = time.perf_counter() - t0

    np.testing.assert_array_equal(native_ids, exp_ids)
    # not a hard perf gate, but native should never be slower
    assert native_t <= python_t, (native_t, python_t)


def test_empty_and_whitespace_only():
    c = NativeCorpus.from_text("\n   \n\t\n")
    assert c.num_tokens == 0
    assert c.vocab_size == 1  # just <unk>


def test_stale_library_is_rebuilt_by_source_hash(tmp_path):
    """Staleness is the hash of the source stored beside the library, not
    mtimes: a copied tree can carry a library NEWER than a source it was
    not built from."""
    import ctypes
    import os

    from pipe_tpu.data.native import _build_lib

    src, lib = str(tmp_path / "f.cpp"), str(tmp_path / "libf.so")

    def value():
        # load under a fresh name: dlopen caches by path
        copy = str(tmp_path / f"load{len(os.listdir(tmp_path))}.so")
        with open(lib, "rb") as a, open(copy, "wb") as b:
            b.write(a.read())
        return ctypes.CDLL(copy).f()

    with open(src, "w") as f:
        f.write('extern "C" int f() { return 1; }\n')
    assert _build_lib(src, lib) == lib and value() == 1
    built = os.path.getmtime(lib)
    assert _build_lib(src, lib) == lib            # fresh: not rebuilt
    assert os.path.getmtime(lib) == built

    with open(src, "w") as f:
        f.write('extern "C" int f() { return 2; }\n')
    old = built - 3600
    os.utime(src, (old, old))                     # source looks OLDER
    assert _build_lib(src, lib) == lib and value() == 2
