"""ctypes binding for the native corpus processor (csrc/pipetpu_io.cpp).

The performance path for host-side input processing: one C++ pass builds the
token-id stream and first-appearance vocabulary (the reference stack's data
loading likewise bottoms out in torchtext's native kernels). The library is
compiled on first use with g++ and cached next to the source; everything
falls back to the pure-Python pipeline (``data.lm_text``) when a toolchain
is unavailable, with identical token-for-token semantics (asserted by
``tests/test_native_io.py``).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import List, Optional, Tuple

import numpy as np

__all__ = ["native_available", "NativeCorpus", "process_corpus",
           "prefetch_available", "BatchPrefetcher"]

_CSRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "csrc")
_SRC = os.path.join(_CSRC, "pipetpu_io.cpp")
_LIB = os.path.join(_CSRC, "libpipetpu_io.so")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def _build_lib(src: str, lib: str, *extra_flags: str) -> Optional[str]:
    """Compile a shared library if missing or stale; None on failure.

    Stale means the hash of the source (and flags) stored beside the
    library differs from the source's: a tree copied as it lies carries an
    untracked library whose mtime says nothing about which source built it.
    """
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", *extra_flags]
    stamp = lib + ".sha256"
    try:
        with open(src, "rb") as f:
            want = hashlib.sha256(
                " ".join(cmd).encode() + b"\0" + f.read()).hexdigest()
        have = None
        if os.path.exists(lib) and os.path.exists(stamp):
            with open(stamp) as f:
                have = f.read().strip()
        if have != want:
            subprocess.run(cmd + [src, "-o", lib],
                           check=True, capture_output=True, timeout=120)
            with open(stamp, "w") as f:
                f.write(want + "\n")
        return lib
    except (OSError, subprocess.SubprocessError):
        return None


def _build() -> Optional[str]:
    return _build_lib(_SRC, _LIB)


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    with _lock:
        if _lib is not None or _build_failed:
            return _lib
        path = _build()
        if path is None:
            _build_failed = True
            return None
        lib = ctypes.CDLL(path)
        lib.ptio_from_bytes.restype = ctypes.c_void_p
        lib.ptio_from_bytes.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.ptio_from_file.restype = ctypes.c_void_p
        lib.ptio_from_file.argtypes = [ctypes.c_char_p]
        lib.ptio_num_tokens.restype = ctypes.c_int64
        lib.ptio_num_tokens.argtypes = [ctypes.c_void_p]
        lib.ptio_vocab_size.restype = ctypes.c_int32
        lib.ptio_vocab_size.argtypes = [ctypes.c_void_p]
        lib.ptio_copy_ids.restype = None
        lib.ptio_copy_ids.argtypes = [ctypes.c_void_p, ctypes.POINTER(
            ctypes.c_int32)]
        lib.ptio_token.restype = ctypes.c_char_p
        lib.ptio_token.argtypes = [ctypes.c_void_p, ctypes.c_int32]
        lib.ptio_lookup.restype = ctypes.c_int32
        lib.ptio_lookup.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.ptio_free.restype = None
        lib.ptio_free.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def native_available() -> bool:
    return _load() is not None


class NativeCorpus:
    """Token ids + vocabulary built by the C++ pass."""

    def __init__(self, handle: int, lib: ctypes.CDLL):
        self._h = handle
        self._lib = lib

    @classmethod
    def from_file(cls, path: str) -> "NativeCorpus":
        lib = _load()
        if lib is None:
            raise RuntimeError("native corpus library unavailable")
        h = lib.ptio_from_file(path.encode())
        if not h:
            raise FileNotFoundError(
                f"{path}: unreadable, non-seekable, or out of memory")
        return cls(h, lib)

    @classmethod
    def from_text(cls, text: str) -> "NativeCorpus":
        lib = _load()
        if lib is None:
            raise RuntimeError("native corpus library unavailable")
        data = text.encode()
        h = lib.ptio_from_bytes(data, len(data))
        if not h:
            raise MemoryError("native corpus build failed")
        return cls(h, lib)

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.ptio_free(self._h)
            self._h = None

    @property
    def num_tokens(self) -> int:
        return int(self._lib.ptio_num_tokens(self._h))

    @property
    def vocab_size(self) -> int:
        return int(self._lib.ptio_vocab_size(self._h))

    def ids(self) -> np.ndarray:
        out = np.empty(self.num_tokens, np.int32)
        self._lib.ptio_copy_ids(
            self._h, out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
        return out

    def token(self, idx: int) -> str:
        raw = self._lib.ptio_token(self._h, idx)
        if raw is None:
            raise IndexError(idx)
        return raw.decode()

    def lookup(self, token: str) -> int:
        return int(self._lib.ptio_lookup(self._h, token.encode()))

    def vocab_list(self) -> List[str]:
        return [self.token(i) for i in range(self.vocab_size)]


def process_corpus(path: Optional[str] = None, text: Optional[str] = None
                   ) -> Tuple[np.ndarray, List[str]]:
    """(ids, vocab) via the native pass, falling back to pure Python.

    The native pass is used only for ASCII corpora — its lowercase and
    whitespace handling are byte-wise, while the Python tokenizer is
    Unicode-aware, so routing non-ASCII text natively would change ids.
    """
    if (path is None) == (text is None):
        raise ValueError("pass exactly one of path or text")
    if text is None:
        with open(path, encoding="utf-8") as f:
            text_content = f.read()
    else:
        text_content = text
    if native_available() and text_content.isascii():
        c = (NativeCorpus.from_file(path) if path is not None
             else NativeCorpus.from_text(text))
        return c.ids(), c.vocab_list()
    from . import lm_text
    lines = text_content.splitlines()
    vocab = lm_text.Vocab(map(lm_text.basic_english_tokenize, lines))
    return lm_text.data_process(lines, vocab), \
        [vocab.lookup_token(i) for i in range(len(vocab))]


# --- native batch prefetcher (csrc/pipetpu_prefetch.cpp) ---

_PF_SRC = os.path.join(_CSRC, "pipetpu_prefetch.cpp")
_PF_LIB = os.path.join(_CSRC, "libpipetpu_prefetch.so")

_pf_lib: Optional[ctypes.CDLL] = None
_pf_build_failed = False


def _load_prefetch() -> Optional[ctypes.CDLL]:
    global _pf_lib, _pf_build_failed
    with _lock:
        if _pf_lib is not None or _pf_build_failed:
            return _pf_lib
        path = _build_lib(_PF_SRC, _PF_LIB, "-pthread")
        if path is None:
            _pf_build_failed = True
            return None
        lib = ctypes.CDLL(path)
        i32p = ctypes.POINTER(ctypes.c_int32)
        lib.ptpf_create.restype = ctypes.c_void_p
        lib.ptpf_create.argtypes = [i32p, ctypes.c_int64, ctypes.c_int64,
                                    ctypes.c_int64, ctypes.c_int64,
                                    i32p, i32p]
        lib.ptpf_num_batches.restype = ctypes.c_int64
        lib.ptpf_num_batches.argtypes = [ctypes.c_void_p]
        lib.ptpf_next.restype = ctypes.c_int64
        lib.ptpf_next.argtypes = [ctypes.c_void_p]
        lib.ptpf_release.restype = None
        lib.ptpf_release.argtypes = [ctypes.c_void_p]
        lib.ptpf_free.restype = None
        lib.ptpf_free.argtypes = [ctypes.c_void_p]
        _pf_lib = lib
        return _pf_lib


def prefetch_available() -> bool:
    return _load_prefetch() is not None


class BatchPrefetcher:
    """Iterator over (data, target) LM batches assembled by a C++ thread.

    Matches the trainer's ``get_batch`` walk exactly (``lm_text.get_batch``
    slice + transpose per full batch; tail batches are never yielded — the
    trainer breaks on them anyway), but the assembly runs on a producer
    thread writing into a ``depth``-slot ring of pre-allocated buffers, so
    batch prep overlaps device compute.

    Double-buffer contract: the arrays yielded for batch ``b`` are views
    into ring slot ``b % depth`` and are valid ONLY until the next
    ``__next__`` call — advancing the iterator releases the previous slot
    back to the producer, which may immediately start overwriting it.
    Callers that keep references across iterations must ``.copy()``
    (``Trainer._batches`` does).
    """

    def __init__(self, source: np.ndarray, bptt: int, depth: int = 2):
        lib = _load_prefetch()
        if lib is None:
            raise RuntimeError("native prefetch library unavailable")
        if source.ndim != 2:
            raise ValueError(f"source must be [nbatch, bsz], got "
                             f"{source.shape}")
        if bptt <= 0 or depth <= 0:
            raise ValueError("bptt and depth must be positive")
        self._lib = lib
        # keep the producer's input alive and contiguous for its lifetime
        self._source = np.ascontiguousarray(source, dtype=np.int32)
        nrows, bsz = self._source.shape
        self._data = np.empty((depth, bsz, bptt), np.int32)
        self._target = np.empty((depth, bsz, bptt), np.int32)
        i32p = ctypes.POINTER(ctypes.c_int32)
        self._h = lib.ptpf_create(
            self._source.ctypes.data_as(i32p), nrows, bsz, bptt, depth,
            self._data.ctypes.data_as(i32p),
            self._target.ctypes.data_as(i32p))
        if not self._h:
            raise MemoryError("native prefetcher creation failed")
        self._outstanding = False
        self.num_batches = int(lib.ptpf_num_batches(self._h))

    def __iter__(self):
        return self

    def __next__(self):
        if self._h is None:
            raise StopIteration
        if self._outstanding:
            self._lib.ptpf_release(self._h)
            self._outstanding = False
        slot = int(self._lib.ptpf_next(self._h))
        if slot < 0:
            self.close()
            raise StopIteration
        self._outstanding = True
        return self._data[slot], self._target[slot]

    def close(self):
        if getattr(self, "_h", None):
            self._lib.ptpf_free(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        self.close()
