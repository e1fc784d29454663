"""ctypes bindings for the native C++ tokenizer (native/tokenizer.cpp).

Builds the shared library on first use (g++ only; no pybind11 in this
environment). Falls back cleanly when the toolchain is unavailable — the
Python tokenizer in ``reader.py`` has identical semantics.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional, Sequence

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, 'native', 'tokenizer.cpp')
_LIB = os.path.join(_REPO_ROOT, 'native', 'build', 'libc2vtok.so')

_TOKEN, _PATH, _TARGET = 0, 1, 2

_lib_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_error: Optional[str] = None


def _build_library() -> None:
    os.makedirs(os.path.dirname(_LIB), exist_ok=True)
    # build to a temp path + atomic rename: a killed or concurrent build
    # must never leave a corrupt .so at the final path
    tmp = '%s.%d.tmp' % (_LIB, os.getpid())
    cmd = ['g++', '-O3', '-std=c++17', '-shared', '-fPIC', '-pthread',
           _SRC, '-o', tmp]
    proc = subprocess.run(cmd, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError('native tokenizer build failed: '
                           + proc.stderr.strip())
    os.replace(tmp, _LIB)


def _load() -> ctypes.CDLL:
    global _lib, _lib_error
    with _lib_lock:
        if _lib is not None:
            return _lib
        if _lib_error is not None:
            raise RuntimeError(_lib_error)
        try:
            if not os.path.isfile(_LIB) or (
                    os.path.isfile(_SRC)
                    and os.path.getmtime(_SRC) > os.path.getmtime(_LIB)):
                _build_library()
            lib = ctypes.CDLL(_LIB)
        except (OSError, RuntimeError) as e:
            _lib_error = str(e)
            raise RuntimeError(_lib_error)
        lib.c2v_tok_create.restype = ctypes.c_void_p
        lib.c2v_tok_destroy.argtypes = [ctypes.c_void_p]
        lib.c2v_tok_add_words.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_char_p,
            ctypes.c_int64, ctypes.POINTER(ctypes.c_int32), ctypes.c_int32]
        lib.c2v_tok_set_special.argtypes = [
            ctypes.c_void_p, ctypes.c_int32, ctypes.c_int32, ctypes.c_int32]
        lib.c2v_tok_tokenize.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p,
            ctypes.POINTER(ctypes.c_int64), ctypes.c_int32, ctypes.c_int32,
            ctypes.c_int32, ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_int32), ctypes.POINTER(ctypes.c_int32),
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_int32)]
        _lib = lib
        return _lib


def is_available() -> bool:
    try:
        _load()
        return True
    except RuntimeError:
        return False


def get_tokenizer(vocabs, config) -> 'NativeTokenizer':
    """Cached per vocab-triple: building one uploads every vocab word into
    the C++ hash maps (tens of MB at java14m scale) — do it once, not per
    reader. The cache lives ON the vocabs object so it can never outlive or
    be confused with another vocab set, and dies with it."""
    cache = getattr(vocabs, '_native_tokenizer_cache', None)
    if cache is None:
        cache = {}
        vocabs._native_tokenizer_cache = cache
    tokenizer = cache.get(config.MAX_CONTEXTS)
    if tokenizer is None:
        tokenizer = NativeTokenizer(vocabs, config)
        cache[config.MAX_CONTEXTS] = tokenizer
    return tokenizer


def _i32_ptr(arr: np.ndarray):
    return arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


class NativeTokenizer:
    """Vocab tables live in C++; ``tokenize_lines`` produces the same Batch
    arrays as the Python path."""

    def __init__(self, vocabs, config):
        from code2vec_tpu.data.reader import Batch  # avoid import cycle
        self._Batch = Batch
        self.config = config
        self.lib = _load()
        self.handle = ctypes.c_void_p(self.lib.c2v_tok_create())
        self.num_threads = max(1, config.READER_NUM_PARALLEL_BATCHES)
        for vocab_id, vocab in ((_TOKEN, vocabs.token_vocab),
                                (_PATH, vocabs.path_vocab),
                                (_TARGET, vocabs.target_vocab)):
            self._add_vocab(vocab_id, vocab)
            pad = getattr(vocab.special_words, 'PAD', None)
            pad_index = vocab.word_to_index[pad] if pad is not None \
                else vocab.oov_index
            self.lib.c2v_tok_set_special(self.handle, vocab_id,
                                         vocab.oov_index, pad_index)

    def _add_vocab(self, vocab_id: int, vocab) -> None:
        words = list(vocab.word_to_index.keys())
        # keys() and values() iterate in the same order
        indices = np.fromiter(vocab.word_to_index.values(),
                              dtype=np.int32, count=len(words))
        blob = '\n'.join(words).encode('utf-8')
        self.lib.c2v_tok_add_words(self.handle, vocab_id, blob,
                                   len(blob), _i32_ptr(indices), len(words))

    def __del__(self):
        try:
            if getattr(self, 'handle', None):
                self.lib.c2v_tok_destroy(self.handle)
        except Exception:
            pass

    def tokenize_lines(self, lines: Sequence[str]):
        n = len(lines)
        max_contexts = self.config.MAX_CONTEXTS
        # surrogatepass: a line the Python path accepts is accepted here
        # (a lone surrogate matches no vocabulary word on either path)
        encoded = [line.encode('utf-8', 'surrogatepass') for line in lines]
        blob = b'\n'.join(encoded)
        # offsets[i] = byte start of line i; the slice [off[i], off[i+1])
        # includes the '\n' separator, which the C++ side strips
        offsets = np.zeros(n + 1, dtype=np.int64)
        np.cumsum([len(e) + 1 for e in encoded], out=offsets[1:])
        offsets[n] = len(blob)

        source = np.empty((n, max_contexts), dtype=np.int32)
        path = np.empty((n, max_contexts), dtype=np.int32)
        target = np.empty((n, max_contexts), dtype=np.int32)
        mask = np.empty((n, max_contexts), dtype=np.float32)
        label = np.empty((n,), dtype=np.int32)
        self.lib.c2v_tok_tokenize(
            self.handle, blob, offsets.ctypes.data_as(
                ctypes.POINTER(ctypes.c_int64)),
            n, max_contexts, self.num_threads,
            _i32_ptr(source), _i32_ptr(path), _i32_ptr(target),
            mask.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            _i32_ptr(label))
        return self._Batch(source=source, path=path, target=target,
                           mask=mask, label=label,
                           weight=np.ones((n,), dtype=np.float32))
