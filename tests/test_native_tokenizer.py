"""Native C++ tokenizer: byte-identical semantics with the Python path."""
import pickle
import sys
import threading

import numpy as np
import pytest

from code2vec_tpu.config import Config
from code2vec_tpu.data import native
from code2vec_tpu.data.reader import (EstimatorAction, PathContextReader,
                                      canonicalize_contexts,
                                      context_triples, parse_c2v_line)
from code2vec_tpu.vocab import Code2VecVocabs

from tests.test_reader import small_setup  # noqa: F401  (fixture)

pytestmark = pytest.mark.skipif(not native.is_available(),
                                reason='native toolchain unavailable')


def _readers(small_setup):  # noqa: F811
    config, vocabs, prefix = small_setup
    py_reader = PathContextReader(vocabs, config, EstimatorAction.Train)
    py_reader._native = None
    config_native = config
    native_reader = PathContextReader(vocabs, config_native,
                                      EstimatorAction.Train)
    native_reader._native = native.get_tokenizer(vocabs, config_native)
    return py_reader, native_reader


LINES = [
    'lbl1 s1,p1,t1 zzz,p2,t1 s2,qqq,qq  ',
    ' s1,p1,t1',                # empty label -> OOV (CSV default is OOV)
    'unknownlbl s1,p1,t1',
    'lbl2 zz,zz,zz',
    'lbl2 s2,p2,t1 s1,p1',      # malformed 2-part context
    'lbl1 ,, s1,p1,t1',         # empty parts
    'onlylabel',
    'lbl1 s1',                  # single-part context
    'lbl1 s1,p1,t1,extra s2,p2,t1,x,y',     # further parts are dropped
]

#: what a predict surface may be handed besides: doubled spaces inside a
#: line, more contexts than MAX_CONTEXTS (4) with an empty slot among
#: those kept, non-ASCII words in and out of the vocabularies, a line
#: ending, a lone surrogate, nothing at all
PREDICT_LINES = LINES + [
    'lbl2 s2,p2,t1  s1,p1,t1   zz,p1,zz',
    'lbl1 s2,p2,t1  t1,p1,s1 s1,p1,t1 s2,p1,s2 s1,p2,s1 t1,p2,t1',
    'lbl1 zzz,p1,t1 s1,p1,t1 b,p2,t1 a,p2,t1 s2,p2,s2',
    'naïve|λ s1,p→q,ünï ünï,p1,日本 日本,p1,s1',
    'lbl1 s1,p1,t1 s2,p2,t1\r\n',
    'lbl1 z\r s1,p1,t1',       # sorts last: its \r ends the canonical line
    'lbl2 s1,p1,\udc80 \udc80,p2,t1',
    '',
]


def test_native_matches_python(small_setup):  # noqa: F811
    py_reader, native_reader = _readers(small_setup)
    py_batch = py_reader.tokenize_lines(LINES)
    native_batch = native_reader.tokenize_lines(LINES)
    np.testing.assert_array_equal(py_batch.source, native_batch.source)
    np.testing.assert_array_equal(py_batch.path, native_batch.path)
    np.testing.assert_array_equal(py_batch.target, native_batch.target)
    np.testing.assert_array_equal(py_batch.mask, native_batch.mask)
    np.testing.assert_array_equal(py_batch.label, native_batch.label)


def test_native_used_in_full_epoch(small_setup):  # noqa: F811
    config, vocabs, prefix = small_setup
    with open(str(prefix) + '.train.c2v', 'w') as f:
        f.write('lbl1 s1,p1,t1\nlbl2 s2,p2,t1\nunknown s1,p1,t1\n' * 10)
    py_reader = PathContextReader(vocabs, config, EstimatorAction.Train)
    py_reader._native = None
    native_reader = PathContextReader(vocabs, config, EstimatorAction.Train)
    native_reader._native = native.get_tokenizer(vocabs, config)
    py_batches = list(py_reader.iter_epoch(shuffle=False))
    native_batches = list(native_reader.iter_epoch(shuffle=False))
    assert len(py_batches) == len(native_batches)
    for a, b in zip(py_batches, native_batches):
        np.testing.assert_array_equal(a.source, b.source)
        np.testing.assert_array_equal(a.label, b.label)
        np.testing.assert_array_equal(a.weight, b.weight)


def test_native_serves_the_evaluate_path(small_setup):  # noqa: F811
    """Evaluate readers use the native tokenizer for indices and retain
    only the label strings (VERDICT r1 #7) — identical batches to the
    Python path, label strings included."""
    config, vocabs, prefix = small_setup
    config.READER_USE_NATIVE = True
    with open(str(prefix) + '.val.c2v', 'w') as f:
        # 3 evaluable rows + 1 the eval filter drops (no valid context)
        f.write('lbl1 s1,p1,t1\nunknown s2,p2,t1\nlbl2 zz,zz,zz\n'
                'lbl2 s2,p1,t1\n')
    config.TEST_DATA_PATH = str(prefix) + '.val.c2v'

    native_reader = PathContextReader(vocabs, config,
                                      EstimatorAction.Evaluate)
    assert native_reader._native is not None  # no Python fallback for eval
    assert native_reader.keep_label_strings
    assert not native_reader.keep_context_strings
    py_reader = PathContextReader(vocabs, config, EstimatorAction.Evaluate)
    py_reader._native = None

    py_batches = list(py_reader.iter_epoch(shuffle=False))
    native_batches = list(native_reader.iter_epoch(shuffle=False))
    assert len(py_batches) == len(native_batches) == 2
    for a, b in zip(py_batches, native_batches):
        np.testing.assert_array_equal(a.source, b.source)
        np.testing.assert_array_equal(a.mask, b.mask)
        np.testing.assert_array_equal(a.label, b.label)
        np.testing.assert_array_equal(a.weight, b.weight)
        np.testing.assert_array_equal(a.label_strings, b.label_strings)
        assert b.context_lines is None  # predict-only payload

    # predict keeps each row's canonical line beside the native index
    # arrays (the attention decode makes the contexts' strings from it)
    predict_reader = PathContextReader(vocabs, config,
                                       EstimatorAction.Predict)
    assert predict_reader.native and predict_reader.keep_context_strings
    batch = predict_reader.process_input_rows(['lbl1 s2,p2,t1 s1,p1,t1'])
    assert list(batch.context_lines) == ['lbl1 s1,p1,t1 s2,p2,t1']


def test_native_multithreaded_large_batch(small_setup):  # noqa: F811
    config, vocabs, prefix = small_setup
    tokenizer = native.get_tokenizer(vocabs, config)
    lines = ['lbl1 s1,p1,t1 s2,p2,t1'] * 500  # > threading threshold
    batch = tokenizer.tokenize_lines(lines)
    assert batch.source.shape == (500, config.MAX_CONTEXTS)
    assert (batch.mask[:, :2] == 1.0).all()
    assert (batch.mask[:, 2:] == 0.0).all()


# ---------------------------------------------------- the predict path
@pytest.fixture
def unicode_setup(tmp_path):
    """``small_setup``'s vocabularies with a non-ASCII token, path and
    label among them."""
    prefix = tmp_path / 'ds'
    with open(str(prefix) + '.dict.c2v', 'wb') as f:
        pickle.dump({'s1': 10, 's2': 9, 't1': 8, 'ünï': 7, '日本': 6}, f)
        pickle.dump({'p1': 7, 'p2': 6, 'p→q': 5}, f)
        pickle.dump({'lbl1': 5, 'lbl2': 4, 'naïve|λ': 3}, f)
        pickle.dump(4, f)
    config = Config(TRAIN_DATA_PATH_PREFIX=str(prefix), VERBOSE_MODE=0,
                    MAX_CONTEXTS=4, TRAIN_BATCH_SIZE=2, TEST_BATCH_SIZE=2,
                    SHUFFLE_BUFFER_SIZE=16, READER_USE_NATIVE=True)
    return config, Code2VecVocabs(config)


def _predict_readers(setup):
    config, vocabs = setup[0], setup[1]
    config.READER_USE_NATIVE = True
    native_reader = PathContextReader(vocabs, config,
                                      EstimatorAction.Predict)
    assert native_reader.native
    py_reader = PathContextReader(vocabs, config, EstimatorAction.Predict)
    py_reader._native = None        # the host without a toolchain
    return py_reader, native_reader


def _assert_batches_equal(a, b):
    for field in ('source', 'path', 'target', 'mask', 'label', 'weight',
                  'label_strings', 'context_lines'):
        x, y = getattr(a, field), getattr(b, field)
        assert x.dtype == y.dtype and x.shape == y.shape, field
        np.testing.assert_array_equal(x, y, err_msg=field)


@pytest.mark.parametrize('line', PREDICT_LINES)
def test_predict_path_native_equals_python(unicode_setup, line):
    """``process_input_rows`` through the native tokenizer gives the
    Python fallback's ids, mask, labels and strings, array for array."""
    py_reader, native_reader = _predict_readers(unicode_setup)
    _assert_batches_equal(py_reader.process_input_rows([line]),
                          native_reader.process_input_rows([line]))


def test_predict_path_native_equals_python_whole_request(unicode_setup):
    py_reader, native_reader = _predict_readers(unicode_setup)
    want = py_reader.process_input_rows(PREDICT_LINES)
    got = native_reader.process_input_rows(PREDICT_LINES)
    _assert_batches_equal(want, got)
    # the vocabularies' non-ASCII words were found, not mapped to OOV
    row = PREDICT_LINES.index('naïve|λ s1,p→q,ünï ünï,p1,日本 日本,p1,s1')
    assert got.label[row] > 0
    assert (got.source[row, :3] > 0).all()
    assert (got.path[row, :3] > 0).all()
    assert (got.target[row, :3] > 0).all()


@pytest.mark.parametrize('line', PREDICT_LINES)
def test_context_strings_made_at_decode_equal_the_parsed_slots(line):
    """``context_triples`` of a row's canonical line, slot by slot, are
    the strings the per-slot parse used to carry with the batch."""
    canonical, = canonicalize_contexts([line], 4)
    # and of a raw line (an evaluate-path reader never canonicalizes):
    # empty slots keep their place
    for text in (canonical, line):
        row = parse_c2v_line(text, 4)
        want = list(zip(row.source_strs, row.path_strs, row.target_strs))
        got = list(context_triples(text))[:4]
        assert got == want[:len(got)]
        assert not any(any(triple) for triple in want[len(got):])


def test_four_threads_tokenize_as_one(unicode_setup):
    """Four callers in the native tokenizer at once (the library runs
    outside the interpreter lock) each get what one caller gets."""
    _, native_reader = _predict_readers(unicode_setup)
    requests = [PREDICT_LINES[i:] + PREDICT_LINES[:i]
                for i in range(len(PREDICT_LINES))]
    want = [native_reader.process_input_rows(r) for r in requests]
    got = [[None] * len(requests) for _ in range(4)]
    start = threading.Barrier(4)

    def caller(t):
        start.wait()
        for _ in range(20):
            for i, request in enumerate(requests):
                got[t][i] = native_reader.process_input_rows(request)

    threads = [threading.Thread(target=caller, args=(t,), daemon=True)
               for t in range(4)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)     # callers preempted mid-request
    try:
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(thread.is_alive() for thread in threads)
    for per_thread in got:
        for a, b in zip(want, per_thread):
            _assert_batches_equal(a, b)
