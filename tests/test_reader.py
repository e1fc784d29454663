import pickle

import numpy as np
import pytest

from code2vec_tpu.config import Config
from code2vec_tpu.data import (Batch, EstimatorAction, PathContextReader,
                               parse_c2v_line)
from code2vec_tpu.data.reader import canonicalize_contexts
from code2vec_tpu.vocab import Code2VecVocabs


@pytest.fixture
def small_setup(tmp_path):
    """Vocab: tokens {s1,s2,t1}, paths {p1,p2}, targets {lbl1,lbl2}."""
    prefix = tmp_path / 'ds'
    with open(str(prefix) + '.dict.c2v', 'wb') as f:
        pickle.dump({'s1': 10, 's2': 9, 't1': 8}, f)
        pickle.dump({'p1': 7, 'p2': 6}, f)
        pickle.dump({'lbl1': 5, 'lbl2': 4}, f)
        pickle.dump(4, f)
    config = Config(TRAIN_DATA_PATH_PREFIX=str(prefix), VERBOSE_MODE=0,
                    MAX_CONTEXTS=4, TRAIN_BATCH_SIZE=2, TEST_BATCH_SIZE=2,
                    SHUFFLE_BUFFER_SIZE=16, READER_USE_NATIVE=False)
    vocabs = Code2VecVocabs(config)
    return config, vocabs, prefix


def _write_train(prefix, lines):
    with open(str(prefix) + '.train.c2v', 'w') as f:
        f.write('\n'.join(lines) + '\n')


def test_parse_line_pads_contexts():
    row = parse_c2v_line('lbl s1,p1,t1 s2,p2,t1', 4)
    assert row.label_str == 'lbl'
    assert row.source_strs == ['s1', 's2', '', '']
    assert row.path_strs == ['p1', 'p2', '', '']
    assert row.target_strs == ['t1', 't1', '', '']


def test_parse_line_truncates_extra_contexts():
    row = parse_c2v_line('lbl a,b,c d,e,f g,h,i', 2)
    assert row.source_strs == ['a', 'd']


def test_tokenize_semantics(small_setup):
    config, vocabs, prefix = small_setup
    reader = PathContextReader(vocabs, config, EstimatorAction.Train)
    # token vocab: <PAD_OR_OOV>=0, s1=1, s2=2, t1=3 (freq order)
    batch = reader.tokenize_lines(['lbl1 s1,p1,t1 zzz,p2,t1 s2,qqq,qq  '])
    np.testing.assert_array_equal(batch.source[0], [1, 0, 2, 0])
    np.testing.assert_array_equal(batch.path[0], [1, 2, 0, 0])
    np.testing.assert_array_equal(batch.target[0], [3, 3, 0, 0])
    # ctx1 fully valid; ctx2 has OOV source but valid path+target -> valid;
    # ctx3 has valid source only -> valid; ctx4 empty -> invalid.
    np.testing.assert_array_equal(batch.mask[0], [1.0, 1.0, 1.0, 0.0])
    assert batch.label[0] == vocabs.target_vocab.lookup_index('lbl1')


def test_all_oov_context_is_masked_with_joined_policy(small_setup):
    # With PAD==OOV, a context whose three parts are all out-of-vocab maps
    # to index 0 everywhere and must be masked out — the reference's
    # hashtable-default behaviour (path_context_reader.py:209-214).
    config, vocabs, prefix = small_setup
    reader = PathContextReader(vocabs, config, EstimatorAction.Train)
    batch = reader.tokenize_lines(['lbl1 zz,zz,zz s1,p1,t1'])
    np.testing.assert_array_equal(batch.mask[0], [0.0, 1.0, 0.0, 0.0])


def test_train_filter_drops_oov_targets_and_empty_rows(small_setup):
    config, vocabs, prefix = small_setup
    _write_train(prefix, [
        'lbl1 s1,p1,t1',          # kept
        'unknownlbl s1,p1,t1',    # dropped: OOV target (train only)
        'lbl2 zz,zz,zz',          # dropped: no valid contexts
        'lbl2 s2,p2,t1',          # kept
    ])
    reader = PathContextReader(vocabs, config, EstimatorAction.Train)
    batches = list(reader.iter_epoch(shuffle=False))
    assert len(batches) == 1
    assert batches[0].num_valid_examples == 2
    labels = set(batches[0].label.tolist())
    assert labels == {vocabs.target_vocab.lookup_index('lbl1'),
                      vocabs.target_vocab.lookup_index('lbl2')}


def test_eval_keeps_oov_targets(small_setup):
    config, vocabs, prefix = small_setup
    test_file = str(prefix) + '.val.c2v'
    with open(test_file, 'w') as f:
        f.write('unknownlbl s1,p1,t1\nlbl1 s1,p1,t1\n')
    config.TEST_DATA_PATH = test_file
    reader = PathContextReader(vocabs, config, EstimatorAction.Evaluate)
    batches = list(reader.iter_epoch(shuffle=False))
    assert len(batches) == 1
    assert batches[0].num_valid_examples == 2
    # eval keeps the label string for host-side metrics
    assert batches[0].label_strings[0] == 'unknownlbl'
    assert batches[0].label[0] == vocabs.target_vocab.oov_index


def test_final_partial_batch_is_padded_static(small_setup):
    config, vocabs, prefix = small_setup
    _write_train(prefix, [
        'lbl1 s1,p1,t1', 'lbl2 s1,p1,t1', 'lbl1 s2,p2,t1',
    ])
    reader = PathContextReader(vocabs, config, EstimatorAction.Train)
    batches = list(reader.iter_epoch(shuffle=False))
    assert len(batches) == 2
    # static shape everywhere
    for batch in batches:
        assert batch.source.shape == (2, 4)
        assert batch.weight.shape == (2,)
    assert batches[1].num_valid_examples == 1
    np.testing.assert_array_equal(batches[1].weight, [1.0, 0.0])
    np.testing.assert_array_equal(batches[1].mask[1], [0, 0, 0, 0])


def test_shuffle_is_a_permutation(small_setup):
    config, vocabs, prefix = small_setup
    lines = ['lbl1 s1,p1,t1'] * 3 + ['lbl2 s2,p2,t1'] * 3
    _write_train(prefix, lines)
    reader = PathContextReader(vocabs, config, EstimatorAction.Train)
    all_labels = []
    for batch in reader.iter_epoch(shuffle=True, seed=0):
        all_labels.extend(batch.label[batch.weight > 0].tolist())
    assert sorted(all_labels) == sorted(
        [vocabs.target_vocab.lookup_index('lbl1')] * 3
        + [vocabs.target_vocab.lookup_index('lbl2')] * 3)


def test_prefetched_equals_sync(small_setup):
    config, vocabs, prefix = small_setup
    _write_train(prefix, ['lbl1 s1,p1,t1', 'lbl2 s2,p2,t1'] * 3)
    reader = PathContextReader(vocabs, config, EstimatorAction.Train)
    sync = list(reader.iter_epoch(shuffle=False))
    prefetched = list(reader.iter_epoch_prefetched(shuffle=False))
    assert len(sync) == len(prefetched)
    for a, b in zip(sync, prefetched):
        np.testing.assert_array_equal(a.source, b.source)
        np.testing.assert_array_equal(a.label, b.label)


def test_prefetched_abandoned_early_does_not_leak_thread(small_setup):
    import threading
    config, vocabs, prefix = small_setup
    config.READER_PREFETCH_BATCHES = 1
    _write_train(prefix, ['lbl1 s1,p1,t1', 'lbl2 s2,p2,t1'] * 20)
    reader = PathContextReader(vocabs, config, EstimatorAction.Train)
    before = threading.active_count()
    for _ in range(5):
        it = reader.iter_epoch_prefetched(shuffle=False)
        next(it)        # take one batch...
        it.close()      # ...then abandon mid-epoch
    assert threading.active_count() <= before


def test_process_input_rows_never_filters(small_setup):
    config, vocabs, prefix = small_setup
    reader = PathContextReader(vocabs, config, EstimatorAction.Predict)
    batch = reader.process_input_rows(['unknownlbl zz,zz,zz'])
    assert batch.label.shape == (1,)
    assert batch.label_strings[0] == 'unknownlbl'
    np.testing.assert_array_equal(batch.mask[0], [0, 0, 0, 0])


@pytest.mark.parametrize('use_native', [False, True],
                         ids=['fallback', 'native'])
def test_predict_batch_keeps_its_lines_through_slice_concat_and_pad(
        small_setup, use_native):
    """A predict batch carries each row's canonical line and label
    beside its ids; slicing (an oversize request's chunks), merging (a
    micro-batch) and padding (a bucket) keep them on their rows."""
    from code2vec_tpu.data import native
    if use_native and not native.is_available():
        pytest.skip('native toolchain unavailable')
    config, vocabs, prefix = small_setup
    config.READER_USE_NATIVE = use_native
    reader = PathContextReader(vocabs, config, EstimatorAction.Predict)
    assert reader.native == use_native
    lines = ['lbl%d %s' % (i, ' '.join(['s1,p1,t1'] * (i % 4 + 1)
                                        + ['s2,p2,t1'] * (i % 3)))
             for i in range(7)]
    batch = reader.process_input_rows(lines)
    canonical = canonicalize_contexts(lines, config.MAX_CONTEXTS)
    assert list(batch.context_lines) == canonical

    def rows_of(b):
        return [(str(b.label_strings[r]), str(b.context_lines[r]),
                 int(b.mask[r].sum())) for r in range(b.label.shape[0])]

    want = [(line.split(' ', 1)[0], line, len(line.split(' ')) - 1)
            for line in canonical]
    assert rows_of(batch) == want
    chunks = [PathContextReader._take_rows(batch, slice(i, i + 3))
              for i in range(0, 7, 3)]
    assert [rows_of(c) for c in chunks] == [want[0:3], want[3:6], want[6:]]
    merged = PathContextReader._concat([chunks[2], chunks[0]])
    assert rows_of(merged) == want[6:] + want[0:3]
    padded = reader.pad_batch_to(merged, 8)
    assert rows_of(padded) == want[6:] + want[0:3] + [('', '', 0)] * 4
    assert list(padded.weight) == [1.0] * 4 + [0.0] * 4
