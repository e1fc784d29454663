"""The generators: pure functions of their parameters and the seed."""
import os
import pickle

import numpy as np
import pytest

from chipbench.traffic import arrivals, corpus

VOCAB = {'token': 600, 'path': 400, 'target': 150}
CORPUS = {'methods': 300,
          'contexts': {'median': 28, 'sigma': 0.9, 'min': 1, 'max': 200}}
MIX = {'rate_per_s': 200.0,
       'rows': {'median': 4, 'sigma': 1.0, 'min': 1, 'max': 64},
       'tiers': {'topk': 0.6, 'attention': 0.2, 'vectors': 0.2}}


def same(a, b) -> bool:
    return all(np.array_equal(x, y) for x, y in zip(a, b))


def test_schedule_is_a_pure_function_of_params_and_seed():
    a = arrivals.generate(MIX, 7, 10.0, 5000)
    assert same(a, arrivals.generate(dict(MIX), 7, 10.0, 5000))
    assert not same(a[:4], arrivals.generate(MIX, 8, 10.0, 5000)[:4])
    assert a.tiers == ('attention', 'topk', 'vectors')


def test_every_seed_offers_the_same_work():
    a = arrivals.generate(MIX, 1, 50.0, 5000)
    b = arrivals.generate(MIX, 2, 50.0, 5000)
    n = a.due_s.shape[0]
    assert n == b.due_s.shape[0] == 200 * 50
    assert sorted(a.rows) == sorted(b.rows) and a.rows.sum() == b.rows.sum()
    assert np.bincount(a.tier).tolist() == np.bincount(b.tier).tolist() \
        == [2000, 6000, 2000]
    assert not np.array_equal(a.rows, b.rows)
    # the same sizes in each tier, too
    assert sorted(zip(a.rows, a.tier)) == sorted(zip(b.rows, b.tier))
    by_tier = [a.rows[a.tier == k].mean() for k in range(3)]
    assert by_tier == pytest.approx([a.rows.mean()] * 3, rel=0.02)


def test_poisson_instants_sizes_and_lines():
    s = arrivals.generate(MIX, 1, 50.0, 5000)
    assert (np.diff(s.due_s) >= 0).all() and 0 <= s.due_s[0] \
        and s.due_s[-1] < 50.0
    gaps = np.diff(s.due_s)
    assert gaps.std() == pytest.approx(gaps.mean(), rel=0.1)   # exponential
    assert s.rows.min() >= 1 and s.rows.max() == 64
    assert np.median(s.rows) == 4
    # the lognormal's mean, clipped: exp(sigma**2 / 2) x the median
    assert s.rows.mean() == pytest.approx(4 * np.exp(0.5), rel=0.05)
    assert (s.first_line + s.rows <= 5000).all()


def test_corpus_is_a_pure_function_and_chunks_do_not_depend_on_order():
    a = corpus.generate(CORPUS, 11, VOCAB)
    assert same(a, corpus.generate(CORPUS, 11, VOCAB))
    assert not same(a, corpus.generate(CORPUS, 12, VOCAB))
    assert a.count.shape == (300,) and a.count.min() >= 1 \
        and a.count.max() <= 200
    assert a.source.shape == (int(a.count.sum()),)
    assert a.source.max() < 600 and a.path.max() < 400 \
        and a.label.max() < 150
    big = dict(CORPUS, methods=corpus.CHUNK + 10)
    second = corpus.generate_chunk(big, 11, VOCAB, 1)
    assert second.count.shape == (10,)
    whole = corpus.generate(big, 11, VOCAB)
    assert np.array_equal(whole.count[corpus.CHUNK:], second.count)


def test_skew_reaches_the_tail_and_is_log_uniform():
    rng = np.random.default_rng(0)
    draws = corpus.skewed_indices(rng, 400_000, 100_000)
    assert draws.min() == 0 and draws.max() > 90_000
    # log-uniform ranks: each decade of ranks takes the same share
    decades = np.histogram(draws + 1, bins=[1, 10, 100, 1000, 10_000,
                                            100_001])[0] / draws.shape[0]
    assert decades == pytest.approx([0.2] * 5, abs=0.02)


def test_rendered_lines_parse_back_to_the_arrays(tmp_path):
    made = corpus.materialize(CORPUS, 11, VOCAB, str(tmp_path), 'toy')
    again = corpus.materialize(CORPUS, 11, VOCAB, str(tmp_path), 'toy')
    assert not made['reused'] and again['reused']
    assert again['sha256_first_mb'] == made['sha256_first_mb']
    want = corpus.generate(CORPUS, 11, VOCAB)
    lines = corpus.read_lines(made['prefix'], 300)
    assert len(lines) == 300 == made['methods']
    at = 0
    for row, line in enumerate(lines):
        label, *contexts = line.split(' ')
        assert label == corpus.word('target', want.label[row])
        assert len(contexts) == want.count[row]
        for context in contexts:
            s, p, t = context.split(',')
            assert (int(s), int(p), int(t)) == (
                want.source[at], want.path[at], want.target[at])
            assert (s, p) == (corpus.word('token', int(s)),
                              corpus.word('path', int(p)))
            at += 1
    with open(made['prefix'] + '.dict.c2v', 'rb') as f:
        tokens, paths, targets = (pickle.load(f) for _ in range(3))
    # more words than the cap, and counts that fall strictly
    assert len(tokens) == 600 + corpus.SPARE_WORDS
    assert tokens[corpus.word('token', 0)] > tokens[corpus.word('token', 1)]
    assert len(paths) == 400 + corpus.SPARE_WORDS and \
        len(targets) == 150 + corpus.SPARE_WORDS
    assert os.path.islink(made['prefix'] + '.dict.c2v')
    changed = corpus.materialize(dict(CORPUS, methods=200), 11, VOCAB,
                                 str(tmp_path), 'toy')
    assert not changed['reused'] and changed['methods'] == 200
