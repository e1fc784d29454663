"""A seeded code2vec data set at the configuration's vocabulary width.

Copied in method from ``chip_smoke.py`` (``generate_dataset``,
``context_counts``, ``zipf_indices``; verdict in PERF.md): a ``.dict.c2v``
whose three count tables overflow the vocabulary caps, so the defaults give
the full width, and ``.c2v`` lines whose context counts are heavy-tailed and
whose indices are skewed. What differs: it takes the seed, and it writes the
lines with numpy and not with a Python loop per context, because a run that
meets a new seed pays for it in set-up.

Every word has a fixed width, so a line is a row of equal records::

    get|value|name|n012345 0001234,000123,0012345 0000007,000045,0000891\\n
    `------ label -------' `----- context ------' `----- context ------'

The dictionary does not depend on the seed and is written once per
checkout; a data set's directory links to it.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, NamedTuple

import numpy as np

TOKEN_DIGITS = 7    # 1,301,136 + spare words < 10**7
PATH_DIGITS = 6     # 911,417 + spare words < 10**6
TARGET_DIGITS = 6   # 261,245 + spare words < 10**6
LABEL_PREFIX = 'get|value|name|n'
RECORD = TOKEN_DIGITS + 1 + PATH_DIGITS + 1 + TOKEN_DIGITS + 1
assert len(LABEL_PREFIX) + TARGET_DIGITS + 1 == RECORD
#: words in each dictionary beyond the cap, so that the cap decides the width
SPARE_WORDS = 1000
#: methods made and rendered at a time, each chunk from a generator of its own
CHUNK = 16384
#: the part of the lines whose SHA-256 a run prints
FIRST_BYTES = 1 << 20


class Corpus(NamedTuple):
    """The arrays a data set is written from: one entry of ``count`` and
    ``label`` per method, one of ``source``/``path``/``target`` per context.
    Indices are word numbers (0 = most frequent), not vocabulary indices."""
    count: np.ndarray
    source: np.ndarray
    path: np.ndarray
    target: np.ndarray
    label: np.ndarray


def context_counts(rng, n: int, spec: dict) -> np.ndarray:
    """Contexts per method: lognormal, clipped (corpus_stats_r4.json has
    p50 28 of 200)."""
    counts = np.exp(rng.normal(np.log(spec['median']), spec['sigma'], size=n))
    return np.clip(np.rint(counts), spec['min'], spec['max']).astype(np.int64)


def skewed_indices(rng, n: int, vocab: int) -> np.ndarray:
    """Draws over [0, vocab) with log-uniform ranks, chip_smoke's
    ``vocab ** u``: a rank-frequency curve of slope -1. In float32: its 24
    bits still reach nearly every rank of 1.3M words."""
    u = rng.random(n, dtype=np.float32)
    rank = np.exp(u * np.log(np.float32(vocab + 1)))
    return np.clip(rank.astype(np.int32) - 1, 0, vocab - 1)


def generate_chunk(params: dict, seed: int, vocab: Dict[str, int],
                   chunk: int) -> Corpus:
    """Methods [chunk * CHUNK, (chunk + 1) * CHUNK) of the data set: a pure
    function of its arguments, so chunks can be made in any order."""
    rng = np.random.default_rng([int(seed), 0xC0DE, int(chunk)])
    n = min(CHUNK, int(params['methods']) - chunk * CHUNK)
    count = context_counts(rng, n, params['contexts'])
    total = int(count.sum())
    return Corpus(
        count=count,
        source=skewed_indices(rng, total, vocab['token']),
        path=skewed_indices(rng, total, vocab['path']),
        target=skewed_indices(rng, total, vocab['token']),
        label=skewed_indices(rng, n, vocab['target']))


def num_chunks(params: dict) -> int:
    return -(-int(params['methods']) // CHUNK)


def generate(params: dict, seed: int, vocab: Dict[str, int]) -> Corpus:
    """The whole data set as arrays."""
    chunks = [generate_chunk(params, seed, vocab, c)
              for c in range(num_chunks(params))]
    return Corpus(*(np.concatenate(parts) for parts in zip(*chunks)))


def digit_table(n_words: int, width: int) -> np.ndarray:
    """(n_words, width) uint8: row i is i in zero-padded decimal digits."""
    powers = 10 ** np.arange(width - 1, -1, -1)
    digits = np.arange(n_words)[:, None] // powers[None, :] % 10
    return (digits + ord('0')).astype(np.uint8)


def word_tables(vocab: Dict[str, int]) -> Dict[str, np.ndarray]:
    return {'token': digit_table(vocab['token'], TOKEN_DIGITS),
            'path': digit_table(vocab['path'], PATH_DIGITS),
            'target': digit_table(vocab['target'], TARGET_DIGITS)}


def render(corpus: Corpus, tables: Dict[str, np.ndarray]) -> np.ndarray:
    """The ``.c2v`` text as a (records, RECORD) uint8 array: each method
    is one label record followed by its context records."""
    n = corpus.count.shape[0]
    total = int(corpus.count.sum())
    contexts = np.empty((total, RECORD), np.uint8)
    at = 0
    for values, kind, width in ((corpus.source, 'token', TOKEN_DIGITS),
                                (corpus.path, 'path', PATH_DIGITS),
                                (corpus.target, 'token', TOKEN_DIGITS)):
        contexts[:, at:at + width] = tables[kind][values]
        contexts[:, at + width] = ord(',')
        at += width + 1
    contexts[:, -1] = ord(' ')
    contexts[np.cumsum(corpus.count) - 1, -1] = ord('\n')
    labels = np.empty((n, RECORD), np.uint8)
    prefix = np.frombuffer(LABEL_PREFIX.encode(), np.uint8)
    labels[:, :prefix.shape[0]] = prefix
    labels[:, prefix.shape[0]:-1] = tables['target'][corpus.label]
    labels[:, -1] = ord(' ')
    label_at = np.cumsum(corpus.count) - corpus.count + np.arange(n)
    is_context = np.ones(n + total, bool)
    is_context[label_at] = False
    records = np.empty((n + total, RECORD), np.uint8)
    records[is_context] = contexts
    records[label_at] = labels
    return records


def word(kind: str, number: int) -> str:
    """The text of word ``number`` of a dictionary."""
    if kind == 'token':
        return '%0*d' % (TOKEN_DIGITS, number)
    if kind == 'path':
        return '%0*d' % (PATH_DIGITS, number)
    return '%s%0*d' % (LABEL_PREFIX, TARGET_DIGITS, number)


def write_dictionary(path: str, vocab: Dict[str, int]) -> None:
    """``.dict.c2v``: token, path and target counts in the file's order.
    Counts fall strictly, so the top-N-by-count cut is exact and word i
    gets vocabulary index i + 1. The reference's file ends with the number
    of train examples, which this program counts itself: 0 here, since
    data sets of any size share the file."""
    tmp = '%s.%d.tmp' % (path, os.getpid())
    with open(tmp, 'wb') as f:
        for kind in ('token', 'path', 'target'):
            n_words = vocab[kind] + SPARE_WORDS
            pickle.dump({word(kind, i): n_words - i for i in range(n_words)},
                        f)
        pickle.dump(0, f)
    os.replace(tmp, path)


def materialize(params: dict, seed: int, vocab: Dict[str, int],
                data_root: str, name: str) -> dict:
    """Make sure ``<data_root>/<name>-<seed>/corpus.{dict,train}.c2v``
    exist and match ``params``; returns the prefix and what was written.
    A directory whose recorded parameters differ is written anew."""
    directory = os.path.join(data_root, '%s-%d' % (name, seed))
    prefix = os.path.join(directory, 'corpus')
    wanted = {'params': params, 'seed': int(seed), 'vocab': vocab,
              'format': 1}
    meta_path = os.path.join(directory, 'corpus.json')
    if os.path.isfile(meta_path):
        with open(meta_path) as f:
            meta = json.load(f)
        if meta.get('wanted') == wanted and \
                os.path.isfile(prefix + '.train.c2v') and \
                os.path.isfile(prefix + '.dict.c2v'):
            return dict(meta, prefix=prefix, reused=True)
    os.makedirs(directory, exist_ok=True)
    shared = os.path.join(data_root, 'dict-%d-%d-%d.c2v' % (
        vocab['token'], vocab['path'], vocab['target']))
    if not os.path.isfile(shared):
        write_dictionary(shared, vocab)
    if os.path.lexists(prefix + '.dict.c2v'):
        os.remove(prefix + '.dict.c2v')
    os.symlink(os.path.relpath(shared, directory), prefix + '.dict.c2v')
    tables = word_tables(vocab)

    def chunk_records(chunk: int):
        corpus = generate_chunk(params, seed, vocab, chunk)
        return corpus.count, render(corpus, tables)

    digest = hashlib.sha256()
    hashed = methods = contexts = size = 0
    # numpy releases the interpreter lock in the draws, the gathers and the
    # copies, so a few threads make the chunks side by side; written in order
    with ThreadPoolExecutor(max_workers=min(8, os.cpu_count() or 1)) as pool, \
            open(prefix + '.train.c2v', 'wb') as f:
        for count, records in pool.map(chunk_records,
                                       range(num_chunks(params))):
            flat = records.reshape(-1)
            if hashed < FIRST_BYTES:
                digest.update(flat[:FIRST_BYTES - hashed].tobytes())
                hashed += min(FIRST_BYTES - hashed, flat.shape[0])
            f.write(flat.data)
            methods += int(count.shape[0])
            contexts += int(count.sum())
            size += int(flat.shape[0])
    meta = {'wanted': wanted, 'methods': methods, 'contexts': contexts,
            'mean_contexts': contexts / methods, 'bytes': size,
            'sha256_first_mb': digest.hexdigest()}
    with open(meta_path, 'w') as f:
        json.dump(meta, f)
    return dict(meta, prefix=prefix, reused=False)


def read_lines(prefix: str, limit: int) -> list:
    """The first ``limit`` methods of a data set, one string each."""
    lines = []
    with open(prefix + '.train.c2v') as f:
        for line in f:
            lines.append(line.rstrip('\n'))
            if len(lines) == limit:
                break
    return lines
