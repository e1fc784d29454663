"""Binary token cache: tokenize the training split once, stream int32
tensors from disk for every later epoch.

The reference re-ran its CSV parse + hashtable lookups for all 20 epochs
(tf.data re-executes the pipeline per repeat, path_context_reader.py:119-151).
Here the first epoch's host tokenization is persisted as raw little-endian
arrays next to the dataset; subsequent epochs are sequential disk reads with
chunk-level shuffling (permute chunk order, permute rows within a chunk) —
both faster and a better shuffle than a 10K-row reservoir.

Format v2 (current) stores the PACKED wire layout (data/packed.py): each
example's contexts densified to its effective length, so the cache on
disk shrinks with the corpus fill rate exactly like the wire does (~12
bytes per retained context + 8 per example, vs v1's 12 bytes for every
one of the C slots). Layout of ``<data>.train.c2v.tokcache/``:

  ctx.bin    int32 (num_contexts, 3) — (source, path, target) triples
  count.bin  int32 (N,) — per-example effective lengths
  label.bin  int32 (N,)
  meta.json  version, row/context counts, max_contexts, vocab fingerprint

Format v1 (``source.bin``/``path.bin``/``target.bin`` padded planes) is
still READ transparently — a fresh v1 cache is used as-is, never
rebuilt; delete the directory to re-materialize it as v2 (MIGRATION.md).
``iter_epoch`` emits either wire format from either on-disk version.

The mask is never stored — recomputed from indices (valid iff any part
!= PAD). Only the train split is cached (eval/predict keep strings for
host-side metrics).
"""
from __future__ import annotations

import contextlib
import fcntl
import json
import os
from typing import Iterator, Optional

import numpy as np

from code2vec_tpu.config import Config
from code2vec_tpu.data import packed as packed_lib
from code2vec_tpu.data.reader import (Batch, PathContextReader,
                                      context_valid_mask)
from code2vec_tpu.vocab import Code2VecVocabs

CACHE_FORMAT_VERSION = 2


@contextlib.contextmanager
def _build_lock(lock_path: str):
    """flock-based inter-process exclusion for cache builds: concurrent
    trainers sharing a dataset directory must not race the
    check → build → publish sequence."""
    with open(lock_path, 'w') as lock_file:
        fcntl.flock(lock_file, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(lock_file, fcntl.LOCK_UN)

_FILES_V2 = ('ctx.bin', 'count.bin', 'label.bin')


def _fingerprint(config: Config, vocabs: Code2VecVocabs,
                 data_path: str) -> dict:
    stat = os.stat(data_path)
    # vocab content hash, not just sizes: sizes are commonly pinned at the
    # MAX_*_VOCAB_SIZE caps, so loading a different model's dictionaries
    # over the same data file keeps every size equal while silently
    # remapping word→index — a stale cache would then feed wrong indices.
    return {
        'data_size': stat.st_size,
        'data_mtime': stat.st_mtime,
        'max_contexts': config.MAX_CONTEXTS,
        'token_vocab': vocabs.token_vocab.size,
        'path_vocab': vocabs.path_vocab.size,
        'target_vocab': vocabs.target_vocab.size,
        'vocab_content_hash': vocabs.content_hash(),
    }


class TokenCache:
    def __init__(self, cache_dir: str, config: Config,
                 vocabs: Code2VecVocabs):
        self.cache_dir = cache_dir
        self.config = config
        self.vocabs = vocabs
        meta_path = os.path.join(cache_dir, 'meta.json')
        with open(meta_path, 'r') as f:
            self.meta = json.load(f)
        self.num_rows = self.meta['num_rows']
        # pre-v2 metas carry no version key — that IS the v1 marker
        self.version = int(self.meta.get('version', 1))
        max_contexts = self.meta['max_contexts']
        if self.version >= 2:
            self.num_contexts = self.meta['num_contexts']
            # size validation BEFORE mapping (ISSUE 3 satellite): a
            # truncated shard (disk-full or killed build) would otherwise
            # surface as an opaque mmap error — or worse, feed mis-aligned
            # epochs if the meta undercounts
            self._check_shard_size('ctx.bin', self.num_contexts * 3 * 4)
            self._check_shard_size('count.bin', self.num_rows * 4)
            self.ctx = np.memmap(os.path.join(cache_dir, 'ctx.bin'),
                                 dtype=np.int32, mode='r',
                                 shape=(self.num_contexts, 3))
            self.count = np.memmap(os.path.join(cache_dir, 'count.bin'),
                                   dtype=np.int32, mode='r',
                                   shape=(self.num_rows,))
            # and the counts must RECONCILE with the context shard: the
            # per-example lengths are the offsets every epoch iteration
            # slices ctx.bin by — a mismatch mis-aligns every batch
            total = int(np.asarray(self.count).sum(dtype=np.int64))
            if total != self.num_contexts:
                raise ValueError(
                    'Token cache at `%s` is corrupt: count.bin totals %d '
                    'contexts but meta.json/ctx.bin hold %d — delete the '
                    'cache directory to rebuild it.'
                    % (cache_dir, total, self.num_contexts))
        else:
            shape2 = (self.num_rows, max_contexts)
            plane_bytes = self.num_rows * max_contexts * 4
            for name in ('source.bin', 'path.bin', 'target.bin'):
                self._check_shard_size(name, plane_bytes)
            self.source = np.memmap(os.path.join(cache_dir, 'source.bin'),
                                    dtype=np.int32, mode='r', shape=shape2)
            self.path = np.memmap(os.path.join(cache_dir, 'path.bin'),
                                  dtype=np.int32, mode='r', shape=shape2)
            self.target = np.memmap(os.path.join(cache_dir, 'target.bin'),
                                    dtype=np.int32, mode='r', shape=shape2)
        self._check_shard_size('label.bin', self.num_rows * 4)
        self.label = np.memmap(os.path.join(cache_dir, 'label.bin'),
                               dtype=np.int32, mode='r',
                               shape=(self.num_rows,))
        # sticky packed-capacity state (packed.StickyPacker): grows
        # monotonically across batches AND epochs so the jitted packed
        # step specializes a handful of times per run, not per batch
        self._packer = None

    def _check_shard_size(self, name: str, expected_bytes: int) -> None:
        """A shard whose on-disk size disagrees with meta.json means a
        truncated or torn cache build: fail with instructions, never
        serve mis-aligned epochs."""
        path = os.path.join(self.cache_dir, name)
        actual = os.path.getsize(path) if os.path.isfile(path) else -1
        if actual != expected_bytes:
            raise ValueError(
                'Token cache at `%s` is truncated or corrupt: %s is %d '
                'bytes but meta.json implies %d (disk-full or killed '
                'build?) — delete the cache directory to rebuild it.'
                % (self.cache_dir, name, actual, expected_bytes))

    def _packer_for(self, data_shards: int) -> packed_lib.StickyPacker:
        if self._packer is None or self._packer.data_shards != data_shards:
            # the cache holds TRAINING data: its packed batches name the
            # rows they touch
            self._packer = packed_lib.StickyPacker(
                self.vocabs.token_vocab.pad_index,
                self.vocabs.path_vocab.pad_index, data_shards=data_shards,
                table_rows=packed_lib.embedding_table_rows(
                    self.vocabs, self.config.PARAM_ROW_ALIGNMENT))
        return self._packer

    # ------------------------------------------------------------ building
    @classmethod
    def build_or_load(cls, config: Config, vocabs: Code2VecVocabs,
                      reader: PathContextReader,
                      data_path: Optional[str] = None) -> 'TokenCache':
        """Multi-host: the reader strides the data file per process, so each
        process builds/loads a cache of ITS OWN stride in a per-process
        directory (``.tokcache.p<i>of<n>``) — processes sharing storage
        never collide, and every epoch after the first is sequential disk
        reads instead of a full re-tokenization per process."""
        data_path = data_path or config.train_data_path
        suffix = ('.tokcache' if reader.process_count <= 1 else
                  '.tokcache.p%dof%d' % (reader.process_index,
                                         reader.process_count))
        cache_dir = data_path + suffix
        expected = _fingerprint(config, vocabs, data_path)
        if reader.process_count > 1:
            # single-process caches skip these keys so pre-existing caches
            # stay fresh; the stride is also encoded in the directory name
            expected['process_index'] = reader.process_index
            expected['process_count'] = reader.process_count
        meta_path = os.path.join(cache_dir, 'meta.json')

        def is_fresh() -> bool:
            # the format version is deliberately NOT part of the
            # freshness check: a fresh v1 cache keeps serving (read
            # compatibility), it is only ever REPLACED when the data or
            # vocab fingerprint changes
            if not os.path.isfile(meta_path):
                return False
            with open(meta_path, 'r') as f:
                meta = json.load(f)
            return all(meta.get(k) == v for k, v in expected.items())

        from code2vec_tpu.telemetry import core as tele_core
        if is_fresh():
            if tele_core.enabled():
                tele_core.registry().counter('input/cache_hit_total').inc()
            return cls(cache_dir, config, vocabs)
        with _build_lock(cache_dir + '.lock'):
            # another process may have built it while we waited
            if not is_fresh():
                if tele_core.enabled():
                    tele_core.registry().counter(
                        'input/cache_miss_total').inc()
                cls._build(config, reader, cache_dir, expected)
            elif tele_core.enabled():
                # a concurrent trainer built it while we held the lock
                tele_core.registry().counter('input/cache_hit_total').inc()
            return cls(cache_dir, config, vocabs)

    @classmethod
    def _build(cls, config: Config, reader: PathContextReader,
               cache_dir: str, fingerprint: dict) -> None:
        tmp_dir = cache_dir + '.building.%d' % os.getpid()
        os.makedirs(tmp_dir, exist_ok=True)
        config.log('Building token cache at `%s` (format v%d) ...'
                   % (cache_dir, CACHE_FORMAT_VERSION))
        num_rows = 0
        num_contexts = 0
        handles = {name: open(os.path.join(tmp_dir, name), 'wb')
                   for name in _FILES_V2}
        try:
            # one filtered, UNSHUFFLED pass; batches here are fixed-shape
            # with a zero-weight padded tail we must drop
            for batch in reader.iter_epoch(shuffle=False,
                                           wire_format='planes'):
                valid = batch.weight > 0
                triples, lengths = packed_lib.ragged_from_planes(
                    np.ascontiguousarray(batch.source[valid]),
                    np.ascontiguousarray(batch.path[valid]),
                    np.ascontiguousarray(batch.target[valid]),
                    batch.mask[valid])
                handles['ctx.bin'].write(
                    np.ascontiguousarray(triples).tobytes())
                handles['count.bin'].write(lengths.tobytes())
                handles['label.bin'].write(
                    np.ascontiguousarray(batch.label[valid]).tobytes())
                num_rows += int(valid.sum())
                num_contexts += int(lengths.sum())
        finally:
            for handle in handles.values():
                handle.close()
        if num_rows == 0:
            import shutil
            shutil.rmtree(tmp_dir, ignore_errors=True)
            raise ValueError(
                'No training examples survived filtering in `%s` — every '
                'row has an out-of-vocab target or no valid contexts.'
                % reader.data_path)
        meta = dict(fingerprint)
        meta['num_rows'] = num_rows
        meta['num_contexts'] = num_contexts
        meta['version'] = CACHE_FORMAT_VERSION
        with open(os.path.join(tmp_dir, 'meta.json'), 'w') as f:
            json.dump(meta, f)
        # atomic publish
        if os.path.isdir(cache_dir):
            import shutil
            shutil.rmtree(cache_dir)
        os.replace(tmp_dir, cache_dir)
        config.log('Token cache built: %d rows, %d contexts (%.1f avg).'
                   % (num_rows, num_contexts, num_contexts / num_rows))

    # ----------------------------------------------------------- iteration
    def iter_epoch(self, batch_size: int, shuffle: bool = True,
                   seed: Optional[int] = None,
                   chunk_rows: int = 1 << 16,
                   wire_format: Optional[str] = None,
                   data_shards: int = 1) -> Iterator[Batch]:
        """Fixed-shape batches from the cache. Shuffle = permuted chunk
        order + in-chunk row permutation (sequential disk reads).

        ``wire_format`` ('planes' default / 'packed') selects the emitted
        batch type independently of the ON-DISK version — a v1 cache can
        feed the packed wire and vice versa."""
        from code2vec_tpu.data.reader import _counted_batches
        wire_format = wire_format or 'planes'
        if self.version >= 2:
            yield from _counted_batches(
                self._iter_epoch_v2(batch_size, shuffle, seed, chunk_rows,
                                    wire_format, data_shards))
            return
        batches = self._iter_epoch_v1(batch_size, shuffle, seed, chunk_rows)
        if wire_format == 'packed':
            packer = self._packer_for(data_shards)
            batches = (packer.pack_batch(batch) for batch in batches)
        yield from _counted_batches(batches)

    # ------------------------------------------------------------ v2 path
    def _emit_v2(self, ctx_rows: np.ndarray, count: np.ndarray,
                 label: np.ndarray, weight: Optional[np.ndarray],
                 wire_format: str, data_shards: int):
        token_pad = self.vocabs.token_vocab.pad_index
        path_pad = self.vocabs.path_vocab.pad_index
        if weight is None:
            weight = np.ones((count.shape[0],), np.float32)
        if wire_format == 'packed':
            return self._packer_for(data_shards).pack_ragged(
                ctx_rows, count, label, weight)
        source, path, target = packed_lib.unpack_ragged_np(
            ctx_rows, count, self.meta['max_contexts'], token_pad, path_pad)
        mask = context_valid_mask(source, path, target, token_pad, path_pad)
        return Batch(source=source, path=path, target=target, mask=mask,
                     label=label, weight=weight)

    def _iter_epoch_v2(self, batch_size: int, shuffle: bool,
                       seed: Optional[int], chunk_rows: int,
                       wire_format: str, data_shards: int):
        rng = np.random.default_rng(seed)
        num_chunks = max(1, -(-self.num_rows // chunk_rows))
        # context-row offset of each chunk boundary: one cheap pass over
        # the count memmap instead of materializing all N example offsets
        chunk_ctx_bounds = np.zeros(num_chunks + 1, np.int64)
        for i in range(num_chunks):
            begin = i * chunk_rows
            end = min(self.num_rows, begin + chunk_rows)
            chunk_ctx_bounds[i + 1] = chunk_ctx_bounds[i] + \
                np.asarray(self.count[begin:end]).sum(dtype=np.int64)
        chunk_order = np.arange(num_chunks)
        if shuffle:
            rng.shuffle(chunk_order)

        pend_ctx = np.zeros((0, 3), np.int32)
        pend_count = np.zeros((0,), np.int32)
        pend_label = np.zeros((0,), np.int32)

        for chunk_idx in chunk_order:
            begin = int(chunk_idx) * chunk_rows
            end = min(self.num_rows, begin + chunk_rows)
            count = np.asarray(self.count[begin:end])
            label = np.asarray(self.label[begin:end])
            ctx_rows = np.asarray(
                self.ctx[chunk_ctx_bounds[chunk_idx]:
                         chunk_ctx_bounds[chunk_idx + 1]])
            if shuffle:
                perm = rng.permutation(end - begin)
                starts = np.cumsum(count) - count
                sel = np.repeat(starts[perm], count[perm]) + \
                    (np.arange(count[perm].sum(), dtype=np.int64)
                     - np.repeat(np.cumsum(count[perm]) - count[perm],
                                 count[perm]))
                ctx_rows = ctx_rows[sel]
                count, label = count[perm], label[perm]
            if pend_count.shape[0]:
                ctx_rows = np.concatenate([pend_ctx, ctx_rows])
                count = np.concatenate([pend_count, count])
                label = np.concatenate([pend_label, label])
            bounds = np.concatenate([[0], np.cumsum(count, dtype=np.int64)])
            n_full = (count.shape[0] // batch_size) * batch_size
            for start in range(0, n_full, batch_size):
                stop = start + batch_size
                yield self._emit_v2(
                    ctx_rows[bounds[start]:bounds[stop]],
                    count[start:stop], label[start:stop], None,
                    wire_format, data_shards)
            pend_ctx = ctx_rows[bounds[n_full]:]
            pend_count = count[n_full:]
            pend_label = label[n_full:]

        if pend_count.shape[0]:
            pad = batch_size - pend_count.shape[0]
            yield self._emit_v2(
                pend_ctx,
                np.concatenate([pend_count, np.zeros((pad,), np.int32)]),
                np.concatenate([pend_label, np.zeros((pad,), np.int32)]),
                np.concatenate([np.ones((pend_count.shape[0],), np.float32),
                                np.zeros((pad,), np.float32)]),
                wire_format, data_shards)

    # ------------------------------------------------------------ v1 path
    def _iter_epoch_v1(self, batch_size: int, shuffle: bool,
                       seed: Optional[int],
                       chunk_rows: int) -> Iterator[Batch]:
        rng = np.random.default_rng(seed)
        token_pad = self.vocabs.token_vocab.pad_index
        path_pad = self.vocabs.path_vocab.pad_index
        num_chunks = max(1, -(-self.num_rows // chunk_rows))
        chunk_order = np.arange(num_chunks)
        if shuffle:
            rng.shuffle(chunk_order)

        pending = []  # leftover rows smaller than batch_size, as arrays
        pending_rows = 0

        def emit(source, path, target, label,
                 weight: Optional[np.ndarray] = None) -> Batch:
            mask = context_valid_mask(source, path, target, token_pad,
                                      path_pad)
            if weight is None:
                weight = np.ones((source.shape[0],), np.float32)
            return Batch(source=source, path=path, target=target, mask=mask,
                         label=label, weight=weight)

        for chunk_idx in chunk_order:
            begin = int(chunk_idx) * chunk_rows
            end = min(self.num_rows, begin + chunk_rows)
            source = np.asarray(self.source[begin:end])
            path = np.asarray(self.path[begin:end])
            target = np.asarray(self.target[begin:end])
            label = np.asarray(self.label[begin:end])
            if shuffle:
                perm = rng.permutation(end - begin)
                source, path, target, label = (source[perm], path[perm],
                                               target[perm], label[perm])
            if pending:
                source = np.concatenate([pending[0], source])
                path = np.concatenate([pending[1], path])
                target = np.concatenate([pending[2], target])
                label = np.concatenate([pending[3], label])
                pending = []
            n_full = (source.shape[0] // batch_size) * batch_size
            for start in range(0, n_full, batch_size):
                stop = start + batch_size
                yield emit(source[start:stop], path[start:stop],
                           target[start:stop], label[start:stop])
            if n_full < source.shape[0]:
                pending = [source[n_full:], path[n_full:], target[n_full:],
                           label[n_full:]]
                pending_rows = source.shape[0] - n_full

        if pending and pending_rows:
            pad = batch_size - pending_rows
            yield emit(
                np.concatenate([pending[0], np.full(
                    (pad, pending[0].shape[1]), token_pad, np.int32)]),
                np.concatenate([pending[1], np.full(
                    (pad, pending[1].shape[1]), path_pad, np.int32)]),
                np.concatenate([pending[2], np.full(
                    (pad, pending[2].shape[1]), token_pad, np.int32)]),
                np.concatenate([pending[3], np.zeros((pad,), np.int32)]),
                weight=np.concatenate([
                    np.ones((pending_rows,), np.float32),
                    np.zeros((pad,), np.float32)]))
