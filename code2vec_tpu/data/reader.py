"""Host-side input pipeline: ``.c2v`` text → fixed-shape int32/float32 batches.

TPU-first redesign of the reference's in-graph tf.data pipeline
(reference path_context_reader.py:119-228):

- **Strings never touch the device.** Vocabulary lookup happens here, on the
  host, with plain dicts (the reference used in-graph
  ``tf.lookup.StaticHashTable``, vocabularies.py:108-139 — impossible and
  undesirable under XLA).
- **Static shapes.** Every batch is exactly ``(batch_size, max_contexts)``;
  row filtering happens host-side before batching, and a short final batch is
  padded with zero-``weight`` rows instead of shrinking (the reference emitted
  ragged final batches, path_context_reader.py:148).
- **Same row semantics.** A context part that is missing or out-of-vocab maps
  to PAD/OOV exactly as the reference's CSV-default + hashtable-default
  pipeline did (path_context_reader.py:82-83, 184-214), including the joined
  PAD==OOV policy subtlety: a context whose three parts all hash to index 0 is
  masked out.
- **Same filter semantics.** Train rows must have an in-vocab target and at
  least one valid context; eval rows only the latter
  (path_context_reader.py:153-177). Predict rows are never filtered (:100).

A background thread parses and tokenizes ahead of the consumer
(``READER_PREFETCH_BATCHES`` deep), mirroring the reference's
``num_parallel_calls`` + ``prefetch`` (:141-150). When the native C++
tokenizer is available (``code2vec_tpu.data.native``) it replaces the Python
inner loop for every action, predict included: the strings a batch keeps
are each row's label and, for predict, its line (``Batch.context_lines``);
the per-context strings the attention decode shows are made from that line
at decode (``context_triples``).
"""
from __future__ import annotations

import itertools
import queue
import random
import threading
from enum import Enum
from typing import (Iterable, Iterator, List, NamedTuple, Optional, Sequence,
                    Tuple)

import numpy as np

from code2vec_tpu.config import Config
from code2vec_tpu.vocab import Code2VecVocabs


class EstimatorAction(Enum):
    Train = 'train'
    Evaluate = 'evaluate'
    Predict = 'predict'

    @property
    def is_train(self) -> bool:
        return self is EstimatorAction.Train

    @property
    def is_evaluate(self) -> bool:
        return self is EstimatorAction.Evaluate

    @property
    def is_predict(self) -> bool:
        return self is EstimatorAction.Predict

    @property
    def is_evaluate_or_predict(self) -> bool:
        return self.is_evaluate or self.is_predict


def context_valid_mask(source: np.ndarray, path: np.ndarray,
                       target: np.ndarray, token_pad: int,
                       path_pad: int) -> np.ndarray:
    """A context is valid iff any of its three parts is non-PAD
    (reference path_context_reader.py:209-214, including the joined
    PAD==OOV subtlety). Single definition — parity-critical."""
    return ((source != token_pad) | (target != token_pad)
            | (path != path_pad)).astype(np.float32)


def _counted_batches(batches):
    """Pass-through that counts emitted batches into the telemetry
    pipeline counter (one bool read per batch when telemetry is off).
    Also hosts the ``hang_input`` fault point (resilience/faults.py):
    firing blocks this stream — from whichever thread drives it, usually
    the prefetch producer — exactly like a wedged filesystem would, so
    the hang watchdog's input-wait arm is exercised end to end."""
    import time as _time

    from code2vec_tpu.resilience import faults
    from code2vec_tpu.telemetry import core
    for batch in batches:
        if faults.maybe_fire('hang_input'):
            _time.sleep(faults.HANG_SECONDS)
        if core.enabled():
            core.registry().counter('input/batches_total').inc()
        yield batch


def prefetch_iterator(make_iterator, depth: int):
    """Run ``make_iterator()`` in a background thread with a bounded queue
    (the reference's ``prefetch``, path_context_reader.py:150). Safe to
    abandon mid-iteration: closing the generator cancels the producer."""
    out: 'queue.Queue' = queue.Queue(depth)
    sentinel = object()
    cancelled = threading.Event()
    error: List[BaseException] = []

    def produce():
        try:
            for item in make_iterator():
                while not cancelled.is_set():
                    try:
                        out.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                if cancelled.is_set():
                    return
        except BaseException as exc:  # propagate to consumer
            error.append(exc)
        finally:
            # must not drop the sentinel on a full queue, or the consumer
            # blocks forever after draining it
            while not cancelled.is_set():
                try:
                    out.put(sentinel, timeout=0.1)
                    break
                except queue.Full:
                    continue

    thread = threading.Thread(target=produce, daemon=True)
    thread.start()
    try:
        while True:
            item = out.get()
            if item is sentinel:
                break
            yield item
    finally:
        cancelled.set()
        thread.join()
    if error:
        raise error[0]


class Batch(NamedTuple):
    """One device-ready batch. All arrays have static leading dimension
    ``batch_size``; short final batches are padded with ``weight == 0`` rows."""
    source: np.ndarray               # (B, C) int32 — source-token indices
    path: np.ndarray                 # (B, C) int32 — path indices
    target: np.ndarray               # (B, C) int32 — target-token indices
    mask: np.ndarray                 # (B, C) float32 — context validity
    label: np.ndarray                # (B,)  int32 — target-name index
    weight: np.ndarray               # (B,)  float32 — example validity
    # Host-only string fields (eval/predict; device code never sees these).
    label_strings: Optional[np.ndarray] = None     # (B,) object
    # predict: each row's line as it was tokenized. The strings of its
    # contexts are made from it at decode (``context_triples``), for the
    # rows whose tier returns attention, never per slot here
    context_lines: Optional[np.ndarray] = None     # (B,) object

    @property
    def num_valid_examples(self) -> int:
        return int(self.weight.sum())

    def device_arrays(self):
        """The arrays the jitted step functions consume, in a fixed order."""
        return (self.source, self.path, self.target, self.mask,
                self.label, self.weight)


class ParsedRow(NamedTuple):
    label_str: str
    source_strs: List[str]
    path_strs: List[str]
    target_strs: List[str]


def context_triples(line: str) -> Iterator[Tuple[str, str, str]]:
    """The ``(source, path, target)`` strings of a line's context slots,
    in slot order: THE split of a ``label ctx1 ctx2 …`` line, where a
    ctx is ``src,path,tgt`` (single-space separators, matching the
    native tokenizer; a missing part is empty, parts beyond the third
    are dropped, an empty slot is three empty strings).  The fallback
    tokenizer fills its rows from it, and the attention decode pairs it
    with a row's weights, on demand and for the row's real contexts
    only."""
    for ctx in line.rstrip('\r\n').split(' ')[1:]:
        pieces = ctx.split(',', 3)
        yield (pieces[0], pieces[1] if len(pieces) > 1 else '',
               pieces[2] if len(pieces) > 2 else '')


def parse_c2v_line(line: str, max_contexts: int) -> ParsedRow:
    """One line as ``max_contexts`` slots of strings.

    Missing/short/empty contexts are padded with empty strings, which
    tokenize to PAD — the host equivalent of the reference's CSV record
    defaults (path_context_reader.py:82-83, 190-196).
    """
    source_strs = [''] * max_contexts
    path_strs = [''] * max_contexts
    target_strs = [''] * max_contexts
    for i, triple in enumerate(
            itertools.islice(context_triples(line), max_contexts)):
        source_strs[i], path_strs[i], target_strs[i] = triple
    return ParsedRow(line.rstrip('\r\n').split(' ', 1)[0],
                     source_strs, path_strs, target_strs)


def canonicalize_contexts(lines: Iterable[str],
                          max_contexts: Optional[int] = None) -> List[str]:
    """Canonical form of raw ``label ctx1 ctx2 …`` predict lines — THE
    definition of request identity (SERVING.md "Memoization tier").
    Every prediction surface funnels through it: ``process_input_rows``
    applies it (so ``model.predict``, ``serving/bulk.py``, and both
    submit paths tokenize identical canonical input), and
    ``ServingEngine.submit`` / ``ServingMesh.submit`` call it up front
    so the memoization key (``serving/memo.py``) and the tokenizer can
    never disagree on what "the same request" is.

    Tokenize-faithful by construction: each line is split exactly as
    ``parse_c2v_line`` splits it (single-space separators — an empty
    slot from a doubled space still OCCUPIES a context slot), then
    truncated to ``max_contexts`` in ORIGINAL extraction order, and
    only then are the surviving empty slots dropped and the survivors
    sorted lexicographically — a canonical MULTISET of the exact
    path-contexts the tokenizer would keep.  Truncating before the
    sort is load-bearing: sorting first would let a different context
    subset survive ``MAX_CONTEXTS`` than the evaluate-path reader
    (which never canonicalizes) keeps, silently changing predictions.
    For the same reason every serving entry point passes its
    ``config.MAX_CONTEXTS`` here — the FIRST canonicalization must be
    the one that truncates.  Dropping empty slots after truncation is
    tokenize-invariant (they map to PAD and are masked), and sorting
    makes every path reduce the attention sum in the same float
    order.  Duplicate ``src,path,tgt`` triples are KEPT: a repeated
    context contributes its attention weight twice in the reference
    model, so the duplicate count is part of request identity.  Line
    order across the request is preserved: results are per-line,
    positional.
    Idempotent at fixed ``max_contexts``:
    ``canonicalize_contexts(canonicalize_contexts(x, m), m)`` equals
    ``canonicalize_contexts(x, m)`` (a canonical line has no empty
    slots and at most ``m`` contexts, so the re-truncation is a
    no-op).
    """
    out = []
    for line in lines:
        parts = str(line).rstrip('\r\n').split(' ')  # parse_c2v_line split
        contexts = parts[1:]
        if max_contexts is not None:
            # extraction-order truncation, empty slots counted — the
            # slots parse_c2v_line would fill (and mask) for this line
            contexts = contexts[:max_contexts]
        out.append(' '.join([parts[0]] + sorted(c for c in contexts if c)))
    return out


class PathContextReader:
    def __init__(self, vocabs: Code2VecVocabs, config: Config,
                 estimator_action: EstimatorAction,
                 data_path: Optional[str] = None,
                 keep_strings: Optional[bool] = None,
                 process_index: int = 0, process_count: int = 1,
                 data_shards: int = 1):
        self.vocabs = vocabs
        self.config = config
        self.estimator_action = estimator_action
        self.data_path = data_path if data_path is not None else \
            config.data_path(is_evaluating=estimator_action.is_evaluate)
        # multi-host: each process reads a disjoint line stride and emits
        # its 1/process_count share of the GLOBAL batch
        self.process_index = process_index
        self.process_count = max(1, process_count)
        # mesh data-axis size: packed-wire batches are packed PER data
        # shard so each device's slice transfers directly to it
        # (data/packed.py; parallel/mesh.py shard_batch)
        self.data_shards = max(1, data_shards)
        # sticky packed-capacity state (packed.StickyPacker), created on
        # first packed emission and kept across epochs
        self._packer = None
        # Eval keeps only the label strings (host-side metric decode);
        # predict additionally keeps each row's line, from which the
        # attention decode makes the per-context strings (reference kept
        # string tensors in the graph, path_context_reader.py:225-227).
        # Neither needs the per-context Python loop, so the native
        # tokenizer covers every action (index arrays in C++, one split
        # a line for the label in Python).
        if keep_strings is None:
            self.keep_context_strings = estimator_action.is_predict
            self.keep_label_strings = estimator_action.is_evaluate_or_predict
        else:
            self.keep_context_strings = keep_strings
            self.keep_label_strings = keep_strings
        self._native = None
        if config.READER_USE_NATIVE:
            try:
                from code2vec_tpu.data import native
                if native.is_available():
                    self._native = native.get_tokenizer(vocabs, config)
            except (ImportError, RuntimeError):
                self._native = None

    # ------------------------------------------------------------ tokenize
    def tokenize_rows(self, rows: Sequence[ParsedRow]) -> Batch:
        """Vocab-lookup a list of parsed rows into the index arrays of
        one dense batch of exactly ``len(rows)`` examples (callers pad
        to batch size): the fallback of a host without the native
        library."""
        n = len(rows)
        max_contexts = self.config.MAX_CONTEXTS
        token_get = self.vocabs.token_vocab.word_to_index.get
        path_get = self.vocabs.path_vocab.word_to_index.get
        target_get = self.vocabs.target_vocab.word_to_index.get
        token_oov = self.vocabs.token_vocab.oov_index
        token_pad = self.vocabs.token_vocab.pad_index
        path_oov = self.vocabs.path_vocab.oov_index
        path_pad = self.vocabs.path_vocab.pad_index
        target_oov = self.vocabs.target_vocab.oov_index
        # Empty strings must map to PAD, not OOV: the reference's CSV default
        # substitutes the PAD word *before* the hashtable lookup.
        source = np.empty((n, max_contexts), dtype=np.int32)
        path = np.empty((n, max_contexts), dtype=np.int32)
        target = np.empty((n, max_contexts), dtype=np.int32)
        label = np.empty((n,), dtype=np.int32)
        for r, row in enumerate(rows):
            label[r] = target_get(row.label_str, target_oov)
            src_row, path_row, tgt_row = source[r], path[r], target[r]
            for c in range(max_contexts):
                s = row.source_strs[c]
                src_row[c] = token_get(s, token_oov) if s else token_pad
                p = row.path_strs[c]
                path_row[c] = path_get(p, path_oov) if p else path_pad
                t = row.target_strs[c]
                tgt_row[c] = token_get(t, token_oov) if t else token_pad
        mask = self._context_valid_mask(source, path, target)
        weight = np.ones((n,), dtype=np.float32)
        return Batch(source=source, path=path, target=target, mask=mask,
                     label=label, weight=weight)

    def _context_valid_mask(self, source: np.ndarray, path: np.ndarray,
                            target: np.ndarray) -> np.ndarray:
        return context_valid_mask(source, path, target,
                                  self.vocabs.token_vocab.pad_index,
                                  self.vocabs.path_vocab.pad_index)

    # ------------------------------------------------------------- batching
    def _lines_from_file(self) -> Iterator[str]:
        with open(self.data_path, 'r', buffering=self.config.CSV_BUFFER_SIZE) as f:
            for line_number, line in enumerate(f):
                if self.process_count > 1 and \
                        line_number % self.process_count != self.process_index:
                    continue
                if line.strip():
                    yield line

    def _shuffled(self, lines: Iterable[str], rng: random.Random) -> Iterator[str]:
        """Streaming shuffle buffer (reference used
        ``dataset.shuffle(SHUFFLE_BUFFER_SIZE)``, path_context_reader.py:139)."""
        buffer: List[str] = []
        size = self.config.SHUFFLE_BUFFER_SIZE
        for line in lines:
            if len(buffer) < size:
                buffer.append(line)
                continue
            idx = rng.randrange(size)
            yield buffer[idx]
            buffer[idx] = line
        rng.shuffle(buffer)
        yield from buffer

    def tokenize_lines(self, lines: Sequence[str]) -> Batch:
        """Parse + tokenize a chunk of raw lines into one dense batch.

        This is the hot host loop; the native C++ tokenizer substitutes for
        it when available, for every action: the strings a batch keeps
        are one split a line (the label) and the line itself, never the
        per-context loop."""
        if self._native is not None:
            batch = self._native.tokenize_lines(lines)
        else:
            batch = self.tokenize_rows(
                [parse_c2v_line(line, self.config.MAX_CONTEXTS)
                 for line in lines])
        if self.keep_label_strings:
            batch = batch._replace(label_strings=np.array(
                [line.rstrip('\r\n').split(' ', 1)[0] for line in lines],
                dtype=object))
        if self.keep_context_strings:
            batch = batch._replace(
                context_lines=np.array(lines, dtype=object))
        return batch

    def _keep_mask(self, batch: Batch) -> np.ndarray:
        """Vectorized row filter (reference path_context_reader.py:153-177):
        train keeps rows with an in-vocab target AND ≥1 valid context; eval
        keeps rows with ≥1 valid context."""
        any_valid = batch.mask.any(axis=1)
        if self.estimator_action.is_train:
            return any_valid & (batch.label > self.vocabs.target_vocab.oov_index)
        return any_valid

    @staticmethod
    def _take_rows(batch: Batch, keep: np.ndarray) -> Batch:
        return Batch(*[None if field is None else field[keep]
                       for field in batch])

    @staticmethod
    def _concat(parts: List[Batch]) -> Batch:
        if len(parts) == 1:
            return parts[0]
        return Batch(*[None if parts[0][i] is None
                       else np.concatenate([p[i] for p in parts])
                       for i in range(len(parts[0]))])

    def _filtered_batches(self, lines: Iterable[str],
                          batch_size: int) -> Iterator[Batch]:
        """Parse, tokenize, filter, and emit fixed-shape batches."""
        pending: List[Batch] = []
        pending_rows = 0
        chunk: List[str] = []
        chunk_size = max(batch_size, 256)

        def flush_chunk():
            nonlocal pending, pending_rows
            batch = self.tokenize_lines(chunk)
            kept = self._take_rows(batch, self._keep_mask(batch))
            if kept.label.shape[0]:
                pending.append(kept)
                pending_rows += kept.label.shape[0]
            while pending_rows >= batch_size:
                merged = self._concat(pending)
                # slice, not fancy-index: views, no copies in the hot loop
                yield self._take_rows(merged, slice(None, batch_size))
                rest = self._take_rows(merged, slice(batch_size, None))
                pending = [rest] if rest.label.shape[0] else []
                pending_rows = merged.label.shape[0] - batch_size

        for line in lines:
            chunk.append(line)
            if len(chunk) >= chunk_size:
                yield from flush_chunk()
                chunk = []
        if chunk:
            yield from flush_chunk()
        if pending_rows:
            yield self._pad_batch(self._concat(pending), batch_size)

    def empty_batch(self, batch_size: int) -> Batch:
        """All-padding batch (every row weight 0): multi-host evaluation
        emits these so every process runs the same number of jitted steps
        even when data shards are uneven — the padded rows drop out of the
        metrics and the loss.  Delegates to ``_pad_batch`` so the pad-row
        fill policy has a single definition."""
        contexts = self.config.MAX_CONTEXTS
        zero_rows = Batch(
            source=np.zeros((0, contexts), np.int32),
            path=np.zeros((0, contexts), np.int32),
            target=np.zeros((0, contexts), np.int32),
            mask=np.zeros((0, contexts), np.float32),
            label=np.zeros((0,), np.int32),
            weight=np.zeros((0,), np.float32))
        if self.keep_label_strings:
            zero_rows = zero_rows._replace(
                label_strings=np.zeros((0,), dtype=object))
        if self.keep_context_strings:
            zero_rows = zero_rows._replace(
                context_lines=np.zeros((0,), dtype=object))
        return self._pad_batch(zero_rows, batch_size)

    def pad_batch_to(self, batch: Batch, batch_size: int) -> Batch:
        """Pad a batch up to ``batch_size`` rows with zero-weight rows
        (replaces the reference's ragged final batch; also used to make
        predict batches divisible by the mesh data axis)."""
        return self._pad_batch(batch, batch_size)

    def _pad_batch(self, batch: Batch, batch_size: int) -> Batch:
        n = batch.label.shape[0]
        if n == batch_size:
            return batch
        pad = batch_size - n

        def pad2(arr, fill):
            return np.concatenate(
                [arr, np.full((pad,) + arr.shape[1:], fill, dtype=arr.dtype)])

        padded = Batch(
            source=pad2(batch.source, self.vocabs.token_vocab.pad_index),
            path=pad2(batch.path, self.vocabs.path_vocab.pad_index),
            target=pad2(batch.target, self.vocabs.token_vocab.pad_index),
            mask=pad2(batch.mask, 0.0),
            label=pad2(batch.label, 0),
            weight=np.concatenate([batch.weight,
                                   np.zeros((pad,), dtype=np.float32)]))
        empty = np.full((pad,), '', dtype=object)
        if batch.label_strings is not None:
            padded = padded._replace(label_strings=np.concatenate(
                [batch.label_strings, empty]))
        if batch.context_lines is not None:
            padded = padded._replace(context_lines=np.concatenate(
                [batch.context_lines, empty]))
        return padded

    # ----------------------------------------------------------- public API
    def wire_format(self) -> str:
        """The wire format this reader emits from ``iter_epoch`` (the
        multi-host fallback lives in Config.wire_format_for)."""
        return self.config.wire_format_for(self.process_count)

    def iter_epoch(self, shuffle: Optional[bool] = None,
                   seed: Optional[int] = None,
                   wire_format: Optional[str] = None) -> Iterator[Batch]:
        """One pass over the data file as fixed-shape batches.

        The trainer drives epochs explicitly (the reference baked
        ``repeat(NUM_TRAIN_EPOCHS)`` into the dataset and trained until
        ``OutOfRangeError``, tensorflow_model.py:74-102 — with JAX's explicit
        stepping we keep the loop in charge).

        ``wire_format`` selects the emitted batch type: 'planes' (the
        default, and what every introspection/test contract reads) or
        'packed' (``data/packed.py::PackedBatch`` — the compact wire
        format whose device-side unpack reproduces the plane batches
        bit-exactly). Training/eval pass ``self.wire_format()`` so the
        config default governs the product path.
        """
        if shuffle is None:
            shuffle = self.estimator_action.is_train
        lines: Iterable[str] = self._lines_from_file()
        if shuffle:
            lines = self._shuffled(lines, random.Random(seed))
        # per-process LOCAL batch: process-local shards assemble into the
        # global batch on device (parallel/mesh.py shard_batch)
        global_batch = self.config.batch_size(
            is_evaluating=self.estimator_action.is_evaluate)
        if global_batch % self.process_count:
            raise ValueError(
                'batch size %d must be divisible by the process count (%d) '
                'so process-local shards assemble into the global batch.'
                % (global_batch, self.process_count))
        batch_size = global_batch // self.process_count
        batches = self._filtered_batches(lines, batch_size)
        if wire_format == 'packed':
            from code2vec_tpu.data import packed as packed_lib
            if self._packer is None:
                # a training stream names the rows it touches
                # (data/packed.py)
                self._packer = packed_lib.StickyPacker(
                    self.vocabs.token_vocab.pad_index,
                    self.vocabs.path_vocab.pad_index,
                    data_shards=self.data_shards,
                    table_rows=(packed_lib.embedding_table_rows(
                        self.vocabs, self.config.PARAM_ROW_ALIGNMENT)
                        if self.estimator_action.is_train else None))
            batches = (self._packer.pack_batch(batch) for batch in batches)
        yield from _counted_batches(batches)

    def iter_epoch_prefetched(self, shuffle: Optional[bool] = None,
                              seed: Optional[int] = None,
                              wire_format: Optional[str] = None
                              ) -> Iterator[Batch]:
        """``iter_epoch`` behind a background prefetch thread."""
        yield from prefetch_iterator(
            lambda: self.iter_epoch(shuffle=shuffle, seed=seed,
                                    wire_format=wire_format),
            self.config.READER_PREFETCH_BATCHES)

    def process_input_rows(self, input_lines: Iterable[str]) -> Batch:
        """Tokenize raw extractor output lines for prediction — never
        filtered (reference path_context_reader.py:96-107).  Lines are
        canonicalized first (``canonicalize_contexts``), so every
        predict surface — direct, bulk, engine, mesh — tokenizes the
        SAME canonical context bag and the memo key (serving/memo.py)
        addresses exactly what was computed.  The canonical lines go to
        the native tokenizer where the reader has one, and ride with the
        batch (``context_lines``) for the attention decode."""
        return self.tokenize_lines(
            canonicalize_contexts(input_lines, self.config.MAX_CONTEXTS))

    @property
    def native(self) -> bool:
        """Whether this reader tokenizes in the native library (else in
        the Python fallback)."""
        return self._native is not None
