"""Compact wire format for path-context batches ("packed", format v2).

The plane format ships six padded arrays per batch — source/path/target
``(B, C)`` int32, mask ``(B, C)`` float32, label/weight ``(B,)`` — 16
bytes for every context SLOT whether or not it holds a context. At the
java14m corpus shape most of the 200 slots per example are padding
(contexts/method p50 is 28, benchmarks/results/corpus_stats_r4.json), so
the wire is mostly zeros.

The packed format densifies each example's leading ``length`` context
slots — ``length`` = index of the LAST valid context + 1 — into a
contiguous stream of ``(source, path, target)`` int32 triples:

  ctx     (data_shards, capacity, 3) int32 — per-shard dense triples,
          tail-padded with (token_pad, path_pad, token_pad)
  count   (B,) int32   — per-example effective lengths
  label   (B,) int32
  weight  (B,) float32

A TRAINING stream (a packer built with ``table_rows``) also names the
embedding rows the STEP's slots touch, ONE set a table whatever the number
of data shards, so the step can build and reduce the two tables' gradients
over those rows and not over the tables (ops/pallas_ragged.py
``_rows_table_grad``): every shard sums its slots into the same row space,
the shards' sums are added, and one scatter writes the table's gradient:

  tok_rows  (data_shards, U_tok / data_shards) int32 — the distinct token
            rows of all shards together (source and target slots),
            ascending, the PAD row among them, cut into ``data_shards``
            equal runs so that the set ships over ``data`` like every
            other array: ``tok_rows.reshape(-1)`` is the set
  path_rows (data_shards, U_path / data_shards) int32 — the same for the
            path rows
  inv       (data_shards, capacity, 3) int32 — for every slot of ``ctx``
            the position of its row in the token set (columns 0, 2) or
            the path set (column 1): ``rows.reshape(-1)[inv[s]] ==
            ctx[s]`` on every slot of every shard, the tail padding
            included

Past the step's distinct rows each set goes on with distinct ascending ids
BEYOND the table's last row (``rows_in_table + k``), so the whole set is
sorted and unique and a ``mode='drop'`` scatter discards the padding.
``U_tok``/``U_path`` are sticky and bucketed like ``capacity`` (and
multiples of ``data_shards``). One shard is the same wire with one run:
``(1, U)`` rows, ``inv`` into them. Every other stream (eval, predict,
serving, bulk) ships the four arrays alone, and so does a one-shard
training stream from the batch on whose capacity passes
``ONE_SHARD_ROWS_MAX_CAPACITY``.

12 bytes per RETAINED slot + 12 bytes per example. Keeping everything up
to the last valid slot (not only the mask-valid slots) is what makes the
round trip BIT-exact: an interior all-PAD hole (e.g. a ``,,`` context in
the source file) stays in the stream at its position, and every slot
past ``length`` is provably the PAD triple, so scattering the stream
back and filling the tail with PAD reproduces the v1 planes — and the
mask, recomputed from them with the same parity-critical predicate
(reader.context_valid_mask) — exactly.

Sharding-awareness: with ``data_shards > 1`` each data-parallel shard's
examples are packed into its own ``capacity`` rows, so the staged
``ctx`` array shards over the mesh data axis on its leading dim and each
device receives exactly its shard's bytes (parallel/mesh.py
shard_batch). All shards share one bucketed capacity so the array stays
rectangular.

``capacity`` is bucketed (``bucketed_capacity``) so the jitted unpack +
step program specializes on a handful of capacities per run instead of
one per batch.

Host-side code here is pure numpy; the device unpack imports jax lazily
so the data layer stays importable without it.
"""
from __future__ import annotations

import contextlib
import logging
import time as _time
from typing import NamedTuple, Optional, Tuple

import numpy as np

logger = logging.getLogger(__name__)

WIRE_FORMATS = ('planes', 'packed')
# how many arrays a packed batch ships: the wire alone, or with a training
# batch's touched rows (the plane wire ships six)
PACKED_ARITIES = (4, 7)

# Floor for the bucketed capacity. Small enough that tiny (test/smoke)
# batches still see a byte win; large batches are governed by the
# total/8 bucket below.
MIN_CAPACITY = 64

# The packed capacity up to which ONE data shard's row-wise table gradients
# beat the dense scatter-adds on the chip (PERF.md section 6, PR 34: +7.4 ms
# a step at 40,960 slots, +4.0..5.5 at 102,400, -1.3..-3.8 at 147,456, at
# either extreme of index skew: XLA's dense scatter-add runs at 13-17 ns an
# update from about 2**17 updates on and at 76-90 ns under that, the row
# form at 15-20 ns throughout). Past it a one-shard stream ships the four
# wire arrays. On a data-parallel mesh the rows also replace the tables'
# all-reduce, and ship whatever the capacity.
ONE_SHARD_ROWS_MAX_CAPACITY = 1 << 17


class PackedBatch(NamedTuple):
    """One device-ready batch in the packed wire format. Mirrors
    ``reader.Batch``'s host-only string ride-alongs (eval/predict)."""
    ctx: np.ndarray                  # (D, cap, 3) int32 — see module doc
    count: np.ndarray                # (B,) int32 — effective lengths
    label: np.ndarray                # (B,) int32 — target-name index
    weight: np.ndarray               # (B,) float32 — example validity
    label_strings: Optional[np.ndarray] = None     # (B,) object
    context_lines: Optional[np.ndarray] = None     # (B,) object
    # the step's touched rows, training streams only: one ascending set a
    # table in D equal runs, and every slot's position in its set
    tok_rows: Optional[np.ndarray] = None          # (D, U_tok / D) int32
    path_rows: Optional[np.ndarray] = None         # (D, U_path / D) int32
    inv: Optional[np.ndarray] = None               # (D, cap, 3) int32

    @property
    def num_valid_examples(self) -> int:
        return int(self.weight.sum())

    def device_arrays(self):
        """The arrays the jitted packed step functions consume, in a
        fixed order (the host-only strings never ship): the four wire
        arrays, then the touched-row arrays where the batch has them."""
        arrays = (self.ctx, self.count, self.label, self.weight)
        if self.inv is not None:
            arrays += (self.tok_rows, self.path_rows, self.inv)
        return arrays


def wire_bytes(batch) -> int:
    """Bytes this batch puts on the host->device wire (either format)."""
    return int(sum(np.asarray(a).nbytes for a in batch.device_arrays()))


def bucketed_capacity(total: int, minimum: int = MIN_CAPACITY) -> int:
    """Round a context total up to a bucket of ~total/8 (power of two),
    bounding both the padding waste (<12.5%) and the number of distinct
    jit specializations per run (a handful: totals cluster per corpus)."""
    cap = max(int(total), minimum)
    bucket = max(minimum, 1 << max(cap.bit_length() - 3, 0))
    return -(-cap // bucket) * bucket


def capacity_ladder(max_total: int, minimum: int = MIN_CAPACITY,
                    growth: int = 4) -> Tuple[int, ...]:
    """Fixed geometric ladder of packed capacities covering ``max_total``.

    The serving engine (serving/engine.py) pre-compiles one step program
    per rung at load, so steady-state packing always lands on a warm
    capacity — the eager-compile counterpart of ``StickyPacker``'s
    grow-on-demand bucketing (which trades a few mid-run recompiles for
    tighter fill during training). ``growth=4`` bounds the rung count to
    ~log4(max_total/minimum)+1 programs per batch bucket while keeping
    worst-case padding waste under the previous rung's 4x.

    Every rung is exact under ``pack_ragged(..., capacity_minimum=rung)``
    for totals <= rung (``bucketed_capacity`` returns its minimum
    unchanged), so picking the first rung >= the shard total yields a
    wire shape that is always one of the pre-compiled ladder shapes."""
    if max_total < 1:
        raise ValueError('max_total must be >= 1, got %d' % max_total)
    if growth < 2:
        raise ValueError('growth must be >= 2, got %d' % growth)
    rungs = []
    cap = minimum
    while cap < max_total:
        rungs.append(cap)
        cap *= growth
    rungs.append(max(max_total, minimum))
    return tuple(rungs)


def table_rows(vocab_size: int, alignment: int) -> int:
    """Rows of an embedding table holding ``vocab_size`` words: rounded up
    to the row alignment (Config.PARAM_ROW_ALIGNMENT) — the one definition
    the backends allocate by and the packer pads the touched-row arrays
    past."""
    alignment = max(int(alignment), 1)
    return -(-int(vocab_size) // alignment) * alignment


def embedding_table_rows(vocabs, alignment: int) -> Tuple[int, int]:
    """(token table rows, path table rows) for ``vocabs``."""
    return (table_rows(vocabs.token_vocab.size, alignment),
            table_rows(vocabs.path_vocab.size, alignment))


def row_capacity(distinct: int, current: int,
                 minimum: int = MIN_CAPACITY) -> int:
    """Capacity of a touched-row array that has to hold ``distinct`` rows:
    ``current`` if that does, else an eighth of head-room on top, rounded
    up to a bucket of a sixteenth to an eighth of the result. The
    head-room is what keeps a stream whose batches name about as many
    rows each (a corpus at one batch size: a standard deviation under 2%)
    on the capacity its first batch gave it."""
    distinct = int(distinct)
    if distinct <= current:
        return current
    want = distinct + distinct // 8
    bucket = max(minimum, 1 << max(want.bit_length() - 4, 0))
    return -(-want // bucket) * bucket


def distinct_rows(ids: np.ndarray, pad_row: int, lut: np.ndarray
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """The distinct values of ``ids`` and ``pad_row``, ascending, and each
    id's position among them. ``lut`` is scratch, one int32 per table row:
    a sort of the ids (numpy's int32 sort is the cheap part) and two passes
    through the table-sized lookup take a little over half of
    ``np.unique``'s time with ``return_inverse``."""
    ordered = np.sort(np.append(ids, np.int32(pad_row)))
    first = np.empty(ordered.shape, bool)
    first[0] = True
    np.not_equal(ordered[1:], ordered[:-1], out=first[1:])
    rows = ordered[first]
    lut[rows] = np.arange(rows.shape[0], dtype=np.int32)
    return rows, lut[ids]


def pad_rows(rows: np.ndarray, capacity: int, rows_in_table: int,
             data_shards: int) -> np.ndarray:
    """An ascending row set -> ``capacity`` int32 rows, continued with
    ``rows_in_table + k`` (still ascending and unique, and out of the
    table's bounds), as the (data_shards, capacity / data_shards) array
    the wire ships."""
    out = np.empty((capacity,), np.int32)
    n = rows.shape[0]
    out[:n] = rows
    out[n:] = rows_in_table + np.arange(capacity - n, dtype=np.int32)
    return out.reshape(data_shards, capacity // data_shards)


def shard_totals(count: np.ndarray, data_shards: int) -> np.ndarray:
    """(data_shards,) int64 of retained-context totals per data-parallel
    shard — the quantity the packed capacity must cover (pack_ragged's
    internal reshape, exposed for callers that pick a capacity BEFORE
    packing, e.g. the serving engine's ladder lookup)."""
    n = count.shape[0]
    if n % data_shards:
        raise ValueError('batch size %d not divisible by data_shards %d'
                         % (n, data_shards))
    return count.reshape(data_shards, n // data_shards).sum(
        axis=1, dtype=np.int64)


def effective_lengths(mask: np.ndarray) -> np.ndarray:
    """(B,) int32 of per-example effective lengths: index of the last
    mask-valid slot + 1, or 0 for all-padding rows."""
    valid = mask > 0
    any_valid = valid.any(axis=1)
    last = mask.shape[1] - np.argmax(valid[:, ::-1], axis=1)
    return np.where(any_valid, last, 0).astype(np.int32)


def ragged_gather_indices(lengths: np.ndarray, stride: int) -> np.ndarray:
    """Flat indices selecting slots [0, lengths[r]) of each row r from a
    row-major (B, stride) array."""
    total = int(lengths.sum())
    starts = np.cumsum(lengths) - lengths
    intra = np.arange(total, dtype=np.int64) - np.repeat(starts, lengths)
    return np.repeat(np.arange(lengths.shape[0], dtype=np.int64) * stride,
                     lengths) + intra


def pack_ragged(ctx_rows: np.ndarray, count: np.ndarray, token_pad: int,
                path_pad: int, data_shards: int = 1,
                capacity_minimum: int = MIN_CAPACITY) -> np.ndarray:
    """(total, 3) ragged triple stream + per-example counts -> the
    rectangular (data_shards, capacity, 3) wire array."""
    totals = shard_totals(count, data_shards)
    cap = bucketed_capacity(int(totals.max(initial=0)), capacity_minimum)
    ctx = np.empty((data_shards, cap, 3), np.int32)
    ctx[..., 0] = token_pad
    ctx[..., 1] = path_pad
    ctx[..., 2] = token_pad
    bounds = np.concatenate([[0], np.cumsum(totals)])
    for d in range(data_shards):
        ctx[d, :totals[d]] = ctx_rows[bounds[d]:bounds[d + 1]]
    return ctx


def ragged_from_planes(source: np.ndarray, path: np.ndarray,
                       target: np.ndarray, mask: np.ndarray
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """Plane arrays -> ((total, 3) int32 triple stream, (B,) effective
    lengths) — the single definition of the wire/cache triple layout."""
    lengths = effective_lengths(mask)
    flat = ragged_gather_indices(lengths, source.shape[1])
    return np.stack([source.ravel()[flat], path.ravel()[flat],
                     target.ravel()[flat]],
                    axis=1).astype(np.int32, copy=False), lengths


def pack_batch(batch, token_pad: int, path_pad: int, data_shards: int = 1,
               capacity_minimum: int = MIN_CAPACITY) -> PackedBatch:
    """reader.Batch (plane format) -> PackedBatch. Host-only string
    fields ride along untouched."""
    ctx_rows, lengths = ragged_from_planes(batch.source, batch.path,
                                           batch.target, batch.mask)
    ctx = pack_ragged(ctx_rows, lengths, token_pad, path_pad, data_shards,
                      capacity_minimum)
    return PackedBatch(ctx=ctx, count=lengths,
                       label=np.ascontiguousarray(batch.label),
                       weight=np.ascontiguousarray(batch.weight),
                       label_strings=batch.label_strings,
                       context_lines=batch.context_lines)


class StickyPacker:
    """Packs a stream of batches under a monotonically GROWING capacity:
    totals that straddle a bucket boundary reuse the larger jitted
    program instead of ping-ponging specializations. One instance per
    data source (reader / cache), living across epochs.

    ``table_rows`` = (token table rows, path table rows) marks a TRAINING
    stream: every batch then also carries the step's touched rows
    (module docstring), under sticky capacities of their own, whatever
    the number of data shards; one shard stops once its sticky capacity
    passes ``ONE_SHARD_ROWS_MAX_CAPACITY``, for good.

    Instrumented (telemetry enabled only — one bool read otherwise):
    pack time (``step/pack_ms`` and, from the same opening, a
    ``host/pack`` profiler event, so that a capture shows the packing on
    the device's clock; recorded from whichever reader/prefetch
    thread packs), the packed fill rate (retained slots / wire
    capacity — the padding waste the capacity buckets trade for fewer
    jit specializations) and, where rows ship, ``input/unique_row_share``
    (the step's distinct rows / retained index slots) and
    ``input/row_capacity_fill`` (the step's distinct rows / ``U_tok +
    U_path``)."""

    def __init__(self, token_pad: int, path_pad: int, data_shards: int = 1,
                 minimum: int = MIN_CAPACITY,
                 table_rows: Optional[Tuple[int, int]] = None):
        self.token_pad = token_pad
        self.path_pad = path_pad
        self.data_shards = data_shards
        self.capacity = self.minimum = minimum
        self.table_rows = table_rows
        self.tok_capacity = self.path_capacity = minimum
        if self.table_rows is not None:
            self._tok_lut = np.empty((table_rows[0],), np.int32)
            self._path_lut = np.empty((table_rows[1],), np.int32)

    def _touched_rows(self, ctx: np.ndarray):
        """(tok_rows, path_rows, inv, distinct) of one packed ``ctx``:
        one row set a table over all of its shards."""
        shards, cap, _ = ctx.shape
        inv = np.empty_like(ctx)
        tok, pos = distinct_rows(
            np.concatenate([ctx[..., 0].ravel(), ctx[..., 2].ravel()]),
            self.token_pad, self._tok_lut)
        inv[..., 0] = pos[:shards * cap].reshape(shards, cap)
        inv[..., 2] = pos[shards * cap:].reshape(shards, cap)
        pth, pos = distinct_rows(ctx[..., 1].ravel(), self.path_pad,
                                 self._path_lut)
        inv[..., 1] = pos.reshape(shards, cap)
        # a multiple of the shards, so that the set ships in equal runs
        grown = tuple(
            -(-row_capacity(rows.shape[0], current, self.minimum)
              // shards) * shards
            for rows, current in ((tok, self.tok_capacity),
                                  (pth, self.path_capacity)))
        if grown != (self.tok_capacity, self.path_capacity):
            logger.info('packed touched-row capacities: token %d -> %d, '
                        'path %d -> %d (one more train-step program)',
                        self.tok_capacity, grown[0], self.path_capacity,
                        grown[1])
            self.tok_capacity, self.path_capacity = grown
        return (pad_rows(tok, self.tok_capacity, self.table_rows[0], shards),
                pad_rows(pth, self.path_capacity, self.table_rows[1],
                         shards),
                inv, tok.shape[0] + pth.shape[0])

    @contextlib.contextmanager
    def _timed(self):
        """One batch's packing, opened once for both sinks where
        telemetry is on: the ``step/pack_ms`` timer and a ``host/pack``
        profiler event whose ``stats`` are the sticky capacities the
        batch met (known when the event opens; they grow in warm-up)."""
        from code2vec_tpu.telemetry import core
        if not core.enabled():
            yield
            return
        from jax.profiler import TraceAnnotation
        t0 = _time.perf_counter()
        with TraceAnnotation('host/pack', capacity=self.capacity,
                             tok_rows=self.tok_capacity,
                             path_rows=self.path_capacity):
            yield
        core.registry().timer('step/pack_ms').record(
            _time.perf_counter() - t0)

    def _finish(self, packed: PackedBatch) -> PackedBatch:
        """Attach the touched rows where this stream ships them, and
        record the batch's gauges."""
        from code2vec_tpu.telemetry import core
        distinct = None
        capacity = packed.ctx.shape[1]
        if (self.table_rows is not None and self.data_shards == 1
                and capacity > ONE_SHARD_ROWS_MAX_CAPACITY):
            logger.info('packed capacity %d is past %d: this one-shard '
                        'stream ships the four wire arrays from here on '
                        '(the dense scatter-adds are the cheaper form '
                        'there)', capacity, ONE_SHARD_ROWS_MAX_CAPACITY)
            self.table_rows = None
        if self.table_rows is not None:
            tok_rows, path_rows, inv, distinct = self._touched_rows(
                packed.ctx)
            packed = packed._replace(tok_rows=tok_rows, path_rows=path_rows,
                                     inv=inv)
        if core.enabled():
            reg = core.registry()
            retained = int(packed.count.sum())
            slots = int(packed.ctx.shape[0]) * int(capacity)
            reg.gauge('input/packed_fill_rate').set(retained / max(slots, 1))
            if distinct is not None:
                reg.gauge('input/unique_row_share').set(
                    distinct / max(3 * retained, 1))
                reg.gauge('input/row_capacity_fill').set(
                    distinct / (self.tok_capacity + self.path_capacity))
        return packed

    def pack_batch(self, batch) -> PackedBatch:
        with self._timed():
            packed = pack_batch(batch, self.token_pad, self.path_pad,
                                data_shards=self.data_shards,
                                capacity_minimum=self.capacity)
            self.capacity = max(self.capacity, packed.ctx.shape[1])
            return self._finish(packed)

    def pack_ragged(self, ctx_rows: np.ndarray, count: np.ndarray,
                    label: np.ndarray, weight: np.ndarray) -> PackedBatch:
        with self._timed():
            ctx = pack_ragged(ctx_rows, count, self.token_pad,
                              self.path_pad, self.data_shards,
                              capacity_minimum=self.capacity)
            self.capacity = max(self.capacity, ctx.shape[1])
            return self._finish(PackedBatch(ctx=ctx, count=count,
                                            label=label, weight=weight))


def unpack_ragged_np(ctx_rows: np.ndarray, count: np.ndarray,
                     max_contexts: int, token_pad: int, path_pad: int
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(total, 3) triple stream + counts -> PAD-filled (B, C) planes."""
    n = count.shape[0]
    flat = ragged_gather_indices(count.astype(np.int64), max_contexts)
    planes = []
    for column, fill in ((0, token_pad), (1, path_pad), (2, token_pad)):
        plane = np.full((n * max_contexts,), fill, np.int32)
        plane[flat] = ctx_rows[:, column]
        planes.append(plane.reshape(n, max_contexts))
    return planes[0], planes[1], planes[2]


def unpack_batch_host(packed: PackedBatch, max_contexts: int,
                      token_pad: int, path_pad: int):
    """Numpy reference inverse of ``pack_batch`` — the ground truth the
    device unpack is property-tested against, and the planes-emission
    path for v2 token caches read under the planes wire format."""
    from code2vec_tpu.data.reader import Batch, context_valid_mask
    shards, cap, _ = packed.ctx.shape
    count2 = packed.count.reshape(shards, -1)
    keep = ragged_gather_indices(
        count2.sum(axis=1, dtype=np.int64).astype(np.int64), cap)
    ctx_rows = packed.ctx.reshape(shards * cap, 3)[keep]
    source, path, target = unpack_ragged_np(
        ctx_rows, packed.count, max_contexts, token_pad, path_pad)
    mask = context_valid_mask(source, path, target, token_pad, path_pad)
    return Batch(source=source, path=path, target=target, mask=mask,
                 label=packed.label, weight=packed.weight,
                 label_strings=packed.label_strings,
                 context_lines=packed.context_lines)


def segment_structure(count2, cap: int):
    """Segment structure of the packed stream, per shard — THE single
    definition of the parity-critical slot->example arithmetic, shared
    by the device unpack below and the ragged fused encoder
    (ops/pallas_ragged.py).

    ``count2`` is the ``(data_shards, per_shard)`` per-example lengths
    (a device array inside jit); returns ``(seg, pos, in_range)``, each
    ``(data_shards, cap)``:

    - ``seg``: segment ids — +1 at each example's start offset,
      cumsummed; repeated starts (zero-length examples) accumulate, and
      slots past the shard's retained total all map to the LAST example
      (the unpack scatters them onto its PAD tail; the fused encoder
      masks them via ``in_range``). The inc row index must be shaped
      like ``starts[:, 1:]`` — (D, Bs-1), NOT a slice of the (D, cap)
      grid: per-shard batch can exceed capacity.
    - ``pos``: the slot's position within its example — its plane
      column (past-the-count for capacity padding).
    - ``in_range``: slot < the shard's retained total (capacity padding
      is not).
    """
    import jax.numpy as jnp

    shards, per_shard = count2.shape
    starts = jnp.cumsum(count2, axis=1) - count2            # (D, Bs)
    inc = jnp.zeros((shards, cap), jnp.int32)
    if per_shard > 1:
        row_idx = jnp.broadcast_to(
            jnp.arange(shards, dtype=jnp.int32)[:, None],
            (shards, per_shard - 1))
        inc = inc.at[row_idx, starts[:, 1:]].add(1, mode='drop')
    seg = jnp.cumsum(inc, axis=1)                           # (D, cap)
    pos = (jnp.arange(cap, dtype=jnp.int32)[None, :]
           - jnp.take_along_axis(starts, seg, axis=1))      # (D, cap)
    in_range = (jnp.arange(cap, dtype=jnp.int32)[None, :]
                < count2.sum(axis=1)[:, None])              # (D, cap)
    return seg, pos, in_range


def unpack_device(ctx, count, max_contexts: int, token_pad: int,
                  path_pad: int):
    """Jitted device-side inverse of ``pack_batch``: segment-scatter the
    dense triples back to the exact (B, C) planes + mask the model
    consumes.

    Shard-structured: every op batches along the leading ``data_shards``
    dim that the mesh data axis shards, so GSPMD partitions the unpack
    per shard. Capacity-padding rows hold the PAD triple and land either
    on out-of-range slots (dropped) or on tail slots whose expected
    value IS the PAD fill — bit-exactness is unconditional (property-
    tested against ``unpack_batch_host`` in tests/test_packed.py).

    The mask predicate mirrors reader.context_valid_mask — the
    parity-critical single definition for the host side; keep in sync.
    """
    import jax.numpy as jnp

    shards, cap, _ = ctx.shape
    batch = count.shape[0]
    per_shard = batch // shards
    count2 = count.reshape(shards, per_shard)
    seg, pos, _in_range = segment_structure(count2, cap)
    shard_idx = jnp.broadcast_to(
        jnp.arange(shards, dtype=jnp.int32)[:, None], (shards, cap))

    def scatter(vals, fill):
        out = jnp.full((shards, per_shard, max_contexts), fill, jnp.int32)
        out = out.at[shard_idx, seg, pos].set(vals, mode='drop')
        return out.reshape(batch, max_contexts)

    source = scatter(ctx[..., 0], token_pad)
    path = scatter(ctx[..., 1], path_pad)
    target = scatter(ctx[..., 2], token_pad)
    mask = ((source != token_pad) | (target != token_pad)
            | (path != path_pad)).astype(jnp.float32)
    return source, path, target, mask
