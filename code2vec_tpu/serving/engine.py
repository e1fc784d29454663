"""High-throughput serving engine: dynamic micro-batching over a fixed
ladder of warm, pre-compiled programs.

The naive serving shape — one ``model.predict`` per request — compiles a
fresh XLA program for every distinct request size, batches nothing
across requests, and computes + transfers attention weights and code
vectors even when the caller wants neither. TPU serving systems instead
coalesce ragged concurrent requests into a small set of pre-compiled
bucketed shapes and keep the device queue full (Ragged Paged Attention,
arxiv 2604.15464; Google's ads-serving infrastructure, arxiv 2501.10546
— PAPERS.md). This module is that shape for code2vec:

- **Bucket ladder.** Batch buckets (``Config.SERVING_BATCH_BUCKETS``,
  each rounded up to a multiple of the mesh data axis) × packed-capacity
  rungs (``data/packed.py::capacity_ladder`` — the eager-compile
  counterpart of training's StickyPacker bucketing) × output tiers
  (``training/trainer.py::PREDICT_TIERS``). ``warmup()`` compiles every
  program in the ladder at load, so steady-state serving never compiles
  (compile-counter-asserted in tests/test_serving_bench.py).
- **Dynamic micro-batcher.** ``submit()`` tokenizes on the caller thread
  and enqueues; a dispatcher thread coalesces concurrent requests under
  a max-latency deadline (``SERVING_MAX_DELAY_MS``) into the smallest
  covering batch bucket, packs them over the compact wire format
  (data/packed.py — the 0.24x bytes win applies directly to the h2d
  serving path), and dispatches asynchronously, so the device queue
  stays full while the NEXT batch coalesces.  A batch closes at the
  first of three: a decode slot is free (fewer batches in flight than
  decode workers — holding the request would buy nothing), the deadline
  passed, or the largest bucket is full.  So an idle engine answers at
  once and a loaded one gathers followers for as long as its own
  pipeline is busy, never past the deadline
  (``stats()['early_close_total']`` counts the first kind).
- **Decode offload.** Host-side decode (device fetch, top-k word lookup,
  attention parsing) runs on a worker pool (``SERVING_DECODE_WORKERS``),
  so device dispatch never waits on Python.

Resilient under overload and across model refreshes (ROBUSTNESS.md
serving pillar; SERVING.md "Overload & rollover runbook"):

- **Admission control.** The front queue is bounded
  (``SERVING_QUEUE_BOUND`` rows); submissions past it — or whose SLO
  deadline (``SERVING_DEADLINE_MS`` / per-``submit`` ``deadline_ms=``)
  the queue's drain estimate already exceeds — are shed with a typed
  ``EngineOverloaded`` at admission. Queued requests whose deadline
  passes are expired with ``DeadlineExceeded`` instead of dispatching
  dead work, and a degradation ladder downgrades output tier
  (full → attention → topk) while the queue runs hot.
- **Canaried zero-downtime rollover.** ``load_params(step|path|pytree)``
  loads candidate params alongside the serving set, shadow-scores live
  micro-batches against both (same shapes and shardings — the warm
  ladder is reused, zero new compiles), and atomically swaps when top-1
  agreement clears ``SERVING_CANARY_AGREEMENT``, else rolls back.
  ``follow_checkpoints`` polls the store and rolls newer steps in.

Instrumented with standalone telemetry instruments (``stats()``) that
mirror into the process-global registry when telemetry is enabled
(``serving/*`` in telemetry/catalog.py; OBSERVABILITY.md).

One engine is one replica: a fleet of them serves behind ONE shared
front queue as a ``ServingMesh`` (serving/mesh.py; SERVING.md "Serving
mesh") — the engine then runs in **external-dispatch mode**
(``external_dispatch=True``): no private queue or dispatcher thread,
the mesh's replica puller feeds ``dispatch_external()`` directly, and
every registry mirror below is replica-labeled
(``serving/...{replica=rN}``) so coexisting replicas never collide in
the process-global registry.

Typical use::

    engine = model.serving_engine()          # warm-compiles the ladder
    future = engine.submit(context_lines)    # -> Future[list[results]]
    results = engine.predict(context_lines)  # sync convenience
    engine.close()                           # or `with model.serving_engine() as engine:`

SERVING.md has the architecture, the latency/throughput model, and the
runbook.
"""
from __future__ import annotations

import collections
import gc
import itertools
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from code2vec_tpu.data import packed as packed_lib
from code2vec_tpu.data.reader import (Batch, EstimatorAction,
                                      PathContextReader,
                                      canonicalize_contexts,
                                      context_triples)
from code2vec_tpu.models.families import family_of
from code2vec_tpu.parallel import mesh as mesh_lib
from code2vec_tpu.resilience import faults
from code2vec_tpu.serving.errors import (DeadlineExceeded, EngineClosed,
                                         EngineOverloaded)
from code2vec_tpu.telemetry import core as tele_core
from code2vec_tpu.telemetry import memory as memory_lib
from code2vec_tpu.telemetry import tracing as tracing_lib
from code2vec_tpu.telemetry.core import Counter, Gauge, Timer
from code2vec_tpu.training.trainer import PREDICT_TIERS

#: overload degradation ladder: tier served at each level (missing keys
#: keep the requested tier). Level 1 sheds the attention decode of
#: 'full'; level 2 serves bare top-k only. 'vectors' is never remapped —
#: its callers need the vectors, not a cheaper answer.
_DEGRADE_LADDER = {
    1: {'full': 'attention'},
    2: {'full': 'topk', 'attention': 'topk'},
}
#: queue-fill fractions (of the admission bound): enter level 2 / enter
#: level 1 / drop back to 0. The wide exit gap is the hysteresis that
#: makes the ladder respond to SUSTAINED overload instead of flapping
#: on every burst.
_OVERLOAD_ENTER_2 = 0.75
_OVERLOAD_ENTER_1 = 0.50
_OVERLOAD_EXIT = 0.25

#: sliding window the drain-estimate throughput aggregates over, and the
#: minimum span it must cover before it overrides the sojourn seed — a
#: burst of near-simultaneous completions spans microseconds and carries
#: no throughput signal
_SERVICE_WINDOW_S = 2.0
_SERVICE_MIN_SPAN_S = 0.05

#: Serializes the ASYNC device enqueue of predict programs across
#: coexisting engines (mesh replicas share one device mesh in-process).
#: Two threads interleaving their per-device enqueues of
#: collective-bearing SPMD programs can cross the programs' rendezvous
#: and deadlock the backend (observed: two replicas' AllGathers wedged
#: on the 8-device CPU test mesh).  Holding the lock only for the
#: enqueue imposes a consistent per-device program order; the
#: executions themselves still pipeline (per-device streams run them
#: in order), so the serialized section is microseconds, not step time.
_DISPATCH_ENQUEUE_LOCK = threading.Lock()


# --------------------------------------------------------------- ladder
def batch_ladder(buckets: Sequence[int], data_axis: int) -> Tuple[int, ...]:
    """Sorted, deduplicated batch buckets, each rounded UP to a multiple
    of the mesh data axis so every bucket shards evenly."""
    if data_axis < 1:
        raise ValueError('data_axis must be >= 1, got %d' % data_axis)
    out = set()
    for bucket in buckets:
        bucket = int(bucket)
        if bucket < 1:
            raise ValueError('batch buckets must be >= 1, got %d' % bucket)
        out.add(-(-bucket // data_axis) * data_axis)
    return tuple(sorted(out))


def pick_bucket(n: int, ladder: Sequence[int]) -> Optional[int]:
    """Smallest bucket covering ``n`` rows, or None when ``n`` exceeds
    the ladder (callers split, or fall back to ad-hoc padding)."""
    for bucket in ladder:
        if bucket >= n:
            return bucket
    return None


def attention_per_context(context_line: str, attention_weights
                          ) -> Dict[Tuple[str, str, str], float]:
    """Per-context attention dict of one row, skipping padding contexts
    (reference model_base.py:115-129). Single definition — model_api and
    the engine decode both use it.  The contexts' strings are made here,
    from the row's line as it was tokenized, slot by slot beside the
    weights: a row of 36 contexts costs 36 splits, and only when its
    tier returned attention."""
    out: Dict[Tuple[str, str, str], float] = {}
    for triple, weight in zip(context_triples(context_line),
                              attention_weights.tolist()):
        if any(triple):  # else a padding context
            out[triple] = weight
    return out


def decode_results(fetched: Dict[str, np.ndarray], batch: Batch,
                   n_rows: int, decode_table: np.ndarray) -> list:
    """Host numpy outputs + the (string-bearing) plane batch -> one
    ``ModelPredictionResults`` per row. Only the keys the tier produced
    are present in ``fetched``; absent tiers decode to empty/None."""
    # lazy: model_api imports this module (circularity-free direction)
    from code2vec_tpu.model_api import ModelPredictionResults
    topk_indices = fetched.get('topk_indices')
    topk_scores = fetched.get('topk_scores')
    attention = fetched.get('attention')
    code_vectors = fetched.get('code_vectors')
    results = []
    for r in range(n_rows):
        attn = {}
        if attention is not None and batch.context_lines is not None:
            attn = attention_per_context(batch.context_lines[r],
                                         attention[r])
        results.append(ModelPredictionResults(
            original_name=(str(batch.label_strings[r])
                           if batch.label_strings is not None else ''),
            topk_predicted_words=(list(decode_table[topk_indices[r]])
                                  if topk_indices is not None else []),
            topk_predicted_words_scores=(topk_scores[r]
                                         if topk_scores is not None
                                         else None),
            attention_per_context=attn,
            code_vector=(code_vectors[r]
                         if code_vectors is not None else None)))
    return results


# ------------------------------------------------------------- requests
def _resolve(future: Future, results: list) -> None:
    """set_result tolerating an already-done future: a caller may
    cancel() (these futures are never marked running, so cancel always
    succeeds) — its own result is then dropped, but delivery to the
    OTHER requests coalesced into the same micro-batch must proceed."""
    if not future.done():
        try:
            future.set_result(results)
        except Exception:
            pass  # lost the race to a concurrent cancel


class _Aggregate:
    """Joins the chunk results of one oversize request back into its
    caller-visible future, preserving row order."""

    # decode workers race on the chunk slots (lock-discipline rule,
    # ANALYSIS.md):
    # graftlint: guard _Aggregate.parts,left by lock
    def __init__(self, future: Future, n_chunks: int, trace=None):
        self.future = future
        self.parts: List[Optional[list]] = [None] * n_chunks
        self.left = n_chunks
        self.trace = trace  # the chunks' SHARED trace; finished at join
        self.lock = threading.Lock()

    def deliver(self, idx: int, results: list) -> None:
        with self.lock:
            self.parts[idx] = results
            self.left -= 1
            # snapshot under the lock: the last-chunk decider must not
            # re-read `parts` barehanded after releasing it
            done = list(self.parts) if self.left == 0 else None
        if done is not None:
            merged: list = []
            for part in done:
                merged.extend(part)
            _resolve(self.future, merged)
            if self.trace is not None:
                self.trace.event('serving.join',
                                 attrs={'chunks': len(done),
                                        'rows': len(merged)})
                self.trace.finish(status='ok')

    def fail(self, exc: BaseException) -> None:
        # first failure wins; set_exception on a done future raises
        if not self.future.done():
            try:
                self.future.set_exception(exc)
            except Exception:
                pass


class _Request:
    """One queue entry: a tokenized chunk of <= max-bucket rows."""

    __slots__ = ('batch', 'rows', 'tier', 'future', 'aggregate',
                 'chunk_idx', 't_enqueue', 't_deadline', 'trace',
                 'span_parent', 'queue_span', 'redispatched', 'exclude')

    def __init__(self, batch: Batch, tier: str,
                 future: Optional[Future] = None,
                 aggregate: Optional[_Aggregate] = None,
                 chunk_idx: int = 0,
                 deadline_s: Optional[float] = None,
                 trace=None, span_parent=None):
        self.batch = batch
        self.rows = int(batch.label.shape[0])
        self.tier = tier
        self.future = future
        self.aggregate = aggregate
        self.chunk_idx = chunk_idx
        # this request's trace (chunks of one oversize submit SHARE it;
        # span_parent is then the chunk span, phases nest under it)
        self.trace = trace
        self.span_parent = span_parent
        self.queue_span = None  # open serving.queue_wait span
        self.t_enqueue = time.perf_counter()
        # absolute expiry instant on the t_enqueue clock; None = no SLO
        self.t_deadline = (self.t_enqueue + deadline_s
                           if deadline_s else None)
        # crash-safe redispatch state (serving/mesh.py): a batch that
        # dies with its worker re-admits its members ONCE at the queue
        # front, excluding the dead replica incarnation
        self.redispatched = False
        self.exclude = None

    def deliver(self, results: list) -> None:
        if self.aggregate is not None:
            self.aggregate.deliver(self.chunk_idx, results)
        else:
            _resolve(self.future, results)

    def finish_trace(self) -> None:
        """Trace bookkeeping after a successful deliver: chunks close
        their chunk span (the shared trace finishes at the aggregate
        join); single requests finish their trace here."""
        if self.trace is None:
            return
        if self.aggregate is not None:
            if self.span_parent is not None:
                self.trace.end(self.span_parent)
        else:
            self.trace.finish(status='ok')

    def fail(self, exc: BaseException) -> None:
        if self.trace is not None:
            # every typed-failed future still gets a terminal span with
            # its reason — no trace is ever truncated by shutdown
            if isinstance(exc, EngineClosed):
                self.trace.event('serving.closed',
                                 parent=self.span_parent,
                                 attrs={'reason': str(exc)})
                self.trace.finish(status='closed')
            elif isinstance(exc, DeadlineExceeded):
                self.trace.event('serving.expired',
                                 parent=self.span_parent,
                                 attrs={'reason': str(exc)})
                self.trace.finish(status='expired')
            else:
                self.trace.finish(status='error', reason=repr(exc))
        if self.aggregate is not None:
            self.aggregate.fail(exc)
        elif not self.future.done():
            self.future.set_exception(exc)


def bound_rejects(admitted: int, rows: int,
                  bound: Optional[int]) -> bool:
    """The admission bound's pile-up rule, shared by the engine's
    ``_admit`` and the mesh's ``FrontQueue.admit``: the bound rejects
    request PILE-UP, not request size — a single request larger than
    the whole bound (the oversize-splitting contract) is admitted
    alone on an idle queue; its own size then bounds the queue, and
    everything behind it sheds until it drains."""
    if bound is None or admitted + rows <= bound:
        return False
    return rows <= bound or admitted > 0


def overload_tier(admitted: int, rows: int, bound: Optional[int],
                  level: int, tier: str,
                  warm_tiers: Sequence[str]) -> Tuple[int, str]:
    """One hysteresis step of the degradation ladder, shared by engine
    and mesh admission: returns ``(new_level, effective_tier)``.  The
    wide enter/exit gap makes the ladder respond to SUSTAINED overload
    instead of flapping on bursts; a downgrade never lands on a cold
    program (``warm_tiers``)."""
    if bound is not None:
        fill = (admitted + rows) / bound
        if fill >= _OVERLOAD_ENTER_2:
            level = 2
        elif fill >= _OVERLOAD_ENTER_1:
            level = max(level, 1)
        elif fill < _OVERLOAD_EXIT:
            level = 0
    effective = _DEGRADE_LADDER.get(level, {}).get(tier, tier)
    if effective != tier and effective not in warm_tiers:
        effective = tier
    return level, effective


def note_service_window(window: collections.deque, window_rows: int,
                        rate: float, rows: int,
                        oldest_enqueue: Optional[float]
                        ) -> Tuple[int, float]:
    """One completion's update of the sliding served-rows/s window —
    the drain-estimate math shared by ``ServingEngine._note_service``
    (one replica) and ``ServingMesh`` (every replica's completions →
    the fleet rate).  Mutates ``window`` in place and returns the new
    ``(window_rows, rate)``; the caller holds its own lock.  See
    ``_note_service`` for why throughput-over-a-window (not sojourn,
    not inter-completion gaps) is the right estimator."""
    now = time.perf_counter()
    window.append((now, rows))
    window_rows += rows
    horizon = now - _SERVICE_WINDOW_S
    while len(window) > 1 and window[0][0] < horizon:
        _t, evicted = window.popleft()
        window_rows -= evicted
    anchor_t, anchor_rows = window[0]
    span = now - anchor_t
    if span >= _SERVICE_MIN_SPAN_S:
        # the anchor's own rows completed AT the span's start — they
        # represent work done before it and are excluded
        rate = (window_rows - anchor_rows) / span
    elif rate <= 0 and oldest_enqueue is not None:
        # seed from batch sojourn until the window spans a measurable
        # interval — biased low, so a shed too many, never a deadline
        # promised and missed
        rate = rows / max(1e-6, now - oldest_enqueue)
    return window_rows, rate


def tokenize_and_chunk(reader: PathContextReader,
                       lines: Sequence[str], tier: str, future: Future,
                       deadline_s: Optional[float], trace,
                       t_tokenize0: float,
                       max_bucket: int) -> List['_Request']:
    """Caller-thread tokenize + oversize chunking, shared by
    ``ServingEngine.submit`` and ``ServingMesh.submit``: one request at
    or under the top bucket stays whole; larger ones split into
    ``_Request`` chunks re-joined in order through an ``_Aggregate``
    (chunk spans nest each chunk's phases under the shared trace)."""
    with tracing_lib.phase('serving.tokenize', rows=len(lines),
                           native=int(reader.native)) as tokenize:
        batch = reader.process_input_rows(lines)
    if trace is not None:
        # from the admission span's end: the two tile
        tokenize.span(trace, t0=t_tokenize0)
    n = int(batch.label.shape[0])
    if n <= max_bucket:
        return [_Request(batch, tier, future=future,
                         deadline_s=deadline_s, trace=trace)]
    n_chunks = -(-n // max_bucket)
    aggregate = _Aggregate(future, n_chunks, trace=trace)
    requests = []
    for i in range(n_chunks):
        chunk = PathContextReader._take_rows(
            batch, slice(i * max_bucket, (i + 1) * max_bucket))
        chunk_span = None
        if trace is not None:
            chunk_span = trace.span(
                'serving.chunk',
                attrs={'chunk': i, 'of': n_chunks,
                       'rows': int(chunk.label.shape[0])})
        requests.append(_Request(
            chunk, tier, aggregate=aggregate, chunk_idx=i,
            deadline_s=deadline_s, trace=trace,
            span_parent=chunk_span))
    return requests


class _GcPauseHook:
    """``gc.callbacks`` hook: every generation-2 collection becomes a
    ``process/gc_pause`` profiler event (entered on ``start``, left on
    ``stop``, both on the collecting thread).  The interpreter calls the
    hook for every collection; younger generations return at once."""

    def __init__(self):
        self._open: Optional[tracing_lib.Phase] = None

    def __call__(self, when: str, info: dict) -> None:
        if info['generation'] != 2:
            return
        if when == 'start':
            self._open = tracing_lib.phase('process.gc_pause',
                                           generation=2)
            self._open.__enter__()
        elif self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


class _Rollover:
    """One in-flight canaried param rollover: the candidate params plus
    the canary tallies. All fields are mutated under the engine's
    ``_cond`` lock (the dispatcher reads it, decode workers tally into
    it, ``load_params``/``close`` create and clear it)."""

    __slots__ = ('params', 'step', 'handle', 'target_batches',
                 'min_agreement', 't_armed', 'batches', 'rows',
                 'agree_rows', 'primary_fetch_s', 'shadow_fetch_s')

    def __init__(self, params, step: Optional[int], handle: Future,
                 target_batches: int, min_agreement: float):
        self.params = params
        self.step = step
        self.handle = handle
        self.target_batches = target_batches
        self.min_agreement = min_agreement
        self.t_armed = time.perf_counter()
        self.batches = 0
        self.rows = 0
        self.agree_rows = 0
        self.primary_fetch_s = 0.0
        self.shadow_fetch_s = 0.0

    def report(self, swapped: bool, reason: str) -> Dict[str, object]:
        rows = max(1, self.rows)
        return {
            'swapped': swapped,
            'reason': reason,
            'step': self.step,
            'agreement': (self.agree_rows / rows if self.rows else None),
            'batches': self.batches,
            'rows': self.rows,
            'primary_fetch_ms': 1e3 * self.primary_fetch_s
            / max(1, self.batches),
            'shadow_fetch_ms': 1e3 * self.shadow_fetch_s
            / max(1, self.batches),
        }


# --------------------------------------------------------------- engine
class ServingEngine:
    """Warm-compiled, micro-batching inference engine over a model's
    trainer + params. Build via ``Code2VecModel.serving_engine()``.

    Thread-safe: ``submit`` may be called from any number of threads;
    one dispatcher thread coalesces, ``decode_workers`` threads decode.
    """

    def __init__(self, config, trainer, params, vocabs,
                 decode_table: np.ndarray,
                 tiers: Optional[Sequence[str]] = None,
                 max_delay_ms: Optional[float] = None,
                 decode_workers: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 queue_bound: Optional[int] = None,
                 canary_batches: Optional[int] = None,
                 canary_agreement: Optional[float] = None,
                 param_source=None,
                 params_step: Optional[int] = None,
                 tracer: Optional[tracing_lib.Tracer] = None,
                 tracing_sample_rate: Optional[float] = None,
                 replica_id: Optional[str] = None,
                 external_dispatch: bool = False,
                 on_batch_done=None,
                 lm_runtime=None,
                 log=None):
        self.config = config
        # the model seam (models/families.py): the family declares the
        # tiers; a language model brings its runtime (the weights, the
        # cache pools and the step programs) and the dispatcher thread
        # runs its step loop (serving/lm_scheduler.py) in place of the
        # micro-batcher below.  None for code2vec: every branch on it
        # is taken at construction or at the top of submit()/warmup()
        self.family = family_of(config)
        self._lm = None
        # mesh-replica identity (serving/mesh.py): labels this engine's
        # registry mirrors so N coexisting replicas never double-count a
        # counter or overwrite each other's gauges, and stamps the
        # dispatch spans for per-replica latency attribution
        self.replica_id = replica_id
        # external-dispatch mode: the engine compiles/dispatches/decodes
        # but owns NO queue — a ServingMesh dispatcher feeds it through
        # dispatch_external(); submit()/follow_checkpoints() are the
        # mesh's job and refuse here
        self._external = bool(external_dispatch)
        # completion hook (mesh replica table): called from the decode
        # worker as (engine, rows, taken, ok) once a dispatched batch
        # delivered (or typed-failed) — drives the mesh's in-flight
        # window, fleet drain estimate, and dispatch-share gauges
        self._on_batch_done = on_batch_done
        self.trainer = trainer
        self.params = params
        self.decode_table = decode_table
        self.log = log if log is not None else (lambda msg: None)
        import jax
        if jax.process_count() > 1:
            # per-host request queues cannot agree on batch contents
            # without a coordination layer; multi-host serving runs one
            # engine per host replica over that host's own mesh instead
            raise NotImplementedError(
                'ServingEngine is single-host only (runs on %d '
                'processes); serve one engine replica per host.'
                % jax.process_count())
        if lm_runtime is None:
            self.mesh = trainer.mesh
            self.data_axis = self.mesh.shape[mesh_lib.DATA_AXIS]
            # predict semantics: rows are never filtered; each row's line
            # rides along for the attention tiers' decode.  Built here, in
            # set-up, because the reader loads the native tokenizer's
            # vocabulary (over a second at java14m size; the first run of
            # a checkout compiles the library too): never on a request
            self.reader = PathContextReader(vocabs, config,
                                            EstimatorAction.Predict)
            self.wire = config.wire_format_for(jax.process_count())
            self.buckets = batch_ladder(config.serving_batch_buckets,
                                        self.data_axis)
            # capacity rungs per bucket: a bucket's per-shard stream can
            # hold at most (bucket / data_axis) * MAX_CONTEXTS retained
            # slots
            self.capacities: Dict[int, Tuple[int, ...]] = {
                bucket: packed_lib.capacity_ladder(
                    (bucket // self.data_axis) * config.MAX_CONTEXTS)
                for bucket in self.buckets}
            tiers = tuple(tiers if tiers is not None
                          else config.serving_warm_tiers)
        else:
            if external_dispatch:
                raise NotImplementedError(
                    'a language model serves from one engine; it is not a '
                    'mesh replica yet')
            self.mesh = self.reader = self.wire = None
            self.data_axis = 1
            # the admission bound's unit: one request is one row, and a
            # "bucket" is the sequences resident at once
            self.buckets = (lm_runtime.slots,)
            self.capacities = {}
            tiers = tuple(tiers if tiers is not None else self.family.tiers)
        for tier in tiers:
            if tier not in self.family.tiers:
                raise ValueError('unknown tier %r; expected a subset of %s'
                                 % (tier, self.family.tiers))
        self.tiers = tiers
        self.max_delay_s = (max_delay_ms if max_delay_ms is not None
                            else config.SERVING_MAX_DELAY_MS) / 1e3
        deadline_ms = (deadline_ms if deadline_ms is not None
                       else config.SERVING_DEADLINE_MS)
        # default SLO deadline in seconds; None = no deadline
        self.deadline_s = deadline_ms / 1e3 if deadline_ms > 0 else None
        bound = (queue_bound if queue_bound is not None
                 else config.SERVING_QUEUE_BOUND)
        # admission bound in queued rows; None = unbounded (-1), auto (0)
        # = a few in-flight fills of the top bucket
        self.queue_bound: Optional[int] = (
            None if bound < 0 else
            8 * self.buckets[-1] if bound == 0 else bound)
        self.canary_batches = (canary_batches
                               if canary_batches is not None
                               else config.SERVING_CANARY_BATCHES)
        self.canary_agreement = (canary_agreement
                                 if canary_agreement is not None
                                 else config.SERVING_CANARY_AGREEMENT)
        self.canary_timeout_s = config.SERVING_CANARY_TIMEOUT_SECS
        # resolves load_params(step|path) refs and newest_step() polls;
        # None on engines built from bare params (load_params then only
        # accepts a params pytree)
        self._param_source = param_source
        workers = (decode_workers if decode_workers is not None
                   else config.SERVING_DECODE_WORKERS)
        # the registry mirror for every emission site below: the plain
        # process-global registry for a standalone engine, a replica-
        # labeled view of it (serving/x_total{replica=rN}) for a mesh
        # replica — telemetry/catalog.py "Instance labels"
        if replica_id is not None:
            self._mirror = tele_core.ScopedRegistry(
                tele_core.registry(), 'replica', replica_id)
        else:
            self._mirror = tele_core.registry()
        # standalone instruments: stats()/benchmarks read them without
        # enabling the process-global telemetry layer; emission sites
        # below mirror into the registry when telemetry is on
        self.latency = Timer('serving/latency_ms')
        self.dispatch_timer = Timer('serving/dispatch_ms')
        self.decode_timer = Timer('serving/decode_ms')
        self.requests_total = Counter('serving/requests_total')
        self.tokenize_native_rows_total = Counter(
            'serving/tokenize_native_rows_total')
        self.tokenize_fallback_rows_total = Counter(
            'serving/tokenize_fallback_rows_total')
        self.batches_total = Counter('serving/batches_total')
        self.early_close_total = Counter('serving/early_close_total')
        self.queue_depth = Gauge('serving/queue_depth')
        self.fill_rate = Gauge('serving/batch_fill_rate')
        self.shed_total = Counter('serving/shed_total')
        self.expired_total = Counter('serving/expired_total')
        self.degraded_total = Counter('serving/degraded_total')
        self.overload_level_gauge = Gauge('serving/overload_level')
        self.rollover_total = Counter('serving/rollover_total')
        self.rollover_rollbacks_total = Counter(
            'serving/rollover_rollbacks_total')
        self.rollover_agreement = Gauge('serving/rollover_agreement')
        self.last_dispatch: Optional[Dict[str, int]] = None
        # submitters, the dispatcher, decode workers, and close() share
        # the queue / rollover / overload state; _cond wraps _lock, so
        # holding either alias guards the fields (lock-discipline rule,
        # ANALYSIS.md):
        # graftlint: guard ServingEngine._queues,_pending_rows,_reserved_rows,_closed,_drain,params,_rollover,_params_step,_overload_level,_peak_rows,_service_rows_per_s,_service_window,_service_window_rows,_in_flight by _lock|_cond
        # graftlint: guard ServingEngine._warm by _warm_lock
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._queues: Dict[str, collections.deque] = {
            tier: collections.deque() for tier in self.family.tiers}
        self._pending_rows: Dict[str, int] = {
            t: 0 for t in self.family.tiers}
        # rows admitted but not yet enqueued (tokenizing on the caller
        # thread): counted against the bound so concurrent submitters
        # cannot overshoot it between admission and enqueue
        self._reserved_rows = 0
        self._closed = False
        self._drain = False  # close(drain=True) serves the queue first
        self._rollover: Optional[_Rollover] = None
        # the retained step the serving params came from (wired by
        # model.serving_engine() from the restored checkpoint): the
        # follow-checkpoints baseline, so the first poll doesn't pay a
        # full restore + canary to re-roll the already-serving step
        self._params_step: Optional[int] = params_step
        self._overload_level = 0
        self._peak_rows = 0
        # served rows/sec over a sliding window of decode completions —
        # the drain estimate admission compares against deadlines
        self._service_rows_per_s = 0.0
        self._service_window: collections.deque = collections.deque()
        self._service_window_rows = 0  # sum of rows in _service_window
        # batches handed to the decode pool and not yet returned from
        # _decode: with fewer of them than workers a decode slot is
        # free, and the dispatcher closes a batch without waiting
        self._in_flight = 0
        self._decode_slots = max(1, workers)
        self._warm = False
        self._index = None  # attach_index() arms submit_neighbors
        self._warm_lock = threading.Lock()
        # per-request tracing (telemetry/tracing.py; OBSERVABILITY.md
        # "Per-request serving traces"): head-sampled span log + the
        # always-on flight recorder. rate 0 = no tracer, and every
        # instrumented site below reduces to one `is not None` check.
        rate = (tracing_sample_rate if tracing_sample_rate is not None
                else config.tracing_sample_rate)
        # an INJECTED tracer belongs to its injector (a mesh shares one
        # across every replica; a bench reads it after the run): only a
        # tracer this engine constructed is closed by engine.close()
        self._owns_tracer = tracer is None
        if tracer is not None:
            self._tracer: Optional[tracing_lib.Tracer] = tracer
        elif rate > 0:
            out_dir = None
            if getattr(config, 'TELEMETRY_DIR', None) or \
                    config.is_saving or config.is_loading:
                # only write span logs where the run already keeps
                # artifacts; with no such directory the tracer runs
                # memory-only (ring works, nothing lands in the CWD)
                from code2vec_tpu.telemetry.stepwatch import telemetry_dir
                out_dir = telemetry_dir(config)
            self._tracer = tracing_lib.Tracer(
                out_dir, sample_rate=rate,
                slow_ms=config.TRACING_SLOW_MS,
                flight_traces=config.TRACING_FLIGHT_TRACES,
                # a worker-mode mesh replica shares the parent's
                # telemetry dir: namespace its flight dumps
                # (flight_<event>_r<N>.jsonl) so two processes never
                # clobber one postmortem file
                instance=replica_id,
                log=self.log)
        else:
            self._tracer = None
        # device-memory ledger (telemetry/memory.py): the engine's
        # initial params are the MODEL's allocation (registered by its
        # owner — trainer init or checkpoint restore), so the engine
        # registers nothing at construction; it owns only the sets IT
        # brings in — a rollover candidate while armed, and the
        # swapped-in serving set afterwards (fixed per-engine keys, so
        # replacement is release).  The abstract param bytes feed the
        # load_params budget precheck.
        self._mem_prefix = 'engine:%x' % id(self)
        self._params_nbytes = memory_lib.tree_nbytes(
            trainer.backend.param_shapes() if lm_runtime is None
            else params)
        self._follow_thread: Optional[threading.Thread] = None
        self._follow_stop = threading.Event()
        # joins a batch's profiler events across the dispatcher and the
        # decode worker (the `batch` stat of serving/pack, /fetch,
        # /deliver)
        self._batch_seq = itertools.count(1)
        # whole-process stalls in a profiler capture: generation-2
        # collections as process/gc_pause events, until close()
        self._gc_hook = _GcPauseHook()
        gc.callbacks.append(self._gc_hook)
        self._decode_pool = ThreadPoolExecutor(
            max_workers=self._decode_slots,
            thread_name_prefix='serving-decode'
            + ('' if replica_id is None else '-%s' % replica_id))
        if lm_runtime is not None:
            from code2vec_tpu.serving.lm_scheduler import LMScheduler
            self._lm = LMScheduler(self, lm_runtime)
        if self._external:
            # a mesh replica owns no queue: the mesh's replica puller
            # is the dispatcher (serving/mesh.py)
            self._dispatcher: Optional[threading.Thread] = None
        else:
            self._dispatcher = threading.Thread(
                target=(self._dispatch_loop if self._lm is None
                        else self._lm.loop),
                daemon=True, name='serving-dispatch')
            self._dispatcher.start()

    # ---------------------------------------------------------- warmup
    def _warm_batches(self, bucket: int):
        """Device-shaped zero batches for one bucket — every wire shape
        the dispatcher can produce for it (programs key on shapes, not
        values; all-PAD rows are valid model input)."""
        contexts = self.config.MAX_CONTEXTS
        if self.wire == 'packed':
            token_pad = self.trainer._token_pad
            path_pad = self.trainer._path_pad
            for cap in self.capacities[bucket]:
                ctx = np.empty((self.data_axis, cap, 3), np.int32)
                ctx[..., 0] = token_pad
                ctx[..., 1] = path_pad
                ctx[..., 2] = token_pad
                yield (ctx, np.zeros((bucket,), np.int32),
                       np.zeros((bucket,), np.int32),
                       np.zeros((bucket,), np.float32))
        else:
            yield (np.zeros((bucket, contexts), np.int32),
                   np.zeros((bucket, contexts), np.int32),
                   np.zeros((bucket, contexts), np.int32),
                   np.zeros((bucket, contexts), np.float32),
                   np.zeros((bucket,), np.int32),
                   np.zeros((bucket,), np.float32))

    def warmup(self) -> 'ServingEngine':
        """Eagerly compile every (bucket x capacity x tier) program in
        the ladder, so steady-state ``submit`` traffic never compiles.
        Idempotent; auto-invoked by the first ``submit`` if skipped."""
        import jax
        with self._warm_lock:
            if self._warm:
                return self
            if self._lm is not None:
                self._warm_lm()
                self._warm = True
                return self
            with self._lock:
                params = self.params
            t0 = time.perf_counter()
            programs = 0
            # executables-bucket accounting (telemetry/memory.py): one
            # AOT memory_analysis per ladder program — an extra compile
            # each, so only for runs that opted into the telemetry
            # LAYER (config), not merely a registry something else
            # enabled in-process (steady state stays compile-free
            # either way; the guards count POST-warmup)
            measure_memory = (tele_core.enabled()
                              and getattr(self.config, 'TELEMETRY',
                                          False))
            ledger = memory_lib.ledger()
            try:
                for bucket in self.buckets:
                    for host_arrays in self._warm_batches(bucket):
                        arrays = mesh_lib.shard_batch(
                            host_arrays, self.mesh,
                            self.config.SHARD_CONTEXTS, direct=True)
                        capacity = (int(host_arrays[0].shape[1])
                                    if self.wire == 'packed' else 0)
                        for tier in self.tiers:
                            out = self.trainer.predict_step_placed(
                                params, arrays, tier=tier)
                            jax.block_until_ready(out)
                            programs += 1
                            if not measure_memory:
                                continue
                            info = self.trainer.predict_program_memory(
                                params, arrays, tier=tier)
                            if info is not None:
                                # keyed and owned by the TRAINER, not
                                # this engine: the compiled programs
                                # live in the trainer's jit caches, so
                                # they survive engine.close() and are
                                # shared by every engine over the same
                                # trainer — trainer-keyed entries match
                                # that lifetime exactly and re-warm as
                                # a replace, never a double-count
                                ledger.register(
                                    'executables',
                                    '%s/%s/b%d/c%d'
                                    % (self.trainer._mem_key, tier,
                                       bucket, capacity),
                                    (info['generated_code_bytes']
                                     + info['temp_bytes']),
                                    kind='executable',
                                    owner=self.trainer,
                                    attrs={'tier': tier,
                                           'bucket': bucket,
                                           'capacity': capacity,
                                           **info})
            except Exception as exc:
                # OOM forensics at the warm-compile boundary: a ladder
                # that does not fit dumps attribution before dying
                ledger.note_oom(exc, 'serving.warmup')
                raise
            warm_s = time.perf_counter() - t0
            if tele_core.enabled():
                reg = self._mirror
                reg.gauge('serving/warmup_s').set(warm_s)
                reg.gauge('serving/programs_warm').set(programs)
            self.log('serving: warmed %d programs (buckets %s x tiers %s, '
                     '%s wire) in %.1fs'
                     % (programs, list(self.buckets), list(self.tiers),
                        self.wire, warm_s))
            self._warm = True
        return self

    def _warm_lm(self) -> None:
        """A language model's warm-up: one program a chunk bucket and the
        decode-only one."""
        t0 = time.perf_counter()
        runtime = self._lm.runtime
        try:
            programs = self._lm.warm()
        except Exception as exc:
            memory_lib.ledger().note_oom(exc, 'serving.warmup')
            raise
        warm_s = time.perf_counter() - t0
        if tele_core.enabled():
            self._mirror.gauge('serving/warmup_s').set(warm_s)
            self._mirror.gauge('serving/programs_warm').set(programs)
        self.log('serving: warmed %d step programs (chunk buckets %s, %d '
                 'decode rows) in %.1fs'
                 % (programs, list(runtime.buckets), runtime.slots, warm_s))

    # ------------------------------------------------------- admission
    def _shed_locked(self, rows: int, why: str) -> None:
        """Reject one submission at admission (typed, nothing enqueued)."""
        self.shed_total.inc()
        if tele_core.enabled():
            self._mirror.counter('serving/shed_total').inc()
        raise EngineOverloaded(
            'request shed at admission (%s): %d rows, %d rows queued, '
            'bound %s — retry against another replica or back off'
            % (why, rows, self._admitted_rows_locked(),
               self.queue_bound))

    def _admitted_rows_locked(self) -> int:
        return sum(self._pending_rows.values()) + self._reserved_rows

    def _admit(self, rows: int, tier: str,
               deadline_s: Optional[float], generate=None) -> str:
        """Admission control for one submission: bound check, drain
        estimate vs deadline, degradation ladder. Reserves ``rows``
        against the bound (released on enqueue or failure) and returns
        the EFFECTIVE tier to serve.  A ``generate`` request is booked
        with the step loop (serving/lm_scheduler.py), which refuses a
        context that could never be resident (for a session's turn,
        counted from the session's end); when it can be is the loop's
        question."""
        with self._cond:
            if self._closed:
                raise EngineClosed('ServingEngine is closed')
            if faults.maybe_fire('reject_all'):
                self._shed_locked(rows, 'reject_all drill')
            admitted = self._admitted_rows_locked()
            bound = self.queue_bound
            if bound_rejects(admitted, rows, bound):
                self._shed_locked(rows, 'queue bound')
            if deadline_s is not None and self._service_rows_per_s > 0:
                drain_s = (admitted + rows) / self._service_rows_per_s
                if drain_s > deadline_s:
                    self._shed_locked(
                        rows, 'drain estimate %.0fms > deadline %.0fms'
                        % (1e3 * drain_s, 1e3 * deadline_s))
            level, effective = overload_tier(
                admitted, rows, bound, self._overload_level, tier,
                self.tiers)
            if level != self._overload_level:
                self._overload_level = level
                self.overload_level_gauge.set(level)
                if tele_core.enabled():
                    self._mirror.gauge(
                        'serving/overload_level').set(level)
            if effective != tier:
                self.degraded_total.inc()
                if tele_core.enabled():
                    self._mirror.counter(
                        'serving/degraded_total').inc()
            if generate is not None:
                self._lm.reserve_locked(generate)
            self._reserved_rows += rows
            self._peak_rows = max(self._peak_rows,
                                  self._admitted_rows_locked())
            if tele_core.enabled():
                self._mirror.gauge(
                    'serving/queue_peak_rows').set(self._peak_rows)
        return effective

    # ---------------------------------------------------------- submit
    def submit(self, context_lines: Sequence[str],
               tier: str = 'topk',
               deadline_ms: Optional[float] = None,
               max_new_tokens: int = 1,
               return_logits: bool = False,
               session=None) -> Future:
        """Enqueue one prediction request (raw extractor/``.c2v`` context
        lines, like ``model.predict``). Returns a Future resolving to
        one ``ModelPredictionResults`` per line, in order. Requests
        larger than the top batch bucket are split transparently.

        ``deadline_ms`` overrides the engine's default SLO deadline for
        this request (0 = none): past it the request is shed at
        admission or expired in the queue with a typed error, never
        dispatched."""
        if self._external:
            raise RuntimeError(
                'this engine is a mesh replica (external dispatch); '
                'submit through its ServingMesh (serving/mesh.py)')
        if self._lm is not None:
            return self._submit_generate(context_lines, tier,
                                         max_new_tokens, return_logits,
                                         session)
        if session is not None:
            raise ValueError('sessions belong to the generate tier of a '
                             'language model')
        if tier not in self.tiers:
            raise ValueError('tier %r is not warmed on this engine '
                             '(tiers=%s)' % (tier, list(self.tiers)))
        # graftlint: disable=lock-discipline -- benign racy fast-fail: a close() racing past this read is re-checked under _cond before enqueue below
        if self._closed:
            raise EngineClosed('ServingEngine is closed')
        # ONE definition of request identity across engine + mesh +
        # memo key (data/reader.py canonicalize_contexts; idempotent at
        # fixed MAX_CONTEXTS — process_input_rows applies it too, so the
        # tokenizer and any caller-side key derivation can never
        # disagree).  MAX_CONTEXTS must reach the FIRST call: it
        # truncates in extraction order before the canonical sort.
        lines = canonicalize_contexts(context_lines,
                                      self.config.MAX_CONTEXTS)
        future: Future = Future()
        if not lines:
            future.set_result([])
            return future
        # graftlint: disable=lock-discipline -- benign racy read: warmup() is idempotent and re-checks _warm under _warm_lock
        if not self._warm:
            self.warmup()
        n = len(lines)
        if deadline_ms is None:
            deadline_s = self.deadline_s
        else:
            deadline_s = deadline_ms / 1e3 if deadline_ms > 0 else None
        self.requests_total.inc()
        if tele_core.enabled():
            self._mirror.counter('serving/requests_total').inc()
        trace = None
        if self._tracer is not None:
            trace = self._tracer.begin(
                'serving.request',
                attrs={'tier': tier, 'rows': n,
                       'deadline_ms': (1e3 * deadline_s
                                       if deadline_s else None)})
        requested_tier = tier
        t_admit0 = time.perf_counter()
        try:
            tier = self._admit(n, tier, deadline_s)  # raises typed on shed
        except EngineOverloaded as exc:
            if trace is not None:
                trace.event('serving.shed', attrs={'reason': str(exc)})
                trace.finish(status='shed')
                self._tracer.note_shed()
            raise
        except EngineClosed as exc:
            if trace is not None:
                trace.event('serving.closed', attrs={'reason': str(exc)})
                trace.finish(status='closed')
            raise
        t_admit1 = time.perf_counter()
        if trace is not None:
            trace.span_at('serving.admission', t_admit0, t_admit1)
            if tier != requested_tier:
                trace.event('serving.degraded',
                            attrs={'requested': requested_tier,
                                   'effective': tier})
        try:
            requests = tokenize_and_chunk(
                self.reader, lines, tier, future, deadline_s, trace,
                t_admit1, self.buckets[-1])
        except BaseException as exc:
            with self._cond:
                self._reserved_rows -= n
            if trace is not None:
                trace.finish(status='error', reason=repr(exc))
            raise
        # how often the native tokenizer engages, against the fallback
        native = self.reader.native
        (self.tokenize_native_rows_total if native
         else self.tokenize_fallback_rows_total).inc(n)
        if tele_core.enabled():
            self._mirror.counter(
                'serving/tokenize_native_rows_total' if native
                else 'serving/tokenize_fallback_rows_total').inc(n)
        with self._cond:
            self._reserved_rows -= n
            if self._closed:
                closed_exc = EngineClosed('ServingEngine is closed')
            else:
                closed_exc = None
                for request in requests:
                    if request.trace is not None:
                        request.queue_span = request.trace.span(
                            'serving.queue_wait',
                            parent=request.span_parent,
                            t0=request.t_enqueue)
                    self._queues[tier].append(request)
                    self._pending_rows[tier] += request.rows
                self._set_queue_depth_locked()
                self._cond.notify_all()
        if closed_exc is not None:
            if trace is not None:
                trace.event('serving.closed',
                            attrs={'reason': str(closed_exc)})
                trace.finish(status='closed')
            raise closed_exc
        return future

    def _submit_generate(self, prompt_ids, tier: str, max_new_tokens: int,
                         return_logits: bool, session=None) -> Future:
        """``submit`` of a language model's engine: ``prompt_ids`` (token
        ids) in, a Future of a ``GenerationResult`` out: ``max_new_tokens``
        greedy ids, always run to their end, and with ``return_logits``
        the float32 logits each was picked from.  With ``session`` (any
        hashable id) the request is a turn of that session: its cache stays
        resident at delivery, and the turn starts at the session's end
        (the token the earlier turn generated last, then ``prompt_ids``),
        not at position 0; ``close_session`` returns the cache."""
        from code2vec_tpu.serving.lm_scheduler import GenerateRequest
        if tier not in self.tiers:
            raise ValueError('tier %r is not served by this engine '
                             '(tiers=%s)' % (tier, list(self.tiers)))
        prompt = np.ascontiguousarray(prompt_ids, dtype=np.int32)
        self._lm.check_request(prompt, max_new_tokens)
        # graftlint: disable=lock-discipline -- benign racy read: warmup() is idempotent and re-checks _warm under _warm_lock
        if not self._warm:
            self.warmup()
        self.requests_total.inc()
        if tele_core.enabled():
            self._mirror.counter('serving/requests_total').inc()
        request = GenerateRequest(prompt, int(max_new_tokens),
                                  bool(return_logits), session)
        self._admit(1, tier, None, generate=request)
        with self._cond:
            self._reserved_rows -= 1
            if self._closed:
                raise EngineClosed('ServingEngine is closed')
            self._queues[tier].append(request)
            self._pending_rows[tier] += 1
            self._set_queue_depth_locked()
            self._cond.notify_all()
        return request.future

    def close_session(self, session) -> bool:
        """Returns a resident session's cache (pages, pooled keys,
        recurrent states) to the pools; False if the engine holds no such
        session.  Raises while a turn of it is submitted and undelivered.
        Closing a session whose turns fail with ``SessionLost`` (True)
        acknowledges the loss: its id can be used anew."""
        if self._lm is None:
            raise ValueError('sessions belong to a language model\'s engine')
        return self._lm.close_session(session)

    def lm_runtime(self):
        """A language model's runtime (``serving/lm_scheduler.py::
        LMRuntime``: weights, cache pools, step programs); None for
        code2vec."""
        return self._lm.runtime if self._lm is not None else None

    def lm_step_log(self) -> list:
        """What every finished step of a language model carried
        (``LMScheduler.step_log``); empty for code2vec."""
        return self._lm.step_log() if self._lm is not None else []

    def lm_request_log(self) -> list:
        """When every delivered request of a language model passed each
        stage (``LMScheduler.request_log``); empty for code2vec."""
        return self._lm.request_log() if self._lm is not None else []

    def predict(self, context_lines: Sequence[str], tier: str = 'topk',
                timeout: Optional[float] = None) -> list:
        """Synchronous ``submit().result()`` convenience."""
        return self.submit(context_lines, tier).result(timeout)

    # -------------------------------------------------------- neighbors
    def attach_index(self, index) -> 'ServingEngine':
        """Arm ``submit_neighbors`` with a k-NN index over the corpus
        (code2vec_tpu/index/, INDEX.md). The engine must have the
        'vectors' tier warmed — neighbor queries ride it through the
        same micro-batching dispatcher as every other tier.

        Memory accounting (telemetry/memory.py): the attach path's
        HBM budget gate lives in the index constructors — ``ExactIndex``
        / ``IVFIndex`` predict their device footprint and fail typed
        (``MemoryBudgetExceeded``) BEFORE placing anything, so by the
        time an index reaches here it is both resident and
        ledger-registered under the ``index`` bucket."""
        if 'vectors' not in self.tiers:
            raise ValueError(
                "submit_neighbors needs the 'vectors' tier warmed on "
                'this engine (tiers=%s); build it with '
                "tiers=('vectors', ...) or SERVING_WARM_TIERS."
                % list(self.tiers))
        self._index = index
        return self

    def submit_neighbors(self, context_or_vectors, k: Optional[int] = None
                         ) -> Future:
        """One warm round-trip from code to its nearest corpus methods:
        raw context lines (like ``submit``) ride the micro-batched
        'vectors' tier, and the resulting code vectors feed the attached
        index; an ``(n, D)`` vector array skips the predict leg. Returns
        a Future of one ``NeighborResult`` per input row, in order."""
        index = self._index
        if index is None:
            raise RuntimeError('no index attached — call '
                               'attach_index(load_index(...)) first '
                               '(code2vec_tpu/index/service.py)')
        k = k if k is not None else self.config.INDEX_NEIGHBORS_K
        from code2vec_tpu.index.service import neighbors_from_search
        outer: Future = Future()
        if isinstance(context_or_vectors, np.ndarray):
            vectors = np.atleast_2d(context_or_vectors)

            def lookup():
                try:
                    values, indices = self._search_index(index, vectors,
                                                         k)
                    _resolve(outer, neighbors_from_search(
                        values, indices, index.labels))
                except BaseException as exc:
                    if not outer.done():
                        outer.set_exception(exc)
            self._decode_pool.submit(lookup)
            return outer
        inner = self.submit(context_or_vectors, tier='vectors')

        def chain(done: Future) -> None:
            # runs on the decode worker that resolved `inner` — the
            # index search stays off the dispatcher thread
            try:
                results = done.result()
                if not results:
                    _resolve(outer, [])
                    return
                vectors = np.stack([r.code_vector for r in results])
                values, indices = self._search_index(index, vectors, k)
                _resolve(outer, neighbors_from_search(
                    values, indices, index.labels))
            except BaseException as exc:
                if not outer.done():
                    outer.set_exception(exc)
        inner.add_done_callback(chain)
        return outer

    def _search_index(self, index, vectors: np.ndarray, k: int):
        """``index.search`` on the calling decode worker, as a phase: the
        callback holds no request trace, so the span is a trace of its
        own (head-sampled: it fires once a neighbour query)."""
        attrs = {'rows': len(vectors), 'k': k}
        with tracing_lib.phase('serving.index_search', **attrs) as search:
            found = index.search(vectors, k)
        if self._tracer is not None:
            self._tracer.single(search.name, attrs=attrs, t0=search.t0,
                                t1=search.t1, always=False)
        return found

    def predict_neighbors(self, context_or_vectors,
                          k: Optional[int] = None,
                          timeout: Optional[float] = None) -> list:
        """Synchronous ``submit_neighbors().result()`` convenience."""
        return self.submit_neighbors(context_or_vectors, k).result(timeout)

    # -------------------------------------------------------- rollover
    def _check_rollover_clear_locked(self) -> None:
        if self._closed:
            raise EngineClosed('ServingEngine is closed')
        if self._rollover is not None:
            raise RuntimeError(
                'a rollover is already in flight (step %s); await '
                'its handle first' % self._rollover.step)

    def load_params(self, source, canary_batches: Optional[int] = None,
                    min_agreement: Optional[float] = None) -> Future:
        """Canaried zero-downtime checkpoint rollover (SERVING.md).

        ``source`` is a retained checkpoint step (int), a model path
        (str) — both resolved through the engine's param source (wired
        by ``model.serving_engine()``) — or a placed params pytree.
        Candidate params must match the serving set's shapes and
        shardings, so every shadow dispatch reuses the warm ladder:
        a live rollover compiles NOTHING.

        With ``canary_batches > 0`` (default ``SERVING_CANARY_BATCHES``)
        the next live micro-batches are shadow-scored against both param
        sets; the swap happens atomically once top-1 agreement over the
        canaried rows clears ``min_agreement`` (default
        ``SERVING_CANARY_AGREEMENT``), else the candidate is dropped.
        ``canary_batches == 0`` swaps immediately.

        Returns a Future resolving to the rollover report dict
        (``{'swapped': bool, 'agreement': ..., ...}``); the canary needs
        live traffic to conclude. Fails with ``EngineClosed`` if the
        engine closes first."""
        handle: Future = Future()
        step: Optional[int] = None
        with self._cond:
            # advisory fast-fail before the checkpoint restore below —
            # a full Orbax read + device placement is too expensive to
            # spend on a call doomed by a closed engine or an in-flight
            # rollover; the locked re-check after the load stays
            # authoritative (the engine can close during the restore)
            self._check_rollover_clear_locked()
        if isinstance(source, (int, str)) and not isinstance(source, bool):
            if self._param_source is None:
                raise RuntimeError(
                    'load_params(%r): this engine has no param source — '
                    'build it via model.serving_engine(), or pass a '
                    'params pytree' % (source,))
            if isinstance(source, int):
                step = source
            # budget precheck (telemetry/memory.py): the candidate is a
            # FULL second param set resident next to the serving one for
            # the whole canary — predict its footprint from the abstract
            # shapes and fail typed BEFORE the restore allocates
            memory_lib.ledger().check_budget(
                self._params_nbytes,
                'serving rollover candidate (%r)' % (source,))
            params = self._param_source.load(source)
        else:
            params = source
        n_canary = (canary_batches if canary_batches is not None
                    else self.canary_batches)
        floor = (min_agreement if min_agreement is not None
                 else self.canary_agreement)
        if n_canary > 0 and all(t == 'vectors' for t in self.tiers):
            # the canary compares top-1 predictions, which the vectors
            # tier does not produce: an armed canary would never
            # conclude and wedge every later rollover
            raise RuntimeError(
                'canaried rollover needs a top-k-producing tier warmed '
                '(tiers=%s are vectors-only); pass canary_batches=0 to '
                'swap without a canary, or warm a topk tier'
                % list(self.tiers))
        report = None
        if n_canary > 0:
            # the armed canary's SECOND param-set copy is visible in the
            # ledger for exactly as long as it is resident. Registered
            # BEFORE arming: every path that can retire the candidate
            # (a decode worker concluding the canary, the dispatch-time
            # timeout, close) only becomes reachable once the entry
            # exists, so none of them can race a late register into a
            # phantom entry.
            memory_lib.ledger().register(
                'params', self._mem_prefix + '/candidate', params,
                owner=self, attrs={'step': step, 'state': 'candidate'})
        try:
            with self._cond:
                self._check_rollover_clear_locked()
                rollover = _Rollover(params, step, handle, n_canary,
                                     floor)
                if n_canary <= 0:
                    self.params = params
                    if step is not None:
                        self._params_step = step
                    report = rollover.report(True, 'no canary configured')
                else:
                    self._rollover = rollover
        except BaseException:
            if n_canary > 0:
                self._mem_drop_candidate()  # arming refused: not resident
            raise
        if report is not None:
            self._mem_swap_in(params, step)
            self._count_rollover(True, None)
            self.log('serving: params swapped without canary (step %s)'
                     % step)
            handle.set_result(report)
        else:
            self.log('serving: rollover armed (step %s): canarying %d '
                     'live batches, agreement floor %.2f'
                     % (step, n_canary, floor))
        return handle

    def adopt_params(self, params, step: Optional[int] = None) -> None:
        """Atomically swap the serving params with NO canary and NO
        ledger registration: the fleet-swap leg of a coordinated mesh
        rollover (serving/mesh.py), where the canary replica already
        validated this exact param set against live traffic and the
        mesh owns the ONE ledger entry for the shared arrays —
        per-replica re-registration of the same pytree would N-count
        it. Refuses while a rollover is in flight on this replica."""
        with self._cond:
            self._check_rollover_clear_locked()
            self.params = params
            if step is not None:
                self._params_step = step

    def _mem_swap_in(self, params, step: Optional[int]) -> None:
        """Ledger bookkeeping for a concluded swap: the candidate entry
        (if any) retires and the engine's serving entry re-registers
        with the new set — replacement releases the previously
        swapped-in set, so repeated rollovers hold a constant params
        footprint (the leak drill in tests/test_memory_ledger.py)."""
        led = memory_lib.ledger()
        led.release('params', self._mem_prefix + '/candidate')
        led.register('params', self._mem_prefix + '/serving', params,
                     owner=self, attrs={'step': step, 'state': 'serving'})

    def _mem_drop_candidate(self) -> None:
        memory_lib.ledger().release('params',
                                    self._mem_prefix + '/candidate')

    def _count_rollover(self, swapped: bool,
                        agreement: Optional[float]) -> None:
        if swapped:
            self.rollover_total.inc()
        else:
            self.rollover_rollbacks_total.inc()
            if self._tracer is not None:
                # a rollback is a postmortem moment: dump the recent
                # traces (incl. the canary_shadow tallies) while fresh
                self._tracer.dump_flight('rollover_rollback')
        if agreement is not None:
            self.rollover_agreement.set(agreement)
        if tele_core.enabled():
            reg = self._mirror
            reg.counter('serving/rollover_total' if swapped
                        else 'serving/rollover_rollbacks_total').inc()
            if agreement is not None:
                reg.gauge('serving/rollover_agreement').set(agreement)

    def _observe_canary(self, rollover: _Rollover, agree_rows: int,
                        rows: int, primary_s: float,
                        shadow_s: float) -> None:
        """Tally one shadow-scored batch; decide the rollover once the
        canary target is reached (decode-worker thread)."""
        decided = None
        with self._cond:
            if self._rollover is not rollover:
                return  # already decided (or cleared by close)
            rollover.batches += 1
            rollover.rows += rows
            rollover.agree_rows += agree_rows
            rollover.primary_fetch_s += primary_s
            rollover.shadow_fetch_s += shadow_s
            if rollover.batches >= rollover.target_batches:
                agreement = rollover.agree_rows / max(1, rollover.rows)
                swapped = agreement >= rollover.min_agreement
                if swapped:
                    self.params = rollover.params
                    if rollover.step is not None:
                        self._params_step = rollover.step
                self._rollover = None
                decided = (swapped, agreement)
        if decided is not None:
            swapped, agreement = decided
            if swapped:
                self._mem_swap_in(rollover.params, rollover.step)
            else:
                self._mem_drop_candidate()
            self._count_rollover(swapped, agreement)
            reason = ('canary passed' if swapped else
                      'agreement %.3f below floor %.2f'
                      % (agreement, rollover.min_agreement))
            self.log('serving: rollover %s (step %s): top-1 agreement '
                     '%.3f over %d rows in %d batches'
                     % ('SWAPPED' if swapped else 'ROLLED BACK',
                        rollover.step, agreement, rollover.rows,
                        rollover.batches))
            _resolve(rollover.handle, rollover.report(swapped, reason))

    def _fail_rollover(self, rollover: Optional[_Rollover],
                       exc: BaseException) -> None:
        if rollover is None:
            return
        with self._cond:
            if self._rollover is rollover:
                self._rollover = None
            elif rollover.handle.done():
                return
        self._mem_drop_candidate()
        if not rollover.handle.done():
            try:
                rollover.handle.set_exception(exc)
            except Exception:
                pass

    def follow_checkpoints(self, poll_secs: Optional[float] = None
                           ) -> 'ServingEngine':
        """Poll the checkpoint store for a newer retained step and roll
        it in through the canary (``--serve-follow-checkpoints``).
        Requires the engine's param source; idempotent."""
        if self._external:
            # the fleet must roll as ONE unit: N replica pollers racing
            # independent canaries is exactly the mode the mesh's
            # coordinated rollover exists to replace
            raise RuntimeError(
                'this engine is a mesh replica; --serve-follow-'
                'checkpoints runs at the mesh '
                '(ServingMesh.follow_checkpoints, serving/mesh.py)')
        if self._param_source is None:
            raise RuntimeError('follow_checkpoints needs a param source '
                               '(build the engine via '
                               'model.serving_engine())')
        poll = (poll_secs if poll_secs is not None
                else self.config.SERVE_FOLLOW_CHECKPOINTS_SECS)
        if poll <= 0:
            raise ValueError('follow_checkpoints needs poll_secs > 0 '
                             '(got %r)' % poll)
        with self._lock:
            # check-and-assign under the lock: concurrent calls must not
            # each see None and start duplicate poller threads (close()
            # only joins the one stored in _follow_thread)
            if self._closed:
                raise EngineClosed('ServingEngine is closed')
            if self._follow_thread is not None:
                return self
            self._follow_thread = threading.Thread(
                target=self._follow_loop, args=(poll,), daemon=True,
                name='serving-follow')
            self._follow_thread.start()
        return self

    def _follow_loop(self, poll_secs: float) -> None:
        attempted: Optional[int] = None  # this thread's memory only
        while not self._follow_stop.wait(poll_secs):
            try:
                newest = self._param_source.newest_step()
                with self._cond:
                    if self._closed:
                        return
                    busy = self._rollover is not None
                    current = self._params_step
                if newest is None or busy:
                    continue
                if attempted is not None and newest <= attempted:
                    continue  # don't hot-loop a rolled-back step
                if current is not None and newest <= current:
                    continue
                self.log('serving: follow-checkpoints found step %d; '
                         'starting canaried rollover' % newest)
                self.load_params(newest)
                # marked only once the restore+arm succeeded: a transient
                # load failure (poll racing an in-progress checkpoint
                # write, a filesystem blip) leaves the step eligible for
                # the next poll, while a canary rollback — which resolves
                # the handle, not this call — still won't be hot-looped
                attempted = newest
            except EngineClosed:
                return
            except Exception as exc:  # poller must survive blips
                self.log('serving: follow-checkpoints poll failed: %s'
                         % exc)

    def _queued_locked(self) -> bool:
        return any(self._queues[t] for t in PREDICT_TIERS)

    def _set_queue_depth_locked(self) -> None:
        depth = sum(len(q) for q in self._queues.values())
        self.queue_depth.set(depth)
        if tele_core.enabled():
            self._mirror.gauge('serving/queue_depth').set(depth)

    # ------------------------------------------------------ dispatcher
    def _dispatch_loop(self) -> None:
        while True:
            abandoned: List[_Request] = []
            with self._cond:
                if not self._closed and not self._queued_locked():
                    with tracing_lib.phase('serving.no_work'):
                        while not self._closed and \
                                not self._queued_locked():
                            self._cond.wait()
                if self._closed and not self._drain:
                    # fail-fast close: queued work is going nowhere —
                    # every undispatched future fails typed below (the
                    # drain=True path instead falls through and keeps
                    # serving until the queues are empty)
                    for t in PREDICT_TIERS:
                        abandoned.extend(self._queues[t])
                        self._queues[t].clear()
                        self._pending_rows[t] = 0
                    self._set_queue_depth_locked()
                done = self._closed and not self._queued_locked()
            if abandoned or done:
                for request in abandoned:
                    request.fail(EngineClosed(
                        'ServingEngine closed with the request still '
                        'queued (close(drain=True) serves the queue '
                        'first)'))
                if done:
                    return
                continue
            with self._cond:
                if not self._queued_locked():
                    continue  # raced a drain-close or expiry
                # serve the tier whose head request has waited longest
                tier = min(
                    (t for t in PREDICT_TIERS if self._queues[t]),
                    key=lambda t: self._queues[t][0].t_enqueue)
                deadline = (self._queues[tier][0].t_enqueue
                            + self.max_delay_s)
                max_bucket = self.buckets[-1]
                early = False
                with tracing_lib.phase('serving.coalesce'):
                    while not self._closed:
                        remaining = deadline - time.perf_counter()
                        if remaining <= 0 or \
                                self._pending_rows[tier] >= max_bucket:
                            break
                        if self._in_flight < self._decode_slots:
                            # a free decode slot: holding the head
                            # request buys nothing.  With every slot
                            # taken a dispatched batch would wait in
                            # hand-off anyway: that wait is the
                            # coalescing window, and the slot's release
                            # (_decode_in_slot) wakes this loop
                            early = True
                            break
                        self._cond.wait(remaining)
                if self._closed and not self._drain:
                    # a fail-fast close() landed during coalescing:
                    # the requests being gathered must fail typed at
                    # the top of the loop, not ride a final dispatch
                    continue
                taken: List[_Request] = []
                expired: List[_Request] = []
                rows = 0
                now = time.perf_counter()
                queue = self._queues[tier]
                while queue and rows + queue[0].rows <= max_bucket:
                    request = queue.popleft()
                    if request.t_deadline is not None \
                            and now >= request.t_deadline:
                        # expire instead of dispatching dead work: the
                        # client's SLO already passed while it queued
                        expired.append(request)
                        self._pending_rows[tier] -= request.rows
                        continue
                    taken.append(request)
                    rows += request.rows
                self._pending_rows[tier] -= rows
                self._set_queue_depth_locked()
            for request in expired:
                self.expired_total.inc()
                if tele_core.enabled():
                    self._mirror.counter(
                        'serving/expired_total').inc()
                request.fail(DeadlineExceeded(
                    'request expired after %.0fms in queue (SLO '
                    'deadline %.0fms)'
                    % (1e3 * (now - request.t_enqueue),
                       1e3 * (request.t_deadline - request.t_enqueue))))
            if taken:
                try:
                    self._dispatch_batch(tier, taken, rows, early)
                except BaseException as exc:  # keep the dispatcher alive
                    # OOM forensics at the jit-dispatch boundary
                    # (telemetry/memory.py): a RESOURCE_EXHAUSTED here
                    # dumps the attribution ledger before the typed
                    # failure reaches the callers
                    memory_lib.ledger().note_oom(exc, 'serving.dispatch')
                    for request in taken:
                        request.fail(exc)

    def dispatch_external(self, tier: str, taken: List[_Request],
                          rows: int) -> None:
        """Mesh-replica dispatch hook (serving/mesh.py): ship one
        coalesced micro-batch the mesh's shared front queue popped.
        Same failure contract as the internal dispatcher — an exception
        fails every member request typed and dumps OOM forensics — but
        it also RE-RAISES so the caller's replica breaker can count the
        failure and weight this replica out of dispatch."""
        try:
            self._dispatch_batch(tier, taken, rows)
        except BaseException as exc:
            memory_lib.ledger().note_oom(exc, 'serving.dispatch')
            for request in taken:
                request.fail(exc)
            raise

    def _pack_padded(self, padded: Batch, bucket: int) -> Tuple[tuple, int]:
        """Pad-complete plane batch -> packed wire arrays on a capacity
        rung from the warm ladder. Returns (arrays, capacity)."""
        ctx_rows, lengths = packed_lib.ragged_from_planes(
            padded.source, padded.path, padded.target, padded.mask)
        per_shard = int(packed_lib.shard_totals(
            lengths, self.data_axis).max(initial=0))
        capacity = pick_bucket(per_shard, self.capacities[bucket])
        ctx = packed_lib.pack_ragged(
            ctx_rows, lengths, self.trainer._token_pad,
            self.trainer._path_pad, data_shards=self.data_axis,
            capacity_minimum=capacity)
        return (ctx, lengths, np.ascontiguousarray(padded.label),
                np.ascontiguousarray(padded.weight)), capacity

    def _dispatch_batch(self, tier: str, taken: List[_Request],
                        rows: int, early: bool = False) -> None:
        """``early``: the dispatcher closed this batch before its
        deadline because a decode slot was free (never set by a mesh's
        puller, whose coalescing is serving/frontqueue.py's)."""
        t0 = time.perf_counter()
        traced = [r for r in taken if r.trace is not None]
        for request in traced:
            if request.queue_span is not None:
                request.trace.end(request.queue_span, t0)
                request.queue_span = None
        stall = None
        if faults.maybe_fire('slow_dispatch'):
            # deterministic overload: the queue keeps filling while the
            # dispatcher stalls here, driving shed/expiry/degrade drills
            with tracing_lib.phase('serving.stall') as stall:
                time.sleep(faults.SLOW_DISPATCH_SECONDS)
        seq = next(self._batch_seq)
        bucket = pick_bucket(rows, self.buckets)
        with tracing_lib.phase('serving.pack', batch=seq, rows=rows,
                               bucket=bucket, requests=len(taken),
                               tier=tier, early=int(early)) as pack:
            merged = (taken[0].batch if len(taken) == 1 else
                      PathContextReader._concat([r.batch for r in taken]))
            padded = self.reader.pad_batch_to(merged, bucket)
            if self.wire == 'packed':
                host_arrays, capacity = self._pack_padded(padded, bucket)
            else:
                host_arrays, capacity = padded.device_arrays(), 0
        with tracing_lib.phase('serving.h2d', batch=seq) as h2d:
            arrays = mesh_lib.shard_batch(host_arrays, self.mesh,
                                          self.config.SHARD_CONTEXTS,
                                          direct=True)
        stale = None
        with self._lock:
            params = self.params
            rollover = self._rollover
            if rollover is not None and self.canary_timeout_s > 0 and \
                    time.perf_counter() - rollover.t_armed \
                    >= self.canary_timeout_s:
                # checked on EVERY tier's dispatches: vectors-only
                # traffic produces no top-1 comparisons, so a canary
                # armed on a mixed-tier engine could otherwise wedge
                # all later rollovers forever
                self._rollover = None
                stale, rollover = rollover, None
        if stale is not None:
            self._mem_drop_candidate()
            self._count_rollover(False, None)
            self.log('serving: rollover ROLLED BACK (step %s): canary '
                     'timed out after %.0fs with %d/%d batches scored '
                     '(no top-1-producing traffic?)'
                     % (stale.step, self.canary_timeout_s,
                        stale.batches, stale.target_batches))
            _resolve(stale.handle, stale.report(
                False, 'canary timed out after %.0fs'
                % self.canary_timeout_s))
        # async dispatch: returns with device futures; the decode pool
        # blocks on them, the dispatcher goes back to coalescing.  The
        # enqueue itself is serialized across engines (mesh replicas):
        # see _DISPATCH_ENQUEUE_LOCK.  The phase is the named host lane
        # of a profiler capture (OBSERVABILITY.md), next to the
        # trainer's StepTraceAnnotation scopes
        with tracing_lib.phase('serving.dispatch',
                               batch=seq) as dispatch, \
                _DISPATCH_ENQUEUE_LOCK:
            out = self.trainer.predict_step_placed(params, arrays,
                                                   tier=tier)
            shadow_out = None
            if rollover is not None and tier != 'vectors':
                # canary shadow: same arrays, same shapes/shardings —
                # the warm program is reused, so a live rollover never
                # compiles (predict programs are never donated:
                # re-feeding `arrays` is safe)
                shadow_out = self.trainer.predict_step_placed(
                    rollover.params, arrays, tier=tier)
        t_disp = dispatch.t1
        if traced:
            t_head = min(request.t_enqueue for request in taken)
            # the pack span carries the dispatch attribution the latency
            # report keys on: bucket, effective tier, and — on a mesh —
            # WHICH replica served the batch (scripts/latency_report.py
            # per-replica columns)
            pack_attrs = {'bucket': bucket, 'capacity': capacity,
                          'batch_rows': rows, 'tier': tier}
            if self.replica_id is not None:
                pack_attrs['replica'] = self.replica_id
            for request in traced:
                tr, parent = request.trace, request.span_parent
                tr.span_at('serving.coalesce', t_head, t0, parent=parent,
                           attrs={'requests': len(taken),
                                  'overlaps': 'queue_wait'})
                if stall is not None:
                    stall.span(tr, parent, {'fault': 'slow_dispatch'},
                               t0=t0)
                pack.span(tr, parent, pack_attrs)
                # from the previous phase's end: the chain tiles (the
                # params read between h2d and dispatch is microseconds)
                h2d.span(tr, parent, t0=pack.t1)
                dispatch.span(tr, parent,
                              {'shadow': shadow_out is not None},
                              t0=h2d.t1)
        dispatch_s = t_disp - t0
        self.dispatch_timer.record(dispatch_s)
        self.batches_total.inc()
        if early:
            self.early_close_total.inc()
        self.fill_rate.set(rows / bucket)
        self.last_dispatch = {'bucket': bucket, 'rows': rows,
                              'capacity': capacity,
                              'requests': len(taken)}
        if tele_core.enabled():
            reg = self._mirror
            reg.timer('serving/dispatch_ms').record(dispatch_s)
            reg.counter('serving/batches_total').inc()
            if early:
                reg.counter('serving/early_close_total').inc()
            reg.gauge('serving/batch_fill_rate').set(rows / bucket)
        with self._lock:
            self._in_flight += 1
        self._decode_pool.submit(self._decode_in_slot, out, shadow_out,
                                 rollover, padded, taken, t_disp, t0, seq)

    # ----------------------------------------------------------- decode
    def _decode_in_slot(self, *batch) -> None:
        """A decode worker's task: ``_decode``, after which (delivered
        or failed) its slot is free, and a dispatcher holding a batch
        for one wakes."""
        try:
            self._decode(*batch)
        finally:
            with self._cond:
                self._in_flight -= 1
                self._cond.notify_all()

    def _decode(self, out: dict, shadow_out: Optional[dict],
                rollover: Optional[_Rollover], padded: Batch,
                taken: List[_Request],
                t_dispatched: Optional[float] = None,
                t_popped: Optional[float] = None,
                seq: int = 0) -> None:
        try:
            t0 = time.perf_counter()
            if t_dispatched is None:
                t_dispatched = t0
            if t_popped is None:
                t_popped = t0
            n_rows = sum(request.rows for request in taken)
            # fetch ONLY the keys the tier produced (np.asarray blocks on
            # the device value — this is the worker pool's job, never the
            # dispatcher's).  The wait for this worker is known here, so
            # it travels on the event that follows it
            with tracing_lib.phase(
                    'serving.fetch', batch=seq, rows=n_rows,
                    handoff_ms=1e3 * (t0 - t_dispatched)) as fetch:
                fetched = {key: np.asarray(value)
                           for key, value in out.items()}
            with tracing_lib.phase('serving.decode', batch=seq) as decode:
                results = decode_results(fetched, padded, n_rows,
                                         self.decode_table)
            t_fetch, t_decode = fetch.t1, decode.t1
            fetch_s = t_fetch - t0
            decode_s = t_decode - t0
            self.decode_timer.record(decode_s)
            if tele_core.enabled():
                self._mirror.timer(
                    'serving/decode_ms').record(decode_s)
            row = 0
            now = time.perf_counter()
            for request in taken:
                t_turn = time.perf_counter()
                with tracing_lib.phase(
                        'serving.deliver', batch=seq, rows=request.rows,
                        tier=request.tier,
                        queue_wait_ms=1e3 * (
                            t_popped - request.t_enqueue),
                        since_enqueue_ms=1e3 * (
                            t_turn - request.t_enqueue)) as deliver:
                    deliver_span = None
                    if request.trace is not None:
                        # record BEFORE deliver: the aggregate-completing
                        # chunk finishes the shared trace inside
                        # deliver(), and spans added after finish are
                        # dropped
                        tr, parent = request.trace, request.span_parent
                        tr.span_at('serving.handoff', t_dispatched, t0,
                                   parent=parent)
                        # device time comes from the EXISTING async fetch
                        # boundary (the blocking np.asarray above), never
                        # a new sync
                        dev = tr.span_at('serving.device_execute', t0,
                                         t_fetch, parent=parent)
                        fetch.span(tr, dev, t0=t0)
                        decode.span(tr, parent, t0=t_fetch)
                        # deliver opens at decode end, so the wait behind
                        # earlier requests' sequential deliveries in this
                        # loop is attributed, not a phase gap
                        deliver_span = deliver.span(
                            tr, parent, {'rows': request.rows},
                            t0=t_decode)
                    request.deliver(results[row:row + request.rows])
                row += request.rows
                latency = now - request.t_enqueue
                self.latency.record(latency)
                if tele_core.enabled():
                    self._mirror.timer(
                        'serving/latency_ms').record(latency)
                if request.trace is not None:
                    request.trace.end(deliver_span)
                    request.finish_trace()
            self._note_service(n_rows, taken)
            if self._on_batch_done is not None:
                # mesh replica-table hook: in-flight window release,
                # fleet drain estimate, dispatch-share accounting
                self._on_batch_done(self, n_rows, taken, True)
        except BaseException as exc:
            # async dispatches surface device OOM at this fetch
            # boundary — same forensics as the dispatch side
            memory_lib.ledger().note_oom(exc, 'serving.decode')
            for request in taken:
                request.fail(exc)
            if self._on_batch_done is not None:
                try:
                    self._on_batch_done(
                        self, sum(r.rows for r in taken), taken, False)
                except Exception:
                    pass  # the failure path must stay failure-proof
            return
        if shadow_out is not None:
            # canary tally AFTER the callers got their answers: the
            # shadow fetch never adds to request latency
            try:
                t1 = time.perf_counter()
                shadow_top = np.asarray(shadow_out['topk_indices'])
                shadow_s = time.perf_counter() - t1
                primary_top = fetched['topk_indices']
                agree = int(np.sum(primary_top[:n_rows, 0]
                                   == shadow_top[:n_rows, 0]))
                if self._tracer is not None:
                    self._tracer.single(
                        'serving.canary_shadow',
                        attrs={'step': rollover.step, 'rows': n_rows,
                               'agree_rows': agree,
                               'shadow_fetch_ms': 1e3 * shadow_s},
                        t0=t1, t1=t1 + shadow_s)
                self._observe_canary(rollover, agree, n_rows,
                                     fetch_s, shadow_s)
            except BaseException as exc:
                self._fail_rollover(rollover, exc)

    def _note_service(self, rows: int, taken: List[_Request]) -> None:
        """Feed the drain estimate with observed THROUGHPUT: rows
        delivered over a sliding window of recent batch completions.
        Unlike rows/sojourn this excludes queue wait (which scales with
        queue depth and would under-report a deep-but-draining queue by
        that factor, shedding deadlines the engine could in fact meet)
        and credits dispatch/decode pipelining; unlike a per-completion
        inter-arrival rate it aggregates across parallel decode
        workers, whose near-simultaneous completions would otherwise
        inflate the estimate by orders of magnitude and admit deadlines
        the queue cannot meet. Until the window spans a measurable
        interval (first batch, or right after an idle gap evicted it)
        the estimate seeds from batch sojourn — biased low, so a shed
        too many, never a deadline promised and missed."""
        oldest = min(request.t_enqueue for request in taken)
        with self._lock:
            self._service_window_rows, self._service_rows_per_s = \
                note_service_window(
                    self._service_window, self._service_window_rows,
                    self._service_rows_per_s, rows, oldest)

    # -------------------------------------------------------- lifecycle
    def stats(self) -> Dict[str, object]:
        """Snapshot of the engine's standalone instruments (latency
        percentiles come from the windowed Timer snapshots)."""
        with self._lock:
            peak_rows = self._peak_rows
            params_step = self._params_step
        return {
            'replica': self.replica_id,
            'requests_total': self.requests_total.snapshot(),
            'tokenize_native_rows_total':
                self.tokenize_native_rows_total.snapshot(),
            'tokenize_fallback_rows_total':
                self.tokenize_fallback_rows_total.snapshot(),
            'batches_total': self.batches_total.snapshot(),
            'early_close_total': self.early_close_total.snapshot(),
            'queue_depth': self.queue_depth.snapshot(),
            'batch_fill_rate': self.fill_rate.snapshot(),
            'latency_ms': self.latency.snapshot(),
            'dispatch_ms': self.dispatch_timer.snapshot(),
            'decode_ms': self.decode_timer.snapshot(),
            'last_dispatch': self.last_dispatch,
            'shed_total': self.shed_total.snapshot(),
            'expired_total': self.expired_total.snapshot(),
            'degraded_total': self.degraded_total.snapshot(),
            'overload_level': self.overload_level_gauge.snapshot(),
            'queue_peak_rows': peak_rows,
            'rollover_total': self.rollover_total.snapshot(),
            'rollover_rollbacks_total':
                self.rollover_rollbacks_total.snapshot(),
            'params_step': params_step,
            'tracing': (self._tracer.stats()
                        if self._tracer is not None else None),
            'lm': self._lm.stats() if self._lm is not None else None,
        }

    def close(self, drain: bool = False) -> None:
        """Stop the engine: new ``submit`` calls raise ``EngineClosed``.

        Default (fail-fast) close fails every still-queued request's
        future with a typed ``EngineClosed`` — nothing is left
        unresolved, and this replica stops serving immediately (the
        micro-batches already dispatched still deliver their results).
        ``close(drain=True)`` instead serves everything already admitted
        before stopping. An armed rollover's handle fails with
        ``EngineClosed`` either way. Idempotent; a second call (any
        mode) just waits for the first shutdown to finish."""
        with self._cond:
            already = self._closed
            if not already:
                self._closed = True
                self._drain = drain
            rollover, self._rollover = self._rollover, None
            self._cond.notify_all()
        self._follow_stop.set()
        if rollover is not None and not rollover.handle.done():
            try:
                rollover.handle.set_exception(EngineClosed(
                    'ServingEngine closed mid-canary (step %s)'
                    % rollover.step))
            except Exception:
                pass
        # every closer (not just the first) joins: a concurrent second
        # close() must not return while the dispatcher/decode workers
        # are still draining (join and shutdown(wait=True) are both
        # safe to call from multiple threads)
        follow = self._follow_thread
        if follow is not None:
            follow.join()
        if self._dispatcher is not None:
            self._dispatcher.join()
        self._decode_pool.shutdown(wait=True)
        if self._gc_hook in gc.callbacks:  # a second close() finds none
            gc.callbacks.remove(self._gc_hook)
        # retire this engine's ledger entries: the params it swapped in
        # and an armed candidate (release is no-op-safe, so racing the
        # weakref finalizer is fine). The warm-ladder executables stay
        # registered on purpose — they live in the TRAINER's jit
        # caches, which a closed engine does not free
        led = memory_lib.ledger()
        led.release('params', self._mem_prefix + '/serving')
        led.release('params', self._mem_prefix + '/candidate')
        if self._tracer is not None and self._owns_tracer:
            # dispatcher + decode pool have drained: every in-flight
            # trace is already finished (delivered or typed-failed), so
            # the close dump is complete, never truncated.  An injected
            # tracer is NOT closed: its owner (the mesh sharing it
            # across replicas, a bench reading it afterwards) decides
            # when the fleet is actually done — a retiring replica must
            # not end the whole fleet's flight recorder
            self._tracer.close()

    def __enter__(self) -> 'ServingEngine':
        return self

    def __exit__(self, *exc) -> None:
        self.close()
