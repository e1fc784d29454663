"""Iteration-level batching of a decoder's ``generate`` tier: the loop the
serving engine's dispatcher thread runs when its model is a language model.

Every iteration plans one **step** and enqueues it: one token for every
sequence that is decoding, and a chunk of the prompt of the oldest
sequence still in prefill, under the token budget the step's shape gives
(``LM_MAX_SEQS`` decode rows and one of ``LM_CHUNK_BUCKETS`` prompt
tokens).  Few programs: one a chunk bucket and one with no chunk, all
compiled in ``warm()``.

The host never waits for a sampled token before planning the next step: a
decoding sequence's input is read on the device from the previous step's
output (``models/decoder.py::make_step``), and since ``max_new_tokens`` is
always run to its end the plan depends on nothing the device computes.  So
step *n + 1* is enqueued while step *n* runs, then step *n*'s tokens are
fetched, first tokens and finished requests are noted and delivered, and
their cache is returned.

Requests reach the loop through the engine's own ``submit`` -> ``_admit``
-> queue (``serving/engine.py``); a request leaves the queue for the
running set when the cache manager (``serving/lm_cache.py``) has a ring
slot and pages for its whole context, in arrival order.
"""
from __future__ import annotations

import collections
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from code2vec_tpu.models import decoder as decoder_lib
from code2vec_tpu.serving import lm_cache
from code2vec_tpu.serving.errors import EngineClosed
from code2vec_tpu.telemetry import core as tele_core
from code2vec_tpu.telemetry import tracing as tracing_lib
from code2vec_tpu.telemetry.core import Counter, Gauge, Timer

GENERATE_TIER = 'generate'


class GenerationResult(NamedTuple):
    """What a ``generate`` request's future resolves to."""
    token_ids: np.ndarray               # int32 [max_new_tokens], greedy
    logits: Optional[np.ndarray]        # float32 [max_new_tokens, vocab]


class GenerateRequest:
    """One queue entry of the ``generate`` tier."""

    __slots__ = ('prompt', 'max_new_tokens', 'return_logits', 'future',
                 'rows', 'tier', 't_enqueue', 'trace')

    def __init__(self, prompt: np.ndarray, max_new_tokens: int,
                 return_logits: bool):
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.return_logits = return_logits
        self.future: Future = Future()
        self.rows = 1
        self.tier = GENERATE_TIER
        self.t_enqueue = time.perf_counter()
        self.trace = None

    @property
    def context(self) -> int:
        """Positions its keys and values take: the last generated token
        is never fed back."""
        return int(self.prompt.shape[0]) + self.max_new_tokens - 1

    def fail(self, exc: BaseException) -> None:
        if not self.future.done():
            self.future.set_exception(exc)


class _Sequence:
    """A request in the running set."""

    __slots__ = ('request', 'lease', 'prefilled', 'decoded', 'last_out',
                 'generated', 'logit_rows', 't_admitted')

    def __init__(self, request: GenerateRequest, lease: lm_cache.Lease):
        self.request = request
        self.lease = lease
        self.prefilled = 0      # prompt tokens whose step is enqueued
        self.decoded = 0        # decode steps enqueued
        self.last_out = -1      # its row of the newest step's outputs
        self.generated: List[int] = []
        self.logit_rows: list = []
        self.t_admitted = time.perf_counter()

    @property
    def in_prefill(self) -> bool:
        return self.prefilled < self.request.prompt.shape[0]

    @property
    def decoding(self) -> bool:
        return not self.in_prefill and \
            self.decoded < self.request.max_new_tokens - 1


class _Step(NamedTuple):
    """A step that is enqueued and not yet fetched."""
    seq: int
    next_ids: object            # device array
    counts: object              # device array [layers, experts]
    harvest: List[Tuple[_Sequence, int]]    # who reads which output row
    decode_rows: int
    prefill_tokens: int
    t_enqueued: float
    bucket: int
    decode_positions: np.ndarray    # the position each decode row is at
    chunk_first: int                # the chunk's first position


def pack_layout(shape: decoder_lib.StepShape
                ) -> Dict[str, Tuple[int, int, tuple]]:
    """{name: (offset, size, shape)} of a step's int32 inputs in the one
    flat array the host sends; ``''`` holds the array's length."""
    layout, at = {}, 0
    for name, dims in decoder_lib.batch_shapes(shape).items():
        size = int(np.prod(dims))
        layout[name] = (at, size, dims)
        at += size
    layout[''] = (at, 0, ())
    return layout


def unpack_batch(packed, layout) -> dict:
    """The named arrays of a flat step input (a numpy or a traced array:
    views, no copies)."""
    return {name: packed[at:at + size].reshape(dims)
            for name, (at, size, dims) in layout.items() if name}


class LMRuntime:
    """The model on the device: configuration, weights, the two pools and
    the step programs, one a shape."""

    def __init__(self, config, cfg: decoder_lib.DecoderConfig, params):
        import jax
        import jax.numpy as jnp
        self.cfg = cfg
        self.params = params
        # float32 is the CPU tests' exact mode; the chip's kernels take
        # bfloat16
        self.dtype = (jnp.float32 if config.COMPUTE_DTYPE == 'float32'
                      else jnp.bfloat16)
        self.slots = int(config.LM_MAX_SEQS)
        self.buckets = tuple(config.lm_chunk_buckets)
        self.subchunk = int(config.LM_WINDOW_SUBCHUNK)
        self.geometry = lm_cache.CacheGeometry.make(
            page_size=int(config.LM_PAGE_SIZE), window=cfg.sliding_window,
            slots=self.slots, pool_pages=int(config.LM_PAGE_POOL_PAGES),
            max_context=int(config.LM_MAX_CONTEXT),
            max_chunk=self.buckets[-1])
        g = self.geometry
        if g.max_context <= self.buckets[-1]:
            raise ValueError('LM_MAX_CONTEXT %d must exceed the largest '
                             'chunk bucket %d'
                             % (g.max_context, self.buckets[-1]))
        self.shapes: Dict[int, decoder_lib.StepShape] = {}
        self.layouts: Dict[int, dict] = {}
        self.programs: Dict[int, object] = {}
        for chunk in (0,) + self.buckets:
            sub_seqs = lm_cache.ceil_div(chunk, self.subchunk)
            shape = decoder_lib.StepShape(
                tokens=self.slots + chunk, chunk=chunk,
                outputs=self.slots + 1,
                full_seqs=self.slots + (1 if chunk else 0),
                full_pages=g.pages_per_seq,
                window_seqs=self.slots + sub_seqs,
                window_pages=g.window_table_pages(
                    min(self.subchunk, chunk) if chunk else 1))
            self.shapes[chunk] = shape
            self.layouts[chunk] = pack_layout(shape)
            self.programs[chunk] = self._program(shape, self.layouts[chunk])
        self.take_row = jax.jit(decoder_lib.take_row)
        self.cache = None
        self.prev_ids = None
        self.reset_cache()

    def _program(self, shape: decoder_lib.StepShape, layout: dict):
        import jax
        g = self.geometry
        step = decoder_lib.make_step(self.cfg, shape, g.ring_layer_pages,
                                     g.pool_layer_pages, self.dtype)

        def run(params, cache, prev_ids, packed):
            return step(params, cache, prev_ids,
                        unpack_batch(packed, layout))
        return jax.jit(run, donate_argnums=(1,))

    def reset_cache(self) -> None:
        """Both pools zeroed (also gives them back after ``drop_cache``)."""
        import jax.numpy as jnp
        g = self.geometry
        self.cache = decoder_lib.zero_cache(
            self.cfg, g.ring_layer_pages, g.pool_layer_pages, g.page_size,
            self.dtype)
        self.prev_ids = jnp.zeros((self.slots + 1,), jnp.int32)

    def drop_cache(self) -> None:
        """Frees the pools' device memory (the benchmark's check computes
        its reference beside the weights)."""
        for array in (self.cache or {}).values():
            array.delete()
        self.cache = None

    def cache_bytes(self) -> Dict[str, int]:
        g = self.geometry
        shapes = decoder_lib.cache_shapes(
            self.cfg, g.ring_layer_pages, g.pool_layer_pages, g.page_size)
        return {name: int(np.prod(shape)) * np.dtype(self.dtype).itemsize
                for name, shape in shapes.items()}

    def run(self, chunk: int, packed: np.ndarray):
        """Enqueues one step; returns its (next_ids, logits, counts)."""
        self.cache, next_ids, logits, counts = self.programs[chunk](
            self.params, self.cache, self.prev_ids, packed)
        self.prev_ids = next_ids
        return next_ids, logits, counts

    def empty_batch(self, chunk: int) -> Tuple[np.ndarray, dict]:
        """A step's inputs with no sequence in them: the flat array and
        its named views.  Every row is padding, written to the pools'
        spare pages."""
        g = self.geometry
        layout = self.layouts[chunk]
        packed = np.zeros((layout[''][0],), np.int32)
        views = unpack_batch(packed, layout)
        views['token_src'][:] = -1
        views['full_rows'][:] = g.pool_pages * g.page_size
        views['window_rows'][:] = g.slots * g.ring_pages * g.page_size
        return packed, views

    def pick_chunk(self, remaining: int) -> Tuple[int, int]:
        """(bucket, tokens taken) for a prompt with ``remaining`` tokens to
        go: the largest bucket while it fills, then the smallest that
        holds the rest."""
        largest = self.buckets[-1]
        if remaining >= largest:
            return largest, largest
        for bucket in self.buckets:
            if bucket >= remaining:
                return bucket, remaining
        raise AssertionError('unreachable')


class LMScheduler:
    """The engine's step loop and the ``generate`` tier's bookkeeping."""

    # the dispatcher thread owns the running set; stats() reads gauges only
    def __init__(self, engine, runtime: LMRuntime):
        self.engine = engine
        self.runtime = runtime
        self.cache = lm_cache.CacheManager(runtime.geometry)
        self._running: List[_Sequence] = []
        self._step_seq = 0
        self._t_last_done = 0.0
        cfg = runtime.cfg
        self._expert_tokens = np.zeros((cfg.num_layers, cfg.num_experts),
                                       np.int64)
        self._expert_lock = threading.Lock()
        # what every step carried, for whoever counts its work afterwards
        # (the benchmark's roofline readers): bounded, newest last
        self._step_log: collections.deque = collections.deque(maxlen=16384)
        self.steps_total = Counter('serving/lm_steps_total')
        self.tokens_total = Counter('serving/lm_tokens_total')
        self.generated_total = Counter('serving/lm_generated_tokens_total')
        self.admit_held_total = Counter('serving/lm_admit_held_total')
        self.decode_step_timer = Timer('serving/lm_decode_step_ms')
        self.prefill_chunk_timer = Timer('serving/lm_prefill_chunk_ms')
        self.ttft_timer = Timer('serving/lm_ttft_ms')
        self.admit_wait_timer = Timer('serving/lm_admit_wait_ms')
        self.ring_fill = Gauge('serving/lm_ring_pool_fill')
        self.page_fill = Gauge('serving/lm_page_pool_fill')
        self.tokens_per_step = Gauge('serving/lm_tokens_per_step')
        self.running_gauge = Gauge('serving/lm_running')

    def warm(self) -> int:
        """Compiles every step program by serving one request a chunk
        bucket through the loop itself: a prompt that fills the bucket
        exactly, two tokens out (the second by a step of decode rows
        only), logits asked for.  Real sequences, because the attention
        kernel's pipeline assumes a step holds at least one.  Returns the
        number of programs."""
        engine, buckets = self.engine, self.runtime.buckets
        requests = [GenerateRequest(np.zeros((bucket,), np.int32), 2, True)
                    for bucket in buckets]
        with engine._cond:
            for request in requests:
                engine._queues[GENERATE_TIER].append(request)
                engine._pending_rows[GENERATE_TIER] += 1
            engine._set_queue_depth_locked()
            engine._cond.notify_all()
        for request in requests:
            np.asarray(request.future.result().logits[-1])
        return len(buckets) + 1

    # ------------------------------------------------------------ intake
    def check_request(self, prompt: np.ndarray, max_new_tokens: int) -> None:
        """Refuses what could never be served, before admission."""
        if prompt.ndim != 1 or prompt.shape[0] < 1:
            raise ValueError('a generate request needs a 1-d prompt of at '
                             'least one token id')
        if max_new_tokens < 1:
            raise ValueError('max_new_tokens must be >= 1')
        vocab = self.runtime.cfg.vocab_size
        if prompt.min() < 0 or prompt.max() >= vocab:
            raise ValueError('prompt ids must lie in [0, %d)' % vocab)
        context = int(prompt.shape[0]) + max_new_tokens - 1
        if not self.cache.fits_ever(context):
            raise ValueError(
                'a context of %d positions can never be admitted '
                '(LM_MAX_CONTEXT %d, page pool %d positions)'
                % (context, self.runtime.geometry.max_context,
                   self.runtime.geometry.pool_pages
                   * self.runtime.geometry.page_size))

    def _admit_queued_locked(self) -> None:
        """Moves requests from the queue's head to the running set while
        the cache has room for them (engine lock held)."""
        engine = self.engine
        queue = engine._queues[GENERATE_TIER]
        held_before = self.cache.held_total
        while queue:
            lease = self.cache.admit(queue[0].context)
            if lease is None:
                break
            request = queue.popleft()
            engine._pending_rows[GENERATE_TIER] -= 1
            sequence = _Sequence(request, lease)
            self._running.append(sequence)
            waited = sequence.t_admitted - request.t_enqueue
            self.admit_wait_timer.record(waited)
            with tracing_lib.phase('serving.lm_admit_wait',
                                   waited_ms=1e3 * waited,
                                   prompt=int(request.prompt.shape[0])):
                pass
            if tele_core.enabled():
                engine._mirror.timer(
                    'serving/lm_admit_wait_ms').record(waited)
        held = self.cache.held_total - held_before
        if held:
            self.admit_held_total.inc(held)
            if tele_core.enabled():
                engine._mirror.counter(
                    'serving/lm_admit_held_total').inc(held)
        engine._set_queue_depth_locked()
        self._set_fill()

    def _set_fill(self) -> None:
        ring, pages = self.cache.fill()
        self.ring_fill.set(ring)
        self.page_fill.set(pages)
        self.running_gauge.set(len(self._running))
        if tele_core.enabled():
            reg = self.engine._mirror
            reg.gauge('serving/lm_ring_pool_fill').set(ring)
            reg.gauge('serving/lm_page_pool_fill').set(pages)

    # ------------------------------------------------------------- plan
    def _plan(self):
        """(chunk bucket, packed inputs, harvest, decode rows, prompt
        tokens) of the next step, or None where no sequence has anything
        left to enqueue."""
        rt, g = self.runtime, self.runtime.geometry
        decoding = [s for s in self._running if s.decoding]
        prefilling = next((s for s in self._running if s.in_prefill), None)
        if not decoding and prefilling is None:
            return None
        bucket, taken = 0, 0
        if prefilling is not None:
            bucket, taken = rt.pick_chunk(
                int(prefilling.request.prompt.shape[0])
                - prefilling.prefilled)
        packed, v = rt.empty_batch(bucket)
        tokens, token_src = v['tokens'], v['token_src']
        positions, valid, out_rows = v['positions'], v['valid'], v['out_rows']
        full_rows, window_rows = v['full_rows'], v['window_rows']
        full_lens, window_lens = v['full_kv_lens'], v['window_kv_lens']
        full_table, window_table = (v['full_page_indices'],
                                    v['window_page_indices'])
        full_cu, window_cu = v['full_cu_q_lens'], v['window_cu_q_lens']
        width = window_table.shape[1]
        harvest: List[Tuple[_Sequence, int]] = []
        n = len(decoding)
        for row, s in enumerate(decoding):
            at = int(s.request.prompt.shape[0]) + s.decoded
            token_src[row] = s.last_out
            positions[row] = at
            full_rows[row] = lm_cache.full_rows(g, s.lease, at)
            window_rows[row] = lm_cache.ring_rows(g, s.lease.slot, at)
            full_lens[row] = at + 1
            full_table[row, :s.lease.pages.shape[0]] = s.lease.pages
            window_lens[row], window_table[row] = lm_cache.window_view(
                g, s.lease.slot, at, 1, width)
            out_rows[row] = row
            s.decoded += 1
            s.last_out = row
            harvest.append((s, row))
        valid[:n] = 1
        full_cu[:n + 1] = np.arange(n + 1)
        window_cu[:n + 1] = np.arange(n + 1)
        full_seqs = window_seqs = n
        if prefilling is not None:
            s, first = prefilling, prefilling.prefilled
            where = np.arange(first, first + taken)
            rows = slice(n, n + taken)
            tokens[rows] = s.request.prompt[first:first + taken]
            positions[rows] = where
            valid[rows] = 1
            full_rows[rows] = lm_cache.full_rows(g, s.lease, where)
            window_rows[rows] = lm_cache.ring_rows(g, s.lease.slot, where)
            full_lens[n] = first + taken
            full_table[n, :s.lease.pages.shape[0]] = s.lease.pages
            full_cu[n + 1] = n + taken
            full_seqs = n + 1
            for start in range(0, taken, rt.subchunk):
                q_len = min(rt.subchunk, taken - start)
                window_lens[window_seqs], window_table[window_seqs] = \
                    lm_cache.window_view(g, s.lease.slot, first + start,
                                         q_len, width)
                window_cu[window_seqs + 1] = n + start + q_len
                window_seqs += 1
            s.prefilled += taken
            if not s.in_prefill:
                # the prompt's last token: its logits give the first
                # generated token
                out_rows[-1] = n + taken - 1
                s.last_out = out_rows.shape[0] - 1
                harvest.append((s, s.last_out))
        full_cu[full_seqs + 1:] = full_cu[full_seqs]
        window_cu[window_seqs + 1:] = window_cu[window_seqs]
        v['full_num_seqs'][0] = full_seqs
        v['window_num_seqs'][0] = window_seqs
        return (bucket, packed, harvest, n, taken,
                positions[:n].copy(),
                prefilling.prefilled - taken if prefilling else 0)

    # ------------------------------------------------------------ a step
    def _enqueue(self, plan) -> _Step:
        (bucket, packed, harvest, decode_rows, prefill_tokens,
         decode_positions, chunk_first) = plan
        self._step_seq += 1
        seq = self._step_seq
        with tracing_lib.phase('serving.lm_step', step=seq,
                               decode_rows=decode_rows,
                               prefill_tokens=prefill_tokens,
                               bucket=bucket):
            next_ids, logits, counts = self.runtime.run(bucket, packed)
            for sequence, row in harvest:
                if sequence.request.return_logits:
                    sequence.logit_rows.append(
                        self.runtime.take_row(logits, row))
        self.steps_total.inc()
        self.tokens_total.inc(decode_rows + prefill_tokens)
        self.tokens_per_step.set(decode_rows + prefill_tokens)
        if tele_core.enabled():
            reg = self.engine._mirror
            reg.counter('serving/lm_steps_total').inc()
            reg.counter('serving/lm_tokens_total').inc(
                decode_rows + prefill_tokens)
            reg.gauge('serving/lm_tokens_per_step').set(
                decode_rows + prefill_tokens)
        return _Step(seq=seq, next_ids=next_ids, counts=counts,
                     harvest=harvest, decode_rows=decode_rows,
                     prefill_tokens=prefill_tokens,
                     t_enqueued=time.perf_counter(), bucket=bucket,
                     decode_positions=decode_positions,
                     chunk_first=chunk_first)

    def _finish(self, step: _Step) -> None:
        """Fetches a step's tokens (waits for the device), times the step,
        notes first tokens and delivers what is complete."""
        tokens = step.prefill_tokens + step.decode_rows
        with (tracing_lib.phase('serving.lm_prefill_chunk', step=step.seq,
                                tokens=tokens) if step.prefill_tokens else
              tracing_lib.phase('serving.lm_decode', step=step.seq,
                                tokens=tokens)):
            ids = np.asarray(step.next_ids)
            counts = np.asarray(step.counts)
        now = time.perf_counter()
        # the device runs steps back to back: this one began when the one
        # before it ended, or when it was enqueued if the device was idle
        took = now - max(self._t_last_done, step.t_enqueued)
        self._t_last_done = now
        timer = (self.prefill_chunk_timer if step.prefill_tokens
                 else self.decode_step_timer)
        timer.record(took)
        with self._expert_lock:
            self._expert_tokens += counts
            self._step_log.append({
                'step': step.seq, 'bucket': step.bucket,
                'decode_positions': step.decode_positions,
                'chunk_first': step.chunk_first,
                'chunk_tokens': step.prefill_tokens,
                'experts_touched': (counts > 0).sum(axis=1),
                't_enqueued': step.t_enqueued, 't_done': now,
                'seconds': took})
        if tele_core.enabled():
            reg = self.engine._mirror
            if step.prefill_tokens:
                reg.timer('serving/lm_prefill_chunk_ms').record(took)
            else:
                reg.timer('serving/lm_decode_step_ms').record(took)
            per_layer = counts.max(axis=1) / np.maximum(
                counts.mean(axis=1), 1e-9)
            reg.gauge('serving/lm_expert_load_max_over_mean').set(
                float(per_layer.mean()))
        finished = []
        for sequence, row in step.harvest:
            sequence.generated.append(int(ids[row]))
            request = sequence.request
            if len(sequence.generated) == 1:
                since = now - request.t_enqueue
                self.ttft_timer.record(since)
                with tracing_lib.phase(
                        'serving.lm_first_token',
                        since_submit_ms=1e3 * since,
                        prompt=int(request.prompt.shape[0])):
                    pass
                if tele_core.enabled():
                    self.engine._mirror.timer(
                        'serving/lm_ttft_ms').record(since)
            if len(sequence.generated) == request.max_new_tokens:
                finished.append(sequence)
        self.generated_total.inc(len(step.harvest))
        for sequence in finished:
            self._deliver(sequence)

    def _deliver(self, sequence: _Sequence) -> None:
        engine, request = self.engine, sequence.request
        logits = None
        if request.return_logits:
            logits = np.stack([np.asarray(row)
                               for row in sequence.logit_rows])
            sequence.logit_rows = []
        result = GenerationResult(
            token_ids=np.asarray(sequence.generated, np.int32),
            logits=logits)
        with engine._cond:
            self._running.remove(sequence)
            self.cache.free(sequence.lease)
            self._set_fill()
        with tracing_lib.phase(
                'serving.deliver', batch=0, rows=1, tier=GENERATE_TIER,
                queue_wait_ms=1e3 * (sequence.t_admitted
                                     - request.t_enqueue),
                since_enqueue_ms=1e3 * (time.perf_counter()
                                        - request.t_enqueue)):
            if not request.future.done():
                request.future.set_result(result)
        latency = time.perf_counter() - request.t_enqueue
        engine.latency.record(latency)
        if tele_core.enabled():
            engine._mirror.timer('serving/latency_ms').record(latency)

    # ------------------------------------------------------------ the loop
    def loop(self) -> None:
        """The dispatcher thread's body for a language model."""
        engine = self.engine
        in_flight: Optional[_Step] = None
        while True:
            abandoned: list = []
            with engine._cond:
                queue = engine._queues[GENERATE_TIER]
                if in_flight is None and not self._running and \
                        not queue and not engine._closed:
                    with tracing_lib.phase('serving.no_work'):
                        while not engine._closed and not queue:
                            engine._cond.wait()
                if engine._closed and not engine._drain:
                    # fail-fast close: nothing unfinished is served on;
                    # the step in flight is dropped with its sequences
                    abandoned.extend(queue)
                    queue.clear()
                    engine._pending_rows[GENERATE_TIER] = 0
                    for sequence in self._running:
                        abandoned.append(sequence.request)
                        self.cache.free(sequence.lease)
                    self._running.clear()
                    in_flight = None
                    engine._set_queue_depth_locked()
                else:
                    self._admit_queued_locked()
            if abandoned:
                exc = EngineClosed(
                    'ServingEngine closed with the request unfinished '
                    '(close(drain=True) serves what was admitted first)')
                for request in abandoned:
                    request.fail(exc)
            try:
                plan = self._plan()
                step = self._enqueue(plan) if plan is not None else None
                if in_flight is not None:
                    self._finish(in_flight)
                in_flight = step
            except BaseException as exc:   # keep the dispatcher alive
                from code2vec_tpu.telemetry import memory as memory_lib
                memory_lib.ledger().note_oom(exc, 'serving.lm_step')
                in_flight = None
                with engine._cond:
                    failed = list(self._running)
                    self._running.clear()
                    for s in failed:
                        self.cache.free(s.lease)
                for s in failed:
                    s.request.fail(exc)
                try:
                    self.runtime.reset_cache()
                except BaseException:
                    pass
            with engine._cond:
                if engine._closed and in_flight is None and \
                        not self._running and \
                        not engine._queues[GENERATE_TIER]:
                    return

    def step_log(self) -> List[dict]:
        """One dict a finished step, oldest first: ``step``, ``bucket``,
        ``decode_positions``, ``chunk_first``, ``chunk_tokens``,
        ``experts_touched`` [layers], ``t_enqueued``, ``t_done``
        (``perf_counter``), ``seconds``."""
        with self._expert_lock:
            return list(self._step_log)

    # ------------------------------------------------------------- stats
    def stats(self) -> Dict[str, object]:
        with self._expert_lock:
            expert_tokens = self._expert_tokens.copy()
        return {
            'steps_total': self.steps_total.snapshot(),
            'tokens_total': self.tokens_total.snapshot(),
            'generated_tokens_total': self.generated_total.snapshot(),
            'admit_held_total': self.admit_held_total.snapshot(),
            'decode_step_ms': self.decode_step_timer.snapshot(),
            'prefill_chunk_ms': self.prefill_chunk_timer.snapshot(),
            'ttft_ms': self.ttft_timer.snapshot(),
            'admit_wait_ms': self.admit_wait_timer.snapshot(),
            'ring_pool_fill': self.ring_fill.snapshot(),
            'page_pool_fill': self.page_fill.snapshot(),
            'tokens_per_step': self.tokens_per_step.snapshot(),
            'running': self.running_gauge.snapshot(),
            'expert_tokens': expert_tokens,
        }
