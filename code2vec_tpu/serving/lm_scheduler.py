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
running set when the cache manager (``serving/lm_cache.py``) has a slot
and pages for its whole context, in arrival order.

A request may name a **session**.  Its lease (slot, pages, and with them
the recurrent states and pooled keys they index) then stays at delivery,
and the session's next turn starts at the session's end and not at
position 0: its effective prompt is the last token the earlier turn
generated (never fed back) followed by the new prompt, so the session's
history is every prompt and every generated token in order.  One turn of a
session runs at a time; a turn submitted while an earlier one runs waits in
the queue, without holding back the requests behind it.

The loop knows no model.  It is handed the model's module
(``LMRuntime.lib``, named by the family's entry in ``models/families.py``)
and asks it for everything that is the model's own: the step's shape and
program (with the kernels of its own, ``lib.step_kernels``, chosen once by
the platform), the cache's arrays, the inputs a plan fills beside the
common ones (``lib.StepPlan``) and what a step's counts mean
(``lib.log_counts``).
``models/decoder.py`` (sliding and full attention, experts: ring slots and
pages), ``models/hybrid_decoder.py`` (linear and block-sparse attention:
state slots, pages and pooled keys) and ``models/latent_decoder.py``
(latent attention, a share of the experts: latent pages alone) are such
modules.
"""
from __future__ import annotations

import collections
import threading
import time
from concurrent.futures import Future
from typing import Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from code2vec_tpu.serving import lm_cache
from code2vec_tpu.serving.errors import EngineClosed, SessionLost
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

    __slots__ = ('prompt', 'max_new_tokens', 'return_logits', 'session',
                 'future', 'rows', 'tier', 't_enqueue', 'trace')

    def __init__(self, prompt: np.ndarray, max_new_tokens: int,
                 return_logits: bool, session=None):
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.return_logits = return_logits
        self.session = session
        self.future: Future = Future()
        self.rows = 1
        self.tier = GENERATE_TIER
        self.t_enqueue = time.perf_counter()
        self.trace = None

    @property
    def context(self) -> int:
        """Positions its keys and values take: the last generated token
        is never fed back.  (A session's turn takes these after the
        session's end, and one more for the token the earlier turn left.)"""
        return int(self.prompt.shape[0]) + self.max_new_tokens - 1

    def fail(self, exc: BaseException) -> None:
        if not self.future.done():
            self.future.set_exception(exc)


class _Session:
    """A resident session: what its turns leave for the next."""

    __slots__ = ('lease', 'length', 'pending', 'planned', 'busy', 'queued',
                 't_free')

    def __init__(self):
        self.lease: Optional[lm_cache.Lease] = None
        self.length = 0         # positions its cache holds
        self.pending: Optional[int] = None   # the token not yet fed back
        self.planned = 0        # its length once every submitted turn ran
        self.busy = False       # a turn of it is in the running set
        self.queued = 0         # turns submitted and not yet delivered
        self.t_free = 0.0       # when its last turn was delivered


class _Sequence:
    """A request in the running set."""

    __slots__ = ('request', 'lease', 'base', 'prompt', 'prefilled',
                 'decoded', 'last_out', 'generated', 'logit_rows',
                 't_admitted', 't_first_token', 'session_wait')

    def __init__(self, request: GenerateRequest, lease: lm_cache.Lease,
                 base: int = 0, pending: Optional[int] = None):
        self.request = request
        self.lease = lease
        self.base = base        # positions an earlier turn left in the cache
        # what this turn feeds: the token the earlier turn generated last
        # (never fed back), then the request's prompt
        self.prompt = request.prompt if pending is None else \
            np.concatenate([np.asarray([pending], np.int32), request.prompt])
        self.prefilled = 0      # prompt tokens whose step is enqueued
        self.decoded = 0        # decode steps enqueued
        self.last_out = -1      # its row of the newest step's outputs
        self.generated: List[int] = []
        self.logit_rows: list = []
        self.t_admitted = time.perf_counter()
        self.t_first_token = 0.0
        self.session_wait: Optional[float] = None   # a session's turn only

    @property
    def in_prefill(self) -> bool:
        return self.prefilled < self.prompt.shape[0]

    @property
    def end(self) -> int:
        """The position its next token goes to."""
        return self.base + int(self.prompt.shape[0]) + self.decoded

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
    note: dict                      # what the model's plan says of the
    #                                 step, for its ``log_counts``


def pack_layout(batch_shapes: Dict[str, tuple]
                ) -> Dict[str, Tuple[int, int, tuple]]:
    """{name: (offset, size, shape)} of a step's int32 inputs (a model's
    ``batch_shapes(shape)``) in the one flat array the host sends; ``''``
    holds the array's length."""
    layout, at = {}, 0
    for name, dims in batch_shapes.items():
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
    """The model on the device: configuration, weights, the cache pools and
    the step programs, one a shape.  ``lib`` is the model's module: the
    seam (its last section says what is asked of it)."""

    def __init__(self, config, cfg, params, lib, step_kernels=None):
        import jax
        import jax.numpy as jnp
        from code2vec_tpu.parallel.mesh import mesh_platform
        self.cfg = cfg
        self.params = params
        self.lib = lib
        # the names of the model's own kernels or their jax.numpy forms,
        # decided once from the platform the step programs run on (a test
        # hands them)
        self.step_kernels = (lib.step_kernels(mesh_platform())
                             if step_kernels is None else step_kernels)
        # float32 is the CPU tests' exact mode; the chip's kernels take
        # bfloat16
        self.dtype = (jnp.float32 if config.COMPUTE_DTYPE == 'float32'
                      else jnp.bfloat16)
        self.slots = int(config.LM_MAX_SEQS)
        self.buckets = tuple(config.lm_chunk_buckets)
        self.subchunk = int(config.LM_WINDOW_SUBCHUNK)
        self.geometry = lm_cache.CacheGeometry.make(
            page_size=int(config.LM_PAGE_SIZE),
            window=lib.ring_window(cfg),
            slots=self.slots, pool_pages=int(config.LM_PAGE_POOL_PAGES),
            max_context=int(config.LM_MAX_CONTEXT),
            max_chunk=self.buckets[-1])
        g = self.geometry
        if g.max_context <= self.buckets[-1]:
            raise ValueError('LM_MAX_CONTEXT %d must exceed the largest '
                             'chunk bucket %d'
                             % (g.max_context, self.buckets[-1]))
        lib.check_geometry(cfg, g)
        self.shapes: Dict[int, object] = {}
        self.layouts: Dict[int, dict] = {}
        self.programs: Dict[int, object] = {}
        for chunk in (0,) + self.buckets:
            shape = lib.step_shape(cfg, g, chunk, self.subchunk)
            self.shapes[chunk] = shape
            self.layouts[chunk] = pack_layout(lib.batch_shapes(shape))
            self.programs[chunk] = self._program(shape, self.layouts[chunk])
        self.take_row = jax.jit(lib.take_row)
        self.cache = None
        self.prev_ids = None
        self.reset_cache()

    def _program(self, shape, layout: dict):
        import jax
        step = self.lib.make_step(self.cfg, shape, self.geometry, self.dtype,
                                  **self.step_kernels)

        def run(params, cache, prev_ids, packed):
            return step(params, cache, prev_ids,
                        unpack_batch(packed, layout))
        run.__name__ = self.lib.program_name(shape)
        return jax.jit(run, donate_argnums=(1,))

    def _zero_cache(self) -> dict:
        return self.lib.zero_cache(self.cfg, self.geometry, self.dtype)

    def reset_cache(self) -> None:
        """Every pool zeroed (also gives them back after ``drop_cache``)."""
        import jax.numpy as jnp
        self.cache = self._zero_cache()
        self.prev_ids = jnp.zeros((self.slots + 1,), jnp.int32)

    def drop_cache(self) -> None:
        """Frees the pools' device memory (the benchmark's check computes
        its reference beside the weights)."""
        for array in (self.cache or {}).values():
            array.delete()
        self.cache = None

    def cache_bytes(self) -> Dict[str, int]:
        import jax
        return {name: int(np.prod(pool.shape)) * pool.dtype.itemsize
                for name, pool in jax.eval_shape(self._zero_cache).items()}

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
        self.lib.pad_rows(self.cfg, g, views)
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
        self._sessions: Dict[object, _Session] = {}
        # sessions whose cache went with a failed step: a turn of one is
        # refused until its caller closes it
        self._lost: set = set()
        self._step_seq = 0
        self._t_last_done = 0.0
        lib = runtime.lib
        # every step's counts summed (the model says what they count)
        self._counts_total = np.zeros(lib.counts_shape(runtime.cfg),
                                      np.int64)
        self._log_lock = threading.Lock()
        # what every step carried, for whoever counts its work afterwards
        # (the benchmark's roofline readers), and when every delivered
        # request passed each stage: bounded, newest last
        self._step_log: collections.deque = collections.deque(maxlen=16384)
        self._request_log: collections.deque = collections.deque(
            maxlen=16384)
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
        # resident sessions and the state they keep
        self.state_fill = Gauge('serving/lm_state_pool_fill')
        self.sessions_gauge = Gauge('serving/lm_sessions_resident')
        self.resident_positions_total = Counter(
            'serving/lm_resident_positions_total')
        self.prefilled_positions_total = Counter(
            'serving/lm_prefilled_positions_total')
        self.session_wait_timer = Timer('serving/lm_session_wait_ms')
        # a slot is what the model keeps in it: its gauge is the model's
        # (a model that keeps nothing a slot counts the slots alone)
        self.bare_slot_fill = Gauge('serving/lm_slot_fill')
        self.slot_fill = {gauge.name: gauge for gauge in
                          (self.ring_fill, self.state_fill,
                           self.bare_slot_fill)}[lib.SLOT_GAUGE]
        # the model's own counters, fed by its reading of a step's counts
        self.model_counters = {name: Counter(name) for name in lib.COUNTERS}

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

    def reserve_locked(self, request: GenerateRequest) -> None:
        """Books ``request``'s positions (engine lock held): refuses a
        context that could never be resident, and for a session's turn
        counts from where the turns submitted before it will end."""
        g = self.runtime.geometry
        if request.session is not None and request.session in self._lost:
            raise SessionLost(
                'session %r lost its cache to a failed step: close it '
                '(close_session) and send its history again'
                % (request.session,))
        session = self._sessions.get(request.session) \
            if request.session is not None else None
        before = session.planned if session is not None else 0
        # a later turn also feeds the token the one before it left
        context = before + (1 if before else 0) + request.context
        if not self.cache.fits_ever(context):
            raise ValueError(
                'a context of %d positions can never be admitted '
                '(LM_MAX_CONTEXT %d, page pool %d positions)'
                % (context, g.max_context, g.pool_pages * g.page_size))
        if request.session is not None:
            if session is None:
                session = self._sessions[request.session] = _Session()
            session.planned = context
            session.queued += 1

    def close_session(self, session_id) -> bool:
        """Returns a resident session's lease to the pools; False if there
        is no such session.  Refused while a turn of it is submitted and
        not yet delivered.  Closing a session that was lost with a failed
        step (True) lets its id be used anew."""
        with self.engine._cond:
            session = self._sessions.get(session_id)
            if session is None:
                lost = session_id in self._lost
                self._lost.discard(session_id)
                return lost
            if session.queued:
                raise RuntimeError(
                    'session %r has %d turn(s) submitted and not yet '
                    'delivered' % (session_id, session.queued))
            del self._sessions[session_id]
            self.cache.close_session(session_id)
            self._set_fill()
            self.engine._cond.notify_all()
        return True

    def _admit_queued_locked(self) -> List[GenerateRequest]:
        """Moves requests from the queue to the running set, in arrival
        order, while the cache has room for them (engine lock held).  A
        turn whose session has a turn running is passed over: it waits for
        that turn, and holds nobody else back.  Returns the turns that
        were booked before their session was lost and queued after it, for
        the caller to fail once the lock is released."""
        engine = self.engine
        queue = engine._queues[GENERATE_TIER]
        held_before = self.cache.held_total
        orphans: List[GenerateRequest] = []
        for request in list(queue):
            session = self._sessions.get(request.session) \
                if request.session is not None else None
            base, pending = 0, None
            if request.session is not None and session is None:
                queue.remove(request)
                engine._pending_rows[GENERATE_TIER] -= 1
                orphans.append(request)
                continue
            if session is not None and session.busy:
                continue
            if session is not None and session.lease is not None:
                base, pending = session.length, session.pending
                lease = session.lease
                if not self.cache.extend(lease,
                                         base + 1 + request.context):
                    break
            else:
                lease = self.cache.admit(request.context)
                if lease is None:
                    break
            queue.remove(request)
            engine._pending_rows[GENERATE_TIER] -= 1
            sequence = _Sequence(request, lease, base, pending)
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
            if request.session is not None:
                session.busy = True
                session.lease = lease
                self.cache.keep(request.session, lease)
                # how long it stood behind its own session's earlier turn
                behind = max(0.0, min(session.t_free, sequence.t_admitted)
                             - request.t_enqueue)
                sequence.session_wait = behind
                self.session_wait_timer.record(behind)
                self.resident_positions_total.inc(base)
                with tracing_lib.phase('serving.lm_session_wait',
                                       waited_ms=1e3 * behind,
                                       resident=base):
                    pass
                if tele_core.enabled():
                    reg = engine._mirror
                    reg.timer('serving/lm_session_wait_ms').record(behind)
                    reg.counter(
                        'serving/lm_resident_positions_total').inc(base)
            prefilled = int(sequence.prompt.shape[0])
            self.prefilled_positions_total.inc(prefilled)
            if tele_core.enabled():
                engine._mirror.counter(
                    'serving/lm_prefilled_positions_total').inc(prefilled)
        held = self.cache.held_total - held_before
        if held:
            self.admit_held_total.inc(held)
            if tele_core.enabled():
                engine._mirror.counter(
                    'serving/lm_admit_held_total').inc(held)
        engine._set_queue_depth_locked()
        self._set_fill()
        return orphans

    def _set_fill(self) -> None:
        slots, pages = self.cache.fill()
        self.slot_fill.set(slots)
        self.page_fill.set(pages)
        self.running_gauge.set(len(self._running))
        self.sessions_gauge.set(self.cache.sessions_kept)
        if tele_core.enabled():
            reg = self.engine._mirror
            reg.gauge(self.slot_fill.name).set(slots)
            reg.gauge('serving/lm_page_pool_fill').set(pages)
            reg.gauge('serving/lm_sessions_resident').set(
                self.cache.sessions_kept)

    # ------------------------------------------------------------- plan
    def _plan(self):
        """(chunk bucket, packed inputs, harvest, decode rows, prompt
        tokens, decode positions, the chunk's first position, the model's
        note of the plan) of the next step, or None where no sequence has
        anything left to enqueue."""
        rt, g = self.runtime, self.runtime.geometry
        decoding = [s for s in self._running if s.decoding]
        prefilling = next((s for s in self._running if s.in_prefill), None)
        if not decoding and prefilling is None:
            return None
        bucket, taken = 0, 0
        if prefilling is not None:
            bucket, taken = rt.pick_chunk(
                int(prefilling.prompt.shape[0]) - prefilling.prefilled)
        packed, v = rt.empty_batch(bucket)
        # what every model's step has; the rest is the model's plan
        tokens, token_src = v['tokens'], v['token_src']
        positions, valid, out_rows = v['positions'], v['valid'], v['out_rows']
        full_rows, full_table = v['full_rows'], v['full_page_indices']
        plan = rt.lib.StepPlan(rt.cfg, g, v, rt.subchunk)
        harvest: List[Tuple[_Sequence, int]] = []
        n = len(decoding)
        for row, s in enumerate(decoding):
            at = s.end
            token_src[row] = s.last_out
            positions[row] = at
            full_rows[row] = lm_cache.full_rows(g, s.lease, at)
            full_table[row, :s.lease.pages.shape[0]] = s.lease.pages
            plan.decode_row(row, s.lease, at)
            out_rows[row] = row
            s.decoded += 1
            s.last_out = row
            harvest.append((s, row))
        valid[:n] = 1
        # the chunk's row of the page table and first row of the batch
        at_chunk = plan.end_decode(n)
        chunk_first = 0
        if prefilling is not None:
            s, first = prefilling, prefilling.prefilled
            chunk_first = s.base + first
            where = np.arange(chunk_first, chunk_first + taken)
            rows = slice(at_chunk, at_chunk + taken)
            tokens[rows] = s.prompt[first:first + taken]
            positions[rows] = where
            valid[rows] = 1
            full_rows[rows] = lm_cache.full_rows(g, s.lease, where)
            full_table[at_chunk, :s.lease.pages.shape[0]] = s.lease.pages
            plan.chunk(n, s.lease, chunk_first, taken)
            s.prefilled += taken
            if not s.in_prefill:
                # the prompt's last token: its logits give the first
                # generated token
                out_rows[-1] = at_chunk + taken - 1
                s.last_out = out_rows.shape[0] - 1
                harvest.append((s, s.last_out))
        return (bucket, packed, harvest, n, taken,
                positions[:n].copy(), chunk_first, plan.close())

    # ------------------------------------------------------------ a step
    def _enqueue(self, plan) -> _Step:
        (bucket, packed, harvest, decode_rows, prefill_tokens,
         decode_positions, chunk_first, note) = plan
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
                     chunk_first=chunk_first, note=note)

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
        entry = {'step': step.seq, 'bucket': step.bucket,
                 'decode_positions': step.decode_positions,
                 'chunk_first': step.chunk_first,
                 'chunk_tokens': step.prefill_tokens,
                 't_enqueued': step.t_enqueued, 't_done': now,
                 'seconds': took}
        lib = self.runtime.lib
        kept, counted = lib.log_counts(counts, step.note)
        entry.update(kept)
        for name, value in counted.items():
            self.model_counters[name].inc(value)
        with self._log_lock:
            self._counts_total += counts
            self._step_log.append(entry)
        if tele_core.enabled():
            reg = self.engine._mirror
            if step.prefill_tokens:
                reg.timer('serving/lm_prefill_chunk_ms').record(took)
            else:
                reg.timer('serving/lm_decode_step_ms').record(took)
            for name, value in counted.items():
                reg.counter(name).inc(value)
            for name, value in lib.step_gauges(counts).items():
                reg.gauge(name).set(value)
        finished = []
        for sequence, row in step.harvest:
            sequence.generated.append(int(ids[row]))
            request = sequence.request
            if len(sequence.generated) == 1:
                sequence.t_first_token = now
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
            session = self._sessions.get(request.session) \
                if request.session is not None else None
            if session is not None:
                # the lease stays: the next turn starts at this one's end
                session.length = sequence.end
                session.pending = int(sequence.generated[-1])
                session.busy = False
                session.queued -= 1
                session.t_free = time.perf_counter()
            else:
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
        done = time.perf_counter()
        with self._log_lock:
            self._request_log.append({
                'session': request.session,
                'prompt': int(request.prompt.shape[0]),
                'new_tokens': request.max_new_tokens,
                't_enqueue': request.t_enqueue,
                't_admitted': sequence.t_admitted,
                't_first_token': sequence.t_first_token, 't_done': done,
                'session_wait': sequence.session_wait})
        latency = done - request.t_enqueue
        engine.latency.record(latency)
        if tele_core.enabled():
            engine._mirror.timer('serving/latency_ms').record(latency)

    def _drop_running_locked(self) -> Tuple[List[GenerateRequest],
                                            List[GenerateRequest]]:
        """Empties the running set and gives every lease back, the
        resident sessions' too (engine lock held): what the device holds
        of them is no longer to be trusted, or no longer wanted.  A
        session that held a cache is remembered as lost, and the turns of
        it still in the queue leave it: served from position 0 they would
        answer without the history the session promises.  Returns (the
        requests that were running, those turns)."""
        running = [sequence.request for sequence in self._running]
        for sequence in self._running:
            if sequence.request.session is None:
                self.cache.free(sequence.lease)
        self._running.clear()
        self.cache.close_all_sessions()
        lost = {name for name, session in self._sessions.items()
                if session.lease is not None}
        for name in lost:
            del self._sessions[name]
        self._lost |= lost
        queue = self.engine._queues[GENERATE_TIER]
        waiting = [request for request in queue if request.session in lost]
        for request in waiting:
            queue.remove(request)
            self.engine._pending_rows[GENERATE_TIER] -= 1
        return running, waiting

    # ------------------------------------------------------------ the loop
    def loop(self) -> None:
        """The dispatcher thread's body for a language model."""
        engine = self.engine
        in_flight: Optional[_Step] = None
        while True:
            abandoned: list = []
            orphans: list = []
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
                    abandoned.extend(self._drop_running_locked()[0])
                    in_flight = None
                    engine._set_queue_depth_locked()
                else:
                    orphans = self._admit_queued_locked()
            for request in orphans:
                request.fail(SessionLost(
                    'session %r lost its cache to a failed step while this '
                    'turn was being submitted' % (request.session,)))
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
                    # the pools are reset below: resident sessions go too
                    failed, waiting = self._drop_running_locked()
                    engine._set_queue_depth_locked()
                    self._set_fill()
                for request in failed:
                    request.fail(exc)
                lost = SessionLost(
                    'the session\'s cache went with a failed step (%r): '
                    'close it and send its history again' % (exc,))
                for request in waiting:
                    request.fail(lost)
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
        ``decode_positions``, ``chunk_first`` (a position of the whole
        session), ``chunk_tokens``, ``t_enqueued``, ``t_done``
        (``perf_counter``), ``seconds``; and what the model's
        ``log_counts`` keeps of the step's counts (``experts_touched``
        [layers] of ``models/decoder.py``; ``blocks_chosen``,
        ``blocks_visible`` and ``dense_tokens`` of
        ``models/hybrid_decoder.py``; ``experts_touched`` and
        ``held_choices`` of ``models/latent_decoder.py``)."""
        with self._log_lock:
            return list(self._step_log)

    def request_log(self) -> List[dict]:
        """One dict a delivered request, oldest first: ``session``,
        ``prompt`` (tokens of its own), ``new_tokens``, and when it passed
        each stage (``perf_counter``): ``t_enqueue``, ``t_admitted``,
        ``t_first_token``, ``t_done``; ``session_wait`` is the seconds a
        session's turn stood behind its own session's earlier turn (None
        for a request of no session)."""
        with self._log_lock:
            return list(self._request_log)

    # ------------------------------------------------------------- stats
    def stats(self) -> Dict[str, object]:
        with self._log_lock:
            counts_total = self._counts_total.copy()
        model = {name[len('serving/lm_'):]: counter.snapshot()
                 for name, counter in self.model_counters.items()}
        return {
            # what the model's steps counted and its own counters
            self.runtime.lib.COUNTS_STAT: counts_total, **model,
            # which of its own kernels the step programs were built with
            'step_kernels': dict(self.runtime.step_kernels),
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
            'state_pool_fill': self.state_fill.snapshot(),
            'slot_fill': self.bare_slot_fill.snapshot(),
            'sessions_resident': self.sessions_gauge.snapshot(),
            'resident_positions_total':
                self.resident_positions_total.snapshot(),
            'prefilled_positions_total':
                self.prefilled_positions_total.snapshot(),
            'session_wait_ms': self.session_wait_timer.snapshot(),
        }

