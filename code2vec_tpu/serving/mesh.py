"""Serving mesh: N ``ServingEngine`` replicas behind ONE shared front
queue, with continuous cross-tier batching, replica-aware admission,
and coordinated canaried rollover (SERVING.md "Serving mesh").

The single-engine story (PRs 4/7/8/9) ends at one replica: "heavy
traffic from millions of users" (ROADMAP north star) needs a FLEET —
the Ads-serving stack's shape (PAPERS.md, arxiv 2501.10546): many model
servers behind shared queues, params refreshed continuously under live
traffic.  This module is that shape for code2vec:

- **One shared front queue** (``serving/frontqueue.py``).  Admission —
  bound, deadline-vs-drain, degradation ladder — moves up to the fleet:
  the drain estimate is the fleet service rate (the mesh's sliding
  window over every replica's completions — numerically the sum of
  per-replica served-rows/s), and shedding/expiry are typed at the
  shared queue, so one slow replica never wedges its share of traffic.
- **Replica pullers = continuous cross-tier batching.**  Each replica
  runs one puller thread that claims work from the shared queue the
  moment the replica has a free in-flight slot: the puller picks the
  tier whose head waited longest and keeps folding newly-arriving
  compatible requests into the still-gathering micro-batch up to the
  coalescing deadline (the Ragged Paged Attention
  insert-into-the-in-flight-batch idea at request granularity), then
  packs onto the smallest covering (bucket x capacity-rung x tier)
  warm program of ITS engine.  Predict tiers and ``submit_neighbors``
  vectors traffic ride the same dispatch stream.
- **Replica-aware weighting.**  The replica table tracks per-replica
  in-flight windows, a dispatch circuit breaker (K consecutive dispatch
  failures open it; half-open probes one batch after the cooldown), and
  retirement — a breaker-open or retired replica simply stops pulling,
  and the queue redirects to its siblings instead of wedging.  A
  replica canarying a rollover pulls with a halved in-flight window
  (it still needs live traffic to conclude the canary; its shadow cost
  is off-latency by the engine's contract).
- **Coordinated rollover.**  ``load_params(step|path|pytree)`` canaries
  on ONE replica (reusing the engine's shadow-scoring machinery), then
  fleet-swaps the SAME validated params onto every other replica on
  agreement (``engine.adopt_params`` — pointer swap, zero compiles,
  one ledger entry), or rolls the canary back and leaves every replica
  serving the old params.  ``follow_checkpoints`` moves up here too:
  the fleet rolls as a unit instead of N pollers racing.

**Replica modes.**  ``MESH_REPLICAS`` in-process replica threads by
default (``MESH_REPLICA_MODE='thread'``): every replica is a
``ServingEngine`` in external-dispatch mode over the model's trainer,
so warm programs are shared through the trainer's jit caches and
replica 2..N warm for free.  ``'process'`` runs each replica as a
spawned worker process hosting its own model + engine, speaking the
framed dispatch wire (serving/transport.py: tokenized ``Batch`` out,
decoded results back, every message length-prefixed + CRC-checked)
over a pipe; ``'socket'`` carries the IDENTICAL protocol over TCP — the
mesh opens a listener, each worker dials in with a rid/proto handshake
and reports its restored params step, so replicas can live on other
machines.  Worker replicas restore params from the model's checkpoint
path (pytrees don't cross processes; checkpoint refs do — which is
also why worker-mode rollover takes step/path sources only).  Both
worker modes spawn their n workers on THIS machine from a parent that
already holds the model's devices, and a TPU belongs to one process:
on a TPU-holding parent the mesh refuses 'process'/'socket' at
construction with ``LocalWorkerNeedsHeldChip`` (the child's backend
init fails with "The TPU is already in use by process with pid N" —
PERF.md "Bring-up").  Thread mode, CPU hosts, and socket workers
started on another machine (scripts/mesh_worker.py) are unaffected.

**Self-healing (SERVING.md "Multi-host mesh").**  Replica death is a
non-event, not an operator page:

- **Liveness distinct from dispatch health.**  Workers heartbeat every
  ``MESH_HEARTBEAT_SECS`` (the in-flight count rides along); a
  worker that misses more than ``MESH_HEARTBEAT_MISSES`` intervals is
  marked dead typed — catching the hung or network-partitioned worker
  the dispatch breaker cannot see because nothing is in flight.
- **Crash-safe redispatch.**  Requests popped into a batch that dies
  with its worker are re-admitted ONCE at the FRONT of the shared
  queue with the dead incarnation excluded and their deadlines intact
  (already-expired members still shed typed at pop), so a crash costs
  latency, not answers; a second crash fails them typed
  (``ReplicaDead``).  The redispatched request's trace carries both
  attempts (``serving.redispatch`` event + a second queue_wait span).
- **Supervised restart.**  A mesh supervisor thread restarts a dead
  locally-spawned worker with exponential backoff under a window-
  scoped budget (``MESH_RESTART_LIMIT`` per
  ``MESH_RESTART_WINDOW_SECS`` — a flapping worker retires permanently
  instead of storming).  The restarted worker cold-starts from the
  checkpoint store, is re-adopted onto the fleet's CURRENT params step
  (including a rollover that happened while it was down) before its
  puller touches the queue, and capacity returns without operator
  action.

**Fleet observability (OBSERVABILITY.md "Fleet observability").**  A
worker replica's spans, metrics, and HBM ledger live in its own
process; the wire carries them home: dispatch frames ship per-member
trace contexts and workers backhaul finished span records (result
frames + heartbeats) for ``adopt_spans`` stitching under a
per-incarnation clock-offset estimate; heartbeats are the typed
schema-versioned ``transport.Heartbeat`` carrying the worker's
registry snapshot + ledger rollup for the replica-labeled fleet merge;
and ``serving/slo.py`` watches the fleet completion stream against
``SERVING_SLO_*`` burn-rate targets, alarming into the flight
recorder.

Measured gates: ``benchmarks/bench_mesh.py`` (open-loop load at fixed
offered rate; p99 / shed rate / per-replica fill at 1/2/4 replicas)
and ``scripts/mesh_soak.py`` (chaos soak: paced load + periodic
``kill_worker``/``drop_heartbeat`` faults; zero lost admitted
requests, zero post-warmup compiles, bounded p99, zero unstitched
trace trees).
"""
from __future__ import annotations

import collections
import os
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from code2vec_tpu.data.reader import (EstimatorAction,
                                      PathContextReader,
                                      canonicalize_contexts)
from code2vec_tpu.parallel import mesh as mesh_lib
from code2vec_tpu.resilience import faults
from code2vec_tpu.serving import engine as engine_lib
from code2vec_tpu.serving import memo as memo_lib
from code2vec_tpu.serving import slo as slo_lib
from code2vec_tpu.serving import transport as transport_lib
from code2vec_tpu.serving.engine import (ServingEngine, _Request,
                                         _resolve)
from code2vec_tpu.serving.errors import (AdoptionRejected,
                                         DeadlineExceeded, EngineClosed,
                                         EngineOverloaded,
                                         LocalWorkerNeedsHeldChip,
                                         ReplicaDead, WireError)
from code2vec_tpu.serving.frontqueue import FrontQueue
from code2vec_tpu.telemetry import core as tele_core
from code2vec_tpu.telemetry import tracing as tracing_lib
from code2vec_tpu.telemetry.core import Counter, Gauge
from code2vec_tpu.training.trainer import PREDICT_TIERS

#: replica dispatch-breaker states (mirrors the extractor breaker's
#: numbering: serving/breaker_state semantics)
_BREAKER_CLOSED = 0
_BREAKER_HALF_OPEN = 1
_BREAKER_OPEN = 2


class _ReplicaSlot:
    """One row of the mesh replica table: transport + health + the
    dispatch accounting the weighting decisions read.  All mutable
    fields are guarded by the MESH's ``_cond`` lock (the replica's
    puller, the decode-completion hook, liveness monitor, supervisor,
    rollover, and retirement all touch them).

    ``dead`` is the liveness verdict (worker exited, wire corrupted,
    or heartbeats missed): a dead slot stops pulling and waits for the
    supervisor, which either restarts it (``transport`` is replaced —
    the OLD transport object doubles as the incarnation token crash-
    safe redispatch excludes) or retires it permanently once the
    window-scoped restart budget is spent."""

    __slots__ = ('rid', 'transport', 'thread', 'retired',
                 'retired_reason', 'adopted', 'device_indices',
                 'inflight', 'rows_dispatched', 'batches',
                 'breaker_fails', 'breaker_state', 'breaker_open_until',
                 'canarying', 'dead', 'restarting', 'restart_times',
                 'restarts')

    def __init__(self, rid: str, transport):
        self.rid = rid
        self.transport = transport
        self.thread: Optional[threading.Thread] = None
        self.retired = False
        #: why this slot retired ('restart_budget' | 'drain' |
        #: 'autoscale' | 'adopted_worker_exit'): an autoscaler
        #: post-mortem must tell budget-retire from drain
        self.retired_reason: Optional[str] = None
        #: externally-spawned worker the mesh adopted: its restart
        #: supervision belongs to the ORCHESTRATOR that spawned it —
        #: its death retires the slot instead of charging the local
        #: restart budget (SERVING.md "Elastic fleet")
        self.adopted = False
        #: this replica's device slice (indices into jax.devices())
        #: under MESH_DEVICES_PER_REPLICA placement; None when
        #: placement is off (every replica time-shares the host)
        self.device_indices: Optional[List[int]] = None
        self.inflight = 0
        self.rows_dispatched = 0
        self.batches = 0
        self.breaker_fails = 0
        self.breaker_state = _BREAKER_CLOSED
        self.breaker_open_until = 0.0
        self.canarying = False
        self.dead = False
        self.restarting = False
        self.restart_times: collections.deque = collections.deque()
        self.restarts = 0


class _ThreadReplica:
    """In-process replica transport: a ``ServingEngine`` in
    external-dispatch mode, called directly."""

    mode = 'thread'

    def __init__(self, engine: ServingEngine):
        self.engine = engine

    def dispatch(self, tier: str, taken: List[_Request],
                 rows: int) -> None:
        self.engine.dispatch_external(tier, taken, rows)

    def wait_ready(self) -> None:
        pass  # in-process: constructed ready

    def warmup(self) -> None:
        self.engine.warmup()

    def load_params(self, source, canary_batches: int,
                    min_agreement: float) -> Future:
        return self.engine.load_params(source,
                                       canary_batches=canary_batches,
                                       min_agreement=min_agreement)

    def adopt(self, params, source, step: Optional[int]) -> None:
        # in-process fleet swap: the canary replica's validated pytree
        # IS the candidate — pointer swap, no restore, no new ledger
        # entry (the arrays are shared across replicas)
        self.engine.adopt_params(params, step=step)

    def stats(self) -> Dict[str, object]:
        return self.engine.stats()

    def close(self) -> None:
        self.engine.close()


class _WorkerReplica:
    """Worker replica transport: a spawned process hosting its own
    model + engine, fed tokenized ``Batch`` payloads over the framed
    wire (serving/transport.py) and returning decoded results.  The
    carrier is a pipe (``mode='process'``) or TCP (``mode='socket'`` —
    the worker dials the mesh listener and introduces itself, the
    shape that lets replicas live on other machines).

    The parent-side receiver thread resolves in-flight dispatches and
    feeds the mesh's completion hook; the worker serves dispatches
    sequentially (its engine still decodes on its own pool) and
    heartbeats on its own thread, so a dispatch-busy worker still
    proves liveness.  A worker death — EOF, a corrupt frame, or a
    liveness kill — is reported ONCE through ``on_worker_dead`` with
    the in-flight batches attached, so the mesh can redispatch them
    instead of failing callers."""

    # the pending map and the send side of the wire are shared by the
    # puller, the receiver thread, the heartbeat monitor, and control
    # calls (lock-discipline rule, ANALYSIS.md):
    # graftlint: guard _WorkerReplica._pending,_control,_seq by _lock
    def __init__(self, rid: str, mode: str,
                 config_overrides: Dict[str, object],
                 on_batch_done, log, on_worker_dead=None,
                 on_telemetry=None, on_spans=None,
                 listener: Optional[transport_lib.SocketListener] = None,
                 start_timeout_s: float = 600.0,
                 channel: Optional[object] = None):
        import multiprocessing
        self.rid = rid
        self.mode = mode
        self.log = log
        self._on_batch_done = on_batch_done
        self._on_worker_dead = on_worker_dead
        #: fleet-merge hook: (transport, registry snapshot, ledger
        #: rollup) per heartbeat — the mesh labels and merges
        self._on_telemetry = on_telemetry
        #: stitching accounting hook: (spans adopted, spans dropped)
        self._on_spans = on_spans
        self._start_timeout_s = start_timeout_s
        self._listener = listener
        self._cancel = threading.Event()
        #: stamped by the receiver on every frame (heartbeats included);
        #: the mesh liveness monitor reads it
        self.last_heartbeat = time.perf_counter()
        #: the worker's last self-reported {'inflight'} (surfaced as
        #: ``worker_reported_inflight`` in mesh.stats())
        self.heartbeat_info: Dict[str, object] = {}
        #: this incarnation's monotonic-clock offset estimate (min-
        #: filter over the ready handshake + every heartbeat) — remote
        #: span stamps shift by it at adoption, so cross-host stamps
        #: order correctly in the stitched tree
        self.clock = transport_lib.ClockOffset()
        #: the worker's last memory-ledger rollup ({attributed_bytes,
        #: budget_bytes, buckets}) — mesh.stats()'s per-worker HBM view
        self.ledger_info: Dict[str, object] = {}
        #: receiver-thread-only: last merged counter values, for the
        #: delta-inc fleet merge (fresh per incarnation, so counters
        #: accumulate across restarts)
        self._merge_last: Dict[str, float] = {}
        #: the ready handshake's {'params_step', 'capabilities'}
        self.ready_info: Dict[str, object] = {}
        ctx = multiprocessing.get_context('spawn')
        if channel is not None:
            # ADOPTED worker (SERVING.md "Elastic fleet"): an external
            # orchestrator exec'd scripts/mesh_worker.py against the
            # mesh listener and this dial-in arrived with an
            # unexpected rid.  There is no local process to spawn,
            # join, or supervise — restart supervision for adopted
            # workers is the orchestrator's job; a later death just
            # retires the slot.
            self._proc = None
            self._channel = channel
        elif mode == 'socket':
            address = listener.address
            self._channel = None  # claimed from the listener at ready
            self._proc = ctx.Process(
                target=_replica_worker_main,
                args=(rid, config_overrides, None, address), daemon=True)
            self._proc.start()
        else:
            self._conn, child = ctx.Pipe()
            self._proc = ctx.Process(
                target=_replica_worker_main,
                args=(rid, config_overrides, child, None), daemon=True)
            # spawn only: the worker's cold start (model build + warmup)
            # is the expensive part, and N replicas must pay it
            # CONCURRENTLY — the mesh constructs every transport first,
            # then wait_ready()s each, so fleet startup is ~one worker's
            # wall clock, not N of them
            self._proc.start()
            child.close()
            self._channel = transport_lib.PipeTransport(self._conn)
        self._lock = threading.Lock()
        self._pending: Dict[int, Tuple[List[_Request], int]] = {}
        self._seq = 0
        self._control: Dict[int, Future] = {}
        self._receiver: Optional[threading.Thread] = None

    def _reap_on_start_failure(self) -> None:
        """Failed-startup cleanup: a SPAWNED worker is reaped (process
        + channel); an ADOPTED one has no local process and its channel
        must stay open — the adoption path still owes the dial-in a
        typed ``adopt_rejected`` frame before the close."""
        if self._proc is not None:
            self.reap()

    def wait_ready(self) -> None:
        """Block until the worker reported ready, then start the
        receiver.  Must run before the first dispatch/control call.
        Interruptible via ``cancel()`` (a mesh closing mid-restart must
        not wait out a worker cold start)."""
        if self._receiver is not None:
            return
        deadline = time.perf_counter() + self._start_timeout_s
        if self._channel is None:
            # socket mode: the worker dials in; claim its validated
            # hello from the listener, pinned to THIS incarnation's
            # pid (a reaped predecessor's late hello must not be
            # handed to the restart)
            try:
                self._channel, _hello = self._listener.claim(
                    self.rid, self._start_timeout_s, cancel=self._cancel,
                    pid=self._proc.pid)
            except BaseException as exc:
                self._reap_on_start_failure()
                raise RuntimeError(
                    'mesh replica %s worker never dialed in: %r'
                    % (self.rid, exc))
        while not self._channel.poll(0.25):
            if self._cancel.is_set():
                self._reap_on_start_failure()
                raise RuntimeError('mesh replica %s startup cancelled '
                                   '(mesh closing)' % self.rid)
            if time.perf_counter() >= deadline:
                self._reap_on_start_failure()
                raise RuntimeError(
                    'mesh replica %s worker did not come up within %.0fs'
                    % (self.rid, self._start_timeout_s))
        try:
            msg = self._channel.recv()
        except (EOFError, OSError, WireError) as exc:
            # worker died before it could even report its failure
            self._reap_on_start_failure()
            raise RuntimeError(
                'mesh replica %s worker exited during startup (%r) — '
                'check the worker log; worker replicas need a '
                'checkpointed model with a retained step'
                % (self.rid, exc))
        if msg[0] == 'failed':
            self._reap_on_start_failure()
            raise RuntimeError('mesh replica %s worker failed to '
                               'start: %s' % (self.rid, msg[1]))
        if msg[0] != 'ready':
            self._reap_on_start_failure()
            raise RuntimeError('mesh replica %s worker failed to start: '
                               '%r' % (self.rid, msg))
        self.ready_info = msg[1] if len(msg) > 1 and \
            isinstance(msg[1], dict) else {}
        self.last_heartbeat = time.perf_counter()
        # first clock-offset sample: the ready frame carries the
        # worker's monotonic stamp (heartbeats refresh it from here on)
        self.clock.observe(self.ready_info.get('t_mono'),
                           self.last_heartbeat)
        self._receiver = threading.Thread(target=self._recv_loop,
                                          daemon=True,
                                          name='mesh-recv-%s' % self.rid)
        self._receiver.start()

    def _control_call(self, kind: str, *payload,
                      timeout: Optional[float] = 600.0):
        future: Future = Future()
        with self._lock:
            seq = self._seq
            self._seq += 1
            self._control[seq] = future
            self._channel.send((kind, seq) + payload)
        return future.result(timeout)

    def dispatch(self, tier: str, taken: List[_Request],
                 rows: int) -> None:
        batches = [request.batch for request in taken]
        # per-member trace context: the worker runs its engine spans
        # UNDER the parent's trace and ships them back for stitching
        # (None for untraced members — the worker records nothing).
        # Re-parenting happens PARENT-side at adoption (the member's
        # span_parent object), so the context stays minimal.
        # the scenario tag rides the dispatch trace context so the
        # worker-side envelope stays attributable per workload after
        # stitching (WORKLOADS.md; root attrs stamped at submit)
        ctxs = [None if request.trace is None else
                {'trace_id': request.trace.trace_id,
                 'sampled': request.trace.sampled,
                 'scenario': (request.trace.root.attrs
                              or {}).get('scenario')}
                for request in taken]
        seq = None
        try:
            with self._lock:
                seq = self._seq
                self._seq += 1
                self._pending[seq] = (taken, rows)
                self._channel.send(('dispatch', seq, tier, batches,
                                    ctxs))
        except BaseException as exc:
            entry = None
            if seq is not None:
                with self._lock:
                    entry = self._pending.pop(seq, None)
            # a dead wire at send time is a worker death with this batch
            # in flight: hand the members to the mesh's crash-safe
            # redispatch (first crash re-admits them at the queue front;
            # a second fails them typed), then re-raise so the puller's
            # breaker accounts the replica failure.  The receiver's EOF
            # path may race this — whoever pops the pending entry owns
            # the requests, so they are handled exactly once.
            if entry is not None and self._on_worker_dead is not None:
                try:
                    self._on_worker_dead(
                        self, [entry],
                        WireError('mesh replica %s wire send failed: %r'
                                  % (self.rid, exc)))
                except Exception:
                    for request in entry[0]:
                        request.fail(EngineClosed(
                            'mesh replica %s wire send failed: %r'
                            % (self.rid, exc)))
            raise
        # the worker pops its queue-wait here, not in an engine this
        # process can see: close the span at hand-off so queue time is
        # attributed, not smeared into the trace tail
        now = time.perf_counter()
        for request in taken:
            if request.queue_span is not None:
                request.trace.end(request.queue_span, now)
                request.queue_span = None

    def _recv_loop(self) -> None:
        while True:
            try:
                msg = self._channel.recv()
                # a partitioned network loses frames while both
                # endpoints stay up: results AND heartbeats vanish, so
                # the liveness monitor (not the breaker) is what
                # notices
                if faults.maybe_fire('partition'):
                    continue
                if msg[0] == 'heartbeat':
                    # schema-versioned typed payload: version skew
                    # between a worker and its mesh fails the replica
                    # TYPED through the one death path below, instead
                    # of feeding the telemetry merge a guessed pickle
                    # shape
                    transport_lib.check_heartbeat(msg[2])
            except (EOFError, OSError, WireError) as exc:
                # worker died (EOF) or its stream is poisoned (a partial
                # frame from a mid-write death fails TYPED instead of
                # misparsing every later frame): drain the in-flight
                # state once and report the death upward — the mesh
                # redispatches the batches and the supervisor restarts
                # the worker
                with self._lock:
                    pending = list(self._pending.values())
                    self._pending.clear()
                    control = list(self._control.values())
                    self._control.clear()
                dead = ReplicaDead(
                    'mesh replica %s worker died (%r) with %d '
                    'dispatch(es) in flight'
                    % (self.rid, exc, len(pending)))
                for future in control:
                    if not future.done():
                        future.set_exception(dead)
                if self._on_worker_dead is not None:
                    try:
                        self._on_worker_dead(self, pending, dead)
                    except Exception:
                        for taken, _rows in pending:
                            for request in taken:
                                request.fail(dead)
                else:
                    for taken, _rows in pending:
                        for request in taken:
                            request.fail(dead)
                return
            self.last_heartbeat = time.perf_counter()
            kind, seq = msg[0], msg[1]
            if kind == 'heartbeat':
                beat = msg[2]
                self.clock.observe(beat.t_mono, self.last_heartbeat)
                self.heartbeat_info = {'inflight': beat.inflight}
                if beat.ledger:
                    self.ledger_info = beat.ledger
                # spans orphaned from their result frame — finished
                # late, or about to be orphaned by a crash — ride the
                # beat and stitch while their dispatch is still pending
                self._adopt_pending_bundles(beat.spans)
                if beat.telemetry is not None and \
                        self._on_telemetry is not None:
                    try:
                        self._on_telemetry(self, beat.telemetry,
                                           beat.ledger)
                    except Exception:
                        pass  # the merge must never kill the receiver
                continue
            if kind in ('result', 'error'):
                with self._lock:
                    entry = self._pending.pop(seq, None)
                    ctrl = self._control.pop(seq, None)
                if entry is not None:
                    taken, rows = entry
                    if kind == 'result':
                        # graft the worker-side span records into the
                        # live traces BEFORE delivery finishes them —
                        # a finished trace is already serialized and
                        # cannot be stitched
                        self._adopt_member_bundles(
                            seq, taken, msg[3] if len(msg) > 3 else None)
                        for request, results in zip(taken, msg[2]):
                            request.deliver(results)
                            request.finish_trace()
                        self._on_batch_done(self, rows, taken, True)
                    else:
                        for request in taken:
                            request.fail(msg[2])
                        self._on_batch_done(self, rows, taken, False)
                elif ctrl is not None:
                    if kind == 'result':
                        _resolve(ctrl, msg[2])
                    elif not ctrl.done():
                        ctrl.set_exception(msg[2])
            elif kind == 'closed':
                with self._lock:
                    ctrl = self._control.pop(seq, None)
                if ctrl is not None:
                    _resolve(ctrl, None)
                return

    # ------------------------------------------------ trace stitching
    def _adopt_one(self, request: Optional[_Request],
                   spans: List[dict]) -> Tuple[int, int]:
        """Graft one bundle's records into its member's live trace;
        returns (adopted, dropped)."""
        if request is None or request.trace is None:
            return 0, len(spans)
        adopted = request.trace.adopt_spans(
            spans, self.clock.offset, parent=request.span_parent)
        return adopted, len(spans) - adopted

    def _adopt_member_bundles(self, seq: int, taken: List[_Request],
                              bundles) -> None:
        """Result-frame stitching: the worker's ``sink.collect(seq)``
        guarantees every bundle here belongs to THIS dispatch, so
        bundles align with its members by index (``seq`` double-checks
        the contract — a mismatch is dropped and counted, never
        mis-grafted; late bundles from other dispatches only ever
        travel on heartbeats)."""
        if not bundles:
            return
        adopted = dropped = 0
        for bundle in bundles:
            member = bundle.get('member')
            request = (taken[member]
                       if bundle.get('seq') == seq
                       and isinstance(member, int)
                       and 0 <= member < len(taken) else None)
            got, lost = self._adopt_one(request,
                                        bundle.get('spans') or [])
            adopted += got
            dropped += lost
        if (adopted or dropped) and self._on_spans is not None:
            self._on_spans(adopted, dropped)

    def _adopt_pending_bundles(self, bundles) -> None:
        """Heartbeat-ridden stitching: each bundle names its dispatch
        seq; bundles whose dispatch already concluded (their trace is
        finished and written) are counted dropped, not mis-grafted."""
        if not bundles:
            return
        adopted = dropped = 0
        for bundle in bundles:
            with self._lock:
                entry = self._pending.get(bundle.get('seq'))
            request = None
            if entry is not None:
                member = bundle.get('member')
                taken = entry[0]
                if isinstance(member, int) and 0 <= member < len(taken):
                    request = taken[member]
            got, lost = self._adopt_one(request,
                                        bundle.get('spans') or [])
            adopted += got
            dropped += lost
        if (adopted or dropped) and self._on_spans is not None:
            self._on_spans(adopted, dropped)

    @property
    def pid(self) -> Optional[int]:
        return self._proc.pid if self._proc is not None else None

    def cancel(self) -> None:
        """Abort a wait_ready in flight (mesh closing mid-restart)."""
        self._cancel.set()
        self.kill()

    def kill(self) -> None:
        """Hard-stop a hung or partitioned worker: SIGKILL + close the
        channel so the blocked receiver unblocks with EOF and the death
        path runs there exactly once."""
        try:
            if self._proc is not None and self._proc.is_alive():
                self._proc.kill()
        except Exception:
            pass
        try:
            if self._channel is not None:
                self._channel.close()
        except Exception:
            pass

    def reap(self) -> None:
        """Terminate + join a worker that is already dead or being
        abandoned, without the graceful close handshake."""
        self.kill()
        try:
            if self._proc is not None:
                self._proc.join(timeout=30.0)
        except Exception:
            pass

    def warmup(self) -> None:
        pass  # the worker warms before it reports ready

    def load_params(self, source, canary_batches: int,
                    min_agreement: float) -> Future:
        """Arm a canaried rollover IN the worker; the returned future
        resolves with the report (a parent-side waiter polls — the
        canary concludes on the worker's live dispatch traffic)."""
        if not isinstance(source, (int, str)) or isinstance(source, bool):
            raise RuntimeError(
                'worker-mode replicas roll over from checkpoint refs '
                '(step int or model path), not param pytrees — pytrees '
                'do not cross process (or host) boundaries')
        self._control_call('load_params', source, canary_batches,
                           min_agreement)
        handle: Future = Future()

        def wait() -> None:
            try:
                while True:
                    report = self._control_call('poll_rollover')
                    if report is not None:
                        _resolve(handle, report)
                        return
                    time.sleep(0.05)
            except BaseException as exc:
                if not handle.done():
                    handle.set_exception(exc)

        threading.Thread(target=wait, daemon=True,
                         name='mesh-canary-%s' % self.rid).start()
        return handle

    def adopt(self, params, source, step: Optional[int]) -> None:
        # cross-process fleet swap ships the checkpoint REF: the worker
        # restores it against its own abstract targets (canary already
        # validated the content on live traffic; canary_batches=0 swaps
        # without re-canarying)
        del params  # unused: pytrees do not cross the process wire
        self._control_call('load_params', source, 0, 0.0)
        while self._control_call('poll_rollover') is None:
            time.sleep(0.02)

    def stats(self) -> Dict[str, object]:
        return self._control_call('stats')

    def close(self) -> None:
        if self._receiver is None:
            # never became ready (a sibling's startup failed, or a
            # cancelled restart): nothing to hand-shake with — just
            # reap the worker
            self.reap()
            return
        try:
            self._control_call('close', timeout=60.0)
        except BaseException:
            pass  # a dead worker's wire refuses the handshake: reap it
        if self._receiver is not threading.current_thread():
            # the worker-dead path closes from the receiver itself
            self._receiver.join(timeout=30.0)
        if self._proc is not None:
            self._proc.join(timeout=60.0)
            if self._proc.is_alive():
                self._proc.terminate()
        if self._channel is not None:
            self._channel.close()


def _worker_ledger_rollup() -> Dict[str, object]:
    """Compact memory-ledger view for the heartbeat: enough for the
    mesh's per-worker HBM rollup (budget pressure visible BEFORE the
    remote worker OOMs), small enough to ride every beat."""
    from code2vec_tpu.telemetry import memory as memory_lib
    ledger = memory_lib.ledger()
    return {'attributed_bytes': ledger.attributed_bytes(),
            'budget_bytes': ledger.budget_bytes(),
            'buckets': {bucket: ledger.bucket_bytes(bucket)
                        for bucket in memory_lib.BUCKETS}}


def _replica_worker_main(rid: str, config_overrides: Dict[str, object],
                         conn, address) -> None:
    """Worker replica entry point (spawned): build the model from the
    shipped config, host one external-dispatch engine, serve the
    framed wire — a pipe connection (``conn``) in process mode, or a
    TCP dial to the mesh listener (``address``) in socket mode.  The
    protocol is identical either way."""
    import signal
    from code2vec_tpu import compile_cache
    from code2vec_tpu.config import Config
    from code2vec_tpu.model_api import Code2VecModel
    if conn is not None:
        channel = transport_lib.PipeTransport(conn)
    else:
        channel = transport_lib.dial(address, rid, os.getpid())
    # the heartbeat thread and the serve loop share the send side
    send_lock = threading.Lock()

    def send(message) -> None:
        with send_lock:
            channel.send(message)

    try:
        # before the worker's first compile: its 23+ warm-ladder programs
        # come from the shared persistent cache when a sibling built them
        compile_cache.configure()
        config = Config(**config_overrides)
        if config.MESH_TELEMETRY_BACKHAUL == 1:
            # the parent resolved the backhaul decision at spawn: with
            # it on, this worker's registry snapshots + ledger rollup
            # ride every heartbeat into the replica-labeled fleet merge
            from code2vec_tpu.telemetry.jit_tracker import \
                install_compile_listener
            tele_core.enable()
            install_compile_listener()
        model = Code2VecModel(config)
        engine = ServingEngine(
            config, model.trainer, model.params, model.vocabs,
            decode_table=model._target_index_to_word,
            tiers=config.serving_warm_tiers,
            param_source=model._serving_param_source(),
            replica_id=rid, external_dispatch=True, log=config.log)
        engine.warmup()
    except BaseException as exc:
        # the parent must learn WHY this replica died, not just see an
        # EOF on the wire (a missing retained step, a model-build
        # failure, ...)
        try:
            send(('failed', repr(exc)))
        except BaseException:
            pass
        raise
    rollover: Dict[str, object] = {'handle': None}
    inflight = [0]
    stop_beats = threading.Event()
    # worker-side half of cross-process stitching: member traces run
    # under the parent's shipped contexts and their finished span
    # records backhaul on the result frame (or a heartbeat)
    sink = tracing_lib.RemoteSpanSink(rid)

    def beat_loop() -> None:
        """Liveness, decoupled from dispatch: a dispatch-busy worker
        still beats; a hung or drilled one goes silent and the mesh
        liveness monitor — not the breaker — declares it dead.  The
        typed payload also carries the observability backhaul: span
        records not yet shipped on a result frame, the telemetry
        registry snapshot, and the memory-ledger rollup."""
        period = float(config.MESH_HEARTBEAT_SECS)
        if period <= 0:
            return
        while not stop_beats.wait(period):
            if faults.maybe_fire('drop_heartbeat'):
                continue  # the drilled shape of a hung worker
            backhaul = config.MESH_TELEMETRY_BACKHAUL == 1
            try:
                # the whole backhaul honors the off switch: with it
                # off, beats carry liveness + the clock stamp only
                telemetry = (tele_core.registry().snapshot()
                             if backhaul and tele_core.enabled()
                             else None)
                ledger = _worker_ledger_rollup() if backhaul else None
            except Exception:
                telemetry, ledger = None, None
            try:
                send(('heartbeat', -1, transport_lib.Heartbeat(
                    inflight=inflight[0],
                    t_mono=time.perf_counter(),
                    # age-gated: a just-finished bundle belongs to its
                    # own result frame; one still here after ~a beat
                    # has missed it (stall or crash-in-progress) and
                    # ships now
                    spans=sink.drain(min_age_s=period / 2),
                    telemetry=telemetry,
                    ledger=ledger)))
            except BaseException:
                return  # wire gone: the serve loop is exiting too

    if faults.maybe_fire('adopt_stall'):
        # the drilled shape of a worker wedging between dial-in and
        # ready: the mesh's bounded adoption wait (or startup timeout)
        # must drop it typed instead of hanging the adoption thread
        time.sleep(faults.ADOPT_STALL_SECONDS)
    engine_stats = engine.stats()
    send(('ready', {
        'params_step': engine_stats.get('params_step'),
        't_mono': time.perf_counter(),
        # 'devices' is the placement view: under MESH_DEVICE_INDICES
        # this worker's sub-mesh covers exactly its slice, and the
        # mesh's stats/assertions read the slice from here
        'capabilities': {'tiers': list(config.serving_warm_tiers),
                         'wire': config.BATCH_WIRE_FORMAT,
                         'proto': transport_lib.WIRE_PROTO,
                         'devices': [int(d.id) for d in
                                     model.mesh.devices.flatten()]},
    }))
    beats = threading.Thread(target=beat_loop, daemon=True,
                             name='mesh-beat-%s' % rid)
    beats.start()
    try:
        while True:
            msg = channel.recv()
            kind, seq = msg[0], msg[1]
            try:
                if kind == 'dispatch':
                    if faults.maybe_fire('kill_worker'):
                        # mid-batch SIGKILL: the parent has this
                        # dispatch in _pending, so the drill exercises
                        # exactly the crash-safe redispatch path
                        os.kill(os.getpid(), signal.SIGKILL)
                    tier, batches = msg[2], msg[3]
                    ctxs = (msg[4] if len(msg) > 4
                            else [None] * len(batches))
                    requests = []
                    for member, (batch, ctx) in enumerate(
                            zip(batches, ctxs)):
                        trace = (sink.begin('serving.remote', ctx, seq,
                                            member)
                                 if ctx is not None else None)
                        requests.append(_Request(batch, tier,
                                                 future=Future(),
                                                 trace=trace))
                    rows = sum(request.rows for request in requests)
                    inflight[0] += 1
                    try:
                        engine.dispatch_external(tier, requests, rows)
                        results = [request.future.result(timeout=600)
                                   for request in requests]
                    finally:
                        inflight[0] -= 1
                    # member traces finish on the decode threads right
                    # after the futures resolve; wait them out so the
                    # result frame carries the full bundle set (a late
                    # finisher rides the next heartbeat instead)
                    sink.wait_finished([r.trace for r in requests],
                                       timeout=5.0)
                    if faults.maybe_fire('kill_worker_after_execute'):
                        # die AFTER the device work but BEFORE the
                        # result frame: the finished spans ride a
                        # heartbeat (the beat thread drains the sink),
                        # then the SIGKILL orphans the batch — the
                        # stitched-trace drill's way of proving a
                        # redispatched request shows BOTH incarnations'
                        # device work
                        time.sleep(max(0.5,
                                       3 * config.MESH_HEARTBEAT_SECS))
                        os.kill(os.getpid(), signal.SIGKILL)
                    send(('result', seq, results, sink.collect(seq)))
                elif kind == 'load_params':
                    source, n_canary, floor = msg[2], msg[3], msg[4]
                    rollover['handle'] = engine.load_params(
                        source, canary_batches=n_canary,
                        min_agreement=floor)
                    send(('result', seq, True))
                elif kind == 'poll_rollover':
                    handle = rollover['handle']
                    if handle is not None and handle.done():
                        rollover['handle'] = None
                        send(('result', seq, handle.result()))
                    else:
                        send(('result', seq, None))
                elif kind == 'stats':
                    send(('result', seq, engine.stats()))
                elif kind == 'close':
                    engine.close()
                    send(('closed', seq))
                    return
                else:
                    raise RuntimeError('unknown mesh wire message %r'
                                       % (kind,))
            except BaseException as exc:
                try:
                    send(('error', seq, exc))
                except BaseException:
                    send(('error', seq, RuntimeError(repr(exc))))
    finally:
        stop_beats.set()
        engine.close()


# ----------------------------------------------------------------- mesh
class ServingMesh:
    """N serving replicas, one shared front queue.  Build via
    ``Code2VecModel.serving_mesh()``; the API mirrors the single
    engine's (``submit`` / ``predict`` / ``submit_neighbors`` /
    ``load_params`` / ``follow_checkpoints`` / ``close``)."""

    # the replica table, fleet service window, rollover slot, restart
    # hand-off and close flags are shared by submitters, N pullers,
    # decode-completion hooks, the supervisor, the liveness monitor,
    # and control calls (lock-discipline rule, ANALYSIS.md); _cond
    # wraps _lock:
    # graftlint: guard ServingMesh._closed,_drain,_rollover,_index_rollover,_index_version,_params_step,_rows_total,_service_window,_service_window_rows,_service_rows_per_s,_restart_pending,_next_rid by _lock|_cond
    def __init__(self, model, replicas: Optional[int] = None,
                 tiers: Optional[Sequence[str]] = None,
                 mode: Optional[str] = None,
                 max_delay_ms: Optional[float] = None,
                 deadline_ms: Optional[float] = None,
                 queue_bound: Optional[int] = None,
                 max_inflight: Optional[int] = None,
                 breaker_threshold: Optional[int] = None,
                 breaker_cooldown_secs: Optional[float] = None,
                 canary_batches: Optional[int] = None,
                 canary_agreement: Optional[float] = None,
                 params_step: Optional[int] = None,
                 memo_cache_bytes: Optional[int] = None,
                 memo_semantic_epsilon: Optional[float] = None,
                 heartbeat_secs: Optional[float] = None,
                 heartbeat_misses: Optional[int] = None,
                 restart_limit: Optional[int] = None,
                 restart_window_secs: Optional[float] = None,
                 restart_backoff_secs: Optional[float] = None,
                 tracer: Optional[tracing_lib.Tracer] = None,
                 tracing_sample_rate: Optional[float] = None,
                 log=None):
        config = model.config
        self.config = config
        self.log = log if log is not None else config.log
        n = int(replicas if replicas is not None else config.MESH_REPLICAS)
        if n < 1:
            raise ValueError('a mesh needs >= 1 replica, got %d' % n)
        self.mode = mode if mode is not None else config.MESH_REPLICA_MODE
        if self.mode not in ('thread', 'process', 'socket'):
            raise ValueError("MESH_REPLICA_MODE must be 'thread', "
                             "'process' or 'socket', got %r"
                             % (self.mode,))
        if self.mode != 'thread':
            # every worker-mode build spawns its n workers on THIS
            # machine, from this process — which holds the model's
            # devices
            if mesh_lib.mesh_platform(model.mesh) == 'tpu':
                raise LocalWorkerNeedsHeldChip(
                    "MESH_REPLICA_MODE=%r spawns its workers locally, and "
                    'this process holds the TPU they would need (a chip '
                    'belongs to one process; the child fails with "The '
                    'TPU is already in use by process with pid %d"). Use '
                    "MESH_REPLICA_MODE='thread' on this host, or run "
                    'scripts/mesh_worker.py on another machine.'
                    % (self.mode, os.getpid()))
        # ---- self-healing knobs (SERVING.md "Multi-host mesh") ----
        self.heartbeat_secs = float(
            heartbeat_secs if heartbeat_secs is not None
            else config.MESH_HEARTBEAT_SECS)
        self.heartbeat_misses = max(1, int(
            heartbeat_misses if heartbeat_misses is not None
            else config.MESH_HEARTBEAT_MISSES))
        self.restart_limit = max(0, int(
            restart_limit if restart_limit is not None
            else config.MESH_RESTART_LIMIT))
        self.restart_window_s = float(
            restart_window_secs if restart_window_secs is not None
            else config.MESH_RESTART_WINDOW_SECS)
        self.restart_backoff_s = float(
            restart_backoff_secs if restart_backoff_secs is not None
            else config.MESH_RESTART_BACKOFF_SECS)
        tiers = tuple(tiers if tiers is not None
                      else config.serving_warm_tiers)
        for tier in tiers:
            if tier not in PREDICT_TIERS:
                raise ValueError('unknown tier %r; expected a subset of '
                                 '%s' % (tier, PREDICT_TIERS))
        self.tiers = tiers
        self.max_delay_s = (max_delay_ms if max_delay_ms is not None
                            else config.SERVING_MAX_DELAY_MS) / 1e3
        deadline_ms = (deadline_ms if deadline_ms is not None
                       else config.SERVING_DEADLINE_MS)
        self.deadline_s = deadline_ms / 1e3 if deadline_ms > 0 else None
        self.max_inflight = max(1, int(
            max_inflight if max_inflight is not None
            else config.MESH_MAX_INFLIGHT))
        self.breaker_threshold = max(1, int(
            breaker_threshold if breaker_threshold is not None
            else config.MESH_BREAKER_THRESHOLD))
        self.breaker_cooldown_s = float(
            breaker_cooldown_secs if breaker_cooldown_secs is not None
            else config.MESH_BREAKER_COOLDOWN_SECS)
        self.canary_batches = (canary_batches
                               if canary_batches is not None
                               else config.SERVING_CANARY_BATCHES)
        self.canary_agreement = (canary_agreement
                                 if canary_agreement is not None
                                 else config.SERVING_CANARY_AGREEMENT)
        # submit-side tokenizer + ladder geometry (identical to every
        # replica's: same config, same mesh data axis — which is what
        # makes admitted results bit-identical to a single engine's)
        self._reader = PathContextReader(model.vocabs, config,
                                         EstimatorAction.Predict)
        # ---- per-replica device placement (SERVING.md "Elastic
        # fleet") ----  MESH_DEVICES_PER_REPLICA partitions
        # jax.devices() into disjoint contiguous slices; each worker
        # builds its own sub-mesh over its slice, so N replicas on one
        # host stop contending for the same chips.
        self.devices_per_replica = max(
            0, int(config.MESH_DEVICES_PER_REPLICA))
        self._placement: Optional[List[List[int]]] = None
        if self.devices_per_replica > 0:
            if self.mode == 'thread':
                raise ValueError(
                    'MESH_DEVICES_PER_REPLICA needs a worker mode '
                    "(MESH_REPLICA_MODE 'process' or 'socket'): thread "
                    "replicas dispatch through the parent trainer's "
                    'programs, which are compiled over the FULL parent '
                    'mesh and cannot be re-placed per replica')
            # carve enough slices for the autoscaler's ceiling, not
            # just the build-time fleet: scale-up must never fail on
            # placement the mesh could have reserved up front
            n_slices = n
            if config.AUTOSCALE_MAX_REPLICAS > 0:
                n_slices = max(n, int(config.AUTOSCALE_MAX_REPLICAS))
            self._placement = mesh_lib.partition_device_indices(
                n_slices, self.devices_per_replica)
        if self._placement is not None:
            # placement on: the submit-side geometry follows a SLICE's
            # data axis, not the parent mesh's — a parent-ladder top
            # bucket wider than the slice ladder's would tokenize
            # batches no replica has a warm program for
            self.data_axis = (self.devices_per_replica
                              // max(1, int(config.MESH_MODEL_AXIS_SIZE)))
        else:
            self.data_axis = model.mesh.shape[mesh_lib.DATA_AXIS]
        self.buckets = engine_lib.batch_ladder(
            config.serving_batch_buckets, self.data_axis)
        bound = (queue_bound if queue_bound is not None
                 else config.MESH_QUEUE_BOUND)
        # auto bound scales WITH the fleet: every replica adds its share
        # of absorbable backlog
        self.queue_bound: Optional[int] = (
            None if bound < 0 else
            n * 8 * self.buckets[-1] if bound == 0 else bound)
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._closed = False
        self._drain = False
        self._rollover: Optional[Dict[str, object]] = None
        # index rollover (canaried index swap — the params-canary
        # machinery generalized to indexes): candidate + live-traffic
        # shadow-query agreement state, armed by rollover_index()
        self._index_rollover: Optional[Dict[str, object]] = None
        self._index_version = 0
        self._rows_total = 0
        # fleet service window: same estimator the engine runs, fed by
        # EVERY replica's completions — the fleet-wide drain rate
        self._service_rows_per_s = 0.0
        self._service_window: collections.deque = collections.deque()
        self._service_window_rows = 0
        if params_step is not None:
            self._params_step: Optional[int] = params_step
        elif model.state is not None:
            self._params_step = int(model.state.step)
        else:
            self._params_step = None
        self._param_source = model._serving_param_source()
        self._follow_thread: Optional[threading.Thread] = None
        self._follow_stop = threading.Event()
        # self-healing state: the close event interrupts supervisor
        # backoffs; _restart_pending is the transport a restart is
        # readying (close() cancels it so fail-fast close never waits
        # out — or leaks — a worker cold start)
        self._close_event = threading.Event()
        self._restart_pending: Optional[_WorkerReplica] = None
        self._supervisor: Optional[threading.Thread] = None
        self._liveness_thread: Optional[threading.Thread] = None
        self._listener: Optional[transport_lib.SocketListener] = None
        self._model_config_overrides: Optional[Dict[str, object]] = None
        # elastic fleet (SERVING.md "Elastic fleet"): scale-up needs
        # the model handle to build new replicas; adoption needs a
        # thread watching the listener for dial-ins the mesh never
        # spawned; rids stay unique across scale-downs and -ups
        self._model = model
        self._next_rid = n
        self._adopt_thread: Optional[threading.Thread] = None
        #: externally-owned workers' ready wait (dial-in -> ready
        #: frame): covers the dialed-in worker's cold start — it dials
        #: FIRST, then builds + warms (scripts/mesh_worker.py).  Drills
        #: shorten it to exercise adopt_stall.
        self.adopt_ready_timeout_s = 600.0
        self._autoscaler = None
        # instruments (mesh-level; per-replica series ride the engines'
        # replica-labeled mirrors)
        self.requests_total = Counter('mesh/requests_total')
        self.rollover_total = Counter('mesh/rollover_total')
        self.rollover_rollbacks_total = Counter(
            'mesh/rollover_rollbacks_total')
        self.index_rollover_total = Counter('index/rollovers_total')
        self.index_rollover_rollbacks_total = Counter(
            'index/rollover_rollbacks_total')
        self.index_rollover_agreement = Gauge(
            'index/rollover_agreement')
        self.breaker_open_total = Counter(
            'mesh/replica_breaker_open_total')
        self.replicas_gauge = Gauge('mesh/replicas')
        self.serving_gauge = Gauge('mesh/replicas_serving')
        self.live_gauge = Gauge('mesh/replicas_live')
        self.restarts_total = Counter('mesh/restarts_total')
        self.redispatched_total = Counter('mesh/redispatched_total')
        # elastic-fleet accounting: WHY replicas leave, and how many
        # external workers the mesh adopted vs turned away
        self.retired_total = Counter('mesh/retired_total')
        self.adopted_total = Counter('mesh/adopted_total')
        self.adoption_rejected_total = Counter(
            'mesh/adoption_rejected_total')
        self.heartbeat_misses_total = Counter(
            'mesh/heartbeat_misses_total')
        # fleet observability plane (OBSERVABILITY.md "Fleet
        # observability"): stitching + backhaul accounting
        self.adopted_spans_total = Counter('tracing/adopted_spans_total')
        self.remote_spans_dropped_total = Counter(
            'tracing/remote_spans_dropped_total')
        self.worker_snapshots_total = Counter(
            'mesh/worker_snapshots_total')
        # tracing: ONE tracer shared with every thread-mode replica, so
        # the flight recorder and span log see the whole fleet
        rate = (tracing_sample_rate if tracing_sample_rate is not None
                else config.tracing_sample_rate)
        # same ownership rule as the engine: an injected tracer is the
        # caller's to close
        self._owns_tracer = tracer is None
        if tracer is not None:
            self._tracer: Optional[tracing_lib.Tracer] = tracer
        elif rate > 0:
            out_dir = None
            if getattr(config, 'TELEMETRY_DIR', None) or \
                    config.is_saving or config.is_loading:
                from code2vec_tpu.telemetry.stepwatch import telemetry_dir
                out_dir = telemetry_dir(config)
            self._tracer = tracing_lib.Tracer(
                out_dir, sample_rate=rate,
                slow_ms=config.TRACING_SLOW_MS,
                flight_traces=config.TRACING_FLIGHT_TRACES,
                log=self.log)
        else:
            self._tracer = None
        # SLO burn-rate monitor (serving/slo.py): availability + p99
        # targets over the fleet's completion stream, alarming into the
        # shared flight recorder
        self._slo: Optional[slo_lib.SloMonitor] = None
        if config.SERVING_SLO_AVAILABILITY > 0 or \
                config.SERVING_SLO_P99_MS > 0:
            self._slo = slo_lib.SloMonitor(
                availability=config.SERVING_SLO_AVAILABILITY,
                p99_ms=config.SERVING_SLO_P99_MS,
                fast_window_s=config.SERVING_SLO_FAST_WINDOW_SECS,
                slow_window_s=config.SERVING_SLO_SLOW_WINDOW_SECS,
                burn_threshold=config.SERVING_SLO_BURN_THRESHOLD,
                tracer=self._tracer, log=self.log)
        self._queue = FrontQueue(tiers, self.queue_bound,
                                 fleet_rate=self._fleet_rate,
                                 log=self.log)
        self._index = None
        # scenario traffic plane (workloads/profile.py): optional
        # ProfileRecorder tapped at admission by submit/submit_neighbors/
        # submit_blended; armed via record_traffic(), never re-armed
        # concurrently with traffic in this codebase's use, so reads
        # need no lock (a racy None just skips one record)
        self._traffic_recorder = None
        self._aux_pool = ThreadPoolExecutor(max_workers=2,
                                            thread_name_prefix='mesh-aux')
        # memoization tier (serving/memo.py, SERVING.md "Memoization
        # tier"): checked at submit BEFORE tokenize/admit; built once
        # here and never reassigned, so reads need no lock
        memo_bytes = int(memo_cache_bytes if memo_cache_bytes is not None
                         else config.MEMO_CACHE_BYTES)
        epsilon = float(memo_semantic_epsilon
                        if memo_semantic_epsilon is not None
                        else config.MEMO_SEMANTIC_EPSILON)
        self._memo: Optional[memo_lib.MemoCache] = (
            memo_lib.MemoCache(memo_bytes, semantic_epsilon=epsilon,
                               params_step=self._params_step,
                               log=self.log)
            if memo_bytes > 0 else None)
        # ---- replica table ----
        self._replicas: List[_ReplicaSlot] = []
        try:
            if self.mode == 'socket':
                # workers dial in: the listener must be up before the
                # first spawn.  MESH_SOCKET_HOST is the bind address —
                # 127.0.0.1 keeps spawned-local workers loopback-only;
                # a routable address lets workers on other machines
                # dial the same wire.
                self._listener = transport_lib.SocketListener(
                    config.MESH_SOCKET_HOST)
            if self.mode != 'thread':
                self._model_config_overrides = \
                    self._process_config_overrides(model)
            for i in range(n):
                rid = 'r%d' % i
                if self.mode == 'thread':
                    engine = ServingEngine(
                        config, model.trainer, model.params, model.vocabs,
                        decode_table=model._target_index_to_word,
                        tiers=tiers,
                        deadline_ms=0.0, queue_bound=-1,
                        canary_batches=self.canary_batches,
                        canary_agreement=self.canary_agreement,
                        param_source=self._param_source,
                        params_step=self._params_step,
                        tracer=self._tracer,
                        tracing_sample_rate=(0.0 if self._tracer is None
                                             else None),
                        replica_id=rid, external_dispatch=True,
                        on_batch_done=self._on_batch_done,
                        log=self.log)
                    transport = _ThreadReplica(engine)
                    device_indices = None
                else:
                    device_indices = self._allocate_slice_locked()
                    transport = self._spawn_worker(rid, device_indices)
                slot = _ReplicaSlot(rid, transport)
                slot.device_indices = device_indices
                self._replicas.append(slot)
            for slot in self._replicas:
                # process workers spawned above cold-start in parallel;
                # this pass just collects their 'ready' handshakes
                slot.transport.wait_ready()
        except BaseException:
            self._queue.close()
            for slot in self._replicas:
                try:
                    slot.transport.close()
                except BaseException:
                    pass
            if self._listener is not None:
                self._listener.close()
            self._aux_pool.shutdown(wait=False)
            raise
        self.replicas_gauge.set(n)
        if tele_core.enabled():
            tele_core.registry().gauge('mesh/replicas').set(n)
        self._set_serving_gauge_locked_free()
        self._set_live_gauge_locked_free()
        for slot in self._replicas:
            slot.thread = threading.Thread(
                target=self._pull_loop, args=(slot, slot.transport),
                daemon=True, name='mesh-pull-%s' % slot.rid)
            slot.thread.start()
        if self.mode != 'thread':
            # the self-healing layer: supervisor restarts dead workers
            # under the window-scoped budget; the liveness monitor
            # detects hung/partitioned workers the breaker cannot see
            self._supervisor = threading.Thread(
                target=self._supervise_loop, daemon=True,
                name='mesh-supervisor')
            self._supervisor.start()
            if self.heartbeat_secs > 0:
                self._liveness_thread = threading.Thread(
                    target=self._liveness_loop, daemon=True,
                    name='mesh-liveness')
                self._liveness_thread.start()
        if self.mode == 'socket':
            # adoption (SERVING.md "Elastic fleet"): dial-ins with a
            # rid the mesh never spawned are externally-owned workers
            # asking to join; this thread validates and seats them
            self._adopt_thread = threading.Thread(
                target=self._adoption_loop, daemon=True,
                name='mesh-adopt')
            self._adopt_thread.start()
        if config.AUTOSCALE_MAX_REPLICAS > 0:
            from code2vec_tpu.serving.autoscaler import Autoscaler
            self._autoscaler = Autoscaler(self, config,
                                          tracer=self._tracer,
                                          log=self.log)
            self._autoscaler.start()

    def _allocate_slice_locked(self) -> Optional[List[int]]:
        """First free device slice of the placement table (None with
        placement off).  Slices held by non-retired slots are taken —
        a retired slot's slice is free for the next scale-up; a
        restart reuses its own slot's slice without coming here."""
        if self._placement is None:
            return None
        used = {tuple(s.device_indices) for s in self._replicas
                if s.device_indices is not None and not s.retired}
        for indices in self._placement:
            if tuple(indices) not in used:
                return list(indices)
        raise RuntimeError(
            'no free device slice: %d slices of %d device(s) are all '
            'held by serving replicas (raise AUTOSCALE_MAX_REPLICAS/'
            'MESH_REPLICAS only as far as the placement table allows)'
            % (len(self._placement), self.devices_per_replica))

    def _spawn_worker(self, rid: str,
                      device_indices: Optional[List[int]] = None
                      ) -> '_WorkerReplica':
        """One worker transport (initial fleet build, supervised
        restart AND autoscaler scale-up): the worker cold-starts from
        the checkpoint store and reports ready over the framed wire."""
        if faults.maybe_fire('spawn_fail'):
            raise RuntimeError(
                'FAULT_INJECT spawn_fail: worker %s spawn refused '
                'before process start' % rid)
        overrides = dict(self._model_config_overrides)
        if overrides.get('MESH_TELEMETRY_BACKHAUL', -1) == -1:
            # resolve the backhaul AUTO at SPAWN time, not mesh build:
            # a telemetry enable after the mesh came up must reach
            # every later-restarted (or scaled-up) worker, or the
            # fleet merge silently stays partial
            overrides['MESH_TELEMETRY_BACKHAUL'] = (
                1 if tele_core.enabled() else 0)
        if device_indices:
            # placement: the worker builds its sub-mesh over exactly
            # this slice (parallel/mesh.py create_mesh)
            overrides['MESH_DEVICE_INDICES'] = ','.join(
                str(i) for i in device_indices)
        if self._listener is not None:
            # register the rid BEFORE the process exists: a dial-in
            # racing this registration must land in the claim table,
            # not the adoption queue
            self._listener.expect(rid)
        return _WorkerReplica(
            rid, self.mode, overrides,
            on_batch_done=self._on_worker_batch_done,
            on_worker_dead=self._on_worker_dead,
            on_telemetry=self._on_worker_telemetry,
            on_spans=self._note_stitched,
            listener=self._listener, log=self.log)

    # ------------------------------------------------- process plumbing
    def _process_config_overrides(self, model) -> Dict[str, object]:
        """The config a process replica rebuilds its model from: the
        parent's fields, pointed at the parent's checkpoint path
        (pytrees don't cross processes; params come from the store)."""
        import dataclasses
        config = model.config
        load_path = (config.MODEL_LOAD_PATH if config.is_loading
                     else config.MODEL_SAVE_PATH
                     if config.is_saving else None)
        if load_path is None:
            raise RuntimeError(
                "MESH_REPLICA_MODE='%s' needs a checkpointed model "
                '(a --save or --load path with at least one retained '
                'step): worker processes restore params from the store, '
                'they cannot share the parent\'s arrays' % self.mode)
        overrides = {}
        for field in dataclasses.fields(type(config)):
            value = getattr(config, field.name, None)
            if isinstance(value, (bool, int, float, str, type(None))):
                overrides[field.name] = value
        overrides['MODEL_LOAD_PATH'] = load_path
        overrides['MODEL_SAVE_PATH'] = ''
        overrides['TRAIN_DATA_PATH_PREFIX'] = ''
        overrides['SERVE_FOLLOW_CHECKPOINTS_SECS'] = 0.0
        # the worker beats at the MESH's resolved period, not whatever
        # the config default says — a constructor override that only
        # reached the liveness monitor would make a healthy worker
        # look dead (monitor dividing by a shorter period than the
        # worker beats at) and grind the restart budget down
        overrides['MESH_HEARTBEAT_SECS'] = self.heartbeat_secs
        # the worker warms the MESH's resolved tiers, not whatever the
        # parent's SERVING_WARM_TIERS default says — a tier the caller
        # added (submit_neighbors' 'vectors') must be warm in every
        # replica, or its first dispatch compiles on the serving path
        overrides['SERVING_WARM_TIERS'] = ','.join(self.tiers)
        return overrides

    # -------------------------------------------- fleet observability
    def _note_stitched(self, adopted: int, dropped: int) -> None:
        """Stitching accounting (receiver threads): spans grafted into
        live traces vs arrived too late to stitch."""
        if adopted:
            self.adopted_spans_total.inc(adopted)
        if dropped:
            self.remote_spans_dropped_total.inc(dropped)
        if tele_core.enabled():
            reg = tele_core.registry()
            if adopted:
                reg.counter('tracing/adopted_spans_total').inc(adopted)
            if dropped:
                reg.counter(
                    'tracing/remote_spans_dropped_total').inc(dropped)

    def _note_retired(self, reason: str) -> None:
        """Retirement accounting: the unlabeled total plus a
        reason-labeled series (mirrors the dispatch_share labeling
        idiom) — a post-mortem can tell budget-retire from drain from
        an orchestrator-owned worker exiting."""
        self.retired_total.inc()
        if tele_core.enabled():
            from code2vec_tpu.telemetry import catalog
            reg = tele_core.registry()
            reg.counter('mesh/retired_total').inc()
            reg.counter(catalog.labeled(
                'mesh/retired_total', 'reason', reason)).inc()

    def _on_worker_telemetry(self, transport, snapshot,
                             ledger) -> None:
        """Fleet merge (one worker heartbeat): label the worker's
        registry snapshot with its replica id and fold it into THIS
        process's registry, so the existing JSONL/Prometheus exporters
        emit ONE fleet export — worker series land exactly where a
        thread-mode replica's ScopedRegistry mirror would put them.
        Counters merge by delta (a restarted incarnation resets its
        own counts; the fleet series keeps accumulating), gauges by
        last-write, timers as MirrorTimer stat adoptions."""
        del ledger  # rides transport.ledger_info for stats(); the
        #             mem/* gauges arrive via the snapshot itself
        self.worker_snapshots_total.inc()
        if not tele_core.enabled():
            return
        from code2vec_tpu.telemetry import catalog
        reg = tele_core.registry()
        reg.counter('mesh/worker_snapshots_total').inc()
        reg.gauge(catalog.labeled(
            'mesh/clock_offset_ms', 'replica', transport.rid)).set(
                transport.clock.offset * 1e3)
        for name, value in (snapshot or {}).items():
            base, label = catalog.split_label(name)
            meta = catalog.CATALOG.get(base)
            if meta is None:
                continue  # uncataloged names never enter the export
            target = (name if label is not None else
                      catalog.labeled(name, 'replica', transport.rid))
            if isinstance(value, dict):
                reg.mirror_timer(target).adopt(value)
            elif meta['type'] == catalog.COUNTER:
                last = transport._merge_last.get(target, 0)
                delta = value if value < last else value - last
                transport._merge_last[target] = value
                if delta:
                    reg.counter(target).inc(int(delta))
            else:
                try:
                    reg.gauge(target).set(float(value))
                except (TypeError, ValueError):
                    continue

    # ----------------------------------------------------- fleet rate
    def _fleet_rate(self) -> float:
        with self._lock:
            return self._service_rows_per_s

    def _note_service_locked(self, rows: int,
                             taken: List[_Request]) -> None:
        """The engine's windowed throughput estimator
        (engine.note_service_window), fed by EVERY replica's
        completions: the window sum over its span IS the fleet-wide
        served-rows/s the shared admission divides deadlines by."""
        oldest = (min(request.t_enqueue for request in taken)
                  if taken else None)
        self._service_window_rows, self._service_rows_per_s = \
            engine_lib.note_service_window(
                self._service_window, self._service_window_rows,
                self._service_rows_per_s, rows, oldest)

    # ------------------------------------------------ replica weighting
    def _slot_cap_locked(self, slot: _ReplicaSlot) -> int:
        """In-flight window of one replica — the dispatch weight.  A
        canarying replica is halved (still pulling: the canary needs
        live traffic), a half-open breaker probes ONE batch."""
        if slot.breaker_state == _BREAKER_HALF_OPEN:
            return 1
        if slot.canarying:
            return max(1, self.max_inflight // 2)
        return self.max_inflight

    def _slot_ready_locked(self, slot: _ReplicaSlot,
                           transport) -> str:
        """'ready' | 'wait' | 'exit' for one puller iteration."""
        if slot.retired or slot.dead or slot.transport is not transport:
            return 'exit'  # dead/replaced incarnation: its puller dies
        if self._closed and not self._drain:
            return 'exit'
        if slot.breaker_state == _BREAKER_OPEN:
            if time.perf_counter() >= slot.breaker_open_until:
                slot.breaker_state = _BREAKER_HALF_OPEN
                self.log('mesh: replica %s breaker half-open (probing '
                         'one batch)' % slot.rid)
            else:
                return 'wait'
        if slot.inflight >= self._slot_cap_locked(slot):
            return 'wait'
        return 'ready'

    def _slot_alive(self, slot: _ReplicaSlot, transport) -> bool:
        """The queue-side claim check a puller passes to
        ``pop_coalesced``: a replica that retired, died, was replaced,
        or tripped its breaker while waiting must leave WITHOUT taking
        work."""
        with self._lock:
            return not (slot.retired or slot.dead
                        or slot.transport is not transport
                        or slot.breaker_state == _BREAKER_OPEN
                        or (self._closed and not self._drain))

    def _set_serving_gauge_locked_free(self) -> None:
        # reads immutable-ish counts outside the lock on purpose: the
        # gauge is advisory, and both call paths immediately follow a
        # locked mutation
        serving = sum(1 for slot in self._replicas
                      if not slot.retired and not slot.dead
                      and slot.breaker_state != _BREAKER_OPEN)
        self.serving_gauge.set(serving)
        if tele_core.enabled():
            tele_core.registry().gauge(
                'mesh/replicas_serving').set(serving)

    def _set_live_gauge_locked_free(self) -> None:
        # the liveness verdict, distinct from dispatch health: a
        # breaker-open replica is still LIVE (its worker heartbeats),
        # a dead one is not.  Thread replicas share this process's
        # liveness by construction.
        live = sum(1 for slot in self._replicas
                   if not slot.retired and not slot.dead)
        self.live_gauge.set(live)
        if tele_core.enabled():
            tele_core.registry().gauge('mesh/replicas_live').set(live)

    # -------------------------------------------------------- pull loop
    def _pull_loop(self, slot: _ReplicaSlot, transport) -> None:
        # `transport` pins this puller to ONE incarnation: after a
        # supervised restart the slot carries a fresh transport and a
        # fresh puller — a straggler from the dead incarnation exits
        # instead of dispatching onto a wire it no longer owns
        while True:
            with self._cond:
                while True:
                    state = self._slot_ready_locked(slot, transport)
                    if state == 'exit':
                        return
                    if state == 'ready':
                        break
                    # bounded wait: breaker cooldowns expire on the
                    # clock, not on a notification
                    self._cond.wait(0.05)
            popped = self._queue.pop_coalesced(
                self.buckets[-1], self.max_delay_s,
                alive=lambda: self._slot_alive(slot, transport),
                claim=transport)
            if popped is None:
                # depth read BEFORE taking the mesh lock: pop_coalesced
                # holds the queue lock while it calls back into the
                # mesh's alive() (queue->mesh order), so the mesh lock
                # must never wait on the queue lock (AB-BA deadlock); a
                # stale depth just loops once more
                depth = self._queue.depth_rows()
                with self._lock:
                    if slot.retired or slot.dead or \
                            slot.transport is not transport or \
                            (self._closed and not self._drain):
                        return
                    if self._closed and depth == 0:
                        return
                continue
            tier, taken, rows, expired = popped
            for request in expired:
                request.fail(DeadlineExceeded(
                    'request expired after %.0fms in the mesh queue '
                    '(SLO deadline %.0fms)'
                    % (1e3 * (time.perf_counter() - request.t_enqueue),
                       1e3 * (request.t_deadline - request.t_enqueue))))
            if not taken:
                continue  # a sibling drained the tier during coalesce
            with self._cond:
                slot.inflight += 1
                probing = slot.breaker_state == _BREAKER_HALF_OPEN
            try:
                transport.dispatch(tier, taken, rows)
            except BaseException as exc:
                # the member requests are already handled (thread mode:
                # dispatch_external failed them typed; worker mode: the
                # wire-send failure routed them through crash-safe
                # redispatch); here the BREAKER accounts the replica
                # failure
                self._dispatch_failed(slot, rows, probing, exc)
                continue
            # completion: thread transport via the engine's decode
            # worker (_on_batch_done), worker transports via their
            # receiver thread — nothing more to do here either way

    def _dispatch_failed(self, slot: _ReplicaSlot, rows: int,
                         probing: bool, exc: BaseException) -> None:
        del rows, probing
        with self._cond:
            slot.inflight = max(0, slot.inflight - 1)
            self._breaker_failure_locked(slot)
            self._cond.notify_all()
        self._queue.kick()
        self.log('mesh: replica %s dispatch failed (%s): %d consecutive'
                 % (slot.rid, exc, slot.breaker_fails))

    def _breaker_failure_locked(self, slot: _ReplicaSlot) -> None:
        slot.breaker_fails += 1
        if slot.breaker_state == _BREAKER_HALF_OPEN or \
                slot.breaker_fails >= self.breaker_threshold:
            if slot.breaker_state != _BREAKER_OPEN:
                self.breaker_open_total.inc()
                if tele_core.enabled():
                    tele_core.registry().counter(
                        'mesh/replica_breaker_open_total').inc()
                self.log('mesh: replica %s dispatch breaker OPEN for '
                         '%.0fs (%d consecutive failures); queue '
                         'redirects to the remaining replicas'
                         % (slot.rid, self.breaker_cooldown_s,
                            slot.breaker_fails))
            slot.breaker_state = _BREAKER_OPEN
            slot.breaker_open_until = (time.perf_counter()
                                       + self.breaker_cooldown_s)
        self._set_serving_gauge_locked_free()

    def _on_batch_done(self, engine, rows: int, taken: List[_Request],
                       ok: bool) -> None:
        """Thread-mode completion hook (runs on the replica engine's
        decode worker)."""
        slot = next(s for s in self._replicas
                    if isinstance(s.transport, _ThreadReplica)
                    and s.transport.engine is engine)
        self._complete(slot, rows, taken, ok)

    def _on_worker_batch_done(self, transport, rows: int,
                              taken: List[_Request], ok: bool) -> None:
        slot = next((s for s in self._replicas
                     if s.transport is transport), None)
        if slot is None:
            return  # a stale completion from a replaced incarnation
        self._complete(slot, rows, taken, ok)

    # ------------------------------------------------------ self-healing
    def _on_worker_dead(self, transport,
                        pending: List[Tuple[List[_Request], int]],
                        reason: BaseException) -> None:
        """A worker replica died — EOF, a corrupt frame, a wire-send
        failure, or a liveness kill.  Mark the slot dead TYPED (the
        supervisor restarts it under the budget; the breaker's
        half-open probe never sacrifices a real micro-batch to a
        corpse), then crash-safe-redispatch the batches that died with
        it: members are re-admitted ONCE at the front of the shared
        queue with this incarnation excluded and their deadlines
        intact, so the crash costs latency, not answers."""
        adopted_exit = False
        with self._cond:
            slot = next((s for s in self._replicas
                         if s.transport is transport), None)
            if slot is not None and not slot.retired and not slot.dead:
                slot.dead = True
                slot.inflight = 0
                if slot.adopted:
                    # restart supervision for an adopted worker belongs
                    # to the ORCHESTRATOR that spawned it: retire the
                    # slot instead of charging the LOCAL restart budget
                    # (a redial lands as a fresh adoption); its
                    # in-flight batches still redispatch below
                    slot.retired = True
                    slot.retired_reason = 'adopted_worker_exit'
                    adopted_exit = True
                self._cond.notify_all()  # puller exits, supervisor wakes
        requeued = failed = 0
        for taken, _rows in pending:
            got = self._redispatch_batch(transport, slot, taken, reason)
            requeued += got
            failed += len(taken) - got
        if adopted_exit:
            self._note_retired('adopted_worker_exit')
        self._set_serving_gauge_locked_free()
        self._set_live_gauge_locked_free()
        self._queue.kick()
        if adopted_exit:
            self.log('mesh: ADOPTED replica %s worker exited (%s): %d '
                     'request(s) redispatched, %d failed typed; its '
                     'orchestrator owns the restart — the local budget '
                     'is not charged'
                     % (slot.rid, reason, requeued, failed))
            self._fail_queue_if_fleet_empty()
        else:
            self.log('mesh: replica %s worker DEAD (%s): %d request(s) '
                     'redispatched to the front of the queue, %d failed '
                     'typed; supervisor will restart it within the '
                     'budget'
                     % (slot.rid if slot is not None else '?', reason,
                        requeued, failed))
        try:
            transport.reap()  # the corpse: SIGKILL + join, no handshake
        except Exception:
            pass

    def _redispatch_batch(self, token, slot: Optional[_ReplicaSlot],
                          taken: List[_Request],
                          reason: BaseException) -> int:
        """Re-admit the members of one crashed batch at the FRONT of
        their tier queue (once per request — a second crash fails them
        typed ``ReplicaDead``).  Returns how many were re-admitted."""
        survivors: List[_Request] = []
        for request in taken:
            if request.trace is not None and \
                    request.queue_span is not None:
                # the wire-send-failure path reaches here with the
                # FIRST queue_wait span still open (the hand-off close
                # only runs after a successful send): end it so the
                # redispatch attempt's span doesn't orphan it
                request.trace.end(request.queue_span)
                request.queue_span = None
            if request.redispatched:
                request.fail(ReplicaDead(
                    'request lost its replica twice (%s); failing '
                    'typed instead of bouncing forever' % reason))
                continue
            request.redispatched = True
            request.exclude = token
            if request.trace is not None:
                # the trace shows BOTH attempts: the first queue_wait/
                # dispatch, this event, then a second queue_wait
                request.trace.event(
                    'serving.redispatch', parent=request.span_parent,
                    attrs={'replica': slot.rid if slot else '?',
                           'reason': str(reason)})
                request.queue_span = request.trace.span(
                    'serving.queue_wait', parent=request.span_parent)
            survivors.append(request)
        if not survivors:
            return 0
        if not self._queue.requeue_front(survivors[0].tier, survivors):
            # mesh closed fail-fast between death and redispatch
            for request in survivors:
                request.fail(EngineClosed(
                    'ServingMesh closed before the crashed batch could '
                    'be redispatched'))
            return 0
        self.redispatched_total.inc(len(survivors))
        if tele_core.enabled():
            tele_core.registry().counter(
                'mesh/redispatched_total').inc(len(survivors))
        return len(survivors)

    def _liveness_loop(self) -> None:
        """Heartbeat monitor: liveness DISTINCT from dispatch health.
        A hung or partitioned worker with nothing in flight looks
        healthy to the breaker (no dispatch fails); its missing
        heartbeats are what betray it.  Past the miss budget the
        replica is killed — the receiver's EOF then runs the one death
        path (redispatch + supervised restart)."""
        period = self.heartbeat_secs
        while not self._close_event.wait(period):
            if self._slo is not None:
                # periodic burn-gauge refresh: exported burns decay
                # after traffic stops instead of freezing at the last
                # burst's value
                self._slo.refresh()
            now = time.perf_counter()
            with self._cond:
                watched = [(s, s.transport) for s in self._replicas
                           if not s.retired and not s.dead
                           and not s.restarting
                           and isinstance(s.transport, _WorkerReplica)]
            for slot, transport in watched:
                missed = (now - transport.last_heartbeat) / period
                if missed < 1.0:
                    continue
                self.heartbeat_misses_total.inc()
                if tele_core.enabled():
                    tele_core.registry().counter(
                        'mesh/heartbeat_misses_total').inc()
                if missed > self.heartbeat_misses:
                    self.log('mesh: replica %s missed %d heartbeats '
                             '(budget %d) — hung or partitioned; '
                             'marking dead and killing the worker'
                             % (slot.rid, int(missed),
                                self.heartbeat_misses))
                    # the kill forces the receiver's EOF: death
                    # handling (redispatch + supervisor) runs there
                    # exactly once
                    transport.kill()

    def _supervise_loop(self) -> None:
        """Supervised restart: a dead locally-spawned worker comes back
        on its own — exponential backoff, a window-scoped restart
        budget (a flapping worker retires permanently instead of
        storming), cold start from the checkpoint store, then
        re-adoption onto the fleet's CURRENT params step before its
        puller touches the queue."""
        while True:
            retire = False
            with self._cond:
                slot = None
                while slot is None:
                    if self._closed:
                        return
                    slot = next((s for s in self._replicas
                                 if s.dead and not s.retired
                                 and not s.adopted
                                 and not s.restarting), None)
                    if slot is None:
                        self._cond.wait(0.2)
                now = time.perf_counter()
                while slot.restart_times and \
                        now - slot.restart_times[0] > self.restart_window_s:
                    slot.restart_times.popleft()
                if len(slot.restart_times) >= self.restart_limit:
                    slot.retired = True
                    slot.retired_reason = 'restart_budget'
                    retire = True
                else:
                    slot.restarting = True
                    slot.restart_times.append(now)
                attempt = len(slot.restart_times)
                self._cond.notify_all()
            if retire:
                self.log('mesh: replica %s spent its restart budget '
                         '(%d in %.0fs) — retiring permanently; the '
                         'queue serves through the remaining replicas'
                         % (slot.rid, self.restart_limit,
                            self.restart_window_s))
                self._note_retired('restart_budget')
                self._set_serving_gauge_locked_free()
                self._set_live_gauge_locked_free()
                self._fail_queue_if_fleet_empty()
                continue
            backoff = self.restart_backoff_s * (2 ** (attempt - 1))
            if backoff > 0 and self._close_event.wait(min(backoff, 30.0)):
                with self._cond:
                    slot.restarting = False
                return
            self.log('mesh: restarting replica %s (attempt %d in '
                     'window, backoff %.2fs)'
                     % (slot.rid, attempt, backoff))
            transport = None
            try:
                # a placed replica restarts onto ITS OWN slice: the
                # warm ladder it cold-starts is placement-identical to
                # the incarnation it replaces
                transport = self._spawn_worker(slot.rid,
                                               slot.device_indices)
                with self._lock:
                    self._restart_pending = transport
                if self._close_event.is_set():
                    # close() may have read _restart_pending before the
                    # assignment above: cancel ourselves so the cold
                    # start is never leaked
                    transport.cancel()
                transport.wait_ready()
                # the worker cold-started from the checkpoint store;
                # re-adopt it onto the fleet's CURRENT step — which may
                # have rolled while it was down — BEFORE it pulls.  An
                # in-flight rollover concludes first, so the step read
                # here is the one the fleet actually settled on.
                with self._cond:
                    while self._rollover is not None and \
                            not self._closed:
                        self._cond.wait(0.1)
                    fleet_step = self._params_step
                worker_step = transport.ready_info.get('params_step')
                if fleet_step is not None and worker_step != fleet_step:
                    self.log('mesh: replica %s rejoined at step %s; '
                             're-adopting the fleet\'s current step %d'
                             % (slot.rid, worker_step, fleet_step))
                    transport.adopt(None, fleet_step, fleet_step)
            except BaseException as exc:
                with self._lock:
                    self._restart_pending = None
                if transport is not None:
                    try:
                        transport.reap()
                    except Exception:
                        pass
                with self._cond:
                    slot.restarting = False  # still dead: retry/budget
                if self._close_event.is_set():
                    return
                self.log('mesh: replica %s restart failed (%r); '
                         'retrying under the budget' % (slot.rid, exc))
                continue
            with self._cond:
                self._restart_pending = None
                if self._closed:
                    closed = True
                else:
                    closed = False
                    slot.transport = transport
                    slot.dead = False
                    slot.restarting = False
                    slot.inflight = 0
                    slot.breaker_fails = 0
                    slot.breaker_state = _BREAKER_CLOSED
                    slot.restarts += 1
                    slot.thread = threading.Thread(
                        target=self._pull_loop, args=(slot, transport),
                        daemon=True, name='mesh-pull-%s' % slot.rid)
                    slot.thread.start()
                    self._cond.notify_all()
            if closed:
                transport.close()
                return
            self.restarts_total.inc()
            if tele_core.enabled():
                tele_core.registry().counter('mesh/restarts_total').inc()
            self._set_serving_gauge_locked_free()
            self._set_live_gauge_locked_free()
            self._queue.kick()
            self.log('mesh: replica %s restarted and rejoined the '
                     'fleet (serving step %s)'
                     % (slot.rid,
                        transport.ready_info.get('params_step')
                        if fleet_step is None else fleet_step))

    def _fail_queue_if_fleet_empty(self) -> None:
        """Every replica permanently retired: admitted work can never
        be served — close the queue and fail it typed instead of
        hanging.  Closing (not just abandoning) also covers the racing
        submitter that passed submit's unlocked all-retired check
        before the last retirement landed: its enqueue re-checks the
        queue's closed flag and raises typed, so nothing can ever
        strand in a queue with zero pullers."""
        with self._cond:
            if not all(s.retired for s in self._replicas):
                return
            self._closed = True  # no replica can ever serve again
            self._cond.notify_all()
        self.log('mesh: NO serving replicas remain; failing the queue '
                 'typed')
        self._queue.close()
        for request in self._queue.abandon():
            request.fail(ReplicaDead(
                'every mesh replica has retired; the queue cannot '
                'drain'))

    # --------------------------------------------------- elastic fleet
    def add_replica(self) -> str:
        """Scale the fleet UP by one locally-built replica (the
        autoscaler's spawn leg; also a public operator verb).  Worker
        modes spawn + cold-start a new worker — on its own device
        slice under placement — and re-adopt it onto the fleet's
        CURRENT params step before its puller touches the queue;
        thread mode builds a sibling engine over the shared trainer
        (cache-hit warmup, zero new compiles).  Returns the new rid."""
        with self._cond:
            if self._closed:
                raise EngineClosed('ServingMesh is closed')
            rid = 'r%d' % self._next_rid
            self._next_rid += 1
            device_indices = (None if self.mode == 'thread'
                              else self._allocate_slice_locked())
            seed_step = self._params_step
        if self.mode == 'thread':
            model = self._model
            engine = ServingEngine(
                self.config, model.trainer, model.params, model.vocabs,
                decode_table=model._target_index_to_word,
                tiers=self.tiers,
                deadline_ms=0.0, queue_bound=-1,
                canary_batches=self.canary_batches,
                canary_agreement=self.canary_agreement,
                param_source=self._param_source,
                params_step=seed_step,
                tracer=self._tracer,
                tracing_sample_rate=(0.0 if self._tracer is None
                                     else None),
                replica_id=rid, external_dispatch=True,
                on_batch_done=self._on_batch_done,
                log=self.log)
            engine.warmup()  # trainer jit caches: cache-hit, 0 compiles
            transport = _ThreadReplica(engine)
            # the model's pytree may predate a fleet rollover: adopt
            # the CURRENT params from a serving sibling (pointer swap)
            with self._cond:
                donor = next(
                    (s for s in self._replicas
                     if isinstance(s.transport, _ThreadReplica)
                     and not s.retired and not s.dead), None)
                step = self._params_step
            if donor is not None:
                engine.adopt_params(donor.transport.engine.params,
                                    step=step)
        else:
            transport = self._spawn_worker(rid, device_indices)
            try:
                transport.wait_ready()
                # wait out an in-flight rollover, then serve the step
                # the fleet settled on (the supervisor's re-adoption
                # leg, reused for scale-up)
                with self._cond:
                    while self._rollover is not None and \
                            not self._closed:
                        self._cond.wait(0.1)
                    fleet_step = self._params_step
                worker_step = transport.ready_info.get('params_step')
                if fleet_step is not None and worker_step != fleet_step:
                    transport.adopt(None, fleet_step, fleet_step)
            except BaseException:
                try:
                    transport.reap()
                except Exception:
                    pass
                raise
        self._seat_replica(rid, transport, device_indices,
                           adopted=False)
        self.log('mesh: scaled UP — replica %s joined the fleet%s'
                 % (rid, (' on devices %s' % (device_indices,))
                    if device_indices else ''))
        return rid

    def _seat_replica(self, rid: str, transport,
                      device_indices: Optional[List[int]],
                      adopted: bool) -> None:
        """Append a ready transport to the replica table and start its
        puller (scale-up and adoption share this tail)."""
        with self._cond:
            if self._closed:
                closed = True
            else:
                closed = False
                slot = _ReplicaSlot(rid, transport)
                slot.adopted = adopted
                slot.device_indices = device_indices
                self._replicas.append(slot)
                slot.thread = threading.Thread(
                    target=self._pull_loop, args=(slot, transport),
                    daemon=True, name='mesh-pull-%s' % rid)
                slot.thread.start()
                self._cond.notify_all()
        if closed:
            try:
                transport.close()
            except BaseException:
                pass
            raise EngineClosed('ServingMesh closed during scale-up')
        self.replicas_gauge.set(len(self._replicas))
        if tele_core.enabled():
            tele_core.registry().gauge(
                'mesh/replicas').set(len(self._replicas))
        self._set_serving_gauge_locked_free()
        self._set_live_gauge_locked_free()
        self._queue.kick()

    def _adoption_loop(self) -> None:
        """Socket mode: seat externally-spawned workers.  A dial-in
        whose rid the mesh never registered (``SocketListener``'s
        unclaimed path) is an orchestrator-owned worker asking to
        join: validate its capabilities, re-adopt it onto the fleet's
        current step, and give it a puller — or turn it away typed."""
        while not self._close_event.is_set():
            got = self._listener.wait_adoptable(
                0.25, cancel=self._close_event)
            if got is None:
                continue
            rid, channel, _hello = got
            try:
                self._adopt_dialin(rid, channel)
            except EngineClosed:
                try:
                    channel.close()
                except BaseException:
                    pass
                return
            except BaseException as exc:
                self.adoption_rejected_total.inc()
                if tele_core.enabled():
                    tele_core.registry().counter(
                        'mesh/adoption_rejected_total').inc()
                self.log('mesh: adoption of dial-in %r REJECTED: %s'
                         % (rid, exc))
                try:
                    # typed answer before the close: the worker (and
                    # its orchestrator's logs) learn WHY
                    channel.send(('adopt_rejected', str(exc)))
                except BaseException:
                    pass
                try:
                    channel.close()
                except BaseException:
                    pass

    def _adopt_dialin(self, rid: str, channel) -> None:
        """Validate + seat ONE adoptable dial-in (raises
        ``AdoptionRejected`` to turn it away typed)."""
        with self._lock:
            if self._closed:
                raise EngineClosed('ServingMesh is closed')
            if any(s.rid == rid and not s.retired
                   for s in self._replicas):
                raise AdoptionRejected(
                    'rid %r already names a serving replica in this '
                    'fleet; external workers need unique --rid values'
                    % rid)
        transport = _WorkerReplica(
            rid, 'socket', {},
            on_batch_done=self._on_worker_batch_done,
            on_worker_dead=self._on_worker_dead,
            on_telemetry=self._on_worker_telemetry,
            on_spans=self._note_stitched,
            listener=self._listener, log=self.log,
            start_timeout_s=self.adopt_ready_timeout_s,
            channel=channel)
        try:
            transport.wait_ready()
        except BaseException as exc:
            raise AdoptionRejected(
                'worker %r dialed in but never reported ready within '
                '%.0fs: %r' % (rid, self.adopt_ready_timeout_s, exc))
        caps = transport.ready_info.get('capabilities') or {}
        try:
            if caps.get('proto') != transport_lib.WIRE_PROTO:
                raise AdoptionRejected(
                    'worker %r speaks wire proto %r, this mesh speaks '
                    '%d' % (rid, caps.get('proto'),
                            transport_lib.WIRE_PROTO))
            if caps.get('wire') != self.config.BATCH_WIRE_FORMAT:
                raise AdoptionRejected(
                    'worker %r ships batches as %r, this mesh expects '
                    '%r' % (rid, caps.get('wire'),
                            self.config.BATCH_WIRE_FORMAT))
            missing = set(self.tiers) - set(caps.get('tiers') or ())
            if missing:
                raise AdoptionRejected(
                    'worker %r did not warm tier(s) %s this mesh '
                    'serves; its first dispatch there would compile on '
                    'the serving path' % (rid, sorted(missing)))
            # re-adopt onto the fleet's CURRENT step — an adoption
            # landing mid-rollover waits the rollover out first, so
            # the step read here is the one the fleet settled on
            with self._cond:
                while self._rollover is not None and not self._closed:
                    self._cond.wait(0.1)
                if self._closed:
                    raise EngineClosed('ServingMesh is closed')
                fleet_step = self._params_step
            worker_step = transport.ready_info.get('params_step')
            if fleet_step is not None and worker_step != fleet_step:
                self.log('mesh: adopting %s at step %s; re-adopting '
                         'the fleet\'s current step %d'
                         % (rid, worker_step, fleet_step))
                transport.adopt(None, fleet_step, fleet_step)
        except BaseException as exc:
            try:
                # typed answer BEFORE tearing the wire down (cancel
                # closes the channel; the adoption loop's fallback
                # send would find it already gone)
                channel.send(('adopt_rejected', str(exc)))
            except BaseException:
                pass
            try:
                transport.cancel()  # stop the receiver; close the wire
            except BaseException:
                pass
            raise
        devices = caps.get('devices')
        self._seat_replica(rid, transport,
                           list(devices) if devices else None,
                           adopted=True)
        self.adopted_total.inc()
        if tele_core.enabled():
            tele_core.registry().counter('mesh/adopted_total').inc()
        self.log('mesh: ADOPTED externally-spawned worker %s (step %s, '
                 'devices %s); restart supervision stays with its '
                 'orchestrator'
                 % (rid, transport.ready_info.get('params_step'),
                    devices))

    def _complete(self, slot: _ReplicaSlot, rows: int,
                  taken: List[_Request], ok: bool) -> None:
        with self._cond:
            # clamp: a partitioned worker's late delivery can land
            # after its death handler already zeroed the window
            slot.inflight = max(0, slot.inflight - 1)
            if ok:
                slot.breaker_fails = 0
                if slot.breaker_state != _BREAKER_CLOSED:
                    slot.breaker_state = _BREAKER_CLOSED
                    self.log('mesh: replica %s breaker closed (probe '
                             'succeeded)' % slot.rid)
                    self._set_serving_gauge_locked_free()
                slot.rows_dispatched += rows
                slot.batches += 1
                self._rows_total += rows
                self._note_service_locked(rows, taken)
                if tele_core.enabled() and self._rows_total > 0:
                    # per-replica dispatch share: replica-labeled series
                    # under one catalog family
                    from code2vec_tpu.telemetry import catalog
                    tele_core.registry().gauge(catalog.labeled(
                        'mesh/dispatch_share', 'replica',
                        slot.rid)).set(
                            slot.rows_dispatched / self._rows_total)
            else:
                self._breaker_failure_locked(slot)
            self._cond.notify_all()
        self._queue.kick()

    # ----------------------------------------------------------- submit
    def submit(self, context_lines: Sequence[str], tier: str = 'topk',
               deadline_ms: Optional[float] = None,
               scenario: Optional[str] = None,
               language: Optional[str] = None,
               record: bool = True, observe: bool = True) -> Future:
        """Enqueue one prediction request on the SHARED front queue;
        whichever free replica claims it serves it.  Same contract as
        ``ServingEngine.submit`` (typed sheds, oversize split, Future
        of one result per line).

        ``scenario``/``language`` tag the request for the scenario
        traffic plane (WORKLOADS.md): the scenario rides the trace root
        attrs (and from there the dispatch context), labels the memo
        hit/miss mirrors and the SLO observations.  ``record=False``
        skips the admission traffic tap, ``observe=False`` skips the
        SLO observation — both used by composing entry points
        (``submit_neighbors``/``submit_blended``) that tap and observe
        once at their own outer future."""
        if tier not in self.tiers:
            raise ValueError('tier %r is not warmed on this mesh '
                             '(tiers=%s)' % (tier, list(self.tiers)))
        # retirement is monotonic, so this unlocked scan can only be
        # conservatively stale: once every replica has permanently
        # retired, admitting more work would hang it forever (checked
        # before the generic closed flag — the fleet-empty path sets
        # both, and the specific reason is the useful one)
        if all(slot.retired for slot in self._replicas):
            raise EngineClosed(
                'every mesh replica has retired (restart budgets '
                'spent); the mesh cannot serve')
        # graftlint: disable=lock-discipline -- benign racy fast-fail: a close() racing past this read is re-checked inside FrontQueue.enqueue
        if self._closed:
            raise EngineClosed('ServingMesh is closed')
        t_submit0 = time.perf_counter()
        # ONE definition of request identity across engine + mesh +
        # memo key (data/reader.py canonicalize_contexts; idempotent at
        # fixed MAX_CONTEXTS — process_input_rows applies it again at
        # tokenize).  MAX_CONTEXTS must reach the FIRST call: it
        # truncates in extraction order before the canonical sort.
        lines = canonicalize_contexts(context_lines,
                                      self.config.MAX_CONTEXTS)
        future: Future = Future()
        if not lines:
            future.set_result([])
            return future
        if record:
            self._record_traffic(scenario or 'softmax_naming', lines,
                                 language=language, tier=tier)
        n = len(lines)
        if deadline_ms is None:
            deadline_s = self.deadline_s
        else:
            deadline_s = deadline_ms / 1e3 if deadline_ms > 0 else None
        self.requests_total.inc()
        if tele_core.enabled():
            tele_core.registry().counter('mesh/requests_total').inc()
        trace = None
        if self._tracer is not None:
            attrs = {'tier': tier, 'rows': n, 'mesh': True,
                     'deadline_ms': (1e3 * deadline_s
                                     if deadline_s else None)}
            if scenario is not None:
                attrs['scenario'] = scenario
            trace = self._tracer.begin('serving.request', attrs=attrs)
        requested_tier = tier
        # memoization tier: content-addressed exact lookup BEFORE
        # tokenize and FrontQueue.admit — a hit resolves the future
        # right here, costing zero device-seconds and no queue slot
        memo = self._memo
        memo_key = None
        if memo is not None:
            memo_key = memo_lib.request_key(lines, tier)
            # the exact tier STANDS DOWN while a canary is in flight:
            # duplicate-heavy traffic served from cache would starve
            # the canary's shadow scorer of batches and the rollover
            # would never conclude — during a canary every request
            # runs live (inserts still happen; the generation check
            # keeps any result in flight across the swap out)
            rolling = self._rollover is not None  # graftlint: disable=lock-discipline -- benign racy read: a stale None serves one more hit, a stale rollover runs one more request live
            cached = None if rolling else memo.lookup(memo_key,
                                                      scenario=scenario)
            if cached is not None:
                if trace is not None:
                    trace.event('serving.memo_hit',
                                attrs={'tier': tier, 'rows': n,
                                       'memo': 'exact'})
                    trace.finish(status='ok')
                if observe and self._slo is not None:
                    self._slo.observe_good(
                        time.perf_counter() - t_submit0,
                        scenario=scenario)
                # lookup returned a fresh copy (memo_lib.copy_results):
                # mutating it cannot poison later hits on this key
                future.set_result(cached)
                return future
        t_admit0 = time.perf_counter()
        try:
            tier = self._queue.admit(n, tier, deadline_s)
        except EngineOverloaded as exc:
            if trace is not None:
                trace.event('serving.shed', attrs={'reason': str(exc)})
                trace.finish(status='shed')
                self._tracer.note_shed()
            if observe and self._slo is not None:
                self._slo.observe_bad('shed', scenario=scenario)
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
            requests = engine_lib.tokenize_and_chunk(
                self._reader, lines, tier, future, deadline_s, trace,
                t_admit1, self.buckets[-1])
        except BaseException as exc:
            self._queue.release_reservation(n)
            if trace is not None:
                trace.finish(status='error', reason=repr(exc))
            raise
        for request in requests:
            if request.trace is not None:
                request.queue_span = request.trace.span(
                    'serving.queue_wait', parent=request.span_parent,
                    t0=request.t_enqueue)
        try:
            self._queue.enqueue(tier, requests, n)
        except EngineClosed:
            if trace is not None:
                trace.event('serving.closed',
                            attrs={'reason': 'ServingMesh is closed'})
                trace.finish(status='closed')
            raise
        if observe and self._slo is not None:
            # one SLO event per CALLER-VISIBLE request, observed at its
            # future — an oversize submit's chunk fan-out must not
            # inflate the good count, and one failed chunk fails the
            # whole answer, burning one full budget unit.  Shed-at-
            # admission is counted at the raise above (the future is
            # never returned); a close-time EngineClosed flood is
            # shutdown, not an SLO violation, and stays out.
            slo, t_admitted, scen = self._slo, t_admit0, scenario

            def _slo_observe(done: Future) -> None:
                try:
                    exc = done.exception()
                except BaseException:
                    return  # caller cancelled: not the server's verdict
                if exc is None:
                    slo.observe_good(time.perf_counter() - t_admitted,
                                     scenario=scen)
                elif not isinstance(exc, EngineClosed):
                    slo.observe_bad(type(exc).__name__, scenario=scen)

            future.add_done_callback(_slo_observe)
        if memo is not None:
            # insert-on-delivery: only a good caller-visible result is
            # cached (fires after oversize chunk re-join); key on the
            # EFFECTIVE tier so a degraded-tier answer can never poison
            # the full-tier key the next caller will look up
            insert_key = (memo_key if tier == requested_tier
                          else memo_lib.request_key(lines, tier))
            generation = memo.generation

            def _memo_insert(done: Future) -> None:
                try:
                    exc = done.exception()
                except BaseException:
                    return  # caller cancelled: nothing was delivered
                if exc is None:
                    memo.insert(insert_key, done.result(), generation)

            future.add_done_callback(_memo_insert)
        return future

    def predict(self, context_lines: Sequence[str], tier: str = 'topk',
                timeout: Optional[float] = None) -> list:
        """Synchronous ``submit().result()`` convenience."""
        return self.submit(context_lines, tier).result(timeout)

    # ------------------------------------------- scenario traffic plane
    def record_traffic(self, recorder) -> 'ServingMesh':
        """Arm (or with ``None`` disarm) the admission traffic tap: a
        ``workloads.profile.ProfileRecorder`` that sees every caller-
        visible submit/submit_neighbors/submit_blended with its scenario
        label, for later durable save + replay (WORKLOADS.md)."""
        self._traffic_recorder = recorder
        return self

    def _record_traffic(self, scenario: str, lines=None, vector=None,
                        language: Optional[str] = None,
                        tier: Optional[str] = None,
                        k: Optional[int] = None,
                        weight: Optional[float] = None) -> None:
        recorder = self._traffic_recorder
        if recorder is None:
            return
        label = None
        if lines:
            # recorded label = the method's true name, recoverable from
            # the context-line head (extractor output contract); lets a
            # replay score quality without a separate label channel
            label = lines[0].split(' ', 1)[0] or None
        try:
            recorder.record(scenario, language=language, lines=lines,
                            vector=vector, label=label, tier=tier,
                            k=k, weight=weight)
        except Exception as exc:  # the tap must never fail a request
            self.log('traffic tap dropped a record: %r' % (exc,))

    # -------------------------------------------------------- neighbors
    def attach_index(self, index) -> 'ServingMesh':
        """Arm ``submit_neighbors``: neighbor queries ride the shared
        dispatch stream's 'vectors' tier, then the attached index (one
        index serves the whole fleet — it is device-resident once)."""
        if 'vectors' not in self.tiers:
            raise ValueError(
                "submit_neighbors needs the 'vectors' tier warmed on "
                'this mesh (tiers=%s)' % list(self.tiers))
        self._index = index
        return self

    def submit_neighbors(self, context_or_vectors,
                         k: Optional[int] = None,
                         scenario: Optional[str] = None,
                         language: Optional[str] = None,
                         record: bool = True,
                         observe: bool = True) -> Future:
        """Mesh analogue of ``ServingEngine.submit_neighbors``: context
        lines ride the micro-batched 'vectors' tier ACROSS the fleet,
        the resulting code vectors feed the shared index.  Scenario
        plumbing as in ``submit``; the inner 'vectors' leg never taps
        or observes on its own (record/observe gating)."""
        index = self._index
        if index is None:
            raise RuntimeError('no index attached — call '
                               'attach_index(load_index(...)) first')
        k = k if k is not None else self.config.INDEX_NEIGHBORS_K
        from code2vec_tpu.index.service import neighbors_from_search
        t_submit0 = time.perf_counter()
        outer: Future = Future()
        memo = self._memo
        scenario_name = scenario or 'neighbor_search'
        # BOTH memo tiers stand down while a canary rollover is in
        # flight, exactly as submit() does: duplicate-heavy neighbors
        # traffic served from cache would starve the canary's shadow
        # scorer of batches and the rollover would never conclude
        # (inserts still happen; the generation check keeps any result
        # in flight across the swap out).  An INDEX rollover stands
        # the neighbor memo down for the same reason: its shadow
        # queries ride live neighbor traffic
        rolling = self._rollover is not None  # graftlint: disable=lock-discipline -- benign racy read: a stale None serves one more hit, a stale rollover runs one more request live
        rolling = rolling or self._index_rollover is not None  # graftlint: disable=lock-discipline -- same benign racy read for the index-rollover axis
        if isinstance(context_or_vectors, np.ndarray):
            vectors = np.atleast_2d(context_or_vectors)
            if record:
                for row in vectors:
                    self._record_traffic(
                        scenario_name, vector=[float(x) for x in row],
                        language=language, k=k)
            shadow_row = None
            if memo is not None and not rolling and vectors.shape[0] == 1:
                # semantic tier: serve a within-epsilon single-row query
                # from a near-identical prior request's cached result
                sem = memo.semantic_lookup(vectors[0], k)
                if sem is not None:
                    sem_row, shadow = sem
                    if not shadow:
                        if self._tracer is not None:
                            attrs = {'tier': 'neighbors', 'rows': 1,
                                     'mesh': True}
                            if scenario is not None:
                                attrs['scenario'] = scenario
                            trace = self._tracer.begin(
                                'serving.request', attrs=attrs)
                            trace.event('serving.memo_hit',
                                        attrs={'tier': 'neighbors',
                                               'rows': 1,
                                               'memo': 'semantic'})
                            trace.finish(status='ok')
                        # cache-served requests stay in the SLO
                        # good-rate denominator, as in submit()
                        if observe and self._slo is not None:
                            self._slo.observe_good(
                                time.perf_counter() - t_submit0,
                                scenario=scenario)
                        outer.set_result([sem_row])
                        return outer
                    # shadow sample: run live anyway, then score the
                    # cached row's top-1 agreement against the live one
                    shadow_row = sem_row
            sem_gen = memo.generation if memo is not None else None
            sem_igen = (memo.index_generation if memo is not None
                        else None)
            # re-read the index AFTER capturing the generation: a
            # rollover concluding between the top-of-function read and
            # here would otherwise search the OLD index yet insert
            # under the NEW generation — a stale cached answer.  This
            # order fails safe: old generation + new index is merely a
            # refused insert
            index = self._index

            def lookup():
                try:
                    values, indices = index.search(vectors, k)
                    self._note_index_shadow(vectors, indices, k)
                    results = neighbors_from_search(
                        values, indices, index.labels)
                    if memo is not None:
                        if shadow_row is not None and results:
                            memo.note_semantic_agreement(
                                shadow_row, results[0])
                        memo.semantic_insert(vectors, results, k,
                                             sem_gen,
                                             index_generation=sem_igen)
                    _resolve(outer, results)
                except BaseException as exc:
                    if not outer.done():
                        outer.set_exception(exc)
            self._aux_pool.submit(lookup)
            return outer
        lines = canonicalize_contexts(context_or_vectors,
                                      self.config.MAX_CONTEXTS)
        if record:
            self._record_traffic(scenario_name, lines,
                                 language=language, k=k)
        nkey = None
        gen = None
        igen = None
        if memo is not None:
            # exact tier for line-based neighbor queries: keyed per k so
            # a k=5 answer can never serve a k=10 ask; stands down
            # during a canary like every other memo serve path
            nkey = memo_lib.request_key(lines, 'neighbors', k=k)
            cached = None if rolling else memo.lookup(nkey,
                                                      scenario=scenario)
            if cached is not None:
                if self._tracer is not None:
                    attrs = {'tier': 'neighbors', 'rows': len(lines),
                             'mesh': True}
                    if scenario is not None:
                        attrs['scenario'] = scenario
                    trace = self._tracer.begin('serving.request',
                                               attrs=attrs)
                    trace.event('serving.memo_hit',
                                attrs={'tier': 'neighbors',
                                       'rows': len(lines),
                                       'memo': 'exact'})
                    trace.finish(status='ok')
                # cache-served requests stay in the SLO good-rate
                # denominator, as in submit()
                if observe and self._slo is not None:
                    self._slo.observe_good(
                        time.perf_counter() - t_submit0,
                        scenario=scenario)
                outer.set_result(cached)
                return outer
            gen = memo.generation
            igen = memo.index_generation
            # re-read AFTER igen — same swap-race ordering as the
            # ndarray path above: never pair the old index with the
            # new generation
            index = self._index
        inner = self.submit(lines, tier='vectors', scenario=scenario,
                            record=False, observe=observe)

        def chain(done: Future) -> None:
            try:
                results = done.result()
                if not results:
                    _resolve(outer, [])
                    return
                vectors = np.stack([r.code_vector for r in results])
                values, indices = index.search(vectors, k)
                self._note_index_shadow(vectors, indices, k)
                out_results = neighbors_from_search(
                    values, indices, index.labels)
                if memo is not None:
                    memo.insert(nkey, out_results, gen,
                                index_generation=igen)
                    memo.semantic_insert(vectors, out_results, k, gen,
                                         index_generation=igen)
                _resolve(outer, out_results)
            except BaseException as exc:
                if not outer.done():
                    outer.set_exception(exc)
        inner.add_done_callback(chain)
        return outer

    # ------------------------------------------- retrieval-augmented
    def submit_blended(self, context_lines: Sequence[str],
                       weight: Optional[float] = None,
                       k: Optional[int] = None,
                       deadline_ms: Optional[float] = None,
                       scenario: Optional[str] = None,
                       language: Optional[str] = None,
                       record: bool = True) -> Future:
        """Retrieval-augmented naming (WORKLOADS.md): blend the softmax
        head's top-k distribution with similarity votes from the
        attached index's top-k neighbor labels.  Returns a Future of
        one ``workloads.blend.BlendResult`` per method.

        Composes the two WARMED paths — ``submit(tier='topk')`` and
        ``submit_neighbors`` — so a blend costs zero new compiles; the
        legs run with ``record=False, observe=False`` and the blend
        registers exactly ONE traffic-tap record and ONE SLO
        observation at its own future.  ``weight <= 0`` short-circuits
        to the plain submit path and wraps the UNTOUCHED result
        (``source='softmax'``, bit-identical scores); no attached
        index degrades typed (``source='softmax_fallback'``) instead
        of raising.  Blended results are memoized under a key carrying
        the weight and k, refused on either a params or an index
        generation mismatch (both generations taken before the legs
        launch)."""
        from code2vec_tpu.workloads import blend as blend_lib
        if weight is None:
            weight = self.config.BLEND_NEIGHBOR_WEIGHT
        weight = float(weight)
        if not 0.0 <= weight <= 1.0:
            raise ValueError('blend weight must be in [0, 1], got %r'
                             % (weight,))
        k = k if k is not None else self.config.INDEX_NEIGHBORS_K
        t_submit0 = time.perf_counter()
        lines = canonicalize_contexts(context_lines,
                                      self.config.MAX_CONTEXTS)
        outer: Future = Future()
        if not lines:
            outer.set_result([])
            return outer
        if tele_core.enabled():
            tele_core.registry().counter(
                'mesh/blend_requests_total').inc()
        if record:
            self._record_traffic(scenario or 'retrieval_naming', lines,
                                 language=language, k=k, weight=weight)

        def _observe_outer(future: Future) -> None:
            if self._slo is None:
                return
            slo, t0, scen = self._slo, t_submit0, scenario

            def _cb(done: Future) -> None:
                try:
                    exc = done.exception()
                except BaseException:
                    return  # caller cancelled: not the server's verdict
                if exc is None:
                    slo.observe_good(time.perf_counter() - t0,
                                     scenario=scen)
                elif not isinstance(exc, EngineClosed):
                    slo.observe_bad(type(exc).__name__, scenario=scen)

            future.add_done_callback(_cb)

        def _wrap_passthrough(source: str) -> Future:
            # one warmed leg, scores passed through UNTOUCHED — the
            # weight=0 parity test asserts bit-identical arrays
            try:
                inner = self.submit(lines, tier='topk',
                                    deadline_ms=deadline_ms,
                                    scenario=scenario, record=False,
                                    observe=False)
            except EngineOverloaded:
                if self._slo is not None:
                    self._slo.observe_bad('shed', scenario=scenario)
                raise

            def _chain(done: Future) -> None:
                try:
                    rows = done.result()
                    _resolve(outer, [blend_lib.BlendResult(
                        original_name=row.original_name,
                        predicted_words=list(row.topk_predicted_words),
                        predicted_scores=row.topk_predicted_words_scores,
                        source=source, weight=weight, base=row,
                        neighbors=None) for row in rows])
                except BaseException as exc:
                    if not outer.done():
                        outer.set_exception(exc)

            inner.add_done_callback(_chain)
            _observe_outer(outer)
            return outer

        if self._index is None:
            # typed fallback, not an error: a scenario can be replayed
            # against a mesh with no index and still answer (pure
            # softmax), visibly degraded via source + counter
            if tele_core.enabled():
                tele_core.registry().counter(
                    'mesh/blend_fallback_total').inc()
            return _wrap_passthrough(blend_lib.SOURCE_FALLBACK)
        if weight <= 0.0:
            return _wrap_passthrough(blend_lib.SOURCE_SOFTMAX)
        memo = self._memo
        bkey = None
        gen = None
        igen = None
        if memo is not None:
            # keyed on weight AND k: a 0.3-blend answer must never
            # serve a 0.7-blend ask; stands down during params OR
            # index rollovers like every other memo serve path
            bkey = memo_lib.request_key(lines, 'blend@%g' % weight, k=k)
            rolling = self._rollover is not None  # graftlint: disable=lock-discipline -- benign racy read: a stale None serves one more hit, a stale rollover runs one more request live
            rolling = rolling or self._index_rollover is not None  # graftlint: disable=lock-discipline -- same benign racy read for the index-rollover axis
            cached = None if rolling else memo.lookup(bkey,
                                                      scenario=scenario)
            if cached is not None:
                if self._tracer is not None:
                    attrs = {'tier': 'blend', 'rows': len(lines),
                             'mesh': True}
                    if scenario is not None:
                        attrs['scenario'] = scenario
                    trace = self._tracer.begin('serving.request',
                                               attrs=attrs)
                    trace.event('serving.memo_hit',
                                attrs={'tier': 'blend',
                                       'rows': len(lines),
                                       'memo': 'exact'})
                    trace.finish(status='ok')
                if self._slo is not None:
                    self._slo.observe_good(
                        time.perf_counter() - t_submit0,
                        scenario=scenario)
                outer.set_result(cached)
                return outer
            # BOTH generations BEFORE the legs launch: a params or
            # index rollover concluding mid-flight makes the insert a
            # refused no-op instead of a stale cached blend
            gen = memo.generation
            igen = memo.index_generation
        try:
            base_future = self.submit(lines, tier='topk',
                                      deadline_ms=deadline_ms,
                                      scenario=scenario, record=False,
                                      observe=False)
            nbr_future = self.submit_neighbors(lines, k=k,
                                               scenario=scenario,
                                               record=False,
                                               observe=False)
        except EngineOverloaded:
            if self._slo is not None:
                self._slo.observe_bad('shed', scenario=scenario)
            raise
        state: Dict[str, object] = {}
        state_lock = threading.Lock()

        def _finish() -> None:
            try:
                base_rows = state['base']
                nbr_rows = state['nbr']
                results = [blend_lib.blend_row(
                    row, (nbr_rows[i] if i < len(nbr_rows) else None),
                    weight) for i, row in enumerate(base_rows)]
                if memo is not None:
                    memo.insert(bkey, results, gen,
                                index_generation=igen)
                _resolve(outer, results)
            except BaseException as exc:
                if not outer.done():
                    outer.set_exception(exc)

        def _arm(name: str):
            def _cb(done: Future) -> None:
                try:
                    value = done.result()
                except BaseException as exc:
                    if not outer.done():
                        outer.set_exception(exc)
                    return
                with state_lock:
                    state[name] = value
                    ready = len(state) == 2
                if ready:
                    _finish()
            return _cb

        base_future.add_done_callback(_arm('base'))
        nbr_future.add_done_callback(_arm('nbr'))
        _observe_outer(outer)
        return outer

    # --------------------------------------------------------- rollover
    def load_params(self, source, canary_batches: Optional[int] = None,
                    min_agreement: Optional[float] = None) -> Future:
        """Coordinated fleet rollover: canary on ONE replica (the
        engine's shadow-scoring machinery — zero new compiles), then on
        agreement fleet-swap the validated params onto every other
        replica atomically; on disagreement roll the canary back and
        leave EVERY replica serving the old params.  Returns a Future
        of the fleet report."""
        n_canary = (canary_batches if canary_batches is not None
                    else self.canary_batches)
        floor = (min_agreement if min_agreement is not None
                 else self.canary_agreement)
        handle: Future = Future()
        with self._cond:
            if self._closed:
                raise EngineClosed('ServingMesh is closed')
            if self._rollover is not None:
                raise RuntimeError(
                    'a fleet rollover is already in flight (replica %s); '
                    'await its handle first'
                    % self._rollover['replica'].rid)
            canary_slot = next(
                (slot for slot in self._replicas
                 if not slot.retired and not slot.dead
                 and not slot.restarting
                 and slot.breaker_state != _BREAKER_OPEN), None)
            if canary_slot is None:
                raise RuntimeError('no serving replica available to '
                                   'canary the rollover on')
            self._rollover = {'replica': canary_slot, 'handle': handle}
            canary_slot.canarying = True
        step = source if isinstance(source, int) and \
            not isinstance(source, bool) else None
        try:
            canary_handle = canary_slot.transport.load_params(
                source, n_canary, floor)
        except BaseException:
            with self._cond:
                self._rollover = None
                canary_slot.canarying = False
            raise
        self.log('mesh: rollover armed — canarying on replica %s '
                 '(%d batches, agreement floor %.2f)'
                 % (canary_slot.rid, n_canary, floor))

        def conclude(done: Future) -> None:
            swapped = 0
            try:
                report = done.result()
            except BaseException as exc:
                self._finish_rollover(canary_slot)
                if not handle.done():
                    handle.set_exception(exc)
                return
            if report.get('swapped'):
                resolved_step = (report.get('step')
                                 if report.get('step') is not None
                                 else step)
                params = getattr(
                    getattr(canary_slot.transport, 'engine', None),
                    'params', None)
                try:
                    for slot in self._replicas:
                        if slot is canary_slot or slot.retired or \
                                slot.dead or slot.restarting:
                            # a dead/restarting sibling re-adopts the
                            # fleet's current step when it rejoins (the
                            # supervisor's re-adoption leg)
                            continue
                        slot.transport.adopt(params, source,
                                             resolved_step)
                        swapped += 1
                except BaseException as exc:
                    # a sibling failed its adopt mid-fleet-swap (its
                    # worker died, its engine closed): the rollover
                    # machinery must still CONCLUDE — a swallowed
                    # done-callback exception would leave _rollover set
                    # forever, wedging every later load_params and the
                    # follow poller.  The canary (and any sibling that
                    # already adopted) serves the new params; the
                    # failed sibling is the breaker/retirement path's
                    # problem; the caller sees the partial swap typed.
                    self._finish_rollover(canary_slot)
                    self.log('mesh: fleet swap FAILED on a sibling '
                             'after the canary passed (%r); %d of %d '
                             'siblings adopted'
                             % (exc, swapped,
                                sum(1 for s in self._replicas
                                    if s is not canary_slot
                                    and not s.retired)))
                    if not handle.done():
                        handle.set_exception(exc)
                    return
                with self._cond:
                    self._params_step = (resolved_step
                                         if resolved_step is not None
                                         else self._params_step)
                if self._memo is not None:
                    # UNCONDITIONAL on swap, not keyed to step: a
                    # pytree-source swap has resolved_step=None and
                    # must still invalidate every memoized result
                    # atomically (generation bump, not per-entry
                    # eviction); a rolled-back canary never reaches
                    # here, so the cache stays warm on rollback
                    self._memo.bump_generation(resolved_step)
                self.rollover_total.inc()
                if tele_core.enabled():
                    tele_core.registry().counter(
                        'mesh/rollover_total').inc()
                self.log('mesh: fleet rollover SWAPPED (step %s): '
                         'canary agreement %.3f on replica %s, %d '
                         'sibling(s) adopted'
                         % (resolved_step, report.get('agreement') or 0,
                            canary_slot.rid, swapped))
            else:
                self.rollover_rollbacks_total.inc()
                if tele_core.enabled():
                    tele_core.registry().counter(
                        'mesh/rollover_rollbacks_total').inc()
                if self._tracer is not None:
                    self._tracer.dump_flight('rollover_rollback')
                self.log('mesh: fleet rollover ROLLED BACK on the '
                         'canary replica %s (%s); every replica keeps '
                         'the old params'
                         % (canary_slot.rid, report.get('reason')))
            self._finish_rollover(canary_slot)
            fleet_report = dict(report)
            fleet_report['canary_replica'] = canary_slot.rid
            fleet_report['replicas_swapped'] = (
                swapped + 1 if report.get('swapped') else 0)
            _resolve(handle, fleet_report)

        canary_handle.add_done_callback(conclude)
        return handle

    def _finish_rollover(self, canary_slot: _ReplicaSlot) -> None:
        with self._cond:
            canary_slot.canarying = False
            self._rollover = None
            self._cond.notify_all()
        self._queue.kick()

    # --------------------------------------------------- index rollover
    def rollover_index(self, candidate,
                       shadow_queries: Optional[int] = None,
                       min_agreement: Optional[float] = None) -> Future:
        """Canaried INDEX swap — the params-canary machinery
        generalized to indexes (SERVING.md rollover runbook, INDEX.md
        "Quantized tier").  The candidate index (a rebuilt, compacted,
        or re-quantized tier over the same corpus) attaches in SHADOW:
        live ``submit_neighbors`` traffic keeps being served by the
        current index while every query is replayed against the
        candidate in the aux pool and scored for top-k id agreement.
        After ``shadow_queries`` scored queries: agreement >= the floor
        swaps the candidate in atomically (new index version; the memo
        tier's index generation bumps, invalidating every cached
        neighbor result while predict entries survive); below the
        floor rolls back — the candidate never serves a single
        request.  Returns a Future of the report dict."""
        n_shadow = (int(shadow_queries) if shadow_queries is not None
                    else 32)
        floor = (float(min_agreement) if min_agreement is not None
                 else self.canary_agreement)
        if n_shadow < 1:
            raise ValueError('rollover_index needs shadow_queries >= 1 '
                             '(got %r)' % shadow_queries)
        if candidate is None or not hasattr(candidate, 'search'):
            raise ValueError('rollover_index needs a candidate index '
                             'with .search (got %r)' % (candidate,))
        handle: Future = Future()
        with self._cond:
            if self._closed:
                raise EngineClosed('ServingMesh is closed')
            if self._index is None:
                raise RuntimeError('no index attached — nothing to '
                                   'roll over; use attach_index for '
                                   'the first attach')
            if self._index_rollover is not None:
                raise RuntimeError('an index rollover is already in '
                                   'flight; await its handle first')
            self._index_rollover = {
                'candidate': candidate, 'handle': handle,
                'needed': n_shadow, 'floor': floor,
                'agree_sum': 0.0, 'count': 0, 'concluding': False,
            }
        self.log('mesh: index rollover armed — shadow-querying the '
                 'candidate on live traffic (%d queries, agreement '
                 'floor %.2f)' % (n_shadow, floor))
        return handle

    def _note_index_shadow(self, vectors: np.ndarray,
                           live_indices: np.ndarray, k: int) -> None:
        """One live neighbor query completed while an index rollover
        is armed: replay it against the candidate in the aux pool and
        accumulate top-k id agreement.  A no-op (one racy None read)
        when no rollover is in flight — the hot path stays lock-free."""
        if self._index_rollover is None:  # graftlint: disable=lock-discipline -- benign racy read: a just-armed rollover misses one query, a just-concluded one scores one extra no-op
            return
        with self._cond:
            state = self._index_rollover
            if state is None or state['concluding']:
                return
        vectors = np.array(vectors, np.float32)
        live_indices = np.array(live_indices)

        def shadow():
            try:
                _, cand_idx = state['candidate'].search(vectors, k)
            except BaseException as exc:
                self._conclude_index_rollover(
                    state, error=exc)
                return
            per_row: List[float] = []
            for row in range(live_indices.shape[0]):
                live = set(int(i) for i in live_indices[row] if i >= 0)
                if not live:
                    continue
                got = set(int(i) for i in cand_idx[row] if i >= 0)
                per_row.append(len(live & got) / len(live))
            with self._cond:
                if self._index_rollover is not state \
                        or state['concluding']:
                    return
                state['agree_sum'] += sum(per_row)
                state['count'] += len(per_row)
                running = (state['agree_sum'] / state['count']
                           if state['count'] else 0.0)
                done = state['count'] >= state['needed']
                if done:
                    state['concluding'] = True
            self.index_rollover_agreement.set(running)
            if tele_core.enabled():
                tele_core.registry().gauge(
                    'index/rollover_agreement').set(running)
            if done:
                self._conclude_index_rollover(state)
        self._aux_pool.submit(shadow)

    def _conclude_index_rollover(self, state: Dict[str, object],
                                 error=None) -> None:
        """Swap-or-rollback decision once the shadow sample is full (or
        the candidate errored — an index that cannot answer the shadow
        queries must never be swapped in)."""
        handle: Future = state['handle']
        with self._cond:
            if self._index_rollover is not state:
                return
            agreement = (state['agree_sum'] / state['count']
                         if state['count'] else 0.0)
            swapped = error is None and agreement >= state['floor']
            if swapped:
                self._index = state['candidate']
                self._index_version += 1
                version = self._index_version
            self._index_rollover = None
            self._cond.notify_all()
        if swapped:
            if self._memo is not None:
                # neighbor results are index-dependent: the index
                # generation bump invalidates them atomically while
                # predict entries survive (the model didn't change)
                self._memo.bump_index_generation()
            self.index_rollover_total.inc()
            if tele_core.enabled():
                tele_core.registry().counter(
                    'index/rollovers_total').inc()
            self.log('mesh: index rollover SWAPPED (version %d): '
                     'shadow agreement %.3f over %d queries'
                     % (version, agreement, state['count']))
        else:
            self.index_rollover_rollbacks_total.inc()
            if tele_core.enabled():
                tele_core.registry().counter(
                    'index/rollover_rollbacks_total').inc()
            self.log('mesh: index rollover ROLLED BACK (%s); the '
                     'serving index and every cached neighbor result '
                     'stay live'
                     % ('candidate error: %r' % error if error
                        is not None else 'shadow agreement %.3f < '
                        'floor %.2f over %d queries'
                        % (agreement, state['floor'], state['count'])))
        report = {'swapped': swapped, 'agreement': agreement,
                  'queries': state['count'],
                  'reason': ('candidate error: %r' % error
                             if error is not None else None)}
        if swapped:
            report['index_version'] = version
        if error is not None and not handle.done():
            handle.set_exception(
                error if isinstance(error, Exception)
                else RuntimeError(repr(error)))
            return
        _resolve(handle, report)

    def follow_checkpoints(self, poll_secs: Optional[float] = None
                           ) -> 'ServingMesh':
        """Fleet-level ``--serve-follow-checkpoints``: ONE poller rolls
        newer retained steps through the coordinated canary, so the
        fleet moves as a unit instead of N pollers racing."""
        if self._param_source is None:
            raise RuntimeError('follow_checkpoints needs a checkpointed '
                               'model (build the mesh via '
                               'model.serving_mesh())')
        poll = (poll_secs if poll_secs is not None
                else self.config.SERVE_FOLLOW_CHECKPOINTS_SECS)
        if poll <= 0:
            raise ValueError('follow_checkpoints needs poll_secs > 0 '
                             '(got %r)' % poll)
        with self._lock:
            if self._closed:
                raise EngineClosed('ServingMesh is closed')
            if self._follow_thread is not None:
                return self
            self._follow_thread = threading.Thread(
                target=self._follow_loop, args=(poll,), daemon=True,
                name='mesh-follow')
            self._follow_thread.start()
        return self

    def _follow_loop(self, poll_secs: float) -> None:
        attempted: Optional[int] = None
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
                self.log('mesh: follow-checkpoints found step %d; '
                         'starting coordinated rollover' % newest)
                self.load_params(newest)
                attempted = newest
            except EngineClosed:
                return
            except Exception as exc:  # poller must survive blips
                self.log('mesh: follow-checkpoints poll failed: %s'
                         % exc)

    # -------------------------------------------------------- lifecycle
    def warmup(self) -> 'ServingMesh':
        """Warm every replica's (bucket x capacity x tier) ladder.
        Thread-mode replicas share the trainer's jit caches, so replica
        2..N warm at cache-hit speed; the fleet compiles each program
        once."""
        for slot in self._replicas:
            slot.transport.warmup()
        return self

    def retire(self, replica_id: str, timeout: float = 120.0,
               reason: str = 'drain') -> None:
        """Drain one replica out of the fleet: it stops pulling, its
        in-flight batches deliver, its engine closes; the shared queue
        redirects to the remaining replicas throughout.  ``reason``
        lands in ``stats()``'s ``retired_reason`` and the
        reason-labeled ``mesh/retired_total`` (the autoscaler passes
        'autoscale'; operators get the 'drain' default)."""
        with self._cond:
            # prefer a non-retired slot: an adopted worker that died
            # and redialed leaves a retired slot with the same rid
            # behind, and retire() must drain the LIVE incarnation
            slot = next((s for s in self._replicas
                         if s.rid == replica_id and not s.retired),
                        None)
            if slot is None:
                slot = next((s for s in self._replicas
                             if s.rid == replica_id), None)
            if slot is None:
                raise ValueError('no replica %r in this mesh (%s)'
                                 % (replica_id,
                                    [s.rid for s in self._replicas]))
            if slot.retired:
                return
            slot.retired = True
            slot.retired_reason = reason
            was_dead = slot.dead
            self._cond.notify_all()
        self._note_retired(reason)
        self._queue.kick()
        if slot.thread is not None:
            slot.thread.join(timeout)
        deadline = time.perf_counter() + timeout
        with self._cond:
            while slot.inflight > 0:
                remaining = deadline - time.perf_counter()
                if remaining <= 0:
                    break
                self._cond.wait(min(remaining, 0.1))
        if not was_dead:
            slot.transport.close()  # a dead worker was already reaped
        self._set_serving_gauge_locked_free()
        self._set_live_gauge_locked_free()
        self.log('mesh: replica %s retired (served %d rows in %d '
                 'batches)' % (slot.rid, slot.rows_dispatched,
                               slot.batches))

    def stats(self) -> Dict[str, object]:
        with self._lock:
            rows_total = self._rows_total
            replicas = [{
                'replica': slot.rid,
                'retired': slot.retired,
                'retired_reason': slot.retired_reason,
                'adopted': slot.adopted,
                # placement view: the parent-assigned slice for spawned
                # workers, the worker's self-reported sub-mesh for
                # adopted ones — per-slice HBM attribution is this row's
                # 'devices' next to its 'worker_memory' ledger rollup
                'devices': (list(slot.device_indices)
                            if slot.device_indices else None),
                'dead': slot.dead,
                'restarts': slot.restarts,
                'breaker_state': slot.breaker_state,
                'inflight': slot.inflight,
                'worker_reported_inflight': (
                    slot.transport.heartbeat_info.get('inflight')
                    if isinstance(slot.transport, _WorkerReplica)
                    else None),
                # per-worker observability backhaul: remote HBM
                # pressure + the stitching clock, visible without
                # touching the worker's wire
                'worker_memory': (
                    dict(slot.transport.ledger_info) or None
                    if isinstance(slot.transport, _WorkerReplica)
                    else None),
                'clock_offset_ms': (
                    slot.transport.clock.offset * 1e3
                    if isinstance(slot.transport, _WorkerReplica)
                    and slot.transport.clock.samples else None),
                'batches': slot.batches,
                'rows_dispatched': slot.rows_dispatched,
                'dispatch_share': (slot.rows_dispatched / rows_total
                                   if rows_total else 0.0),
            } for slot in self._replicas]
            params_step = self._params_step
            fleet_rate = self._service_rows_per_s
            index_version = self._index_version
        out = {
            'replicas': replicas,
            'mode': self.mode,
            'requests_total': self.requests_total.snapshot(),
            'rows_dispatched': rows_total,
            'fleet_rows_per_s': fleet_rate,
            'params_step': params_step,
            'rollover_total': self.rollover_total.snapshot(),
            'rollover_rollbacks_total':
                self.rollover_rollbacks_total.snapshot(),
            'index_version': index_version,
            'index_rollover_total':
                self.index_rollover_total.snapshot(),
            'index_rollover_rollbacks_total':
                self.index_rollover_rollbacks_total.snapshot(),
            'replica_breaker_open_total':
                self.breaker_open_total.snapshot(),
            'restarts_total': self.restarts_total.snapshot(),
            'redispatched_total': self.redispatched_total.snapshot(),
            'retired_total': self.retired_total.snapshot(),
            'adopted_total': self.adopted_total.snapshot(),
            'adoption_rejected_total':
                self.adoption_rejected_total.snapshot(),
            'proto_rejected_total': (
                self._listener.rejected_total
                if self._listener is not None else 0),
            'placement': (
                {'devices_per_replica': self.devices_per_replica,
                 'slices': len(self._placement),
                 'data_axis': self.data_axis}
                if self._placement is not None else None),
            'autoscaler': (self._autoscaler.stats()
                           if self._autoscaler is not None else None),
            'heartbeat_misses_total':
                self.heartbeat_misses_total.snapshot(),
            'replicas_live': self.live_gauge.snapshot(),
            'adopted_spans_total': self.adopted_spans_total.snapshot(),
            'remote_spans_dropped_total':
                self.remote_spans_dropped_total.snapshot(),
            'worker_snapshots_total':
                self.worker_snapshots_total.snapshot(),
            'slo': (self._slo.stats()
                    if self._slo is not None else None),
            'memo': (self._memo.stats()
                     if self._memo is not None else None),
            'tracing': (self._tracer.stats()
                        if self._tracer is not None else None),
        }
        out.update(self._queue.stats())
        return out

    def replica_stats(self) -> List[Dict[str, object]]:
        """Per-replica engine stats (fill rate, latency timers, ...) —
        the per-replica device-fill column of bench_mesh.py.  A dead or
        retired replica has no wire to query: its row says so instead
        of hanging on a corpse."""
        out = []
        for slot in self._replicas:
            if slot.dead or slot.retired:
                out.append({'replica': slot.rid, 'dead': slot.dead,
                            'retired': slot.retired})
            else:
                out.append(slot.transport.stats())
        return out

    def close(self, drain: bool = False) -> None:
        """Stop the fleet.  Fail-fast (default): still-queued requests
        fail typed ``EngineClosed``; in-flight micro-batches deliver.
        ``drain=True`` serves everything admitted first.  Idempotent.

        The self-healing machinery is reaped, not leaked: the
        supervisor and liveness threads are joined, a restart in flight
        is cancelled (its half-built worker terminated — never adopted
        into a closed fleet, never double-restarted), and the socket
        listener closes so no late-dialing worker is left accepted."""
        with self._cond:
            already = self._closed
            if not already:
                self._closed = True
                self._drain = drain
            rollover = self._rollover
            self._rollover = None
            restart_pending = self._restart_pending
            self._cond.notify_all()
        self._follow_stop.set()
        self._close_event.set()
        if self._autoscaler is not None:
            # the autoscaler must stop DECIDING before the fleet it
            # reads starts tearing down
            self._autoscaler.close()
        if restart_pending is not None:
            # interrupt a supervisor blocked in wait_ready: the worker
            # cold start must not outlive (or be leaked by) the mesh
            restart_pending.cancel()
        self._queue.close(drain)
        if not drain:
            for request in self._queue.abandon():
                request.fail(EngineClosed(
                    'ServingMesh closed with the request still queued '
                    '(close(drain=True) serves the queue first)'))
        if rollover is not None:
            handle = rollover['handle']
            if isinstance(handle, Future) and not handle.done():
                try:
                    handle.set_exception(EngineClosed(
                        'ServingMesh closed mid-rollover'))
                except Exception:
                    pass
        follow = self._follow_thread
        if follow is not None:
            follow.join()
        if self._supervisor is not None:
            self._supervisor.join(timeout=60.0)
        if self._liveness_thread is not None:
            self._liveness_thread.join(timeout=60.0)
        if self._adopt_thread is not None:
            self._adopt_thread.join(timeout=60.0)
        for slot in self._replicas:
            if slot.thread is not None:
                slot.thread.join()
        for slot in self._replicas:
            if not slot.retired and not slot.dead:
                slot.transport.close()  # dead workers were reaped
        if self._listener is not None:
            self._listener.close()
        self._aux_pool.shutdown(wait=True)
        if self._memo is not None:
            self._memo.close()
        if self._tracer is not None and self._owns_tracer:
            self._tracer.close()

    def __enter__(self) -> 'ServingMesh':
        return self

    def __exit__(self, *exc) -> None:
        self.close()
