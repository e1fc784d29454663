"""Device-memory ledger: per-subsystem attribution of live device bytes.

The telemetry layer (OBSERVABILITY.md) made *time* observable; this
module does the same for *space*.  Every subsystem that owns device
memory — trainer state, checkpoint restores, the staging ring, the
serving engine's param sets and warm compilation ladder, index shards —
registers its allocations here, so at any moment the process can answer
"who holds how many device bytes", diff two moments for leaks, refuse
an allocation that would blow the HBM budget BEFORE it happens, and
dump a full forensic ledger when the backend reports
``RESOURCE_EXHAUSTED``.

Design constraints (mirroring ``telemetry/core.py``):

- **Dependency-free at import** — jax is imported lazily inside the
  functions that need it, so the graftlint engine (and any other
  jax-free consumer) can import the catalogs below in a bare
  interpreter.
- **Thread-safe** — the staging ring registers from the input thread
  while the serving engine's dispatcher swaps param sets; one lock
  guards the ledger state.
- **Zero host syncs** — bookkeeping reads only array METADATA
  (``.nbytes``); reconciliation enumerates ``jax.live_arrays()`` /
  ``device.memory_stats()``, neither of which blocks on device work.
  Nothing here ever calls ``device_get`` / ``block_until_ready`` or
  fetches a device value (guarded in tests/test_memory_ledger.py).

Accounting is in LOGICAL bytes (one count per array, replication along
mesh axes NOT multiplied) — the same basis as ``jax.Array.nbytes`` and
therefore directly reconcilable against ``jax.live_arrays()`` on every
backend, including the CPU test mesh.  ``memory_stats()`` per-device
physical numbers ride along in snapshots when the backend provides
them (TPU), so the physical view is never lost — it is just not the
reconciliation basis.

Bucket taxonomy (OBSERVABILITY.md "Device memory ledger"):

- ``params``       — model parameter sets, one entry per SET: the
                     training/serving state plus, during a canaried
                     rollover, the candidate copy (so the second copy
                     an armed canary holds is visible, not mystery
                     bytes).
- ``opt_state``    — optimizer moments (Adam mu/nu + scalars).
- ``staging``      — batches resident in the device staging ring
                     (``Trainer.stage_batches``).
- ``index``        — embedding-index residents: exact-tier store
                     shards, IVF cluster-sorted rows + centroids.
- ``executables``  — the serving compilation ladder's programs
                     (bucket × capacity × tier), measured at warmup
                     via AOT ``memory_analysis``.  kind='executable':
                     reported, but excluded from the array
                     reconciliation (an executable is not a
                     ``jax.Array``).
- ``memo``         — the serving memoization tier's cached results
                     (``serving/memo.py``) — HOST bytes, the one
                     host-resident bucket in the taxonomy.
                     kind='host': reported so the cache budget is
                     visible next to the device residents it spares,
                     but excluded from the live-array reconciliation
                     (nothing here lives on a device).

Everything live on the backend but in no bucket is the residual
"unattributed" — reconciliation keeps it honest: nothing hides.
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time
import weakref
from typing import Any, Dict, List, Optional, Tuple

from code2vec_tpu.telemetry import core as tele_core

ENV_BUDGET = 'HBM_BUDGET_BYTES'
TOUCH_FILE_NAME = 'MEM_NOW'
OOM_DUMP_NAME = 'oom_ledger.json'

#: the ledger's bucket taxonomy — registration validates against it so
#: a typo'd bucket cannot silently fork the accounting
BUCKETS = ('params', 'opt_state', 'staging', 'index', 'executables',
           'memo')

#: bucket -> catalog gauge mirrored into the telemetry registry
#: (names cataloged in telemetry/catalog.py; OBSERVABILITY.md)
_BUCKET_GAUGE = {
    'params': 'mem/params_bytes',
    'opt_state': 'mem/opt_state_bytes',
    'staging': 'mem/staging_bytes',
    'index': 'mem/index_bytes',
    'executables': 'mem/executables_bytes',
    'memo': 'mem/memo_bytes',
}

_EVENT_RING = 128


class MemoryBudgetExceeded(RuntimeError):
    """An allocation would cross ``HBM_BUDGET_BYTES`` — raised BEFORE
    the allocation happens (index attach, serving ``load_params``), so
    the caller fails typed instead of the backend failing with an
    undiagnosable ``RESOURCE_EXHAUSTED`` mid-dispatch."""


# ------------------------------------------------------- alloc catalog
# Cataloged allocation owners (graftlint rule ``alloc-catalog``,
# ANALYSIS.md): every device-allocation site — ``device_put``,
# batch/param placement (``shard_batch``/``shard_params``), and
# host-initiated ``jnp.zeros/empty/full/asarray`` — inside these owner
# modules must belong to a function cataloged here (meaning: its
# allocations are ledger-registered, or deliberately exempt with the
# reason recorded) or carry an inline graftlint suppression.  ``count``
# pins the number of sites in the function, so a NEW allocation slipped
# into an already-cataloged owner still fails the lint; an entry whose
# function no longer allocates is stale and fails too.
ALLOC_OWNER_FILES = (
    'code2vec_tpu/training/trainer.py',
    'code2vec_tpu/serving/engine.py',
    'code2vec_tpu/index/exact.py',
    'code2vec_tpu/index/ivf.py',
    'code2vec_tpu/index/quant.py',
)

ALLOC_CATALOG = (
    {'file': 'code2vec_tpu/training/trainer.py',
     'func': 'Trainer.init_state', 'count': 2,
     'reason': 'fresh params placement + step scalar — registered as '
               'params/opt_state via register_state_memory'},
    {'file': 'code2vec_tpu/training/trainer.py',
     'func': 'Trainer.state_from_params', 'count': 2,
     'reason': 'params placement + step scalar — registered via '
               'register_state_memory'},
    {'file': 'code2vec_tpu/training/trainer.py',
     'func': 'Trainer.train_step', 'count': 1,
     'reason': 'unstaged one-shot batch placement (tests/REPL); the '
               'staged path accounts in stage_batches, and a one-shot '
               'batch is consumed (and donated) within the call'},
    {'file': 'code2vec_tpu/training/trainer.py',
     'func': 'Trainer.stage_batches', 'count': 2,
     'reason': 'THE staging ring: both placement branches register '
               'into the staging bucket (telemetry on) and release at '
               'pop'},
    {'file': 'code2vec_tpu/training/trainer.py',
     'func': 'Trainer.eval_step', 'count': 1,
     'reason': 'one-shot eval batch placement, consumed within the '
               'call (the eval loop goes through stage_batches)'},
    {'file': 'code2vec_tpu/training/trainer.py',
     'func': 'Trainer.predict_step', 'count': 1,
     'reason': 'REPL-path predict batch placement, consumed within '
               'the call; serving traffic accounts in the engine'},
    {'file': 'code2vec_tpu/serving/engine.py',
     'func': 'ServingEngine.warmup', 'count': 1,
     'reason': 'warmup ladder batches: transient compile fodder, dead '
               'after the eager compile; the EXECUTABLES they produce '
               'are what registers (bucket executables)'},
    {'file': 'code2vec_tpu/serving/engine.py',
     'func': 'ServingEngine._dispatch_batch', 'count': 1,
     'reason': 'micro-batch placement: in flight only between dispatch '
               'and decode, bounded by the bucket ladder; per-request '
               'accounting would put ledger ops on the hot path'},
    {'file': 'code2vec_tpu/index/exact.py',
     'func': 'ExactIndex.__init__', 'count': 4,
     'reason': 'store matrix + -inf row mask (sharded and single-'
               'device branches) — budget-checked before allocation, '
               'registered as index/exact'},
    {'file': 'code2vec_tpu/index/ivf.py',
     'func': 'IVFIndex.__init__', 'count': 2,
     'reason': 'cluster-sorted rows + centroids — registered as '
               'index/ivf'},
    {'file': 'code2vec_tpu/index/ivf.py',
     'func': 'kmeans', 'count': 2,
     'reason': 'build-path device copies of the store + init '
               'centroids, freed when the build returns (transient; '
               'the persistent residents register in IVFIndex '
               '__init__)'},
    {'file': 'code2vec_tpu/index/quant.py',
     'func': 'QuantizedIVFIndex._install_base_locked', 'count': 2,
     'reason': 'cluster-sorted quantized codes + codec constants '
               '(scales / codebooks / centroids) — budget-checked at '
               'attach, registered as index quant:<fp>:base'},
    {'file': 'code2vec_tpu/index/quant.py',
     'func': 'QuantizedIVFIndex._refresh_append_device_locked',
     'count': 1,
     'reason': 'capacity-rung padded append-segment buffer — the '
               'delta to the next rung is budget-gated before '
               'placement, re-registered per segment as '
               'quant:<fp>:seg%05d + quant:<fp>:segslack'},
)


# ------------------------------------------------------------- helpers
def tree_nbytes(tree) -> int:
    """Total LOGICAL bytes of a pytree of arrays (jax arrays, numpy
    arrays, or abstract ``ShapeDtypeStruct``s — anything with
    ``.nbytes`` or ``shape``+``dtype``).  Metadata only: never blocks
    on device values."""
    import jax
    import numpy as np

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        nbytes = getattr(leaf, 'nbytes', None)
        if nbytes is not None:
            total += int(nbytes)
            continue
        shape = getattr(leaf, 'shape', None)
        dtype = getattr(leaf, 'dtype', None)
        if shape is not None and dtype is not None:
            size = 1
            for dim in shape:
                size *= int(dim)
            total += size * np.dtype(dtype).itemsize
    return total


def backend_memory() -> Dict[str, Any]:
    """Backend-reported memory: LOGICAL live-array bytes (every backend;
    the reconciliation basis) plus per-device physical ``memory_stats``
    when the runtime provides them (TPU/GPU; CPU returns None)."""
    import jax

    live = 0
    count = 0
    for arr in jax.live_arrays():
        try:
            if arr.is_deleted():
                continue  # donated-away buffers linger as husks
        except Exception:
            pass
        live += int(arr.nbytes)
        count += 1
    devices = []
    for dev in jax.local_devices():
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        if stats:
            devices.append({
                'id': int(dev.id),
                'bytes_in_use': int(stats.get('bytes_in_use', 0)),
                'peak_bytes_in_use': int(stats.get('peak_bytes_in_use',
                                                   0)),
            })
    return {'live_bytes': live, 'live_arrays': count,
            'source': 'live_arrays', 'devices': devices}


def is_oom_error(exc: BaseException) -> bool:
    """Does this exception look like a device out-of-memory?  XLA
    surfaces them as ``XlaRuntimeError: RESOURCE_EXHAUSTED: ...`` (the
    jit-dispatch boundary) or allocation failures mentioning
    out-of-memory (the ``device_put`` attach boundary)."""
    text = str(exc)
    return ('RESOURCE_EXHAUSTED' in text
            or 'out of memory' in text.lower())


def _atomic_write_json(path: str, payload: dict) -> None:
    tmp = path + '.tmp'
    with open(tmp, 'w') as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    os.replace(tmp, path)


class _Entry:
    __slots__ = ('bucket', 'key', 'nbytes', 'kind', 'attrs', 't',
                 'finalizer')

    def __init__(self, bucket: str, key: str, nbytes: int, kind: str,
                 attrs: Optional[dict]):
        self.bucket = bucket
        self.key = key
        self.nbytes = int(nbytes)
        self.kind = kind
        self.attrs = attrs or {}
        self.t = time.time()
        self.finalizer = None

    def record(self) -> Dict[str, Any]:
        out = {'key': self.key, 'bytes': self.nbytes, 'kind': self.kind}
        if self.attrs:
            out['attrs'] = self.attrs
        return out


# --------------------------------------------------------------- ledger
class MemoryLedger:
    """The process-global device-memory ledger.

    ``register`` replaces any existing entry under the same
    ``(bucket, key)`` — owners re-registering across restores/rollovers
    therefore never double-count, and replacing IS the release of the
    previous generation.  ``owner=`` attaches a ``weakref.finalize`` so
    an owner that is garbage-collected auto-releases its entry instead
    of leaving the ledger stale.
    """

    # registration races between the input thread, the serving
    # dispatcher/decode workers, and snapshot readers (lock-discipline
    # rule, ANALYSIS.md):
    # graftlint: guard MemoryLedger._entries,_events,_watermarks,_budget,_dump_dir,_oom_dumps by _lock
    def __init__(self):
        # RLock, deliberately: a weakref.finalize callback (owner
        # collected) calls release(), and cyclic GC can fire it on THIS
        # thread while it already holds the lock inside register() (the
        # locked region allocates). A plain Lock would self-deadlock the
        # staging/dispatcher thread; re-entering is safe — release
        # mutates before the watermark/export reads run.
        self._lock = threading.RLock()
        self._entries: Dict[Tuple[str, str], _Entry] = {}
        self._events: collections.deque = collections.deque(
            maxlen=_EVENT_RING)
        self._watermarks: Dict[str, int] = {}
        self._budget: Optional[int] = None  # None = env var decides
        self._dump_dir: Optional[str] = None
        self._oom_dumps = 0

    # ------------------------------------------------------ configure
    def configure(self, budget_bytes: Optional[int] = None,
                  dump_dir: Optional[str] = None) -> None:
        """Pin the budget (overriding the ``HBM_BUDGET_BYTES`` env var;
        0 = unlimited) and/or the directory forensic dumps land in
        (default: the current working directory)."""
        with self._lock:
            if budget_bytes is not None:
                self._budget = int(budget_bytes)
            if dump_dir is not None:
                self._dump_dir = dump_dir

    def budget_bytes(self) -> int:
        """The effective budget: the configured value, else the
        ``HBM_BUDGET_BYTES`` environment variable, else 0 (unlimited)."""
        with self._lock:
            budget = self._budget
        if budget is not None:
            return budget
        try:
            return int(os.environ.get(ENV_BUDGET, '0') or 0)
        except ValueError:
            raise ValueError('%s must be an integer byte count, got %r'
                             % (ENV_BUDGET, os.environ.get(ENV_BUDGET)))

    def dump_dir(self) -> str:
        with self._lock:
            return self._dump_dir or '.'

    # ------------------------------------------------------- mutation
    def register(self, bucket: str, key: str, source,
                 kind: str = 'array', owner=None,
                 attrs: Optional[dict] = None) -> int:
        """Attribute ``source`` (a pytree of arrays, or an int byte
        count) to ``(bucket, key)``.  Returns the registered bytes."""
        if bucket not in BUCKETS:
            raise ValueError('unknown ledger bucket %r (taxonomy: %s)'
                             % (bucket, list(BUCKETS)))
        nbytes = (int(source) if isinstance(source, (int, float))
                  else tree_nbytes(source))
        entry = _Entry(bucket, key, nbytes, kind, attrs)
        if owner is not None:
            entry.finalizer = weakref.finalize(
                owner, self.release, bucket, key)
        with self._lock:
            old = self._entries.get((bucket, key))
            if old is not None and old.finalizer is not None:
                old.finalizer.detach()
            self._entries[(bucket, key)] = entry
            self._events.append({'t': entry.t, 'op': 'register',
                                 'bucket': bucket, 'key': key,
                                 'bytes': nbytes})
            self._update_watermarks_locked()
            self._export_locked()
        return nbytes

    def release(self, bucket: str, key: str) -> int:
        """Drop an entry (no-op when absent — finalizers may race an
        explicit release).  Returns the released bytes."""
        with self._lock:
            entry = self._entries.pop((bucket, key), None)
            if entry is None:
                return 0
            if entry.finalizer is not None:
                entry.finalizer.detach()
            self._events.append({'t': time.time(), 'op': 'release',
                                 'bucket': bucket, 'key': key,
                                 'bytes': entry.nbytes})
            self._export_locked()
            return entry.nbytes

    # ------------------------------------------------------- accounting
    def _totals_locked(self) -> Dict[str, int]:
        totals = {bucket: 0 for bucket in BUCKETS}
        for entry in self._entries.values():
            totals[entry.bucket] += entry.nbytes
        return totals

    def _attributed_locked(self) -> int:
        """Array-kind bytes only: executables are not ``jax.Array``s and
        must not count against the live-array reconciliation."""
        return sum(entry.nbytes for entry in self._entries.values()
                   if entry.kind == 'array')

    def _update_watermarks_locked(self) -> None:
        totals = self._totals_locked()
        for bucket, value in totals.items():
            if value > self._watermarks.get(bucket, 0):
                self._watermarks[bucket] = value
        attributed = self._attributed_locked()
        if attributed > self._watermarks.get('total', 0):
            self._watermarks['total'] = attributed

    def _export_locked(self) -> None:
        """Mirror bucket totals into the telemetry registry (one gauge
        set per bucket; a no-op bool check when telemetry is off)."""
        if not tele_core.enabled():
            return
        reg = tele_core.registry()
        totals = self._totals_locked()
        for bucket, metric in _BUCKET_GAUGE.items():
            reg.gauge(metric).set(totals[bucket])
        reg.gauge('mem/attributed_bytes').set(self._attributed_locked())
        reg.gauge('mem/watermark_bytes').set(
            self._watermarks.get('total', 0))

    def attributed_bytes(self) -> int:
        with self._lock:
            return self._attributed_locked()

    def bucket_bytes(self, bucket: str) -> int:
        with self._lock:
            return self._totals_locked().get(bucket, 0)

    def export_gauges(self) -> None:
        """Refresh the ``mem/*`` gauges (telemetry flush cadence)."""
        budget = self.budget_bytes()  # env read outside the lock
        with self._lock:
            self._export_locked()
        if tele_core.enabled():
            tele_core.registry().gauge('mem/budget_bytes').set(budget)

    # -------------------------------------------------------- snapshot
    def snapshot(self, reconcile: bool = True,
                 reason: str = 'snapshot') -> Dict[str, Any]:
        """Full ledger state; with ``reconcile`` (the default) also the
        backend's live bytes and the unattributed residual.  Pure
        metadata — zero host syncs, zero compiles."""
        backend = backend_memory() if reconcile else None
        budget = self.budget_bytes()
        with self._lock:
            totals = self._totals_locked()
            attributed = self._attributed_locked()
            buckets = {}
            for bucket in BUCKETS:
                entries = sorted(
                    (e.record() for e in self._entries.values()
                     if e.bucket == bucket),
                    key=lambda r: -r['bytes'])
                buckets[bucket] = {'bytes': totals[bucket],
                                   'entries': entries}
            snap = {
                'time': time.time(),
                'reason': reason,
                'budget_bytes': budget,
                'attributed_bytes': attributed,
                'executables_bytes': totals['executables'],
                'buckets': buckets,
                'watermarks': dict(self._watermarks),
                'events': list(self._events),
            }
        if backend is not None:
            snap['backend'] = backend
            snap['unattributed_bytes'] = (backend['live_bytes']
                                          - attributed)
            if tele_core.enabled():
                reg = tele_core.registry()
                reg.gauge('mem/backend_live_bytes').set(
                    backend['live_bytes'])
                reg.gauge('mem/unattributed_bytes').set(
                    snap['unattributed_bytes'])
        return snap

    @staticmethod
    def diff(before: Dict[str, Any], after: Dict[str, Any]
             ) -> Dict[str, Any]:
        """Delta view of two snapshots — the leak-detection primitive:
        per-bucket byte deltas, per-entry added/removed/grown, and the
        attributed/backend/unattributed deltas."""
        out: Dict[str, Any] = {
            'attributed_delta': (after['attributed_bytes']
                                 - before['attributed_bytes']),
            'buckets': {},
        }
        if 'backend' in before and 'backend' in after:
            out['backend_live_delta'] = (
                after['backend']['live_bytes']
                - before['backend']['live_bytes'])
            out['unattributed_delta'] = (
                after['unattributed_bytes']
                - before['unattributed_bytes'])
        for bucket in BUCKETS:
            b_entries = {e['key']: e['bytes'] for e in
                         before['buckets'][bucket]['entries']}
            a_entries = {e['key']: e['bytes'] for e in
                         after['buckets'][bucket]['entries']}
            changed = {}
            for key in sorted(set(b_entries) | set(a_entries)):
                delta = a_entries.get(key, 0) - b_entries.get(key, 0)
                if delta:
                    changed[key] = delta
            out['buckets'][bucket] = {
                'bytes_delta': (after['buckets'][bucket]['bytes']
                                - before['buckets'][bucket]['bytes']),
                'entries': changed,
            }
        return out

    # ------------------------------------------------ budget/forensics
    def check_budget(self, incoming_bytes: int, what: str) -> None:
        """Refuse an allocation that would cross the budget: dumps the
        forensic ledger and raises ``MemoryBudgetExceeded`` BEFORE any
        device memory moves.  A budget of 0 (the default) admits
        everything."""
        budget = self.budget_bytes()
        if budget <= 0:
            return
        attributed = self.attributed_bytes()
        if attributed + incoming_bytes <= budget:
            return
        path = self.dump(reason='budget: %s' % what)
        raise MemoryBudgetExceeded(
            '%s needs %d bytes but only %d of the %d-byte HBM budget '
            'remain (%d attributed; %s). Nothing was allocated. Ledger '
            'dumped to `%s` — render with scripts/memory_report.py.'
            % (what, incoming_bytes, max(0, budget - attributed),
               budget, attributed, ENV_BUDGET, path))

    def note_oom(self, exc: BaseException, context: str
                 ) -> Optional[str]:
        """OOM forensics hook for the jit-dispatch / attach boundaries:
        when ``exc`` is a backend out-of-memory, dump ``oom_ledger.json``
        (full ledger + watermarks + recent allocation events) so the
        postmortem starts with attribution instead of a bare
        ``RESOURCE_EXHAUSTED``.  Callers re-raise either way."""
        if not is_oom_error(exc):
            return None
        with self._lock:
            self._oom_dumps += 1
        if tele_core.enabled():
            tele_core.registry().counter('mem/oom_dumps_total').inc()
        return self.dump(
            path=os.path.join(self.dump_dir(), OOM_DUMP_NAME),
            reason='oom: %s: %s' % (context, exc))

    def dump(self, path: Optional[str] = None,
             reason: str = 'dump') -> str:
        """Write a reconciled snapshot as JSON (atomic), default
        ``<dump_dir>/oom_ledger.json`` for forensic reasons and
        ``memory_*.json`` for the report paths."""
        if path is None:
            path = os.path.join(self.dump_dir(), OOM_DUMP_NAME)
        out_dir = os.path.dirname(path)
        if out_dir:
            os.makedirs(out_dir, exist_ok=True)
        try:
            snap = self.snapshot(reason=reason)
        except Exception:
            # forensics must not mask the original failure: fall back
            # to the unreconciled ledger if the backend query dies
            snap = self.snapshot(reconcile=False, reason=reason)
        _atomic_write_json(path, snap)
        if tele_core.enabled():
            tele_core.registry().counter('mem/snapshots_total').inc()
        return path

    def reset(self) -> None:
        """Drop every entry and watermark (test isolation)."""
        with self._lock:
            for entry in self._entries.values():
                if entry.finalizer is not None:
                    entry.finalizer.detach()
            self._entries.clear()
            self._events.clear()
            self._watermarks.clear()
            self._budget = None
            self._dump_dir = None
            self._oom_dumps = 0


_LEDGER = MemoryLedger()


def ledger() -> MemoryLedger:
    """The process-global ledger."""
    return _LEDGER


def configure(budget_bytes: Optional[int] = None,
              dump_dir: Optional[str] = None) -> None:
    _LEDGER.configure(budget_bytes=budget_bytes, dump_dir=dump_dir)


def reset() -> None:
    _LEDGER.reset()


# ------------------------------------------------------- MEM_NOW trigger
class MemoryReportController:
    """Touch-file ledger snapshots from a live run, mirroring
    ``TRACE_NOW`` (telemetry/trace.py): ``touch <telemetry_dir>/MEM_NOW``
    and the next telemetry flush consumes it and writes
    ``memory_step<N>.json``.  Repeatable — touch again for another
    snapshot."""

    def __init__(self, out_dir: str, log=None):
        self.out_dir = out_dir
        self.touch_path = os.path.join(out_dir, TOUCH_FILE_NAME)
        self._log = log or (lambda msg: None)

    def poll(self, step: int) -> Optional[str]:
        """Called at the telemetry flush cadence: one ``stat`` per
        flush, nothing per step."""
        if not os.path.exists(self.touch_path):
            return None
        try:
            os.remove(self.touch_path)  # consume: one snapshot per touch
        except OSError:
            pass
        path = os.path.join(self.out_dir, 'memory_step%d.json' % step)
        _LEDGER.dump(path, reason='MEM_NOW at step %d' % step)
        self._log('memory: ledger snapshot written to `%s` (render: '
                  'python scripts/memory_report.py %s)' % (path, path))
        return path


def write_report(config) -> str:
    """``--memory-report``: write a reconciled ledger snapshot next to
    the run's telemetry artifacts and log where it landed."""
    from code2vec_tpu.telemetry.stepwatch import telemetry_dir
    out_dir = telemetry_dir(config)
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, 'memory_report.json')
    _LEDGER.dump(path, reason='--memory-report')
    config.log('memory: ledger report written to `%s` (render: python '
               'scripts/memory_report.py %s)' % (path, path))
    return path
