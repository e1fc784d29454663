"""Typed errors of the serving resilience layer (SERVING.md "Overload &
rollover runbook"; ROBUSTNESS.md serving pillar).

Every way a serving request can fail WITHOUT a model answer has a named
type here, so callers can route on it (shed -> retry elsewhere with
backoff; expired -> drop, the client already timed out; closed -> this
replica is going away) instead of string-matching RuntimeError text.

Hierarchy notes:

- the engine-side errors subclass ``RuntimeError``: pre-resilience
  callers that caught ``RuntimeError`` around ``submit`` keep working;
- the extractor-side errors subclass ``ValueError``: the REPL loop's
  "extraction errors are user-recoverable" contract
  (serving/predict.py) catches ``ValueError``, and these must ride that
  path — an unavailable extractor re-prompts instead of killing the
  shell.
"""
from __future__ import annotations


class ServingError(RuntimeError):
    """Base of the serving engine's typed request failures."""


class EngineClosed(ServingError):
    """The engine is shut down (or closing): the request was rejected at
    submit, or its future was failed by a non-draining ``close()``.
    Clients should fail over to another replica."""


class EngineOverloaded(ServingError):
    """Admission control shed this request: the bounded queue is full,
    the drain estimate exceeds the request's deadline, or a
    ``reject_all`` fault drill is armed. Nothing was enqueued — retry
    against another replica or with client-side backoff."""


class DeadlineExceeded(ServingError):
    """The request was admitted but its SLO deadline passed while it
    waited in the queue; it was expired instead of dispatching work the
    client has already given up on."""


class SessionLost(ServingError):
    """A language model's resident session lost its cache: a step failed,
    the pools were reset, and what the session's earlier turns left on the
    device went with them.  The turns of it that stood in the queue fail
    with this, and so does every later ``submit(session=...)`` until the
    caller acknowledges the loss with ``close_session`` and sends the
    history again: a turn served from position 0 would be a normal-looking
    answer computed without the context the session promises."""


class ReplicaDead(ServingError):
    """The mesh replica holding this request died (worker exit, wire
    corruption, or heartbeat-declared liveness failure) and the request
    could not be served anywhere else: it had already been redispatched
    once after a previous crash, or no serving replica remains.  A
    first crash is invisible to callers — the mesh re-admits the batch
    members at the queue front and a sibling (or the supervised
    restart) serves them."""


class AdoptionRejected(ServingError):
    """An externally-spawned worker dialed the mesh listener but failed
    adoption validation — wire-proto / batch-wire-format mismatch, a
    warm-tier ladder that does not cover the fleet's, a duplicate
    replica id, or a ready frame that never arrived within the adoption
    timeout.  The dial-in is answered with a typed ``adopt_rejected``
    frame and closed; the orchestrator that spawned the worker owns the
    retry (restart supervision for external workers is explicitly NOT
    the mesh's job — SERVING.md "Elastic fleet")."""


class LocalWorkerNeedsHeldChip(ServingError):
    """A worker-mode mesh (``MESH_REPLICA_MODE`` 'process' or 'socket')
    was asked to spawn a worker on this machine while the parent process
    holds its TPU.  A TPU belongs to one process: the child's backend
    init fails with "The TPU is already in use by process with pid N"
    (established on a v5e, PERF.md "Bring-up"), and nothing in the tree
    restricts a child's visible chips.  Refused at mesh construction,
    before any spawn, instead of waiting out the worker start timeout.
    Thread mode, and socket workers started on another machine
    (scripts/mesh_worker.py), are unaffected."""


class WireError(ServingError):
    """A mesh transport frame failed validation — bad magic, truncated
    body, or CRC mismatch (the on-wire shape of a worker dying mid-
    write, or of stream corruption).  The replica behind the wire is
    failed typed and its stream abandoned; one bad frame never poisons
    the parent's receiver into misparsing every later frame."""


class ExtractorError(ValueError):
    """Base of the extractor bridge's typed failures (a ``ValueError``
    so the REPL's recoverable-error contract holds)."""


class ExtractorCrash(ExtractorError):
    """One extractor invocation failed for an infrastructure reason —
    spawn failure, nonzero/signal exit, or per-call timeout — as opposed
    to a clean "no paths in this input" outcome. Retried by
    ``ExtractorPool``; counted against its circuit breaker."""


class ExtractorUnavailable(ExtractorError):
    """The extractor circuit breaker is OPEN: recent calls crashed
    consecutively past the threshold, so the pool fails fast (no
    subprocess spawn, no timeout wait) until the cooldown elapses and a
    half-open probe succeeds."""
