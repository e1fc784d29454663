"""Serving-mesh benchmark: p99-under-load at FIXED offered load as the
replica count scales (SERVING.md "Serving mesh").

An open-loop load generator submits a mixed tier/size profile — topk +
attention predict requests and ``submit_neighbors`` vectors traffic in
ONE dispatch stream — at a fixed offered rate against a 1-, 2-, and
4-replica mesh over the same model.  Offered load is calibrated to
~2.2x one replica's measured capacity, so the single-replica arm
saturates (admission sheds the excess) while the larger fleets absorb
it: the measured gate is SUSTAINED ADMITTED THROUGHPUT, plus p99
latency over delivered requests, shed/expired rates, per-replica
device fill, and dispatch share.  The telemetry compile counter runs
across every arm — steady-state mesh serving (mixed tiers included)
must compile NOTHING after warmup.

Prints one JSON line per metric:
  {"metric": "mesh_offered_rows_per_sec", "value": ...}
  {"metric": "mesh_admitted_rows_per_sec", "replicas": N, "value": ...,
   "p50_ms": ..., "p99_ms": ..., "shed_rate": ..., "per_replica_fill":
   [...], "dispatch_share": [...], "postwarm_compiles": 0, ...}
  {"metric": "mesh_scaling_2x", "value": admitted_2/admitted_1, ...}

Interpreting the scaling number: replica threads parallelize the
per-batch host pipeline (pack/h2d/dispatch/decode) and concurrent XLA
executions — on a MULTI-core host 2 replicas sustain >= 1.8x one
replica's admitted throughput at this profile; a 1-core container
cannot parallelize anything, so the record carries ``host_cores`` and
the smoke guard (tests/test_bench_smoke.py) gates the ratio assertion
on it.  On-chip runs go through benchmarks/capture_all.sh (stage
``mesh``).

BENCH_SMOKE=1 shrinks shapes, rates, and durations for the CPU smoke
(metrics carry a ``smoke`` field).

Usage: python benchmarks/bench_mesh.py [--replica-counts 1,2,4]
       [--offered-factor 2.2] [--secs S] [--deadline-ms MS]
"""
from __future__ import annotations

import argparse
import json
import os
import random
import sys
import tempfile
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from code2vec_tpu import benchlib  # noqa: E402
from benchmarks.bench_serving import synthesize_dataset  # noqa: E402


class _MiniIndex:
    """Tiny host-side k-NN over a handful of corpus vectors: enough to
    give the ``submit_neighbors`` leg its real shape (vectors-tier
    dispatch through the shared stream, then an index lookup on the
    completion path) without dragging an index build into the bench."""

    def __init__(self, dim: int, n: int = 64, seed: int = 7):
        rng = np.random.default_rng(seed)
        self.vectors = rng.standard_normal((n, dim)).astype(np.float32)
        self.labels = np.array(['method|%d' % i for i in range(n)],
                               dtype=object)

    def search(self, queries, k):
        scores = queries.astype(np.float32) @ self.vectors.T
        idx = np.argsort(-scores, axis=1)[:, :k]
        return np.take_along_axis(scores, idx, axis=1), idx


def make_profile(lines, n_requests: int, max_lines: int, seed: int = 3):
    """Mixed tier/size request profile: ragged sizes, 60% topk / 20%
    attention / 20% neighbors (vectors tier through submit_neighbors)."""
    rng = random.Random(seed)
    profile = []
    for _ in range(n_requests):
        draw = rng.random()
        kind = ('topk' if draw < 0.6 else
                'attention' if draw < 0.8 else 'neighbors')
        request_lines = [rng.choice(lines)
                         for _ in range(rng.randint(1, max_lines))]
        profile.append((kind, request_lines))
    return profile


def make_zipf_profile(lines, n_requests: int, max_lines: int,
                      n_templates: int, alpha: float, seed: int = 5,
                      vec_dim: int = 0, vec_share: float = 0.15):
    """Zipf-replayed duplicate-heavy traffic: a pool of distinct request
    templates replayed with probability proportional to 1/rank^alpha —
    the fleet-traffic shape the memoization tier exists for (hot
    methods arrive over and over; SERVING.md "Memoization tier").
    ``vec_share`` of the templates are single-row VECTOR neighbor
    queries replayed with per-request jitter: near-identical but never
    byte-identical, so exact dedup cannot catch them — the semantic
    tier's traffic."""
    templates = make_profile(lines, n_templates, max_lines, seed=seed)
    rng = np.random.default_rng(seed)
    if vec_dim:
        for t in range(n_templates):
            if rng.random() < vec_share:
                base = rng.standard_normal(vec_dim).astype(np.float32)
                templates[t] = ('neighbors_vec', base)
    ranks = np.arange(1, n_templates + 1, dtype=np.float64)
    weights = ranks ** -alpha
    weights /= weights.sum()
    picks = rng.choice(n_templates, size=n_requests, p=weights)
    profile = []
    for i in picks:
        kind, payload = templates[int(i)]
        if kind == 'neighbors_vec':
            jitter = rng.standard_normal(vec_dim).astype(np.float32)
            payload = payload + np.float32(1e-4) * jitter
        profile.append((kind, payload))
    return profile


def run_arm(model, index, profile, replicas: int, offered_rows_per_s: float,
            deadline_ms: float, compiles, generators: int = 4) -> dict:
    """One fixed-offered-load arm against an n-replica mesh.  The
    arrival schedule (request i lands at cumulative_rows_before_i /
    offered rate) is precomputed and driven by ``generators`` paced
    submitter threads — caller-thread tokenize is part of the serving
    contract, so a single generator thread would itself become the
    bottleneck and silently under-offer the fleet (the achieved rate is
    reported so a generator-limited arm is visible, not hidden)."""
    import threading
    from code2vec_tpu.serving.errors import (DeadlineExceeded,
                                             EngineOverloaded)
    mesh = model.serving_mesh(
        replicas=replicas, tiers=('topk', 'attention', 'vectors'),
        max_delay_ms=2.0, deadline_ms=deadline_ms)
    mesh.attach_index(index)
    warm_compiles = compiles.value if compiles is not None else 0
    delivered_rows = [0]
    latencies = []
    lat_lock = threading.Lock()
    # absolute arrival offsets for the whole profile
    offsets = []
    cum_rows = 0
    for _kind, lines in profile:
        offsets.append(cum_rows / offered_rows_per_s)
        cum_rows += len(lines)
    shed_counts = [0] * generators
    expired_counts = [0] * generators
    futures_per: list = [[] for _ in range(generators)]
    last_submit = [0.0] * generators
    t0 = time.perf_counter()

    def generator(g: int) -> None:
        for i in range(g, len(profile), generators):
            kind, lines = profile[i]
            target = t0 + offsets[i]
            now = time.perf_counter()
            if target > now:
                time.sleep(target - now)
            t_submit = time.perf_counter()
            try:
                if kind == 'neighbors':
                    future = mesh.submit_neighbors(lines)
                else:
                    future = mesh.submit(lines, tier=kind)
            except EngineOverloaded:
                shed_counts[g] += 1
                last_submit[g] = time.perf_counter()
                continue

            def stamp(done, t_submit=t_submit, rows=len(lines)):
                if done.exception() is None:
                    with lat_lock:
                        latencies.append(time.perf_counter() - t_submit)
                        delivered_rows[0] += rows
            future.add_done_callback(stamp)
            futures_per[g].append(future)
            last_submit[g] = time.perf_counter()

    try:
        threads = [threading.Thread(target=generator, args=(g,))
                   for g in range(generators)]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join()
        for g in range(generators):
            for future in futures_per[g]:
                try:
                    future.result(timeout=600)
                except DeadlineExceeded:
                    expired_counts[g] += 1
                except EngineOverloaded:
                    shed_counts[g] += 1
        wall = time.perf_counter() - t0
        submit_wall = max(last_submit) - t0
        stats = mesh.stats()
        per_replica = mesh.replica_stats()
    finally:
        mesh.close()
    postwarm = (compiles.value - warm_compiles
                if compiles is not None else None)
    shed = sum(shed_counts)
    expired = sum(expired_counts)
    lat_ms = np.asarray(sorted(latencies)) * 1e3
    total = len(profile)
    return {
        'replicas': replicas,
        'value': round(delivered_rows[0] / wall, 1),
        'delivered_rows': delivered_rows[0],
        'offered_rows_per_sec': round(offered_rows_per_s, 1),
        'achieved_offer_rows_per_sec':
            round(cum_rows / max(1e-9, submit_wall), 1),
        'wall_s': round(wall, 2),
        'p50_ms': (round(float(np.percentile(lat_ms, 50)), 2)
                   if len(lat_ms) else None),
        'p99_ms': (round(float(np.percentile(lat_ms, 99)), 2)
                   if len(lat_ms) else None),
        'shed_rate': round(shed / total, 3),
        'expired_rate': round(expired / total, 3),
        'mesh_shed_total': stats['shed_total'],
        'mesh_expired_total': stats['expired_total'],
        'per_replica_fill': [
            round(float(s['batch_fill_rate']), 3) for s in per_replica],
        'dispatch_share': [
            round(r['dispatch_share'], 3) for r in stats['replicas']],
        'replica_batches': [r['batches'] for r in stats['replicas']],
        'postwarm_compiles': postwarm,
    }


def run_memo_arm(model, index, profile, offered_rows_per_s: float,
                 deadline_ms: float, compiles, memo_bytes: int,
                 epsilon: float, capacity: float,
                 generators: int = 4) -> dict:
    """One Zipf-replay arm: the same paced open-loop driver as
    ``run_arm``, but latencies split at the SUBMIT boundary — a memo
    hit comes back already resolved (``future.done()`` on return), so
    cache-served and live-served p99 are measured separately.  Device
    work is the mesh's ``rows_dispatched`` (a hit never dispatches);
    device-seconds-per-1k-requests is the host-side proxy
    rows_dispatched / one replica's measured capacity."""
    import threading
    from code2vec_tpu.serving.errors import (DeadlineExceeded,
                                             EngineOverloaded)
    mesh = model.serving_mesh(
        replicas=1, tiers=('topk', 'attention', 'vectors'),
        max_delay_ms=2.0, deadline_ms=deadline_ms,
        memo_cache_bytes=memo_bytes, memo_semantic_epsilon=epsilon)
    mesh.attach_index(index)
    warm_compiles = compiles.value if compiles is not None else 0
    cache_lat: list = []
    live_lat: list = []
    vec_lat: list = []
    lat_lock = threading.Lock()
    offsets = []
    cum_rows = 0
    for kind, payload in profile:
        offsets.append(cum_rows / offered_rows_per_s)
        cum_rows += 1 if kind == 'neighbors_vec' else len(payload)
    shed_counts = [0] * generators
    expired_counts = [0] * generators
    futures_per: list = [[] for _ in range(generators)]
    t0 = time.perf_counter()

    def generator(g: int) -> None:
        for i in range(g, len(profile), generators):
            kind, payload = profile[i]
            target = t0 + offsets[i]
            now = time.perf_counter()
            if target > now:
                time.sleep(target - now)
            t_submit = time.perf_counter()
            try:
                if kind in ('neighbors', 'neighbors_vec'):
                    future = mesh.submit_neighbors(payload)
                else:
                    future = mesh.submit(payload, tier=kind)
            except EngineOverloaded:
                shed_counts[g] += 1
                continue
            if kind == 'neighbors_vec':
                # vector queries never ride the device (the index is
                # host-side here) — timed separately; the semantic
                # tier's effect shows in semantic_hits + vec p99
                def vstamp(done, t_submit=t_submit):
                    if done.exception() is None:
                        with lat_lock:
                            vec_lat.append(
                                time.perf_counter() - t_submit)
                future.add_done_callback(vstamp)
            elif future.done() and future.exception() is None:
                # resolved AT submit: served from the memo tier (a
                # live request cannot complete before submit returns —
                # it has a device round-trip ahead of it)
                with lat_lock:
                    cache_lat.append(time.perf_counter() - t_submit)
            else:
                def stamp(done, t_submit=t_submit):
                    if done.exception() is None:
                        with lat_lock:
                            live_lat.append(
                                time.perf_counter() - t_submit)
                future.add_done_callback(stamp)
            futures_per[g].append(future)

    try:
        threads = [threading.Thread(target=generator, args=(g,))
                   for g in range(generators)]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join()
        for g in range(generators):
            for future in futures_per[g]:
                try:
                    future.result(timeout=600)
                except DeadlineExceeded:
                    expired_counts[g] += 1
                except EngineOverloaded:
                    shed_counts[g] += 1
        wall = time.perf_counter() - t0
        stats = mesh.stats()
    finally:
        mesh.close()
    postwarm = (compiles.value - warm_compiles
                if compiles is not None else None)
    memo_stats = stats['memo']
    total = len(profile)
    device_rows = stats['rows_dispatched']

    def p99(lat):
        arr = np.asarray(sorted(lat)) * 1e3
        return round(float(np.percentile(arr, 99)), 3) if len(arr) \
            else None

    return {
        'cache_served': len(cache_lat),
        'live_served': len(live_lat),
        'hit_rate': (round(memo_stats['hit_rate'], 3)
                     if memo_stats else 0.0),
        'memo_entries': memo_stats['entries'] if memo_stats else 0,
        'memo_bytes': memo_stats['bytes'] if memo_stats else 0,
        'semantic_hits': (memo_stats['semantic_hits']
                          if memo_stats else 0),
        'semantic_agreement': (memo_stats['semantic']['agreement']
                               if memo_stats else None),
        'cache_p99_ms': p99(cache_lat),
        'live_p99_ms': p99(live_lat),
        'vec_served': len(vec_lat),
        'vec_p99_ms': p99(vec_lat),
        'shed_rate': round(sum(shed_counts) / total, 3),
        'expired_rate': round(sum(expired_counts) / total, 3),
        'device_rows_dispatched': device_rows,
        'device_rows_per_1k_requests':
            round(device_rows * 1e3 / total, 1),
        'device_seconds_per_1k_requests':
            round(device_rows / max(1e-9, capacity) * 1e3 / total, 4),
        'postwarm_compiles': postwarm,
        'wall_s': round(wall, 2),
    }


def run_stepped_arm(model, lines, capacity: float, max_lines: int,
                    secs: float, compiles) -> list:
    """Stepped-offered-load arm (SERVING.md "Elastic fleet"): one
    process-mode replica with the SLO/queue-driven autoscaler live,
    driven low -> high -> low.  The high step must pull the fleet to 2
    replicas (scale-up latency = load step to the new replica LIVE,
    cold start included); the low step must drain it back to 1
    (scale-down latency = load step to the drained slot retired); p99
    over requests submitted DURING each transition window is reported
    next to steady-state p99 — the cost of an elastic transition is a
    latency bulge, never a lost or misrouted request.

    CPU-only today: the parent here has loaded the model, so on a TPU
    it holds the chip and a locally spawned process-mode worker cannot
    initialise the backend — ``ServingMesh`` refuses the combination at
    construction with ``LocalWorkerNeedsHeldChip`` (PERF.md "Bring-up",
    PR 21) and this arm raises it. ROADMAP C5 decides whether 'process'
    mode survives."""
    import random as random_lib
    import threading
    from code2vec_tpu.serving.errors import ServingError
    config = model.config
    knobs = dict(
        MESH_REPLICA_MODE='process',
        AUTOSCALE_MAX_REPLICAS=2, AUTOSCALE_MIN_REPLICAS=1,
        AUTOSCALE_INTERVAL_SECS=0.25,
        # the shared queue's admission bound caps visible backlog, so
        # the up threshold must sit well UNDER bound/service_rate or a
        # bounded queue can never look busy enough to scale
        AUTOSCALE_UP_QUEUE_SECS=0.02,
        AUTOSCALE_UP_COOLDOWN_SECS=2.0,
        AUTOSCALE_DOWN_COOLDOWN_SECS=2.0,
        AUTOSCALE_DOWN_IDLE_SECS=1.0,
        AUTOSCALE_DOWN_UTILIZATION=0.9,
        AUTOSCALE_FLAP_WINDOW_SECS=120.0, AUTOSCALE_FLAP_LIMIT=20)
    old = {name: getattr(config, name) for name in knobs}
    for name, value in knobs.items():
        setattr(config, name, value)
    try:
        mesh = model.serving_mesh(replicas=1, tiers=('topk',),
                                  max_delay_ms=2.0)
    finally:
        for name, value in old.items():
            setattr(config, name, value)
    records = []
    lat = []
    lat_lock = threading.Lock()
    shed = [0]
    rng = random_lib.Random(17)
    live_mark = {'t': None}
    stats_gate = [0.0]

    def live_replicas() -> int:
        # throttled: the pacing loop polls this per submit
        now = time.perf_counter()
        if now < stats_gate[0] and live_mark.get('last') is not None:
            return live_mark['last']
        stats_gate[0] = now + 0.05
        live_mark['last'] = mesh.stats()['replicas_live']
        return live_mark['last']

    def drive(rate_rows_per_s: float, seconds: float = None,
              until=None, timeout: float = 180.0):
        """Paced submits at the offered rate until the duration (or
        the condition) is reached; returns (elapsed_s, condition_met)."""
        t_start = time.perf_counter()
        next_t = t_start
        while True:
            now = time.perf_counter()
            if until is not None and until():
                return now - t_start, True
            if seconds is not None and now - t_start >= seconds:
                return now - t_start, False
            if until is not None and now - t_start >= timeout:
                return now - t_start, False
            n = rng.randint(1, max_lines)
            request_lines = [rng.choice(lines) for _ in range(n)]
            t_submit = time.perf_counter()
            try:
                future = mesh.submit(request_lines, tier='topk')
            except ServingError:
                shed[0] += 1
            else:
                def stamp(done, t_submit=t_submit):
                    # completion-time latency, stamped when the future
                    # RESOLVES (not when the drain loop reaches it)
                    if done.exception() is None:
                        with lat_lock:
                            lat.append((t_submit,
                                        time.perf_counter() - t_submit))
                future.add_done_callback(stamp)
                records.append(future)
            next_t += n / rate_rows_per_s
            pause = next_t - time.perf_counter()
            if pause > 0:
                time.sleep(pause)
            else:
                if -pause > 1.0:
                    # the generator fell behind the schedule (caller-
                    # thread tokenize is part of the serving contract):
                    # don't accumulate debt into a burst, and yield so
                    # the fleet and the autoscaler keep their cores
                    next_t = time.perf_counter()
                time.sleep(0.0005)

    def drive_burst(rows_per_burst: float, period_s: float,
                    seconds: float = None, until=None,
                    timeout: float = 180.0):
        """Bursty offered load: ``rows_per_burst`` rows submitted
        back-to-back each ``period_s``.  A paced generator sharing
        cores with the fleet cannot reliably out-offer it (the
        tokenize-in-caller contract), but a burst pins the bounded
        queue full on every period — the unambiguous shape of a load
        step, which is what the scale-up trigger must see."""
        t_start = time.perf_counter()
        while True:
            now = time.perf_counter()
            if until is not None and until():
                return now - t_start, True
            if seconds is not None and now - t_start >= seconds:
                return now - t_start, False
            if until is not None and now - t_start >= timeout:
                return now - t_start, False
            sent = 0
            while sent < rows_per_burst:
                n = rng.randint(1, max_lines)
                request_lines = [rng.choice(lines) for _ in range(n)]
                t_submit = time.perf_counter()
                try:
                    future = mesh.submit(request_lines, tier='topk')
                except ServingError:
                    shed[0] += 1
                else:
                    def stamp(done, t_submit=t_submit):
                        if done.exception() is None:
                            with lat_lock:
                                lat.append(
                                    (t_submit,
                                     time.perf_counter() - t_submit))
                    future.add_done_callback(stamp)
                    records.append(future)
                sent += n
            rest = period_s - (time.perf_counter() - now)
            if rest > 0:
                time.sleep(rest)

    warm_compiles = compiles.value
    windows = {}
    try:
        # process-replica capacity probe: the thread-mode calibration
        # over-reads a worker's capacity (no IPC, no wire) — the steps
        # are sized against THIS mesh's single replica so 'high' is a
        # genuine 2x overload, not a host-starving flood
        proc_capacity = 0.0
        for _ in range(2):
            probe = []
            probe_rows = 0
            t_probe = time.perf_counter()
            for _ in range(32):
                n = rng.randint(1, max_lines)
                probe_rows += n
                probe.append(mesh.submit(
                    [rng.choice(lines) for _ in range(n)],
                    tier='topk'))
            for future in probe:
                future.result(timeout=600)
            proc_capacity = max(
                proc_capacity,
                probe_rows / (time.perf_counter() - t_probe))
        low = 0.4 * proc_capacity
        high = 2.0 * proc_capacity
        # steady low: one replica is comfortable, no scaling
        drive(low, seconds=max(2.0, secs * 0.4))
        base_up = mesh.stats()['autoscaler']['scale_up_total']
        # ---- STEP UP: the high step must pull a second replica ----
        # 2x offered as half-second bursts of one replica-second of
        # rows each: every burst refills the bounded queue, so the
        # drain estimate stays over the up threshold for as long as
        # the step lasts
        t_step_up = time.perf_counter()
        _, scaled = drive_burst(proc_capacity, 0.5,
                                until=lambda: live_replicas() >= 2)
        t_live2 = time.perf_counter()
        windows['up'] = (t_step_up, t_live2, scaled)
        # steady at 2: the transition bulge must clear
        drive_burst(proc_capacity, 0.5, seconds=max(2.0, secs * 0.3))
        # ---- STEP DOWN: sustained low must drain the extra out ----
        t_step_down = time.perf_counter()
        _, drained = drive(
            low, until=lambda: live_replicas() <= 1
            and mesh.stats()['autoscaler']['scale_down_total'] >= 1)
        t_live1 = time.perf_counter()
        windows['down'] = (t_step_down, t_live1, drained)
        drive(low, seconds=max(1.0, secs * 0.2))
        asc_stats = mesh.stats()['autoscaler']
        retired = [(r['replica'], r['retired_reason'])
                   for r in mesh.stats()['replicas'] if r['retired']]
        # drain every admitted future (latencies stamped by the done
        # callbacks above); failures must all be typed
        typed = 0
        for future in records:
            try:
                future.result(timeout=600)
            except ServingError:
                typed += 1
    finally:
        mesh.close()
    postwarm = compiles.value - warm_compiles

    def p99_ms(pairs):
        arr = np.asarray(sorted(l for _, l in pairs)) * 1e3
        return round(float(np.percentile(arr, 99)), 1) if len(arr) \
            else None

    up_t0, up_t1, scaled = windows['up']
    down_t0, down_t1, drained = windows['down']
    in_up = [p for p in lat if up_t0 <= p[0] < up_t1]
    in_down = [p for p in lat if down_t0 <= p[0] < down_t1]
    steady = [p for p in lat
              if not (up_t0 <= p[0] < up_t1)
              and not (down_t0 <= p[0] < down_t1)]
    out = []
    out.append({'metric': 'mesh_stepped_scale_up_s',
                'value': round(up_t1 - up_t0, 2) if scaled else None,
                'reached_2_replicas': scaled,
                'offered_low_rows_per_sec': round(low, 1),
                'offered_high_rows_per_sec': round(high, 1),
                'process_capacity_rows_per_sec_1r':
                    round(proc_capacity, 1),
                'scale_up_total': asc_stats['scale_up_total'],
                'scale_up_before_step': base_up})
    out.append({'metric': 'mesh_stepped_scale_down_s',
                'value': (round(down_t1 - down_t0, 2)
                          if drained else None),
                'drained_to_1_replica': drained,
                'scale_down_total': asc_stats['scale_down_total'],
                'retired': retired})
    out.append({'metric': 'mesh_stepped_transition_p99_ms',
                'value': p99_ms(in_up + in_down),
                'up_p99_ms': p99_ms(in_up),
                'down_p99_ms': p99_ms(in_down),
                'steady_p99_ms': p99_ms(steady),
                'delivered': len(lat), 'typed_failures': typed,
                'shed_at_admission': shed[0],
                'flap_freezes_total': asc_stats['flap_freezes_total'],
                'postwarm_compiles': postwarm,
                'host_cores': os.cpu_count()})
    return out


def measure_capacity(model, index, profile, reps: int = 2) -> float:
    """One replica's sustainable rows/s: open-loop firehose (no arrival
    pacing, no deadline) through a 1-replica mesh — delivered rows over
    the drain wall clock, best of ``reps`` (the first rep pays
    first-dispatch warm-in; under-measuring capacity would under-size
    the offered load and starve every arm of its saturation regime)."""
    # queue_bound=-1: the firehose deliberately holds the whole profile
    # in flight; the admission bound is the LOAD arms' regime, not the
    # capacity probe's
    mesh = model.serving_mesh(replicas=1,
                             tiers=('topk', 'attention', 'vectors'),
                             max_delay_ms=2.0, queue_bound=-1)
    mesh.attach_index(index)
    best = 0.0
    try:
        for _ in range(reps):
            rows = 0
            futures = []
            t0 = time.perf_counter()
            for kind, lines in profile:
                rows += len(lines)
                if kind == 'neighbors':
                    futures.append(mesh.submit_neighbors(lines))
                else:
                    futures.append(mesh.submit(lines, tier=kind))
            for future in futures:
                future.result(timeout=600)
            best = max(best, rows / (time.perf_counter() - t0))
    finally:
        mesh.close()
    return best


def main() -> None:
    smoke = benchlib.smoke_requested()
    parser = argparse.ArgumentParser()
    parser.add_argument('--replica-counts', default='1,2,4',
                        help='mesh sizes to drive, comma-separated')
    parser.add_argument('--offered-factor', type=float, default=2.2,
                        help='offered load as a multiple of one '
                             "replica's measured capacity")
    parser.add_argument('--secs', type=float,
                        default=4.0 if smoke else 20.0,
                        help='load duration per arm (approximate: the '
                             'profile is sized as offered x secs)')
    parser.add_argument('--deadline-ms', type=float,
                        default=2000.0,
                        help='per-request SLO deadline under load '
                             '(drives shed/expiry at saturation)')
    parser.add_argument('--stepped-load', action='store_true',
                        help='run the stepped-offered-load elasticity '
                             'arm instead of the replica-scaling arms: '
                             'low -> high -> low against one process '
                             'replica with the autoscaler live; '
                             'reports scale-up/scale-down latency and '
                             'transition p99 (SERVING.md "Elastic '
                             'fleet")')
    parser.add_argument('--zipf-alpha', type=float, default=0.0,
                        help='run the memoization-tier comparison '
                             'instead of the replica-scaling arms: '
                             'replay a Zipf(alpha)-weighted template '
                             'pool through memo off / exact / '
                             'exact+semantic meshes (SERVING.md '
                             '"Memoization tier")')
    parser.add_argument('--memo-templates', type=int, default=None,
                        help='distinct request templates in the Zipf '
                             'pool (default 48 smoke / 256)')
    parser.add_argument('--memo-cache-bytes', type=int,
                        default=64 << 20,
                        help='exact-tier cache budget for the memo '
                             'arms')
    parser.add_argument('--memo-epsilon', type=float, default=0.05,
                        help='semantic-tier epsilon for the '
                             'exact+semantic arm')
    parser.add_argument('--memo-offered-factor', type=float,
                        default=0.8,
                        help='memo arms run below one replica\'s '
                             'capacity (sustainable regime: p99 '
                             'comparisons are about the cache, not '
                             'saturation)')
    parser.add_argument('--max-request-lines', type=int,
                        default=4 if smoke else 8)
    parser.add_argument('--rows', type=int, default=200 if smoke else 2000)
    parser.add_argument('--contexts', type=int, default=6 if smoke else 200)
    parser.add_argument('--tokens', type=int, default=500 if smoke else 20000)
    parser.add_argument('--paths', type=int, default=500 if smoke else 30000)
    parser.add_argument('--labels', type=int, default=100 if smoke else 5000)
    parser.add_argument('--buckets', default='8,32' if smoke else '8,32,128')
    args = parser.parse_args()

    from code2vec_tpu.config import Config
    from code2vec_tpu.model_api import Code2VecModel
    from code2vec_tpu.telemetry import core as tele_core
    from code2vec_tpu.telemetry.jit_tracker import install_compile_listener

    workdir = tempfile.mkdtemp(prefix='c2v_meshbench_')
    prefix = os.path.join(workdir, 'synth')
    lines = synthesize_dataset(prefix, args.rows, args.contexts,
                               args.tokens, args.paths, args.labels)
    config = Config(
        TRAIN_DATA_PATH_PREFIX=prefix, DL_FRAMEWORK='jax',
        VERBOSE_MODE=0, READER_USE_NATIVE=False,
        MAX_CONTEXTS=args.contexts, SERVING_BATCH_BUCKETS=args.buckets,
        # the stepped arm scales PROCESS replicas: workers restore
        # params from the checkpoint store
        MODEL_SAVE_PATH=(os.path.join(workdir, 'model')
                         if args.stepped_load else ''))
    model = Code2VecModel(config)
    index = _MiniIndex(config.CODE_VECTOR_SIZE)

    tele_core.enable()
    install_compile_listener()
    compiles = tele_core.registry().counter('jit/compiles_total')

    def emit(record):
        if smoke:
            record['smoke'] = True
        print(json.dumps(record), flush=True)

    counts = [int(c) for c in args.replica_counts.split(',') if c.strip()]

    # calibration: one replica's capacity on the same mixed profile
    cal_profile = make_profile(lines, 192 if smoke else 512,
                               args.max_request_lines, seed=11)
    capacity = measure_capacity(model, index, cal_profile)

    if args.stepped_load:
        # ---- elasticity arm (stage mesh_stepped) ----
        model.save(state=model.state, epoch=0, wait=True)
        emit({'metric': 'mesh_capacity_rows_per_sec_1r',
              'value': round(capacity, 1)})
        for record in run_stepped_arm(model, lines, capacity,
                                      args.max_request_lines,
                                      args.secs, compiles):
            emit(record)
        emit({'metric': 'mesh_peak_hbm_bytes',
              **benchlib.device_memory_record()})
        return

    if args.zipf_alpha > 0:
        # ---- memoization-tier comparison (stage mesh_memo) ----
        n_templates = (args.memo_templates if args.memo_templates
                       else (48 if smoke else 256))
        offered = args.memo_offered_factor * capacity
        emit({'metric': 'mesh_memo_capacity_rows_per_sec_1r',
              'value': round(capacity, 1)})
        mean_rows = (1 + args.max_request_lines) / 2
        n_requests = max(64, int(offered * args.secs / mean_rows))
        profile = make_zipf_profile(lines, n_requests,
                                    args.max_request_lines,
                                    n_templates, args.zipf_alpha,
                                    vec_dim=config.CODE_VECTOR_SIZE)
        arms = (('off', 0, 0.0),
                ('exact', args.memo_cache_bytes, 0.0),
                ('exact+semantic', args.memo_cache_bytes,
                 args.memo_epsilon))
        for name, memo_bytes, epsilon in arms:
            arm = run_memo_arm(model, index, profile, offered,
                               args.deadline_ms, compiles, memo_bytes,
                               epsilon, capacity)
            arm.update({'metric': 'mesh_memo_arm', 'memo': name,
                        'zipf_alpha': args.zipf_alpha,
                        'templates': n_templates,
                        'requests': len(profile),
                        'offered_rows_per_sec': round(offered, 1),
                        'host_cores': os.cpu_count()})
            emit(arm)
        emit({'metric': 'mesh_peak_hbm_bytes',
              **benchlib.device_memory_record()})
        return

    offered = args.offered_factor * capacity
    emit({'metric': 'mesh_capacity_rows_per_sec_1r',
          'value': round(capacity, 1)})
    emit({'metric': 'mesh_offered_rows_per_sec',
          'value': round(offered, 1), 'factor': args.offered_factor,
          'host_cores': os.cpu_count()})

    # profile sized to ~secs of offered load; mean rows/request =
    # (1 + max)/2
    mean_rows = (1 + args.max_request_lines) / 2
    n_requests = max(32, int(offered * args.secs / mean_rows))
    profile = make_profile(lines, n_requests, args.max_request_lines)
    tiers_served = sorted({kind for kind, _ in profile})

    admitted = {}
    for n in counts:
        arm = run_arm(model, index, profile, n, offered,
                      args.deadline_ms, compiles)
        arm.update({'metric': 'mesh_admitted_rows_per_sec',
                    'tiers': tiers_served,
                    'host_cores': os.cpu_count()})
        admitted[n] = arm['value']
        emit(arm)

    base = counts[0]
    for n in counts[1:]:
        emit({'metric': 'mesh_scaling_%dx' % (n // base),
              'value': round(admitted[n] / max(1e-9, admitted[base]), 3),
              'replicas': n, 'vs_replicas': base,
              'host_cores': os.cpu_count(),
              'note': 'admitted-throughput ratio at fixed offered '
                      'load; >=1.8 expected at 2x on multi-core hosts '
                      '/ on chip'})
    emit({'metric': 'mesh_peak_hbm_bytes',
          **benchlib.device_memory_record()})


if __name__ == '__main__':
    main()
