"""Chaos soak for the self-healing serving mesh (SERVING.md
"Multi-host mesh").

A paced open-loop generator drives a worker-mode mesh while the fault
grammar periodically kills its workers: every worker incarnation is
armed with ``kill_worker`` (SIGKILL at its K-th dispatch, mid-batch)
and ``drop_heartbeat`` (goes silent after its B-th beat, the
hung-worker shape) — each supervised restart re-arms the plan in the
fresh process, so the faults fire PERIODICALLY for the whole soak.
The assertions are the self-healing contract:

- **zero lost admitted requests** — every submitted future resolves
  with results or a TYPED serving error; a hung future or an untyped
  exception fails the soak (crash-safe redispatch + supervised restart
  mean a crash costs latency, not answers);
- **zero post-warmup compiles in the parent** — healing never escapes
  the warm path on the serving side of the wire (worker cold starts
  compile in their OWN processes, off the parent's counter);
- **bounded p99** — restart latency is visible but bounded
  (``--p99-bound-ms``);
- **zero unstitched trace trees** — at ``TRACING_SAMPLE_RATE=1.0``,
  every delivered request's span tree must carry its worker-side
  device-execute spans (cross-process stitching, OBSERVABILITY.md
  "Fleet observability"); a wire-truncated tree fails the soak
  (memo-hit traces are exempt by design — they never reach a worker);
- **zero stale memo serves** — the soak runs with the memoization tier
  ON (``--memo-bytes``) and half the load replaying one hot request;
  mid-soak fleet rollover drills (``--rollovers``) swap params to a
  freshly saved step and assert the swap atomically invalidated the
  cache: zero entries survive, the first post-swap duplicate runs
  LIVE, and the generation advanced per completed rollover;
- **elastic transitions survive the chaos** (SERVING.md "Elastic
  fleet") — mid-soak the fleet SCALES UP by one replica while the
  kill/heartbeat chaos keeps firing (the cold start, step re-adopt,
  and queue join must not lose a request), serves through it, then
  DRAINS that replica back out while a ``partition`` fault blackholes
  parent-side frames — the liveness monitor, not the drain, must break
  the stall, the retirement lands typed (``retired_reason='drain'``),
  and zero admitted requests are lost across both transitions.

Prints one JSON line per metric (``mesh_soak_*``); exit 1 on any
violation.  ``BENCH_SMOKE=1`` shrinks shapes and duration for the
tier-1 smoke (tests/test_bench_smoke.py); the slow-marked full run and
``capture_all.sh`` (stage ``mesh_soak``) use the real durations.

Usage: python scripts/mesh_soak.py [--secs S] [--replicas N]
       [--mode process|socket] [--kill-every K] [--drop-beat-at B]
       [--interval-ms MS] [--p99-bound-ms MS] [--memo-bytes B]
       [--rollovers R]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from code2vec_tpu import benchlib  # noqa: E402


def main() -> int:
    smoke = benchlib.smoke_requested()
    parser = argparse.ArgumentParser()
    parser.add_argument('--secs', type=float,
                        default=10.0 if smoke else 45.0,
                        help='paced-load duration')
    parser.add_argument('--replicas', type=int, default=2)
    parser.add_argument('--mode', default='process',
                        choices=['process', 'socket'])
    parser.add_argument('--kill-every', type=int,
                        default=6 if smoke else 25,
                        help='kill_worker fires at each incarnation\'s '
                             'K-th dispatch (mid-batch SIGKILL)')
    parser.add_argument('--drop-beat-at', type=int,
                        default=14 if smoke else 60,
                        help='drop_heartbeat window start: the '
                             'incarnation goes silent from its B-th '
                             'beat (liveness kill)')
    parser.add_argument('--interval-ms', type=float,
                        default=80.0 if smoke else 50.0,
                        help='pacing between submits')
    parser.add_argument('--p99-bound-ms', type=float, default=30000.0,
                        help='bounded-p99 assertion over delivered '
                             'requests (restart latency included)')
    parser.add_argument('--memo-bytes', type=int, default=32 << 20,
                        help='memoization-tier budget for the soak '
                             '(default ON: the chaos drills must hold '
                             'with the cache in front of the fleet; '
                             '0 disables)')
    parser.add_argument('--rollovers', type=int, default=2,
                        help='mid-soak fleet rollover drills: each '
                             'must atomically invalidate the memo '
                             'cache (generation bump) with zero stale '
                             'serves after the swap')
    parser.add_argument('--elastic', type=int, default=1,
                        help='run the elastic-transition drill: scale '
                             'up one replica under the kill chaos, '
                             'serve, then drain it back out during a '
                             'partition window (0 disables)')
    parser.add_argument('--index-rollovers', type=int, default=1,
                        help='run the canaried INDEX rollover drill: '
                             'shadow-query a disagreeing candidate on '
                             'live neighbor traffic (must roll back, '
                             'memo stays warm), then an agreeing one '
                             '(must swap: memo index generation bumps, '
                             'zero stale neighbor serves, predict '
                             'entries survive) (0 disables)')
    parser.add_argument('--rows', type=int, default=200 if smoke else 1000)
    parser.add_argument('--contexts', type=int, default=6 if smoke else 50)
    parser.add_argument('--tokens', type=int, default=500 if smoke else 5000)
    parser.add_argument('--paths', type=int, default=500 if smoke else 8000)
    parser.add_argument('--labels', type=int, default=100 if smoke else 1000)
    args = parser.parse_args()

    from benchmarks.bench_serving import synthesize_dataset
    from code2vec_tpu.config import Config
    from code2vec_tpu.model_api import Code2VecModel
    from code2vec_tpu.resilience import faults
    from code2vec_tpu.serving.errors import ServingError
    from code2vec_tpu.telemetry import core as tele_core
    from code2vec_tpu.telemetry.jit_tracker import install_compile_listener

    workdir = tempfile.mkdtemp(prefix='c2v_meshsoak_')
    prefix = os.path.join(workdir, 'synth')
    lines = synthesize_dataset(prefix, args.rows, args.contexts,
                               args.tokens, args.paths, args.labels)
    # every restarted worker re-arms this plan in its fresh process, so
    # the faults fire once per INCARNATION — periodic chaos by
    # construction
    fault_spec = ('kill_worker@dispatch=%d,drop_heartbeat@beat=%d..%d'
                  % (args.kill_every, args.drop_beat_at,
                     args.drop_beat_at + 9999))
    config = Config(
        TRAIN_DATA_PATH_PREFIX=prefix,
        MODEL_SAVE_PATH=os.path.join(workdir, 'model'),
        DL_FRAMEWORK='jax', VERBOSE_MODE=0, READER_USE_NATIVE=False,
        MAX_CONTEXTS=args.contexts, SERVING_BATCH_BUCKETS='8,32',
        SERVING_WARM_TIERS='topk', FAULT_INJECT=fault_spec,
        MESH_HEARTBEAT_SECS=0.25, MESH_HEARTBEAT_MISSES=2,
        MESH_RESTART_BACKOFF_SECS=0.1,
        MESH_RESTART_LIMIT=10_000,  # the soak must keep healing
        MESH_RESTART_WINDOW_SECS=3600.0,
        # trace EVERY request: the stitching assertion below needs the
        # full span-tree population, not a sample
        TRACING_SAMPLE_RATE=1.0)
    model = Code2VecModel(config)
    model.save(state=model.state, epoch=0, wait=True)

    tele_core.enable()
    install_compile_listener()
    compiles = tele_core.registry().counter('jit/compiles_total')

    def emit(record):
        if smoke:
            record['smoke'] = True
        print(json.dumps(record), flush=True)

    tiers = (('topk', 'vectors') if args.index_rollovers
             else ('topk',))  # attach_index needs the vectors tier
    mesh = model.serving_mesh(replicas=args.replicas, tiers=tiers,
                              mode=args.mode, max_delay_ms=1.0,
                              memo_cache_bytes=args.memo_bytes)
    memo_on = args.memo_bytes > 0
    violations = []
    rollovers_done = 0
    drill_retries = 0
    try:
        import jax.numpy as jnp

        # warm the whole serving path once
        mesh.predict([lines[0]], tier='topk', timeout=300)
        rng = np.random.default_rng(11)
        # the memo tier's traffic shape: half the load replays one hot
        # request, so cache hits ride THROUGH the kill/restart chaos
        hot = [lines[0], lines[1]]

        index_drill = {'rollback_ok': None, 'swap_ok': None,
                       'agreement': None, 'stale_serves': 0,
                       'predict_survived': None, 'error': None}

        def index_rollover_drill(attempt: int):
            """Canaried index rollover (ISSUE 19): shadow-query a
            DISAGREEING candidate on live neighbor traffic (must roll
            back; the neighbor memo stays warm), then an AGREEING one
            (must swap: the memo index generation bumps — zero stale
            neighbor serves — while predict entries survive, since the
            model didn't change).  Runs before the compile mark is
            pinned: index builds/searches compile their own warm
            programs, which are not the serving path's compiles."""
            from code2vec_tpu.index import store as store_lib
            from code2vec_tpu.index.quant import QuantizedIVFIndex
            index_drill.update(rollback_ok=None, swap_ok=None,
                               agreement=None, stale_serves=0,
                               predict_survived=None, error=None)
            # a prior attempt may have died with a rollover armed;
            # feed it shadow traffic until it concludes so arming a
            # fresh one doesn't refuse with 'already in flight'
            for _ in range(64):
                if mesh._index_rollover is None:
                    break
                try:
                    mesh.submit_neighbors(hot, k=5).result(timeout=300)
                except Exception:
                    time.sleep(0.2)
            dim = mesh.predict([lines[0]], tier='vectors',
                               timeout=300)[0].code_vector.shape[0]
            rng_i = np.random.default_rng(7)
            corpus = rng_i.normal(size=(512, dim)).astype(np.float32)
            store = store_lib.build(
                os.path.join(workdir, 'drill%d.vecindex' % attempt),
                [corpus], labels=['m%d' % i for i in range(512)])
            class _Counting:
                """Search-call counter: a cache-served neighbor answer
                never touches the index, while a live one always does —
                unlike .done(), which is also True when the chain
                resolves synchronously off a warm vectors-tier hit."""

                def __init__(self, inner):
                    self._inner = inner
                    self.searches = 0

                def search(self, vectors, k):
                    self.searches += 1
                    return self._inner.search(vectors, k)

                def __getattr__(self, name):
                    return getattr(self._inner, name)

            live_idx = QuantizedIVFIndex.build(store, kind='int8',
                                               seed=0)
            live_idx.warmup(5)
            live = _Counting(live_idx)
            mesh.attach_index(live)
            # warm one neighbor memo entry + confirm the duplicate is
            # served WITHOUT a live index search
            mesh.submit_neighbors(hot, k=5).result(timeout=300)
            searches = live.searches
            mesh.submit_neighbors(hot, k=5).result(timeout=300)
            if live.searches != searches:
                index_drill['error'] = 'neighbor memo never warmed'
                return
            # predict-tier entry that must SURVIVE the index swap
            mesh.predict(hot, tier='topk', timeout=300)
            if not mesh.submit(hot, tier='topk').done():
                index_drill['error'] = 'predict memo never warmed'
                return
            # --- leg 1: disagreeing candidate must ROLL BACK
            other = rng_i.normal(size=(512, dim)).astype(np.float32)
            bad_store = store_lib.build(
                os.path.join(workdir, 'drill%d_bad.vecindex' % attempt),
                [other], labels=['x%d' % i for i in range(512)])
            bad = QuantizedIVFIndex.build(bad_store, kind='int8',
                                          seed=0)
            bad.warmup(5)
            # drive the shadow with a DIFFERENT query than the `hot`
            # probe key: a driver admitted right after a conclusion
            # re-inserts its own key under the new generation, which
            # must not turn the staleness probe into a legitimate hit
            drv = [lines[2], lines[3]]
            handle = mesh.rollover_index(bad, shadow_queries=2,
                                         min_agreement=0.9)
            while not handle.done():  # memo stands down: runs live
                mesh.submit_neighbors(drv, k=5).result(timeout=300)
            report = handle.result(timeout=300)
            index_drill['rollback_ok'] = (report['swapped'] is False)
            searches = live.searches
            mesh.submit_neighbors(hot, k=5).result(timeout=300)
            if live.searches != searches:
                # rollback must leave the neighbor memo WARM
                index_drill['rollback_ok'] = False
            # --- leg 2: agreeing candidate (same sidecars) must SWAP
            cand_idx = QuantizedIVFIndex(
                store_lib.VectorStore(store.path))
            cand_idx.warmup(5)
            cand = _Counting(cand_idx)
            handle = mesh.rollover_index(cand, shadow_queries=2,
                                         min_agreement=0.9)
            while not handle.done():
                mesh.submit_neighbors(drv, k=5).result(timeout=300)
            report = handle.result(timeout=300)
            index_drill['swap_ok'] = (report['swapped'] is True)
            index_drill['agreement'] = report['agreement']
            searches = cand.searches
            post = mesh.submit_neighbors(hot, k=5)
            post.result(timeout=300)
            if cand.searches == searches:
                # answered WITHOUT touching the new index: a pre-swap
                # neighbor result was served post-swap
                index_drill['stale_serves'] += 1
            index_drill['predict_survived'] = \
                mesh.submit(hot, tier='topk').done()

        if args.index_rollovers:
            for attempt in range(5):
                try:
                    index_rollover_drill(attempt)
                    break
                except Exception as exc:  # worker died mid-drill: retry
                    index_drill['error'] = repr(exc)
                    time.sleep(1.0)

        # pin the compile mark AFTER the index drill: the soak loop
        # below must run compile-free
        warm = compiles.value

        def rollover_drill(i: int):
            """Save the current params at a fresh step, roll the fleet
            to it (restore-and-swap, no canary), then probe the memo
            stale-serving contract: the swap must atomically invalidate
            (generation bump) and the first post-swap duplicate must
            run LIVE.  Returns (ok, error)."""
            step = 100 + rollovers_done
            model.save(state=model.state._replace(
                step=jnp.asarray(step, jnp.int32)), epoch=0, wait=True)
            probe = [lines[0]]
            try:
                mesh.predict(probe, tier='topk', timeout=180)
                report = mesh.load_params(
                    step, canary_batches=0).result(timeout=180)
            except Exception as exc:  # a worker died mid-drill: retry
                return False, repr(exc)
            if not report.get('swapped'):
                return False, 'rollover did not swap: %r' % (report,)
            if memo_on:
                memo_stats = mesh.stats()['memo']
                if memo_stats['entries'] != 0 or memo_stats['bytes']:
                    violations.append(
                        'rollover %d left %d memo entries (%d bytes) '
                        'live after the swap'
                        % (i, memo_stats['entries'],
                           memo_stats['bytes']))
                post = mesh.submit(probe, tier='topk')
                if post.done():
                    violations.append(
                        'STALE: memo served a pre-rollover result '
                        'after swap %d' % i)
                try:
                    post.result(timeout=180)
                except ServingError:
                    pass  # typed shed under chaos: the stale check above
                          # already ran; nothing stale was delivered
            return True, None

        drill_state = {'scale_rid': None, 'scale_ms': None,
                       'drain_ms': None, 'drain_reason': None}

        def elastic_drill():
            """Scale-up-under-kill, then drain-during-partition
            (SERVING.md "Elastic fleet").  Runs CONCURRENTLY with the
            paced generator: the transitions happen under live load
            and live chaos, which is the whole point."""
            t = time.perf_counter()
            try:
                rid = mesh.add_replica()
            except Exception as exc:
                violations.append(
                    'scale-up-under-kill drill failed: %r' % exc)
                return
            drill_state['scale_rid'] = rid
            drill_state['scale_ms'] = (time.perf_counter() - t) * 1e3
            # let the new replica pull some of the paced load before
            # draining it back out
            time.sleep(max(1.0, args.secs * 0.15))
            # the partition window: parent-side frames (results AND
            # heartbeats, from every worker) blackhole while the drain
            # is in flight — liveness detection must break any stall
            faults.configure(fault_spec + ',partition@frame=0..19')
            t = time.perf_counter()
            try:
                mesh.retire(rid, timeout=120.0, reason='drain')
                drill_state['drain_ms'] = \
                    (time.perf_counter() - t) * 1e3
            except Exception as exc:
                violations.append(
                    'drain-during-partition drill failed: %r' % exc)
            finally:
                # restore the soak's ambient plan (the configure above
                # replaced it parent-side; worker plans are per-process
                # and unaffected)
                faults.configure(fault_spec)
            row = next((r for r in mesh.stats()['replicas']
                        if r['replica'] == rid), None)
            drill_state['drain_reason'] = (row['retired_reason']
                                           if row else None)

        elastic_thread = None
        futures = []
        stamps = []
        t0 = time.perf_counter()
        deadline = t0 + args.secs
        elastic_at = (t0 + args.secs * 0.3 if args.elastic else None)
        roll_idx = 0
        roll_times = [t0 + args.secs * (i + 1) / (args.rollovers + 1)
                      for i in range(args.rollovers)]
        while time.perf_counter() < deadline:
            if elastic_at is not None and \
                    time.perf_counter() >= elastic_at:
                elastic_at = None
                elastic_thread = threading.Thread(
                    target=elastic_drill, daemon=True,
                    name='soak-elastic-drill')
                elastic_thread.start()
            if roll_idx < len(roll_times) and \
                    time.perf_counter() >= roll_times[roll_idx]:
                ok_drill, err = rollover_drill(roll_idx)
                if ok_drill:
                    rollovers_done += 1
                    roll_idx += 1
                else:
                    drill_retries += 1
                    print('rollover drill %d retry %d: %s'
                          % (roll_idx, drill_retries, err),
                          file=sys.stderr)
                    roll_times[roll_idx] = time.perf_counter() + 1.0
                    if drill_retries > 5 * max(1, args.rollovers):
                        violations.append(
                            'rollover drill %d kept failing: %s'
                            % (roll_idx, err))
                        roll_idx += 1
            if memo_on and rng.random() < 0.5:
                request_lines = hot
            else:
                request_lines = [lines[rng.integers(len(lines))]
                                 for _ in range(int(rng.integers(1, 4)))]
            try:
                futures.append(mesh.submit(request_lines, tier='topk'))
                stamps.append(time.perf_counter())
            except ServingError:
                futures.append(None)  # typed shed at admission: fine
                stamps.append(time.perf_counter())
            time.sleep(args.interval_ms / 1e3)
        if elastic_at is not None:
            # the soak ended before the drill's start mark (a very
            # short --secs): run it now so the contract still gets
            # exercised once
            elastic_thread = threading.Thread(
                target=elastic_drill, daemon=True,
                name='soak-elastic-drill')
            elastic_thread.start()
        if elastic_thread is not None:
            elastic_thread.join(timeout=300.0)
            if elastic_thread.is_alive():
                violations.append('elastic drill wedged (scale-up or '
                                  'partitioned drain never finished)')
        # drain: every admitted future must RESOLVE — results or typed
        from concurrent.futures import TimeoutError as FutureTimeout
        ok = shed = typed = lost = untyped = 0
        latencies = []
        for t_submit, future in zip(stamps, futures):
            if future is None:
                shed += 1
                continue
            try:
                results = future.result(timeout=180)
            except ServingError:
                typed += 1  # expired/shed/replica-dead: typed, not lost
            except FutureTimeout:
                # a future that never resolved inside the generous
                # drain window is LOST — the exact hang this soak
                # exists to catch
                lost += 1
                violations.append('hung future (never resolved)')
            except Exception as exc:
                untyped += 1
                violations.append('untyped failure: %r' % exc)
            else:
                assert results
                ok += 1
                latencies.append(time.perf_counter() - t_submit)
        postwarm = compiles.value - warm
        wall = time.perf_counter() - t0
        stats = mesh.stats()
    finally:
        mesh.close()
        model.close_stores()

    lat_ms = np.asarray(sorted(latencies)) * 1e3
    p50 = float(np.percentile(lat_ms, 50)) if len(lat_ms) else None
    p99 = float(np.percentile(lat_ms, 99)) if len(lat_ms) else None
    total = len(futures)
    if ok == 0:
        violations.append('no request ever completed')
    if postwarm != 0:
        violations.append('%d post-warmup parent compiles' % postwarm)
    if p99 is not None and p99 > args.p99_bound_ms:
        violations.append('p99 %.0fms > bound %.0fms'
                          % (p99, args.p99_bound_ms))
    if stats['restarts_total'] < 1:
        violations.append('no supervised restart fired — the chaos '
                          'never bit (raise --secs or lower '
                          '--kill-every)')

    # cross-process stitching contract (OBSERVABILITY.md "Fleet
    # observability"): ZERO admitted requests may finish with a
    # wire-truncated trace tree — every delivered trace must carry its
    # worker-side device-execute spans, grafted by adopt_spans
    scripts_dir = os.path.dirname(os.path.abspath(__file__))
    if scripts_dir not in sys.path:
        sys.path.insert(0, scripts_dir)
    from latency_report import (group_traces, load_spans,
                                unstitched_traces)
    spans_path = os.path.join(workdir, 'telemetry', 'spans.jsonl')
    stitched_total = unstitched = None
    if os.path.exists(spans_path):
        traces = group_traces(load_spans(spans_path))
        delivered = [e for e in traces.values()
                     if e['root'] is not None
                     and e['root'].get('status') in (None, 'ok')]
        truncated = unstitched_traces(traces)
        stitched_total = len(delivered)
        unstitched = len(truncated)
        if ok and not delivered:
            violations.append('requests completed but the span log '
                              'has no delivered traces (tracing '
                              'broken?)')
        if truncated:
            violations.append(
                '%d delivered trace(s) finished UNSTITCHED (no '
                'worker device-execute spans): %s'
                % (len(truncated), truncated[:8]))
    elif ok:
        violations.append('no span log at %s (stitching assertion '
                          'could not run)' % spans_path)
    emit({'metric': 'mesh_soak_unstitched_traces', 'value': unstitched,
          'delivered_traces': stitched_total,
          'adopted_spans': stats.get('adopted_spans_total'),
          'remote_spans_dropped':
              stats.get('remote_spans_dropped_total')})

    emit({'metric': 'mesh_soak_requests', 'value': total, 'ok': ok,
          'shed_at_admission': shed, 'typed_failures': typed,
          'untyped_failures': untyped, 'lost': lost,
          'wall_s': round(wall, 2), 'mode': args.mode,
          'replicas': args.replicas, 'fault_spec': fault_spec})
    emit({'metric': 'mesh_soak_lost_requests', 'value': lost + untyped})
    emit({'metric': 'mesh_soak_p99_ms',
          'value': round(p99, 1) if p99 is not None else None,
          'p50_ms': round(p50, 1) if p50 is not None else None,
          'bound_ms': args.p99_bound_ms})
    emit({'metric': 'mesh_soak_restarts',
          'value': stats['restarts_total'],
          'redispatched': stats['redispatched_total'],
          'heartbeat_misses': stats['heartbeat_misses_total'],
          'replica_breaker_open_total':
              stats['replica_breaker_open_total']})
    emit({'metric': 'mesh_soak_postwarm_compiles', 'value': postwarm})
    if args.elastic:
        if drill_state['scale_ms'] is None:
            violations.append('scale-up-under-kill never completed')
        if drill_state['drain_ms'] is None:
            violations.append(
                'drain-during-partition never completed')
        elif drill_state['drain_reason'] != 'drain':
            violations.append(
                "drained replica retired as %r, expected 'drain'"
                % (drill_state['drain_reason'],))
        emit({'metric': 'mesh_soak_scale_up_ms',
              'value': (round(drill_state['scale_ms'], 1)
                        if drill_state['scale_ms'] is not None
                        else None),
              'rid': drill_state['scale_rid']})
        emit({'metric': 'mesh_soak_drain_partition_ms',
              'value': (round(drill_state['drain_ms'], 1)
                        if drill_state['drain_ms'] is not None
                        else None),
              'retired_reason': drill_state['drain_reason']})
    if args.index_rollovers:
        if index_drill['rollback_ok'] is not True:
            violations.append(
                'index rollover drill: disagreeing candidate did not '
                'roll back cleanly (%r)'
                % (index_drill['error'] or index_drill['rollback_ok'],))
        if index_drill['swap_ok'] is not True:
            violations.append(
                'index rollover drill: agreeing candidate did not swap '
                '(%r)' % (index_drill['error']
                          or index_drill['swap_ok'],))
        if index_drill['stale_serves']:
            violations.append(
                'STALE: memo served %d pre-swap neighbor result(s) '
                'after the index rollover'
                % index_drill['stale_serves'])
        if index_drill['swap_ok'] and not index_drill['predict_survived']:
            violations.append(
                'index rollover drill: predict memo entries did not '
                'survive the index swap (the model did not change)')
        emit({'metric': 'mesh_soak_index_rollover',
              'value': 1 if (index_drill['swap_ok']
                             and index_drill['rollback_ok']) else 0,
              'agreement': index_drill['agreement'],
              'stale_neighbor_serves': index_drill['stale_serves'],
              'predict_survived': index_drill['predict_survived'],
              'index_version': stats.get('index_version'),
              'memo_index_generation':
                  (stats['memo'].get('index_generation')
                   if memo_on else None),
              'error': index_drill['error']})
    if memo_on:
        # memoization-tier soak contract (SERVING.md "Memoization
        # tier"): the cache must actually serve under the duplicate-
        # heavy traffic, and every completed rollover must have
        # invalidated it (generation bump) — zero stale serves is
        # asserted inline by each drill's post-swap probe above.
        memo_stats = stats['memo']
        if memo_stats['hits'] == 0:
            violations.append('memo tier never served a hit under the '
                              'duplicate-heavy soak traffic')
        if args.rollovers > 0 and rollovers_done == 0:
            violations.append('no rollover drill ever completed '
                              '(%d retries)' % drill_retries)
        # >= not ==: a drill whose handle died AFTER the swap landed
        # still bumped the generation server-side; under-counting
        # rollovers must not read as a missed invalidation
        if memo_stats['generation'] < rollovers_done:
            violations.append(
                'memo generation %d < %d completed rollovers — a swap '
                'concluded without invalidating the cache'
                % (memo_stats['generation'], rollovers_done))
        emit({'metric': 'mesh_soak_memo', 'value': memo_stats['hits'],
              'hit_rate': round(memo_stats['hit_rate'], 3),
              'entries': memo_stats['entries'],
              'bytes': memo_stats['bytes'],
              'evictions': memo_stats['evictions'],
              'generation': memo_stats['generation'],
              'rollovers': rollovers_done,
              'drill_retries': drill_retries})
    if violations:
        emit({'metric': 'mesh_soak_violations', 'value': len(violations),
              'detail': violations})
        return 1
    return 0


if __name__ == '__main__':
    sys.exit(main())
