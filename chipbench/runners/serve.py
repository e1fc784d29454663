"""Open-loop serving through ``Code2VecModel.serving_engine().submit``.

The arrival schedule is computed before the run, by the generator the mix
names (``traffic/<generator>.py``), and the load is offered at its fixed
rate whatever the engine does: a few generator threads take the requests in
order, each sleeps until its request is due and submits it (the caller's
thread tokenizes, by the engine's contract). A request's latency runs from
the instant it was DUE to the instant its result was delivered, so a stall
is charged to every request it delays; how late the generator itself ran is
reported beside it.

Where the configuration holds an index of the corpus's code vectors, it is
made on the device from the seed and attached to the engine, and the
requests of the ``vectors`` tier ask for their methods' nearest neighbours
(``submit_neighbors``): the code vector, then the search.

A request fails if ``submit`` raises (``EngineOverloaded`` among others),
if its future holds an exception (an expiry, say), if its result does not
have one finite answer per method, or if it is unanswered ``drain_s``
after the window's last arrival. A failed request counts at ``drain_s`` in
the latency percentiles: it missed any limit.
"""
from __future__ import annotations

import threading
import time
from typing import List

import numpy as np

from chipbench import manifest
from chipbench.runners import common
from chipbench.traffic import corpus as corpus_lib


def _well_formed(results, rows: int, tier: str) -> bool:
    if len(results) != rows:
        return False
    for r in results:
        if hasattr(r, 'indices'):        # a neighbour query's answer
            if not np.isfinite(r.scores).all() or (r.indices < 0).any():
                return False
        elif tier == 'vectors':
            if r.code_vector is None or \
                    not np.isfinite(r.code_vector).all():
                return False
        elif not np.isfinite(r.topk_predicted_words_scores).all():
            return False
    return True


class Runner:
    def __init__(self, ctx: common.Context, compiles: common.CompileCounter):
        self.ctx = ctx
        self.compiles = compiles
        self.arrivals = manifest.load_module('traffic',
                                             ctx.traffic['generator'])

    def setup(self) -> None:
        ctx = self.ctx
        self.corpus = common.make_corpus(ctx)
        self.model = common.build_model(ctx, self.corpus['prefix'],
                                        weights_only=True)
        self.lines = corpus_lib.read_lines(self.corpus['prefix'],
                                           self.corpus['methods'])
        with ctx.span('engine.warmup_s'):
            self.engine = self.model.serving_engine()
        self.index_rows = None
        if ctx.config.get('index'):
            with ctx.span('index.build_s'):
                self.attach_index(ctx.config['index'])
        self.drain_s = float(ctx.traffic['drain_s'])

    def make_index_rows(self, spec: dict):
        """The index's rows on the device: unit vectors from the seed, in
        one jitted call."""
        import jax
        import jax.numpy as jnp
        shape = (int(spec['rows']), self.ctx.settings['CODE_VECTOR_SIZE'])

        def rows(key):
            x = jax.random.normal(key, shape, jnp.float32)
            x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
            return x.astype(spec['dtype'])
        return jax.jit(rows)(jax.random.PRNGKey(self.ctx.seed))

    def attach_index(self, spec: dict) -> None:
        from code2vec_tpu.index.exact import ExactIndex
        # ExactIndex takes host rows: down once, and up again inside it
        host = np.asarray(self.make_index_rows(spec))
        index = ExactIndex(host, metric='dot',
                           query_buckets=spec['query_buckets'])
        index.warmup(int(spec['k']))
        self.engine.attach_index(index)
        self.index_rows = int(spec['rows'])

    def warm(self) -> None:
        """The mix itself for ``warm_s`` seconds, its results dropped: the
        threads, the engine's service-rate estimate and the allocator reach
        their steady state before the window."""
        warm_s = float(self.ctx.traffic['warm_s'])
        with self.ctx.span('loadgen.warm_s'):
            self.offer(self.ctx.traffic['arrivals'], self.ctx.seed + 1,
                       warm_s)

    # ------------------------------------------------------------- load
    def offer(self, params: dict, seed: int, seconds: float,
              sample_every_s: float = 0.05) -> dict:
        """Offers ``seconds`` of the schedule and waits for the answers.
        Returns per-request arrays and samples of the engine's gauges."""
        import jax
        engine, lines = self.engine, self.lines
        neighbours = self.index_rows is not None
        schedule = self.arrivals.generate(params, seed, seconds, len(lines))
        n = schedule.due_s.shape[0]
        tiers = schedule.tiers
        submitted = np.full(n, np.nan)     # when submit() was entered
        done = np.full(n, np.nan)          # when the result was delivered
        ok = np.zeros(n, bool)
        taken = [0]
        take_lock = threading.Lock()
        t0 = time.perf_counter() + 0.05

        def finished(future, i: int, rows: int, tier: str) -> None:
            done[i] = time.perf_counter() - t0
            ok[i] = future.exception() is None and \
                _well_formed(future.result(), rows, tier)

        def generate() -> None:
            while True:
                with take_lock:
                    i = taken[0]
                    taken[0] += 1
                if i >= n:
                    return
                rows, tier = int(schedule.rows[i]), tiers[schedule.tier[i]]
                first = int(schedule.first_line[i])
                wait = t0 + schedule.due_s[i] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                submitted[i] = time.perf_counter() - t0
                try:
                    with jax.profiler.TraceAnnotation('chipbench/submit'):
                        request = lines[first:first + rows]
                        future = (engine.submit_neighbors(request)
                                  if neighbours and tier == 'vectors'
                                  else engine.submit(request, tier=tier))
                except Exception:        # shed, closed: a failed request
                    done[i] = time.perf_counter() - t0
                    continue
                future.add_done_callback(
                    lambda f, i=i, rows=rows, tier=tier:
                    finished(f, i, rows, tier))

        samples: List[tuple] = []
        sampling = threading.Event()

        def sample() -> None:
            while not sampling.wait(sample_every_s):
                stats = engine.stats()
                samples.append((time.perf_counter() - t0,
                                stats['queue_depth'],
                                stats['batch_fill_rate']))

        before = engine.stats()
        threads = [threading.Thread(target=generate, daemon=True,
                                    name='chipbench-load-%d' % g)
                   for g in range(int(self.ctx.traffic['generator_threads']))]
        sampler = threading.Thread(target=sample, daemon=True,
                                   name='chipbench-sample')
        sampler.start()
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join()
        deadline = t0 + (schedule.due_s[-1] if n else 0.0) + self.drain_s
        with jax.profiler.TraceAnnotation('chipbench/drain'):
            while np.isnan(done).any() and time.perf_counter() < deadline:
                time.sleep(0.005)
        sampling.set()
        sampler.join()
        after = engine.stats()
        return {'schedule': schedule, 'submitted': submitted, 'done': done,
                'ok': ok & ~np.isnan(done), 't0': t0, 'samples': samples,
                'stats_before': before, 'stats_after': after}

    def summarize(self, run: dict, seconds: float) -> dict:
        """Latencies from the due instants, failures at ``drain_s``."""
        schedule = run['schedule']
        latency = np.where(run['ok'], run['done'] - schedule.due_s,
                           self.drain_s)
        late = run['submitted'] - schedule.due_s
        n = latency.shape[0]
        half = schedule.due_s < seconds / 2
        depth = np.asarray([s[1] for s in run['samples']], np.float64)
        fill = np.asarray([s[2] for s in run['samples']], np.float64)
        before, after = run['stats_before'], run['stats_after']
        batches = after['batches_total'] - before['batches_total']
        return {
            'requests': n, 'failed': int((~run['ok']).sum()),
            'offered_per_s': n / seconds,
            'rows_per_s': float(schedule.rows[run['ok']].sum() / seconds),
            'p50_ms': float(np.percentile(latency, 50) * 1e3),
            'p95_ms': float(np.percentile(latency, 95) * 1e3),
            'p99_ms': float(np.percentile(latency, 99) * 1e3),
            'p50_first_half_ms': float(np.median(latency[half]) * 1e3),
            'p50_second_half_ms': float(np.median(latency[~half]) * 1e3),
            'late_p50_ms': float(np.nanpercentile(late, 50) * 1e3),
            'late_p99_ms': float(np.nanpercentile(late, 99) * 1e3),
            'mean_gap_ms': 1e3 * seconds / max(n, 1),
            'queue_depth_mean': float(depth.mean()) if depth.size else 0.0,
            'queue_depth_max': float(depth.max()) if depth.size else 0.0,
            'queue_depth_last': float(depth[-1]) if depth.size else 0.0,
            'batch_fill_rate': float(fill.mean()) if fill.size else 0.0,
            'batches': int(batches),
            'rows_per_batch': float(schedule.rows.sum() / max(batches, 1)),
            'shed': int(after['shed_total'] - before['shed_total']),
            'expired': int(after['expired_total'] - before['expired_total']),
            'dispatch_ms_p50': after['dispatch_ms']['p50_ms'],
            'decode_ms_p50': after['decode_ms']['p50_ms'],
            'engine_latency_ms_p50': after['latency_ms']['p50_ms'],
        }

    def measure(self, seconds: float) -> dict:
        ctx = self.ctx
        tracer = common.start_trace_slice(ctx)
        compiles_at_start = self.compiles.value
        try:
            run = self.offer(ctx.traffic['arrivals'], ctx.seed, seconds)
        finally:
            if tracer is not None:
                tracer.finish()
        summary = self.summarize(run, seconds)
        ctx.log('window: %s' % summary)
        limit = float(ctx.traffic['max_late_p50_share_of_gap'])
        self.late_fault = None
        # judged in the untraced run: starting and stopping the profiler
        # stalls the generator's threads for tens of milliseconds
        if not ctx.trace and \
                summary['late_p50_ms'] > limit * summary['mean_gap_ms']:
            self.late_fault = (
                'the load generator ran late: median %.3f ms against a mean '
                'gap of %.3f ms (limit %.2f of it)'
                % (summary['late_p50_ms'], summary['mean_gap_ms'], limit))
        return {
            'window_start': run['t0'],
            'attempted': summary['requests'], 'failed': summary['failed'],
            'compiles_in_window': self.compiles.value - compiles_at_start,
            'end_to_end': {'serve_p50_ms': summary['p50_ms']},
            'serve': summary,
        }

    def check_neighbours(self, lines, tolerance: float) -> list:
        """A neighbour query's scores against the float32 product of the
        reference's code vectors with the same rows."""
        import jax
        import jax.numpy as jnp
        from chipbench import reference
        spec = self.ctx.config['index']
        results = self.engine.submit_neighbors(lines).result(timeout=120)
        if len(results) != len(lines):
            return ['%d neighbour results for %d lines'
                    % (len(results), len(lines))]
        parsed = common.parse_for_reference(self.model, lines)

        def best_scores(tables, rows, source, path, target, valid):
            vector, _, _ = reference.forward(tables, source, path, target,
                                             valid)
            with jax.default_matmul_precision('highest'):
                scores = vector @ rows.astype(jnp.float32).T
            return jax.lax.top_k(scores, int(spec['k']))[0]

        want = np.asarray(jax.jit(best_scores)(
            common.reference_tables(self.model), self.make_index_rows(spec),
            parsed.source, parsed.path, parsed.target, parsed.valid))
        got = np.stack([r.scores for r in results])
        worst = float(np.abs(got - want).max())
        self.ctx.log('check: neighbour scores off by at most %.3g' % worst)
        if not worst <= tolerance:
            return ['neighbour scores off by %.3g (tolerance %.3g)'
                    % (worst, tolerance)]
        return []

    def check(self) -> dict:
        """One seeded request of each tier of the mix through ``submit``
        and through the reference."""
        spec = self.ctx.config['check']
        rng = np.random.default_rng([self.ctx.seed, 0xC4EC])
        n = int(self.ctx.traffic['check_methods'])
        faults = [self.late_fault] if self.late_fault else []
        for tier in sorted(self.ctx.traffic['arrivals']['tiers']):
            first = int(rng.integers(0, len(self.lines) - n + 1))
            lines = self.lines[first:first + n]
            results = self.engine.submit(lines, tier=tier).result(
                timeout=120)
            faults += common.check_results(self.model, lines, results, tier,
                                           spec['tolerance'])
            if tier == 'vectors' and self.index_rows is not None:
                faults += self.check_neighbours(
                    lines, spec['tolerance']['neighbor_score'])
        return {'faults': faults}

    def close(self) -> None:
        self.engine.close()
        self.model.close_stores()
