"""Open-loop serving of a language model through the same
``serving_engine().submit``, tier ``generate``.

The schedule is computed before the run by the generator the mix names
(``traffic/ide_replay.py``): the same lengths, order and instants in every
run; ``--seed`` picks the weights and the prompts' token ids.  It starts
``lead_in_s`` before the window, so that the window opens on an engine in
steady state; the lead-in is set-up.  A few generator threads sleep until
each request is due and submit it.  A request's latency runs from the
instant it was DUE to the instant its last token was delivered; the
end-to-end median is over the requests due inside the window, each waited
for up to ``drain_s`` after the window's last arrival; one unanswered by
then, or answered with an exception or with ids that are not
``max_new_tokens`` ids of the vocabulary, failed and counts at ``drain_s``.

The check (``correct``): ``check_requests`` of the window's own requests
were submitted with ``return_logits``; after the window the cache pools are
freed and ``reference_mellum2.py`` computes the full forward pass over
each one's prompt and generated ids.  The float32 logits the timed path
produced, in prefill and at every decode step through the cache, are held
to the configuration's written tolerance.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import List

import numpy as np

from chipbench import manifest
from chipbench.runners import common

#: the keys of a configuration's file that are the model's own config.json
MODEL_KEYS = (
    'attention_bias', 'head_dim', 'hidden_act', 'hidden_size',
    'intermediate_size', 'layer_types', 'mlp_layer_types',
    'max_position_embeddings', 'max_window_layers', 'model_type',
    'moe_intermediate_size', 'norm_topk_prob', 'num_attention_heads',
    'num_experts', 'num_experts_per_tok', 'num_hidden_layers',
    'num_key_value_heads', 'rms_norm_eps', 'rope_parameters',
    'sliding_window', 'tie_word_embeddings', 'vocab_size',
    'use_sliding_window')


def _well_formed(result, new_tokens: int, vocab: int) -> bool:
    ids = np.asarray(result.token_ids)
    return ids.shape == (new_tokens,) and bool(
        ((ids >= 0) & (ids < vocab)).all())


class _Layers:
    """The program's layers as the reference's ``LayerWeights``, one at a
    time: the fused products are split when a layer is asked for, so that
    only one layer's copies live beside the weights."""

    def __init__(self, layers, model_config: dict):
        self.layers = layers
        self.q = model_config['num_attention_heads'] \
            * model_config['head_dim']
        self.kv = model_config['num_key_value_heads'] \
            * model_config['head_dim']
        self.width = model_config['moe_intermediate_size']

    def __len__(self) -> int:
        return len(self.layers)

    def __iter__(self):
        from chipbench import reference_mellum2 as ref
        q, kv, width = self.q, self.kv, self.width
        for layer in self.layers:
            yield ref.LayerWeights(
                attn_norm=layer['attn_norm'], wq=layer['wqkv'][:, :q],
                wk=layer['wqkv'][:, q:q + kv], wv=layer['wqkv'][:, q + kv:],
                wo=layer['wo'], mlp_norm=layer['mlp_norm'],
                router=layer['router'],
                w_gate=layer['w_gate_up'][..., :width],
                w_up=layer['w_gate_up'][..., width:],
                w_down=layer['w_down'])


def reference_weights(params, model_config: dict):
    """The program's parameter tree as the reference's ``Weights``:
    nothing cast (the reference casts up)."""
    from chipbench import reference_mellum2 as ref
    return ref.Weights(embed=params['embed'], head=params['head'],
                       final_norm=params['final_norm'],
                       layers=_Layers(params['layers'], model_config))


def compare_logits(got: np.ndarray, want: np.ndarray) -> np.ndarray:
    """Per position: the largest difference over the vocabulary, relative
    to the spread (standard deviation) of the reference's logits there."""
    return np.abs(got - want).max(axis=-1) / want.std(axis=-1)


def judge(errors: np.ndarray, tolerance: dict) -> List[str]:
    """What of the written tolerance ``errors`` (one a position) break."""
    faults = []
    beyond = float((errors > tolerance['relative_error']).mean())
    if not beyond <= tolerance['share_beyond']:
        faults.append('%.3f of the positions are off by more than %.3g of '
                      'the logits\' spread (allowed %.3g)'
                      % (beyond, tolerance['relative_error'],
                         tolerance['share_beyond']))
    worst = float(errors.max())
    if not worst <= tolerance['relative_error_cap']:
        faults.append('a position is off by %.3g of the logits\' spread '
                      '(cap %.3g)' % (worst,
                                      tolerance['relative_error_cap']))
    return faults


class Runner:
    def __init__(self, ctx: common.Context, compiles: common.CompileCounter):
        self.ctx = ctx
        self.compiles = compiles
        self.replay = manifest.load_module('traffic',
                                           ctx.traffic['generator'])
        self.checked: dict = {}

    # ------------------------------------------------------------- set-up
    def setup(self) -> None:
        ctx = self.ctx
        self.model_config = {k: ctx.config[k] for k in MODEL_KEYS
                             if k in ctx.config}
        path = os.path.join(ctx.run_dir, 'config.json')
        with open(path, 'w') as f:
            json.dump(self.model_config, f)
        with ctx.span('lifecycle.build_s'):
            from code2vec_tpu import model_api    # the program's imports
            config = common.make_config(
                ctx, LM_CONFIG_PATH=path,
                LM_PARAM_SEED=ctx.seed % (2 ** 31 - 1))
            self.model = model_api.create_model(config)
        with ctx.span('engine.warmup_s'):
            self.engine = self.model.serving_engine()
        self.vocab = int(self.model_config['vocab_size'])
        self.drain_s = float(ctx.traffic['drain_s'])
        runtime = self.engine.lm_runtime()
        ctx.log('cache pools %s bytes; geometry %s'
                % (runtime.cache_bytes(), runtime.geometry))

    def warm(self) -> None:
        """A few requests through every host path (admission, chunking,
        logits rows, delivery), their results dropped."""
        with self.ctx.span('loadgen.warm_s'):
            rng = np.random.default_rng([self.ctx.seed, 0x3A2])
            futures = [self.engine.submit(
                rng.integers(0, self.vocab, int(length), dtype=np.int32),
                tier='generate', max_new_tokens=int(new),
                return_logits=True)
                for length, new in self.ctx.traffic['warm_requests']]
            for future in futures:
                np.asarray(future.result(timeout=600).logits[0])

    # ------------------------------------------------------------- load
    def offer(self, params: dict, seed: int, seconds: float,
              sample_every_s: float = 0.05, check_requests: int = 0) -> dict:
        """Offers the lead-in and ``seconds`` of the schedule and waits
        for the answers of the window's requests."""
        import jax
        engine = self.engine
        with self.ctx.span('lifecycle.data_s'):
            schedule = self.replay.generate(params, seed, seconds,
                                            self.vocab)
            n = schedule.due_s.shape[0]
            prompts = [self.replay.prompt_ids(schedule, i)
                       for i in range(n)]
        in_window = schedule.due_s >= 0
        picked = self.pick_checked(schedule, check_requests)
        with_logits = set(picked)
        submitted = np.full(n, np.nan)
        done = np.full(n, np.nan)
        ok = np.zeros(n, bool)
        results: dict = {}
        taken = [0]
        take_lock = threading.Lock()
        lead = -float(schedule.due_s[0]) if n else 0.0
        t0 = time.perf_counter() + 0.05 + lead     # the window's start

        def finished(future, i: int) -> None:
            done[i] = time.perf_counter() - t0
            if future.exception() is None:
                result = future.result()
                ok[i] = _well_formed(result, int(schedule.new_tokens[i]),
                                     self.vocab)
                if i in with_logits:
                    results[i] = result

        def generate() -> None:
            while True:
                with take_lock:
                    i = taken[0]
                    taken[0] += 1
                if i >= n:
                    return
                wait = t0 + schedule.due_s[i] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                submitted[i] = time.perf_counter() - t0
                try:
                    with jax.profiler.TraceAnnotation('chipbench/submit'):
                        future = engine.submit(
                            prompts[i], tier='generate',
                            max_new_tokens=int(schedule.new_tokens[i]),
                            return_logits=i in with_logits)
                except Exception:        # shed, closed: a failed request
                    done[i] = time.perf_counter() - t0
                    continue
                future.add_done_callback(lambda f, i=i: finished(f, i))

        samples: List[tuple] = []
        sampling = threading.Event()

        def sample() -> None:
            while not sampling.wait(sample_every_s):
                stats = engine.stats()
                lm = stats['lm']
                samples.append((time.perf_counter() - t0,
                                stats['queue_depth'], lm['ring_pool_fill'],
                                lm['page_pool_fill'], lm['running']))

        before = engine.stats()
        threads = [threading.Thread(target=generate, daemon=True,
                                    name='chipbench-load-%d' % g)
                   for g in range(int(self.ctx.traffic['generator_threads']))]
        sampler = threading.Thread(target=sample, daemon=True,
                                   name='chipbench-sample')
        sampler.start()
        at_window = [None, None]

        def mark_window() -> None:
            wait = t0 - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            at_window[0] = engine.stats()
            at_window[1] = self.compiles.value
        marker = threading.Thread(target=mark_window, daemon=True,
                                  name='chipbench-mark')
        marker.start()
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join()
        marker.join()
        deadline = t0 + (schedule.due_s[-1] if n else 0.0) + self.drain_s
        with jax.profiler.TraceAnnotation('chipbench/drain'):
            while np.isnan(done[in_window]).any() and \
                    time.perf_counter() < deadline:
                time.sleep(0.005)
        sampling.set()
        sampler.join()
        after = engine.stats()
        return {'schedule': schedule, 'submitted': submitted, 'done': done,
                'ok': ok & ~np.isnan(done), 'in_window': in_window,
                't0': t0, 'samples': samples, 'stats_before': before,
                'stats_at_window': at_window[0],
                'compiles_at_window': at_window[1], 'stats_after': after,
                'results': results, 'prompts': prompts, 'picked': picked}

    @staticmethod
    def pick_checked(schedule, count: int) -> List[int]:
        """Which of the window's requests the check reads: the shortest
        completion (its prompt fills the window exactly, its decode wraps
        the ring), the completion at the median length (its prefill wraps
        the ring) and the longest chat (positions past the original
        context of the full layers' YaRN table)."""
        if count <= 0:
            return []
        window = np.flatnonzero(schedule.due_s >= 0)
        picks: List[int] = []
        complete = [i for i in window if schedule.kind[i] == 0]
        chat = [i for i in window if schedule.kind[i] == 1]
        if complete:
            by_length = sorted(complete, key=lambda i: schedule.prompt_len[i])
            picks += [by_length[0], by_length[len(by_length) // 2]]
        if chat:
            picks.append(max(chat, key=lambda i: schedule.prompt_len[i]))
        return [int(i) for i in dict.fromkeys(picks)][:count]

    def summarize(self, run: dict, seconds: float) -> dict:
        """Latencies of the window's requests from their due instants,
        failures at ``drain_s``; the engine's own counters over the
        window."""
        schedule, window = run['schedule'], run['in_window']
        due = schedule.due_s[window]
        latency = np.where(run['ok'][window], run['done'][window] - due,
                           self.drain_s)
        late = run['submitted'][window] - due
        n = int(window.sum())
        half = due < seconds / 2
        samples = [s for s in run['samples'] if s[0] >= 0]
        table = np.asarray(samples, np.float64).reshape(-1, 5)
        start = run['stats_at_window'] or run['stats_before']
        end = run['stats_after']
        lm0, lm1 = start['lm'], end['lm']
        steps = lm1['steps_total'] - lm0['steps_total']
        tokens = lm1['tokens_total'] - lm0['tokens_total']
        experts = (lm1['expert_tokens'] - lm0['expert_tokens']).astype(
            np.float64)
        load = experts.max(axis=1) / np.maximum(experts.mean(axis=1), 1e-9)

        def median(values) -> float:
            return float(np.median(values)) if len(values) else 0.0
        return {
            'requests': n, 'failed': int((~run['ok'][window]).sum()),
            'lead_in_requests': int((~window).sum()),
            'offered_per_s': n / seconds,
            'prompt_tokens': int(schedule.prompt_len[window].sum()),
            'new_tokens': int(schedule.new_tokens[window].sum()),
            'p50_ms': float(np.percentile(latency, 50) * 1e3),
            'p95_ms': float(np.percentile(latency, 95) * 1e3),
            'p99_ms': float(np.percentile(latency, 99) * 1e3),
            'p50_first_half_ms': median(latency[half]) * 1e3,
            'p50_second_half_ms': median(latency[~half]) * 1e3,
            'p50_complete_ms': median(
                latency[schedule.kind[window] == 0]) * 1e3,
            'p50_chat_ms': median(latency[schedule.kind[window] == 1]) * 1e3,
            'late_p50_ms': float(np.nanpercentile(late, 50) * 1e3),
            'late_p99_ms': float(np.nanpercentile(late, 99) * 1e3),
            'mean_gap_ms': 1e3 * seconds / max(n, 1),
            'queue_depth_mean': float(table[:, 1].mean())
            if table.size else 0.0,
            'queue_depth_max': float(table[:, 1].max())
            if table.size else 0.0,
            'queue_depth_last': float(table[-1, 1]) if table.size else 0.0,
            'ring_pool_fill': float(table[:, 2].mean())
            if table.size else 0.0,
            'page_pool_fill': float(table[:, 3].mean())
            if table.size else 0.0,
            'running_mean': float(table[:, 4].mean()) if table.size else 0.0,
            'steps': int(steps),
            'tokens_per_step': float(tokens / max(steps, 1)),
            'admit_held': int(lm1['admit_held_total']
                              - lm0['admit_held_total']),
            'expert_load_max_over_mean': float(load.mean()),
            'shed': int(end['shed_total'] - start['shed_total']),
            'expired': int(end['expired_total'] - start['expired_total']),
            'ttft_ms_p50': lm1['ttft_ms']['p50_ms'],
            'decode_step_ms_p50': lm1['decode_step_ms']['p50_ms'],
            'prefill_chunk_ms_p50': lm1['prefill_chunk_ms']['p50_ms'],
            'admit_wait_ms_p50': lm1['admit_wait_ms']['p50_ms'],
        }

    def measure(self, seconds: float) -> dict:
        ctx = self.ctx
        params = ctx.traffic['arrivals']
        tracer = None
        if ctx.trace:
            spec = ctx.traffic['trace']
            tracer = common.TraceSlice(
                ctx.trace_dir,
                float(params['lead_in_s']) + spec['start_after_s'],
                spec['length_s'])
            tracer.start()
        compiles_at_start = self.compiles.value
        try:
            run = self.offer(params, ctx.seed, seconds,
                             check_requests=int(
                                 ctx.traffic['check_requests']))
        finally:
            if tracer is not None:
                tracer.finish()
        summary = self.summarize(run, seconds)
        ctx.log('window: %s' % summary)
        self.checked = {i: (run['prompts'][i], run['results'][i])
                        for i in run['results']}
        self.missing = sorted(set(run['picked']) - set(run['results']))
        limit = float(ctx.traffic['max_late_p50_share_of_gap'])
        self.late_fault = None
        if not ctx.trace and \
                summary['late_p50_ms'] > limit * summary['mean_gap_ms']:
            self.late_fault = (
                'the load generator ran late: median %.3f ms against a mean '
                'gap of %.3f ms (limit %.2f of it)'
                % (summary['late_p50_ms'], summary['mean_gap_ms'], limit))
        return {
            # the lead-in is set-up: the window starts at t0
            'window_start': run['t0'],
            'attempted': summary['requests'], 'failed': summary['failed'],
            # from the lead-in's start: stricter than the window alone
            'compiles_in_window': self.compiles.value - compiles_at_start,
            'end_to_end': {'serve_p50_ms': summary['p50_ms']},
            'serve': summary,
            'lm': {'step_log': self.engine.lm_step_log(),
                   'model_config': self.model_config,
                   'trace_dir': ctx.trace_dir,
                   'slots': int(ctx.settings['LM_MAX_SEQS'])},
            # (a key of its own keeps `serve` a flat table of numbers)
        }

    # ------------------------------------------------------------ check
    def check(self) -> dict:
        from chipbench import reference_mellum2 as ref
        tolerance = self.ctx.config['check']['tolerance']
        faults = [self.late_fault] if self.late_fault else []
        if self.missing or not self.checked:
            faults.append('the check\'s requests %s were not answered'
                          % (self.missing or 'of the window'))
        # the reference runs beside the weights: give it the pools' room
        self.engine.close()
        self.engine.lm_runtime().drop_cache()
        weights = reference_weights(self.model.params, self.model_config)
        errors = []
        for i, (prompt, result) in sorted(self.checked.items()):
            got = np.stack([np.asarray(row) for row in result.logits])
            ids = np.concatenate([prompt, result.token_ids[:-1]])
            want = np.asarray(ref.forward(
                self.model_config, weights, ids,
                first_logit=int(prompt.shape[0]) - 1))
            error = compare_logits(got, want)
            errors.append(error)
            self.ctx.log(
                'check: request %d (prompt %d, %d new): off by at most '
                '%.4g of the logits\' spread, quantiles 50/75/90/95/99 %s, '
                '%.3f of %d positions beyond %.3g (beyond 0.06/0.1/0.2/0.3: '
                '%s); greedy ids agree at %.3f'
                % (i, prompt.shape[0], got.shape[0], error.max(),
                   np.round(np.percentile(error, [50, 75, 90, 95, 99]), 4),
                   (error > tolerance['relative_error']).mean(),
                   error.shape[0], tolerance['relative_error'],
                   [round(float((error > b).mean()), 3)
                    for b in (0.06, 0.1, 0.2, 0.3)],
                   (want.argmax(-1) == result.token_ids).mean()))
            if self.ctx.config['check'].get('probe_lower_precision') \
                    and len(errors) == 1:
                self.probe_lower_precision(weights, ids, prompt, want)
        if errors:
            faults += judge(np.concatenate(errors), tolerance)
        return {'faults': faults}

    def probe_lower_precision(self, weights, ids, prompt, want) -> None:
        """The reading a tolerance is set against (PERF.md, section 4):
        the reference itself with every matrix rounded to three mantissa
        bits (float8 e4m3's; its narrower exponent left aside), the
        nearest precision below the configuration's bfloat16, against the
        reference proper.  Never
        part of a shipped run: a key of a scratch copy of the
        configuration turns it on."""
        import jax
        import jax.numpy as jnp
        from chipbench import reference_mellum2 as ref

        @jax.jit
        def rounded(w):
            # three mantissa bits, round to nearest even, by bit
            # arithmetic: a float8 cast and back is a pair of converts the
            # chip's compiler may drop (it did: the first probe read 0)
            if w.ndim < 2:
                return w
            bits = jax.lax.bitcast_convert_type(
                w.astype(jnp.float32), jnp.uint32)
            drop = 20
            bits = (bits + ((1 << (drop - 1)) - 1) + ((bits >> drop) & 1)) \
                & jnp.uint32(~((1 << drop) - 1) & 0xFFFFFFFF)
            return jax.lax.bitcast_convert_type(
                bits, jnp.float32).astype(w.dtype)

        class Rounded:
            def __init__(self, layers):
                self.layers = layers

            def __iter__(self):
                for layer in self.layers:
                    yield ref.LayerWeights(*[rounded(w) for w in layer])
        low = ref.Weights(embed=rounded(weights.embed),
                          head=rounded(weights.head),
                          final_norm=weights.final_norm,
                          layers=Rounded(weights.layers))
        got = np.asarray(ref.forward(self.model_config, low, ids,
                                     first_logit=int(prompt.shape[0]) - 1))
        error = compare_logits(got, want)
        self.ctx.log('check: PROBE float8 weights in the reference: off by '
                     'at most %.4g, quantiles 5/25/50/75/95 %s: %s'
                     % (error.max(),
                        np.round(np.percentile(error, [5, 25, 50, 75, 95]),
                                 4),
                        judge(error, self.ctx.config['check']['tolerance'])
                        or 'WOULD PASS'))

    def close(self) -> None:
        self.engine.close()
        self.model.close_stores()
