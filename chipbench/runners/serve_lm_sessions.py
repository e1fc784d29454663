"""Open-loop turns of resident sessions on a language model, through the
same ``serving_engine().submit``, tier ``generate``, with ``session=``.

Set-up builds the model, warms the engine's programs and then makes the
mix's sessions resident: each session's context goes through the engine's
own ``generate`` path as a first turn with one new token (a minute or more
of prefill at the cell's sizes: set-up, not window).  The schedule
(``traffic/session_turns.py``) is computed before the run: the same lengths,
order, sessions and instants in every run; ``--seed`` picks the weights and
the token ids.  It starts ``lead_in_s`` before the window.  A turn's latency
runs from the instant it was DUE to the instant its last token was
delivered; the end-to-end median is over the turns due inside the window.
The load generator, the well-formedness test, the logit comparison and the
judge are ``runners/serve_lm.py``'s.

The check (``correct``): the shortest session's first ``check_turns`` turns
of the window were submitted with ``return_logits``; after the window the
pools are freed and ``reference_minicpm_sala.py`` computes ONE full forward
pass over that session's whole history from position 0 (its context, every
earlier turn's prompt and generated ids) to the later turn's end.  The
float32 logits the timed path produced at each checked turn's last prompt
position and at each of its decode steps, through resident recurrent
states, pooled keys and chosen blocks, are held to the configuration's
written tolerance.
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
from chipbench.runners.serve_lm import _well_formed, compare_logits, judge

#: the keys of a configuration's file that are the model's own config.json,
#: and the two this repository adds to say what of it is run
MODEL_KEYS = (
    'attention_bias', 'attn_use_rope', 'head_dim', 'hidden_act',
    'hidden_size', 'intermediate_size', 'lightning_head_dim',
    'lightning_nh', 'lightning_nkv', 'lightning_scale',
    'lightning_use_rope', 'max_position_embeddings', 'model_type',
    'mixer_types', 'num_attention_heads', 'num_hidden_layers',
    'num_key_value_heads', 'qk_norm', 'rand_init', 'rms_norm_eps',
    'vocab_size', 'rope_theta', 'scale_emb', 'scale_depth',
    'mup_denominator', 'dim_model_base', 'tie_word_embeddings',
    'use_output_gate', 'use_output_norm', 'attn_use_output_gate',
    'sparse_config', 'first_hidden_layer')


class _Layers:
    """The program's layers as the reference's ``LayerWeights``, one at a
    time: the fused products are split (a slice is a copy) when a layer is
    asked for, so that only one layer's copies live beside the weights.
    ``each`` is applied to every layer as it is made."""

    def __init__(self, layers, model_config: dict, each=None):
        self.layers = layers
        self.model_config = model_config
        self.each = each

    def __len__(self) -> int:
        return len(self.layers)

    def __iter__(self):
        from chipbench import reference_minicpm_sala as ref
        config = self.model_config
        first = int(config.get('first_hidden_layer', 0))
        kinds = config['mixer_types'][
            first:first + int(config['num_hidden_layers'])]
        width = int(config['intermediate_size'])
        for i, (kind, layer) in enumerate(zip(kinds, self.layers)):
            if kind == 'lightning-attn':
                q = kv = int(config['lightning_nh']) \
                    * int(config['lightning_head_dim'])
            else:
                q = int(config['num_attention_heads']) \
                    * int(config['head_dim'])
                kv = int(config['num_key_value_heads']) \
                    * int(config['head_dim'])
            fused = layer['wqkvg']
            made = ref.LayerWeights(
                kind=kind, index=first + i, attn_norm=layer['attn_norm'],
                wq=fused[:, :q], wk=fused[:, q:q + kv],
                wv=fused[:, q + kv:q + 2 * kv], wg=fused[:, q + 2 * kv:],
                wo=layer['wo'], q_norm=layer['q_norm'],
                k_norm=layer['k_norm'], o_norm=layer.get('o_norm'),
                mlp_norm=layer['mlp_norm'],
                w_gate=layer['w_gate_up'][:, :width],
                w_up=layer['w_gate_up'][:, width:], w_down=layer['w_down'])
            yield made if self.each is None else self.each(made)


def reference_weights(params, model_config: dict, each=None):
    """The program's parameter tree as the reference's ``Weights``:
    nothing cast (the reference casts up)."""
    from chipbench import reference_minicpm_sala as ref
    return ref.Weights(embed=params['embed'], head=params['head'],
                       final_norm=params['final_norm'],
                       layers=_Layers(params['layers'], model_config, each))


class Runner:
    def __init__(self, ctx: common.Context, compiles: common.CompileCounter):
        self.ctx = ctx
        self.compiles = compiles
        self.turns = manifest.load_module('traffic',
                                          ctx.traffic['generator'])
        # what each session's cache holds, as the engine will see it:
        # tokens of history (every prompt and generated id), None before
        # set-up; and the ids of the session the check reads
        self.history_len = np.zeros((0,), np.int64)
        self.checked_history: List[np.ndarray] = []
        self.checked: list = []
        self.epoch = 0

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
        logits rows, delivery), their results dropped; then the sessions
        are made resident."""
        with self.ctx.span('loadgen.warm_s'):
            rng = np.random.default_rng([self.ctx.seed, 0x3A2])
            futures = [self.engine.submit(
                rng.integers(0, self.vocab, int(length), dtype=np.int32),
                tier='generate', max_new_tokens=int(new),
                return_logits=True)
                for length, new in self.ctx.traffic['warm_requests']]
            for future in futures:
                np.asarray(future.result(timeout=600).logits[0])
        schedule = self.turns.generate(self.ctx.traffic['arrivals'],
                                       self.ctx.seed, 1.0, self.vocab)
        self.make_sessions(schedule)

    def session_name(self, s: int):
        return 'session-%d-%d' % (self.epoch, s)

    def make_sessions(self, schedule) -> None:
        """Closes the sessions there are and prefills the mix's anew: each
        context a first turn with one new token."""
        with self.ctx.span('loadgen.sessions_s'):
            for s in range(len(self.history_len)):
                self.engine.close_session(self.session_name(s))
            self.epoch += 1
            t0 = time.perf_counter()
            contexts = [self.turns.session_ids(schedule, s)
                        for s in range(schedule.session_len.shape[0])]
            futures = [self.engine.submit(ids, tier='generate',
                                          max_new_tokens=1,
                                          session=self.session_name(s))
                       for s, ids in enumerate(contexts)]
            firsts = [future.result(timeout=1800).token_ids
                      for future in futures]
            took = time.perf_counter() - t0
        self.history_len = np.asarray(
            [int(ids.shape[0]) + 1 for ids in contexts], np.int64)
        self.checked_history = [contexts[0], firsts[0]]
        self.ctx.log('sessions: %d resident, %d positions prefilled in '
                     '%.1f s (%.0f tokens/s); pools %s'
                     % (len(contexts),
                        self.history_len.sum() - len(contexts), took,
                        (self.history_len.sum() - len(contexts)) / took,
                        {k: round(v, 3) for k, v in
                         self.engine.stats()['lm'].items()
                         if k.endswith('_fill')}))

    def room_for(self, schedule) -> bool:
        """Whether the sessions, grown by every turn of ``schedule``, stay
        inside the longest context and the page pool."""
        g = self.engine.lm_runtime().geometry
        grown = self.history_len.copy()
        np.add.at(grown, schedule.session,
                  schedule.prompt_len + schedule.new_tokens)
        pages = -(-(grown - 1) // g.page_size)
        return bool((grown - 1 <= g.max_context).all()
                    and pages.sum() <= g.pool_pages)

    # ------------------------------------------------------------- load
    def offer(self, params: dict, seed: int, seconds: float,
              sample_every_s: float = 0.05, check_turns: int = 0) -> dict:
        """Offers the lead-in and ``seconds`` of the schedule and waits
        for the answers of the window's turns."""
        import jax
        engine = self.engine
        with self.ctx.span('lifecycle.data_s'):
            schedule = self.turns.generate(params, seed, seconds,
                                           self.vocab)
            n = schedule.due_s.shape[0]
            prompts = [self.turns.prompt_ids(schedule, i) for i in range(n)]
        if not self.room_for(schedule):
            # a sweep's later rates: the sessions have outgrown the pool
            self.ctx.log('sessions have no room for %d more turns: made '
                         'anew' % n)
            self.make_sessions(schedule)
            if not self.room_for(schedule):
                raise SystemExit('chipbench: the schedule outgrows '
                                 'LM_MAX_CONTEXT or the page pool')
        in_window = schedule.due_s >= 0
        picked = self.pick_checked(schedule, check_turns)
        with_logits = set(picked)
        # every turn of the checked session up to the last one checked is
        # part of the history the reference reads
        kept = {i for i in range(max(picked) + 1)
                if schedule.session[i] == 0} if picked else set()
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
                if i in kept:
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
                            return_logits=i in with_logits,
                            session=self.session_name(
                                int(schedule.session[i])))
                except Exception:        # shed, closed: a failed turn
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
                                stats['queue_depth'], lm['state_pool_fill'],
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
            # every turn, the lead-in's too: a session's next offer starts
            # where this one's last turn ended
            while np.isnan(done).any() and time.perf_counter() < deadline:
                time.sleep(0.005)
        sampling.set()
        sampler.join()
        after = engine.stats()
        np.add.at(self.history_len, schedule.session,
                  schedule.prompt_len + schedule.new_tokens)
        return {'schedule': schedule, 'submitted': submitted, 'done': done,
                'ok': ok & ~np.isnan(done), 'in_window': in_window,
                't0': t0, 'samples': samples, 'stats_before': before,
                'stats_at_window': at_window[0],
                'compiles_at_window': at_window[1], 'stats_after': after,
                'request_log': engine.lm_request_log(),
                'step_log': engine.lm_step_log(),
                'results': results, 'prompts': prompts, 'picked': picked,
                'kept': sorted(kept)}

    @staticmethod
    def pick_checked(schedule, count: int) -> List[int]:
        """The shortest session's (session 0's) first ``count`` turns of
        the window."""
        if count <= 0:
            return []
        own = np.flatnonzero((schedule.due_s >= 0)
                             & (schedule.session == 0))
        return [int(i) for i in own[:count]]

    def summarize(self, run: dict, seconds: float) -> dict:
        """Latencies of the window's turns from their due instants,
        failures at ``drain_s``; the engine's own counters over the
        window."""
        schedule, window = run['schedule'], run['in_window']
        due = schedule.due_s[window]
        latency = np.where(run['ok'][window], run['done'][window] - due,
                           self.drain_s)
        late = run['submitted'][window] - due
        n = int(window.sum())
        half = due < seconds / 2
        # the window's own samples: after it the queue drains whatever the
        # rate was
        samples = [s for s in run['samples'] if 0 <= s[0] <= seconds]
        table = np.asarray(samples, np.float64).reshape(-1, 5)
        start = run['stats_at_window'] or run['stats_before']
        end = run['stats_after']
        lm0, lm1 = start['lm'], end['lm']

        def over(key: str) -> float:
            return float(lm1[key] - lm0[key])
        steps = over('steps_total')
        resident = over('resident_positions_total')
        prefilled = over('prefilled_positions_total')
        visible = over('sparse_blocks_visible_total')

        def median(values) -> float:
            return float(np.median(values)) if len(values) else 0.0

        # the window's own turns and steps, by the program's stamps (its
        # timers hold the process's last 512 samples, set-up's among them)
        t0 = run['t0']
        turns = [r for r in run['request_log']
                 if t0 <= r['t_enqueue'] < t0 + seconds]
        stepped = [s for s in run['step_log']
                   if t0 <= s['t_done'] < t0 + seconds]

        def median_ms(values):
            return 1e3 * float(np.median(values)) if len(values) else None

        def column(k: int, reduce=np.mean) -> float:
            return float(reduce(table[:, k])) if table.size else 0.0
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
            'late_p50_ms': float(np.nanpercentile(late, 50) * 1e3),
            'late_p99_ms': float(np.nanpercentile(late, 99) * 1e3),
            'mean_gap_ms': 1e3 * seconds / max(n, 1),
            'queue_depth_mean': column(1),
            'queue_depth_max': column(1, np.max),
            'queue_depth_last': float(table[-1, 1]) if table.size else 0.0,
            'state_pool_fill': column(2),
            'page_pool_fill': column(3),
            'running_mean': column(4),
            'steps': int(steps),
            'tokens_per_step': over('tokens_total') / max(steps, 1),
            'admit_held': int(over('admit_held_total')),
            'resident_positions': int(resident),
            'prefilled_positions': int(prefilled),
            'resident_positions_share':
                resident / max(resident + prefilled, 1.0),
            'blocks_read_share':
                over('sparse_blocks_chosen_total') / visible
                if visible else None,
            'dense_branch_calls': int(over('sparse_dense_branch_total')),
            'shed': int(end['shed_total'] - start['shed_total']),
            'expired': int(end['expired_total'] - start['expired_total']),
            'ttft_ms_p50': median_ms(
                [r['t_first_token'] - r['t_enqueue'] for r in turns]),
            'decode_step_ms_p50': median_ms(
                [s['seconds'] for s in stepped if not s['chunk_tokens']]),
            'prefill_chunk_ms_p50': median_ms(
                [s['seconds'] for s in stepped if s['chunk_tokens']]),
            'admit_wait_ms_p50': median_ms(
                [r['t_admitted'] - r['t_enqueue'] for r in turns]),
            'session_wait_ms_p50': median_ms(
                [r['session_wait'] for r in turns
                 if r['session_wait'] is not None]),
            'turns_stamped': len(turns), 'steps_stamped': len(stepped),
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
                             check_turns=int(ctx.traffic['check_turns']))
        finally:
            if tracer is not None:
                tracer.finish()
        summary = self.summarize(run, seconds)
        ctx.log('window: %s' % summary)
        # the checked session's history, and where each checked turn's
        # logits lie in it
        self.checked = []
        self.missing = sorted(set(run['kept']) - set(run['results']))
        at = sum(int(part.shape[0]) for part in self.checked_history)
        if not self.missing:
            for i in run['kept']:
                prompt, result = run['prompts'][i], run['results'][i]
                if i in run['picked']:
                    self.checked.append(
                        (i, at + int(prompt.shape[0]) - 1, result))
                self.checked_history += [prompt, result.token_ids]
                at += int(prompt.shape[0]) + int(result.token_ids.shape[0])
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
            # (a key of its own: `lm` is the expert model's, and its
            # readers count that model's work)
            'lmhybrid': {'step_log': run['step_log'],
                         'model_config': self.model_config,
                         'trace_dir': ctx.trace_dir,
                         'slots': int(ctx.settings['LM_MAX_SEQS']),
                         'programs': self.program_texts() if ctx.trace
                         else {}},
        }

    def program_texts(self) -> dict:
        """{program's name in the trace: its compiled HLO text} of the step
        programs: the text names every instruction's ``jax.named_scope``,
        which the trace's events do not."""
        runtime = self.engine.lm_runtime()
        texts = {}
        try:
            import jax
            for chunk, program in runtime.programs.items():
                layout = runtime.layouts[chunk]
                shaped = jax.tree_util.tree_map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                    (runtime.params, runtime.cache, runtime.prev_ids))
                packed = jax.ShapeDtypeStruct((layout[''][0],), np.int32)
                compiled = program.lower(*shaped, packed).compile()
                texts[chunk] = compiled.as_text()
        except Exception as exc:    # a reader without texts says nothing
            self.ctx.log('no program texts: %r' % (exc,))
        return texts

    # ------------------------------------------------------------ check
    def timed_logits(self):
        """Ends the timed path and gives its pools' room to the reference,
        which runs beside the weights.  Returns (the checked session's
        whole history, the positions of it whose logits the checked turns
        returned, those float32 logits), or None where the checked turns
        were not answered."""
        self.engine.close()
        self.engine.lm_runtime().drop_cache()
        if self.missing or not self.checked:
            return None
        history = np.concatenate(self.checked_history)[:-1]
        rows = np.concatenate([first + np.arange(result.logits.shape[0])
                               for _, first, result in self.checked])
        got = np.concatenate([np.stack([np.asarray(row)
                                        for row in result.logits])
                              for _, _, result in self.checked])
        return history, rows, got

    def check(self) -> dict:
        from chipbench import reference_minicpm_sala as ref
        tolerance = self.ctx.config['check']['tolerance']
        faults = [self.late_fault] if self.late_fault else []
        timed = self.timed_logits()
        if timed is None:
            faults.append('the check\'s turns %s were not answered'
                          % (self.missing or 'of the window'))
            return {'faults': faults}
        history, rows, got = timed
        weights = reference_weights(self.model.params, self.model_config)
        t0 = time.perf_counter()
        want = np.asarray(ref.forward(self.model_config, weights, history,
                                      logit_positions=rows))
        import jax
        stats = jax.local_devices()[0].memory_stats() or {}
        self.ctx.log('check: the reference read %d positions in %.1f s; '
                     'the device held at most %s bytes'
                     % (history.shape[0], time.perf_counter() - t0,
                        stats.get('peak_bytes_in_use')))
        errors = compare_logits(got, want)
        at = 0
        for i, first, result in self.checked:
            error = errors[at:at + result.logits.shape[0]]
            at += result.logits.shape[0]
            self.ctx.log(
                'check: turn %d (logits at positions %d..%d of the '
                'session): off by at most %.4g of the logits\' spread, '
                'quantiles 50/75/90/95/99 %s, %.3f of %d positions beyond '
                '%.3g (beyond 0.03/0.04/0.05/0.06/0.08: %s); greedy ids '
                'agree at %.3f'
                % (i, first, first + error.shape[0] - 1, error.max(),
                   np.round(np.percentile(error, [50, 75, 90, 95, 99]), 4),
                   (error > tolerance['relative_error']).mean(),
                   error.shape[0], tolerance['relative_error'],
                   [round(float((error > b).mean()), 3)
                    for b in (0.03, 0.04, 0.05, 0.06, 0.08)],
                   (want[at - error.shape[0]:at].argmax(-1)
                    == result.token_ids).mean()))
        faults += judge(errors, tolerance)
        return {'faults': faults}

    def close(self) -> None:
        self.engine.close()
        self.model.close_stores()
