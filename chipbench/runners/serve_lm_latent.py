"""Open-loop turns of resident sessions on a latent-attention model with a
share of the experts (``MODEL_FAMILY`` ``mistral4``), through the same
``serving_engine().submit``, tier ``generate``, with ``session=``.

Everything but the model is ``runners/serve_lm_sessions.py``'s: set-up
prefills the mix's sessions through the engine's own ``generate`` path, the
schedule is ``traffic/session_turns.py``'s, a turn's latency runs from the
instant it was DUE to its last token, and the end-to-end median is over the
turns due inside the window.  What is this model's own:

- the keys of its ``config.json`` (``MODEL_KEYS``) and the map of the
  program's weights to the reference's (``reference_weights``);
- the window's counters: routing choices and those on held experts, the
  held experts' load, latent positions read and up-projected
  (``summarize``);
- the check: the shortest session's first ``check_turns`` turns of the
  window were submitted with ``return_logits``; after the window the pools
  are freed and ``reference_mistral4.py`` computes ONE full forward pass
  over that session's whole history from position 0.  The float32 logits
  the timed path produced at each checked turn's last prompt position (a
  chunk: the expanded path) and at each of its decode steps (the absorbed
  path: on the chip the kernel) are held to the configuration's written
  tolerance (``judge``: the share of positions beyond ``relative_error``;
  this configuration writes no cap on the worst position);
- the collector: once set-up is done, every object it made is frozen out
  of the collector's reach (``gc.freeze``), as a long-running server does
  after start-up, and the window's full collections are timed and put on
  the window's line.
"""
from __future__ import annotations

import gc
import json
import os
import time

import numpy as np

from chipbench.runners import common, serve_lm, serve_lm_sessions
from chipbench.runners.serve_lm import compare_logits

#: the keys of a configuration's file that are the model's own config.json,
#: and the two this repository adds to say which experts are held
MODEL_KEYS = (
    'attention_bias', 'first_k_dense_replace', 'head_dim', 'hidden_act',
    'hidden_size', 'intermediate_size', 'kv_lora_rank',
    'max_position_embeddings', 'mlp_bias', 'model_type',
    'moe_intermediate_size', 'n_group', 'n_routed_experts',
    'n_shared_experts', 'norm_topk_prob', 'num_attention_heads',
    'num_experts_per_tok', 'num_hidden_layers', 'num_key_value_heads',
    'q_lora_rank', 'qk_head_dim', 'qk_nope_head_dim', 'qk_rope_head_dim',
    'rms_norm_eps', 'rope_interleave', 'rope_parameters',
    'routed_scaling_factor', 'sliding_window', 'tie_word_embeddings',
    'topk_group', 'v_head_dim', 'vocab_size',
    'n_routed_experts_published', 'first_held_expert')


def judge(errors: np.ndarray, tolerance: dict):
    """``serve_lm.judge`` with no cap on the worst position unless the
    tolerance writes one."""
    return serve_lm.judge(errors, dict({'relative_error_cap': np.inf},
                                       **tolerance))


class FullCollections:
    """``gc.callbacks`` hook: (start on ``time.perf_counter``'s clock,
    seconds) of every generation-2 collection, while it is installed."""

    def __init__(self):
        self.pauses = []
        self._start = None

    def __call__(self, when: str, info: dict) -> None:
        if info['generation'] != 2:
            return
        if when == 'start':
            self._start = time.perf_counter()
        elif self._start is not None:
            self.pauses.append((self._start,
                                time.perf_counter() - self._start))
            self._start = None


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
        from chipbench import reference_mistral4 as ref
        config = self.model_config
        q_lora = int(config['q_lora_rank'])
        width = int(config['moe_intermediate_size'])
        shared = int(config['n_shared_experts']) * width
        for layer in self.layers:
            made = ref.LayerWeights(
                attn_norm=layer['attn_norm'], wq_a=layer['wa'][:, :q_lora],
                q_norm=layer['q_norm'], wq_b=layer['wq_b'],
                wkv_a=layer['wa'][:, q_lora:], kv_norm=layer['kv_norm'],
                wkv_b=layer['wkv_b'], wo=layer['wo'],
                mlp_norm=layer['mlp_norm'], router=layer['router'],
                w_gate=layer['w_gate_up'][..., :width],
                w_up=layer['w_gate_up'][..., width:],
                w_down=layer['w_down'],
                shared_gate=layer['shared_gate_up'][:, :shared],
                shared_up=layer['shared_gate_up'][:, shared:],
                shared_down=layer['shared_down'])
            yield made if self.each is None else self.each(made)


def reference_weights(params, model_config: dict, each=None):
    """The program's parameter tree as the reference's ``Weights``:
    nothing cast (the reference casts up)."""
    from chipbench import reference_mistral4 as ref
    return ref.Weights(embed=params['embed'], head=params['head'],
                       final_norm=params['final_norm'],
                       layers=_Layers(params['layers'], model_config, each))


class Runner(serve_lm_sessions.Runner):
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
        ctx.log('cache pools %s bytes; geometry %s; kernels %s'
                % (runtime.cache_bytes(), runtime.geometry,
                   runtime.step_kernels))
        self.collections = FullCollections()

    def warm(self) -> None:
        super().warm()
        gc.collect()
        gc.freeze()
        if self.collections not in gc.callbacks:
            gc.callbacks.append(self.collections)
        self.ctx.log('gc: %d objects frozen after set-up'
                     % gc.get_freeze_count())

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
        samples = [s for s in run['samples'] if 0 <= s[0] <= seconds]
        table = np.asarray(samples, np.float64).reshape(-1, 5)
        start = run['stats_at_window'] or run['stats_before']
        end = run['stats_after']
        lm0, lm1 = start['lm'], end['lm']

        def over(key: str) -> float:
            return float(lm1[key] - lm0[key])
        steps = over('steps_total')
        routed = over('routing_choices_total')
        experts = (lm1['expert_tokens'] - lm0['expert_tokens']).astype(
            np.float64)
        load = experts.max(axis=1) / np.maximum(experts.mean(axis=1), 1e-9)
        t0 = run['t0']
        turns = [r for r in run['request_log']
                 if t0 <= r['t_enqueue'] < t0 + seconds]
        pauses = [took for at, took in self.collections.pauses
                  if t0 <= at < t0 + seconds]
        stepped = [s for s in run['step_log']
                   if t0 <= s['t_done'] < t0 + seconds]

        def median_ms(values):
            return 1e3 * float(np.median(values)) if len(values) else None

        def median(values) -> float:
            return float(np.median(values)) if len(values) else 0.0

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
            'page_pool_fill': column(3),
            'running_mean': column(4),
            'steps': int(steps),
            'tokens_per_step': over('tokens_total') / max(steps, 1),
            'admit_held': int(over('admit_held_total')),
            'shed': int(end['shed_total'] - start['shed_total']),
            'expired': int(end['expired_total'] - start['expired_total']),
            'held_choice_share':
                over('held_choices_total') / routed if routed else None,
            'expert_load_max_over_mean': float(load.mean()),
            'latent_positions_read': int(over('latent_positions_read_total')),
            'latent_positions_upprojected':
                int(over('latent_positions_upprojected_total')),
            'ttft_ms_p50': median_ms(
                [r['t_first_token'] - r['t_enqueue'] for r in turns]),
            'decode_step_ms_p50': median_ms(
                [s['seconds'] for s in stepped if not s['chunk_tokens']]),
            'prefill_chunk_ms_p50': median_ms(
                [s['seconds'] for s in stepped if s['chunk_tokens']]),
            'session_wait_ms_p50': median_ms(
                [r['session_wait'] for r in turns
                 if r['session_wait'] is not None]),
            'turns_stamped': len(turns), 'steps_stamped': len(stepped),
            'gc_full_collections': len(pauses),
            'gc_pause_ms_max': 1e3 * max(pauses, default=0.0),
        }

    def measure(self, seconds: float) -> dict:
        obs = super().measure(seconds)
        # this model's readers read their own key
        obs['lmlatent'] = obs.pop('lmhybrid')
        return obs

    def check(self) -> dict:
        from chipbench import reference_mistral4 as ref
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
                'session; the first through a chunk, the rest decode '
                'steps): off by at most %.4g of the logits\' spread, '
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
        if self.collections in gc.callbacks:
            gc.callbacks.remove(self.collections)
        super().close()
