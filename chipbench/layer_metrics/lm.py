"""The language-model step loop (serving/lm_scheduler.py): its timers and
counters over the window, and the whole step's share of the chip's peak.

==========================  =============================================
metric                      read from
==========================  =============================================
lm.ttft_ms_p50              Timer serving/lm_ttft_ms (the
                            serving/lm_first_token events' since_submit_ms)
lm.prefill_chunk_ms_p50     Timer serving/lm_prefill_chunk_ms: a step that
                            carried a chunk, device-paced
lm.decode_step_ms_p50       Timer serving/lm_decode_step_ms: a step of
                            decode rows only
lm.admit_wait_ms_p50        Timer serving/lm_admit_wait_ms (the
                            serving/lm_admit_wait events' waited_ms)
lm.tokens_per_step          counters lm_tokens_total / lm_steps_total
lm.ring_pool_fill_share     gauge lm_ring_pool_fill, mean of the samples
lm.page_pool_fill_share     gauge lm_page_pool_fill, mean of the samples
lm.expert_load_max_over_mean  per-expert token counts: max over mean a
                            layer, mean over the layers
lm.step_mfu                 required FLOPs of the traced steps
                            (work_lm.py) over the device time of the step
                            programs in the trace, over the chip's peak
==========================  =============================================

The timers are windowed (the last 512 samples), which suits a median.
"""
from chipbench.layer_metrics import lmkernels, present


def read(run):
    serve = run['obs'].get('serve', {})
    values = present({
        'lm.ttft_ms_p50': serve.get('ttft_ms_p50'),
        'lm.prefill_chunk_ms_p50': serve.get('prefill_chunk_ms_p50'),
        'lm.decode_step_ms_p50': serve.get('decode_step_ms_p50'),
        'lm.admit_wait_ms_p50': serve.get('admit_wait_ms_p50'),
        'lm.tokens_per_step': serve.get('tokens_per_step'),
        'lm.ring_pool_fill_share': (100.0 * serve['ring_pool_fill']
                                    if 'ring_pool_fill' in serve else None),
        'lm.page_pool_fill_share': (100.0 * serve['page_pool_fill']
                                    if 'page_pool_fill' in serve else None),
        'lm.expert_load_max_over_mean':
            serve.get('expert_load_max_over_mean'),
    })
    traced = lmkernels.of_run(run)
    if traced and traced['step_seconds'] > 0:
        values['lm.step_mfu'] = 100.0 * traced['work']['step']['flops'] / (
            traced['step_seconds'] * run['peaks']['flops_per_s_bf16'])
    return values
