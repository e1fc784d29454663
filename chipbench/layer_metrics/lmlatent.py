"""The step loop (serving/lm_scheduler.py) serving a latent-attention model
with a share of the experts from resident sessions: its timers and counters
over the window, and the whole step's share of the chip's peak.

=================================  ======================================
metric                             read from
=================================  ======================================
lmlatent.ttft_ms_p50               the window's turns (the program's stamps
                                   of each delivered request,
                                   ``engine.lm_request_log()``): enqueued
                                   to first token, median
lmlatent.prefill_chunk_ms_p50      the steps that finished in the window
                                   and carried a chunk
                                   (``engine.lm_step_log()``, ``seconds``:
                                   device-paced), median
lmlatent.decode_step_ms_p50        the window's steps of decode rows only
lmlatent.tokens_per_step           counters lm_tokens_total /
                                   lm_steps_total
lmlatent.page_pool_fill_share      gauge lm_page_pool_fill (the latent
                                   pages), mean of samples
lmlatent.held_choice_share         counters lm_held_choices_total /
                                   lm_routing_choices_total: the routing
                                   choices that fell on this chip's experts
lmlatent.expert_load_max_over_mean ``stats()['lm']['expert_tokens']`` over
                                   the window: the busiest held expert's
                                   tokens over the mean held expert's, a
                                   layer, mean over the layers
lmlatent.step_mfu                  required FLOPs of the traced steps
                                   (work_mistral4.py) over the device time
                                   of those same steps' programs in the
                                   trace, over the chip's peak
=================================  ======================================

Every reading is of the window alone: the counters as differences over it,
the medians over its own turns and steps.
"""
from chipbench.layer_metrics import lmlatentkernels, present


def percent(value):
    return None if value is None else 100.0 * value


def read(run):
    serve = run['obs'].get('serve', {})
    values = present({
        'lmlatent.ttft_ms_p50': serve.get('ttft_ms_p50'),
        'lmlatent.prefill_chunk_ms_p50': serve.get('prefill_chunk_ms_p50'),
        'lmlatent.decode_step_ms_p50': serve.get('decode_step_ms_p50'),
        'lmlatent.tokens_per_step': serve.get('tokens_per_step'),
        'lmlatent.page_pool_fill_share':
            percent(serve.get('page_pool_fill')),
        'lmlatent.held_choice_share':
            percent(serve.get('held_choice_share')),
        'lmlatent.expert_load_max_over_mean':
            serve.get('expert_load_max_over_mean'),
    })
    traced = lmlatentkernels.of_run(run)
    if traced and traced['step_seconds'] > 0:
        values['lmlatent.step_mfu'] = \
            100.0 * traced['work']['step']['flops'] / (
                traced['step_seconds'] * run['peaks']['flops_per_s_bf16'])
    return values
