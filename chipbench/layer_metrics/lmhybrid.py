"""The step loop (serving/lm_scheduler.py) serving a model of linear and
block-sparse attention from resident sessions: its timers and counters over
the window, and the whole step's and the new kernels' share of the device.

================================  =======================================
metric                            read from
================================  =======================================
lmhybrid.ttft_ms_p50              the window's turns (the program's stamps
                                  of each delivered request,
                                  ``engine.lm_request_log()``): enqueued to
                                  first token, median
lmhybrid.admit_wait_ms_p50        the same turns: enqueued to admitted
lmhybrid.session_wait_ms_p50      the same turns: how long each stood
                                  behind its own session's earlier turn (the
                                  serving/lm_session_wait events' waited_ms)
lmhybrid.prefill_chunk_ms_p50     the steps that finished in the window and
                                  carried a chunk (``engine.lm_step_log()``,
                                  ``seconds``: device-paced), median
lmhybrid.decode_step_ms_p50       the window's steps of decode rows only
lmhybrid.tokens_per_step          counters lm_tokens_total / lm_steps_total
lmhybrid.page_pool_fill_share     gauge lm_page_pool_fill, mean of samples
lmhybrid.state_pool_fill_share    gauge lm_state_pool_fill, mean of samples
lmhybrid.resident_positions_share counters lm_resident_positions_total over
                                  it plus lm_prefilled_positions_total: the
                                  context a turn found in its session's
                                  lease against what it had to prefill
lmhybrid.blocks_read_share        counters lm_sparse_blocks_chosen_total /
                                  lm_sparse_blocks_visible_total: the mean
                                  share of the cache a sparse query reads
lmhybrid.step_mfu                 required FLOPs of the traced steps
                                  (work_minicpm_sala.py) over the device
                                  time of those same steps' programs in the
                                  trace, over the chip's peak
lmhybrid.new_kernels_time_share   the four new kernels' seconds in those
                                  steps (lmhybridkernels.py) over the same
                                  time
================================  =======================================

Every reading is of the window alone: the counters as differences over it,
the medians over its own turns and steps (the program's timers hold the
process's last 512 samples, set-up's prefills and the lead-in among them).
"""
from chipbench.layer_metrics import lmhybridkernels, present


def percent(value):
    return None if value is None else 100.0 * value


def read(run):
    serve = run['obs'].get('serve', {})
    values = present({
        'lmhybrid.ttft_ms_p50': serve.get('ttft_ms_p50'),
        'lmhybrid.prefill_chunk_ms_p50': serve.get('prefill_chunk_ms_p50'),
        'lmhybrid.decode_step_ms_p50': serve.get('decode_step_ms_p50'),
        'lmhybrid.admit_wait_ms_p50': serve.get('admit_wait_ms_p50'),
        'lmhybrid.session_wait_ms_p50': serve.get('session_wait_ms_p50'),
        'lmhybrid.tokens_per_step': serve.get('tokens_per_step'),
        'lmhybrid.page_pool_fill_share':
            percent(serve.get('page_pool_fill')),
        'lmhybrid.state_pool_fill_share':
            percent(serve.get('state_pool_fill')),
        'lmhybrid.resident_positions_share':
            percent(serve.get('resident_positions_share')),
        'lmhybrid.blocks_read_share':
            percent(serve.get('blocks_read_share')),
    })
    traced = lmhybridkernels.of_run(run)
    if traced and traced['step_seconds'] > 0:
        values['lmhybrid.step_mfu'] = \
            100.0 * traced['work']['step']['flops'] / (
                traced['step_seconds'] * run['peaks']['flops_per_s_bf16'])
        values['lmhybrid.new_kernels_time_share'] = \
            100.0 * sum(traced['kernel_seconds'].values()) \
            / traced['step_seconds']
    return values
