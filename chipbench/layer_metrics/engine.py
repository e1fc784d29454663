"""Serving engine (serving/engine.py): its own counters and timers.

The timers are windowed (the last 512 samples), which suits a median; the
cell's p50 and p99 are not taken from them but from every request, on the
client's side.
"""
from chipbench.layer_metrics import present


def read(run):
    serve = run['obs'].get('serve', {})
    return present({
        'engine.warmup_s': run['spans'].get('engine.warmup_s'),
        'engine.batch_fill_rate': (100.0 * serve['batch_fill_rate']
                                   if 'batch_fill_rate' in serve else None),
        'engine.rows_per_batch': serve.get('rows_per_batch'),
        'engine.queue_depth_mean': serve.get('queue_depth_mean'),
        'engine.dispatch_ms_p50': serve.get('dispatch_ms_p50'),
        'engine.decode_ms_p50': serve.get('decode_ms_p50'),
        # the tails, from every request on the client's side: they do not
        # repeat within any bound the benchmark may set (PERF.md, section 2)
        'engine.p95_ms': serve.get('p95_ms'),
        'engine.p99_ms': serve.get('p99_ms'),
    })
