"""``engine.decode_pool_busy_share``: the share of the decode pool's time
(``SERVING_DECODE_WORKERS`` x the traced slice) that its workers spent in
``serving/fetch``, ``/decode`` and ``/deliver`` events; a worker is a line
of the trace that holds ``serving/fetch`` events
(``reduce/host_spans.py``)."""
from chipbench.layer_metrics import present
from chipbench.reduce import host_spans


def read(run):
    workers = int(run['cell'].config['settings']['SERVING_DECODE_WORKERS'])
    return present({'engine.decode_pool_busy_share':
                    host_spans.pool_busy_share(host_spans.of_run(run),
                                               workers)})
