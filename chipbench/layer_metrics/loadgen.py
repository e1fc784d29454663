"""The benchmark's own load generator: how late it ran.

A validity check more than a layer: a starved generator offers less than
the cell says, and must not read as a fast server.
"""
from chipbench.layer_metrics import present


def read(run):
    serve = run['obs'].get('serve', {})
    return present({
        'loadgen.late_ms_p99': serve.get('late_p99_ms'),
        'loadgen.offered_per_s': serve.get('offered_per_s'),
    })
