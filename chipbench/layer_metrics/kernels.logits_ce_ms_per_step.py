"""``kernels.logits_ce_ms_per_step``: a step's own device time, in ms, under
``c2v_logits`` and ``c2v_ce``, forward and backward: they fuse into each
other, so they are one part. From the capture and the legend the trainer
wrote beside it (``reduce/step_scopes.py``)."""
from chipbench.reduce import step_scopes


def read(run):
    return step_scopes.read_metric(run, 'logits_ce_ms_per_step')
