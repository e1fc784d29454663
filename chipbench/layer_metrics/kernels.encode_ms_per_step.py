"""``kernels.encode_ms_per_step``: a step's own device time, in ms, under
``c2v_encode``: the ragged fused encoder's forward, its recompute in the
backward and the gradients of ``transform`` / ``attention``. From the
capture and the legend the trainer wrote beside it
(``reduce/step_scopes.py``)."""
from chipbench.reduce import step_scopes


def read(run):
    return step_scopes.read_metric(run, 'encode_ms_per_step')
