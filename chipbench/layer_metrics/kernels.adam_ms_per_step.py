"""``kernels.adam_ms_per_step``: a step's own device time, in ms, under
``c2v_adam``: ``optimizer.update`` + ``apply_updates``, the walk over the
three tables and the dense parameters, and the casts that feed it. From the
capture and the legend the trainer wrote beside it
(``reduce/step_scopes.py``)."""
from chipbench.reduce import step_scopes


def read(run):
    return step_scopes.read_metric(run, 'adam_ms_per_step')
