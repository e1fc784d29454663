"""``kernels.unscoped_ms_per_step``: a step's own device time, in ms, under no
``c2v_`` scope and no collective: the coverage of the names. From the
capture and the legend the trainer wrote beside it
(``reduce/step_scopes.py``)."""
from chipbench.reduce import step_scopes


def read(run):
    return step_scopes.read_metric(run, 'unscoped_ms_per_step')
