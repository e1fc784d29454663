"""``kernels.table_grad_ms_per_step``: a step's own device time, in ms, under
``c2v_table_grad``: the compact scatter-adds into the step's row buffers,
the zero-fill and the one unique sorted scatter a table (the dense
scatter-adds on the fallback path). From the capture and the legend the
trainer wrote beside it (``reduce/step_scopes.py``)."""
from chipbench.reduce import step_scopes


def read(run):
    return step_scopes.read_metric(run, 'table_grad_ms_per_step')
