"""``kernels.table_grad_roofline``: the least time the chip's peaks allow
the ``table_grad`` part's work (``work_train_parts.py``) over the device
time ``kernels.table_grad_ms_per_step`` reads, in percent; the bound is named
on an earlier line (``reduce/step_scopes.py``)."""
from chipbench.reduce import step_scopes


def read(run):
    return step_scopes.read_metric(run, 'table_grad_roofline')
