"""``input.row_capacity_fill``: the program's gauge
``input/row_capacity_fill`` in percent: the step's distinct embedding rows
over the sticky touched-row capacities ``U_tok + U_path`` of the last batch
packed (``data/packed.py::StickyPacker``): how much of the row buffers the
step's scatters and its all-reduce carry is rows, and how much padding. A
program or a stream without the gauge reads nothing."""
from code2vec_tpu.telemetry import core


def read(run):
    gauge = core.registry().get('input/row_capacity_fill')
    return {} if gauge is None else {
        'input.row_capacity_fill': 100.0 * gauge.value}
