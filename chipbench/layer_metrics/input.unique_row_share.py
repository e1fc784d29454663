"""``input.unique_row_share``: the program's gauge
``input/unique_row_share`` in percent: the step's distinct embedding rows
(a row two shards name counted once) over the retained index slots of the
last batch packed (``data/packed.py::StickyPacker``). Set only where the
batch carries its rows, so a value says that the row-wise table gradients
are engaged; a program or a stream without the gauge reads nothing."""
from code2vec_tpu.telemetry import core


def read(run):
    gauge = core.registry().get('input/unique_row_share')
    return {} if gauge is None else {
        'input.unique_row_share': 100.0 * gauge.value}
