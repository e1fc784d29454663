"""Per-layer metric readers, one file per layer (or per metric).

A reader is ``read(run) -> {metric name: value}``. ``run`` holds what one
traced run left behind::

    cell      the manifest's Cell (configuration and traffic files parsed)
    obs       what the runner's measure() returned
    spans     the harness's own spans, seconds by name
    trace     chipbench.reduce.trace's reduction of the profiler trace ({} if
              there is none)
    work      chipbench.work's operations and bytes of one step on one chip
              (None where the mix names no work function)
    peaks     the chip's entry of peaks.json
    compiles  {'total', 'cache_hits', 'cache_misses'} of the whole process
    device    the result line's ``device`` object
    log       a function that prints an earlier line

A reader that finds nothing to read returns nothing for that metric, and the
harness leaves it out of the line. Values carry the unit the manifest gives
the metric; a share is in percent.
"""


def per_sample_ms(instruments, name):
    """Mean milliseconds per sample of one of the program's timers over the
    measured window, from its (total seconds, count) at the window's start
    and end; None if it took no sample there."""
    start, end = instruments or (None, None)
    if not start or not end or name not in start or name not in end:
        return None
    seconds = end[name][0] - start[name][0]
    count = end[name][1] - start[name][1]
    return 1e3 * seconds / count if count > 0 else None


def present(values: dict) -> dict:
    return {name: value for name, value in values.items()
            if value is not None}
