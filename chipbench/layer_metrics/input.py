"""Host input: data/cache.py, data/packed.py, reader.prefetch_iterator."""
from chipbench.layer_metrics import per_sample_ms, present


def read(run):
    instruments = run['obs'].get('instruments')
    fill = (instruments[1].get('input/packed_fill_rate')
            if instruments and instruments[1] else None)
    return present({
        # time the fit loop is starved, placement subtracted
        'input.batch_wait_ms_per_step':
            per_sample_ms(instruments, 'step/batch_wait_ms'),
        # StickyPacker, on the prefetch thread
        'input.pack_ms_per_batch': per_sample_ms(instruments, 'step/pack_ms'),
        # retained slots over wire capacity, last batch of the window
        'input.packed_fill_rate': None if fill is None else 100.0 * fill,
    })
