"""Trainer: the staging ring and the fit loop (training/trainer.py)."""
from chipbench.layer_metrics import per_sample_ms, present


def read(run):
    instruments = run['obs'].get('instruments')
    return present({
        'trainer.h2d_ms_per_step': per_sample_ms(instruments, 'step/h2d_ms'),
        'trainer.dispatch_ms_per_step':
            per_sample_ms(instruments, 'step/dispatch_ms'),
        'trainer.sync_ms_per_window':
            per_sample_ms(instruments, 'step/sync_ms'),
        # first to last sync of the window, epoch turns included
        'trainer.whole_window_examples_per_sec_per_chip':
            run['obs'].get('whole_window_examples_per_sec_per_chip'),
    })
