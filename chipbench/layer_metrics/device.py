"""The device: idle share from the trace, peak memory from the runtime."""
from chipbench.layer_metrics import present


def read(run):
    devices = run['trace'].get('devices', {}) if run['trace'] else {}
    memory = run['obs'].get('memory_at_window_end', {})
    return present({
        # the worst device, where there are several
        'device.idle_share': (100.0 * max(d['idle_share']
                                          for d in devices.values())
                              if devices else None),
        # at the window's end, before the check against the reference:
        # arrays at their peak, or arrays plus what programs reserve
        'device.peak_hbm_bytes': memory.get('peak_bytes'),
        # the part of it that is no array: the temporaries of the largest
        # program loaded, which `peak_bytes_in_use` leaves out
        'device.reserved_bytes': memory.get('bytes_reserved'),
    })
