"""The least time the chip could take for a step's gated delta rule (the
larger of its needed FLOPs over peak FLOP/s and needed bytes over peak
bytes/s, ``Config.kda_scan_needs``: the chunked form's matrix products at
chunks of 64 and the bytes of its operands and chunk states, whatever
implements it) over ``kda_scan_ms``."""

import catalog

measured = catalog.metric_module('kda_scan_ms').seconds


def bounds(c):
    flops, nbytes = c['config'].kda_scan_needs()
    return (flops / c['peaks']['bf16_flops_per_s'],
            nbytes / c['peaks']['hbm_bytes_per_s'])


def explain(c):
    compute, memory = bounds(c)
    return {'bound_by': 'compute' if compute >= memory else 'memory',
            'least_seconds_by_compute': compute, 'least_seconds_by_memory': memory,
            'scan_seconds_a_step': measured(c)}


def read(c):
    per_step = measured(c)
    if per_step is None or not hasattr(c['config'], 'kda_scan_needs'):
        return None
    return 100.0 * max(bounds(c)) / per_step
