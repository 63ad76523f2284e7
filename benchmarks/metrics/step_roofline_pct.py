"""The least time the chip could take for one step (the larger of needed FLOPs
over peak FLOP/s and needed bytes over peak bytes/s) over ``step_device_ms``."""


def bounds(c):
    """(seconds by compute, seconds by memory) of one step."""
    config, peaks = c['config'], c['peaks']
    return (config.needed_flops_per_sample() * config.batch
            / peaks['bf16_flops_per_s'],
            config.needed_bytes_per_step() / peaks['hbm_bytes_per_s'])


def explain(c):
    compute, memory = bounds(c)
    return {'bound_by': 'compute' if compute >= memory else 'memory',
            'least_seconds_by_compute': compute, 'least_seconds_by_memory': memory}


def read(c):
    trace = c['trace']
    if not trace or not trace['step_count']:
        return None
    return 100.0 * max(bounds(c)) / (trace['step_device_s'] / trace['step_count'])
