"""The least time the chip could take for a step's attention inside documents
(the larger of needed FLOPs over peak FLOP/s and needed bytes over peak
bytes/s, ``Config.flash_attention_needs``) over the device time a step of the
flash kernels: the operations whose name holds ``pt_flash_`` (``pt_flash_fwd``,
``pt_flash_bwd_dq``, ``pt_flash_bwd_dkv``, as ``ops/flash_attention.py`` names
its Pallas calls).  ``None`` without a trace or where no such kernel ran."""

KERNEL = 'pt_flash_'


def measured(c):
    trace = c['trace']
    if not trace or not trace['step_count']:
        return None
    found = [s for name, s in trace['device_ops'] if KERNEL in name]
    return sum(found) / trace['step_count'] if found else None


def bounds(c):
    flops, nbytes = c['config'].flash_attention_needs()
    return (flops / c['peaks']['bf16_flops_per_s'],
            nbytes / c['peaks']['hbm_bytes_per_s'])


def explain(c):
    compute, memory = bounds(c)
    return {'bound_by': 'compute' if compute >= memory else 'memory',
            'least_seconds_by_compute': compute, 'least_seconds_by_memory': memory,
            'kernel_seconds_a_step': measured(c)}


def read(c):
    per_step = measured(c)
    if per_step is None or not hasattr(c['config'], 'flash_attention_needs'):
        return None
    return 100.0 * max(bounds(c)) / per_step
