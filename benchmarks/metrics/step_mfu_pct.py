"""The whole step's share of the chip's bf16 peak, idle time included: FLOPs
the forward and backward passes need per sample x samples finished in the
traced window, over its seconds x the peak."""


def read(c):
    trace = c['trace']
    if not trace or not trace['step_count']:
        return None
    flops = c['config'].needed_flops_per_sample() * trace['step_count'] \
        * c['config'].batch
    return 100.0 * flops / (trace['window_s'] * c['peaks']['bf16_flops_per_s']
                            * c['cell']['chips'])
