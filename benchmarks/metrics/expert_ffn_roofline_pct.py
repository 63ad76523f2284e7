"""The least time the chip could take for a step's grouped expert products
(the larger of their needed FLOPs over peak FLOP/s and needed bytes over peak
bytes/s, ``Config.expert_ffn_needs``) over ``expert_ffn_ms``: a share of the
products alone, as ``expert_ffn_ms`` is their time alone.  The needed FLOPs
are an EXPECTATION of the routing (``tokens x top_k x held / num_experts``
assignments), not the count the step did: where fewer assignments fall on held
experts the share reads that much too high."""

import catalog

measured = catalog.metric_module('expert_ffn_ms').seconds


def bounds(c):
    flops, nbytes = c['config'].expert_ffn_needs()
    return (flops / c['peaks']['bf16_flops_per_s'],
            nbytes / c['peaks']['hbm_bytes_per_s'])


def explain(c):
    compute, memory = bounds(c)
    return {'bound_by': 'compute' if compute >= memory else 'memory',
            'least_seconds_by_compute': compute, 'least_seconds_by_memory': memory}


def read(c):
    per_step = measured(c)
    if per_step is None or not hasattr(c['config'], 'expert_ffn_needs'):
        return None
    return 100.0 * max(bounds(c)) / per_step
