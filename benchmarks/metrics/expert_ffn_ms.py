"""Device time a step of the grouped expert PRODUCTS alone: the operations
of the traced window that XLA names ``ragged-dot*`` (what
``jax.lax.ragged_dot`` compiles to on the TPU: the grouped matmuls, forward
and backward, and the small program that lays out their tiles), over the steps
traced.  It is not the time of the expert layer: the sort, the gathers of the
assignments' rows and the combine around the products run under other names,
which the reduced trace cannot tell from the rest of the step (PERF.md section
7 says what ``trace_reduce.py`` would have to keep).  ``None`` without a trace
or where the step holds no such operation."""

PREFIX = 'ragged-dot'


def seconds(c):
    trace = c['trace']
    if not trace or not trace['step_count']:
        return None
    found = [s for name, s in trace['device_ops'] if name.startswith(PREFIX)]
    return sum(found) / trace['step_count'] if found else None


def read(c):
    per_step = seconds(c)
    return None if per_step is None else 1e3 * per_step
