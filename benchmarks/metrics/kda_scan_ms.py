"""Device time a step of the gated delta rule's recurrence (``ops/kda.py``):
the intra-chunk solve, the scan over the chunk states and their backward.
``kda_chunked`` keeps all of a chunk's work in the body of one ``lax.scan``,
and the step holds no other loop, so on the device the recurrence is the
operations that XLA names ``while*`` (one for each forward pass of a layer,
one for each backward pass); a kernel that takes their place is named
``pt_kda_*``.  A ``while`` event spans its body's operations, which the trace
lists under their own (numbered) names too: this sums the loops, not both.
Not counted: the projections, convolutions and gates around the recurrence
(scopes ``pt/kda_project``, ``pt/kda_conv``).  ``None`` without a trace or
where the step holds no such operation."""

LOOP, KERNEL = 'while', 'pt_kda_'


def seconds(c):
    trace = c['trace']
    if not trace or not trace['step_count']:
        return None
    found = [s for name, s in trace['device_ops']
             if name.startswith(LOOP) or KERNEL in name]
    return sum(found) / trace['step_count'] if found else None


def read(c):
    per_step = seconds(c)
    return None if per_step is None else 1e3 * per_step
