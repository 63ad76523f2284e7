"""From the profiler's ``.xplane.pb`` to the numbers the per-layer metrics read.

What a trace of this program on a TPU v5e holds (looked at by hand, PR 25; see
``PERF.md``): one plane ``/device:TPU:<n>`` for each chip, whose line
``XLA Ops`` has one event for every operation the TensorCore ran (named by its
whole HLO instruction text) and whose line ``XLA Modules`` has one event for
every execution of a jitted program, named ``jit_<function>(<fingerprint>)``
(``Async XLA Ops`` holds the copy-starts again and is not read; the planes
``#Chip0 ...``, ``/host:metadata`` and ``Task Environment`` hold no events);
and one plane ``/host:CPU`` with a line for each host thread, where
``jax.profiler.TraceAnnotation`` spans (the loader's ``pt/host_batch``,
``pt/transform``, ``pt/device_put`` and the harness's own ``bench/window``)
appear under their names.  All of them are on one clock, in nanoseconds.

    busy        union of the XLA Ops intervals inside the window, a device
    step        the executions of the step program inside the window
    device_ops  the operations that took most device time, by name
    idle_gaps   the device's idle time inside the window, by the ``pt/*`` span
                that covers most of each gap ("train loop" where none does)
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r'^/device:TPU:\d+$')
HOST_PLANE = '/host:CPU'
OPS_LINE, MODULES_LINE = 'XLA Ops', 'XLA Modules'
SPAN_PREFIX = 'pt/'
NO_SPAN = 'train loop'


class TraceError(Exception):
    """The trace does not hold what the reduction needs."""


def extract(path, span_names=()):
    """The trace as plain lists of ``(name, start_ns, duration_ns)``:
    ``{'devices': {plane: {'ops': [...], 'modules': [...]}}, 'host': [...]}``.
    Of the host's events only the ``pt/*`` spans and ``span_names`` are kept."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {'ops': [], 'modules': []}
            for line in plane.lines:
                key = {OPS_LINE: 'ops', MODULES_LINE: 'modules'}.get(line.name)
                if key is not None:
                    lines[key] = [(short(e.name), e.start_ns, e.duration_ns)
                                  for e in line.events]
            devices[plane.name] = lines
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.duration_ns) for e in line.events
                            if e.name.startswith(SPAN_PREFIX) or e.name in span_names)
    return {'devices': devices, 'host': host}


def short(name):
    """An operation is named by its whole HLO instruction (``%fusion.7 =
    bf16[...] fusion(...)``): keep what stands before the ``=``."""
    return name.split(' = ', 1)[0].lstrip('%')


def union(intervals, lo, hi):
    """The sorted, merged parts of ``intervals`` [(start, end)] inside [lo, hi]."""
    merged = []
    for start, end in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if end <= start:
            continue
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def gaps(busy, lo, hi):
    out, cursor = [], lo
    for start, end in busy:
        if start > cursor:
            out.append((cursor, start))
        cursor = max(cursor, end)
    if hi > cursor:
        out.append((cursor, hi))
    return out


def attribute(gap, spans):
    """The name of the span that covers most of ``gap``, if it covers at least
    half of it."""
    lo, hi = gap
    best, best_cover = NO_SPAN, 0.5 * (hi - lo)
    for name, start, end in spans:
        cover = min(end, hi) - max(start, lo)
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def reduce(trace, step_prefix, window_span):
    if not trace['devices']:
        raise TraceError('the trace holds no /device:TPU:<n> plane')
    windows = [(s, s + d) for name, s, d in trace['host'] if name == window_span]
    if len(windows) != 1:
        raise TraceError('the trace holds %d %r spans, not one'
                         % (len(windows), window_span))
    lo, hi = windows[0]
    spans = [(name, s, s + d) for name, s, d in trace['host']
             if name.startswith(SPAN_PREFIX) and s + d > lo and s < hi]
    busy_ns, step_ns, step_count = [], [], []
    op_ns, gap_ns, seen_modules = {}, {}, set()
    for index, (plane, lines) in enumerate(sorted(trace['devices'].items())):
        busy = union([(s, s + d) for _, s, d in lines['ops']], lo, hi)
        busy_ns.append(sum(end - start for start, end in busy))
        seen_modules.update(name for name, _, _ in lines['modules'])
        steps = [d for name, s, d in lines['modules']
                 if name.startswith(step_prefix) and s >= lo and s + d <= hi]
        step_ns.append(sum(steps))
        step_count.append(len(steps))
        if index:
            continue       # names and gaps from the first device
        for name, s, d in lines['ops']:
            if s + d > lo and s < hi:
                op_ns[name] = op_ns.get(name, 0) + d
        for gap in gaps(busy, lo, hi):
            name = attribute(gap, spans)
            gap_ns[name] = gap_ns.get(name, 0) + gap[1] - gap[0]
    if not sum(step_count):
        raise TraceError('no execution of a program named %s* inside the window; '
                         'the trace has: %s' % (step_prefix, sorted(seen_modules)))
    n = len(busy_ns)

    def ranked(table):
        return [[name, ns / 1e9] for name, ns in
                sorted(table.items(), key=lambda kv: -kv[1])]
    return {'window_s': (hi - lo) / 1e9, 'busy_s': sum(busy_ns) / n / 1e9,
            'step_device_s': sum(step_ns) / n / 1e9,
            'step_count': sum(step_count) // n,
            'device_ops': ranked(op_ns), 'idle_gaps': ranked(gap_ns)}


def reduce_dir(trace_dir, step_prefix, window_span):
    found = glob.glob(os.path.join(trace_dir, 'plugins', 'profile', '*', '*.xplane.pb'))
    if len(found) != 1:
        raise TraceError('%d .xplane.pb files under %s, not one'
                         % (len(found), trace_dir))
    return reduce(extract(found[0], (window_span,)), step_prefix, window_span)
