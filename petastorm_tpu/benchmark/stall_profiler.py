"""Step-time data-stall profiler — the BASELINE.json headline metric.

The reference has no equivalent (SURVEY.md §5.1 gap); the north-star target
is **<= 2% step-time data-stall** for ImageNet-Parquet -> ResNet-50.  The
monitor wraps any batch iterator and attributes wall time to "waiting for
data" (inside ``__next__``) versus "step" (between yields):

    monitor = StallMonitor()
    for batch in monitor.wrap(loader):
        train_step(batch)            # counted as step time
    print(monitor.report())          # {'stall_pct': ..., ...}

With JAX async dispatch the *device* is only truly stalled when ``__next__``
blocks, which is exactly what this measures.  Optional
``jax.profiler.TraceAnnotation`` spans make the stalls visible in TensorBoard
profiles (enabled when ``annotate=True``).
"""

import time


class StallMonitor(object):
    def __init__(self, annotate=False, warmup_steps=1, trace_recorder=None):
        self._annotate = annotate
        self._warmup_steps = warmup_steps
        #: optional ``benchmark.TraceRecorder``: every wait/step pair is
        #: also recorded as chrome-trace spans (``data_wait`` / ``step``),
        #: composing with the loader's spans into one host timeline.
        self._trace = trace_recorder
        self.reset()

    def reset(self):
        self.wait_time = 0.0
        self.step_time = 0.0
        self.steps = 0
        self._skipped = 0

    def wrap(self, iterable):
        annotation = None
        if self._annotate:
            from jax.profiler import TraceAnnotation
            annotation = TraceAnnotation
        iterator = iter(iterable)
        while True:
            wait_start = time.monotonic()
            try:
                if annotation is not None:
                    with annotation('petastorm_tpu.data_wait'):
                        batch = next(iterator)
                else:
                    batch = next(iterator)
            except StopIteration:
                return
            wait_end = time.monotonic()
            yield batch
            step_end = time.monotonic()
            warmup = self._skipped < self._warmup_steps
            if self._trace is not None:
                # Warmup pairs stay ON the timeline but under their own
                # names: stall_breakdown attributes only 'data_wait'
                # windows, so it covers exactly the population stall_pct
                # counts — pipeline-fill/compile waits must not name the
                # compact line's top component.
                suffix = '_warmup' if warmup else ''
                self._trace.event('data_wait' + suffix, wait_start, wait_end)
                self._trace.event('step' + suffix, wait_end, step_end)
            if warmup:
                # First pulls pay pipeline fill + compile; not steady state.
                self._skipped += 1
                continue
            self.wait_time += wait_end - wait_start
            self.step_time += step_end - wait_end
            self.steps += 1

    @property
    def stall_fraction(self):
        total = self.wait_time + self.step_time
        return (self.wait_time / total) if total > 0 else 0.0

    def stall_breakdown(self):
        """Attribute the recorded ``data_wait`` time to pipeline
        components (lease-wait / decode / IPC / cache-fill / H2D) from
        the attached recorder's spans — including any worker spans merged
        cross-process (ISSUE 5).  None without a recorder or waits."""
        if self._trace is None:
            return None
        from petastorm_tpu.telemetry import attribute_stalls
        return attribute_stalls(self._trace.events)

    def report(self):
        out = {
            'steps': self.steps,
            'data_wait_s': round(self.wait_time, 4),
            'step_s': round(self.step_time, 4),
            'stall_pct': round(100.0 * self.stall_fraction, 2),
        }
        breakdown = self.stall_breakdown()
        if breakdown:
            out['stall_breakdown'] = breakdown['pct']
            out['stall_top_component'] = '%s:%.0f%%' % (
                breakdown['top'], breakdown['pct'][breakdown['top']])
        return out
