"""1 - (union of the intervals with an operation on the device) over the
traced window (profiler trace)."""


def read(c):
    trace = c['trace']
    if not trace or trace['busy_s'] <= 0:
        return None
    return 100.0 * (1.0 - trace['busy_s'] / trace['window_s'])
