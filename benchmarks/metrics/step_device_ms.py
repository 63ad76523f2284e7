"""Device time of the step program's executions over the steps traced
(profiler trace, ``XLA Modules`` line of the device's plane)."""


def read(c):
    trace = c['trace']
    if not trace or not trace['step_count']:
        return None
    return 1e3 * trace['step_device_s'] / trace['step_count']
