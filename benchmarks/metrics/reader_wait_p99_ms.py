"""p99 of the ``host_batch`` histogram in the window.  The histogram has log2
buckets over microseconds, so this is the upper edge of the bucket that holds
the 99th percentile (``petastorm_tpu/telemetry/registry.py::hist_quantile``)."""

import math


def read(c):
    hist = c['histograms'].get('host_batch')
    if not hist or hist['count'] <= 0:
        return None
    need, seen = math.ceil(0.99 * hist['count']), 0
    for bucket, n in enumerate(hist['counts']):
        seen += n
        if seen >= need:
            return (2.0 ** (bucket + 1)) / 1e3
    return None
