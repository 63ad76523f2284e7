"""The longest pause of the whole process seen in the window: the larger of the
highest non-empty log2 buckets of ``process_gc_pause`` (a garbage collection,
which stops every Python thread) and ``process_tick_late`` (how much later than
its interval the flight recorder's thread woke: a thread that only sleeps wakes
late exactly when the process or the GIL stood still).  0.0 where neither saw
anything; ``None`` from a program that has neither."""


def top_edge_ms(hist):
    """Upper edge of the highest non-empty bucket, in ms; 0.0 where empty."""
    for bucket in range(len(hist['counts']) - 1, -1, -1):
        if hist['counts'][bucket] > 0:
            return (2.0 ** (bucket + 1)) / 1e3
    return 0.0


def read(c):
    found = [c['histograms'][name]
             for name in ('process_gc_pause', 'process_tick_late')
             if name in c['histograms']]
    if not found:
        return None
    return max(top_edge_ms(hist) for hist in found)
