"""90th percentile, over all steps of the window, of the wall time from one
step's completion to the next (host clock)."""

import math


def read(c):
    ordered = sorted(c['step_s'])
    return 1e3 * ordered[min(len(ordered), math.ceil(0.9 * len(ordered))) - 1]
