"""Mean of the loader's own ``next_wait`` histogram in the window: how long the
training thread was blocked in the loader for its next batch, a call, measured
by the loader (``loader_wait_pct`` is the harness's stopwatch around the same
call).  ``None`` from a program that lacks the stage."""


def read(c):
    hist = c['histograms'].get('next_wait')
    if hist is None:
        return None
    return 1e3 * hist['sum'] / hist['count'] if hist['count'] > 0 else 0.0
