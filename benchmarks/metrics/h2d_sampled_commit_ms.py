"""Mean of the ``h2d_commit_sampled`` histogram in the window: the 1-in-32 full
samples alone, dispatch to device-ready of the batch just put.
(``h2d_commit_ms`` mixes them with the residual waits at ring-slot reuse.)
``None`` from a program that lacks the histogram."""


def read(c):
    hist = c['histograms'].get('h2d_commit_sampled')
    if hist is None:
        return None
    return 1e3 * hist['sum'] / hist['count'] if hist['count'] > 0 else 0.0
