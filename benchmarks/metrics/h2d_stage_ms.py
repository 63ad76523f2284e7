"""Mean of the ``h2d_stage`` histogram in the window: the transfer plane's
host copy of one batch into its staging slab, the first part of
``pt/device_put``."""


def read(c):
    hist = c['histograms'].get('h2d_stage')
    if hist is None:
        return None
    return 1e3 * hist['sum'] / hist['count'] if hist['count'] > 0 else 0.0
