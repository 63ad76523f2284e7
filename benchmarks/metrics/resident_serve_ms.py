"""Mean of the ``resident_serve`` histogram in the window: host time of one warm
batch of the resident loader on the training thread (the tier's checks, the
gather's dispatch, the provenance seal).  ``None`` from a program that lacks
the stage."""


def read(c):
    hist = c['histograms'].get('resident_serve')
    if hist is None:
        return None
    return 1e3 * hist['sum'] / hist['count'] if hist['count'] > 0 else 0.0
