"""Mean of the ``h2d_commit`` histogram in the window: how long a transfer
took to complete where the plane waited for it or sampled it."""


def read(c):
    hist = c['histograms'].get('h2d_commit')
    if not hist or hist['count'] <= 0:
        return None
    return 1e3 * hist['sum'] / hist['count']
