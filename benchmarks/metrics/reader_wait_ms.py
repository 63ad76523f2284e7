"""Mean time the loader's pump waited for a host batch: the ``host_batch_s``
counter over the ``host_batch`` histogram's count, in the window."""


def read(c):
    hist = c['histograms'].get('host_batch')
    if not hist or hist['count'] <= 0:
        return None
    return 1e3 * hist['sum'] / hist['count']
