"""Batches the resident loader fetched from the host inside the window: none
once the tier is warm."""


def read(c):
    if 'residency_host_batches' not in c['counters']:
        return None
    return c['counters']['residency_host_batches']
