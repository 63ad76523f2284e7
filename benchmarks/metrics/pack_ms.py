"""Mean of the ``pack`` histogram in the window: the packer's host time for
one emitted batch (its ``add`` over the documents that went into the batch and
the rendering of the batch's leaves), inside ``pt/host_batch`` on the pump's
thread.  ``None`` from a program that lacks the stage."""


def read(c):
    hist = c['histograms'].get('pack')
    if hist is None:
        return None
    return 1e3 * hist['sum'] / hist['count'] if hist['count'] > 0 else 0.0
