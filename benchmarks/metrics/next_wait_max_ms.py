"""The longest wait of the training thread for its next batch in the window, to
the resolution of the ``next_wait`` histogram: the upper edge of its highest
non-empty log2 bucket (bucket ``i`` counts ``[2**i, 2**(i+1))`` microseconds).
A run that loses seconds in one piece reads it here if the loader's call was
the long one.  ``None`` from a program that lacks the stage."""


def top_edge_ms(hist):
    """Upper edge of the highest non-empty bucket, in ms; 0.0 where empty."""
    for bucket in range(len(hist['counts']) - 1, -1, -1):
        if hist['counts'][bucket] > 0:
            return (2.0 ** (bucket + 1)) / 1e3
    return 0.0


def read(c):
    hist = c['histograms'].get('next_wait')
    if hist is None:
        return None
    return top_edge_ms(hist)
