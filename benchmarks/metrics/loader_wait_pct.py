"""``data_wait_pct`` in the cells where it is no end-to-end metric."""


def read(c):
    return 100.0 * c['wait_s'] / c['window_s']
