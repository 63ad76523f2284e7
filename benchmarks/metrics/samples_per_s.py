"""All samples whose training step finished inside the window, over the whole
window's seconds (host clock)."""


def read(c):
    return c['samples'] / c['window_s']
