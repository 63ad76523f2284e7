"""Bytes the transfer plane put on the link in the window, over its seconds."""


def read(c):
    wire = c['counters'].get('h2d_bytes_wire', 0)
    if wire <= 0:
        return None
    return wire / 1e6 / c['window_s']
