"""Share of the positions sent to the device in the window that hold no token:
100 x ``packed_pad_tokens`` over ``packed_tokens + packed_pad_tokens`` (the
packing loader's counters).  ``None`` from a program whose loader counts
neither."""


def read(c):
    counters = c['counters']
    if 'packed_pad_tokens' not in counters or 'packed_tokens' not in counters:
        return None
    sent = counters['packed_tokens'] + counters['packed_pad_tokens']
    return 100.0 * counters['packed_pad_tokens'] / sent if sent > 0 else 0.0
