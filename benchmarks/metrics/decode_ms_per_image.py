"""Seconds the reader's workers spent decoding codec columns in the window
(``reader_codec_decode_s``, timed once a column of a row group) over the cells
they decoded (``reader_codec_cells``): the decode time of one image out of
``native/``, on one worker thread.  ``None`` from a program that lacks the
counters."""


def read(c):
    counters = c['counters']
    if 'reader_codec_decode_s' not in counters \
            or 'reader_codec_cells' not in counters:
        return None
    cells = counters['reader_codec_cells']
    return 1e3 * counters['reader_codec_decode_s'] / cells if cells > 0 else 0.0
