"""Mean of the reader pool's ``rowgroup_read`` histogram in the window: one
Parquet ``read_row_group`` (read + decompress) on a worker thread, apart from
the codec decode that follows it.  ``None`` from a program that lacks the
stage."""


def read(c):
    hist = c['histograms'].get('reader_rowgroup_read')
    if hist is None:
        return None
    return 1e3 * hist['sum'] / hist['count'] if hist['count'] > 0 else 0.0
