"""Shared host-delivery-plane measurement plumbing.

One implementation of "open the right reader for this dataset and pump
host batches against a deadline", used by the doctor's host-plane
section and ``benchmark.autotune`` — the fallback and row-accounting
rules must not fork between them.
"""

import time

__all__ = ['open_host_reader', 'pump_host_batches']


def open_host_reader(dataset_url, **reader_kwargs):
    """Open ``dataset_url`` for host-plane measurement.

    Petastorm datasets open via ``make_reader(columnar_decode=True)``
    (the fast columnar decode path); plain Parquet falls back to
    ``make_batch_reader``.  Returns ``(reader, info)`` where ``info``
    carries ``kind`` (human label) and ``extra_kwargs`` — the kwargs
    beyond the caller's that REPRODUCE this pipeline (so a measurement's
    recommendation configures what was actually measured).
    """
    from petastorm_tpu import make_batch_reader, make_reader
    from petastorm_tpu.errors import MetadataError

    try:
        reader = make_reader(dataset_url, columnar_decode=True,
                             **reader_kwargs)
        return reader, {'kind': 'make_reader (codec decode)',
                        'extra_kwargs': {'columnar_decode': True}}
    except MetadataError:
        reader = make_batch_reader(dataset_url, **reader_kwargs)
        return reader, {'kind': 'make_batch_reader (plain parquet)',
                        'extra_kwargs': {}}


def pump_host_batches(loader, seconds, warmup_batches=0):
    """Pump ``loader.iter_host_batches()`` until the deadline.

    Returns ``(rows, dt_seconds)`` over the timed window (after
    ``warmup_batches`` absorbing pool spin-up / first row-group read).
    Raises ``ValueError`` when the dataset yields nothing — an empty or
    fully-filtered dataset must be a diagnosis, not a StopIteration
    traceback.
    """
    gen = loader.iter_host_batches()
    for _ in range(warmup_batches):
        if next(gen, None) is None:
            raise ValueError('dataset yielded no host batches (empty, '
                             'fully filtered, or smaller than one batch '
                             'with drop_last)')
    rows = 0
    t0 = time.monotonic()
    deadline = t0 + seconds
    for batch in gen:
        rows += len(next(iter(batch.values())))
        if time.monotonic() >= deadline:
            break
    dt = time.monotonic() - t0
    if rows == 0:
        raise ValueError('dataset yielded no host batches (empty, '
                         'fully filtered, or smaller than one batch '
                         'with drop_last)')
    return rows, dt
