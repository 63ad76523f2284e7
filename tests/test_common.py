"""Shared synthetic-dataset fixtures.

Modeled on the reference's ``petastorm/tests/test_common.py ::
create_test_dataset, TestSchema`` — the most load-bearing test asset — but
Spark-free: ground-truth rows are generated in memory and written with the
pyarrow ``DatasetWriter``.
"""

from collections import namedtuple

import numpy as np

from petastorm_tpu.codecs import (CompressedImageCodec, CompressedNdarrayCodec,
                                  NdarrayCodec, ScalarCodec)
from petastorm_tpu.etl.dataset_metadata import DatasetWriter
from petastorm_tpu.unischema import Unischema, UnischemaField

SyntheticDataset = namedtuple('SyntheticDataset', ['url', 'path', 'data'])

TestSchema = Unischema('TestSchema', [
    UnischemaField('id', np.int64, (), None, False),
    UnischemaField('id2', np.int32, (), None, False),
    UnischemaField('image_png', np.uint8, (16, 32, 3), CompressedImageCodec('png'), False),
    UnischemaField('matrix', np.float32, (8, 4), NdarrayCodec(), False),
    UnischemaField('decimal_like', np.float64, (), None, False),
    UnischemaField('embedding', np.float32, (32,), CompressedNdarrayCodec(), False),
    UnischemaField('sensor_name', np.str_, (), ScalarCodec(np.str_), False),
    UnischemaField('nullable_scalar', np.float64, (), None, True),
])


def make_test_rows(num_rows, seed=0):
    rng = np.random.default_rng(seed)
    rows = []
    for i in range(num_rows):
        rows.append({
            'id': np.int64(i),
            'id2': np.int32(i % 5),
            'image_png': rng.integers(0, 255, (16, 32, 3), dtype=np.uint8),
            'matrix': rng.standard_normal((8, 4)).astype(np.float32),
            'decimal_like': float(i) / 3.0,
            'embedding': rng.standard_normal(32).astype(np.float32),
            'sensor_name': 'sensor_%d' % (i % 3),
            'nullable_scalar': None if i % 4 == 0 else float(i),
        })
    return rows


def create_test_dataset(url, num_rows=30, rows_per_rowgroup=5, seed=0, schema=TestSchema):
    """Write a synthetic petastorm-format dataset; return ground truth."""
    rows = make_test_rows(num_rows, seed=seed)
    with DatasetWriter(url, schema, rows_per_rowgroup=rows_per_rowgroup) as writer:
        writer.write_many(rows)
    path = url[len('file://'):] if url.startswith('file://') else url
    return SyntheticDataset(url=url, path=path, data=rows)


def assert_rows_equal(actual_rows, expected_rows, id_field='id'):
    """Order-insensitive equality between decoded rows and ground truth."""
    actual = {int(r[id_field] if isinstance(r, dict) else getattr(r, id_field)): r
              for r in actual_rows}
    expected = {int(r[id_field]): r for r in expected_rows}
    assert set(actual) == set(expected), \
        'row id mismatch: extra=%s missing=%s' % (sorted(set(actual) - set(expected))[:5],
                                                  sorted(set(expected) - set(actual))[:5])
    for key, exp in expected.items():
        act = actual[key]
        for field, value in exp.items():
            got = act[field] if isinstance(act, dict) else getattr(act, field)
            if value is None:
                assert got is None or (isinstance(got, float) and np.isnan(got)), \
                    'field %r of row %d: expected None, got %r' % (field, key, got)
            elif isinstance(value, np.ndarray):
                np.testing.assert_array_equal(got, value, err_msg='field %r row %d' % (field, key))
            else:
                assert got == value, 'field %r of row %d: %r != %r' % (field, key, got, value)


def shm_residue(prefix=None):
    """Current shm-plane entries in ``/dev/shm`` (one helper for every
    suite's zero-residue lifecycle assertion — the segment naming scheme
    must not be duplicated across test files)."""
    import os

    from petastorm_tpu.workers_pool import shm_plane

    prefix = prefix or shm_plane.PREFIX
    return {f for f in os.listdir(shm_plane.SHM_DIR)
            if f.startswith(prefix)}


def assert_iteration_path(loader, transfer):
    """``loader`` took the iteration path the ``transfer`` fixture (conftest)
    asked for: pumped, every batch crossed the transfer plane's ring and none
    fell back to an inline put; inline, the plane carried nothing."""
    counters = loader.metrics.as_dict()
    if transfer:
        assert counters.get('h2d_batches', 0) > 0, counters
        assert counters.get('h2d_degraded', 0) == 0, counters
    else:
        assert counters.get('h2d_batches', 0) == 0, counters
