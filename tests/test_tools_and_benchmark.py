"""Tools (copy/metadata CLIs) + benchmark harness + stall profiler + DLRM."""

import numpy as np
import pytest

from petastorm_tpu import make_reader
from petastorm_tpu.benchmark import StallMonitor, reader_throughput
from petastorm_tpu.errors import MetadataError
from petastorm_tpu.etl.dataset_metadata import get_schema_from_dataset_url
from petastorm_tpu.etl.petastorm_generate_metadata import generate_petastorm_metadata
from petastorm_tpu.tools.copy_dataset import copy_dataset

from test_common import TestSchema, create_test_dataset


@pytest.fixture(scope='module')
def dataset(tmp_path_factory):
    path = tmp_path_factory.mktemp('toolsds')
    return create_test_dataset('file://' + str(path), num_rows=20, rows_per_rowgroup=5)


def test_copy_dataset_projection_and_filter(dataset, tmp_path):
    target = 'file://' + str(tmp_path / 'copy')
    n = copy_dataset(dataset.url, target, field_regex=['id', 'matrix', 'nullable_scalar'],
                     not_null_fields=['nullable_scalar'], rows_per_rowgroup=4)
    expected = [r for r in dataset.data if r['nullable_scalar'] is not None]
    assert n == len(expected)
    with make_reader(target, reader_pool_type='dummy') as reader:
        rows = list(reader)
    assert set(rows[0]._fields) == {'id', 'matrix', 'nullable_scalar'}
    assert {int(r.id) for r in rows} == {r['id'] for r in expected}


def test_copy_dataset_refuses_overwrite(dataset, tmp_path):
    target = 'file://' + str(tmp_path / 'c2')
    copy_dataset(dataset.url, target, field_regex=['id'])
    with pytest.raises(ValueError, match='overwrite_output'):
        copy_dataset(dataset.url, target, field_regex=['id'])
    copy_dataset(dataset.url, target, field_regex=['id'], overwrite_output=True)


def test_generate_metadata_on_plain_dataset(tmp_path):
    """Stamp petastorm metadata onto externally-written Parquet files."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    pq.write_table(pa.table({'a': [1, 2, 3]}), str(tmp_path / 'f.parquet'))
    url = 'file://' + str(tmp_path)
    with pytest.raises(MetadataError):
        get_schema_from_dataset_url(url)
    schema = generate_petastorm_metadata(url)
    assert 'a' in schema.fields
    assert get_schema_from_dataset_url(url).fields['a'].numpy_dtype == np.dtype('int64')


def test_generate_metadata_with_unischema_class(tmp_path, dataset):
    import shutil
    target = tmp_path / 'cloned'
    shutil.copytree(dataset.path, target)
    (target / '_common_metadata').unlink()
    url = 'file://' + str(target)
    schema = generate_petastorm_metadata(
        url, unischema_class='test_common.TestSchema')
    assert schema == TestSchema
    with make_reader(url, reader_pool_type='dummy') as reader:
        assert len(list(reader)) == 20


def test_metadata_util_prints(dataset, capsys):
    from petastorm_tpu.etl.metadata_util import print_dataset_metadata
    print_dataset_metadata(dataset.url)
    out = capsys.readouterr().out
    assert 'TestSchema' in out and 'Row groups: 4' in out


def test_reader_throughput_harness(dataset):
    result = reader_throughput(dataset.url, warmup_rows=5, measure_rows=10,
                               pool_type='dummy', workers_count=1)
    assert result.rows_read == 10
    assert result.rows_per_second > 0


def test_reader_throughput_multiple_loaders(dataset):
    """loaders_count=N runs N concurrent readers and aggregates rows."""
    result = reader_throughput(dataset.url, warmup_rows=2, measure_rows=10,
                               pool_type='dummy', loaders_count=3)
    assert result.rows_read == 30
    assert result.rows_per_second > 0


def test_reader_throughput_spawn_new_process(dataset):
    """spawn_new_process runs the measurement in a fresh interpreter."""
    result = reader_throughput(dataset.url, warmup_rows=2, measure_rows=8,
                               pool_type='dummy', spawn_new_process=True)
    assert result.rows_read == 8
    assert result.rows_per_second > 0


def test_reader_throughput_rejects_unknown_read_method(dataset):
    """Silently ignored knobs are how benchmarks lie — unknown values raise."""
    with pytest.raises(NotImplementedError, match='read_method'):
        reader_throughput(dataset.url, read_method='batch')


def test_reader_throughput_spawn_rejects_unserializable(dataset):
    with pytest.raises(NotImplementedError, match='JSON-serializable'):
        reader_throughput(dataset.url, spawn_new_process=True,
                          predicate=lambda row: True)


def test_stall_monitor_attribution():
    import time
    monitor = StallMonitor(warmup_steps=0)

    def slow_source():
        for _ in range(5):
            time.sleep(0.02)   # data wait
            yield 1

    for _ in monitor.wrap(slow_source()):
        time.sleep(0.01)       # step
    report = monitor.report()
    assert report['steps'] == 5
    assert report['data_wait_s'] > report['step_s']
    assert 50 < report['stall_pct'] < 85


def test_dlrm_forward_shapes():
    import jax
    import jax.numpy as jnp
    from petastorm_tpu.models.dlrm import DLRM
    model = DLRM(vocab_sizes=[100, 200, 300], embedding_dim=16)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 13)),
                        jnp.zeros((2, 3), jnp.int32))
    out = jax.jit(model.apply)(params, jnp.ones((4, 13)),
                               jnp.ones((4, 3), jnp.int32))
    assert out.shape == (4,)
    assert np.isfinite(np.asarray(out)).all()


class _FakeMonitor:
    def __init__(self, stall_pct, steps=10, step_s=1.0):
        self._r = {'stall_pct': stall_pct, 'steps': steps, 'step_s': step_s,
                   'data_wait_s': 0.0}

    def report(self):
        return dict(self._r)


class _FakeLoader:
    def __init__(self, host=0.0, transform=0.0, put=0.0, batches=10,
                 decode_util=None):
        self.stats = {'host_batch_s': host, 'transform_s': transform,
                      'device_put_s': put, 'batches': batches}
        if decode_util is None:
            self.reader = None
        else:
            class _R:
                diagnostics = {'decode_utilization': decode_util,
                               'pool': 'thread'}
            self.reader = _R()


def test_advisor_regimes():
    from petastorm_tpu.benchmark import diagnose, format_report

    healthy = diagnose(_FakeLoader(host=0.1), _FakeMonitor(1.2))
    assert healthy['regime'] == 'chip_bound'

    decode = diagnose(_FakeLoader(host=5.0, put=0.2, decode_util=0.95),
                      _FakeMonitor(60.0))
    assert decode['regime'] == 'decode_bound'
    assert any('ResizeImages' in s for s in decode['suggestions'])

    io = diagnose(_FakeLoader(host=5.0, put=0.2, decode_util=0.2),
                  _FakeMonitor(60.0))
    assert io['regime'] == 'io_bound'
    assert any('workers_count' in s for s in io['suggestions'])

    transform = diagnose(_FakeLoader(host=0.5, transform=4.0, put=0.2),
                         _FakeMonitor(40.0))
    assert transform['regime'] == 'transform_bound'

    transport = diagnose(_FakeLoader(host=0.5, put=6.0), _FakeMonitor(50.0))
    assert transport['regime'] == 'transport_bound'
    assert any('scan_batches' in s for s in transport['suggestions'])

    empty = diagnose(_FakeLoader(batches=0))
    assert empty['regime'] == 'unknown'
    assert 'pipeline regime' in format_report(transport)


def test_advisor_on_live_loader(tmp_path):
    """End to end: iterate a real loader under a StallMonitor, diagnose."""
    import numpy as np
    from petastorm_tpu import make_reader
    from petastorm_tpu.benchmark import StallMonitor, diagnose
    from petastorm_tpu.jax import DataLoader
    from test_common import create_test_dataset

    create_test_dataset('file://' + str(tmp_path / 'adv'), num_rows=40,
                        rows_per_rowgroup=8)
    monitor = StallMonitor(warmup_steps=1)
    with make_reader('file://' + str(tmp_path / 'adv'),
                     reader_pool_type='dummy',
                     shuffle_row_groups=False) as reader:
        loader = DataLoader(reader, batch_size=8)
        for batch in monitor.wrap(loader):
            np.asarray(batch['id']).sum()
        result = diagnose(loader, monitor)
    assert result['regime'] in ('chip_bound', 'decode_bound', 'io_bound',
                                'transport_bound', 'transform_bound')
    assert result['evidence']['batches'] == 5


def test_doctor_report_over_petastorm_dataset(dataset, capsys):
    """petastorm-tpu-doctor: every applicable section reports, exit code
    reflects section health, --json emits one parseable line."""
    import json as _json

    from petastorm_tpu.tools.doctor import main as doctor_main, run_doctor

    report = run_doctor(dataset_url=dataset.url,
                        sample_seconds=0.5, batch_size=4)
    assert report['backend']['backend'] == 'cpu', report['backend']
    assert report['backend']['device_count'] >= 1
    assert 'loaded' in report['native']
    host = report['host_plane']
    assert 'error' not in host, host
    assert host['reader'].startswith('make_reader')
    assert host['rows'] > 0 and host['rows_per_s'] > 0
    assert 'host_batch_s' in host['stage_seconds']
    # ISSUE 9: the effective dispatch policy + measured decode skew ride
    # the host-plane section (skew >= 8x with idle workers is what
    # scheduling='adaptive' exists for)
    assert host['scheduling'] in ('fifo', 'adaptive')
    assert 'decode_skew_p99_over_p50' in host
    assert 'regime' in report['advisor']
    # the doctor itself gates h2d on the live probe — when present it ran
    if 'h2d' in report:
        assert report['h2d'].get('bytes_per_s') or 'error' in report['h2d']

    rc = doctor_main(['--dataset-url', dataset.url, '--json',
                      '--seconds', '0.5', '--batch-size', '4'])
    line = capsys.readouterr().out.strip().splitlines()[-1]
    parsed = _json.loads(line)
    assert parsed['host_plane']['rows'] > 0
    assert rc in (0, 1)  # 1 only if an environment plane failed


def test_doctor_cache_plane_section(tmp_path):
    """The cache-plane check: tier dirs probed writable, /dev/shm
    headroom reported, crash residue (a dead writer's tmp file) swept."""
    import os

    from petastorm_tpu.tools.doctor import _check_cache_plane

    plane_dir = str(tmp_path / 'plane')
    os.makedirs(plane_dir)
    # fake crash residue: a tmp file stamped with a certainly-dead pid
    open(os.path.join(plane_dir, '.tmp.999999999.dead'), 'w').close()
    out = _check_cache_plane(plane_dir)
    assert out['disk_tier_writable'] is True
    assert out['disk_tier_entries'] == 0
    assert out['swept_tmp_files'] == 1
    assert not [f for f in os.listdir(plane_dir) if f.startswith('.tmp.')]
    # without a dir the host-level half still reports
    host_only = _check_cache_plane(None)
    assert 'shm_free_bytes' in host_only or 'shm_note' in host_only


def test_doctor_plain_parquet_and_human_format(tmp_path, capsys):
    import pyarrow as pa
    import pyarrow.parquet as pq

    from petastorm_tpu.tools.doctor import main as doctor_main

    pq.write_table(pa.table({'x': np.arange(64, dtype=np.int64)}),
                   str(tmp_path / 'plain.parquet'))
    rc = doctor_main(['--dataset-url', 'file://' + str(tmp_path),
                      '--seconds', '0.5', '--batch-size', '8'])
    out = capsys.readouterr().out
    assert 'host_plane' in out and 'make_batch_reader' in out
    assert rc in (0, 1)


def test_check_reference_empty_and_populated(tmp_path, capsys):
    """SURVEY §0 protocol tool: exit 2 on the (current) empty mount; on a
    populated tree it locates anchors, verifies footer-key byte-identity,
    diffs the make_reader kwarg surface, and writes the report."""
    from petastorm_tpu.tools.check_reference import main as check_main

    empty = tmp_path / 'empty_ref'
    empty.mkdir()
    assert check_main(['--reference-root', str(empty)]) == 2

    ref = tmp_path / 'ref'
    (ref / 'petastorm' / 'etl').mkdir(parents=True)
    (ref / 'petastorm' / 'reader.py').write_text(
        "def make_reader(dataset_url, schema_fields=None, "
        "reader_pool_type='thread', workers_count=10, cur_shard=None, "
        "shard_count=None, frobnicate_rows=False):\n    pass\n"
        "def make_batch_reader(dataset_url):\n    pass\n")
    (ref / 'petastorm' / 'etl' / 'dataset_metadata.py').write_text(
        "UNISCHEMA_KEY = b'dataset-toolkit.unischema.v1'\n"
        "ROW_GROUPS_PER_FILE_KEY = "
        "b'dataset-toolkit.num_row_groups_per_file.v1'\n"
        "def materialize_dataset():\n    pass\n")
    report = tmp_path / 'check.md'
    rc = check_main(['--reference-root', str(ref),
                     '--report', str(report)])
    assert rc == 1  # populated WITH discrepancies (missing anchors)
    text = report.read_text()
    # found anchors check off; absent ones flag as MISSING
    assert '- [x] `def make_reader`' in text
    assert 'MISSING' in text and 'class NGram' in text
    # byte-identical footer keys verified
    assert '- [x] `UNISCHEMA_KEY` = `dataset-toolkit.unischema.v1`' in text
    # a reference kwarg we don't accept is surfaced as a parity gap
    assert 'frobnicate_rows' in text
    capsys.readouterr()


def test_autotune_recommends_fastest_config(dataset):
    """benchmark.autotune: measures the host plane under a workers grid
    and recommends make_reader kwargs matching its fastest measurement."""
    from petastorm_tpu.benchmark import autotune

    result = autotune(dataset.url, batch_size=4, seconds_per_config=0.3,
                      workers_grid=(1, 2))
    ms = result['measurements']
    assert len(ms) == 2
    assert all(m['rows_per_s'] > 0 for m in ms)
    assert ms[0]['rows_per_s'] >= ms[1]['rows_per_s']  # fastest first
    rec = result['recommendation']
    assert rec['workers_count'] == ms[0]['workers_count']
    assert rec['reader_pool_type'] == ms[0]['pool']
    # the recommendation is directly usable as make_reader kwargs
    with make_reader(dataset.url, num_epochs=1, **rec) as reader:
        assert sum(1 for _ in reader) > 0


def test_doctor_autotune_section(dataset, capsys):
    import json as _json

    from petastorm_tpu.tools.doctor import main as doctor_main

    rc = doctor_main(['--dataset-url', dataset.url, '--json',
                      '--seconds', '0.6', '--batch-size', '4',
                      '--autotune'])
    line = capsys.readouterr().out.strip().splitlines()[-1]
    parsed = _json.loads(line)
    assert 'recommendation' in parsed['autotune']
    assert rc in (0, 1)


def test_pack_dataset_tool_roundtrip(tmp_path):
    """petastorm-tpu-pack-dataset: variable-length docs -> fixed-shape
    packed petastorm dataset.  Every input token appears exactly once in
    the output with consistent segment/position bookkeeping, the written
    dataset reads back through plain make_reader with static shapes, and
    next_token_targets composes (labels never cross packing boundaries)."""
    from petastorm_tpu.codecs import NdarrayCodec
    from petastorm_tpu.etl.dataset_metadata import write_dataset
    from petastorm_tpu.jax.packing import next_token_targets
    from petastorm_tpu.tools.pack_dataset import main as pack_main, pack_dataset
    from petastorm_tpu.unischema import Unischema, UnischemaField

    src = 'file://' + str(tmp_path / 'docs')
    out = 'file://' + str(tmp_path / 'packed')
    rng = np.random.default_rng(3)
    schema = Unischema('Docs', [
        UnischemaField('tokens', np.int32, (None,), NdarrayCodec(), False),
    ])
    docs = [rng.integers(1, 90, rng.integers(3, 14)).astype(np.int32)
            for _ in range(37)]
    write_dataset(schema, [{'tokens': d} for d in docs], src,
                  rows_per_rowgroup=8)

    stats = pack_dataset(src, out, field='tokens', max_len=16,
                         rows_per_batch=4)
    assert stats['sequences_in'] == 37
    assert stats['tokens_in'] == sum(len(d) for d in docs)
    assert 0.5 < stats['packing_efficiency'] <= 1.0

    with make_reader(out, reader_pool_type='dummy', num_epochs=1,
                     shuffle_row_groups=False) as reader:
        rows = list(reader)
    assert len(rows) == stats['rows_out']
    # no all-pad filler rows may be baked into the offline dataset
    assert all(int(np.asarray(r.segment_ids).max()) > 0 for r in rows)
    seen = []
    for row in rows:
        assert row.tokens.shape == (16,)
        assert row.segment_ids.shape == (16,)
        for seg in range(1, int(row.segment_ids.max()) + 1):
            mask = row.segment_ids == seg
            seen.append(row.tokens[mask].tolist())
            # positions restart per segment
            np.testing.assert_array_equal(row.positions[mask],
                                          np.arange(mask.sum()))
        assert (row.tokens[row.segment_ids == 0] == 0).all()
        # LM labels derived from packed rows stay within segments
        targets, weights = next_token_targets(row.tokens[None],
                                              row.segment_ids[None])
        assert targets.shape == (1, 16) and weights.shape == (1, 16)
    # every document appears exactly once (packing is a permutation)
    assert sorted(map(tuple, seen)) == sorted(map(tuple, (d.tolist() for d in docs)))

    # CLI form over a fresh output
    rc = pack_main([src, 'file://' + str(tmp_path / 'packed2'),
                    '--field', 'tokens', '--max-len', '16'])
    assert rc == 0

    # oversized sequence -> the packer's named refusal propagates
    write_dataset(schema, [{'tokens': np.arange(99, dtype=np.int32)}],
                  'file://' + str(tmp_path / 'big'), rows_per_rowgroup=4)
    with pytest.raises(ValueError, match='exceeds'):
        pack_dataset('file://' + str(tmp_path / 'big'),
                     'file://' + str(tmp_path / 'packed3'),
                     field='tokens', max_len=16)
