"""The readers of the metrics that read the program's own stage counters
(PR 26): each on a synthetic ``context``, on an empty one (the stage exists and
saw nothing: 0.0) and on a program that lacks the stage (``None``: the result
line leaves the metric out, as a parent commit's must)."""

import pytest

import catalog

NEW = {'next_wait_ms', 'next_wait_max_ms', 'host_pause_max_ms', 'rowgroup_read_ms',
       'decode_ms_per_image', 'h2d_stage_ms', 'h2d_sampled_commit_ms',
       'resident_serve_ms'}


def hist(*seconds):
    """A window's delta of a log2-bucket histogram that saw ``seconds``."""
    from petastorm_tpu.telemetry import MetricsRegistry
    h = MetricsRegistry().histogram('h')
    for s in seconds:
        h.observe(s)
    return {'counts': list(h.counts), 'sum': h.sum, 'count': h.count}


def context(histograms=None, counters=None):
    return {'histograms': histograms or {}, 'counters': counters or {},
            'window_s': 20.0, 'trace': None}


#: (metric, the histogram it takes the mean of)
MEANS = [('next_wait_ms', 'next_wait'), ('rowgroup_read_ms', 'reader_rowgroup_read'),
         ('h2d_stage_ms', 'h2d_stage'),
         ('h2d_sampled_commit_ms', 'h2d_commit_sampled'),
         ('resident_serve_ms', 'resident_serve')]


@pytest.mark.parametrize('metric,histogram', MEANS)
def test_a_mean_reader_reads_its_histogram(metric, histogram):
    read = catalog.metric_reader(metric)
    assert read(context({histogram: hist(0.001, 0.003)})) == pytest.approx(2.0)
    assert read(context({histogram: hist()})) == 0.0
    assert read(context({'host_batch': hist(0.5)})) is None


def test_next_wait_max_is_the_upper_edge_of_the_highest_bucket():
    read = catalog.metric_reader('next_wait_max_ms')
    # 2.3 s lies in [2**21, 2**22) us: the edge is 4194.304 ms
    assert read(context({'next_wait': hist(0.0001, 0.001, 2.3)})) \
        == pytest.approx(2 ** 22 / 1e3)
    assert read(context({'next_wait': hist(0.0001)})) == pytest.approx(0.128)
    assert read(context({'next_wait': hist()})) == 0.0
    assert read(context()) is None


def test_host_pause_max_is_the_larger_of_collection_and_late_tick():
    read = catalog.metric_reader('host_pause_max_ms')
    gc, late = 'process_gc_pause', 'process_tick_late'
    assert read(context({gc: hist(0.05), late: hist(0.0002, 3.4)})) \
        == pytest.approx(2 ** 22 / 1e3)
    assert read(context({gc: hist(0.05), late: hist()})) \
        == pytest.approx(2 ** 16 / 1e3)
    assert read(context({gc: hist(), late: hist()})) == 0.0
    assert read(context({late: hist(0.001)})) == pytest.approx(1.024)
    assert read(context({'next_wait': hist(1.0)})) is None


def test_decode_ms_per_image_is_decode_seconds_over_cells():
    read = catalog.metric_reader('decode_ms_per_image')
    assert read(context(counters={'reader_codec_decode_s': 25.0,
                                  'reader_codec_cells': 8320})) \
        == pytest.approx(25e3 / 8320)
    assert read(context(counters={'reader_codec_decode_s': 0.0,
                                  'reader_codec_cells': 0})) == 0.0
    assert read(context(counters={'batches': 3})) is None


def test_the_catalog_finds_all_eight_in_the_cells_that_list_them():
    listed = {row['cell']: set(row['metrics']) for row in catalog.listing()}
    assert NEW - {'resident_serve_ms'} <= listed['resnet50.stream']
    assert listed['resnet50.resident'] & NEW == {
        'next_wait_ms', 'next_wait_max_ms', 'host_pause_max_ms',
        'resident_serve_ms'}
    assert listed['dlrm.stream'] & NEW == NEW - {'resident_serve_ms',
                                                 'decode_ms_per_image'}
    entries = {m['name']: m for m in catalog.benchmark()['per_layer']}
    for name in NEW:
        assert entries[name]['source'] == 'program_counter'
        assert entries[name]['moves'] == 'samples_per_s'
        assert entries[name]['better'] == 'lower' and entries[name]['unit'] == 'ms'


def test_the_loader_snapshot_a_run_takes_holds_what_the_readers_read(tmp_path):
    """On the CPU, the real program: one small streamed loader's
    ``metrics.snapshot()`` (what ``run.py`` takes its window deltas of) has
    every histogram and counter the eight readers look for, bar the resident
    loader's own."""
    import numpy as np

    from petastorm_tpu import make_reader
    from petastorm_tpu.codecs import CompressedImageCodec
    from petastorm_tpu.etl.dataset_metadata import DatasetWriter
    from petastorm_tpu.jax import DataLoader
    from petastorm_tpu.unischema import Unischema, UnischemaField

    schema = Unischema('Tiny', [
        UnischemaField('id', np.int64, (), None, False),
        UnischemaField('image', np.uint8, (8, 8, 3), CompressedImageCodec('png'),
                       False)])
    url = 'file://' + str(tmp_path / 'ds')
    rng = np.random.default_rng(0)
    rows = [{'id': np.int64(i),
             'image': rng.integers(0, 255, (8, 8, 3), dtype=np.uint8)}
            for i in range(32)]
    with DatasetWriter(url, schema, rows_per_rowgroup=8) as writer:
        writer.write_many(rows)
    with DataLoader(make_reader(url, num_epochs=1, columnar_decode=True,
                                reader_pool_type='thread', workers_count=2),
                    batch_size=8, transfer=True) as loader:
        assert len(list(loader)) == 4
        snap = loader.metrics.snapshot()
    c = context(snap['histograms'], snap['counters'])
    for name in NEW - {'resident_serve_ms'}:
        value = catalog.metric_reader(name)(c)
        assert value is not None and value >= 0.0, name
    assert snap['counters']['reader_codec_cells'] == 32
