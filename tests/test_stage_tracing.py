"""The loader's one stage primitive (``telemetry.Stages``) and what it
measures where the work happens (ISSUE 26): the consumer's wait, the
row-group read, the codec decode, the resident serve, process pauses.

* a CPU profile (``jax.profiler``, read back with ``ProfileData``) holds
  every span on the host plane, nested as documented, and no ``pt/*`` span
  on the consuming thread of the pumped path;
* the counters and histograms count what they say, under their names, in
  ``loader.metrics.snapshot()`` (the reader's as ``reader_*``, the
  process's as ``process_*``);
* one pair of clock readings feeds counter, histogram, recorder span and
  provenance window alike, at each of the four former hand-timed sites.
"""

import gc
import glob
import os
import threading
import time

import numpy as np
import pytest

from petastorm_tpu import make_reader
from petastorm_tpu.benchmark import TraceRecorder
from petastorm_tpu.codecs import CompressedImageCodec
from petastorm_tpu.etl.dataset_metadata import DatasetWriter
from petastorm_tpu.jax import DataLoader, ResidentDataLoader
from petastorm_tpu.telemetry import (MetricsRegistry, Stages, flight,
                                     process_registry)
from petastorm_tpu.unischema import Unischema, UnischemaField

ROWS, BATCH, ROWGROUP = 64, 8, 16
N = ROWS // BATCH

ImageSchema = Unischema('ImageSchema', [
    UnischemaField('id', np.int64, (), None, False),
    UnischemaField('image', np.uint8, (8, 8, 3), CompressedImageCodec('png'),
                   False)])


@pytest.fixture(scope='module')
def dataset(tmp_path_factory):
    url = 'file://' + str(tmp_path_factory.mktemp('stage_ds'))
    rng = np.random.default_rng(0)
    with DatasetWriter(url, ImageSchema, rows_per_rowgroup=ROWGROUP) as writer:
        writer.write_many([
            {'id': np.int64(i),
             'image': rng.integers(0, 255, (8, 8, 3), dtype=np.uint8)}
            for i in range(ROWS)])
    return url


def reader_of(url, pool='thread', **kwargs):
    return make_reader(url, num_epochs=1, columnar_decode=True,
                       reader_pool_type=pool, workers_count=2,
                       shuffle_row_groups=False, **kwargs)


# -- (i) the profiler's host plane ---------------------------------------------

def host_lines(tmp_path, body):
    """Run ``body()`` under a CPU profile; the host plane's lines as
    ``[[(name, start_ns, end_ns), ...], ...]``, one list a thread."""
    import jax
    from jax.profiler import ProfileData
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(tmp_path), 'plugins', 'profile', '*',
                                   '*.xplane.pb'))
    assert len(found) == 1
    lines = []
    for plane in ProfileData.from_file(found[0]).planes:
        if plane.name == '/host:CPU':
            for line in plane.lines:
                lines.append([(e.name, e.start_ns, e.start_ns + e.duration_ns)
                              for e in line.events])
    return lines


def named(lines, name):
    return [e for line in lines for e in line if e[0] == name]


def inside(lines, inner, outer):
    """Every ``inner`` span lies within an ``outer`` span of its own line."""
    for line in lines:
        outers = [e for e in line if e[0] == outer]
        for _, start, end in (e for e in line if e[0] == inner):
            if not any(o[1] <= start and end <= o[2] for o in outers):
                return False
    return True


def test_pumped_loader_profile_holds_every_span_where_the_work_is(
        dataset, tmp_path):
    def body():
        with DataLoader(reader_of(dataset), batch_size=BATCH,
                        transfer=True) as loader:
            assert len(list(loader)) == N

    lines = host_lines(tmp_path, body)
    for name, at_least in (('pt/host_batch', N), ('pt/device_put', N),
                           ('ptp/h2d_stage', N), ('ptp/h2d_dispatch', N),
                           ('ptp/h2d_commit', 1), ('ptc/next_wait', N),
                           ('ptw/rowgroup_read', ROWS // ROWGROUP),
                           ('ptw/codec_decode', ROWS // ROWGROUP)):
        assert len(named(lines, name)) >= at_least, name
    for part in ('ptp/h2d_stage', 'ptp/h2d_dispatch', 'ptp/h2d_commit'):
        assert inside(lines, part, 'pt/device_put'), part
    # pt/ holds what may own an idle gap of the device and nothing else: the
    # trace reduction walks every pt/* span for every gap
    assert {e[0] for line in lines for e in line if e[0].startswith('pt/')} \
        <= {'pt/host_batch', 'pt/device_put', 'pt/gc', 'pt/flight_tick'}
    # the consuming thread's line holds its waits and no pt/* span: under
    # pt/ a wait would cover the pump's spans and take their idle gaps
    consumer = [line for line in lines
                if any(e[0] == 'ptc/next_wait' for e in line)]
    assert len(consumer) == 1
    assert not [e for e in consumer[0]
                if e[0].startswith('pt/') and e[0] != 'pt/gc']   # gc: anywhere


def test_resident_loader_profile_holds_serve_gather_gc_and_flight_tick(
        dataset, tmp_path):
    recorder = flight.FlightRecorder(interval_s=3600.0)

    def body():
        with ResidentDataLoader(reader_of(dataset), batch_size=BATCH,
                                num_epochs=2, seed=3) as loader:
            for i, _ in enumerate(loader):
                if i == N + 1:
                    gc.collect()
        recorder.tick()

    lines = host_lines(tmp_path, body)
    assert len(named(lines, 'pt/resident_serve')) == N       # the warm epoch
    assert len(named(lines, 'ptp/resident_gather')) == N
    assert inside(lines, 'ptp/resident_gather', 'pt/resident_serve')
    # the cold epoch streams through the pump: spans where there were none
    assert len(named(lines, 'pt/host_batch')) == N
    assert len(named(lines, 'pt/device_put')) == N
    # a batch each, and the wait that met each epoch's end (a span, no sample)
    assert len(named(lines, 'ptc/next_wait')) == 2 * N + 2
    assert named(lines, 'pt/gc')
    assert named(lines, 'pt/flight_tick')   # ours, and the process's own


def test_jitted_programs_carry_their_names_and_scopes():
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.jax import residency
    from petastorm_tpu.jax.transfer import TransferPlane

    host = {'x': np.arange(32, dtype=np.float32).reshape(8, 4),
            'y': np.arange(8, dtype=np.int32)}
    plan = residency.wire_plan(host, 'auto')
    tier = residency.ResidencyTier(
        plan, 8, 4, None, residency.ensure_counters(MetricsRegistry()))
    wire = {k: jax.device_put(v) for k, v in plan.narrow(host).items()}
    assert tier.admit(np.arange(8), wire) == 'admitted'
    tier.gather(jnp.arange(8), 0)
    gather = tier._gather_program(4).lower(
        tier._slabs, tier._slot_map(), jnp.arange(8), 0).as_text(debug_info=True)
    assert 'jit_pt_residency_gather' in gather
    assert 'pt/residency_gather' in gather and 'pt/residency_widen' in gather
    update = next(iter(tier._write_fns.values())).lower(
        tier._slabs, wire, 0).as_text(debug_info=True)
    assert 'jit_pt_residency_update' in update
    plan.widen(wire)
    assert 'jit_pt_residency_widen' in plan._widen_fn.lower(wire).as_text()

    plane = TransferPlane(ring_slots=2)
    plane.put(host)
    layout, unpack, _ = next(iter(plane._prepared.values()))
    text = unpack.lower(jnp.zeros(layout.slab_nbytes, jnp.uint8)).as_text(
        debug_info=True)
    assert 'jit_pt_h2d_unpack' in text and 'pt/h2d_unpack' in text


# -- (ii) what the counters count ----------------------------------------------

@pytest.mark.parametrize('transfer', [True, False], ids=['pumped', 'inline'])
def test_stream_counters_count_what_they_name(dataset, transfer):
    batches = 5 * N     # five epochs: the 1-in-32 sample fires twice (1, 33)
    with DataLoader(make_reader(dataset, num_epochs=5, columnar_decode=True,
                                reader_pool_type='thread', workers_count=2),
                    batch_size=BATCH, transfer=transfer) as loader:
        assert len(list(loader)) == batches
        snap = loader.metrics.snapshot()
    hists, counters = snap['histograms'], snap['counters']
    assert hists['next_wait']['count'] == batches
    assert counters['next_wait_s'] == pytest.approx(hists['next_wait']['sum'])
    assert hists['host_batch']['count'] == batches
    # the reader pool's own registry, seen through the loader's
    assert counters['reader_codec_cells'] == 5 * ROWS
    assert counters['reader_codec_bytes'] > 0
    assert counters['reader_codec_decode_s'] > 0
    assert hists['reader_codec_decode']['count'] == 5 * ROWS // ROWGROUP
    assert hists['reader_rowgroup_read']['count'] == 5 * ROWS // ROWGROUP
    assert counters['reader_rowgroup_read_s'] \
        == pytest.approx(hists['reader_rowgroup_read']['sum'])
    assert counters['reader_items_processed'] == 5 * ROWS // ROWGROUP
    # the process's
    assert {'process_gc_collections', 'process_gc_pause_s',
            'process_tick_late_s', 'process_flight_tick_s'} <= set(counters)
    assert {'process_gc_pause', 'process_tick_late', 'process_flight_tick'} \
        <= set(hists)
    if transfer:
        assert hists['h2d_stage']['count'] == batches
        assert hists['h2d_dispatch']['count'] == batches
        assert hists['h2d_commit_sampled']['count'] == 2
        # h2d_commit keeps both meanings: ring waits and the samples
        ring_waits = batches - 3        # ring of prefetch + 1 = 3 slots
        assert hists['h2d_commit']['count'] == ring_waits + 2
    else:
        assert 'h2d_commit_sampled' not in hists
        assert hists['h2d_commit']['count'] == 2      # the inline samples
    # the attached registries show in snapshot() alone: the flat views stay
    # the loader's own (tests/test_telemetry.py pins their keys)
    assert not [k for k in loader.metrics.as_dict()
                if k.startswith(('reader_', 'process_'))]


def test_resident_serve_counts_the_warm_batches(dataset):
    with ResidentDataLoader(reader_of(dataset), batch_size=BATCH,
                            num_epochs=3, seed=1) as loader:
        assert len(list(loader)) == 3 * N
        snap = loader.metrics.snapshot()
    hists = snap['histograms']
    assert hists['resident_serve']['count'] == 2 * N
    assert hists['resident_gather']['count'] == 2 * N
    assert hists['resident_gather']['sum'] <= hists['resident_serve']['sum']
    assert hists['next_wait']['count'] == 3 * N
    assert hists['host_batch']['count'] == N          # the cold epoch
    assert snap['counters']['residency_hits'] == 2 * N
    assert snap['counters']['resident_serve_s'] \
        == pytest.approx(hists['resident_serve']['sum'])


def test_dropped_tier_is_no_warm_serve(dataset):
    with ResidentDataLoader(reader_of(dataset), batch_size=BATCH,
                            num_epochs=2, seed=1) as loader:
        for i, _ in enumerate(loader):
            if i == N + 2:
                loader.drop_resident_tier()
        hists = loader.metrics.snapshot()['histograms']
    assert hists['resident_serve']['count'] == 3      # warm until the drop
    assert hists['host_batch']['count'] == N + (N - 3)


def test_arrow_worker_times_its_row_group_reads(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    from petastorm_tpu import make_batch_reader
    path = tmp_path / 'plain'
    path.mkdir()
    pq.write_table(pa.table({'a': np.arange(40), 'b': np.arange(40.0)}),
                   str(path / 'part.parquet'), row_group_size=10)
    with DataLoader(make_batch_reader('file://' + str(path), num_epochs=1,
                                      reader_pool_type='thread',
                                      workers_count=2),
                    batch_size=10) as loader:
        assert len(list(loader)) == 4
        snap = loader.metrics.snapshot()
    assert snap['histograms']['reader_rowgroup_read']['count'] == 4
    assert 'reader_codec_cells' not in snap['counters']   # no codec column


def test_attach_is_by_reference_and_resolved_at_snapshot_time():
    mine, other = MetricsRegistry('mine'), MetricsRegistry('other')
    holder = {'registry': None}
    mine.attach('late_', lambda: holder['registry'])
    mine.attach('o_', other)
    mine.counter('own').inc(2)
    assert set(mine.snapshot()['counters']) == {'own'}
    other.counter('seen').inc(3)
    other.histogram('h').observe(0.001)
    holder['registry'] = other
    snap = mine.snapshot()
    assert snap['counters'] == {'own': 2, 'o_seen': 3, 'late_seen': 3}
    assert snap['histograms']['o_h']['count'] == 1
    assert mine.snapshot(attached=False)['counters'] == {'own': 2}
    other.counter('seen').inc()
    assert mine.snapshot()['counters']['o_seen'] == 4
    # a pickled registry carries its own counts, not its neighbours'
    import pickle
    assert pickle.loads(pickle.dumps(mine)).snapshot()['counters'] == {'own': 2}


# -- (iii) process pauses ------------------------------------------------------

def test_gc_hook_counts_collections_while_a_loader_is_entered(dataset):
    def ours():
        return [cb for cb in gc.callbacks
                if getattr(cb, '__self__', None) is flight._GC_WATCH]

    assert not ours()
    counters = lambda: process_registry().snapshot()['counters']  # noqa: E731
    before = counters()
    outer = DataLoader(reader_of(dataset, pool='dummy'), batch_size=BATCH)
    inner = DataLoader(reader_of(dataset, pool='dummy'), batch_size=BATCH)
    with outer:
        with inner:
            for i, _ in enumerate(outer):
                if i == 2:
                    gc.collect()
            snap = outer.metrics.snapshot()
        assert len(ours()) == 1        # the last loader out takes the hook
        gc.collect()
    assert not ours()
    after = counters()
    assert after['gc_collections'] >= before['gc_collections'] + 2
    assert after['gc_pause_s'] > before['gc_pause_s']
    assert snap['counters']['process_gc_collections'] \
        >= before['gc_collections'] + 1
    assert snap['histograms']['process_gc_pause']['count'] \
        == snap['counters']['process_gc_collections']
    gc.collect()
    assert counters()['gc_collections'] == after['gc_collections']


def test_a_collection_inside_the_process_registrys_lock_does_not_block():
    """``snapshot()`` builds dicts under the registry's lock, so a collection
    can start there: the hook's instruments take no lock."""
    flight.watch_gc()
    try:
        registry = process_registry()
        with registry._lock:
            gc.collect()
    finally:
        flight.unwatch_gc()


def test_a_slow_flight_tick_and_a_late_wake_show_in_the_process_registry():
    hists = lambda: process_registry().snapshot()['histograms']  # noqa: E731
    before = hists()

    def slow_source():
        time.sleep(0.05)
        return MetricsRegistry('src').snapshot()

    recorder = flight.FlightRecorder(interval_s=0.02, source=slow_source)
    assert recorder.tick() is not None
    after = hists()
    assert after['flight_tick']['count'] >= before['flight_tick']['count'] + 1
    assert after['flight_tick']['sum'] - before['flight_tick']['sum'] >= 0.05
    # (the process's own recorder may tick in between: no equality here)
    assert process_registry().counter('flight_tick_s').value \
        >= after['flight_tick']['sum']
    # the thread observes how late it woke, once a wake
    recorder.start()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline and \
            hists()['tick_late']['count'] < before['tick_late']['count'] + 2:
        time.sleep(0.01)
    recorder.stop()
    recorder._thread.join(5.0)
    assert not recorder._thread.is_alive()
    assert hists()['tick_late']['count'] >= before['tick_late']['count'] + 2


# -- (iv) one pair of clock readings -------------------------------------------

def test_stage_primitive_feeds_every_surface_from_one_pair_of_readings():
    metrics, recorder = MetricsRegistry('m'), TraceRecorder()
    stages = Stages(metrics, recorder)
    with stages('work', event='the/work', tag=7) as stage:
        time.sleep(0.002)
    t0, t1 = stage.window
    assert stage.seconds == t1 - t0 >= 0.002
    assert metrics.counter('work_s').value == t1 - t0
    assert metrics.histogram('work').sum == t1 - t0
    assert metrics.histogram('work').count == 1
    (event,) = recorder.events
    assert event['name'] == 'the/work' and event['args'] == {'tag': 7}
    assert event['dur'] == round(1e6 * (t1 - t0), 1)
    # no event without a name for it; nothing at all from a block that is
    # left by an exception or disowned
    with stages('quiet'):
        pass
    with pytest.raises(StopIteration):
        with stages('work', event='the/work'):
            raise StopIteration
    with stages('work', event='the/work') as disowned:
        disowned.keep = False
    assert len(recorder.events) == 1
    assert metrics.histogram('work').count == 1
    assert metrics.histogram('quiet').count == 1


def test_a_sample_can_carry_the_seconds_of_the_blocks_before_it():
    """A stage whose work for one sample comes in several blocks (the packer's,
    one a document): the blocks that close no sample are disowned but can be
    read, and the one that closes it carries their seconds."""
    metrics = MetricsRegistry('m')
    stages = Stages(metrics)
    carried = 0.0
    for closes in (False, False, True):
        with stages('pack', span='ptp/pack') as block:
            block.carried = carried
            time.sleep(0.001)
            block.keep = closes
        carried = 0.0 if closes else carried + block.seconds
    hist = metrics.histogram('pack')
    assert hist.count == 1 and hist.sum >= 0.003
    assert metrics.counter('pack_s').value == hist.sum


def test_stages_are_safe_to_share_between_threads():
    import sys
    metrics = MetricsRegistry('shared')
    stages = Stages(metrics)
    threads, each = 16, 300
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(each):
                with stages('busy'):
                    pass
        pool = [threading.Thread(target=work) for _ in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(30.0)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert metrics.histogram('busy').count == threads * each
    assert metrics.counter('busy_s').value \
        == pytest.approx(metrics.histogram('busy').sum)


def _pumped(url, recorder):
    loader = DataLoader(reader_of(url, pool='dummy'), batch_size=BATCH,
                        transfer=True, trace_recorder=recorder,
                        transform_fn=lambda b: b)
    with loader:
        assert len(list(loader)) == N
    return loader, {'host_batch': 'host_batch', 'transform': 'transform',
                    'h2d_stage': 'h2d/stage', 'h2d_dispatch': 'h2d/dispatch'}


def _inline(url, recorder):
    loader = DataLoader(reader_of(url, pool='dummy'), batch_size=BATCH,
                        transfer=False, trace_recorder=recorder,
                        transform_fn=lambda b: b)
    with loader:
        assert len(list(loader)) == N
    return loader, {'host_batch': 'host_batch', 'transform': 'transform',
                    'h2d_dispatch': 'device_put'}


def _scan(url, recorder):
    import jax.numpy as jnp
    loader = DataLoader(reader_of(url, pool='dummy'), batch_size=BATCH,
                        trace_recorder=recorder)
    with loader:
        chunks = list(loader.scan_batches(
            lambda c, b: (c, jnp.sum(b['id'])), 0, steps_per_call=2,
            donate_carry=False))
    assert len(chunks) == N // 2
    return loader, {'host_batch': 'host_batch'}


def _resident_stream(url, recorder):
    loader = ResidentDataLoader(reader_of(url, pool='dummy'), batch_size=BATCH,
                                num_epochs=1, seed=0, trace_recorder=recorder)
    with loader:
        assert len(list(loader)) == N
    return loader, {'host_batch': 'host_batch', 'h2d_dispatch': 'device_put'}


#: provenance stage -> the loader's histogram of the same readings
HISTOGRAM_OF = {'host_batch': 'host_batch', 'transform': 'transform',
                'h2d_stage': 'h2d_stage', 'h2d_dispatch': 'device_put'}


@pytest.mark.parametrize('site', [_pumped, _inline, _scan, _resident_stream],
                         ids=['iter_pumped', 'iter_inline', 'scan_batches',
                              'resident_stream_one'])
def test_former_call_sites_feed_all_surfaces_from_the_same_readings(
        dataset, site):
    recorder = TraceRecorder()
    loader, stage_events = site(dataset, recorder)
    records = loader.provenance.records()
    assert len(records) == N
    events = {}
    for ev in recorder.events:
        events.setdefault(ev['name'], []).append(ev['dur'])
    for stage, event_name in stage_events.items():
        windows = [r['stages'][stage] for r in records]
        spent = sum(t1 - t0 for t0, t1 in windows)
        histogram = HISTOGRAM_OF[stage]
        if site is _pumped and stage == 'h2d_dispatch':
            histogram = 'h2d_dispatch'      # the plane's own stage there
        # the provenance windows ARE the readings the counter summed, the
        # histogram observed and the recorder drew
        assert loader.metrics.histogram(histogram).sum \
            == pytest.approx(spent, abs=1e-9), stage
        assert loader.metrics.counter(histogram + '_s').value \
            == pytest.approx(spent, abs=1e-9), stage
        assert loader.metrics.histogram(histogram).count == len(windows)
        assert sorted(events[event_name]) \
            == sorted(round(1e6 * (t1 - t0), 1) for t0, t1 in windows), stage
