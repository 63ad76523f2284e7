"""Pipelined host→device transfer plane (petastorm_tpu.jax.transfer).

Runs on the CPU backend (8 virtual devices, conftest) with the plane
FORCED on (``transfer=True``) — the same code path drives accelerator
backends, where ``transfer='auto'`` enables it by default.  The core
contract under test: the plane changes WHEN and HOW bytes move, never
WHAT arrives — every path must be bit-identical to ``jax.device_put``
unless narrowing was explicitly opted into.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from petastorm_tpu import make_batch_reader, make_reader
from petastorm_tpu.jax import DataLoader, DeviceInMemDataLoader
from petastorm_tpu.jax.transfer import (KILL_SWITCH, TransferPlane,
                                        plane_enabled)
from petastorm_tpu.parallel import data_parallel_sharding, make_mesh

from test_common import create_test_dataset


@pytest.fixture(scope='module')
def dataset(tmp_path_factory):
    path = tmp_path_factory.mktemp('transferds')
    return create_test_dataset('file://' + str(path), num_rows=64,
                               rows_per_rowgroup=8)


def _tree_equal(a, b):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype, (x.dtype, y.dtype)
        assert np.array_equal(x, y)


# -- policy -------------------------------------------------------------------

def test_plane_enabled_policy(monkeypatch):
    # 'auto' stays off on the CPU backend; True forces; the kill switch
    # beats everything.
    assert jax.default_backend() == 'cpu'
    assert plane_enabled('auto') is False
    assert plane_enabled(True) is True
    assert plane_enabled(False) is False
    assert plane_enabled(None) is False
    monkeypatch.setenv(KILL_SWITCH, '1')
    assert plane_enabled(True) is False
    assert plane_enabled('auto') is False


# -- coalesced slab round-trip ------------------------------------------------

def _mixed_tree(rng):
    return {
        'image': rng.integers(0, 256, (16, 8, 8, 3)).astype(np.uint8),
        'x': rng.standard_normal((16, 4)).astype(np.float32),
        'wide': rng.integers(-2 ** 50, 2 ** 50, (16,)).astype(np.int64),
        'flag': rng.random(16) < 0.5,
        'small': rng.integers(-100, 100, (16,)).astype(np.int8),
        'nested': {'y': rng.standard_normal((16,)).astype(np.float64)},
    }


def _wide_table_tree(rng):
    """The regime coalescing is for: one image column beside 96 narrow
    float columns and a label, 98 puts a batch without the plane."""
    tree = {'image': rng.integers(0, 256, (64, 96, 96, 3)).astype(np.uint8),
            'label': rng.integers(0, 1000, (64,)).astype(np.int64)}
    for i in range(96):
        tree['feat_%02d' % i] = rng.standard_normal((64, 16)).astype(np.float32)
    return tree


@pytest.mark.parametrize('make_tree', [_mixed_tree, _wide_table_tree],
                         ids=['mixed', 'wide_table'])
def test_coalesced_slab_pytree_roundtrip(make_tree):
    """Mixed-dtype nested pytree through pack → one device_put → jitted
    on-device unpack equals jax.device_put bit-for-bit, canonicalization
    included (int64 → int32 under default x64-disabled JAX)."""
    tree = make_tree(np.random.default_rng(0))
    plane = TransferPlane(ring_slots=2)
    _tree_equal(plane.put(tree), jax.device_put(tree))
    diag = plane.metrics.as_dict()
    assert diag['h2d_batches'] == 1
    assert diag['h2d_degraded'] == 0
    assert diag['h2d_bytes_wire'] > 0
    assert diag['h2d_stage_count'] == diag['h2d_dispatch_count'] == 1


def test_ring_cycling_values_never_torn():
    """A 2-slot ring cycled through 16 distinct batches: slot reuse must
    wait for the previous occupant's commit, so no delivered batch may
    ever see a later batch's bytes (the donated-reuse tearing class)."""
    plane = TransferPlane(ring_slots=2)
    batches = []
    for i in range(16):
        tree = {'a': np.full((2048,), i, np.int32),
                'b': np.full((64,), float(i), np.float32)}
        batches.append(plane.put(tree))
    for i, dev in enumerate(batches):
        assert np.array_equal(np.asarray(dev['a']),
                              np.full((2048,), i, np.int32))
        assert np.array_equal(np.asarray(dev['b']),
                              np.full((64,), float(i), np.float32))
    # ring commits observed (every slot reuse lands in h2d_commit)
    assert plane.metrics.as_dict()['h2d_commit_count'] >= 14


# -- narrowing ----------------------------------------------------------------

def test_narrowing_cast_equivalence():
    """'auto' ships f32/f64 as bf16 and casts back on device: the result
    equals the host-side bf16 round-trip reference exactly, uint8 passes
    through untouched, and the wire byte counter shrinks."""
    rng = np.random.default_rng(1)
    f32 = rng.standard_normal((16, 32)).astype(np.float32)
    f64 = rng.standard_normal((16,)).astype(np.float64)
    u8 = rng.integers(0, 256, (16, 16)).astype(np.uint8)
    tree = {'f32': f32, 'f64': f64, 'img': u8}

    plane = TransferPlane(ring_slots=2, wire_dtypes='auto')
    dev = plane.put(tree)
    assert np.asarray(dev['f32']).dtype == np.float32
    np.testing.assert_array_equal(
        np.asarray(dev['f32']),
        f32.astype(jnp.bfloat16).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(dev['f64']),
        # canonical output dtype is f32; the wire is bf16
        f64.astype(jnp.bfloat16).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(dev['img']), u8)

    exact = TransferPlane(ring_slots=2)
    wire_n = plane.metrics.counter('h2d_bytes_wire').value
    exact.put(tree)
    wire_e = exact.metrics.counter('h2d_bytes_wire').value
    assert wire_n < wire_e

    # dict policy: only the named field narrows
    sel = TransferPlane(ring_slots=2, wire_dtypes={'f32': 'bfloat16'})
    dev = sel.put(tree)
    np.testing.assert_array_equal(
        np.asarray(dev['f32']), f32.astype(jnp.bfloat16).astype(np.float32))
    _tree_equal({'f64': dev['f64'], 'img': dev['img']},
                jax.device_put({'f64': f64, 'img': u8}))


def test_wire_dtypes_rejects_garbage():
    with pytest.raises(ValueError):
        TransferPlane(wire_dtypes='yes please')


def test_transfer_kwarg_rejects_stringly_off(dataset):
    """'off'/'false' from a config parse are truthy — a lenient read
    would silently ENABLE the plane the caller meant to disable."""
    reader = make_reader(dataset.url, reader_pool_type='dummy')
    try:
        with pytest.raises(ValueError, match='transfer must be'):
            DataLoader(reader, batch_size=16, transfer='off')
    finally:
        reader.stop()
        reader.join()
    with pytest.raises(ValueError, match='transfer must be'):
        plane_enabled('false')


# -- degrade matrix -----------------------------------------------------------

def test_degrade_matrix_unit():
    plane = TransferPlane(ring_slots=2)
    # unsupported dtype (datetime64) degrades, never raises
    assert plane.put({'t': np.array(['2020-01-01'], 'datetime64[s]'),
                      'x': np.zeros((4,), np.float32)}) is None
    # a single full-width leaf is a no-op coalesce: inline path wins
    assert plane.put({'only': np.zeros((16, 4), np.float32)}) is None
    # zero-size leaves degrade
    assert plane.put({'a': np.zeros((4, 0), np.float32),
                      'b': np.zeros((4,), np.float32)}) is None
    assert plane.metrics.counter('h2d_degraded').value == 3
    # ...but a single NARROWABLE leaf still rides (narrowing pays alone)
    nplane = TransferPlane(ring_slots=2, wire_dtypes='auto')
    assert nplane.put({'only': np.ones((16, 4), np.float32)}) is not None
    # oversized staging slab degrades
    tiny = TransferPlane(ring_slots=2, max_staging_bytes=64)
    assert tiny.put({'a': np.zeros((64,), np.float32),
                     'b': np.zeros((64,), np.float32)}) is None


def test_kill_switch_forces_inline_path(dataset, monkeypatch):
    monkeypatch.setenv(KILL_SWITCH, '1')
    with DataLoader(make_reader(dataset.url, reader_pool_type='dummy',
                                shuffle_row_groups=False),
                    batch_size=16, transfer=True) as loader:
        killed = list(loader)
        assert loader._pump is None and loader._plane is None
    monkeypatch.delenv(KILL_SWITCH)
    with DataLoader(make_reader(dataset.url, reader_pool_type='dummy',
                                shuffle_row_groups=False),
                    batch_size=16, transfer=False) as loader:
        inline = list(loader)
    for a, b in zip(killed, inline):
        _tree_equal(a, b)


def test_unsupported_structure_degrades_transparently(dataset):
    """A batch structure the plane refuses (single full-width leaf) must
    ride the pump's inline fallback bit-identically — the degrade is
    per-structure, invisible to the consumer."""
    def squeeze(batch):
        return {'matrix': batch['matrix']}

    def run(transfer):
        with DataLoader(make_reader(dataset.url, reader_pool_type='dummy',
                                    shuffle_row_groups=False),
                        batch_size=16, transform_fn=squeeze,
                        transfer=transfer) as loader:
            return list(loader), dict(loader.diagnostics)

    plain, _ = run(False)
    pumped, diag = run(True)
    assert diag['h2d_degraded'] == len(pumped)
    assert diag['h2d_batches'] == 0
    for a, b in zip(plain, pumped):
        _tree_equal(a, b)


# -- pumped DataLoader iteration ----------------------------------------------

def _row_source(url, transfer):
    return DataLoader(make_reader(url, reader_pool_type='dummy', seed=7),
                      batch_size=16, shuffling_queue_capacity=24, seed=5,
                      transfer=transfer)


def _batch_source(url, transfer):
    """The DLRM shape: a batch reader's many columns stacked into few
    leaves by ``transform_fn`` on the pump thread."""
    def stack(batch):
        return {'id': batch['id'],
                'dense': np.stack([batch['decimal_like'].astype(np.float32),
                                   batch['id2'].astype(np.float32)], axis=1),
                'matrix': batch['matrix']}

    return DataLoader(make_batch_reader(url, reader_pool_type='dummy', seed=7),
                      batch_size=10, drop_last=False, transform_fn=stack,
                      shuffling_queue_capacity=24, seed=5, transfer=transfer)


def _packed_source(url, transfer):
    from petastorm_tpu.jax import PackedDataLoader
    from test_loader_resume import _SeqReader
    reader = _SeqReader(make_reader(url, reader_pool_type='dummy', seed=7,
                                    num_epochs=1))
    return PackedDataLoader(reader, 'tokens', max_len=16, batch_size=4,
                            drop_last=False, transfer=transfer)


@pytest.mark.parametrize('source', [_row_source, _batch_source, _packed_source],
                         ids=['row', 'batch', 'packed'])
def test_pumped_loader_matches_inline(dataset, source):
    """The same seeded dataset through both iteration paths: the same
    batches, bit for bit and leaf for leaf, in the same order, and on the
    pumped path every one of them through the plane's ring."""
    def run(transfer):
        with source(dataset.url, transfer) as loader:
            return list(loader), dict(loader.diagnostics)

    plain, inline_diag = run(False)
    pumped, diag = run(True)
    assert len(plain) == len(pumped) >= 4
    for a, b in zip(plain, pumped):
        assert set(a) == set(b)
        _tree_equal(a, b)
    assert 'h2d_batches' not in inline_diag  # the plane was never built
    assert diag['h2d_batches'] == len(pumped)
    assert diag['h2d_degraded'] == 0
    assert diag['batches'] == diag['device_put_count'] == len(pumped)


def test_pumped_loader_early_break_tears_down(dataset):
    """Abandoning iteration mid-stream must stop the dispatch thread and
    leave the loader exitable (a training loop breaks out of it)."""
    with DataLoader(make_reader(dataset.url, reader_pool_type='dummy',
                                shuffle_row_groups=False, num_epochs=None),
                    batch_size=16, transfer=True) as loader:
        for i, _ in enumerate(loader):
            if i == 2:
                break
    # the reference survives teardown (so __exit__ could verify the
    # thread really exited before closing the plane) but the thread is
    # gone
    assert loader._pump is not None
    assert not loader._pump.alive


def test_pump_error_propagates_to_consumer(dataset):
    calls = {'n': 0}

    def boom(batch):
        calls['n'] += 1
        if calls['n'] == 3:
            raise RuntimeError('transform died')
        return batch

    with DataLoader(make_reader(dataset.url, reader_pool_type='dummy',
                                shuffle_row_groups=False),
                    batch_size=16, transform_fn=boom,
                    transfer=True) as loader:
        with pytest.raises(RuntimeError, match='transform died'):
            list(loader)


def test_pumped_resume_drains_ring(dataset):
    """state_dict taken mid-stream with the pump running: the paused
    pipeline's prefetched (in-flight ring) batches land in the token's
    ``pending``, the continuation serves the exact remaining rows, and
    the original loader keeps training (checkpoint-then-keep-training)."""
    with DataLoader(make_reader(dataset.url, reader_pool_type='dummy',
                                shuffle_row_groups=False),
                    batch_size=16, transfer=True) as loader:
        it = iter(loader)
        first = [next(it), next(it)]
        state = loader.state_dict()
        kept = list(it)
    # the snapshot drained the ring: prefetched device batches became
    # host 'pending' entries
    assert state['pending'], 'expected in-flight ring batches in the token'
    with DataLoader(make_reader(dataset.url, reader_pool_type='dummy',
                                shuffle_row_groups=False,
                                resume_state=state['reader']),
                    batch_size=16, transfer=True,
                    resume_state=state) as loader2:
        resumed = list(loader2)

    def ids(batches):
        return sorted(int(i) for b in batches for i in np.asarray(b['id']))

    assert ids(resumed) == ids(kept)
    assert ids(first + kept) == sorted(r['id'] for r in dataset.data)


def test_pumped_packed_loader_resume_preserves_tokens(dataset):
    """PackedDataLoader.state_dict holds the pump paused across BOTH the
    base snapshot and the packer-residue read (a resume between them
    would let the dispatch thread double-count pushback rows into the
    packer) — the packed token multiset must survive a pumped resume."""
    from petastorm_tpu.jax import PackedDataLoader
    from test_loader_resume import _SeqReader

    def seqs_of(batches):
        toks = []
        for b in batches:
            t, s = np.asarray(b['tokens']), np.asarray(b['segment_ids'])
            toks.extend(t[s > 0].tolist())
        return sorted(toks)

    def build_loader(resume=None, reader_resume=None):
        reader = _SeqReader(make_reader(
            dataset.url, reader_pool_type='dummy', shuffle_row_groups=False,
            num_epochs=1, resume_state=reader_resume))
        return reader, PackedDataLoader(reader, 'tokens', max_len=16,
                                        rows_per_batch=4, drop_last=False,
                                        transfer=True, resume_state=resume)

    _, loader = build_loader()
    with loader:
        full = seqs_of(list(loader))

    wrapped, loader = build_loader()
    it = iter(loader)
    consumed = [next(it) for _ in range(2)]
    state = loader.state_dict()
    wrapped.stop()
    wrapped.join()

    _, loader2 = build_loader(resume=state, reader_resume=state['reader'])
    with loader2:
        resumed = list(loader2)
    assert seqs_of(consumed + resumed) == full


# -- the other consumer paths -------------------------------------------------

def test_scan_batches_via_plane_matches(dataset):
    def step(carry, batch):
        return carry + batch['matrix'].sum(), batch['id']

    def run(transfer):
        with DataLoader(make_reader(dataset.url, reader_pool_type='dummy',
                                    shuffle_row_groups=False),
                        batch_size=16, transfer=transfer) as loader:
            return [np.asarray(outs) for _, outs in loader.scan_batches(
                step, np.zeros((), np.float32), steps_per_call=2)]

    for a, b in zip(run(False), run(True)):
        np.testing.assert_array_equal(a, b)


def test_device_inmem_materialize_via_plane(dataset):
    def run(transfer):
        with make_reader(dataset.url, reader_pool_type='dummy',
                         num_epochs=1, shuffle_row_groups=False) as reader:
            loader = DeviceInMemDataLoader(reader, batch_size=16,
                                           num_epochs=1, shuffle=False,
                                           transfer=transfer)
            return [np.asarray(b['id']) for b in loader]

    for a, b in zip(run(False), run(True)):
        np.testing.assert_array_equal(a, b)


def test_sharded_parallel_transfer_matches_global_assembly(dataset):
    """With a leading-axis sharding the plane dispatches per-device
    slices concurrently and reassembles via
    make_array_from_single_device_arrays — same values, same sharding as
    the make_array_from_process_local_data path."""
    mesh = make_mesh()
    sharding = data_parallel_sharding(mesh)

    def run(transfer):
        with DataLoader(make_reader(dataset.url, reader_pool_type='dummy',
                                    shuffle_row_groups=False),
                        batch_size=16, sharding=sharding,
                        transfer=transfer) as loader:
            return list(loader), dict(loader.diagnostics)

    plain, _ = run(False)
    sharded, diag = run(True)
    assert diag['h2d_batches'] == len(sharded) > 0
    for a, b in zip(plain, sharded):
        for key in a:
            assert b[key].sharding.is_equivalent_to(a[key].sharding,
                                                    a[key].ndim), key
        _tree_equal(a, b)


# -- telemetry ----------------------------------------------------------------

def test_inline_commit_sampling_populates_h2d_commit(dataset):
    """Satellite: device_put_s times only the async dispatch; the
    periodic block_until_ready sample must feed a separate h2d_commit
    histogram so diagnostics shows dispatch AND commit percentiles."""
    with DataLoader(make_reader(dataset.url, reader_pool_type='dummy',
                                shuffle_row_groups=False),
                    batch_size=16, transfer=False) as loader:
        list(loader)
        diag = loader.diagnostics
    assert diag['h2d_commit_count'] >= 1
    assert diag['h2d_commit_p99_ms'] is not None
    assert diag['device_put_count'] == 4


def test_plane_spans_reach_trace_recorder(dataset):
    from petastorm_tpu.benchmark import TraceRecorder

    recorder = TraceRecorder()
    with DataLoader(make_reader(dataset.url, reader_pool_type='dummy',
                                shuffle_row_groups=False),
                    batch_size=16, transfer=True,
                    trace_recorder=recorder) as loader:
        list(loader)
    names = {e['name'] for e in recorder.events if e.get('ph') == 'X'}
    assert {'h2d/stage', 'h2d/dispatch', 'host_batch'} <= names
    # Plane-handled batches must NOT also record the generic
    # 'device_put' wrapper span: it would enclose h2d/stage, making
    # the 'h2d' link component a superset of 'h2d_stage' so stall
    # attribution could never name staging as the top component.
    assert 'device_put' not in names


def test_attribute_stalls_splits_h2d_staging_from_link():
    """Acceptance: the new spans let attribute_stalls separate the
    staging copy from the link, and a transfer-bound wait names h2d."""
    from petastorm_tpu.telemetry import attribute_stalls

    events = [
        {'name': 'data_wait', 'ph': 'X', 'ts': 0, 'dur': 100},
        {'name': 'h2d/stage', 'ph': 'X', 'ts': 0, 'dur': 20},
        {'name': 'h2d/dispatch', 'ph': 'X', 'ts': 20, 'dur': 10},
        {'name': 'h2d/commit', 'ph': 'X', 'ts': 30, 'dur': 60},
    ]
    breakdown = attribute_stalls(events)
    assert breakdown['pct']['h2d'] == 70.0
    assert breakdown['pct']['h2d_stage'] == 20.0
    assert breakdown['top'] == 'h2d'
