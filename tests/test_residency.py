"""Device-resident data plane (petastorm_tpu/jax/residency.py, ISSUE 17):
wire-plan narrowing/widening, the residency LRU tier, the epoch-keyed
shuffle contract, and ResidentDataLoader end to end (streamed epoch 0 ->
warm resident epochs, kill switch, budget pressure, mid-epoch tier drop,
resume tokens).

Runs on the CPU backend (conftest): buffer donation is a no-op there, but
the admission / gather / eviction code paths are identical to the
accelerator ones.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from petastorm_tpu import make_reader
from petastorm_tpu.jax import ResidentDataLoader, residency
from petastorm_tpu.telemetry import MetricsRegistry

from test_common import create_test_dataset


@pytest.fixture(scope='module')
def dataset(tmp_path_factory):
    path = tmp_path_factory.mktemp('resds')
    return create_test_dataset('file://' + str(path), num_rows=64,
                               rows_per_rowgroup=8)


def _tree():
    return {'image': (np.arange(12 * 8, dtype=np.int64) % 251)
            .astype(np.uint8).reshape(12, 8),
            'feat': np.linspace(-2.0, 2.0, 12 * 4,
                                dtype=np.float32).reshape(12, 4),
            'id': np.arange(12, dtype=np.int64)}


def _counters():
    return residency.ensure_counters(MetricsRegistry('test_residency'))


# ---------------------------------------------------------------------------
# Wire plan: narrow on host, widen in step
# ---------------------------------------------------------------------------

def test_widen_uint8_and_int_exact():
    tree = _tree()
    plan = residency.wire_plan(tree, 'auto')
    assert plan is not None and plan.narrowed
    out = plan.widen({k: jax.device_put(v)
                      for k, v in plan.narrow(tree).items()})
    np.testing.assert_array_equal(np.asarray(out['image']), tree['image'])
    # int64 canonicalizes to int32 (standard x64-disabled JAX), exactly.
    np.testing.assert_array_equal(np.asarray(out['id']),
                                  tree['id'].astype(np.int32))
    assert out['image'].dtype == jnp.uint8


def test_widen_bf16_error_bounded():
    tree = _tree()
    plan = residency.wire_plan(tree, 'auto')
    assert plan.fields['feat'].wire == np.dtype(jnp.bfloat16)
    out = plan.widen({k: jax.device_put(v)
                      for k, v in plan.narrow(tree).items()})
    feat = np.asarray(out['feat'])
    assert feat.dtype == np.float32
    # bf16 keeps 8 significand bits: relative error <= 2^-8.
    err = np.max(np.abs(feat - tree['feat'])
                 / np.maximum(np.abs(tree['feat']), 1e-6))
    assert err <= 1.0 / 256.0
    # ...and widening is NOT the identity (the narrowing really happened).
    assert np.abs(feat - tree['feat']).max() > 0


def test_wire_plan_unsupported_dtype_degrades_to_none():
    tree = {'ok': np.zeros((4, 2), np.float32),
            'when': np.zeros(4, dtype='datetime64[s]')}
    assert residency.wire_plan(tree, 'auto') is None
    assert residency.wire_plan({}, 'auto') is None


def test_wire_plan_no_policy_is_passthrough():
    plan = residency.wire_plan(_tree(), None)
    assert plan is not None and not plan.narrowed
    wire = {k: jax.device_put(v)
            for k, v in plan.narrow(_tree()).items()}
    assert plan.widen(wire) is wire  # identity, no jit


def test_estimate_budget_math():
    est = residency.estimate_budget(_tree(), 'auto')
    # image 8 u8 + feat 4x(4->2) + id (8->4): wire 8+8+4=20, logical
    # against canonical dtypes 8+16+4=28.
    assert est['wire_bytes_per_row'] == 20
    assert est['logical_bytes_per_row'] == 28
    assert est['narrowed'] and 1.0 < est['hbm_ratio'] < 2.0


# ---------------------------------------------------------------------------
# Epoch-keyed shuffle
# ---------------------------------------------------------------------------

def test_epoch_permutation_is_pure_function_of_seed_and_epoch():
    a = np.asarray(residency.epoch_permutation(7, 3, 32))
    b = np.asarray(residency.epoch_permutation(7, 3, 32))
    np.testing.assert_array_equal(a, b)
    assert sorted(a.tolist()) == list(range(32))
    assert not np.array_equal(
        a, np.asarray(residency.epoch_permutation(7, 4, 32)))
    assert not np.array_equal(
        a, np.asarray(residency.epoch_permutation(8, 3, 32)))


# ---------------------------------------------------------------------------
# Residency LRU tier
# ---------------------------------------------------------------------------

def _admit(tier, plan, tree, start, rows):
    ids = np.arange(start, start + rows)
    wire = plan.narrow({k: v[start:start + rows] for k, v in tree.items()})
    return tier.admit(ids, {k: jax.device_put(v) for k, v in wire.items()})


def test_tier_admit_gather_roundtrip():
    tree = _tree()
    plan = residency.wire_plan(tree, 'auto')
    tier = residency.ResidencyTier(plan, 12, 4, None, _counters())
    for start in (0, 4, 8):
        assert _admit(tier, plan, tree, start, 4) == 'admitted'
    assert tier.fully_resident and tier.serving_ok()
    order = residency.epoch_permutation(0, 1, 12)
    onp = np.asarray(order)
    batch = tier.gather(order, 4)
    np.testing.assert_array_equal(np.asarray(batch['image']),
                                  tree['image'][onp[4:8]])
    np.testing.assert_array_equal(np.asarray(batch['id']),
                                  tree['id'][onp[4:8]].astype(np.int32))


# How a row lies in its slab follows its elements (ISSUE 27): up to
# ``_TAKE_MAX_ROW_ELEMS`` flat and read with ``take``, beyond it as
# ``(ceil(elems / 128), 128)`` and read one copy a row.  Every case is served
# beside a 1-D field, and must deliver what streaming delivers.
ROW_CASES = {
    'wide_uint8': (np.uint8, (96, 128, 3), 1),          # 36,864 = 288 x 128
    'wide_uint8_ragged_lanes': (np.uint8, (181, 61, 3), 1),   # 33,123
    'wide_float32_as_bf16': (np.float32, (129, 256), 1),      # 33,024
    'widest_narrow_uint8': (np.uint8, (256, 128), 0),   # 32,768: still take
    'narrow_uint8_odd_bytes': (np.uint8, (7, 5, 3), 0),       # 105 bytes
    'narrow_float32_as_bf16': (np.float32, (13,), 0),
    'narrow_int32': (np.int32, (26,), 0),
    'one_d_only': (np.int64, (), 0),
}


def _case_tree(case, n):
    dtype, row_shape, _ = ROW_CASES[case]
    rng = np.random.default_rng(n)
    if np.dtype(dtype).kind == 'f':
        field = rng.standard_normal((n,) + row_shape).astype(dtype)
    else:
        field = rng.integers(0, 200, (n,) + row_shape).astype(dtype)
    return {'field': field, 'id': np.arange(n, dtype=np.int64)}


def _streamed(plan, tree, idx):
    """What the streamed path delivers for these rows: widen(narrow(rows))."""
    wire = plan.narrow({k: v[idx] for k, v in tree.items()})
    return {k: np.asarray(v) for k, v in
            plan.widen({k: jax.device_put(v) for k, v in wire.items()}).items()}


def _assert_delivery(got, want):
    _assert_same([{k: np.asarray(v) for k, v in got.items()}], [want])


@pytest.mark.parametrize('case', sorted(ROW_CASES))
def test_warm_gather_is_bit_identical_to_streamed(case):
    n, bs = 22, 4                      # five full batches and a tail of two
    tree = _case_tree(case, n)
    plan = residency.wire_plan(tree, 'auto')
    c = _counters()
    tier = residency.ResidencyTier(plan, n, bs, None, c)
    assert int(c.rowcopy_fields.value) == ROW_CASES[case][2]
    for start in range(0, n, bs):
        assert _admit(tier, plan, tree, start, min(bs, n - start)) == 'admitted'
    assert tier.serving_ok()
    wide = tier._slabs['field'].ndim == 3
    assert wide == bool(ROW_CASES[case][2])
    assert tier._slabs['field'].shape[0] == n and tier._slabs['id'].shape == (n,)
    order = residency.epoch_permutation(3, 1, n)
    onp = np.asarray(order)
    for start in (0, 8, 16):
        _assert_delivery(tier.gather(order, start),
                         _streamed(plan, tree, onp[start:start + bs]))
    _assert_delivery(tier.gather_tail(order, 20), _streamed(plan, tree, onp[20:]))
    assert int(c.hits.value) == 4


@pytest.mark.parametrize('case', sorted(ROW_CASES))
def test_gather_after_eviction_and_readmission_into_a_freed_range(case):
    n, bs = 12, 4
    tree = _case_tree(case, n)
    plan = residency.wire_plan(tree, 'auto')
    # what a row takes in the slabs (a wide row's lanes are padded)
    stored = residency.ResidencyTier(plan, n, bs, None, _counters())._row_bytes
    assert plan.wire_row_nbytes <= stored < plan.wire_row_nbytes + 128 * 4
    tier = residency.ResidencyTier(plan, n, bs, 8 * stored, _counters())
    assert tier.capacity_rows == 8
    assert _admit(tier, plan, tree, 0, 4) == 'admitted'
    assert _admit(tier, plan, tree, 4, 4) == 'admitted'
    assert _admit(tier, plan, tree, 8, 4) == 'evicted'     # into rows 0-3's range
    assert sorted(tier._slot_of_row[8:12]) == [0, 1, 2, 3]
    order = jnp.asarray([9, 4, 11, 7, 8, 6, 10, 5], jnp.int32)
    for start in (0, 4):
        _assert_delivery(tier.gather(order, start),
                         _streamed(plan, tree, np.asarray(order)[start:start + 4]))


def test_tier_lru_eviction_under_tight_budget():
    tree = _tree()
    plan = residency.wire_plan(tree, 'auto')
    c = _counters()
    # Budget for exactly 8 of the 12 rows: two 4-row entries fit, the
    # third admission must displace the LRU (oldest) entry.
    tier = residency.ResidencyTier(plan, 12, 4,
                                   8 * plan.wire_row_nbytes, c)
    assert tier.capacity_rows == 8 and not tier.can_hold_dataset
    assert _admit(tier, plan, tree, 0, 4) == 'admitted'
    assert _admit(tier, plan, tree, 4, 4) == 'admitted'
    assert _admit(tier, plan, tree, 8, 4) == 'evicted'
    assert int(c.admitted.value) == 3
    assert int(c.evictions.value) == 1
    assert int(c.thrash.value) == 1
    assert not tier.fully_resident
    # Rows 0-3 (the displaced entry) are gone; 4-11 still resident.
    assert tier.resident_rows == 8
    # A batch larger than the whole budget can never ride: bypass.
    big = residency.ResidencyTier(plan, 12, 4,
                                  2 * plan.wire_row_nbytes, c)
    assert _admit(big, plan, tree, 0, 4) == 'bypass'


def test_tier_drop_releases_and_stops_serving():
    tree = _tree()
    plan = residency.wire_plan(tree, 'auto')
    c = _counters()
    tier = residency.ResidencyTier(plan, 12, 4, None, c)
    for start in (0, 4, 8):
        _admit(tier, plan, tree, start, 4)
    assert tier.serving_ok()
    tier.drop()
    assert not tier.serving_ok() and not tier.fully_resident
    assert int(c.rows.value) == 0 and int(c.bytes.value) == 0
    # Idempotent, and admissions after a drop bypass.
    tier.drop()
    assert _admit(tier, plan, tree, 0, 4) == 'bypass'


def test_device_cache_valid_detects_deleted_buffers():
    placed = residency.place_once({'x': np.arange(8, dtype=np.float32)})
    assert residency.device_cache_valid(placed)
    for leaf in placed.values():
        leaf.delete()
    assert not residency.device_cache_valid(placed)
    assert not residency.device_cache_valid(None)


# ---------------------------------------------------------------------------
# ResidentDataLoader end to end
# ---------------------------------------------------------------------------

def _loader(dataset, monkeypatch=None, kill=False, **kwargs):
    if monkeypatch is not None:
        if kill:
            monkeypatch.setenv(residency.KILL_SWITCH, '1')
        else:
            monkeypatch.delenv(residency.KILL_SWITCH, raising=False)
    reader = make_reader(dataset.url, reader_pool_type='dummy',
                         num_epochs=1, shuffle_row_groups=False)
    kwargs.setdefault('batch_size', 16)
    return ResidentDataLoader(reader, **kwargs)


def _pull_all(loader):
    with loader:
        return [{k: np.asarray(v) for k, v in b.items()} for b in loader]


def _assert_same(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert sorted(x) == sorted(y)
        for k in x:
            assert x[k].dtype == y[k].dtype
            np.testing.assert_array_equal(x[k], y[k])


def test_resident_epochs_bit_identical_to_streamed(dataset, monkeypatch):
    """Warm resident epochs deliver bit-for-bit what the kill-switch
    (pre-residency) loader streams under the same (seed, epoch) keys, and
    fetch zero host batches."""
    ldr = _loader(dataset, monkeypatch, num_epochs=3, seed=7,
                  wire_dtypes=None)
    resident = _pull_all(ldr)
    stats = ldr.residency_stats
    killed = _pull_all(_loader(dataset, monkeypatch, kill=True,
                               num_epochs=3, seed=7, wire_dtypes=None))
    _assert_same(resident, killed)
    assert len(resident) == 12  # 3 epochs x 4 full batches
    # Epoch 0 streamed 4 host batches; epochs 1-2 were pure tier hits.
    assert stats['host_batches'] == 4
    assert stats['hits'] == 8
    assert stats['admitted'] == 4 and stats['evictions'] == 0


def test_wide_rows_resident_epochs_bit_identical_to_streamed(
        tmp_path, monkeypatch):
    """The same contract over a field the tier serves one copy a row, with
    the ragged tail delivered (``drop_last=False``)."""
    from petastorm_tpu.codecs import NdarrayCodec
    from petastorm_tpu.etl.dataset_metadata import DatasetWriter
    from petastorm_tpu.unischema import Unischema, UnischemaField
    schema = Unischema('Wide', [
        UnischemaField('id', np.int64, (), None, False),
        UnischemaField('image', np.uint8, (96, 128, 3), NdarrayCodec(), False)])
    url = 'file://' + str(tmp_path / 'wide')
    rng = np.random.default_rng(5)
    with DatasetWriter(url, schema, rows_per_rowgroup=7) as writer:
        writer.write_many([
            {'id': np.int64(i),
             'image': rng.integers(0, 255, (96, 128, 3), dtype=np.uint8)}
            for i in range(21)])

    def pull(kill):
        if kill:
            monkeypatch.setenv(residency.KILL_SWITCH, '1')
        else:
            monkeypatch.delenv(residency.KILL_SWITCH, raising=False)
        ldr = ResidentDataLoader(
            make_reader(url, reader_pool_type='dummy', num_epochs=1,
                        shuffle_row_groups=False),
            batch_size=8, num_epochs=3, seed=7, drop_last=False)
        return _pull_all(ldr), ldr.residency_stats

    resident, stats = pull(kill=False)
    killed, killed_stats = pull(kill=True)
    _assert_same(resident, killed)
    assert [len(b['id']) for b in resident] == [8, 8, 5] * 3
    assert stats['host_batches'] == 3 and stats['hits'] == 6
    assert stats['rowcopy_fields'] == 1 and killed_stats['rowcopy_fields'] == 0


def test_kill_switch_counters_keep_full_shape(dataset, monkeypatch):
    ldr = _loader(dataset, monkeypatch, kill=True, num_epochs=2, seed=1)
    _pull_all(ldr)
    stats = ldr.residency_stats
    assert stats == {'admitted': 0, 'evictions': 0, 'hits': 0,
                     'bypass': 0, 'thrash': 0, 'host_batches': 8,
                     'rowcopy_fields': 0}
    # The rollup carries every counter even with the plane off.
    counters = ldr.metrics.snapshot()['counters']
    for name in residency.COUNTER_NAMES:
        assert name in counters


def test_kill_switch_keeps_wire_narrowing(dataset, monkeypatch):
    """The kill switch disables the TIER, not the transfer plane's wire
    narrowing: killed 'auto' delivery must equal resident 'auto'
    delivery (= pre-residency streaming, widen(narrow(rows))) even for
    lossy bf16-narrowed float fields."""
    on_ldr = _loader(dataset, monkeypatch, num_epochs=2, seed=4,
                     wire_dtypes='auto')
    on = _pull_all(on_ldr)
    assert on_ldr._plan is not None and on_ldr._plan.narrowed
    off = _pull_all(_loader(dataset, monkeypatch, kill=True, num_epochs=2,
                            seed=4, wire_dtypes='auto'))
    _assert_same(on, off)


def test_narrowed_warm_epoch_matches_cold(dataset, monkeypatch):
    """Under 'auto' narrowing the cold (streamed) and warm (resident)
    epochs deliver identical values for the SAME rows: both are
    widen(narrow(rows)).  shuffle=False pins the order."""
    ldr = _loader(dataset, monkeypatch, num_epochs=2, shuffle=False,
                  wire_dtypes='auto')
    batches = _pull_all(ldr)
    _assert_same(batches[:4], batches[4:])
    assert ldr.residency_stats['hits'] == 4
    # float32 leaves really rode the wire narrowed.
    assert ldr._plan is not None and ldr._plan.narrowed


def test_shuffle_covers_all_rows_and_varies_by_epoch(dataset, monkeypatch):
    ldr = _loader(dataset, monkeypatch, num_epochs=2, seed=11,
                  wire_dtypes='auto')
    batches = _pull_all(ldr)
    e0 = np.concatenate([b['id'] for b in batches[:4]])
    e1 = np.concatenate([b['id'] for b in batches[4:]])
    assert sorted(e0.tolist()) == list(range(64))
    assert sorted(e1.tolist()) == list(range(64))
    assert not np.array_equal(e0, e1)


def test_tight_budget_streams_every_epoch(dataset, monkeypatch):
    """A budget smaller than the dataset can never serve warm: every
    epoch streams (values unchanged), the LRU churns visibly."""
    ldr = _loader(dataset, monkeypatch, num_epochs=2, seed=5,
                  wire_dtypes=None)
    # Row bytes via the loader's own plan after one pull-through.
    tight = _loader(dataset, monkeypatch, num_epochs=2, seed=5,
                    wire_dtypes=None, hbm_budget_bytes=1)
    reference = _pull_all(ldr)
    got = _pull_all(tight)
    _assert_same(got, reference)
    stats = tight.residency_stats
    assert stats['hits'] == 0
    assert stats['host_batches'] == 8      # both epochs streamed
    assert stats['bypass'] == 8            # every admission bypassed


def test_partial_budget_evicts_and_never_serves_warm(dataset, monkeypatch):
    numeric_plan = None
    ldr = _loader(dataset, monkeypatch, num_epochs=2, seed=5,
                  wire_dtypes=None)
    reference = _pull_all(ldr)
    numeric_plan = ldr._plan
    assert numeric_plan is not None
    budget = 24 * numeric_plan.wire_row_nbytes  # 24 of 64 rows
    tight = _loader(dataset, monkeypatch, num_epochs=2, seed=5,
                    wire_dtypes=None, hbm_budget_bytes=budget)
    got = _pull_all(tight)
    _assert_same(got, reference)
    stats = tight.residency_stats
    assert stats['hits'] == 0 and stats['host_batches'] == 8
    assert stats['evictions'] > 0 and stats['thrash'] > 0


def test_drop_tier_mid_epoch_falls_back_to_streaming(dataset, monkeypatch):
    """Dropping the tier mid-warm-epoch streams the remaining batches
    from the retained host cache — the delivered sequence stays
    bit-identical to the uninterrupted reference."""
    reference = _pull_all(_loader(dataset, monkeypatch, kill=True,
                                  num_epochs=2, seed=3, wire_dtypes=None))
    ldr = _loader(dataset, monkeypatch, num_epochs=2, seed=3,
                  wire_dtypes=None)
    got = []
    with ldr:
        it = iter(ldr)
        for _ in range(6):   # epoch 0 (4 streamed) + 2 warm hits
            got.append({k: np.asarray(v) for k, v in next(it).items()})
        ldr.drop_resident_tier()
        for b in it:         # remaining 2 batches of epoch 1: streamed
            got.append({k: np.asarray(v) for k, v in b.items()})
    _assert_same(got, reference)
    stats = ldr.residency_stats
    assert stats['hits'] == 2
    assert stats['host_batches'] == 6      # 4 cold + 2 fallback
    assert stats['bypass'] == 2


def test_resume_token_mid_epoch_and_warm_restart(dataset, monkeypatch):
    """A token taken mid-epoch resumes the exact remaining stream in a
    fresh loader (tier rebuilt by streaming + backfill, values
    unchanged)."""
    reference = _pull_all(_loader(
        dataset, monkeypatch, num_epochs=3, seed=9, wire_dtypes=None,
        deterministic_cache_order=True))
    first = _loader(dataset, monkeypatch, num_epochs=3, seed=9,
                    wire_dtypes=None, deterministic_cache_order=True)
    got = []
    with first:
        it = iter(first)
        for _ in range(6):  # into epoch 1 (2 warm batches deep)
            got.append({k: np.asarray(v) for k, v in next(it).items()})
        token = first.state_dict()
    second = _loader(dataset, monkeypatch, num_epochs=3, seed=9,
                     wire_dtypes=None, deterministic_cache_order=True,
                     resume_state=token)
    got.extend(_pull_all(second))
    _assert_same(got, reference)
    # The resumed loader finished epoch 1 by streaming (its tier was
    # empty), backfilled, then served epoch 2 warm.
    stats = second.residency_stats
    assert stats['hits'] == 4


def test_resume_token_requires_matching_seed(dataset, monkeypatch):
    ldr = _loader(dataset, monkeypatch, num_epochs=2, seed=9)
    with ldr:
        it = iter(ldr)
        for _ in range(4):
            next(it)
        token = ldr.state_dict()
    with pytest.raises(ValueError, match='seed'):
        _loader(dataset, monkeypatch, num_epochs=2, seed=10,
                resume_state=token)
    with pytest.raises(ValueError, match='explicit seed'):
        with _loader(dataset, monkeypatch, num_epochs=1) as unseeded:
            next(iter(unseeded))
            unseeded.state_dict()


def test_provenance_records_residency_outcomes(dataset, monkeypatch):
    ldr = _loader(dataset, monkeypatch, num_epochs=2, seed=2,
                  wire_dtypes='auto')
    with ldr:
        list(ldr)
        journal = ldr.provenance.records()
    outcomes = [r.get('residency') for r in journal]
    assert outcomes[:4] == ['admitted'] * 4
    assert outcomes[4:] == ['hit'] * 4


# ---------------------------------------------------------------------------
# Health + doctor integration
# ---------------------------------------------------------------------------

def test_health_residency_thrash_regime():
    from petastorm_tpu.telemetry.health import classify_regime, health_report
    delta = {'counters': {'residency_admitted': 20, 'residency_thrash': 10,
                          'residency_hits': 0}}
    candidates = classify_regime(delta)
    assert candidates and candidates[0][1] == 'residency-thrash'
    report = health_report(delta)
    assert report['regime'] == 'residency-thrash'
    assert 'residency' in report['components']


def test_health_resident_regime_labels_warm_window():
    from petastorm_tpu.telemetry.health import health_report
    delta = {'counters': {'residency_hits': 8, 'residency_host_batches': 0,
                          'residency_admitted': 0}}
    report = health_report(delta)
    assert report['regime'] == 'resident'
    assert 'device-resident tier' in report['regime_evidence']


def test_doctor_residency_probe():
    from petastorm_tpu.tools.doctor import _check_residency
    out = _check_residency()
    assert out['widen_uint8_exact'] is True
    assert out['widen_bf16_bounded'] is True
    assert out['budget_estimate_ok'] is True
    assert out['tier_fully_resident'] is True
    assert out['donation_supported'] is False  # CPU backend: copy, not
    #                                            in-place recycling
