"""Multi-tenant serving tier + closed-loop autoscaler (ISSUE 16).

Unit tests drive the tenancy primitives directly — the WDRR scheduler's
convergence/clamp/refund contract, bounded admission, quota accounting,
and the autoscaler control law against a fake launcher with an injected
clock.  The migration tests prove a PR 15 (v1) ledger restores as the
single default-tenant job it describes while corrupt/future files cold
start.  The integration tests run a real fleet: two tenants share one
worker exactly-once, and a dispatcher restart restores BOTH tenants'
jobs from one v2 ledger.
"""

import json
import logging
import threading
import time

import numpy as np
import pytest

from petastorm_tpu.service import (Dispatcher, ServiceConfig,
                                   ServiceDataLoader, Worker,
                                   register_tenant_job)
from petastorm_tpu.service import tenancy
from petastorm_tpu.service.autoscaler import (KILL_SWITCH, Autoscaler,
                                              WorkerLauncher, killed)
from petastorm_tpu.service.ledger import DispatcherLedger

ROWS = 64


@pytest.fixture()
def dataset_url(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq
    d = tmp_path / 'ds'
    d.mkdir()
    pq.write_table(
        pa.table({'id': np.arange(ROWS, dtype=np.int64),
                  'x': np.arange(ROWS, dtype=np.float64) * 0.5}),
        str(d / 'data.parquet'), row_group_size=4)
    return 'file://' + str(d)


def _config(dataset_url, tmp_path, **overrides):
    overrides.setdefault('rowgroups_per_split', 2)
    overrides.setdefault('lease_ttl_s', 2.0)
    overrides.setdefault('reader_kwargs', {'workers_count': 1})
    overrides.setdefault('ledger_path', str(tmp_path / 'ledger.json'))
    return ServiceConfig(dataset_url, num_consumers=1, **overrides)


def _job(tenant, weight=1.0):
    """A scheduler-facing stub job (pick() reads tenant + weight only)."""
    return tenancy.TenantJob(tenant, weight, config=None, job_info=None,
                             split_base=0, num_splits=0)


# -- WDRR scheduler -----------------------------------------------------------

def test_wdrr_grant_shares_converge_to_weights():
    scheduler = tenancy.TenantScheduler()
    jobs = [_job('a', 1.0), _job('b', 3.0)]
    grants = {'a': 0, 'b': 0}
    for _ in range(400):
        grants[scheduler.pick(jobs)] += 1
    # The fluid schedule is 100/300; WDRR quantization wobbles by at
    # most a grant or two over the run.
    assert abs(grants['a'] - 100) <= 2, grants
    assert abs(grants['b'] - 300) <= 2, grants
    # ...and the empirical share ratio is the weight ratio.
    assert abs(grants['b'] / grants['a'] - 3.0) <= 0.2


def test_wdrr_single_tenant_fast_path_is_bookkeeping_free():
    """A lone eligible tenant reproduces the pre-tenancy dispatcher
    schedule exactly: no deficit state is touched at all."""
    scheduler = tenancy.TenantScheduler()
    job = _job('default')
    for _ in range(50):
        assert scheduler.pick([job]) == 'default'
    assert scheduler.deficits() == {}
    assert scheduler.pick([]) is None


def test_wdrr_refund_restores_the_grant_credit():
    """An affinity-deferred pick refunds: the tenant keeps its credit
    and wins the next grant instead of losing a turn."""
    scheduler = tenancy.TenantScheduler()
    jobs = [_job('a', 1.0), _job('b', 1.0)]
    assert scheduler.pick(jobs) == 'a'  # tie-break: earliest registered
    scheduler.refund('a')
    assert scheduler.pick(jobs) == 'a'  # credit intact: a wins again
    # Without the refund the debit stands and the grant alternates.
    assert scheduler.pick(jobs) == 'b'


def test_wdrr_deficit_clamp_bounds_banked_bursts():
    scheduler = tenancy.TenantScheduler()
    jobs = [_job('a', 1.0), _job('b', 1.0)]
    # A deficit bank far over the clamp (however it accrued) is cut to
    # the clamp at the next accrual: one pick leaves clamp - 1.0, not 99.
    scheduler._deficit['a'] = 100.0
    assert scheduler.pick(jobs) == 'a'
    assert scheduler.deficits()['a'] == pytest.approx(7.0)
    # The steady-state schedule keeps every deficit inside the clamp.
    jobs = [_job('a', 1.0), _job('b', 9.0)]
    scheduler = tenancy.TenantScheduler()
    for _ in range(1000):
        scheduler.pick(jobs)
    assert all(abs(d) <= 8.0 + 1e-9 for d in scheduler.deficits().values())


# -- admission + quotas -------------------------------------------------------

def test_registry_admission_cap_refuses_with_retry_hint():
    registry = tenancy.TenantRegistry(max_jobs=2)
    assert registry.admit(_job('a')) is None
    assert registry.admit(_job('b')) is None
    refusal = registry.admit(_job('c'))
    assert 'max_tenant_jobs=2' in refusal['error']
    assert refusal['retry_after_s'] == tenancy.ADMISSION_RETRY_S
    # A duplicate tenant id is an error, not a retry — backoff would
    # never clear it.
    duplicate = registry.admit(_job('a'))
    assert 'already registered' in duplicate['error']
    assert 'retry_after_s' not in duplicate
    # The cap counts CONCURRENT jobs: retiring one frees the slot.
    assert registry.evict('a').tenant == 'a'
    assert registry.admit(_job('c')) is None
    assert registry.tenants() == ['b', 'c']


def test_quota_ledger_charges_refunds_and_refuses_without_stalling():
    quota = tenancy.QuotaLedger()
    # No budget = unlimited for that tenant.
    assert quota.charge('free', 1 << 40)
    quota.set_budget('t', 100)
    assert quota.charge('t', 60)
    # Refusal is the ONLY enforcement: the charge is rejected, usage is
    # unchanged, and the caller degrades to the direct path.
    assert not quota.charge('t', 50)
    assert quota.refusals == 1
    assert quota.used('t') == 60
    quota.refund('t', 30)
    assert quota.charge('t', 50)
    assert quota.used('t') == 80
    # Over-refund clamps at zero (acks can race a restart).
    quota.refund('t', 10 ** 9)
    assert quota.used('t') == 0
    snap = quota.snapshot()
    assert snap['budgets'] == {'t': 100} and snap['refusals'] == 1


# -- autoscaler control law ---------------------------------------------------

class _FakeLauncher(WorkerLauncher):
    def __init__(self):
        self.spawned, self.drains, self.closed = [], [], False

    def spawn(self, dispatcher_addr):
        self.spawned.append(dispatcher_addr)
        return len(self.spawned)

    def notify_drain(self, worker_id):
        self.drains.append(worker_id)

    def close(self):
        self.closed = True


def _scaler(launcher, **overrides):
    kwargs = dict(dataset_url='file:///dev/null', autoscale=True,
                  autoscale_min_workers=1, autoscale_max_workers=4,
                  autoscale_step=2, autoscale_cooldown_s=5.0,
                  autoscale_starve_s=2.0, autoscale_idle_s=10.0)
    kwargs.update(overrides)
    return Autoscaler(ServiceConfig(**kwargs), launcher, now=0.0)


_STARVING = {'pending': 4, 'leased': 0, 'alive': ['w0'], 'free_slots': 0,
             'coverage': {}, 'dispatcher_addr': 'tcp://x:1'}


def test_autoscaler_scales_out_on_sustained_starvation_only():
    launcher = _FakeLauncher()
    scaler = _scaler(launcher)
    # First starving tick only STARTS the starve clock — a transient
    # queue blip must not spawn processes.
    assert scaler.maybe_tick(_STARVING, now=0.0) is None
    assert launcher.spawned == []
    # Sustained past autoscale_starve_s: one bounded-step action.
    assert scaler.maybe_tick(_STARVING, now=2.5) == ('scale_out', 2)
    assert launcher.spawned == ['tcp://x:1', 'tcp://x:1']
    assert scaler.scale_outs == 1 and scaler.actions == 1
    assert scaler.snapshot()['last_action'] == 'scale_out'


def test_autoscaler_cooldown_suppresses_and_counts():
    scaler = _scaler(_FakeLauncher())
    scaler.maybe_tick(_STARVING, now=0.0)
    assert scaler.maybe_tick(_STARVING, now=2.5) == ('scale_out', 2)
    scaler.maybe_tick(_STARVING, now=3.5)   # starve clock restarts
    # Sustained again at 6.0 — but inside the 5 s cooldown window: the
    # urge is counted, not acted on.
    assert scaler.maybe_tick(_STARVING, now=6.0) is None
    assert scaler.suppressed == 1
    # Cooldown elapsed: the second action fires.
    assert scaler.maybe_tick(_STARVING, now=8.0) == ('scale_out', 2)
    assert scaler.scale_outs == 2


def test_autoscaler_respects_max_workers_bound():
    launcher = _FakeLauncher()
    scaler = _scaler(launcher)
    at_max = dict(_STARVING, alive=['w0', 'w1', 'w2', 'w3'])
    scaler.maybe_tick(at_max, now=0.0)
    assert scaler.maybe_tick(at_max, now=3.0) is None
    assert launcher.spawned == [] and scaler.suppressed == 1


def test_autoscaler_drains_least_coverage_victim_on_idle():
    launcher = _FakeLauncher()
    scaler = _scaler(launcher)
    idle = {'pending': 0, 'leased': 0, 'alive': ['w0', 'w1', 'w2'],
            'free_slots': 3, 'coverage': {'w0': 5, 'w1': 0, 'w2': 2},
            'dispatcher_addr': 'tcp://x:1'}
    assert scaler.maybe_tick(idle, now=0.0) is None  # idle clock starts
    # Sustained past autoscale_idle_s: drain the worker whose departure
    # costs the least cache-directory coverage.
    assert scaler.maybe_tick(idle, now=10.5) == ('scale_in', 'w1')
    assert launcher.drains == ['w1'] and scaler.scale_ins == 1


def test_autoscaler_never_drains_below_min_workers():
    scaler = _scaler(_FakeLauncher())
    idle = {'pending': 0, 'leased': 0, 'alive': ['w0'], 'free_slots': 1,
            'coverage': {}, 'dispatcher_addr': 'tcp://x:1'}
    scaler.maybe_tick(idle, now=0.0)
    assert scaler.maybe_tick(idle, now=11.0) is None
    # The floor is a non-trigger, not a suppression: nothing wanted to
    # act.
    assert scaler.actions == 0 and scaler.suppressed == 0


def test_autoscaler_kill_switch_beats_config(monkeypatch):
    monkeypatch.setenv(KILL_SWITCH, '1')
    assert killed()
    launcher = _FakeLauncher()
    scaler = _scaler(launcher)
    assert not scaler.enabled
    assert scaler.maybe_tick(_STARVING, now=100.0) is None
    assert launcher.spawned == []
    snap = scaler.snapshot()
    assert snap == {'enabled': False, 'killed': True, 'scale_outs': 0,
                    'scale_ins': 0, 'actions': 0, 'suppressed': 0,
                    'last_action': None}
    monkeypatch.setenv(KILL_SWITCH, '0')
    assert not killed()  # '0' reads as off, like every kill switch here


# -- ledger migration (v1 -> v2) ----------------------------------------------

def test_v1_ledger_restores_as_single_default_tenant_job(dataset_url,
                                                         tmp_path):
    """A PR 15 ledger (version 1, no tenant table) restores exactly as
    it always did: one default-tenant job, done set + attempt counters
    intact."""
    config = _config(dataset_url, tmp_path, lease_ttl_s=0.3)
    d1 = Dispatcher(config)  # 16 rowgroups -> 8 splits
    w0 = d1._op_register_worker({'data_addr': 'tcp://x:1'})['worker_id']
    a = d1._op_lease({'worker_id': w0})['split']
    b = d1._op_lease({'worker_id': w0})['split']
    assert d1._op_complete({'worker_id': w0, 'split_id': a['split_id'],
                            'attempt': 0})['ok']
    time.sleep(0.4)
    d1._op_heartbeat({'worker_id': w0, 'held': []})
    d1._expire_leases()
    assert d1._splits[b['split_id']].attempt == 1
    d1._ledger_save(force=True)
    d1._ledger.release()

    # Rewrite the snapshot as the v1 file PR 15 would have left behind.
    path = str(tmp_path / 'ledger.json')
    with open(path) as f:
        state = json.load(f)
    assert state['version'] == 2 and state['tenants'] == []
    state['version'] = 1
    del state['tenants']
    with open(path, 'w') as f:
        json.dump(state, f)

    d2 = Dispatcher(config)
    try:
        assert d2.ledger_restores == 1
        assert d2._splits[a['split_id']].state == 'done'
        assert d2._splits[b['split_id']].attempt == 1
        stats = d2._op_stats({})
        assert list(stats['tenants']) == ['default']
        assert stats['tenants']['default']['done'] == 1
    finally:
        d2._ledger.release()


def test_corrupt_and_future_version_ledgers_cold_start(dataset_url,
                                                       tmp_path, caplog):
    path = str(tmp_path / 'ledger.json')
    ledger = DispatcherLedger(path)
    # Corrupt JSON: load() keeps its never-raises contract.
    with open(path, 'w') as f:
        f.write('{"kind": "dispatcher_ledger", "version": ')
    assert ledger.load() is None
    # A FUTURE version (downgraded dispatcher) is refused whole with a
    # distinct warning — half-applying unknown state would be worse
    # than a re-decode.
    with open(path, 'w') as f:
        json.dump({'kind': 'dispatcher_ledger', 'version': 3,
                   'fingerprint': 'x', 'splits': []}, f)
    with caplog.at_level(logging.WARNING,
                         logger='petastorm_tpu.service.ledger'):
        assert ledger.load() is None
    assert 'newer release' in caplog.text
    # ...and a real dispatcher over that file cold-starts cleanly.
    d = Dispatcher(_config(dataset_url, tmp_path))
    try:
        assert d.ledger_restores == 0
        assert all(s.state == 'pending' for s in d._splits)
    finally:
        d._ledger.release()


def test_restart_restores_both_tenants_jobs(dataset_url, tmp_path):
    """The v2 tenant table round-trips: a dispatcher restart rebuilds
    every registered tenant's job — split slice, weight, and per-tenant
    progress — without touching the tenants' datasets."""
    config = _config(dataset_url, tmp_path)
    d1 = Dispatcher(config)
    job_info = d1._op_register_job(
        {'tenant': 'burst', 'weight': 3.0,
         'config': {'dataset_url': dataset_url, 'rowgroups_per_split': 2,
                    'num_consumers': 1,
                    'reader_kwargs': {'workers_count': 1}}})['job']
    assert job_info['split_base'] == 8 and job_info['num_splits'] == 8
    w0 = d1._op_register_worker({'data_addr': 'tcp://x:1'})['worker_id']
    for _ in range(4):
        split = d1._op_lease({'worker_id': w0})['split']
        assert d1._op_complete({'worker_id': w0,
                                'split_id': split['split_id'],
                                'attempt': 0})['ok']
    before = d1._op_stats({})['tenants']
    assert sum(row['done'] for row in before.values()) == 4
    d1._ledger_save(force=True)
    d1._ledger.release()

    d2 = Dispatcher(config)
    try:
        assert d2.ledger_restores == 1
        after = d2._op_stats({})['tenants']
        assert set(after) == {'default', 'burst'}
        assert after['burst']['weight'] == 3.0
        assert after['burst']['split_base'] == 8
        for tenant in before:
            assert after[tenant]['done'] == before[tenant]['done']
            assert after[tenant]['pending'] == before[tenant]['pending']
    finally:
        d2._ledger.release()


# -- dispatcher-level fair share + parity -------------------------------------

def test_dispatcher_lease_grants_follow_weights(dataset_url, tmp_path):
    """Two tenants with pending work on one dispatcher: grants land
    3:1.  Driven at the RPC layer so the two-level pick (WDRR tenant,
    affinity split) is what's under test."""
    config = _config(dataset_url, tmp_path, ledger_path=None)
    d = Dispatcher(config)
    d._op_register_job(
        {'tenant': 'burst', 'weight': 3.0,
         'config': {'dataset_url': dataset_url, 'rowgroups_per_split': 2,
                    'num_consumers': 1,
                    'reader_kwargs': {'workers_count': 1}}})
    w0 = d._op_register_worker({'data_addr': 'tcp://x:1'})['worker_id']
    grants = {'default': 0, 'burst': 0}
    for _ in range(8):
        split = d._op_lease({'worker_id': w0})['split']
        grants[split['tenant']] += 1
    # 8 grants against weights 1:3 -> exactly 2 + 6 (both tenants stay
    # eligible throughout: 8 splits each, only 8 leased in total).
    assert grants == {'default': 2, 'burst': 6}
    rows = d._op_stats({})['tenants']
    assert rows['default']['grants'] == 2
    assert rows['burst']['grants'] == 6


def test_single_tenant_default_config_parity(dataset_url, tmp_path):
    """ISSUE 16 acceptance: under the default config the dispatcher is
    bit-compatible with the single-tenant one — same split ids from
    base 0, one implicit default-tenant row, autoscaler inert."""
    config = _config(dataset_url, tmp_path, ledger_path=None)
    assert config.autoscale is False
    d = Dispatcher(config)
    assert d.autoscaler is None
    assert [s.split_id for s in d._splits] == list(range(8))
    assert all(s.tenant == tenancy.DEFAULT_TENANT for s in d._splits)
    stats = d._op_stats({})
    assert list(stats['tenants']) == ['default']
    row = stats['tenants']['default']
    assert row['split_base'] == 0 and row['num_splits'] == 8
    assert row['weight'] == 1.0 and row['deficit'] == 0.0
    assert stats['autoscale']['enabled'] is False
    assert stats['autoscale']['actions'] == 0
    # The tenant-less job RPC still answers with the default job.
    assert d._op_job({})['job']['num_splits'] == 8


# -- two tenants, one fleet (integration) -------------------------------------

def test_two_tenants_share_one_worker_exactly_once(dataset_url, tmp_path):
    """Two tenants' loaders drain the SAME one-worker fleet
    concurrently: each receives its whole dataset exactly once, bit for
    bit what a direct read delivers, and the per-tenant rollups account
    for every grant."""
    from petastorm_tpu.test_util.chaos import (DeliveryDigest,
                                               direct_read_digest)
    config = _config(dataset_url, tmp_path, ledger_path=None)
    with Dispatcher(config) as dispatcher:
        worker = Worker(dispatcher.addr).start()
        register_tenant_job(
            dispatcher.addr, 'burst',
            {'dataset_url': dataset_url, 'rowgroups_per_split': 2,
             'num_consumers': 1, 'reader_kwargs': {'workers_count': 1}},
            weight=3.0)
        ids = {'default': [], 'burst': []}
        digests = {'default': DeliveryDigest(), 'burst': DeliveryDigest()}
        errors = []

        def pump(tenant):
            kwargs = {'tenant': tenant} if tenant != 'default' else {}
            try:
                with ServiceDataLoader(dispatcher.addr, batch_size=8,
                                       consumer=0, drop_last=False,
                                       queue_splits=1, credits=2,
                                       **kwargs) as loader:
                    for batch in loader.iter_host_batches():
                        ids[tenant].extend(
                            np.asarray(batch['id']).tolist())
                        digests[tenant].update(batch)
            except Exception as e:  # noqa: BLE001 — surface in-main
                errors.append((tenant, e))

        threads = [threading.Thread(target=pump, args=(t,), daemon=True)
                   for t in ids]
        for thread in threads:
            thread.start()
        for thread in threads:
            thread.join(120)
            assert not thread.is_alive(), 'tenant delivery wedged'
        assert not errors, errors
        stats = dispatcher._op_stats({})
        worker.stop()
        worker.join()
    # Exactly once PER TENANT over the shared fleet.
    assert sorted(ids['default']) == list(range(ROWS))
    assert sorted(ids['burst']) == list(range(ROWS))
    truth = direct_read_digest(dataset_url)
    assert digests['default'].hexdigest() == truth
    assert digests['burst'].hexdigest() == truth
    rows = stats['tenants']
    assert rows['default']['done'] == 8 and rows['burst']['done'] == 8
    assert rows['default']['grants'] >= 8
    assert rows['burst']['grants'] >= 8


def test_co_tenant_on_a_decoded_dataset_rides_the_cache(dataset_url, tmp_path):
    """A tenant registered on a fleet whose cache plane already holds its
    dataset (another tenant's epoch decoded it) decodes nothing: every one
    of its splits is served out of the plane, exactly once."""
    plane = str(tmp_path / 'plane')
    job = {'dataset_url': dataset_url, 'rowgroups_per_split': 2,
           'num_consumers': 1, 'reader_kwargs': {'workers_count': 1},
           'cache_plane': True, 'cache_plane_dir': plane}
    config = _config(dataset_url, tmp_path, ledger_path=None,
                     cache_plane=True, cache_plane_dir=plane)

    def consume(addr, **kwargs):
        ids = []
        with ServiceDataLoader(addr, batch_size=8, consumer=0,
                               drop_last=False, **kwargs) as loader:
            for batch in loader.iter_host_batches():
                ids.extend(np.asarray(batch['id']).tolist())
        return sorted(ids)

    with Dispatcher(config) as dispatcher:
        worker = Worker(dispatcher.addr).start()
        try:
            assert consume(dispatcher.addr) == list(range(ROWS))
            cold = dict(worker.diagnostics)
            register_tenant_job(dispatcher.addr, 'burst', job, weight=3.0)
            assert consume(dispatcher.addr, tenant='burst') \
                == list(range(ROWS))
            warm = dict(worker.diagnostics)
        finally:
            worker.stop()
            worker.join()
    assert cold['cache_misses'] == 16   # one decode a row group, once
    assert warm['cache_misses'] == cold['cache_misses']
    assert warm['cache_remote_hits'] - cold['cache_remote_hits'] == 16
