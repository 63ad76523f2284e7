"""Crash-survivable control plane (ISSUE 15): durable dispatcher
ledger, graceful worker drain, and the unified retry/backoff policy.

Unit tests drive the dispatcher's RPC handlers directly (no serve
thread) — restore, reconciliation (held-claim adoption vs
attempt-intact requeue), drain/release/deregister semantics, and the
backoff schedules.  The integration tests run the real wire: the
acceptance scenario SIGKILLs a real subprocess dispatcher mid-epoch
with real subprocess workers and asserts the restarted control plane
completes the epoch with a bit-identical delivery digest.
"""

import json
import os
import time

import numpy as np
import pytest

from petastorm_tpu.errors import ServiceError
from petastorm_tpu.service import (Dispatcher, ServiceConfig,
                                   ServiceDataLoader, Worker)
from petastorm_tpu.service.ledger import (DispatcherLedger, LedgerHeldError,
                                          decode_splits, encode_splits)
from petastorm_tpu.utils import backoff

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
    # The ledger must live OUTSIDE the dataset dir (the row-group scan
    # reads every file there).
    overrides.setdefault('ledger_path', str(tmp_path / 'ledger.json'))
    return ServiceConfig(dataset_url, num_consumers=1, **overrides)


# -- backoff policy -----------------------------------------------------------

def test_backoff_envelope_grows_to_cap():
    policy = backoff.BackoffPolicy(base_s=0.1, cap_s=2.0, factor=2.0)
    assert [round(policy.envelope(i), 3) for i in range(6)] == \
        [0.1, 0.2, 0.4, 0.8, 1.6, 2.0]


def test_backoff_delay_jitters_within_envelope():
    policy = backoff.BackoffPolicy(base_s=0.1, cap_s=10.0, factor=2.0)
    import random
    rng = random.Random(3)
    delays = [policy.delay(4, rng=rng) for _ in range(200)]
    assert all(policy.base_s <= d <= policy.envelope(4) for d in delays)
    assert max(delays) - min(delays) > 0.2, 'no spread = no jitter'


def test_backoff_jitter_kill_switch(monkeypatch):
    monkeypatch.setenv('PETASTORM_TPU_NO_BACKOFF_JITTER', '1')
    policy = backoff.BackoffPolicy(base_s=0.1, cap_s=10.0, factor=2.0)
    assert policy.delay(3) == policy.envelope(3)
    assert backoff.jittered(1.0) == 1.0


def test_backoff_jittered_bounds():
    import random
    rng = random.Random(0)
    values = [backoff.jittered(1.0, spread=0.2, rng=rng)
              for _ in range(200)]
    assert all(0.8 <= v <= 1.2 for v in values)
    assert max(values) - min(values) > 0.1


def test_backoff_episode_deadline_and_attempt_budget():
    clock = [0.0]
    policy = backoff.BackoffPolicy(base_s=1.0, cap_s=8.0, deadline_s=5.0)
    episode = backoff.Backoff(policy, now=lambda: clock[0])
    assert not episode.give_up()
    clock[0] = 4.5
    # The next delay is clamped so the last retry fires AT the deadline.
    assert episode.next_delay() <= 0.5 + 1e-9
    clock[0] = 5.0
    assert episode.give_up()
    capped = backoff.BackoffPolicy(base_s=0.1, cap_s=1.0, max_attempts=2)
    episode = capped.episode()
    episode.next_delay()
    assert not episode.give_up()
    episode.next_delay()
    assert episode.give_up()
    episode.reset()
    assert not episode.give_up()


def test_backoff_policy_validation():
    with pytest.raises(ValueError):
        backoff.BackoffPolicy(base_s=0, cap_s=1.0)
    with pytest.raises(ValueError):
        backoff.BackoffPolicy(base_s=2.0, cap_s=1.0)


# -- ledger codec + file ------------------------------------------------------

def test_ledger_split_codec_round_trip(dataset_url, tmp_path):
    dispatcher = Dispatcher(
        _config(dataset_url, tmp_path, ledger_path=None), num_pieces=8)
    splits = dispatcher._splits
    splits[0].state, splits[0].attempt = 'done', 0
    splits[1].state, splits[1].attempt = 'leased', 2
    splits[3].state, splits[3].attempt = 'failed', 5
    records = json.loads(json.dumps(encode_splits(splits)))  # wire trip
    assert decode_splits(records) == [
        ('done', 0), ('leased', 2), ('pending', 0), ('failed', 5)]
    with pytest.raises(KeyError):
        decode_splits([['z', 0]])  # corrupt code rejects whole


def test_ledger_file_round_trip_and_version_gate(tmp_path):
    ledger = DispatcherLedger(str(tmp_path / 'l.json')).acquire()
    try:
        assert ledger.load() is None  # missing file = cold start
        assert ledger.save({'fingerprint': 'f', 'splits': []})
        state = ledger.load()
        assert state['kind'] == 'dispatcher_ledger'
        assert state['fingerprint'] == 'f'
        assert ledger.saves == 1
        # Wrong kind/version/corruption all read as cold start.
        (tmp_path / 'l.json').write_text('{"kind": "other"}')
        assert ledger.load() is None
        (tmp_path / 'l.json').write_text('not json')
        assert ledger.load() is None
    finally:
        ledger.release()


def test_ledger_owner_lock_is_exclusive(tmp_path):
    path = str(tmp_path / 'l.json')
    owner = DispatcherLedger(path).acquire()
    try:
        with pytest.raises(LedgerHeldError):
            DispatcherLedger(path).acquire()
    finally:
        owner.release()
    # Released: the next owner acquires, and the snapshot file (had one
    # existed) would have survived — only the .owner sidecar goes.
    second = DispatcherLedger(path).acquire()
    second.release()
    assert not os.path.exists(path + '.owner')


# -- dispatcher restore + reconciliation --------------------------------------

def test_restart_restores_done_and_attempts(dataset_url, tmp_path):
    config = _config(dataset_url, tmp_path, lease_ttl_s=0.3)
    d1 = Dispatcher(config)  # 16 rowgroups -> 8 splits
    w0 = d1._op_register_worker({'data_addr': 'tcp://x:1'})['worker_id']
    a = d1._op_lease({'worker_id': w0})['split']
    b = d1._op_lease({'worker_id': w0})['split']
    assert d1._op_complete({'worker_id': w0, 'split_id': a['split_id'],
                            'attempt': 0})['ok']
    # b's lease expires once pre-crash: its attempt counter must survive.
    time.sleep(0.4)
    d1._op_heartbeat({'worker_id': w0, 'held': []})
    d1._expire_leases()
    assert d1._splits[b['split_id']].attempt == 1
    d1._ledger_save(force=True)
    d1._ledger.release()  # simulate death (the flock dies with the pid)

    d2 = Dispatcher(config)
    assert d2.ledger_restores == 1
    assert d2._splits[a['split_id']].state == 'done'
    assert d2._splits[b['split_id']].attempt == 1
    stats = d2._op_stats({})
    assert stats['done'] == 1
    assert stats['control_plane']['ledger_restores'] == 1
    d2._ledger.release()


def test_restart_orphan_lease_adopted_by_held_claim(dataset_url, tmp_path):
    config = _config(dataset_url, tmp_path)
    d1 = Dispatcher(config)
    w0 = d1._op_register_worker({'data_addr': 'tcp://x:1'})['worker_id']
    split = d1._op_lease({'worker_id': w0})['split']
    d1._ledger_save(force=True)
    d1._ledger.release()

    d2 = Dispatcher(config)
    restored = d2._splits[split['split_id']]
    assert restored.state == 'leased' and restored.worker_id is None
    # The worker re-registers (fresh id) and its held claim adopts the
    # orphan: the lease resumes, attempt intact, nothing re-decodes.
    w_new = d2._op_register_worker({'data_addr': 'tcp://x:1'})['worker_id']
    assert d2._op_heartbeat({'worker_id': w_new,
                             'held': [split['split_id']]})['ok']
    assert restored.worker_id == w_new
    assert restored.attempt == split['attempt']
    assert d2.ledger_adoptions == 1
    # ...and its completion under the adopted lease stands.
    assert d2._op_complete({'worker_id': w_new,
                            'split_id': split['split_id'],
                            'attempt': split['attempt']})['ok']
    d2._ledger.release()


def test_restart_unclaimed_orphan_requeues_attempt_intact(dataset_url,
                                                          tmp_path):
    config = _config(dataset_url, tmp_path, lease_ttl_s=0.2)
    d1 = Dispatcher(config)
    w0 = d1._op_register_worker({'data_addr': 'tcp://x:1'})['worker_id']
    split = d1._op_lease({'worker_id': w0})['split']
    d1._ledger_save(force=True)
    d1._ledger.release()

    d2 = Dispatcher(config)
    time.sleep(0.3)
    d2._expire_leases()
    restored = d2._splits[split['split_id']]
    # Attempt INTACT (the restart was not the worker's failure) and no
    # lease_churn counted — this is not an expiry-class event.
    assert restored.state == 'pending'
    assert restored.attempt == split['attempt']
    assert d2.ledger_requeues == 1
    assert d2.lease_churn == 0
    d2._ledger.release()


def test_restart_ignores_mismatched_geometry(dataset_url, tmp_path):
    config = _config(dataset_url, tmp_path)
    d1 = Dispatcher(config)
    w0 = d1._op_register_worker({'data_addr': 'tcp://x:1'})['worker_id']
    split = d1._op_lease({'worker_id': w0})['split']
    assert d1._op_complete({'worker_id': w0, 'split_id': split['split_id'],
                            'attempt': 0})['ok']
    d1._ledger_save(force=True)
    d1._ledger.release()

    other = _config(dataset_url, tmp_path, rowgroups_per_split=4)
    d2 = Dispatcher(other)  # different geometry: cold start, no restore
    assert d2.ledger_restores == 0
    assert all(s.state == 'pending' for s in d2._splits)
    d2._ledger.release()


def test_restart_restores_cache_directory_by_addr(dataset_url, tmp_path):
    config = _config(dataset_url, tmp_path, cache_plane=True,
                     cache_plane_dir=str(tmp_path / 'plane'))
    d1 = Dispatcher(config)
    w0 = d1._op_register_worker({'data_addr': 'tcp://x:1'})['worker_id']
    d1._op_heartbeat({'worker_id': w0, 'cache_digests': ['aa', 'bb']})
    d1._ledger_save(force=True)
    d1._ledger.release()

    d2 = Dispatcher(config)
    # The directory restores keyed by data addr: the re-registering
    # worker re-enters it immediately under its NEW id.
    w_new = d2._op_register_worker({'data_addr': 'tcp://x:1'})['worker_id']
    assert d2._worker_digests[w_new] == {'aa', 'bb'}
    d2._ledger.release()


# -- drain RPC semantics ------------------------------------------------------

def test_drain_release_deregister_semantics(dataset_url, tmp_path):
    config = _config(dataset_url, tmp_path, ledger_path=None)
    d = Dispatcher(config)
    w0 = d._op_register_worker({'data_addr': 'tcp://x:1'})['worker_id']
    split = d._op_lease({'worker_id': w0})['split']
    assert not d._op_drain({'worker_id': 'nope'})['ok']
    assert d._op_drain({'worker_id': w0})['ok']
    # The worker learns on its next heartbeat, and gets no new leases.
    assert d._op_heartbeat({'worker_id': w0,
                            'held': [split['split_id']]})['drain'] is True
    assert d._op_lease({'worker_id': w0}) == {'wait': True, 'drain': True}
    # Hand-back requeues at the FRONT, attempt intact.
    assert d._op_release({'worker_id': w0, 'split_id': split['split_id'],
                          'attempt': split['attempt']})['ok']
    # (the pending deque is per-tenant since ISSUE 16; this job is the
    # implicit default tenant's)
    pending = d._tenants.get('default').pending
    assert pending[0].split_id == split['split_id']
    assert pending[0].attempt == split['attempt']
    # Releasing a lease that moved on has no standing.
    assert not d._op_release({'worker_id': w0,
                              'split_id': split['split_id'],
                              'attempt': split['attempt']})['ok']
    assert d._op_deregister({'worker_id': w0, 'timed_out': False})['ok']
    stats = d._op_stats({})
    assert stats['control_plane']['drains'] == 1
    assert stats['control_plane']['drain_timeouts'] == 0
    assert w0 not in stats['workers']


def test_timed_out_deregister_requeues_immediately(dataset_url, tmp_path):
    config = _config(dataset_url, tmp_path, ledger_path=None)
    d = Dispatcher(config)
    w0 = d._op_register_worker({'data_addr': 'tcp://x:1'})['worker_id']
    split = d._op_lease({'worker_id': w0})['split']
    assert d._op_deregister({'worker_id': w0, 'timed_out': True})['ok']
    requeued = d._splits[split['split_id']]
    # Expiry-class semantics, minus the TTL wait: attempt+1, churn.
    assert requeued.state == 'pending'
    assert requeued.attempt == split['attempt'] + 1
    assert d.lease_churn == 1
    assert d.drain_timeouts == 1


# -- integration: live drain + the dispatcher-restart acceptance scenario ----

def test_worker_drain_mid_epoch_zero_lost_splits(dataset_url, tmp_path):
    """SIGTERM-equivalent drain of a live in-process worker mid-epoch:
    every row still arrives exactly once, the drained worker exits its
    run loop on its own (clean deregister), and the fleet finishes on
    the survivor with no client errors."""
    import threading
    config = _config(dataset_url, tmp_path, drain_timeout_s=20.0)
    with Dispatcher(config) as dispatcher:
        w1 = Worker(dispatcher.addr).start()
        w2 = Worker(dispatcher.addr).start()
        ids = []
        loader = ServiceDataLoader(dispatcher.addr, batch_size=8,
                                   consumer=0, drop_last=False,
                                   queue_splits=1, credits=2)

        def pump():
            with loader:
                for batch in loader.iter_host_batches():
                    ids.extend(np.asarray(batch['id']).tolist())
                    time.sleep(0.03)

        thread = threading.Thread(target=pump, daemon=True)
        thread.start()
        deadline = time.monotonic() + 60
        while dispatcher._op_stats({})['done'] < 1:
            assert time.monotonic() < deadline, 'epoch never started'
            time.sleep(0.05)
        w1.drain()
        thread.join(120)
        assert not thread.is_alive(), 'delivery wedged across the drain'
        w1.join()  # exits on its own: drained
        assert w1.drained and not w1.drain_timed_out
        stats = dispatcher._op_stats({})
        w2.stop()
        w2.join()
    assert sorted(ids) == list(range(ROWS))
    assert stats['control_plane']['drains'] == 1
    assert stats['control_plane']['drain_timeouts'] == 0


def test_dispatcher_sigkill_restart_completes_epoch_bit_identical(tmp_path):
    """THE ISSUE 15 acceptance scenario, via the chaos harness: SIGKILL
    a real subprocess dispatcher mid-epoch (real subprocess workers, a
    live client, splits done AND pending), restart it on the same port
    + ledger, and assert the epoch completes exactly-once with a
    delivery digest bit-identical to the direct-read ground truth, zero
    residue."""
    from petastorm_tpu.test_util import chaos
    url, rows = chaos.make_chaos_dataset(str(tmp_path / 'ds'), seed=5)
    report = chaos.run_scenario('dispatcher_kill', url, rows,
                                str(tmp_path), seed=5)
    assert report['checks'].get('kill_dispatcher') == 'killed', report
    assert report['checks'].get('restart_dispatcher') == 'restarted'
    assert report['ok'], report
    # The restarted incarnation restored from the ledger (lineage = 1
    # restart), recorded in the ledger file it left behind.
    # Durable state = snapshot + journal replay (DispatcherLedger.load,
    # NOT the raw snapshot JSON: completes landing between the last
    # serve-loop tick and the teardown kill live in the journal).
    state = DispatcherLedger(
        str(tmp_path / 'ledger_dispatcher_kill.json')).load()
    assert state['restores'] == 1
    # Most splits reached 'done' in the durable record and none failed.
    # Slack = 2 workers x 3 in-flight splits: the client's epoch ends at
    # its own acks, one hop BEFORE the workers' complete RPCs — teardown
    # can kill the fleet with that many completes still in flight, and
    # those splits legitimately stay leased (a next restore would
    # requeue them attempt-intact; the live client already deduped).
    codes = [code for code, _ in state['splits']]
    assert codes.count('d') >= len(codes) - 6, codes
    assert codes.count('d') >= 1
    assert 'f' not in codes


@pytest.mark.parametrize('ledger', [True, False], ids=['restored', 'cold'])
def test_client_rides_through_dispatcher_outage_with_backoff(dataset_url,
                                                             tmp_path, ledger):
    """A live client keeps polling through a dispatcher outage on the
    exponential discovery backoff (no 1 Hz hammer), then finishes the
    epoch against the restarted dispatcher — no resume token, no client
    error.  With a ledger the new dispatcher goes on where the old one
    stopped; without one it hands every split out again and the client
    drops what it has already delivered: exactly once either way."""
    import socket
    import threading
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        addr = 'tcp://127.0.0.1:%d' % s.getsockname()[1]
    config = _config(dataset_url, tmp_path, ledger_path=str(
        tmp_path / 'ledger.json') if ledger else None)
    d1 = Dispatcher(config, bind=addr).start()
    worker = Worker(addr).start()
    ids = []
    # rpc_timeout_s well under the outage: ZMQ's transparent reconnect
    # would otherwise park the 20 s-timeout poll across a short outage
    # and the backoff path would (correctly) never fire.
    loader = ServiceDataLoader(addr, batch_size=8, consumer=0,
                               drop_last=False, queue_splits=1, credits=2,
                               rpc_timeout_s=1.0)
    connection = loader.reader._conn

    def pump():
        with loader:
            for batch in loader.iter_host_batches():
                ids.extend(np.asarray(batch['id']).tolist())
                time.sleep(0.03)

    thread = threading.Thread(target=pump, daemon=True)
    thread.start()
    deadline = time.monotonic() + 60
    while d1._op_stats({})['done'] < 1:
        assert time.monotonic() < deadline
        time.sleep(0.05)
    d1.stop()
    d1.join()
    time.sleep(3.0)  # outage: discovery polls time out and back off
    d2 = Dispatcher(config, bind=addr).start()
    thread.join(120)
    alive = thread.is_alive()
    worker.stop()
    worker.join()
    d2.stop()
    d2.join()
    assert not alive, 'client wedged across the dispatcher outage'
    assert sorted(ids) == list(range(ROWS))
    assert connection.retry_attempts >= 1, \
        'outage never exercised the discovery backoff'
    assert d2.ledger_restores == (1 if ledger else 0)


def test_drain_rpc_reaches_worker_via_heartbeat(dataset_url, tmp_path):
    """Dispatcher-initiated drain (the `drain` RPC / CLI): the worker
    learns on its next heartbeat and runs the same drain path."""
    config = _config(dataset_url, tmp_path, ledger_path=None)
    with Dispatcher(config) as dispatcher:
        worker = Worker(dispatcher.addr).start()
        assert dispatcher._op_drain(
            {'worker_id': worker.worker_id})['ok']
        deadline = time.monotonic() + 30
        while not worker.drained:
            assert time.monotonic() < deadline, 'drain never completed'
            time.sleep(0.05)
        worker.join()
        assert dispatcher._op_stats({})['control_plane']['drains'] == 1


def test_heartbeat_failure_uses_backoff_not_lockstep(dataset_url, tmp_path):
    """Heartbeats that fail (injected at the chaos `rpc.request` seam)
    schedule their retries on the jittered-exponential policy — counted
    in `retry_attempts` and visible fleet-wide via the heartbeat stats
    — instead of the old fixed-interval lockstep."""
    from petastorm_tpu.test_util import chaos
    config = _config(dataset_url, tmp_path, ledger_path=None,
                     lease_ttl_s=1.0)
    with Dispatcher(config) as dispatcher:
        state = chaos.activate({'seed': 1, 'faults': [
            {'seam': 'rpc.request', 'action': 'drop', 'p': 1.0,
             'max': 3, 'ops': ['heartbeat']}]})
        try:
            worker = Worker(dispatcher.addr).start()
            deadline = time.monotonic() + 30
            while worker.diagnostics['retry_attempts'] < 3:
                assert time.monotonic() < deadline, \
                    'heartbeat failures never hit the backoff path'
                time.sleep(0.05)
        finally:
            chaos.deactivate()
        assert state.counts[('rpc.request', 'drop')] == 3
        # The fleet rollup carries the counters once a healthy beat
        # ships the stats (the injection budget is exhausted by now).
        deadline = time.monotonic() + 30
        while True:
            control = dispatcher._op_stats({})['control_plane']
            if control['retry_attempts'] >= 3:
                break
            assert time.monotonic() < deadline, \
                'retry counters never reached the fleet rollup'
            time.sleep(0.1)
        worker.stop()
        worker.join()


# -- write-ahead journal (code-review round: O(1) per complete) ---------------

def test_ledger_journal_write_ahead_replay(dataset_url, tmp_path):
    """A complete is durable the moment its O(1) journal line lands —
    even when the dispatcher dies before the next full snapshot, the
    restore replays it; and the next incarnation's first snapshot
    absorbs + truncates the journal."""
    config = _config(dataset_url, tmp_path)
    d1 = Dispatcher(config)
    w0 = d1._op_register_worker({'data_addr': 'tcp://x:1'})['worker_id']
    split = d1._op_lease({'worker_id': w0})['split']
    d1._ledger_save(force=True)  # last full snapshot: split still leased
    assert d1._op_complete({'worker_id': w0, 'split_id': split['split_id'],
                            'attempt': 0})['ok']
    journal = tmp_path / 'ledger.json.journal'
    assert journal.read_text().strip(), 'complete never hit the journal'
    d1._ledger.release()  # death: NO final snapshot

    d2 = Dispatcher(config)
    assert d2._splits[split['split_id']].state == 'done'
    # d2's construction-time snapshot absorbed the journal.
    assert journal.read_text() == ''
    d2._ledger.release()


def test_ledger_journal_torn_tail_line_skipped(tmp_path):
    path = str(tmp_path / 'l.json')
    ledger = DispatcherLedger(path).acquire()
    try:
        ledger.save({'fingerprint': 'f',
                     'splits': [['p', 0], ['p', 0]]})
        assert ledger.append({'op': 'done', 'split': 0})
        # SIGKILL mid-append: a torn final line.
        with open(path + '.journal', 'a') as f:
            f.write('{"op": "done", "spl')
        state = ledger.load()
        assert state['splits'][0] == ['d', 0]   # replayed
        assert state['splits'][1] == ['p', 0]   # torn line skipped
    finally:
        ledger.release()


def test_restore_rejects_short_split_record_list(dataset_url, tmp_path):
    """A truncated ledger is rejected WHOLE (zip would silently
    half-apply it: tail splits re-decoding at attempt 0)."""
    config = _config(dataset_url, tmp_path)
    d1 = Dispatcher(config)
    w0 = d1._op_register_worker({'data_addr': 'tcp://x:1'})['worker_id']
    split = d1._op_lease({'worker_id': w0})['split']
    assert d1._op_complete({'worker_id': w0, 'split_id': split['split_id'],
                            'attempt': 0})['ok']
    d1._ledger_save(force=True)
    d1._ledger.release()
    path = tmp_path / 'ledger.json'
    state = json.loads(path.read_text())
    state['splits'] = state['splits'][:3]
    path.write_text(json.dumps(state))
    d2 = Dispatcher(config)
    assert d2.ledger_restores == 0
    assert all(s.state == 'pending' for s in d2._splits)
    d2._ledger.release()


def test_malformed_rpc_gets_error_reply_not_a_dead_dispatcher(dataset_url,
                                                              tmp_path):
    """A peer pickling a non-dict costs one error reply, never the
    serve thread (a dead REP socket would wedge the whole fleet)."""
    import pickle

    import zmq
    config = _config(dataset_url, tmp_path, ledger_path=None)
    with Dispatcher(config) as dispatcher:
        context = zmq.Context()
        sock = context.socket(zmq.REQ)
        sock.setsockopt(zmq.LINGER, 0)
        sock.connect(dispatcher.addr)
        try:
            sock.send(pickle.dumps('hello'))
            assert sock.poll(10000), 'no reply to the malformed request'
            reply = pickle.loads(sock.recv())
            assert 'malformed request' in reply['error']
            # ...and the control plane still serves real RPCs after it.
            sock.send(pickle.dumps({'op': 'job'}, protocol=4))
            assert sock.poll(10000), 'dispatcher died on malformed input'
            assert pickle.loads(sock.recv())['job']['num_consumers'] == 1
        finally:
            sock.close(0)
            context.term()


def test_fresh_client_on_reused_ledger_raises_instead_of_hanging(
        dataset_url, tmp_path):
    """A ledger outlives clean shutdowns by design; a token-less client
    pointed at a restored dispatcher whose ledger already retired its
    splits must get a clear ServiceError, not an eternal hang (those
    splits will never stream again)."""
    config = _config(dataset_url, tmp_path)
    # Run 1: complete the whole epoch against the ledger.
    with Dispatcher(config) as d1:
        with Worker(d1.addr):
            loader = ServiceDataLoader(d1.addr, batch_size=8, consumer=0,
                                       drop_last=False)
            ids = []
            with loader:
                for batch in loader.iter_host_batches():
                    ids.extend(np.asarray(batch['id']).tolist())
            assert sorted(ids) == list(range(ROWS))
    # Run 2: same ledger, fresh token-less client.
    with Dispatcher(config) as d2:
        assert d2.ledger_restores == 1
        with Worker(d2.addr):
            loader = ServiceDataLoader(d2.addr, batch_size=8, consumer=0,
                                       drop_last=False)
            with pytest.raises(ServiceError, match='restored ledger'):
                with loader:
                    for _ in loader.iter_host_batches():
                        pass
