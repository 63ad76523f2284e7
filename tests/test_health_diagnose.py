"""Fleet health & diagnosis plane (ISSUE 7).

Covers the three tentpole pieces: the flight recorder (bounded ring,
windowed deltas, persistence, pid-keyed singleton), the health engine
(every regime classified from a synthetic fixture — these fixtures ARE
the rule contract), the ``petastorm-tpu-diagnose`` CLI over all three
input kinds (live fleet RPC, flight dump, watchdog artifact — including
the end-to-end watchdog round-trip that pins the artifact schema).
"""

import json
import os
import subprocess
import sys
import time

import pytest

from petastorm_tpu import telemetry
from petastorm_tpu.telemetry import (MetricsRegistry, flight, health,
                                     snapshot_delta, summarize_hist)
from petastorm_tpu.telemetry import diagnose
from petastorm_tpu.telemetry.registry import BUCKETS

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- canonical histogram summary (satellite) ----------------------------------

def test_summarize_hist_canonical_shape():
    registry = MetricsRegistry('s')
    hist = registry.histogram('stage')
    for v in (0.001, 0.002, 0.004, 0.128):
        hist.observe(v)
    summary = summarize_hist(registry.snapshot()['histograms']['stage'])
    assert set(summary) == {'count', 'p50_ms', 'p99_ms', 'max_ms'}
    assert summary['count'] == 4
    # bucket upper bounds with the standard ms() rounding
    assert summary['p50_ms'] >= 2.048
    assert summary['p99_ms'] >= 128.0
    assert summary['max_ms'] >= summary['p99_ms']
    empty = summarize_hist({'counts': [0] * BUCKETS, 'count': 0})
    assert empty == {'count': 0, 'p50_ms': None, 'p99_ms': None,
                     'max_ms': None}


def test_snapshot_delta_subtracts_and_clamps():
    a = MetricsRegistry('d')
    a.counter('n').inc(10)
    a.gauge('depth').set(3)
    a.histogram('stage').observe(0.004)
    old = a.snapshot()
    a.counter('n').inc(5)
    a.gauge('depth').set(9)
    a.histogram('stage').observe(0.004)
    delta = snapshot_delta(a.snapshot(), old)
    assert delta['counters']['n'] == 5
    assert delta['gauges']['depth'] == 9          # gauges: new value
    assert delta['histograms']['stage']['count'] == 1
    # a counter RESET (worker restart) clamps to 0, not negative
    fresh = MetricsRegistry('d2')
    fresh.counter('n').inc(2)
    clamped = snapshot_delta(fresh.snapshot(), old)
    assert clamped['counters']['n'] == 0
    # old=None passes through (delta from process start)
    assert snapshot_delta(old, None)['counters']['n'] == 10


# -- flight recorder ----------------------------------------------------------

def test_flight_ring_bounds_and_window():
    registry = MetricsRegistry('fr')
    recorder = flight.FlightRecorder(interval_s=0.01, max_frames=4,
                                     label='t')
    for i in range(7):
        registry.counter('ticks').inc()
        recorder.tick()
        time.sleep(0.002)
    frames = recorder.frames()
    assert len(frames) == 4          # ring bound holds
    old, new = recorder.window(60.0)
    assert old is not None and new['t_mono'] > old['t_mono']
    delta = snapshot_delta(new['snapshot'], old['snapshot'])
    assert delta['counters']['ticks'] == 3   # frames 4..7
    # frames carry both clocks for postmortem alignment
    assert new['unix_time'] > 0 and new['t_mono'] > 0


def test_flight_persist_round_trip(tmp_path):
    path = str(tmp_path / 'flight.json')
    recorder = flight.FlightRecorder(interval_s=0.01, label='persist-test',
                                     persist_path=path, persist_every=2)
    recorder.tick()
    recorder.tick()                  # periodic persist fires here
    assert os.path.exists(path)
    recorder.tick()
    assert recorder.persist(reason='test') == path
    dump = json.load(open(path))
    assert dump['kind'] == 'flight_recorder'
    assert dump['label'] == 'persist-test'
    assert dump['reason'] == 'test'
    assert len(dump['frames']) == 3
    assert dump['pid'] == os.getpid()


def test_flight_singleton_pid_keyed_and_kill_switch(monkeypatch):
    flight.disable()
    try:
        first = flight.enable(label='one', interval_s=60.0)
        assert first is not None
        assert flight.enable(label='two') is first   # first enabler wins
        assert flight.get() is first
        flight.disable()
        assert flight.get() is None
        monkeypatch.setenv('PETASTORM_TPU_NO_FLIGHT', '1')
        assert flight.enable(label='off') is None
    finally:
        monkeypatch.delenv('PETASTORM_TPU_NO_FLIGHT', raising=False)
        flight.disable()


def test_flight_span_peek_never_drains():
    buffer = telemetry.current_buffer()
    buffer.drain()                    # start clean
    recorder = flight.FlightRecorder(interval_s=60.0)
    t = time.monotonic()
    buffer.span('probe/stage', t - 0.01, t, cid='x')
    frame = recorder.tick()
    assert any(s['name'] == 'probe/stage' for s in frame['spans'])
    # the real drain channel still owns the span
    assert any(s['name'] == 'probe/stage' for s in buffer.peek())
    # ...and the next frame does not re-record it (watermark)
    frame2 = recorder.tick()
    assert not any(s['name'] == 'probe/stage' for s in frame2['spans'])
    buffer.drain()


# -- health engine: the regime fixtures ARE the rule contract -----------------

def _fixture_delta(counters=None, hist_sums=None, hist_counts=None):
    """Synthetic windowed delta: counters + histograms with given
    busy-time sums (counts/buckets don't matter for busy shares) and,
    via ``hist_counts``, explicit bucket populations (the skew rule
    reads quantile RATIOS, so the shape matters there)."""
    histograms = {}
    for name, busy_s in (hist_sums or {}).items():
        counts = [0] * BUCKETS
        counts[20] = 10
        histograms[name] = {'counts': counts, 'sum': busy_s, 'count': 10}
    for name, bucket_population in (hist_counts or {}).items():
        counts = [0] * BUCKETS
        for bucket, n in bucket_population.items():
            counts[bucket] = n
        histograms[name] = {'counts': counts, 'sum': 1.0,
                            'count': sum(counts)}
    return {'namespace': 'fix', 'counters': dict(counters or {}),
            'gauges': {}, 'histograms': histograms}


REGIME_FIXTURES = {
    'decode-bound': dict(
        delta=_fixture_delta(hist_sums={'decode_split': 8.0,
                                        'serialize': 0.4}),
        stall_pct={'decode': 94.0, 'ipc': 6.0, 'h2d': 2.0,
                   'lease_wait': 1.0}),
    'link-bound': dict(
        delta=_fixture_delta(hist_sums={'h2d_commit': 5.0,
                                        'decode_split': 0.5}),
        stall_pct={'decode': 5.0, 'h2d': 81.0, 'h2d_stage': 30.0,
                   'lease_wait': 2.0}),
    'lease-starved': dict(
        delta=_fixture_delta(hist_sums={'decode_split': 0.1}),
        stall_pct={'decode': 4.0, 'h2d': 1.0, 'lease_wait': 88.0}),
    'cache-degraded': dict(
        delta=_fixture_delta(counters={'cache_degraded': 120,
                                       'cache_hits': 30,
                                       'cache_misses': 20}),
        stall_pct=None),
    'shm-degraded': dict(
        delta=_fixture_delta(counters={'shm_degraded': 400,
                                       'shm_chunks': 600}),
        stall_pct=None),
    # ISSUE 10: peer fetches failing back to direct decode while the
    # cluster tier IS moving entries — the fleet is re-decoding a
    # dataset a peer already holds.
    'cluster-cache-degraded': dict(
        delta=_fixture_delta(counters={'cache_peer_degraded': 80,
                                       'cache_peer_fills': 15,
                                       'cache_remote_hits': 25}),
        stall_pct=None),
    # ISSUE 9: bimodal per-item decode latency (90 fast items 10 buckets
    # below 10 slow ones: p99/p50 = 2^10) while the pool reports idle
    # gaps — must name skew-bound OVER the decode-bound busy-share
    # fallback, because the decode-bound knob (more workers) cannot fix
    # a head-of-line straggler.
    'skew-bound': dict(
        delta=_fixture_delta(hist_counts={'decode': {10: 90, 20: 10}}),
        stall_pct=None,
        meta={'decode_utilization': 0.35}),
}


@pytest.mark.parametrize('regime', sorted(REGIME_FIXTURES))
def test_health_classifies_every_regime(regime):
    fixture = REGIME_FIXTURES[regime]
    report = health.health_report(fixture['delta'],
                                  stall_pct=fixture['stall_pct'],
                                  meta=fixture.get('meta'))
    assert report['regime'] == regime, report
    assert report['regime_severity'] > 0
    assert report['regime_evidence']


def test_skew_without_idle_gaps_stays_decode_bound():
    """The same bimodal latency with a SATURATED pool is not a
    scheduling problem — all-busy skew is plain decode-bound (add
    workers), so the skew rule must not fire."""
    delta = _fixture_delta(hist_counts={'decode': {10: 90, 20: 10}})
    report = health.health_report(delta,
                                  meta={'decode_utilization': 0.97})
    assert report['regime'] != 'skew-bound'


def test_cluster_cache_degraded_verdict_names_redecode():
    """ISSUE 10: the verdict reads 'fleet re-decoding a dataset a peer
    already holds' and points at peer reachability + the kill switch."""
    fixture = REGIME_FIXTURES['cluster-cache-degraded']
    report = health.health_report(fixture['delta'])
    evidence = {'source': 'fixture', 'health': report,
                'stages': {}, 'counters': fixture['delta']['counters'],
                'meta': {},
                'workers': {'w0': {'cache_peer_degraded': 80,
                                   'cache_hits': 0}},
                'span_residue': 0, 'reason': None}
    verdicts = diagnose.run_rules(evidence)
    assert verdicts[0]['id'] == 'cluster-cache-degraded'
    assert 're-decoding a dataset a peer already holds' \
        in verdicts[0]['action']
    assert 'PETASTORM_TPU_NO_CLUSTER_CACHE' in verdicts[0]['action']
    assert 'worst worker w0' in verdicts[0]['evidence']


def test_skew_bound_verdict_points_at_adaptive_scheduling():
    fixture = REGIME_FIXTURES['skew-bound']
    report = health.health_report(fixture['delta'],
                                  meta=fixture['meta'])
    evidence = {'source': 'fixture', 'health': report,
                'stages': health.summarize_stages(
                    fixture['delta']['histograms']),
                'counters': {}, 'meta': fixture['meta'], 'workers': {},
                'span_residue': 0, 'reason': None}
    verdicts = diagnose.run_rules(evidence)
    assert verdicts[0]['id'] == 'skew-bound'
    assert "scheduling='adaptive'" in verdicts[0]['action']
    assert 'p99/p50' in verdicts[0]['evidence']


def test_health_busy_share_fallback_without_spans():
    """Counters-only input (fleet rollup with no trace attached): the
    stage busy-time shares still name decode-bound."""
    delta = _fixture_delta(hist_sums={'decode_split': 6.0,
                                      'serialize': 0.5,
                                      'shm_publish': 0.5})
    report = health.health_report(delta)
    assert report['regime'] == 'decode-bound'
    assert 'busy-share fallback' in report['regime_evidence']


def test_health_link_degrade_counters_claim_link_bound():
    """h2d_degraded (transfer plane falling back to inline puts) is a
    link problem: it must claim the link-bound regime and drag the link
    component score down even without span attribution."""
    delta = _fixture_delta(counters={'h2d_degraded': 40,
                                     'h2d_batches': 60})
    report = health.health_report(delta)
    assert report['regime'] == 'link-bound'
    assert 'h2d_degraded' in report['regime_evidence']
    assert report['components']['link']['score'] < 50


def test_diagnose_live_dead_fleet_reads_lease_starved():
    """A reply whose workers all stopped heartbeating (stale age_s) must
    count 0 alive — registered is not alive — so the health fallback
    classifies lease starvation instead of 'healthy'."""
    stats = {'pending': 5, 'leased': 0, 'done': 1, 'failed': 0,
             'lease_churn': 3, 'cache': {}, 'shm': {}, 'stages': {},
             'workers': {'w0': {'age_s': 900.0}, 'w1': {'age_s': 850.0}}}
    evidence = diagnose.evidence_from_stats(stats)
    assert evidence['meta']['workers_alive'] == 0
    assert evidence['health']['regime'] == 'lease-starved'


def test_health_idle_healthy_and_meta_starvation():
    assert health.health_report({})['regime'] == 'idle'
    busy = _fixture_delta(counters={'cache_hits': 50},
                          hist_sums={'decode_split': 0.1})
    assert health.health_report(busy)['regime'] == 'healthy'
    starved = health.health_report(
        _fixture_delta(), meta={'pending': 7, 'workers_alive': 0})
    assert starved['regime'] == 'lease-starved'
    assert '0 live workers' in starved['regime_evidence']


def test_health_component_scores_and_gauge_export():
    fixture = REGIME_FIXTURES['decode-bound']
    report = health.health_report(fixture['delta'],
                                  stall_pct=fixture['stall_pct'])
    assert report['components']['decode']['score'] == pytest.approx(6.0)
    assert report['components']['control']['score'] == pytest.approx(99.0)
    registry = MetricsRegistry('hx')
    health.export_gauges(registry, report)
    rendered = registry.render_prometheus()
    assert 'petastorm_tpu_hx_health_decode' in rendered
    assert 'petastorm_tpu_hx_health_regime_severity' in rendered


def test_health_report_from_frames_windows_the_ring():
    registry = MetricsRegistry('hw')
    recorder = flight.FlightRecorder(interval_s=0.01)
    registry.counter('cache_misses').inc(100)   # pre-window traffic
    recorder.tick()
    registry.counter('cache_degraded').inc(60)
    registry.counter('cache_misses').inc(10)
    recorder.tick()
    report = health.report_from_frames(recorder.frames(), window_s=60.0)
    assert report['regime'] == 'cache-degraded'
    # the pre-window 100 misses subtracted out: ratio is 60/(60+10)
    assert '86%' in report['regime_evidence']


# -- diagnose: verdict rules over the same fixtures ---------------------------

@pytest.mark.parametrize('regime', sorted(REGIME_FIXTURES))
def test_diagnose_top_verdict_per_regime(regime):
    fixture = REGIME_FIXTURES[regime]
    report = health.health_report(fixture['delta'],
                                  stall_pct=fixture['stall_pct'],
                                  meta=fixture.get('meta'))
    evidence = {
        'source': 'fixture', 'health': report,
        'stages': health.summarize_stages(
            fixture['delta']['histograms']),
        'counters': fixture['delta']['counters'],
        'meta': fixture.get('meta') or {}, 'workers': {},
        'span_residue': 0, 'reason': None,
    }
    verdicts = diagnose.run_rules(evidence)
    assert verdicts[0]['id'] == regime, verdicts
    assert verdicts[0]['severity'] in ('crit', 'warn')
    assert verdicts[0]['action']
    text = diagnose.render_report(diagnose.diagnose(evidence))
    assert regime in text


def test_diagnose_healthy_bill_of_health():
    evidence = {'source': 'fixture', 'health': health.health_report({}),
                'stages': {}, 'counters': {}, 'meta': {}, 'workers': {},
                'span_residue': 0, 'reason': None}
    verdicts = diagnose.run_rules(evidence)
    assert verdicts and verdicts[0]['severity'] == 'ok'


def test_diagnose_failed_splits_and_clock_drift_rules():
    evidence = {
        'source': 'fixture', 'health': health.health_report({}),
        'stages': {}, 'counters': {},
        'meta': {'failed': 2, 'pending': 0},
        'workers': {'w0': {'clock_drift_ms': 0.1},
                    'w3': {'clock_drift_ms': 412.0}},
        'span_residue': 0, 'reason': None}
    verdicts = diagnose.run_rules(evidence)
    ids = [v['id'] for v in verdicts]
    assert ids[0] == 'failed-splits'          # crit outranks warn
    assert 'clock-drift' in ids
    drift = verdicts[ids.index('clock-drift')]
    assert 'w3' in drift['summary']


def test_diagnose_flight_dump_cli(tmp_path, capsys):
    registry = MetricsRegistry('dg')
    recorder = flight.FlightRecorder(interval_s=0.01, label='cli-test')
    recorder.tick()
    registry.counter('cache_degraded').inc(80)
    registry.counter('cache_misses').inc(20)
    recorder.tick()
    path = str(tmp_path / 'flight.json')
    recorder.persist(path=path, reason='test')
    rc = diagnose.main(['--flight', path])
    assert rc == 0
    out = capsys.readouterr().out
    assert 'cache-degraded' in out and 'cli-test' in out
    rc = diagnose.main(['--flight', path, '--json'])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert report['verdicts'][0]['id'] == 'cache-degraded'
    # unreadable input: clean nonzero, not a traceback
    assert diagnose.main(['--flight', str(tmp_path / 'nope.json')]) == 1


def test_diagnose_artifact_with_trace_events(tmp_path, capsys):
    """A dump_state-shaped artifact whose timeline shows a decode-bound
    stall: attribute_stalls evidence must drive the verdict."""
    registry = MetricsRegistry('ar')
    registry.histogram('decode_split').observe(0.05)
    artifact = {
        'registries': [registry.snapshot()],
        'trace_events': [{'origin_monotonic': 1.0, 'events': [
            {'name': 'data_wait', 'ph': 'X', 'ts': 0, 'dur': 100},
            {'name': 'service/decode_split', 'ph': 'X', 'ts': 0,
             'dur': 92},
        ]}],
        'span_residue': [],
        'flight': None,
        'reason': 'exitstatus_1',
    }
    path = str(tmp_path / 'telemetry_dump.json')
    json.dump(artifact, open(path, 'w'))
    rc = diagnose.main(['--artifact', path])
    assert rc == 0
    out = capsys.readouterr().out
    assert 'decode-bound' in out
    assert 'watchdog artifact' in out


def test_watchdog_artifact_round_trip_through_diagnose(tmp_path):
    """Satellite: arm the REAL conftest watchdog over a hanging test,
    then feed the artifact it writes to petastorm-tpu-diagnose — this
    pins the dump schema the CLI depends on end-to-end."""
    import shutil
    shutil.copy(os.path.join(REPO, 'tests', 'conftest.py'),
                str(tmp_path / 'conftest.py'))
    test = tmp_path / 'test_hang.py'
    test.write_text(
        'import time\n'
        'from petastorm_tpu.telemetry import MetricsRegistry\n\n'
        'def test_hangs():\n'
        '    registry = MetricsRegistry("hungproc")\n'
        '    registry.histogram("decode_split").observe(0.2)\n'
        '    time.sleep(5)\n')
    artifact = tmp_path / 'artifacts' / 'telemetry_dump.json'
    env = dict(os.environ,
               PETASTORM_TPU_FAULT_TIMEOUT='2',
               PETASTORM_TPU_FLIGHT_INTERVAL_S='0.2',
               PETASTORM_TPU_TELEMETRY_ARTIFACT=str(artifact),
               PYTHONPATH=os.pathsep.join(
                   p for p in (REPO, os.environ.get('PYTHONPATH')) if p),
               JAX_PLATFORMS='cpu')
    out = subprocess.run(
        [sys.executable, '-m', 'pytest', str(test), '-q',
         '-p', 'no:cacheprovider', '-p', 'no:randomly'],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=180)
    assert out.returncode == 0, out.stdout + out.stderr
    assert artifact.exists(), 'watchdog never wrote the telemetry dump'
    dump = json.loads(artifact.read_text())
    # the schema diagnose depends on
    assert {'registries', 'trace_events', 'span_residue',
            'flight', 'reason'} <= set(dump)
    assert dump['reason'] == 'watchdog_timeout'
    assert dump['flight'] and dump['flight']['frames']
    # the flight ring also landed as its own artifact next to the dump
    flight_path = artifact.parent / 'flight_recorder.json'
    assert flight_path.exists()
    evidence = diagnose.evidence_from_artifact(dump)
    verdicts = diagnose.run_rules(evidence)
    assert verdicts, 'diagnose produced no verdict from the artifact'
    assert any(v['id'] == 'suite-hang' and v['severity'] == 'crit'
               for v in verdicts)
    # the flight file feeds --flight directly
    fl = subprocess.run(
        [sys.executable, '-m', 'petastorm_tpu.telemetry.diagnose',
         '--flight', str(flight_path)],
        capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS='cpu'))
    assert fl.returncode == 0, fl.stderr
    assert 'petastorm-tpu-diagnose' in fl.stdout


# -- live fleet ingestion -----------------------------------------------------

def test_diagnose_live_fleet_decode_bound(capsys):
    """Live mode end-to-end over the dispatcher RPC: a fleet whose
    heartbeats show decode dominating must yield the decode-bound top
    verdict, enriched with the canonical stage numbers."""
    import zmq

    from petastorm_tpu.service import Dispatcher, ServiceConfig
    from petastorm_tpu.service.worker import _Rpc
    config = ServiceConfig('file:///unused', num_consumers=1)
    with Dispatcher(config, num_pieces=4) as dispatcher:
        context = zmq.Context()
        rpc = _Rpc(context, dispatcher.addr)
        try:
            reply = rpc.call({'op': 'register_worker',
                              'data_addr': 'tcp://127.0.0.1:1'})
            registry = MetricsRegistry('service_worker')
            for _ in range(40):
                registry.histogram('decode_split').observe(0.04)
                registry.histogram('serialize').observe(0.002)
            beat = {'rows_decoded': 100, 'clock_drift_ms': 0.5,
                    'registry': registry.snapshot()}
            rpc.call({'op': 'heartbeat', 'worker_id': reply['worker_id'],
                      'stats': beat})
            # two stats polls bracket a fleet flight-ring window
            rpc.call({'op': 'stats'})
            time.sleep(0.05)
            rc = diagnose.main(['--dispatcher', dispatcher.addr])
        finally:
            rpc.close()
            context.term()
    assert rc == 0
    out = capsys.readouterr().out
    assert 'decode-bound' in out.splitlines()[2]   # top verdict line
    assert 'fleet decode p99' in out
    # the dispatcher's own registry now carries the health gauges
    assert 'health_regime_severity' in dispatcher.metrics.render_prometheus()
    # unreachable dispatcher: clean nonzero
    assert diagnose.main(['--dispatcher', 'tcp://127.0.0.1:1',
                          '--rpc-timeout', '0.3']) == 1


def test_worker_clock_ewma_and_drift():
    """Satellite: repeated handshakes EWMA into clock_offset; drift vs
    the registration-time estimate is surfaced in ms."""
    from petastorm_tpu.service.worker import Worker
    worker = Worker('tcp://127.0.0.1:1')
    worker._update_clock(100.0, 200.0, 200.0)   # offset 100
    assert worker.clock_offset == 100.0
    assert worker.clock_drift_ms == 0.0
    # clock drifts: the remote now reads 0.5s lower for the same local
    for _ in range(60):
        worker._update_clock(100.0, 200.5, 200.5)
    assert abs(worker.clock_offset - 100.5) < 0.01
    assert 450 < worker.clock_drift_ms <= 500
    assert worker.heartbeat_stats()['clock_drift_ms'] == \
        worker.clock_drift_ms
    # one outlier beat cannot yank the estimate (alpha 0.2)
    before = worker.clock_offset
    worker._update_clock(100.0, 210.0, 210.0)
    assert abs(worker.clock_offset - before) < 2.0


# -- control-plane-degraded regime + verdicts (ISSUE 15) ----------------------

def test_control_plane_degraded_regime_candidates():
    from petastorm_tpu.telemetry import health

    def regimes(delta, meta=None):
        return [r for _, r, _ in health.classify_regime(delta, meta=meta)]

    # Windowed restart delta (a flight/artifact window spanning one).
    assert 'control-plane-degraded' in regimes(
        {'counters': {'ledger_restores': 1}})
    # Cumulative lineage >= 2 = crash loop (a restarted dispatcher's
    # fresh ring can never show its own restart as a delta).
    assert 'control-plane-degraded' in regimes(
        {}, meta={'ledger_restores': 2})
    assert 'control-plane-degraded' not in regimes(
        {}, meta={'ledger_restores': 1})
    # Drain timeouts and backoff giveups evidence it from the WINDOWED
    # delta only — one resolved day-1 incident (cumulative meta) must
    # not classify the fleet degraded forever.
    assert 'control-plane-degraded' in regimes(
        {'counters': {'drain_timeouts': 1}})
    assert 'control-plane-degraded' in regimes(
        {'counters': {'retry_giveups': 3}})
    # ...but a single giveup (one stale peer-fetch hint) stays quiet.
    assert 'control-plane-degraded' not in regimes(
        {'counters': {'retry_giveups': 1}})
    assert 'control-plane-degraded' not in regimes(
        {'counters': {}}, meta={'drain_timeouts': 5,
                                'retry_giveups': 9})
    # ...and a clean window stays quiet.
    assert 'control-plane-degraded' not in regimes(
        {'counters': {}}, meta={'ledger_restores': 0,
                                'drain_timeouts': 0,
                                'retry_giveups': 0})
    assert 'control-plane-degraded' in health.REGIMES


def test_dispatcher_restarts_verdict():
    from petastorm_tpu.telemetry.diagnose import rule_dispatcher_restarts
    assert rule_dispatcher_restarts({'control_plane': {}}) is None
    verdict = rule_dispatcher_restarts({'control_plane': {
        'ledger_restores': 1, 'ledger_adoptions': 2,
        'ledger_requeues': 1}})
    assert verdict['severity'] == 'warn'
    assert 'restarted 1 time' in verdict['summary']
    assert '2 orphan lease(s) resumed' in verdict['evidence']
    crit = rule_dispatcher_restarts({'control_plane': {
        'ledger_restores': 3}})
    assert crit['severity'] == 'crit'


def test_drain_timeout_verdict():
    from petastorm_tpu.telemetry.diagnose import rule_drain_timeouts
    assert rule_drain_timeouts({'control_plane': {'drains': 5}}) is None
    verdict = rule_drain_timeouts({'control_plane': {
        'drain_timeouts': 2, 'drains': 5}})
    assert verdict['severity'] == 'warn'
    assert 'timed out 2 time(s) (of 5 drains)' in verdict['summary']
    assert 'drain_timeout_s' in verdict['action']


def test_stats_evidence_carries_control_plane_rollup():
    from petastorm_tpu.telemetry.diagnose import (evidence_from_stats,
                                                  run_rules)
    evidence = evidence_from_stats({
        'pending': 0, 'leased': 0, 'done': 4, 'failed': 0,
        'lease_churn': 0, 'workers': {},
        'control_plane': {'ledger_restores': 3, 'drain_timeouts': 1,
                          'drains': 2}})
    assert evidence['control_plane']['ledger_restores'] == 3
    ids = {v['id'] for v in run_rules(evidence)}
    assert 'dispatcher-restarts' in ids
    assert 'drain-timeout' in ids
