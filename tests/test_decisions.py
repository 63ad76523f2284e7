"""Control-plane decision journal (ISSUE 20): every autonomous action
explains itself.

Pins the journal contract end to end: the golden per-actor record
schema (CATALOGUE is the single source of truth the docs table syncs
against), the bounded ring + rarest-K retention, the JSON dump/restore
round-trip the dispatcher ledger persists, the `petastorm-tpu-why` CLI
over all three ingest modes (live dispatcher RPC, flight dump, watchdog
artifact), the determinism cross-check (an injected drift must be
flagged divergent), the Prometheus scrape endpoint, and the
``PETASTORM_TPU_NO_DECISIONS=1`` kill switch — which must leave
delivery bit-identical because every control law decides BEFORE it
records.
"""

import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from petastorm_tpu.telemetry import decisions, why

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _journal():
    return decisions.DecisionJournal(label='test')


def _consistent_scale_out(journal, worker='w9'):
    """A scale_out record whose inputs REPLAY to scale_out — the
    canonical self-consistent record the drift test then tampers."""
    return journal.record(
        'autoscaler', 'scale_out', 'autoscale_starve_s',
        {'pending': 4, 'alive': ['w1'], 'free_slots': 0,
         'starve_s': 1.2, 'threshold_s': 0.5, 'step': 1,
         'max_workers': 4, 'cooldown_remaining_s': 0.0},
        spawned=[worker])


# ---------------------------------------------------------------------------
# Golden record schema — one source of truth (CATALOGUE)
# ---------------------------------------------------------------------------

def test_catalogue_pins_the_seven_actors():
    """The seven instrumented control laws, by name — adding an eighth
    (or renaming one) must update the catalogue, the docs table, and
    this pin together."""
    assert decisions.ACTORS == (
        'autoscaler', 'tenant_sched', 'affinity', 'materialize',
        'hedge', 'autotuner', 'residency')
    assert set(decisions.CATALOGUE) == set(decisions.ACTORS)
    for actor, vocab in decisions.CATALOGUE.items():
        assert vocab['actions'], actor
        assert vocab['rules'], actor


def test_golden_record_schema_per_actor():
    """Every (actor, action, rule) triple the catalogue allows produces
    a record carrying the full required-key schema."""
    journal = _journal()
    for actor, vocab in decisions.CATALOGUE.items():
        for action in vocab['actions']:
            rec = journal.record(actor, action, vocab['rules'][0],
                                 {'x': 1}, suppressed=(action == 'hold'))
            assert set(decisions.RECORD_REQUIRED_KEYS) <= set(rec), actor
            assert rec['actor'] == actor and rec['action'] == action
            assert isinstance(rec['seq'], int)
            assert rec['unix_time'] > 0 and rec['t_mono'] > 0
    # every record is JSON-able as recorded — the dump IS the wire shape
    json.dumps(journal.dump())


def test_every_catalogue_rule_has_a_replay():
    """The determinism cross-check covers the full rule vocabulary: a
    new rule without a pure replay would silently go 'unchecked'."""
    for actor, vocab in decisions.CATALOGUE.items():
        for rule in vocab['rules']:
            assert rule in decisions.REPLAYS, (actor, rule)


# ---------------------------------------------------------------------------
# Ring + rarest-K + counters + flap tally
# ---------------------------------------------------------------------------

def test_ring_bounds_and_notable_survives_eviction():
    journal = decisions.DecisionJournal(capacity=8)
    real = _consistent_scale_out(journal)
    for _ in range(20):  # storm of suppressions evicts the real action
        journal.record('autoscaler', 'hold', 'autoscale_cooldown_s',
                       {'cooldown_remaining_s': 3.0, 'want': 1},
                       suppressed=True)
    assert len(journal.records()) == 8
    assert all(r['suppressed'] for r in journal.records())
    # ...but the last REAL action is retained past ring eviction
    assert journal.last('autoscaler', suppressed=False)['seq'] \
        == real['seq']
    counts = journal.counts()['autoscaler']
    assert counts == {'actions': 1, 'suppressed': 20}
    summary = journal.summary()['autoscaler']
    assert summary['last']['action'] == 'scale_out'
    assert summary['last']['age_s'] >= 0.0


def test_opposing_actions_flap_tally():
    journal = _journal()
    assert journal.opposing_actions() == {}
    for action in ('scale_out', 'scale_in', 'scale_out', 'scale_in',
                   'scale_out'):
        journal.record('autoscaler', action, 'autoscale_starve_s', {})
    journal.record('residency', 'admitted', 'residency_budget', {})
    assert journal.opposing_actions(window_s=60.0) == {'autoscaler': 2}
    # records older than the window stop counting
    assert journal.opposing_actions(window_s=60.0,
                                    now=time.monotonic() + 120.0) == {}


def test_dump_restore_roundtrip_attempt_intact():
    journal = _journal()
    rec = _consistent_scale_out(journal)
    journal.record('tenant_sched', 'quota_refused', 'quota_budget',
                   {'used': 9, 'nbytes': 4, 'budget': 10},
                   suppressed=True, tenant='teamA')
    state = json.loads(json.dumps(journal.dump()))  # through real JSON
    fresh = decisions.DecisionJournal(label='restored')
    assert fresh.restore(state)
    assert [r['seq'] for r in fresh.records()] \
        == [r['seq'] for r in journal.records()]
    restored = fresh.last('autoscaler', suppressed=False)
    assert restored['inputs'] == rec['inputs']      # attempt-intact
    assert restored['spawned'] == ['w9']
    assert fresh.dump()['restores'] == 1
    # corrupt sections lose history, never raise
    assert not fresh.restore({'kind': 'nope'})
    assert not fresh.restore('garbage')


def test_record_decision_seam_and_heartbeat_payload(monkeypatch):
    monkeypatch.delenv(decisions.KILL_SWITCH, raising=False)
    monkeypatch.setattr(decisions, '_DEFAULT', None)
    rec = decisions.record_decision(
        'hedge', 'hedge', 'hedge_deadline_s',
        {'blocked_s': 2.0, 'deadline_s': 1.0})
    assert rec is not None and rec['actor'] == 'hedge'
    assert decisions.default_journal().last('hedge') is not None
    beat = decisions.heartbeat_payload(k=4)
    assert set(beat) == {'summary', 'recent'}
    assert beat['summary']['hedge']['actions'] == 1
    assert len(beat['recent']) <= 4
    refs = decisions.recent_summaries(k=3)
    assert refs and all(
        set(r) >= {'actor', 'action', 'rule', 'age_s'} for r in refs)


# ---------------------------------------------------------------------------
# Kill switch: no records, bit-identical behavior
# ---------------------------------------------------------------------------

def _drive_residency_tier():
    """The tight-budget admit sequence from test_residency, returning
    (outcomes, slot_map) — the OBSERVABLE behavior the kill switch must
    not change."""
    import jax

    from petastorm_tpu.jax import residency
    from petastorm_tpu.telemetry.registry import MetricsRegistry
    tree = {'feat': np.linspace(-2.0, 2.0, 12 * 4,
                                dtype=np.float32).reshape(12, 4)}
    plan = residency.wire_plan(tree, 'auto')
    counters = residency.ensure_counters(MetricsRegistry('dec_res'))
    tier = residency.ResidencyTier(plan, 12, 4,
                                   8 * plan.wire_row_nbytes, counters)
    outcomes = []
    for start in (0, 4, 8, 0):
        ids = np.arange(start, start + 4)
        wire = plan.narrow({k: v[start:start + 4]
                            for k, v in tree.items()})
        outcomes.append(tier.admit(
            ids, {k: jax.device_put(v) for k, v in wire.items()}))
    return outcomes, tier._slot_of_row.copy()


def test_kill_switch_is_bit_identical_and_inert(monkeypatch):
    monkeypatch.delenv(decisions.KILL_SWITCH, raising=False)
    monkeypatch.setattr(decisions, '_DEFAULT', None)
    on_outcomes, on_slots = _drive_residency_tier()
    on_journal = decisions.default_journal()
    assert any(r['actor'] == 'residency' for r in on_journal.records())

    monkeypatch.setenv(decisions.KILL_SWITCH, '1')
    monkeypatch.setattr(decisions, '_DEFAULT', None)
    assert not decisions.enabled()
    off_outcomes, off_slots = _drive_residency_tier()
    # bit-identical: same admission outcomes, same slot assignments
    assert on_outcomes == off_outcomes
    np.testing.assert_array_equal(on_slots, off_slots)
    # inert: the seam returns None, nothing was journaled
    assert decisions.record_decision('hedge', 'hedge',
                                     'hedge_deadline_s', {}) is None
    assert decisions.default_journal().records() == []


# ---------------------------------------------------------------------------
# Determinism cross-check + drift injection
# ---------------------------------------------------------------------------

def test_replay_matches_self_consistent_record():
    journal = _journal()
    rec = _consistent_scale_out(journal)
    verdict = decisions.replay_decision(rec)
    assert verdict['verdict'] == 'match'
    assert verdict['replayed'] == {'action': 'scale_out'}


def test_replay_flags_injected_drift():
    journal = _journal()
    rec = dict(_consistent_scale_out(journal))
    rec['action'] = 'hold'  # the code "did" something else than its law
    verdict = decisions.replay_decision(rec)
    assert verdict['verdict'] == 'divergent'
    assert verdict['recorded'] == {'action': 'hold'}
    assert verdict['replayed'] == {'action': 'scale_out'}


def test_replay_unknown_rule_and_bad_snapshot_are_unchecked():
    assert decisions.replay_decision(
        {'rule': 'not_a_rule', 'inputs': {}})['verdict'] == 'unchecked'
    assert decisions.replay_decision(
        {'rule': 'autoscale_starve_s',
         'inputs': 'oops'})['verdict'] == 'unchecked'
    # residency 'drop' carries no allocator snapshot: unchecked, not a
    # false divergence
    assert decisions.replay_decision(
        {'rule': 'residency_budget', 'actor': 'residency',
         'action': 'drop', 'inputs': {'entries': 2}})['verdict'] \
        == 'unchecked'


def test_replay_residency_simulates_the_allocator():
    """The residency replay is a faithful allocator simulation: the
    fragmentation edge (evict everything, STILL no fit — freed segments
    never coalesce) must replay to bypass, not evicted."""
    base = {'capacity': 8, 'bump': 8, 'dropped': False}
    fits = decisions.replay_decision(
        {'rule': 'residency_budget', 'action': 'evicted',
         'inputs': dict(base, rows=4, free_rows=[], entry_rows=[4, 4])})
    assert fits['verdict'] == 'match'
    frag = decisions.replay_decision(
        {'rule': 'residency_budget', 'action': 'bypass',
         'inputs': dict(base, rows=6, free_rows=[], entry_rows=[4, 4])})
    assert frag['verdict'] == 'match'


def test_live_residency_records_replay_clean(monkeypatch):
    """Acceptance for the cross-check: drive the REAL allocator, then
    replay every record it journaled — zero divergence on the shipped
    tree."""
    monkeypatch.delenv(decisions.KILL_SWITCH, raising=False)
    monkeypatch.setattr(decisions, '_DEFAULT', None)
    _drive_residency_tier()
    records = [r for r in decisions.default_journal().records()
               if r['actor'] == 'residency']
    assert records
    verdicts = [decisions.replay_decision(r)['verdict'] for r in records]
    assert 'divergent' not in verdicts
    assert 'match' in verdicts


# ---------------------------------------------------------------------------
# petastorm-tpu-why — all three ingest modes
# ---------------------------------------------------------------------------

def _artifact(tmp_path, state, name='state.json'):
    path = tmp_path / name
    path.write_text(json.dumps(state))
    return str(path)


def test_why_artifact_mode_explains_a_drain(tmp_path, capsys):
    journal = _journal()
    journal.record('autoscaler', 'hold', 'autoscale_cooldown_s',
                   {'cooldown_remaining_s': 2.0, 'want': 1},
                   suppressed=True)
    journal.record(
        'autoscaler', 'scale_in', 'autoscale_idle_s',
        {'pending': 0, 'leased': 0, 'alive': ['w1', 'w3'], 'idle_s': 31.0,
         'threshold_s': 30.0, 'min_workers': 1,
         'cooldown_remaining_s': 0.0, 'coverage': {'w1': 5, 'w3': 0}},
        worker_id='w3')
    path = _artifact(tmp_path, {'decisions': [journal.dump()]})
    rc = why.main(['--artifact', path, '--worker', 'w3'])
    assert rc == 0
    out = capsys.readouterr().out
    # the answer: action + victim + NAMED rule + inputs + causal timeline
    assert 'scale_in w3' in out
    assert 'rule autoscale_idle_s' in out
    assert 'idle_s=31' in out
    assert 'preceding related decisions:' in out
    assert 'SUPPRESSED' in out                      # the cooldown hold


def test_why_flight_mode_json_contract(tmp_path, capsys):
    journal = _journal()
    _consistent_scale_out(journal)
    path = _artifact(tmp_path, {'kind': 'flight_recorder',
                                'decisions': [journal.dump()]})
    rc = why.main(['--flight', path, '--json'])
    assert rc == 0
    report = json.loads(capsys.readouterr().out)
    assert set(report) == {'meta', 'decisions'}
    assert report['meta']['actors'] == ['autoscaler']
    row = report['decisions'][-1]
    assert set(row) == {'record', 'related'}
    assert row['record']['rule'] == 'autoscale_starve_s'


def test_why_dispatcher_mode_live_rpc(capsys):
    from petastorm_tpu.service import Dispatcher, ServiceConfig
    config = ServiceConfig('file:///unused', num_consumers=1)
    with Dispatcher(config, num_pieces=4) as dispatcher:
        _consistent_scale_out(dispatcher._decisions)
        rc = why.main(['--dispatcher', dispatcher.addr, '--worker', 'w9'])
        assert rc == 0
        out = capsys.readouterr().out
        assert 'scale_out w9' in out
        assert 'rule autoscale_starve_s' in out
        assert 'dispatcher' in out                  # journal origin label
        # and the check passes over the live journal
        assert why.main(['--dispatcher', dispatcher.addr,
                         '--check']) == 0
    # unreachable dispatcher: clean nonzero exit, not a hang
    assert why.main(['--dispatcher', 'tcp://127.0.0.1:1',
                     '--rpc-timeout', '0.3']) == 1


def test_why_no_match_and_empty_and_usage(tmp_path, capsys):
    journal = _journal()
    _consistent_scale_out(journal)
    path = _artifact(tmp_path, {'decisions': [journal.dump()]})
    assert why.main(['--artifact', path, '--actor', 'hedge']) == 1
    assert 'no decision matches' in capsys.readouterr().err
    empty = _artifact(tmp_path, {'decisions': []}, name='empty.json')
    assert why.main(['--artifact', empty]) == 1
    # the error names the kill switch — the #1 reason a journal is empty
    assert decisions.KILL_SWITCH in capsys.readouterr().err
    with pytest.raises(SystemExit) as exc:
        why.main([])                                # no source: usage
    assert exc.value.code == 2


def test_why_check_flags_injected_drift(tmp_path, capsys):
    journal = _journal()
    _consistent_scale_out(journal)
    state = journal.dump()
    state['records'][-1]['action'] = 'hold'        # inject drift
    state['notable'] = []
    path = _artifact(tmp_path, {'decisions': [state]})
    rc = why.main(['--artifact', path, '--check'])
    assert rc == 1
    out = capsys.readouterr().out
    assert 'DIVERGENT' in out and '1 divergent' in out
    # JSON form carries the verdict detail
    rc = why.main(['--artifact', path, '--check', '--json'])
    assert rc == 1
    report = json.loads(capsys.readouterr().out)
    assert report['counts']['divergent'] == 1
    assert report['divergent'][0]['rule'] == 'autoscale_starve_s'


def test_why_merges_restarted_journals(tmp_path, capsys):
    """Post-restart: the restored journal answers for PRE-kill decisions
    (same seq, same inputs) and the report says it survived."""
    journal = _journal()
    rec = _consistent_scale_out(journal)
    state = json.loads(json.dumps(journal.dump()))
    reborn = decisions.DecisionJournal(label='dispatcher')
    assert reborn.restore(state)
    path = _artifact(tmp_path, {'decisions': [reborn.dump()]})
    rc = why.main(['--artifact', path, '--worker', 'w9'])
    assert rc == 0
    out = capsys.readouterr().out
    assert 'survived 1 restart(s)' in out
    assert '#%d' % rec['seq'] in out


# ---------------------------------------------------------------------------
# Prometheus scrape endpoint (satellite)
# ---------------------------------------------------------------------------

def test_metrics_endpoint_serves_decision_gauges():
    from petastorm_tpu.telemetry import scrape
    journal = _journal()
    _consistent_scale_out(journal)
    refreshed = []
    server = scrape.start_metrics_server(0, host='127.0.0.1',
                                         refresh=lambda:
                                         refreshed.append(1))
    try:
        port = server.server_address[1]
        with urllib.request.urlopen(
                'http://127.0.0.1:%d/metrics' % port, timeout=5) as resp:
            assert resp.status == 200
            assert resp.headers['Content-Type'].startswith('text/plain')
            body = resp.read().decode('utf-8')
        assert refreshed                            # hook ran pre-render
        assert '# TYPE petastorm_tpu_decisions_actions_total counter' \
            in body
        assert 'petastorm_tpu_decisions_actions_total{actor="autoscaler"}' \
            in body
        assert 'petastorm_tpu_decisions_last_action_age_seconds' in body
        # live MetricsRegistry instances ride the same scrape
        from petastorm_tpu.telemetry.registry import MetricsRegistry
        registry = MetricsRegistry('scrape_probe')
        registry.counter('hits').inc(3)
        with urllib.request.urlopen(
                'http://127.0.0.1:%d/' % port, timeout=5) as resp:
            body = resp.read().decode('utf-8')
        assert 'petastorm_tpu_scrape_probe_hits 3' in body
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(
                'http://127.0.0.1:%d/nope' % port, timeout=5)
        assert exc.value.code == 404
    finally:
        server.shutdown()


def test_render_process_metrics_survives_bad_refresh():
    from petastorm_tpu.telemetry import scrape

    def boom():
        raise RuntimeError('refresh died')
    body = scrape.render_process_metrics(refresh=boom)
    assert body.endswith('\n')                      # scrape still served


# ---------------------------------------------------------------------------
# health / top / docs integration
# ---------------------------------------------------------------------------

def test_health_classifies_control_flapping():
    from petastorm_tpu.telemetry import health
    busy = {'namespace': 'fix', 'counters': {'cache_hits': 50},
            'gauges': {}, 'histograms': {}}
    calm = health.health_report(dict(busy))
    assert calm['regime'] != 'control-flapping'
    report = health.health_report(
        dict(busy), meta={'control_flaps': {'autoscaler': 3}})
    assert report['regime'] == 'control-flapping'
    assert 'control-flapping' in health.REGIMES
    assert 'autoscaler' in report['regime_evidence']
    assert '3 opposing action pair(s)' in report['regime_evidence']
    # one opposing pair is a legitimate correction, not a flap
    single = health.health_report(
        dict(busy), meta={'control_flaps': {'autoscaler': 1}})
    assert single['regime'] != 'control-flapping'


def test_top_renders_decisions_line_with_last_action_age():
    from petastorm_tpu.telemetry import top
    summary = {'actor': 'autoscaler', 'action': 'scale_in',
               'rule': 'autoscale_idle_s', 'suppressed': False,
               'seq': 7, 'age_s': 42.0, 'worker_id': 'w3'}
    stats = {'pending': 1, 'leased': 0, 'done': 0, 'failed': 0,
             'autoscale': {'enabled': True, 'killed': False,
                           'scale_outs': 1, 'scale_ins': 1,
                           'actions': 2, 'suppressed': 5,
                           'last_action': 'scale_in'},
             'decisions': {'autoscaler':
                           {'actions': 2, 'suppressed': 5,
                            'last': summary}}}
    text = top.render_stats(stats)
    # the ISSUE 20 bugfix: WHO and WHEN, not just the bare action name
    assert 'drained w3 42s ago' in text
    assert 'decisions (acted/suppressed):' in text
    assert 'autoscaler 2/5' in text


def test_docs_decision_catalogue_synced_with_code():
    """docs/observability.md's decision-catalogue table must carry one
    row per actor naming every action and rule the code can emit —
    CATALOGUE is the single source of truth."""
    obs = open(os.path.join(REPO, 'docs', 'observability.md')).read()
    assert 'PETASTORM_TPU_NO_DECISIONS' in obs
    assert 'petastorm-tpu-why' in obs
    assert '--metrics-port' in obs
    for actor, vocab in decisions.CATALOGUE.items():
        assert '`%s`' % actor in obs, actor
        for name in vocab['actions'] + vocab['rules']:
            assert name in obs, (actor, name)


def test_decision_record_overhead_is_micro():
    """The seam must stay cheap enough to sit on every control-law
    tick: well under a millisecond per record even on a loaded CI box
    (the real number is ~µs)."""
    journal = decisions.DecisionJournal(capacity=256)
    inputs = {'pending': 3, 'alive': ['w1', 'w2'], 'starve_s': 0.7,
              'threshold_s': 0.5}
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        journal.record('autoscaler', 'hold', 'autoscale_starve_s',
                       inputs, suppressed=True)
    per_record = (time.perf_counter() - t0) / n
    assert per_record < 500e-6, '%.1fus per record' % (per_record * 1e6)
