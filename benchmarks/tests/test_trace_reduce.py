"""``trace_reduce`` against a small trace recorded on a TPU v5e
(``record_fixture.py``) and against hand-made intervals."""

import os

import pytest

import trace_reduce

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), 'data',
                       'fixture.xplane.pb')
MS = 1_000_000


def hand_made():
    """A window of 100 ms on one device: ops busy 10-30 (two overlapping),
    50-60 and 90-120 ms (the last runs past the window); two steps."""
    return {
        'devices': {'/device:TPU:0': {
            'ops': [('fusion.1', 10 * MS, 15 * MS), ('fusion.2', 20 * MS, 10 * MS),
                    ('copy.3', 50 * MS, 10 * MS), ('fusion.1', 90 * MS, 30 * MS)],
            'modules': [('jit_pt_bench_train_step(123)', 10 * MS, 20 * MS),
                        ('jit_gather(9)', 50 * MS, 10 * MS),
                        ('jit_pt_bench_train_step(123)', 60 * MS, 30 * MS),
                        ('jit_pt_bench_train_step(123)', 90 * MS, 30 * MS)]}},
        'host': [('bench/window', 0, 100 * MS),
                 ('pt/host_batch', 28 * MS, 20 * MS),    # covers most of 30-50
                 ('pt/device_put', 61 * MS, 10 * MS)]}   # covers a third of 60-90


def test_busy_is_the_union_clipped_to_the_window():
    out = trace_reduce.reduce(hand_made(), 'jit_pt_bench_train_step', 'bench/window')
    assert out['window_s'] == pytest.approx(0.100)
    assert out['busy_s'] == pytest.approx(0.020 + 0.010 + 0.010)
    idle_share = 1 - out['busy_s'] / out['window_s']
    assert idle_share == pytest.approx(0.60)


def test_steps_are_the_step_programs_executions_inside_the_window():
    out = trace_reduce.reduce(hand_made(), 'jit_pt_bench_train_step', 'bench/window')
    assert out['step_count'] == 2            # the third ends after the window
    assert out['step_device_s'] == pytest.approx(0.050)


def test_gaps_go_to_the_span_that_covers_most_of_them():
    out = trace_reduce.reduce(hand_made(), 'jit_pt_bench_train_step', 'bench/window')
    gaps = dict(out['idle_gaps'])
    assert gaps['pt/host_batch'] == pytest.approx(0.020)          # 30-50
    assert gaps['train loop'] == pytest.approx(0.010 + 0.030)     # 0-10, 60-90
    assert 'pt/device_put' not in gaps
    assert sum(gaps.values()) == pytest.approx(out['window_s'] - out['busy_s'])


def test_device_ops_are_ranked_by_time_inside_the_window():
    out = trace_reduce.reduce(hand_made(), 'jit_pt_bench_train_step', 'bench/window')
    assert out['device_ops'][0] == ['fusion.1', pytest.approx(0.045)]
    assert [name for name, _ in out['device_ops']] == ['fusion.1', 'fusion.2', 'copy.3']


def test_a_step_name_that_is_missing_is_an_error():
    with pytest.raises(trace_reduce.TraceError, match='jit_gather'):
        trace_reduce.reduce(hand_made(), 'jit_renamed_step', 'bench/window')


def test_a_trace_without_a_device_or_a_window_is_an_error():
    trace = hand_made()
    with pytest.raises(trace_reduce.TraceError, match='window'):
        trace_reduce.reduce(dict(trace, host=trace['host'][1:]),
                            'jit_pt_bench_train_step', 'bench/window')
    with pytest.raises(trace_reduce.TraceError, match='device'):
        trace_reduce.reduce(dict(trace, devices={}),
                            'jit_pt_bench_train_step', 'bench/window')


def test_two_devices_are_averaged():
    trace = hand_made()
    trace['devices']['/device:TPU:1'] = {
        'ops': [('fusion.1', 0, 100 * MS)],
        'modules': [('jit_pt_bench_train_step(123)', 0, 50 * MS),
                    ('jit_pt_bench_train_step(123)', 50 * MS, 50 * MS)]}
    out = trace_reduce.reduce(trace, 'jit_pt_bench_train_step', 'bench/window')
    assert out['busy_s'] == pytest.approx((0.040 + 0.100) / 2)
    assert out['step_count'] == 2


def test_the_recorded_trace_of_a_v5e():
    """Four steps of a program named pt_bench_train_step, with a 4 ms
    pt/host_batch and a 1 ms pt/device_put sleep before each dispatch and a 3 ms
    sleep under no span after the second."""
    trace = trace_reduce.extract(FIXTURE, ('bench/window',))
    assert list(trace['devices']) == ['/device:TPU:0']
    out = trace_reduce.reduce(trace, 'jit_pt_bench_train_step', 'bench/window')
    assert out['step_count'] == 4
    assert 0 < out['step_device_s'] <= out['busy_s'] * 1.001 < out['window_s']
    gaps = dict(out['idle_gaps'])
    assert gaps['pt/host_batch'] >= 0.004 * 3       # the sleeps are in the gaps
    assert gaps['train loop'] >= 0.003
    assert sum(gaps.values()) == pytest.approx(out['window_s'] - out['busy_s'])
    assert out['device_ops'] and all(s > 0 for _, s in out['device_ops'])
    with pytest.raises(trace_reduce.TraceError):
        trace_reduce.reduce(trace, 'jit_some_other_step', 'bench/window')
