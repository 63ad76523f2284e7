"""The readers of the metrics that join the step's traced operations to its
``pt/*`` scopes through ``step_scopes``: on made-up tables, on a tiny packed
step compiled on the CPU, and on a program without the join (a parent
commit's), where each gives ``None`` and the result line leaves it out."""

import sys

import numpy as np
import pytest

import catalog
import oracle
import step_scopes
import trace_reduce
from test_stage_metrics import context
from test_trace_reduce import FIXTURE
from timed_path import TimedPath

SCOPED = ('dense_ffn_ms', 'expert_layer_ms', 'lm_head_loss_ms', 'kda_conv_ms',
          'unscoped_ms')


def entry(seconds, forward=0.0, recompute=0.0, backward=0.0):
    return {'seconds': seconds, 'forward': forward, 'recompute': recompute,
            'backward': backward}


JOINED = {'by_scope': {
    'pt/ffn': entry(0.25, 0.0625, 0.0625, 0.125),
    'pt/moe_route': entry(0.0125), 'pt/moe_experts': entry(0.05),
    'pt/moe_combine': entry(0.025), 'pt/moe_shared': entry(0.0375),
    'pt/lm_head_loss': entry(0.04), 'pt/kda_conv': entry(0.1),
    'pt/attention': entry(0.07), None: entry(0.2, 0.2), '<unmatched>': entry(0.001)},
    'unscoped_ops': [['fusion.9', 0.15], ['copy.2', 0.05]],
    'hlo': {'source': 'kept', 'fetch_s': 1.5, 'instructions': 12}}


@pytest.mark.parametrize('metric', SCOPED)
def test_without_a_trace_each_reads_none(metric):
    read = catalog.metric_reader(metric)
    assert read(context()) is None
    assert read(dict(context(), trace={'step_count': 0, 'device_ops': []})) is None


@pytest.mark.parametrize('metric', SCOPED)
def test_on_a_program_without_the_join_each_reads_none(metric, monkeypatch):
    import petastorm_tpu.telemetry
    monkeypatch.delattr(petastorm_tpu.telemetry, 'device_scopes', raising=False)
    monkeypatch.setitem(sys.modules, 'petastorm_tpu.telemetry.device_scopes', None)
    c = dict(context(), trace={'step_count': 2, 'device_ops': [['fusion.1', 0.5]]})
    assert catalog.metric_reader(metric)(c) is None
    assert c['scopes'] is None


@pytest.mark.parametrize('metric,ms', [
    ('dense_ffn_ms', 250.0), ('expert_layer_ms', 125.0), ('lm_head_loss_ms', 40.0),
    ('kda_conv_ms', 100.0), ('unscoped_ms', 201.0)])
def test_each_reads_its_scopes(metric, ms):
    c = dict(context(), scopes=JOINED)
    assert catalog.metric_reader(metric)(c) == pytest.approx(ms)


def test_a_scope_the_step_lacks_reads_none():
    c = dict(context(), scopes={'by_scope': {None: entry(0.2)}})
    assert catalog.metric_reader('kda_conv_ms')(c) is None
    assert catalog.metric_reader('unscoped_ms')(c) == pytest.approx(200.0)


def test_the_unscoped_line_holds_the_whole_table():
    said = catalog.metric_module('unscoped_ms').explain(dict(context(), scopes=JOINED))
    assert list(said['scopes_ms'])[:3] == ['pt/ffn', '(none)', 'pt/kda_conv']
    assert said['scopes_ms']['pt/ffn'] == {'seconds': 250.0, 'forward': 62.5,
                                           'recompute': 62.5, 'backward': 125.0}
    assert said['total_ms'] == pytest.approx(786.0)
    assert said['unscoped_ops_ms'] == [['fusion.9', 150.0], ['copy.2', 50.0]]


def test_reduce_returns_the_keys_it_had():
    reduced = trace_reduce.reduce(trace_reduce.extract(FIXTURE, ('bench/window',)),
                                  'jit_pt_bench_train_step', 'bench/window')
    assert set(reduced) == {'window_s', 'busy_s', 'step_device_s', 'step_count',
                            'device_ops', 'idle_gaps'}


@pytest.fixture(scope='module')
def tiny_step(tmp_path_factory):
    """``lfm2.packed``'s configuration at its tiny sizes after a few steps of
    its timed path on the CPU, and the window step's own HLO text."""
    _, spec, module, traffic = catalog.cell('lfm2.packed')
    config = module.Config(spec, tiny=True)
    data = str(tmp_path_factory.mktemp('scopes') / 'data')
    config.write_dataset(data, 5)
    with TimedPath(config, traffic, data, 5, oracle.key_of(5), tiny=True) as path:
        path.first_steps(spec['correct']['steps'])
        path.settle()
        batch = path.next_batch()
        ran = path.step.lower(path.state, batch).compile().as_text()
        path.window(0.5, np.random.default_rng(0))
        path.free()
    return config, ran


def test_the_step_s_own_executable_is_handed_back(tiny_step):
    from petastorm_tpu.telemetry import device_scopes
    config, ran = tiny_step
    text, source = step_scopes.step_hlo(config, device_scopes)
    assert (source, text) == ('kept', ran)
    named = step_scopes.named_scopes(device_scopes.scope_table(text))
    assert {'pt/ffn', 'pt/moe_experts', 'pt/lm_head_loss', 'pt/norm'} <= named


def test_a_stale_executable_is_compiled_again(tiny_step, monkeypatch):
    """An executable that lacks a scope the lowered step names (one read back
    from a persistent cache that another program filled) is not used: the
    step is compiled again, to the same instructions."""
    from petastorm_tpu.telemetry import device_scopes
    config, ran = tiny_step
    named, calls = step_scopes.named_scopes, []

    def one_scope_more(table):
        calls.append(table)
        return named(table) | ({'pt/only_in_the_code'} if len(calls) == 1 else set())
    monkeypatch.setattr(step_scopes, 'named_scopes', one_scope_more)
    text, source = step_scopes.step_hlo(config, device_scopes)
    assert source == 'compiled'
    assert device_scopes.scope_table(text) == device_scopes.scope_table(ran)


def test_a_traced_context_is_joined_once(tiny_step):
    from petastorm_tpu.telemetry import device_scopes
    config, ran = tiny_step
    table = device_scopes.scope_table(ran)
    ops = [[name, 0.001] for name, (_, _, opcode) in table.items()
           if opcode == 'fusion'] + [['jit_pt_h2d_unpack_only.1', 0.5]]
    c = dict(context(), config=config, trace={'step_count': 2, 'device_ops': ops})
    values = {m: catalog.metric_reader(m)(c) for m in SCOPED}
    assert c['scopes']['hlo']['source'] == 'kept'
    assert values['kda_conv_ms'] is None          # no delta rule in this model
    assert values['dense_ffn_ms'] > 0 and values['expert_layer_ms'] > 0
    fused = 1e3 * 0.001 * len(ops[:-1]) / 2
    assert sum(1e3 * e['seconds'] for e in c['scopes']['by_scope'].values()) \
        == pytest.approx(fused + 250.0)
    assert c['scopes']['by_scope']['<unmatched>']['seconds'] == pytest.approx(0.25)
