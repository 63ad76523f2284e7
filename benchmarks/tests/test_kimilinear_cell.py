"""The cell ``kimilinear.packed`` on the CPU: a ``--tiny`` run ends ``correct``,
each planted fault of the configuration's file and its control end it
otherwise, the needed FLOPs, bytes and parameters at the published sizes are
ISSUE 34's arithmetic, and the two readers that came with the cell read the
reduced trace's operations by name."""

import argparse

import pytest

import catalog
import oracle
import run as harness
from test_stage_metrics import context

CELL, CONFIG = 'kimilinear.packed', 'kimi-linear-48b-a3b'
TRAINING = ('grad_gap_median', 'change_gap_median', 'grad_gap', 'change_gap')


def config_of(tiny=False):
    _, spec, module, _ = catalog.cell(CELL)
    return module, spec, module.Config(spec, tiny=tiny)


def test_a_tiny_run_of_the_cell_is_correct():
    result = harness.run(argparse.Namespace(
        workload=CELL, seed=2_147_483_777, seconds=1.0, trace=0, tiny=True))
    assert result['correct'] is True, result['checks']
    assert set(TRAINING) <= set(result['checks'])
    assert 'metrics' not in result and result['platform'] == 'cpu'


@pytest.fixture(scope='module')
def first_steps():
    """(configuration, spec, key, the first steps' batches, the sound
    reference's numbers) at the tiny sizes."""
    _, spec, config = config_of(tiny=True)
    seed = 2_147_483_777
    data = harness.ensure_dataset(config, spec['name'], seed)
    ids = config.all_row_ids(data)[:3 * 12].reshape(3, 12)
    batches = config.reference_batches(data, list(ids))
    key = oracle.key_of(seed)
    return config, spec, key, batches, config.reference(key, batches)


FAULTS = ('half_batch', 'missing_expert', 'missing_shared', 'state_leak',
          'leaking_tap', 'scalar_decay')


def test_the_file_names_these_faults():
    _, spec, _ = config_of()
    assert tuple(spec['correct']['faults']) == FAULTS
    assert spec['correct']['control'] == 'fp8' and spec['correct']['steps'] == 3


@pytest.mark.parametrize('fault', FAULTS)
def test_a_planted_fault_is_not_correct(first_steps, fault):
    """The reference with the fault, in the program's place, passes at least
    one of the cell's limits; without it, none."""
    config, spec, key, batches, reference = first_steps
    numbers = oracle.Numbers(spec['correct']['limits'])
    oracle.compare_training(numbers, config.reference(key, batches, fault=fault),
                            reference)
    assert numbers.correct() is False, numbers.as_dict()
    failed = [n for n, c in numbers.as_dict().items() if not c['value'] <= c['limit']]
    assert set(failed) & set(TRAINING)


def test_the_faults_of_the_new_mixers_move_what_they_should(first_steps):
    """``state_leak`` and ``leaking_tap`` reach a document's first tokens from
    the document before it, so the first documents of the rows keep their
    losses; ``scalar_decay`` changes every document."""
    config, _, key, batches, reference = first_steps
    import numpy as np
    first_of_a_row = np.concatenate(
        [np.asarray(b['segment_ids'][:, :1] == 1) for b in batches[:1]]).sum()
    for fault, all_move in (('state_leak', False), ('leaking_tap', False),
                            ('scalar_decay', True)):
        got = config.reference(key, batches[:1], fault=fault)
        moved = np.abs(got['sample_losses'][0] - reference['sample_losses'][0]) \
            .reshape(-1, config.head_tokens).max(axis=1) > 1e-5
        assert moved.any(), fault
        if not all_move:
            assert (~moved).sum() >= first_of_a_row, (fault, moved)


def test_the_needs_at_the_published_sizes_are_the_issues_arithmetic():
    module, spec, config = config_of()
    d, width = 2304, 32 * 128
    kda = 3 * d * width + 3 * 4 * width + (d * 128 + 128 * width) + d * 32 + 32 \
        + width + (d * 128 + 128 * width + width) + 128 + width * d
    mla = d * 32 * 192 + d * 576 + 512 + 512 * 32 * 256 + width * d
    expert = 3 * d * 1024
    experts = d * 256 + expert + 8 * expert          # router, shared, 8 held
    norms = 2 * d
    assert (kda, mla) == (39_518_368, 29_114_880)       # 39.52 M and 29.11 M
    total = (kda + 3 * d * 9216 + norms) + 3 * (kda + experts + norms) \
        + (mla + experts + norms) + 2 * 20480 * d + d
    assert config.parameter_count() == total
    assert round(total / 1e6, 1) == 602.4
    assert config.needed_bytes_per_step() == 32 * total + 16 * 2 * 8192
    # the delta rule: 180,224 FLOPs a token and head forward, x 4 passes
    assert module.kda_flops_per_token_head(128) == 2 * 128 * 64 * 5 + 3 * 2 * 128 * 128 \
        == 180_224
    flops, nbytes = config.kda_scan_needs()
    assert flops == 4 * 4 * 16384 * 32 * 180_224
    one_pass = 16384 * 32 * (4 * 128 * 2 + 128 * 4 + 4) + 256 * 32 * 128 * 128 * 4
    assert nbytes == 4 * 2 * one_pass
    assert 1.4e12 < flops < 1.6e12 and 10e9 < nbytes < 11e9
    # latent attention: pairs x 32 heads x (192 + 128) x 2, three passes
    flops, nbytes = config.flash_attention_needs()
    pairs = 2 * config.attention_pairs_per_row()
    assert flops == pytest.approx(3 * 2 * 32 * (192 + 128) * pairs)
    assert nbytes == 2 * 3 * 16384 * 32 * (2 * 192 + 2 * 128)
    # the experts' products: 4,096 assignments a layer at the fair share
    flops, nbytes = config.expert_ffn_needs()
    assert flops == 4 * 3 * 2 * 4096 * expert
    assert config.layers == [('kda', True), ('kda', False), ('kda', False),
                             ('mla', False), ('kda', False)]


def test_the_file_states_the_cut():
    _, spec, config = config_of()
    assert spec['reduced'] == ['num_hidden_layers', 'num_experts', 'vocab_size',
                               'dataset']
    assert spec['published'] == {'num_hidden_layers': 27, 'num_experts': 256,
                                 'vocab_size': 163840}
    assert 'expert-parallel 32' in spec['deployment']
    assert (config.batch, config.max_len, len(config.experts_held)) == (2, 8192, 8)
    for name in ('A_log', 'dt_bias', 'conv', 'output_gate_bias', 'topk_weight_eps',
                 'expert_bias', 'float32_parts', 'weights', 'optimizer'):
        assert name in spec['assumed'], name
    entry = {c['name']: c for c in catalog.benchmark()['configs']}[CONFIG]
    assert entry['reduced'] == spec['reduced'] and len(entry['source']) <= 200


def test_the_catalog_lists_the_cell_with_its_metrics():
    listed = {row['cell']: row for row in catalog.listing()}
    row = listed[CELL]
    assert (row['config'], row['traffic'], row['chips']) == (CONFIG, 'packed', 1)
    for name in ('samples_per_s', 'step_p90_ms', 'setup_s', 'kda_scan_ms',
                 'kda_scan_roofline_pct', 'flash_attention_roofline_pct',
                 'expert_ffn_ms', 'expert_ffn_roofline_pct', 'step_mfu_pct',
                 'step_roofline_pct', 'padding_waste_pct', 'pack_ms'):
        assert name in row['metrics'], name
    entries = {m['name']: m for m in catalog.benchmark()['per_layer']}
    for name in ('kda_scan_ms', 'kda_scan_roofline_pct'):
        assert entries[name]['workloads'] == [CELL]
        assert entries[name]['layer'] == 'kernels'
        assert entries[name]['moves'] == 'samples_per_s'
        assert name not in listed['lfm2.packed']['metrics']


class Needs(object):
    """A configuration whose delta rule needs 1.97e12 FLOPs (10 ms at the
    peak) and 1.638e10 bytes (20 ms) a step."""

    def kda_scan_needs(self):
        return 1.97e12, 1.638e10


OPS = [['fusion.12', 3.0], ['while.3', 0.30], ['while', 0.10], ['fusion.77', 0.25],
       ['jvp_pt_flash_fwd_.1', 0.02], ['pt_kda_state.2', 0.40], ['copy.4', 0.5]]
PEAKS = {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}


def traced(ops, config=None):
    return dict(context(), config=config or Needs(), peaks=PEAKS,
                trace={'step_count': 4, 'device_ops': ops})


def test_kda_scan_ms_sums_the_loops_and_the_kernels_named_for_it():
    read = catalog.metric_reader('kda_scan_ms')
    assert read(traced(OPS)) == pytest.approx(1e3 * 0.80 / 4)
    assert read(traced([['fusion.12', 3.0], ['conditional.2', 1.0]])) is None
    assert read(context()) is None


def test_kda_scan_roofline_is_the_larger_bound_over_the_measured_time():
    module = catalog.metric_module('kda_scan_roofline_pct')
    # bound by memory: 20 ms needed of 200 ms measured
    assert module.read(traced(OPS)) == pytest.approx(10.0)
    assert module.explain(traced(OPS))['bound_by'] == 'memory'
    assert module.read(traced([['fusion.12', 3.0]])) is None
    assert module.read(traced(OPS, config=object())) is None   # a parent's program
    assert module.read(context()) is None
