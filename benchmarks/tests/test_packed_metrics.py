"""The readers of the metrics that came with ``lfm2.packed`` (PR 28), each on
recorded numbers: the two that read the packing loader's counters and ``pack``
histogram, and the three that read the reduced trace's operations by name.
On a program that lacks what they read (a parent commit's) each gives
``None`` and the result line leaves the metric out."""

import pytest

import catalog
from test_stage_metrics import context, hist

NEW = ('padding_waste_pct', 'pack_ms', 'expert_ffn_ms', 'expert_ffn_roofline_pct',
       'flash_attention_roofline_pct')


class Needs(object):
    """A configuration that needs 1.97e12 FLOPs (10 ms at the peak) and 8.19e8
    bytes (1 ms) of its experts, and 3.94e11 FLOPs (2 ms) and 4.095e9 bytes (5
    ms) of its attention, a step."""

    def expert_ffn_needs(self):
        return 1.97e12, 8.19e8

    def flash_attention_needs(self):
        return 3.94e11, 4.095e9


PEAKS = {'bf16_flops_per_s': 197e12, 'hbm_bytes_per_s': 819e9}


def traced(ops, steps=4, config=None):
    return dict(context(), config=config or Needs(), peaks=PEAKS,
                trace={'step_count': steps, 'device_ops': ops})


OPS = [['fusion.12', 3.0], ['ragged-dot-none.3', 0.12], ['ragged-dot-none', 0.06],
       ['ragged-dot-metadata.1', 0.02], ['jvp_pt_flash_fwd_.1', 0.02],
       ['transpose_jvp_pt_flash_bwd_dkv__.1', 0.03],
       ['transpose_jvp_pt_flash_bwd_dq__.1', 0.03], ['copy.4', 0.5]]


def test_padding_waste_is_the_pad_share_of_the_positions_sent():
    read = catalog.metric_reader('padding_waste_pct')
    assert read(context(counters={'packed_tokens': 32_000, 'packed_pad_tokens': 768})) \
        == pytest.approx(100 * 768 / 32_768)
    assert read(context(counters={'packed_tokens': 0, 'packed_pad_tokens': 0})) == 0.0
    assert read(context(counters={'batches': 3})) is None


def test_pack_ms_is_the_mean_of_the_pack_histogram():
    read = catalog.metric_reader('pack_ms')
    assert read(context({'pack': hist(0.001, 0.003)})) == pytest.approx(2.0)
    assert read(context({'pack': hist()})) == 0.0
    assert read(context({'host_batch': hist(0.5)})) is None


def test_expert_ffn_ms_sums_the_ragged_dot_operations_over_the_steps():
    read = catalog.metric_reader('expert_ffn_ms')
    assert read(traced(OPS)) == pytest.approx(1e3 * 0.20 / 4)
    assert read(traced([['fusion.12', 3.0]])) is None
    assert read(context()) is None


def test_expert_ffn_roofline_is_the_larger_bound_over_the_measured_time():
    read = catalog.metric_reader('expert_ffn_roofline_pct')
    # bound by compute: 10 ms needed of 50 ms measured
    assert read(traced(OPS)) == pytest.approx(20.0)
    assert catalog.metric_module('expert_ffn_roofline_pct').explain(traced(OPS))[
        'bound_by'] == 'compute'
    assert read(traced([['fusion.12', 3.0]])) is None
    assert read(traced(OPS, config=object())) is None
    assert read(context()) is None


def test_flash_attention_roofline_reads_the_kernels_by_their_names():
    read = catalog.metric_reader('flash_attention_roofline_pct')
    # bound by memory: 5 ms needed of 0.08 s / 4 steps = 20 ms measured
    assert read(traced(OPS)) == pytest.approx(25.0)
    assert catalog.metric_module('flash_attention_roofline_pct').explain(
        traced(OPS))['bound_by'] == 'memory'
    assert read(traced([['fusion.12', 3.0]])) is None
    assert read(traced(OPS, config=object())) is None
    assert read(context()) is None


def test_the_catalog_lists_the_five_in_the_packed_cell_alone():
    listed = {row['cell']: set(row['metrics']) for row in catalog.listing()}
    assert set(NEW) <= listed['lfm2.packed']
    for cell, metrics in listed.items():
        if cell != 'lfm2.packed':
            assert not metrics & set(NEW)
    entries = {m['name']: m for m in catalog.benchmark()['per_layer']}
    for name in NEW:
        assert entries[name]['workloads'] == ['lfm2.packed']
        assert entries[name]['moves'] == 'samples_per_s'
    assert entries['expert_ffn_roofline_pct']['unit'] == '%'
    assert entries['flash_attention_roofline_pct']['layer'] == 'kernels'


def test_the_flash_kernels_carry_the_names_the_reader_looks_for():
    """The names are the program's: ``ops/flash_attention.py`` gives them to
    its three Pallas calls."""
    import inspect

    import petastorm_tpu.ops.flash_attention  # noqa: F401
    import sys
    source = inspect.getsource(sys.modules['petastorm_tpu.ops.flash_attention'])
    kernel = catalog.metric_module('flash_attention_roofline_pct').KERNEL
    for name in ('fwd', 'bwd_dq', 'bwd_dkv'):
        assert "'%s%s'" % (kernel, name) in source
