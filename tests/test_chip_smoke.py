"""``chip_smoke.py`` off the chip: it must fail there, and its phases must
hold at a tiny size (the rehearsal that costs no chip time).

The script has no CPU branch and grows none for these tests: each test
steers it from outside — tiny sizes as arguments, the transfer plane's
``'auto'`` resolved to on (on the CPU it resolves to off; the plane's code is
the same), the device check replaced where four *virtual* devices stand in
for four chips.  What only the chip can show (Mosaic-compiled kernels, real
donation, a real link) is what ``python chip_smoke.py`` is for.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = dict(rows=96, batch=8, hw=(32, 32), seed=0)


def _force_plane_on(monkeypatch):
    from petastorm_tpu.jax import transfer
    real = transfer.plane_enabled
    monkeypatch.setattr(transfer, 'plane_enabled',
                        lambda t: True if t == 'auto' else real(t))


def test_without_a_tpu_the_script_fails_and_prints_no_result():
    res = subprocess.run([sys.executable, os.path.join(REPO, 'chip_smoke.py')],
                         env=dict(os.environ, JAX_PLATFORMS='cpu'),
                         capture_output=True, text=True, timeout=120)
    assert res.returncode != 0
    assert 'no TPU' in res.stderr
    assert '"ok"' not in res.stdout


@pytest.fixture(scope='module')
def streamed(tmp_path_factory):
    """Phases 3's tiny run: its compiled step and state feed phase 4."""
    url = 'file://' + str(tmp_path_factory.mktemp('smoke') / 'imagenet_like')
    with pytest.MonkeyPatch.context() as mp:
        _force_plane_on(mp)
        step, state, ids = chip_smoke.phase_stream_train(url, **TINY)
    return url, step, state, ids


def test_stream_train_phase_tiny(streamed, capsys):
    _, _, _, ids = streamed
    # every check of the phase held (plane on, 0 degraded, every row once,
    # images within 1 LSB of cv2, plane losses == inline losses); with
    # shuffling off the rows arrive in file order
    assert ids == list(range(TINY['rows']))


def test_stream_train_phase_fails_when_the_plane_is_off(tmp_path):
    # On the CPU 'auto' resolves to off: the phase must refuse, not pass
    # through the inline path under the plane's name.
    with pytest.raises(chip_smoke.CheckFailed, match='transfer plane'):
        chip_smoke.phase_stream_train('file://' + str(tmp_path / 'ds'), **TINY)


def test_resident_phase_tiny(streamed, capsys):
    url, step, state, _ = streamed
    chip_smoke.phase_resident(url, TINY['rows'], TINY['batch'], step, state,
                              TINY['seed'])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line['phase'] == 'resident' and line['warm_host_batches'] == 0
    assert line['residency']['hits'] == TINY['rows'] // TINY['batch']


@pytest.mark.parametrize('dtype,packed', [('bfloat16', False),
                                          ('bfloat16', True),
                                          ('float32', False)])
def test_kernel_comparison_tiny(dtype, packed):
    """The comparison the kernels phase makes, on the Pallas interpreter at
    a shape with two heads, several blocks and (packed) a padded tail."""
    import jax.numpy as jnp
    fwd, bwd, lowered = chip_smoke.attention_errors(
        (2, 256, 2, 64), jnp.dtype(dtype), packed, seed=0)
    assert fwd <= chip_smoke.KERNEL_FWD_TOL and bwd <= chip_smoke.KERNEL_BWD_RTOL
    assert 'tpu_custom_call' not in lowered     # interpreted here, not compiled


def test_kernels_phase_refuses_the_interpreter():
    with pytest.raises(chip_smoke.CheckFailed, match='interpreter'):
        chip_smoke.phase_kernels(seed=0)


def test_chunked_kernel_cases_take_the_chunked_path():
    import jax.numpy as jnp
    from petastorm_tpu.ops.flash_attention import kv_chunk_default
    chunked = [c for c in chip_smoke.KERNEL_CASES if c[0].startswith('chunked')]
    assert {c[2] for c in chunked} == {'bfloat16', 'float32'}
    for _, shape, dtype, _ in chunked:
        assert shape[1] > kv_chunk_default(shape[3], jnp.dtype(dtype))


def _main_with_recorded_phases(monkeypatch, capsys, argv, count):
    calls = []
    monkeypatch.setattr(chip_smoke, 'phase_device', lambda chips: {
        'platform': 'tpu', 'kind': 'recorded', 'count': count})
    for name in ('phase_native', 'phase_resident', 'phase_kernels',
                 'phase_across_chips'):
        monkeypatch.setattr(chip_smoke, name,
                            lambda *a, _n=name, **k: calls.append(_n))
    monkeypatch.setattr(chip_smoke, 'phase_stream_train', lambda *a, **k: (
        calls.append('phase_stream_train'), None, None))
    monkeypatch.setattr(chip_smoke, 'CompileMeter',
                        lambda: type('M', (), {'facts': lambda self: {}})())
    from petastorm_tpu import utils
    monkeypatch.setattr(utils, 'enable_compile_cache', lambda: 'unused')
    assert chip_smoke.main(argv) == 0
    return calls, capsys.readouterr().out.strip().splitlines()[-1]


def test_no_arguments_runs_every_one_chip_phase(monkeypatch, capsys):
    calls, last = _main_with_recorded_phases(monkeypatch, capsys, [], 1)
    assert calls == ['phase_native', 'phase_stream_train', 'phase_resident',
                     'phase_kernels']
    assert json.loads(last) == {'ok': True, 'device': {
        'platform': 'tpu', 'kind': 'recorded', 'count': 1}}


def test_chips_4_selects_only_the_across_chip_phase(monkeypatch, capsys):
    calls, last = _main_with_recorded_phases(monkeypatch, capsys,
                                             ['--chips', '4'], 4)
    assert calls == ['phase_native', 'phase_across_chips']
    assert json.loads(last)['device']['count'] == 4


_FOUR_VIRTUAL = '''
import json, sys
import jax
import chip_smoke
from petastorm_tpu.jax import transfer
real = transfer.plane_enabled
transfer.plane_enabled = lambda t: True if t == 'auto' else real(t)
chip_smoke.ROWS_4, chip_smoke.BATCH, chip_smoke.IMAGE_HW = 64, 16, (32, 32)
chip_smoke.RING_SHAPE = (1, 256, 2, 64)
def virtual_device(chips):
    devices = jax.devices()
    assert len(devices) == chips == 4 and devices[0].platform == 'cpu'
    return {'platform': 'cpu', 'kind': 'virtual', 'count': len(devices)}
chip_smoke.phase_device = virtual_device
sys.exit(chip_smoke.main(['--chips', '4']))
'''


def test_chips_4_on_four_virtual_devices(tmp_path):
    """The across-chip phase end to end on four virtual CPU devices: each
    device holds a different row range, the data-parallel loss agrees with
    the one-device loss, ring attention agrees with flash — and no one-chip
    phase runs."""
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               XLA_FLAGS='--xla_force_host_platform_device_count=4',
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / 'cache'),
               PYTHONPATH=os.pathsep.join(
                   p for p in (REPO, os.environ.get('PYTHONPATH')) if p))
    res = subprocess.run([sys.executable, '-c', _FOUR_VIRTUAL], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    lines = [json.loads(l) for l in res.stdout.strip().splitlines()]
    assert [l['phase'] for l in lines[:-1]] == [
        'native', 'sharded_stream_train', 'ring_attention', 'compile']
    sharded = lines[1]
    assert sharded['devices'] == 4 and sharded['rows_per_device'] == 4
    ranges = sorted(sharded['row_ranges_last_batch'].values())
    assert len(ranges) == 4 and all(hi - lo == 3 for lo, hi in ranges)
    assert all(a[1] < b[0] for a, b in zip(ranges, ranges[1:]))   # disjoint
    assert sharded['h2d_degraded'] == 0 and sharded['h2d_batches'] == 4
    assert lines[-1] == {'ok': True, 'device': {
        'platform': 'cpu', 'kind': 'virtual', 'count': 4}}
