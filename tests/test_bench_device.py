"""``bench.py`` names the device it ran on and never another one.

Without a TPU it exits non-zero — it does not wait, probe from a child, or
re-run itself on the CPU under the same metric names — unless
``JAX_PLATFORMS=cpu`` asked for a CPU rehearsal, whose every line says
``cpu``.  Device facts it cannot ask the device for are errors, not defaults.
"""

import json
import os
import subprocess
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _run(args, platforms, timeout=120):
    env = dict(os.environ)
    env.pop('JAX_PLATFORMS', None)
    if platforms is not None:
        env['JAX_PLATFORMS'] = platforms
    return subprocess.run([sys.executable] + args, env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize('platforms,why', [
    (None, 'no TPU'),                         # jax settles for the CPU itself
    ('no_such_platform', 'no_such_platform'),  # jax raises; nothing catches it
])
def test_bench_without_a_tpu_exits_nonzero(platforms, why):
    res = _run(['bench.py'], platforms)
    assert res.returncode != 0
    assert why in res.stderr
    assert res.stdout.strip() == ''     # no metric line under any label


def test_bench_has_no_child_probe_or_cpu_reexec():
    src = open(os.path.join(REPO, 'bench.py')).read()
    for gone in ('_device_probe_ok', '_reexec_cpu_fallback', '_wait_for_device',
                 'os.execve', '_load_last_tpu', 'BENCH_TPU_LAST'):
        assert gone not in src, gone
    assert len(src.splitlines()) < 3249


def test_cpu_rehearsal_is_admitted_and_labelled_cpu():
    import bench
    assert os.environ['JAX_PLATFORMS'] == 'cpu'      # conftest asked for it
    assert bench._require_device() == 'cpu'


def test_watchdog_line_names_the_backend_and_exits_nonzero():
    body = ('import time, bench\n'
            "bench._PARTIAL_BASE.update(backend='cpu', value=12.5)\n"
            'bench._start_watchdog(0.2)\n'
            'time.sleep(30)\n')
    res = _run(['-c', body], 'cpu')
    assert res.returncode == 3
    line = json.loads(res.stdout.strip().splitlines()[-1])
    assert line['backend'] == 'cpu' and line['value'] == 12.5
    assert 'watchdog' in line['error']
    assert 'last_tpu' not in line


def _fake_devices(monkeypatch, **attrs):
    import jax
    device = types.SimpleNamespace(**attrs)
    monkeypatch.setattr(jax, 'devices', lambda *a: [device])


def test_unknown_device_kind_is_an_error(monkeypatch):
    import bench
    assert bench._device_peak_tflops() == (None, 'cpu')   # a rehearsal: no MFU
    _fake_devices(monkeypatch, platform='tpu', device_kind='TPU v5 lite')
    assert bench._device_peak_tflops() == (197.0, 'TPU v5 lite')
    # 'TPU v5' used to read 459 TFLOP/s by substring, whatever came after
    _fake_devices(monkeypatch, platform='tpu', device_kind='TPU v5 next')
    with pytest.raises(RuntimeError, match='TPU v5 next'):
        bench._device_peak_tflops()


def test_device_memory_is_asked_not_assumed(monkeypatch):
    import bench
    assert bench._device_hbm_bytes() > 1 << 30     # the CPU device: host RAM
    _fake_devices(monkeypatch, platform='tpu', device_kind='TPU v5 lite',
                  memory_stats=lambda: {'bytes_limit': 123})
    assert bench._device_hbm_bytes() == 123
    _fake_devices(monkeypatch, platform='tpu', device_kind='TPU v5 lite',
                  memory_stats=lambda: None)
    with pytest.raises(RuntimeError, match='no memory capacity'):
        bench._device_hbm_bytes()      # used to return 16 GiB
