"""``ensure_jax_backend`` has one job: apply ``JAX_PLATFORMS``, initialize
the backend in THIS process, and raise what that raises.

A program that asked for an accelerator and cannot reach it must fail — not
probe from a child, not continue on the CPU under the same name.  Each case
runs in a fresh interpreter where the backend is not yet initialized
(in-process the conftest has already locked in the CPU backend).

No reference equivalent (the reference's torch examples pick devices
implicitly).
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_fresh(body, extra_env=None, timeout=120):
    env = dict(os.environ)
    env.pop('JAX_PLATFORMS', None)
    env['PYTHONPATH'] = os.pathsep.join(
        p for p in (REPO, env.get('PYTHONPATH')) if p)
    env.update(extra_env or {})
    return subprocess.run([sys.executable, '-c', body], env=env,
                          capture_output=True, text=True, timeout=timeout)


def _assert_ok(res):
    assert res.returncode == 0, res.stderr[-3000:]
    assert 'OK' in res.stdout


def test_returns_the_devices_of_the_platform_asked_for():
    body = (
        "import petastorm_tpu.utils as u\n"
        "devs = u.ensure_jax_backend()\n"
        "import jax\n"
        "assert devs == jax.devices() and devs[0].platform == 'cpu', devs\n"
        "print('OK')\n"
    )
    _assert_ok(_run_fresh(body, extra_env={'JAX_PLATFORMS': 'cpu'}))


def test_platform_that_cannot_initialize_raises():
    # No branch continues on the CPU: the process dies with JAX's own error.
    body = (
        "import petastorm_tpu.utils as u\n"
        "u.ensure_jax_backend()\n"
        "print('OK')\n"
    )
    res = _run_fresh(body, extra_env={'JAX_PLATFORMS': 'no_such_platform'})
    assert res.returncode != 0
    assert 'OK' not in res.stdout
    assert 'no_such_platform' in res.stderr


def test_backend_failure_propagates_and_leaves_the_platform_alone():
    body = (
        "import jax, os\n"
        "import petastorm_tpu.utils as u\n"
        "def devices():\n"
        "    raise RuntimeError('no accelerator')\n"
        "jax.devices = devices\n"
        "before = jax.config.jax_platforms\n"
        "try:\n"
        "    u.ensure_jax_backend()\n"
        "except RuntimeError as e:\n"
        "    assert 'no accelerator' in str(e)\n"
        "else:\n"
        "    raise AssertionError('backend failure was swallowed')\n"
        "assert 'JAX_PLATFORMS' not in os.environ\n"
        "assert jax.config.jax_platforms == before\n"
        "print('OK')\n"
    )
    _assert_ok(_run_fresh(body))


def test_starts_no_child_process():
    # One process for each chip: a child that initializes JAX would need
    # the chip its parent is about to hold.
    body = (
        "import subprocess\n"
        "def boom(*a, **k):\n"
        "    raise AssertionError('ensure_jax_backend started a child')\n"
        "subprocess.Popen = subprocess.run = boom\n"
        "import os\n"
        "os.fork = os.posix_spawn = boom\n"
        "import petastorm_tpu.utils as u\n"
        "assert u.ensure_jax_backend()\n"
        "print('OK')\n"
    )
    _assert_ok(_run_fresh(body, extra_env={'JAX_PLATFORMS': 'cpu'}))


def test_sets_no_environment_variable():
    body = (
        "import os\n"
        "import petastorm_tpu.utils as u\n"
        "before = dict(os.environ)\n"
        "u.ensure_jax_backend()\n"
        "assert dict(os.environ) == before\n"
        "print('OK')\n"
    )
    _assert_ok(_run_fresh(body, extra_env={'JAX_PLATFORMS': 'cpu'}))


def test_platform_set_after_import_is_applied():
    # jax reads JAX_PLATFORMS once, at import; a launcher that sets it later
    # still gets the platform it asked for.
    body = (
        "import jax, os\n"
        "assert not jax.config.jax_platforms\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "import petastorm_tpu.utils as u\n"
        "u.apply_jax_platforms_env()\n"
        "assert jax.config.jax_platforms == 'cpu'\n"
        "assert u.ensure_jax_backend()[0].platform == 'cpu'\n"
        "print('OK')\n"
    )
    _assert_ok(_run_fresh(body))


def test_unset_platform_leaves_the_config_alone():
    body = (
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import petastorm_tpu.utils as u\n"
        "u.apply_jax_platforms_env()\n"
        "assert jax.config.jax_platforms == 'cpu'\n"
        "print('OK')\n"
    )
    _assert_ok(_run_fresh(body))


def test_probe_and_cpu_switch_surface_is_gone():
    import inspect

    import petastorm_tpu.utils as u
    for name in ('_backend_probe_ok', '_fall_back', '_backend_initialized',
                 '_non_cpu_backend_possible', '_PROBE_CHILD_CODE'):
        assert not hasattr(u, name), name
    assert not inspect.signature(u.ensure_jax_backend).parameters
    src = inspect.getsource(u.ensure_jax_backend)
    assert 'subprocess' not in src and 'except' not in src


def test_default_shard_reraises_a_backend_failure(monkeypatch):
    """A backend that fails to initialize must not read as "one host": every
    host of a pod would then be fed the whole dataset."""
    import jax
    import pytest

    from petastorm_tpu import reader

    def process_count():
        raise RuntimeError('Unable to initialize backend')

    monkeypatch.setattr(jax, 'process_count', process_count)
    with pytest.raises(RuntimeError, match='Unable to initialize backend'):
        reader._jax_default_shard()
    monkeypatch.undo()
    assert reader._jax_default_shard() == (None, None)   # one CPU host
