"""``enable_compile_cache``: one helper for chip_smoke.py and the
example mains.  The directory can be placed from outside
(``JAX_COMPILATION_CACHE_DIR``); otherwise it is one fixed path inside the
checkout — never one built from ``tempfile``, a pid or the time, because the
path is part of what a cache hit depends on.  Each case runs in a fresh
interpreter: the setting is read before a process's first compilation.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_BODY = '''
import json, os, sys
import jax, jax.numpy as jnp
from petastorm_tpu.utils import COMPILE_CACHE_DIR, enable_compile_cache
returned = enable_compile_cache()
if sys.argv[1] == 'compile':
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(8.0)).block_until_ready()
print(json.dumps({
    'returned': returned, 'configured': jax.config.jax_compilation_cache_dir,
    'fixed': COMPILE_CACHE_DIR, 'pid': os.getpid(),
    'min_compile_secs': jax.config.jax_persistent_cache_min_compile_time_secs,
}))
'''


def _run(mode, cache_dir=None):
    env = dict(os.environ, JAX_PLATFORMS='cpu', PYTHONPATH=os.pathsep.join(
        p for p in (REPO, os.environ.get('PYTHONPATH')) if p))
    env.pop('JAX_COMPILATION_CACHE_DIR', None)
    if cache_dir is not None:
        env['JAX_COMPILATION_CACHE_DIR'] = cache_dir
    res = subprocess.run([sys.executable, '-c', _BODY, mode], env=env,
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_env_places_the_cache_and_no_other_directory_is_set(tmp_path):
    placed = str(tmp_path / 'placed')
    out = _run('compile', cache_dir=placed)
    assert out['returned'] == out['configured'] == placed
    # thresholds lowered: even this tiny executable is cached, and there
    assert os.listdir(placed)
    assert out['min_compile_secs'] == 0


def test_unset_the_cache_is_one_fixed_path_inside_the_checkout():
    first, second = _run('config'), _run('config')
    assert first['pid'] != second['pid']
    assert first['returned'] == second['returned'] == first['configured'] \
        == os.path.join(REPO, '.jax_compile_cache')
    assert str(first['pid']) not in first['returned']
    # ignored by git: a run must not leave the tree dirty
    ignored = open(os.path.join(REPO, '.gitignore')).read().split()
    assert '.jax_compile_cache/' in ignored


def test_every_entry_point_uses_the_one_helper():
    """chip_smoke.py and the example mains call the helper; no file of the
    repo sets a cache directory of its own."""
    callers = ['chip_smoke.py']
    for root, _, files in os.walk(os.path.join(REPO, 'examples')):
        callers += [os.path.relpath(os.path.join(root, f), REPO)
                    for f in files if f.endswith('.py')
                    and 'ensure_jax_backend()' in open(
                        os.path.join(root, f)).read()]
    assert len(callers) == 9, callers
    for path in callers:
        assert 'enable_compile_cache()' in open(os.path.join(REPO, path)).read(), path
    setters = []
    for top in ('petastorm_tpu', 'examples', 'chip_smoke.py',
                '__graft_entry__.py'):
        paths = [os.path.join(REPO, top)] if top.endswith('.py') else [
            os.path.join(r, f) for r, _, fs in os.walk(os.path.join(REPO, top))
            for f in fs if f.endswith('.py')]
        setters += [os.path.relpath(p, REPO) for p in paths
                    if 'jax_compilation_cache_dir' in open(p).read()]
    assert setters == [os.path.join('petastorm_tpu', 'utils', '__init__.py')]
