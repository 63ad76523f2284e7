"""General helpers shared across layers.

Parity: reference ``petastorm/utils.py :: decode_row, run_in_subprocess``.
"""

import os
import pickle
import subprocess
import sys

from petastorm_tpu.errors import DecodeFieldError

__all__ = ['decode_row', 'run_in_subprocess', 'ensure_jax_backend',
           'apply_jax_platforms_env', 'enable_compile_cache']

#: Where compiled programs persist when ``JAX_COMPILATION_CACHE_DIR`` is not
#: set: one fixed path inside the checkout (ignored by git).  Fixed because
#: the path is part of what a cache hit depends on — a directory named after
#: a pid, the time or ``tempfile`` is a cache that never hits.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    '.jax_compile_cache')


def apply_jax_platforms_env():
    """Apply an explicit ``JAX_PLATFORMS`` env var via ``jax.config``.

    JAX reads the variable once, at import; a launcher that sets it after
    ``import jax`` (or a test that re-pins it) needs the config updated to
    get the platform it asked for.  No-op when the var is unset.
    """
    import jax
    if os.environ.get('JAX_PLATFORMS'):
        jax.config.update('jax_platforms', os.environ['JAX_PLATFORMS'])


def ensure_jax_backend():
    """Initialize the JAX backend this process asked for; returns
    ``jax.devices()``.

    Applies ``JAX_PLATFORMS`` (see :func:`apply_jax_platforms_env`) and calls
    ``jax.devices()`` in this process.  Whatever that raises propagates: a
    program that asked for an accelerator and cannot reach it must fail, not
    continue on another platform under the same name.

    Call this BEFORE any other JAX use but AFTER ``jax.distributed``
    initialization if you use one — it initializes the backend.
    No reference equivalent (torch device selection is implicit there).
    """
    import jax
    apply_jax_platforms_env()
    return jax.devices()


def enable_compile_cache():
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` places it from outside and no other
    directory is then set; unset, it is :data:`COMPILE_CACHE_DIR`.  The
    minimum-compile-time threshold drops to zero so the small executables of
    the data path (unpack, gather, augment) are cached beside the step.
    Call before the first compilation.
    """
    import jax
    cache_dir = os.environ.get('JAX_COMPILATION_CACHE_DIR') or COMPILE_CACHE_DIR
    jax.config.update('jax_compilation_cache_dir', cache_dir)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    return cache_dir


def decode_row(row, schema):
    """Decode all cells of an encoded row dict through their field codecs.

    Parity: ``petastorm/utils.py :: decode_row``.  Runs inside L2 reader
    workers — the per-row CPU hot path.
    """
    decoded = {}
    for name, value in row.items():
        field = schema.fields.get(name)
        if field is None:
            continue
        if value is None:
            decoded[name] = None
            continue
        try:
            decoded[name] = field.codec_or_default.decode(field, value)
        except Exception as e:
            raise DecodeFieldError('Failed to decode field %r: %s' % (name, e)) from e
    return decoded


def run_in_subprocess(func, *args, **kwargs):
    """Run ``func(*args, **kwargs)`` in a fresh python subprocess and return
    its pickled result.

    Parity: ``petastorm/utils.py :: run_in_subprocess``.  Used by ETL helpers
    that must not pollute the parent interpreter (e.g. metadata regeneration).
    """
    payload = pickle.dumps((func, args, kwargs))
    program = (
        'import pickle, sys\n'
        'func, args, kwargs = pickle.loads(sys.stdin.buffer.read())\n'
        'sys.stdout.buffer.write(pickle.dumps(func(*args, **kwargs)))\n'
    )
    proc = subprocess.run([sys.executable, '-c', program], input=payload,
                          stdout=subprocess.PIPE, check=True)
    return pickle.loads(proc.stdout)
