"""Spawn a function in a brand-new python interpreter (not fork).

Parity: reference ``petastorm/workers_pool/exec_in_new_process.py ::
exec_in_new_process`` — a fresh ``exec`` dodges fork-unsafe state (grpc/JAX
runtime threads, opened TPU clients) that a forked child would inherit;
exactly the states a TPU-VM host process is full of.
"""

import os
import pickle
import subprocess
import sys
import tempfile


def exec_in_new_process(func, *args, **kwargs):
    """Start ``func(*args, **kwargs)`` in a new interpreter; returns Popen.

    The callable and arguments must be picklable and importable by path
    (no lambdas/closures).
    """
    fd, payload_path = tempfile.mkstemp(suffix='.pkl', prefix='pstpu_spawn_')
    try:
        with os.fdopen(fd, 'wb') as f:
            pickle.dump((func, args, kwargs, sys.path), f, protocol=4)
        program = (
            'import pickle, sys\n'
            'with open(sys.argv[1], "rb") as f:\n'
            '    func, args, kwargs, parent_path = pickle.load(f)\n'
            'import os; os.remove(sys.argv[1])\n'
            'sys.path[:0] = [p for p in parent_path if p not in sys.path]\n'
            'func(*args, **kwargs)\n'
        )
        env = dict(os.environ)
        # Child processes are pure CPU decode workers: a chip belongs to
        # one process, and that process is the parent.
        env['JAX_PLATFORMS'] = 'cpu'
        return subprocess.Popen([sys.executable, '-c', program, payload_path],
                                env=env)
    except BaseException:
        # The spawned child owns (and removes) the payload file; until the
        # spawn succeeds it is still ours — a failed pickle.dump or Popen
        # must not leak it (lint resource-lifecycle).
        os.unlink(payload_path)
        raise
