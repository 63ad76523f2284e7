"""Real ``jax.distributed`` 2-process cluster on CPU.

Closes the last monkeypatch gap in the multi-host story: `sync_hosts`,
`min_over_hosts`, `host_shard_info`, and `epoch_steps` run over an actual
distributed runtime (coordinator + 2 processes, cross-process CPU
collectives), not a faked ``jax.process_index``.  The scenario is the
SURVEY.md §7 deadlock risk end-to-end: an uneven row-group layout where the
rank with the larger shard must stop at the common step budget, verified by
a real per-step ``psum`` that would hang forever if the budgets diverged.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from test_common import create_test_dataset

_CHILD = r'''
import json, sys
import jax

coordinator, rank, url, batch_size = (
    sys.argv[1], int(sys.argv[2]), sys.argv[3], int(sys.argv[4]))
jax.distributed.initialize(coordinator_address=coordinator,
                           num_processes=2, process_id=rank)

import numpy as np
from itertools import islice

import jax.experimental.multihost_utils  # used per-step in the loop below

from petastorm_tpu import make_reader
from petastorm_tpu.jax import DataLoader
from petastorm_tpu.parallel import (epoch_steps, host_shard_info,
                                    min_over_hosts, sync_hosts)

assert jax.process_count() == 2, jax.process_count()
pi, pc = host_shard_info()
assert (pi, pc) == (rank, 2), (pi, pc)

# Real cross-process reduction: ranks contribute different values.
assert min_over_hosts(7 if rank == 0 else 3) == 3
sync_hosts('test-barrier')

# Reader auto-shards by process identity (no explicit cur_shard).
with make_reader(url, schema_fields=['id'], reader_pool_type='dummy',
                 shuffle_row_groups=False, num_epochs=1) as reader:
    budget = epoch_steps(reader, batch_size)       # min over hosts inside
    loader = DataLoader(reader, batch_size=batch_size, drop_last=True)
    ids, steps = [], 0
    devices = jax.devices()
    for batch in islice(loader, budget):
        ids.extend(np.asarray(batch['id']).tolist())
        # A collective every step: if one rank had a bigger budget, this
        # would deadlock (the test's timeout is the failure detector).
        total = jax.experimental.multihost_utils.process_allgather(
            np.asarray(steps))
        assert (total == steps).all()
        steps += 1

sync_hosts('epoch-done')
print('RESULT ' + json.dumps({'rank': rank, 'steps': steps, 'ids': ids,
                              'budget': int(budget)}))
'''


def _free_port():
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


@pytest.mark.timeout(300)
def test_two_process_jax_distributed_epoch(tmp_path):
    # Uneven layout: 5 row groups of 4 rows -> rank0 gets 3 groups (12 rows),
    # rank1 gets 2 (8 rows). batch 4 -> budgets 3 vs 2; common budget 2.
    dataset = create_test_dataset('file://' + str(tmp_path / 'dist'),
                                  num_rows=20, rows_per_rowgroup=4)
    coordinator = '127.0.0.1:%d' % _free_port()
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'
    env['PYTHONPATH'] = os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))

    procs = [subprocess.Popen(
        [sys.executable, '-c', _CHILD, coordinator, str(rank),
         dataset.url, '4'],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env)
        for rank in range(2)]
    results = {}
    try:
        for proc in procs:
            out, err = proc.communicate(timeout=240)
            assert proc.returncode == 0, 'child failed:\n%s\n%s' % (out, err)
            payload = [l for l in out.splitlines() if l.startswith('RESULT ')]
            assert payload, out
            result = json.loads(payload[0][len('RESULT '):])
            results[result['rank']] = result
    finally:
        # A deadlocked collective (the failure this test exists to catch)
        # must not leak spinning children holding the coordinator port.
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait()

    # Identical budgets == the collective-hang guard held.
    assert results[0]['budget'] == results[1]['budget'] == 2
    assert results[0]['steps'] == results[1]['steps'] == 2
    # Disjoint shards (completeness is deliberately bounded: drop_last
    # discards the ragged tail beyond the common budget).
    seen0, seen1 = set(results[0]['ids']), set(results[1]['ids'])
    assert not (seen0 & seen1)
    assert len(seen0) == len(seen1) == 8  # 2 steps x batch 4 each
