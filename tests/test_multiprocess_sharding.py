"""True multi-process multi-host simulation (round-1 VERDICT missing #4).

Each simulated host is a REAL child interpreter (no monkeypatched
``jax.process_index``): it builds its own reader + ``jax.DataLoader`` over
the shared dataset with explicit ``cur_shard``/``shard_count`` (the exact
calls ``_jax_default_shard`` would make from the process topology — SURVEY.md
§2.6 DP row), reports its shard contents and step budget, then runs a
bounded epoch.  The parent asserts the three multi-host invariants over an
**uneven** row-group layout:

* shard **disjointness** — no row is seen by two hosts;
* union **completeness** — every row is seen by exactly one host;
* identical bounded **step counts** — every host can take exactly
  ``min(local_steps)`` full batches (the collective-hang guard that
  ``parallel.epoch_steps`` + ``min_over_hosts`` implement): the host with
  the SMALLEST shard still completes, and no host needs more data than its
  shard holds.
"""

import json
import os
import subprocess
import sys

import pytest

from tests.test_common import create_test_dataset

_CHILD = r'''
import json, sys
import jax
jax.config.update('jax_platforms', 'cpu')

url, shard, shard_count, batch_size, budget = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]),
    int(sys.argv[5]))

from itertools import islice
from petastorm_tpu import make_reader
from petastorm_tpu.jax import DataLoader

with make_reader(url, cur_shard=shard, shard_count=shard_count,
                 reader_pool_type='thread', workers_count=2,
                 shuffle_row_groups=False, num_epochs=1) as reader:
    local_rows = reader.num_local_rows()
    local_steps = local_rows // batch_size
    loader = DataLoader(reader, batch_size=batch_size)
    ids = []
    batches = 0
    take = budget if budget >= 0 else local_steps
    for batch in islice(iter(loader), take):
        ids.extend(int(i) for i in batch['id'])
        batches += 1
print(json.dumps({'shard': shard, 'local_rows': local_rows,
                  'local_steps': local_steps, 'batches': batches,
                  'ids': ids}))
'''


def _run_hosts(url, shard_count, batch_size, budget):
    """Launch one child interpreter per simulated host, in parallel."""
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'
    env['PYTHONPATH'] = os.pathsep.join(
        [p for p in [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     env.get('PYTHONPATH')] if p])
    procs = [subprocess.Popen(
        [sys.executable, '-c', _CHILD, url, str(shard), str(shard_count),
         str(batch_size), str(budget)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, env=env)
        for shard in range(shard_count)]
    results = []
    try:
        for proc in procs:
            out, err = proc.communicate(timeout=180)
            assert proc.returncode == 0, 'host process failed:\n%s' % err[-4000:]
            results.append(json.loads(out.strip().splitlines()[-1]))
    finally:
        # One hung/failed child must not leak the siblings into the session.
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    return sorted(results, key=lambda r: r['shard'])


@pytest.fixture(scope='module')
def uneven_dataset(tmp_path_factory):
    # 70 rows at 8 rows/row-group -> 9 row groups (last ragged at 6 rows);
    # 3 shards x 3 row groups, but shard 2 gets the ragged group: local row
    # counts 24/24/22 — the exact uneven layout that hangs naive pjit loops.
    url = 'file://' + str(tmp_path_factory.mktemp('mphosts') / 'ds')
    return create_test_dataset(url, num_rows=70, rows_per_rowgroup=8)


def test_shards_disjoint_and_complete_across_real_processes(uneven_dataset):
    results = _run_hosts(uneven_dataset.url, shard_count=3, batch_size=8,
                         budget=-1)
    all_ids = [set(r['ids']) for r in results]
    assert [r['local_rows'] for r in results] == [24, 24, 22]
    for i in range(len(all_ids)):
        for j in range(i + 1, len(all_ids)):
            assert not (all_ids[i] & all_ids[j]), 'shards overlap'
    union = set().union(*all_ids)
    # budget=-1 drains each host's full-batch budget; the sub-batch tail
    # rows (drop_last) are the only ones unseen.
    full_batches_rows = sum(r['batches'] * 8 for r in results)
    assert len(union) == full_batches_rows
    assert union <= set(range(70))


def test_all_rows_covered_without_batching(uneven_dataset):
    """batch_size=1, full drain: union must be EXACTLY the 70 written rows."""
    results = _run_hosts(uneven_dataset.url, shard_count=3, batch_size=1,
                         budget=-1)
    union = set()
    for r in results:
        union.update(r['ids'])
    assert union == set(range(70))
    assert sum(r['local_rows'] for r in results) == 70


def test_min_budget_completes_identically_on_every_host(uneven_dataset):
    """The collective-hang guard: with the min-over-hosts step budget every
    host takes EXACTLY that many steps — including the smallest shard."""
    probe = _run_hosts(uneven_dataset.url, shard_count=3, batch_size=8,
                       budget=0)
    local_steps = [r['local_steps'] for r in probe]
    assert local_steps == [3, 3, 2]  # uneven: the guard is load-bearing
    budget = min(local_steps)

    results = _run_hosts(uneven_dataset.url, shard_count=3, batch_size=8,
                         budget=budget)
    assert [r['batches'] for r in results] == [budget] * 3
    # And the per-host ids are still disjoint under the bounded run.
    seen = [set(r['ids']) for r in results]
    assert all(len(s) == budget * 8 for s in seen)
    for i in range(3):
        for j in range(i + 1, 3):
            assert not (seen[i] & seen[j])


_ELASTIC_CHECKPOINT_CHILD = r'''
import json, sys
import jax
jax.config.update('jax_platforms', 'cpu')

url, shard, shard_count, consume = (
    sys.argv[1], int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4]))

from petastorm_tpu import make_reader

reader = make_reader(url, cur_shard=shard, shard_count=shard_count,
                     reader_pool_type='thread', workers_count=2,
                     shuffle_row_groups=True, seed=13, num_epochs=1)
ids = []
it = iter(reader)
for _ in range(consume):
    ids.append(int(next(it).id))
ids.extend(int(r.id) for r in reader.drain_in_flight())
state = reader.state_dict()
reader.stop(); reader.join()
print(json.dumps({'shard': shard, 'ids': ids, 'state': state}))
'''

_ELASTIC_RESUME_CHILD = r'''
import json, sys
import jax
jax.config.update('jax_platforms', 'cpu')

url, shard, shard_count = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
token = json.loads(sys.argv[4])

from petastorm_tpu import make_reader

with make_reader(url, cur_shard=shard, shard_count=shard_count,
                 reader_pool_type='thread', workers_count=2,
                 shuffle_row_groups=True, seed=13, num_epochs=1,
                 resume_state=token) as reader:
    ids = [int(r.id) for r in reader]
print(json.dumps({'shard': shard, 'ids': ids}))
'''


def _spawn(child, args):
    env = dict(os.environ)
    env['JAX_PLATFORMS'] = 'cpu'
    env['PYTHONPATH'] = os.pathsep.join(
        [p for p in [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     env.get('PYTHONPATH')] if p])
    return subprocess.Popen([sys.executable, '-c', child] + [str(a) for a in args],
                            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                            text=True, env=env)


def test_elastic_reshard_across_real_processes(uneven_dataset):
    """The pod-resize flow over REAL interpreters: 3 hosts checkpoint
    (uneven progress), the coordinator reshards their tokens to 2 hosts,
    2 fresh interpreters finish the epoch — every row delivered exactly
    once across both topologies (thread pools, drained tokens)."""
    from collections import Counter

    from petastorm_tpu.elastic import reshard_reader_states

    procs = [_spawn(_ELASTIC_CHECKPOINT_CHILD,
                    [uneven_dataset.url, shard, 3, 3 + 2 * shard])
             for shard in range(3)]
    consumed = []
    states = []
    for proc in procs:
        out, err = proc.communicate(timeout=180)
        assert proc.returncode == 0, 'checkpoint host failed:\n%s' % err[-4000:]
        payload = json.loads(out.strip().splitlines()[-1])
        consumed.extend(payload['ids'])
        states.append(payload['state'])

    tokens = reshard_reader_states(states, 2)  # tokens arrived via JSON
    procs = [_spawn(_ELASTIC_RESUME_CHILD,
                    [uneven_dataset.url, m, 2, json.dumps(tokens[m])])
             for m in range(2)]
    for proc in procs:
        out, err = proc.communicate(timeout=180)
        assert proc.returncode == 0, 'resume host failed:\n%s' % err[-4000:]
        consumed.extend(json.loads(out.strip().splitlines()[-1])['ids'])

    assert Counter(consumed) == Counter({i: 1 for i in range(70)})
