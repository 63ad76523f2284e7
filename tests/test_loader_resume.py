"""Exact mid-epoch loader resume (SURVEY §5.4 build obligation).

The contract under test: ``DataLoader.state_dict()`` at step k, restore in
a FRESH PROCESS, and the resumed loader yields exactly what the
uninterrupted run had left — the same row multiset for concurrent pools
(thread/process: delivery order is scheduling-dependent), and the same
batch-for-batch order for deterministic seeded runs (dummy pool).

Exactness needs more than the reader's row-group token: the snapshot
drains in-flight results (which the bare token would replay or lose),
and captures the shuffling buffer (+ rng state), the partial batch, the
prefetched device batches, and the packer residue.
"""

import os
import pickle
import subprocess
import sys

import numpy as np
import pytest

from petastorm_tpu import make_batch_reader, make_reader
from petastorm_tpu.jax import DataLoader, PackedDataLoader

from test_common import assert_iteration_path, create_test_dataset

BATCH = 10
ROWS = 64


@pytest.fixture(scope='module')
def dataset(tmp_path_factory):
    path = tmp_path_factory.mktemp('resumeds')
    return create_test_dataset('file://' + str(path), num_rows=ROWS,
                               rows_per_rowgroup=8)


def _reader(url, pool, **kw):
    kw.setdefault('num_epochs', 2)
    kw.setdefault('shuffle_row_groups', True)
    kw.setdefault('seed', 7)
    if pool != 'dummy':
        kw.setdefault('workers_count', 3)
    return make_reader(url, reader_pool_type=pool, **kw)


_CHILD = r"""
import pickle, sys
import numpy as np
import jax
jax.config.update('jax_platforms', 'cpu')
payload = pickle.load(open(sys.argv[1], 'rb'))
sys.path.insert(0, payload['repo'])
sys.path.insert(0, payload['testdir'])
from petastorm_tpu import make_reader
from petastorm_tpu.jax import DataLoader

state = payload['state']
kw = dict(payload['reader_kwargs'])
reader = make_reader(payload['url'], resume_state=state['reader'], **kw)
loader = DataLoader(reader, batch_size=payload['batch'],
                    resume_state=state, **payload['loader_kwargs'])
with loader:
    ids = [np.asarray(b['id']).tolist() for b in loader]
pickle.dump(ids, open(sys.argv[2], 'wb'))
"""


def _resume_in_fresh_process(tmp_path, dataset, state, pool, reader_kwargs,
                             loader_kwargs):
    payload = {
        'repo': os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        'testdir': os.path.dirname(os.path.abspath(__file__)),
        'url': dataset.url,
        'state': state,
        'batch': BATCH,
        'reader_kwargs': dict({'reader_pool_type': pool, 'num_epochs': 2,
                               'shuffle_row_groups': True, 'seed': 7},
                              **reader_kwargs),
        'loader_kwargs': loader_kwargs,
    }
    if pool != 'dummy':
        payload['reader_kwargs'].setdefault('workers_count', 3)
    pin = tmp_path / 'payload.pkl'
    pout = tmp_path / 'out.pkl'
    with open(pin, 'wb') as f:
        pickle.dump(payload, f)
    script = tmp_path / 'child.py'
    script.write_text(_CHILD)
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    res = subprocess.run([sys.executable, str(script), str(pin), str(pout)],
                         capture_output=True, text=True, timeout=300, env=env)
    assert res.returncode == 0, res.stderr[-3000:]
    with open(pout, 'rb') as f:
        return pickle.load(f)


def _run_uninterrupted(dataset, pool, loader_kwargs):
    with DataLoader(_reader(dataset.url, pool), batch_size=BATCH,
                    **loader_kwargs) as loader:
        return [np.asarray(b['id']).tolist() for b in loader]


def _run_interrupted(dataset, pool, k, loader_kwargs):
    reader = _reader(dataset.url, pool)
    loader = DataLoader(reader, batch_size=BATCH, **loader_kwargs)
    consumed = []
    it = iter(loader)
    for _ in range(k):
        consumed.append(np.asarray(next(it)['id']).tolist())
    state = loader.state_dict()
    # simulate the crash: abandon this loader entirely
    reader.stop()
    reader.join()
    return consumed, state


@pytest.mark.parametrize('pool', ['dummy', 'thread', 'process'])
def test_multiset_exactness_across_pools(dataset, pool, tmp_path, transfer):
    """consumed ⊎ resumed == every row exactly twice (2 epochs) — nothing
    lost, nothing doubled, even with rows in flight in the pool at snapshot
    time.  drop_last=False so the invariant is order-independent (with a
    concurrent pool the *which-rows-land-in-the-tail* varies per run)."""
    loader_kwargs = {'seed': 5, 'shuffling_queue_capacity': 24,
                     'drop_last': False, 'transfer': transfer}
    consumed, state = _run_interrupted(dataset, pool, 3, loader_kwargs)
    resumed = _resume_in_fresh_process(tmp_path, dataset, state, pool, {},
                                       loader_kwargs)
    got = sorted(sum(consumed, []) + sum(resumed, []))
    assert got == sorted(list(range(ROWS)) * 2)


def test_exact_order_for_seeded_dummy_pool(dataset, tmp_path, transfer):
    """Deterministic pipeline: the resumed stream must be batch-for-batch
    identical to what the uninterrupted run had left."""
    loader_kwargs = {'seed': 5, 'shuffling_queue_capacity': 24,
                     'transfer': transfer}
    full = _run_uninterrupted(dataset, 'dummy', loader_kwargs)
    consumed, state = _run_interrupted(dataset, 'dummy', 3, loader_kwargs)
    assert consumed == full[:3]
    resumed = _resume_in_fresh_process(tmp_path, dataset, state, 'dummy', {},
                                       loader_kwargs)
    assert resumed == full[3:]


def test_resume_without_shuffle_buffer(dataset, tmp_path, transfer):
    loader_kwargs = {'transfer': transfer}
    full = _run_uninterrupted(dataset, 'dummy', loader_kwargs)
    consumed, state = _run_interrupted(dataset, 'dummy', 2, loader_kwargs)
    resumed = _resume_in_fresh_process(tmp_path, dataset, state, 'dummy', {},
                                       loader_kwargs)
    assert consumed + resumed == full


def test_checkpoint_then_keep_training(dataset, transfer):
    """state_dict must not disturb the live run: the in-process stream
    continues exactly as if no snapshot had been taken."""
    loader_kwargs = {'seed': 5, 'shuffling_queue_capacity': 24,
                     'transfer': transfer}
    full = _run_uninterrupted(dataset, 'dummy', loader_kwargs)
    reader = _reader(dataset.url, 'dummy')
    with DataLoader(reader, batch_size=BATCH, **loader_kwargs) as loader:
        it = iter(loader)
        got = [np.asarray(next(it)['id']).tolist() for _ in range(3)]
        loader.state_dict()   # snapshot mid-stream ...
        for b in it:          # ... and keep consuming
            got.append(np.asarray(b['id']).tolist())
    assert_iteration_path(loader, transfer)
    assert got == full


def test_columnar_reader_resume(dataset, tmp_path, transfer):
    """make_batch_reader path: chunk residue rides the snapshot."""
    with DataLoader(make_batch_reader(dataset.url, reader_pool_type='dummy',
                                      shuffle_row_groups=False, num_epochs=1),
                    batch_size=BATCH, transfer=transfer) as loader:
        full = [np.asarray(b['id']).tolist() for b in loader]

    reader = make_batch_reader(dataset.url, reader_pool_type='dummy',
                               shuffle_row_groups=False, num_epochs=1)
    loader = DataLoader(reader, batch_size=BATCH, transfer=transfer)
    it = iter(loader)
    consumed = [np.asarray(next(it)['id']).tolist() for _ in range(2)]
    state = loader.state_dict()
    reader.stop()
    reader.join()

    payload_kwargs = {'reader_pool_type': 'dummy', 'shuffle_row_groups': False,
                      'num_epochs': 1}
    # child uses make_reader; drive make_batch_reader inline instead
    reader2 = make_batch_reader(dataset.url, resume_state=state['reader'],
                                **payload_kwargs)
    with DataLoader(reader2, batch_size=BATCH, resume_state=state,
                    transfer=transfer) as loader2:
        resumed = [np.asarray(b['id']).tolist() for b in loader2]
    assert_iteration_path(loader2, transfer)
    assert consumed + resumed == full


class _SeqReader:
    """Adapt dataset rows to variable-length int sequences (len = id%13+1)
    while forwarding the exact-checkpoint reader protocol."""

    num_epochs = 1
    ngram = None
    batched_output = False

    def __init__(self, inner):
        self._inner = inner

    @staticmethod
    def _to_seq(row):
        rid = int(row.id)
        return {'tokens': np.full(rid % 13 + 1, rid, np.int32)}

    def __iter__(self):
        return (self._to_seq(row) for row in self._inner)

    def drain_in_flight(self):
        return [self._to_seq(r) for r in self._inner.drain_in_flight()]

    def resume_dispatch(self):
        self._inner.resume_dispatch()

    def state_dict(self):
        return self._inner.state_dict()

    def stop(self):
        self._inner.stop()

    def join(self):
        self._inner.join()


def test_packed_loader_resume_preserves_tokens(dataset, transfer):
    """Packer residue (open rows) must survive: token multiset across the
    remaining packed batches equals the uninterrupted run's remainder."""
    def seqs_of(batches):
        toks = []
        for b in batches:
            t, s = np.asarray(b['tokens']), np.asarray(b['segment_ids'])
            toks.extend(t[s > 0].tolist())
        return sorted(toks)

    def build_loader(resume=None, reader_resume=None):
        reader = _SeqReader(make_reader(
            dataset.url, reader_pool_type='dummy', shuffle_row_groups=False,
            num_epochs=1, resume_state=reader_resume))
        return reader, PackedDataLoader(reader, 'tokens', max_len=16,
                                        rows_per_batch=4, drop_last=False,
                                        resume_state=resume,
                                        transfer=transfer)

    _, loader = build_loader()
    with loader:
        full = seqs_of(list(loader))

    wrapped, loader = build_loader()
    it = iter(loader)
    consumed = [next(it) for _ in range(2)]
    state = loader.state_dict()
    wrapped.stop()
    wrapped.join()

    _, loader2 = build_loader(resume=state, reader_resume=state['reader'])
    with loader2:
        resumed = list(loader2)
    assert_iteration_path(loader2, transfer)
    assert seqs_of(consumed + resumed) == full


def test_disk_cached_loader_exact_resume(dataset, tmp_path):
    """DiskCachedDataLoader: (epoch, offset, order, rng) over the on-disk
    cache gives exact order-preserving resume regardless of pool type."""
    from petastorm_tpu.jax import DiskCachedDataLoader

    cache = str(tmp_path / 'dcache')

    def build(resume=None):
        reader = make_reader(dataset.url, reader_pool_type='thread',
                             workers_count=3, shuffle_row_groups=False,
                             num_epochs=1)
        return DiskCachedDataLoader(reader, batch_size=BATCH,
                                    decoded_cache_dir=cache, num_epochs=3,
                                    seed=11, resume_state=resume)

    with build() as loader:
        full = [np.asarray(b['id']).tolist() for b in loader]

    # epoch 0 rebuilds nothing (cache complete); interrupt inside epoch 2
    with build() as loader:
        it = iter(loader)
        consumed = [np.asarray(next(it)['id']).tolist() for _ in range(9)]
        state = loader.state_dict()

    state = pickle.loads(pickle.dumps(state))   # fresh-process equivalence
    with build(resume=state) as loader2:
        resumed = [np.asarray(b['id']).tolist() for b in loader2]

    # The second loader serves all 3 epochs from the complete cache with
    # the same seed, so its uninterrupted stream would be cache epochs
    # 1..3-equivalent; compare against its own uninterrupted twin instead.
    with build() as loader3:
        twin = [np.asarray(b['id']).tolist() for b in loader3]
    assert consumed + resumed == twin



def test_state_dict_before_first_batch_preserves_restored_state(
        dataset, tmp_path, transfer):
    """A checkpoint-every-N loop can land right after a restore, before the
    first next(): the re-snapshot must carry the restored rows forward, not
    silently drop them."""
    loader_kwargs = {'seed': 5, 'shuffling_queue_capacity': 24,
                     'drop_last': False, 'transfer': transfer}
    consumed, state = _run_interrupted(dataset, 'dummy', 3, loader_kwargs)

    # restore, immediately re-checkpoint without consuming anything
    reader = make_reader(dataset.url, reader_pool_type='dummy', num_epochs=2,
                         shuffle_row_groups=True, seed=7,
                         resume_state=state['reader'])
    loader = DataLoader(reader, batch_size=BATCH,
                        resume_state=state, **loader_kwargs)
    state2 = loader.state_dict()
    reader.stop()
    reader.join()

    resumed = _resume_in_fresh_process(tmp_path, dataset, state2, 'dummy', {},
                                       loader_kwargs)
    got = sorted(sum(consumed, []) + sum(resumed, []))
    assert got == sorted(list(range(ROWS)) * 2)


def test_weighted_sampling_reader_resume_multiset(dataset, tmp_path,
                                                  transfer):
    """The mixed stream checkpoints too: constituent tokens + the draw
    rng + surviving-reader set.  exhaust='drop' delivers every row of
    every constituent exactly once, so consumed + resumed must equal the
    full union (exhaust='stop' truncates at a draw-aligned point that
    draining legitimately shifts — see state_dict docstring)."""
    from petastorm_tpu.weighted_sampling_reader import WeightedSamplingReader

    path2 = tmp_path / 'ds2'
    ds2 = create_test_dataset('file://' + str(path2), num_rows=32,
                              rows_per_rowgroup=8)

    def build(mix_resume=None):
        tokens = (mix_resume or {}).get('constituents', [None, None])
        r1 = make_reader(dataset.url, reader_pool_type='dummy',
                         shuffle_row_groups=False, num_epochs=1,
                         resume_state=tokens[0])
        r2 = make_reader(ds2.url, reader_pool_type='dummy',
                         shuffle_row_groups=False, num_epochs=1,
                         resume_state=tokens[1])
        return WeightedSamplingReader([r1, r2], [0.7, 0.3], seed=13,
                                      exhaust='drop', resume_state=mix_resume)

    full = sorted(list(range(64)) + list(range(32)))

    mixed = build()
    loader = DataLoader(mixed, batch_size=8, drop_last=False,
                        transfer=transfer)
    it = iter(loader)
    consumed = [int(x) for _ in range(2) for x in np.asarray(next(it)['id'])]
    state = pickle.loads(pickle.dumps(loader.state_dict()))
    mixed.stop()
    mixed.join()

    with DataLoader(build(mix_resume=state['reader']), batch_size=8,
                    drop_last=False, resume_state=state,
                    transfer=transfer) as loader2:
        resumed = [int(x) for b in loader2 for x in np.asarray(b['id'])]
    assert_iteration_path(loader2, transfer)
    assert sorted(consumed + resumed) == full


def test_inmem_deterministic_exact_resume(dataset):
    """InMemDataLoader(deterministic_cache_order=True): the content-sorted
    cache makes the epoch stream a pure function of (dataset, seed), so an
    exact mid-epoch token survives a rebuild through ANY pool — here the
    interrupted run caches via a thread pool and the resumed run via the
    dummy pool, the strongest order-scrambling the contract must absorb."""
    from petastorm_tpu.jax import InMemDataLoader

    def build(pool, resume=None):
        reader = make_reader(dataset.url, reader_pool_type=pool,
                             workers_count=3 if pool == 'thread' else 10,
                             shuffle_row_groups=(pool == 'thread'),
                             num_epochs=1)
        return InMemDataLoader(reader, batch_size=BATCH, num_epochs=3,
                               seed=11, deterministic_cache_order=True,
                               resume_state=resume)

    with build('thread') as loader:
        full = [np.asarray(b['id']).tolist() for b in loader]
    assert len(full) == 3 * (ROWS // BATCH)

    with build('thread') as loader:
        it = iter(loader)
        consumed = [np.asarray(next(it)['id']).tolist() for _ in range(8)]
        state = loader.state_dict()

    state = pickle.loads(pickle.dumps(state))  # fresh-process equivalence
    with build('dummy', resume=state) as loader2:
        resumed = [np.asarray(b['id']).tolist() for b in loader2]

    assert consumed + resumed == full


def test_inmem_without_deterministic_order_still_refuses(dataset):
    from petastorm_tpu.jax import InMemDataLoader

    reader = make_reader(dataset.url, reader_pool_type='dummy', num_epochs=1)
    with InMemDataLoader(reader, batch_size=BATCH, num_epochs=1) as loader:
        next(iter(loader))
        with pytest.raises(NotImplementedError,
                           match='deterministic_cache_order'):
            loader.state_dict()


def test_device_inmem_epoch_boundary_resume(dataset):
    """DeviceInMemDataLoader: 'k epochs done' + the explicit seed fully
    determine the continuation; mid-epoch tokens are refused."""
    from petastorm_tpu.jax import DeviceInMemDataLoader

    def build(resume=None):
        reader = make_reader(dataset.url, reader_pool_type='dummy',
                             shuffle_row_groups=False, num_epochs=1)
        return DeviceInMemDataLoader(reader, batch_size=BATCH, num_epochs=3,
                                     seed=23, resume_state=resume)

    with build() as loader:
        full = [np.asarray(b['id']).tolist() for b in loader]
    steps_per_epoch = ROWS // BATCH

    with build() as loader:
        it = iter(loader)
        consumed = []
        for _ in range(steps_per_epoch):  # exactly one full epoch
            consumed.append(np.asarray(next(it)['id']).tolist())
        state = loader.state_dict()
        # mid-epoch without a deterministic cache order must refuse
        consumed.append(np.asarray(next(it)['id']).tolist())
        with pytest.raises(ValueError, match='deterministic_cache_order'):
            loader.state_dict()

    state = pickle.loads(pickle.dumps(state))
    with build(resume=state) as loader2:
        resumed = [np.asarray(b['id']).tolist() for b in loader2]
    assert consumed[:steps_per_epoch] + resumed == full

    # an epoch-boundary token is batch-size-independent: resuming with a
    # different batch_size is valid (only the mid-epoch cursor pins it)
    reader = make_reader(dataset.url, reader_pool_type='dummy',
                         shuffle_row_groups=False, num_epochs=1)
    from petastorm_tpu.jax import DeviceInMemDataLoader as DIML
    with DIML(reader, batch_size=BATCH * 2, num_epochs=3, seed=23,
              drop_last=False, resume_state=state) as loader3:
        rows = sorted(sum((np.asarray(b['id']).tolist() for b in loader3),
                          []))
    assert rows == sorted(list(range(ROWS)) * 2)  # 2 remaining epochs

    # wrong/absent seed is refused up front
    reader = make_reader(dataset.url, reader_pool_type='dummy',
                         shuffle_row_groups=False, num_epochs=1)
    with pytest.raises(ValueError, match='seed'):
        DeviceInMemDataLoader(reader, batch_size=BATCH, num_epochs=3,
                              seed=99, resume_state=state)
    reader.stop(); reader.join()


def test_device_inmem_scan_epochs_resume(dataset):
    """scan_epochs group yields are epoch boundaries: a token taken
    between groups resumes the remaining epochs exactly."""
    from petastorm_tpu.jax import DeviceInMemDataLoader

    def build(resume=None):
        reader = make_reader(dataset.url, reader_pool_type='dummy',
                             shuffle_row_groups=False, num_epochs=1)
        return DeviceInMemDataLoader(reader, batch_size=BATCH, num_epochs=3,
                                     seed=31, resume_state=resume)

    def collect(loader, max_groups=None):
        out = []
        gen = loader.scan_epochs(lambda c, b: (c, b['id']), 0,
                                 donate_carry=False)
        for i, (_, ids) in enumerate(gen):
            out.append(np.asarray(ids))
            if max_groups is not None and i + 1 == max_groups:
                break
        return out

    with build() as loader:
        full = np.concatenate(collect(loader))

    with build() as loader:
        first = collect(loader, max_groups=1)
        state = loader.state_dict()
    with build(resume=state) as loader2:
        rest = collect(loader2)
    got = np.concatenate(first + rest)
    np.testing.assert_array_equal(got, full)


def test_device_inmem_mid_epoch_resume_deterministic(dataset):
    """deterministic_cache_order=True unlocks EXACT mid-epoch resume on the
    HBM loader: (epochs_done, steps_into_epoch) + seed replay the
    uninterrupted stream's tail, through a pickle round-trip, on any pool
    (the canonical cache order is what survives the restart)."""
    from petastorm_tpu.jax import DeviceInMemDataLoader

    def build(pool, resume=None):
        reader = make_reader(dataset.url, reader_pool_type=pool,
                             shuffle_row_groups=False, num_epochs=1)
        return DeviceInMemDataLoader(reader, batch_size=BATCH, num_epochs=3,
                                     seed=47, deterministic_cache_order=True,
                                     resume_state=resume)

    with build('dummy') as loader:
        full = [np.asarray(b['id']).tolist() for b in loader]
    steps_per_epoch = ROWS // BATCH
    cut = steps_per_epoch + 2  # two steps into epoch 1

    with build('dummy') as loader:
        it = iter(loader)
        consumed = [np.asarray(next(it)['id']).tolist() for _ in range(cut)]
        state = loader.state_dict()
    assert state['device_inmem']['steps_into_epoch'] == 2

    state = pickle.loads(pickle.dumps(state))
    # resume on a DIFFERENT pool: delivery order changes, canonical
    # cache order (and therefore the continuation) must not
    with build('thread', resume=state) as loader2:
        # a snapshot BEFORE the first pull must re-emit the restored
        # cursor, not an epoch-start rewind of it (double-training bug)
        assert loader2.state_dict()['device_inmem']['steps_into_epoch'] == 2
        resumed = [np.asarray(b['id']).tolist() for b in loader2]
    assert consumed + resumed == full

    # the step cursor counts batches of the checkpointed size
    reader = make_reader(dataset.url, reader_pool_type='dummy',
                         shuffle_row_groups=False, num_epochs=1)
    with pytest.raises(ValueError, match='batch_size'):
        DeviceInMemDataLoader(reader, batch_size=BATCH + 1, num_epochs=3,
                              seed=47, deterministic_cache_order=True,
                              resume_state=state)
    reader.stop(); reader.join()

    # scan_epochs composes with the mid-epoch token (fused epochs × exact
    # resume): the partial epoch finishes as its own first dispatch, then
    # full epochs follow — together exactly the per-step continuation.
    with build('dummy', resume=state) as loader3:
        groups = [np.asarray(ids) for _, ids in
                  loader3.scan_epochs(lambda c, b: (c, b['id']), 0,
                                      donate_carry=False)]
    assert [g.shape[0] for g in groups] == [steps_per_epoch - 2,
                                            steps_per_epoch]
    got = np.concatenate(groups).reshape(-1, BATCH).tolist()
    assert got == full[cut:]


def test_device_inmem_scan_epochs_mid_epoch_grouped_resume(dataset):
    """Mid-epoch resume into scan_epochs(epochs_per_call=2): the partial
    epoch is its own first dispatch — yielded WITH the epochs axis as
    (1, steps - cut, ...) so grouped consumers never see a shape change
    (ADVICE r05 #2) — and later epochs keep the requested grouping; the
    stream equals the uninterrupted one."""
    from petastorm_tpu.jax import DeviceInMemDataLoader

    def build(resume=None):
        reader = make_reader(dataset.url, reader_pool_type='dummy',
                             shuffle_row_groups=False, num_epochs=1)
        return DeviceInMemDataLoader(reader, batch_size=BATCH, num_epochs=3,
                                     seed=53, deterministic_cache_order=True,
                                     resume_state=resume)

    steps_per_epoch = ROWS // BATCH
    with build() as loader:
        full = [np.asarray(b['id']).tolist() for b in loader]

    cut = 2  # two steps into epoch 0
    with build() as loader:
        it = iter(loader)
        for _ in range(cut):
            next(it)
        state = loader.state_dict()

    with build(resume=state) as loader2:
        shapes, flat = [], []
        for _, ids in loader2.scan_epochs(lambda c, b: (c, b['id']), 0,
                                          donate_carry=False,
                                          epochs_per_call=2):
            ids = np.asarray(ids)
            shapes.append(ids.shape)
            flat.append(ids.reshape(-1, BATCH))
    # tail of epoch 0 as a 1-epoch group (every grouped yield carries the
    # epochs axis), then epochs 1+2 as one group
    assert shapes == [(1, steps_per_epoch - cut, BATCH),
                      (2, steps_per_epoch, BATCH)]
    assert np.concatenate(flat).tolist() == full[cut:]


def test_device_inmem_scan_epochs_ragged_tail_token_resumes_next_epoch(
        dataset):
    """A token taken past the last FULL batch (inside the ragged tail a
    drop_last=False per-step pass exposes) resumes scan_epochs at the next
    epoch with no partial dispatch — scan always drops partial batches."""
    from petastorm_tpu.jax import DeviceInMemDataLoader

    steps_per_epoch = ROWS // BATCH  # full batches only
    assert ROWS % BATCH, 'test needs a ragged tail'

    def build(resume=None, **kw):
        reader = make_reader(dataset.url, reader_pool_type='dummy',
                             shuffle_row_groups=False, num_epochs=1)
        return DeviceInMemDataLoader(reader, batch_size=BATCH, num_epochs=2,
                                     seed=59, deterministic_cache_order=True,
                                     resume_state=resume, **kw)

    # scan baseline: both epochs, full batches only
    with build() as loader:
        base = [np.asarray(ids) for _, ids in
                loader.scan_epochs(lambda c, b: (c, b['id']), 0,
                                   donate_carry=False)]

    with build(drop_last=False) as loader:
        it = iter(loader)
        for _ in range(steps_per_epoch):  # all full batches of epoch 0
            next(it)
        state = loader.state_dict()
    assert state['device_inmem']['steps_into_epoch'] == steps_per_epoch

    with build(resume=state) as loader2:
        groups = [np.asarray(ids) for _, ids in
                  loader2.scan_epochs(lambda c, b: (c, b['id']), 0,
                                      donate_carry=False)]
    assert [g.shape for g in groups] == [(steps_per_epoch, BATCH)]
    np.testing.assert_array_equal(groups[0], base[1])


def test_device_inmem_scan_epochs_rejects_geometry_changed_token(dataset):
    """A cursor past the geometry's legitimate maximum is a changed
    dataset/batch shape and must raise — same contract as __iter__ — not
    silently skip the rest of the checkpointed epoch."""
    from petastorm_tpu.jax import DeviceInMemDataLoader

    def build(batch_size, steps_into_epoch):
        reader = make_reader(dataset.url, reader_pool_type='dummy',
                             shuffle_row_groups=False, num_epochs=1)
        token = {'version': 1,
                 'device_inmem': {'epochs_done': 0,
                                  'steps_into_epoch': steps_into_epoch,
                                  'batch_size': batch_size, 'seed': 61}}
        return DeviceInMemDataLoader(reader, batch_size=batch_size,
                                     num_epochs=2, seed=61,
                                     deterministic_cache_order=True,
                                     resume_state=token)

    # ROWS=64, BATCH=10: ragged tail exists, max legitimate cursor is 6
    with build(BATCH, 50) as loader:
        with pytest.raises(ValueError, match='geometry'):
            next(loader.scan_epochs(lambda c, b: (c, b['id']), 0,
                                    donate_carry=False))
    # batch_size=8 divides 64: no ragged tail, cursor==steps is impossible
    with build(8, 8) as loader:
        with pytest.raises(ValueError, match='geometry'):
            next(loader.scan_epochs(lambda c, b: (c, b['id']), 0,
                                    donate_carry=False))


def test_device_inmem_scan_epochs_ragged_cursor_honors_token_drop_last(
        dataset):
    """A cursor AT the full-batch count is only reachable by a
    drop_last=False per-step pass; the token records which run took it.
    A drop_last=True token parked there means the geometry changed and
    must raise, while the drop_last=False twin resumes at the next epoch
    (ADVICE r05 item 1)."""
    from petastorm_tpu.jax import DeviceInMemDataLoader

    steps_per_epoch = ROWS // BATCH
    assert ROWS % BATCH, 'test needs a ragged tail'

    def build(token_drop_last):
        reader = make_reader(dataset.url, reader_pool_type='dummy',
                             shuffle_row_groups=False, num_epochs=1)
        token = {'version': 1,
                 'device_inmem': {'epochs_done': 0,
                                  'steps_into_epoch': steps_per_epoch,
                                  'batch_size': BATCH,
                                  'drop_last': token_drop_last, 'seed': 67}}
        return DeviceInMemDataLoader(reader, batch_size=BATCH, num_epochs=2,
                                     seed=67, deterministic_cache_order=True,
                                     resume_state=token)

    with build(token_drop_last=True) as loader:
        with pytest.raises(ValueError, match='drop_last'):
            next(loader.scan_epochs(lambda c, b: (c, b['id']), 0,
                                    donate_carry=False))
    with build(token_drop_last=False) as loader:
        groups = [np.asarray(ids) for _, ids in
                  loader.scan_epochs(lambda c, b: (c, b['id']), 0,
                                     donate_carry=False)]
    # the whole checkpointed epoch is behind the cursor: one epoch remains
    assert [g.shape for g in groups] == [(steps_per_epoch, BATCH)]


def test_device_inmem_scan_epochs_rejects_flagless_ragged_cursor(dataset):
    """ADVICE r05 #1 tightening: ONLY a token that records
    drop_last=False may park its cursor at the full-batch count.  A
    forged or stale token that lacks the flag cannot prove the
    ragged-tail provenance, and accepting it would silently complete the
    checkpointed epoch with zero dispatched steps — it must raise the
    geometry error instead."""
    from petastorm_tpu.jax import DeviceInMemDataLoader

    steps_per_epoch = ROWS // BATCH
    assert ROWS % BATCH, 'test needs a ragged tail'

    reader = make_reader(dataset.url, reader_pool_type='dummy',
                         shuffle_row_groups=False, num_epochs=1)
    forged = {'version': 1,
              'device_inmem': {'epochs_done': 0,
                               'steps_into_epoch': steps_per_epoch,
                               'batch_size': BATCH, 'seed': 71}}  # no flag
    with DeviceInMemDataLoader(reader, batch_size=BATCH, num_epochs=2,
                               seed=71, deterministic_cache_order=True,
                               resume_state=forged) as loader:
        with pytest.raises(ValueError, match='drop_last'):
            next(loader.scan_epochs(lambda c, b: (c, b['id']), 0,
                                    donate_carry=False))


def test_device_inmem_mid_epoch_token_requires_deterministic(dataset):
    """A mid-epoch token is refused at RESUME time too when the rebuilding
    loader lacks deterministic_cache_order (the cursor would index into an
    unreproduced row order)."""
    from petastorm_tpu.jax import DeviceInMemDataLoader

    reader = make_reader(dataset.url, reader_pool_type='dummy',
                         shuffle_row_groups=False, num_epochs=1)
    token = {'version': 1,
             'device_inmem': {'epochs_done': 0, 'steps_into_epoch': 3,
                              'batch_size': BATCH, 'seed': 47}}
    with pytest.raises(ValueError, match='deterministic_cache_order'):
        DeviceInMemDataLoader(reader, batch_size=BATCH, num_epochs=3,
                              seed=47, resume_state=token)
    reader.stop(); reader.join()
