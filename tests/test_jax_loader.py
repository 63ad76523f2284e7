"""petastorm_tpu.jax.DataLoader: device batches, double buffering, sharding.

Runs on 8 virtual CPU devices (conftest) — the same code path drives real
TPU meshes.
"""

import numpy as np
import pytest

import jax

from petastorm_tpu import make_batch_reader, make_reader
from petastorm_tpu.jax import DataLoader
from petastorm_tpu.parallel import data_parallel_sharding, make_mesh

from test_common import assert_iteration_path, create_test_dataset


@pytest.fixture(scope='module')
def dataset(tmp_path_factory):
    path = tmp_path_factory.mktemp('jaxds')
    return create_test_dataset('file://' + str(path), num_rows=64, rows_per_rowgroup=8)


def test_row_loader_yields_device_batches(dataset, transfer):
    with DataLoader(make_reader(dataset.url, reader_pool_type='dummy',
                                shuffle_row_groups=False),
                    batch_size=16, transfer=transfer) as loader:
        batches = list(loader)
    assert_iteration_path(loader, transfer)
    assert len(batches) == 4
    b = batches[0]
    assert isinstance(b['image_png'], jax.Array)
    assert b['image_png'].shape == (16, 16, 32, 3)
    assert b['matrix'].shape == (16, 8, 4)
    # String field excluded from device transfer.
    assert 'sensor_name' not in b
    expected = {r['id']: r for r in dataset.data}
    ids = np.asarray(b['id'])
    np.testing.assert_array_equal(np.asarray(b['matrix'][0]),
                                  expected[int(ids[0])]['matrix'])


def test_row_loader_all_rows_once(dataset, transfer):
    with DataLoader(make_reader(dataset.url, reader_pool_type='thread', workers_count=4),
                    batch_size=16, transfer=transfer) as loader:
        ids = np.concatenate([np.asarray(b['id']) for b in loader])
    assert_iteration_path(loader, transfer)
    assert sorted(ids.tolist()) == list(range(64))


def test_columnar_loader_rebatches(dataset, transfer):
    # batch reader yields 8-row chunks; loader re-batches to 10 with drop_last.
    with DataLoader(make_batch_reader(dataset.url, reader_pool_type='dummy',
                                      shuffle_row_groups=False),
                    batch_size=10, transfer=transfer) as loader:
        batches = list(loader)
    assert_iteration_path(loader, transfer)
    assert len(batches) == 6  # 64 rows -> 6 full batches of 10
    for b in batches:
        assert np.asarray(b['id']).shape == (10,)


def test_columnar_loader_keep_last(dataset, transfer):
    with DataLoader(make_batch_reader(dataset.url, reader_pool_type='dummy'),
                    batch_size=10, drop_last=False, transfer=transfer) as loader:
        sizes = [len(np.asarray(b['id'])) for b in loader]
    assert_iteration_path(loader, transfer)
    assert sorted(sizes, reverse=True) == [10] * 6 + [4]


def test_shuffling_changes_order_not_content(dataset, transfer):
    with DataLoader(make_reader(dataset.url, reader_pool_type='dummy',
                                shuffle_row_groups=False),
                    batch_size=16, shuffling_queue_capacity=32, seed=5,
                    transfer=transfer) as loader:
        shuffled = np.concatenate([np.asarray(b['id']) for b in loader])
    assert_iteration_path(loader, transfer)
    assert sorted(shuffled.tolist()) == list(range(64))
    assert shuffled.tolist() != list(range(64))


def test_columnar_shuffle(dataset, transfer):
    with DataLoader(make_batch_reader(dataset.url, reader_pool_type='dummy',
                                      shuffle_row_groups=False),
                    batch_size=16, shuffling_queue_capacity=32, seed=5,
                    transfer=transfer) as loader:
        ids = np.concatenate([np.asarray(b['id']) for b in loader])
    assert_iteration_path(loader, transfer)
    assert sorted(ids.tolist()) == list(range(64))
    assert ids.tolist() != list(range(64))


def test_transform_fn_casts(dataset, transfer):
    def to_bf16(batch):
        batch['matrix'] = batch['matrix'].astype('bfloat16') \
            if hasattr(batch['matrix'], 'astype') else batch['matrix']
        return batch

    def cast(batch):
        d = dict(batch._asdict() if hasattr(batch, '_asdict') else batch)
        d['matrix'] = np.asarray(d['matrix'], dtype=np.float32) * 0 + 1
        return d

    with DataLoader(make_reader(dataset.url, schema_fields=['id', 'matrix'],
                                reader_pool_type='dummy'),
                    batch_size=8, transform_fn=cast, transfer=transfer) as loader:
        b = next(iter(loader))
    assert_iteration_path(loader, transfer)
    np.testing.assert_array_equal(np.asarray(b['matrix']),
                                  np.ones((8, 8, 4), np.float32))


def test_global_sharded_batch_over_mesh(tmp_path):
    """pjit-style global batch over the 8-device CPU mesh."""
    import pandas as pd
    import pyarrow as pa
    import pyarrow.parquet as pq
    df = pd.DataFrame({
        'idx': np.arange(64, dtype=np.int64),
        'matrix': [np.arange(32, dtype=np.float32).reshape(8, 4) + i for i in range(64)],
    })
    table = pa.table({
        'idx': pa.array(df['idx']),
        'matrix': pa.array([m.ravel().tolist() for m in df['matrix']],
                           type=pa.list_(pa.float32())),
    })
    pq.write_table(table, str(tmp_path / 'd.parquet'), row_group_size=16)

    mesh = make_mesh({'data': 8})
    sharding = data_parallel_sharding(mesh)
    with DataLoader(make_batch_reader('file://' + str(tmp_path), reader_pool_type='dummy'),
                    batch_size=32, sharding=sharding,
                    transform_fn=lambda b: {k: (v.reshape(-1, 8, 4) if k == 'matrix' else v)
                                            for k, v in b.items()}) as loader:
        b = next(iter(loader))
    arr = b['matrix']
    assert isinstance(arr, jax.Array)
    assert arr.shape == (32, 8, 4)        # single-host: global == local
    assert len(arr.sharding.device_set) == 8

    # The sharded batch feeds a jitted computation without resharding.
    @jax.jit
    def mean_norm(x):
        return jax.numpy.mean(x * x)

    val = mean_norm(arr)
    assert np.isfinite(float(val))


def test_prefetch_pipeline_depth(dataset, transfer):
    with DataLoader(make_reader(dataset.url, reader_pool_type='dummy',
                                shuffle_row_groups=False),
                    batch_size=8, prefetch=3, transfer=transfer) as loader:
        batches = list(loader)
    assert_iteration_path(loader, transfer)
    assert len(batches) == 8
    ids = np.concatenate([np.asarray(b['id']) for b in batches])
    np.testing.assert_array_equal(ids, np.arange(64))


def test_make_jax_loader_convenience(dataset):
    from petastorm_tpu.jax import make_jax_loader
    with make_jax_loader(dataset.url, batch_size=16, batched=True,
                         reader_pool_type='dummy') as loader:
        total = sum(len(np.asarray(b['id'])) for b in loader)
    assert total == 64


def test_columnar_decode_fast_path(dataset):
    """make_reader(columnar_decode=True): codec-decoded columnar batches."""
    with make_reader(dataset.url, reader_pool_type='dummy', shuffle_row_groups=False,
                     columnar_decode=True) as reader:
        chunks = list(reader)
    assert reader.batched_output
    assert chunks[0].image_png.shape == (8, 16, 32, 3)
    ids = np.concatenate([c.id for c in chunks])
    assert sorted(ids.tolist()) == list(range(64))
    expected = {r['id']: r for r in dataset.data}
    np.testing.assert_array_equal(chunks[0].matrix[3],
                                  expected[int(chunks[0].id[3])]['matrix'])


def test_columnar_decode_through_loader(dataset, transfer):
    with DataLoader(make_reader(dataset.url, reader_pool_type='thread', workers_count=4,
                                columnar_decode=True),
                    batch_size=16, transfer=transfer) as loader:
        ids = np.concatenate([np.asarray(b['id']) for b in loader])
    assert_iteration_path(loader, transfer)
    assert sorted(ids.tolist()) == list(range(64))


def test_per_stage_stats_and_pool_utilization(dataset, transfer):
    """SURVEY §5.1: per-stage timing on the loader + decode-plane
    utilization in reader diagnostics."""
    with make_reader(dataset.url, workers_count=2,
                     shuffle_row_groups=False) as reader:
        loader = DataLoader(reader, batch_size=16,
                            transform_fn=lambda b: b, transfer=transfer)
        n = sum(1 for _ in loader)
        diag = reader.diagnostics
    assert n == 4
    assert_iteration_path(loader, transfer)
    stats = loader.stats
    assert stats['batches'] == 4
    assert stats['host_batch_s'] > 0.0
    assert stats['transform_s'] >= 0.0
    assert stats['device_put_s'] > 0.0
    assert diag['decode_busy_s'] > 0.0
    assert 0.0 < diag['decode_utilization'] <= 1.0


def test_inmem_loader_epochs_and_reshuffle(dataset):
    """InMemDataLoader (InMemBatchedDataLoader parity): one read, N epochs
    served from RAM with per-epoch reshuffle."""
    from petastorm_tpu.jax import InMemDataLoader
    with make_reader(dataset.url, reader_pool_type='dummy', num_epochs=1,
                     shuffle_row_groups=False) as reader:
        loader = InMemDataLoader(reader, batch_size=16, num_epochs=3, seed=7)
        epochs = [[] for _ in range(3)]
        ids = []
        for i, batch in enumerate(loader):
            epochs[i // 4].append(np.asarray(batch['id']))
            ids.append(np.asarray(batch['id']))
    assert len(ids) == 12  # 64 rows / 16 per batch * 3 epochs
    flat = [sorted(np.concatenate(e).tolist()) for e in epochs]
    assert flat[0] == flat[1] == flat[2] == list(range(64))  # each epoch complete
    # Reshuffled: order differs between epochs.
    assert not all((epochs[0][j] == epochs[1][j]).all() for j in range(4))


def test_inmem_loader_no_shuffle_deterministic(dataset):
    from petastorm_tpu.jax import InMemDataLoader
    with make_reader(dataset.url, reader_pool_type='dummy', num_epochs=1,
                     shuffle_row_groups=False) as reader:
        loader = InMemDataLoader(reader, batch_size=16, num_epochs=2, shuffle=False)
        batches = [np.asarray(b['id']) for b in loader]
    np.testing.assert_array_equal(np.concatenate(batches[:4]),
                                  np.concatenate(batches[4:]))


def test_inmem_loader_caches_ragged_tail(tmp_path):
    """Regression: drop_last must apply per epoch, not to the cache build —
    a 70-row dataset with batch 16 keeps all 70 rows cached."""
    from petastorm_tpu.jax import InMemDataLoader
    ds = create_test_dataset('file://' + str(tmp_path / 'ragged'), num_rows=70,
                             rows_per_rowgroup=8)
    with make_reader(ds.url, reader_pool_type='dummy', num_epochs=1,
                     shuffle_row_groups=False) as reader:
        loader = InMemDataLoader(reader, batch_size=16, num_epochs=2, seed=3)
        per_epoch = [0, 0]
        for i, batch in enumerate(loader):
            per_epoch[i // 4] += batch['id'].shape[0]
    assert per_epoch == [64, 64]  # drop_last per epoch
    assert len(loader._cache['id']) == 70  # ...but the cache holds every row

    with make_reader(ds.url, reader_pool_type='dummy', num_epochs=1,
                     shuffle_row_groups=False) as reader:
        loader = InMemDataLoader(reader, batch_size=16, num_epochs=1,
                                 drop_last=False, shuffle=False)
        total = sum(b['id'].shape[0] for b in loader)
    assert total == 70


def test_device_inmem_loader_epochs_and_reshuffle(dataset):
    """DeviceInMemDataLoader: HBM-resident epoch cache, on-device gather per
    batch, per-epoch device-side reshuffle — zero host work after epoch 0."""
    import jax
    from petastorm_tpu.jax import DeviceInMemDataLoader
    with make_reader(dataset.url, reader_pool_type='dummy', num_epochs=1,
                     shuffle_row_groups=False) as reader:
        loader = DeviceInMemDataLoader(reader, batch_size=16, num_epochs=3, seed=7)
        epochs = [[] for _ in range(3)]
        for i, batch in enumerate(loader):
            assert isinstance(batch['id'], jax.Array)  # device-resident
            epochs[i // 4].append(np.asarray(batch['id']))
    flat = [sorted(np.concatenate(e).tolist()) for e in epochs]
    assert flat[0] == flat[1] == flat[2] == list(range(64))  # each epoch complete
    assert not all((epochs[0][j] == epochs[1][j]).all() for j in range(4))  # reshuffled


def test_device_inmem_loader_no_shuffle_matches_source_order(dataset):
    from petastorm_tpu.jax import DeviceInMemDataLoader
    with make_reader(dataset.url, reader_pool_type='dummy', num_epochs=1,
                     shuffle_row_groups=False) as reader:
        loader = DeviceInMemDataLoader(reader, batch_size=16, num_epochs=1,
                                       shuffle=False)
        got = np.concatenate([np.asarray(b['id']) for b in loader])
    np.testing.assert_array_equal(got, np.arange(64))


def test_device_inmem_materializes_device_cache_once(dataset, monkeypatch):
    """Re-iterating must NOT re-upload: the device cache is placed once
    and reused while its buffers stay live (ISSUE 17 satellite)."""
    from petastorm_tpu.jax import DeviceInMemDataLoader, residency
    calls = []
    real = residency.place_once

    def counting(numeric, plane=None, device=None):
        calls.append(len(numeric))
        return real(numeric, plane=plane, device=device)

    monkeypatch.setattr(residency, 'place_once', counting)
    with make_reader(dataset.url, reader_pool_type='dummy', num_epochs=1,
                     shuffle_row_groups=False) as reader:
        loader = DeviceInMemDataLoader(reader, batch_size=16, num_epochs=1,
                                       shuffle=False)
        first = np.concatenate([np.asarray(b['id']) for b in loader])
        second = np.concatenate([np.asarray(b['id']) for b in loader])
    np.testing.assert_array_equal(first, second)
    assert len(calls) == 1


def test_device_inmem_deleted_cache_raises(dataset):
    """If the cached device buffers were donated/freed, re-iteration must
    fail loudly instead of serving deleted arrays (host cache is gone)."""
    from petastorm_tpu.jax import DeviceInMemDataLoader
    import pytest
    with make_reader(dataset.url, reader_pool_type='dummy', num_epochs=1,
                     shuffle_row_groups=False) as reader:
        loader = DeviceInMemDataLoader(reader, batch_size=16, num_epochs=1,
                                       shuffle=False)
        list(loader)
        for leaf in loader._dev_cache.values():
            leaf.delete()
        with pytest.raises(RuntimeError, match='rebuild the loader'):
            list(loader)


def test_device_inmem_scan_epochs(dataset):
    """scan_epochs: one lax.scan dispatch per epoch drives the same batches
    the per-step iterator would — full coverage every epoch, reshuffled
    across epochs, carry threaded through every step."""
    import jax.numpy as jnp
    from petastorm_tpu.jax import DeviceInMemDataLoader

    def step(carry, batch):
        return carry + batch['id'].sum(), batch['id']

    with make_reader(dataset.url, reader_pool_type='dummy', num_epochs=1,
                     shuffle_row_groups=False) as reader:
        loader = DeviceInMemDataLoader(reader, batch_size=16, num_epochs=3,
                                       seed=7)
        carry0 = jnp.int64(0) if jax.config.jax_enable_x64 else jnp.int32(0)
        epochs = list(loader.scan_epochs(step, carry0, donate_carry=False))
    assert len(epochs) == 3
    per_epoch_ids = [np.sort(np.asarray(outs).ravel()) for _, outs in epochs]
    for ids in per_epoch_ids:
        np.testing.assert_array_equal(ids, np.arange(64))  # full coverage
    # reshuffled between epochs (unsorted orders differ)
    orders = [np.asarray(outs).ravel() for _, outs in epochs]
    assert not np.array_equal(orders[0], orders[1])
    # carry accumulated every step of every epoch: 3 epochs x sum(0..63)
    final_carry = np.asarray(epochs[-1][0])
    assert int(final_carry) == 3 * (63 * 64) // 2
    assert loader.stats['batches'] == 12


def test_device_inmem_scan_epochs_grouped(dataset):
    """epochs_per_call folds several epochs into one dispatch; a trailing
    partial group yields with its smaller epoch count."""
    from petastorm_tpu.jax import DeviceInMemDataLoader

    def step(carry, batch):
        return carry + 1, batch['id']

    with make_reader(dataset.url, reader_pool_type='dummy', num_epochs=1,
                     shuffle_row_groups=False) as reader:
        loader = DeviceInMemDataLoader(reader, batch_size=16, num_epochs=4,
                                       seed=7)
        calls = list(loader.scan_epochs(step, np.int32(0), donate_carry=False,
                                        epochs_per_call=3))
    assert len(calls) == 2
    first_outs = np.asarray(calls[0][1])
    assert first_outs.shape == (3, 4, 16)     # (epochs, steps, batch)
    # a trailing 1-epoch group keeps the epochs axis (consumers index it)
    assert np.asarray(calls[1][1]).shape == (1, 4, 16)
    for epoch_ids in first_outs:
        np.testing.assert_array_equal(np.sort(epoch_ids.ravel()),
                                      np.arange(64))
    # carry counted every step of every epoch
    assert int(np.asarray(calls[-1][0])) == 4 * 4
    assert loader.stats['batches'] == 16


def test_device_inmem_scan_epochs_no_shuffle_order(dataset):
    from petastorm_tpu.jax import DeviceInMemDataLoader

    def step(carry, batch):
        return carry, batch['id']

    with make_reader(dataset.url, reader_pool_type='dummy', num_epochs=1,
                     shuffle_row_groups=False) as reader:
        loader = DeviceInMemDataLoader(reader, batch_size=16, num_epochs=1,
                                       shuffle=False)
        (carry, outs), = list(loader.scan_epochs(step, np.int32(0),
                                                 donate_carry=False))
    np.testing.assert_array_equal(np.asarray(outs).ravel(), np.arange(64))


def test_echo_repeats_batches(dataset, transfer):
    """echo=2: every decoded batch is served twice consecutively (data
    echoing for decode-bound pipelines); works through __iter__ and
    scan_batches alike."""
    with make_reader(dataset.url, reader_pool_type='dummy',
                     shuffle_row_groups=False) as reader:
        loader = DataLoader(reader, batch_size=16, echo=2, transfer=transfer)
        ids = [np.asarray(b['id']) for b in loader]
    assert_iteration_path(loader, transfer)
    assert len(ids) == 8  # 4 batches x 2 echoes
    for i in range(0, 8, 2):
        np.testing.assert_array_equal(ids[i], ids[i + 1])
    all_ids = np.concatenate(ids)
    assert sorted(set(all_ids.tolist())) == list(range(64))

    def step(carry, batch):
        return carry + 1, batch['id']

    with make_reader(dataset.url, reader_pool_type='dummy',
                     shuffle_row_groups=False) as reader:
        loader = DataLoader(reader, batch_size=16, echo=3, transfer=transfer)
        chunks = list(loader.scan_batches(step, np.int32(0),
                                          steps_per_call=6,
                                          donate_carry=False))
    assert_iteration_path(loader, transfer)
    assert int(np.asarray(chunks[-1][0])) == 12  # 4 batches x 3 echoes
    with pytest.raises(ValueError, match='echo'):
        with make_reader(dataset.url, reader_pool_type='dummy') as reader:
            from petastorm_tpu.jax import DeviceInMemDataLoader
            DeviceInMemDataLoader(reader, batch_size=16, echo=2)


def test_iter_host_batches_stops_at_host_boundary(dataset):
    with make_reader(dataset.url, reader_pool_type='dummy',
                     shuffle_row_groups=False) as reader:
        loader = DataLoader(reader, batch_size=16)
        batches = list(loader.iter_host_batches())
    assert len(batches) == 4
    ids = np.concatenate([np.asarray(b['id']) for b in batches])
    np.testing.assert_array_equal(np.sort(ids), np.arange(64))
    # host numpy, not device arrays; strings still present (no transfer
    # filter ran)
    assert not isinstance(batches[0]['id'], jax.Array)
    assert 'sensor_name' in batches[0]


def test_scan_batches_matches_iteration(dataset):
    """scan_batches: one fused dispatch per k steps sees exactly the batches
    __iter__ would — full coverage, carry threaded, ragged tail handled."""
    def step(carry, batch):
        return carry + batch['id'].sum(), batch['id']

    with make_reader(dataset.url, reader_pool_type='dummy',
                     shuffle_row_groups=False) as reader:
        loader = DataLoader(reader, batch_size=10, drop_last=False)
        ids = []
        carry = np.int32(0)
        chunks = 0
        for carry, outs in loader.scan_batches(step, carry, steps_per_call=3,
                                               donate_carry=False):
            ids.extend(np.asarray(outs).ravel().tolist())
            chunks += 1
    # 64 rows / batch 10 -> 6 full batches + ragged 4; k=3 -> 2 full chunks
    # then the ragged batch flushes as its own chunk
    assert chunks == 3
    assert sorted(ids) == list(range(64))
    assert int(np.asarray(carry)) == (63 * 64) // 2
    assert loader.stats['batches'] == 7


def test_scan_batches_checkpoint_roundtrip(dataset):
    """state_dict mid-scan captures the partial chunk; resuming serves the
    previous run's prefetched batches first — no loss either direction."""
    def step(carry, batch):
        return carry, batch['id']

    with make_reader(dataset.url, reader_pool_type='dummy',
                     shuffle_row_groups=False) as reader:
        loader = DataLoader(reader, batch_size=8, prefetch=1)
        seen = []
        gen = loader.scan_batches(step, np.int32(0), steps_per_call=3,
                                  donate_carry=False)
        _, outs = next(gen)
        seen.extend(np.asarray(outs).ravel().tolist())
        state = loader.state_dict()
        loader.__exit__(None, None, None)

    with make_reader(dataset.url, reader_pool_type='dummy',
                     shuffle_row_groups=False,
                     resume_state=state['reader']) as reader:
        loader = DataLoader(reader, batch_size=8, prefetch=1,
                            resume_state=state)
        for _, outs in loader.scan_batches(step, np.int32(0),
                                           steps_per_call=3,
                                           donate_carry=False):
            seen.extend(np.asarray(outs).ravel().tolist())
    assert sorted(seen) == list(range(64))


def test_scan_batches_resume_pending_not_retransformed(dataset):
    """Pending batches in a snapshot are post-transform; scan_batches must
    not run transform_fn on them again."""
    def double_ids(batch):
        out = dict(batch)
        out['id'] = np.asarray(batch['id']) * 2
        return out

    def step(carry, batch):
        return carry, batch['id']

    with make_reader(dataset.url, reader_pool_type='dummy',
                     shuffle_row_groups=False) as reader:
        loader = DataLoader(reader, batch_size=8, prefetch=2,
                            transform_fn=double_ids)
        it = iter(loader)
        first = next(it)           # leaves pending batches behind
        state = loader.state_dict()
        assert state['pending'], 'test needs prefetched batches in the state'
        seen = list(np.asarray(first['id']))
        loader.__exit__(None, None, None)

    with make_reader(dataset.url, reader_pool_type='dummy',
                     shuffle_row_groups=False,
                     resume_state=state['reader']) as reader:
        loader = DataLoader(reader, batch_size=8, transform_fn=double_ids,
                            resume_state=state)
        for _, outs in loader.scan_batches(step, np.int32(0),
                                           donate_carry=False,
                                           steps_per_call=3):
            seen.extend(np.asarray(outs).ravel().tolist())
    # every id delivered exactly once, exactly doubled (never quadrupled)
    assert sorted(seen) == [2 * i for i in range(64)]


def test_scan_batches_sharded_global_arrays(dataset):
    """scan_batches assembles stacked chunks as global arrays with an
    unsharded leading step axis when sharding= is set."""
    mesh = make_mesh()
    sharding = data_parallel_sharding(mesh)

    def step(carry, batch):
        return carry + batch['id'].sum(), batch['id'].max()

    with make_reader(dataset.url, reader_pool_type='dummy',
                     shuffle_row_groups=False) as reader:
        loader = DataLoader(reader, batch_size=16, sharding=sharding)
        total = np.int32(0)
        for total, _ in loader.scan_batches(step, total, steps_per_call=2,
                                            donate_carry=False):
            pass
    assert int(np.asarray(total)) == (63 * 64) // 2


def test_device_inmem_loader_rejects_sharding(dataset):
    from jax.sharding import NamedSharding, PartitionSpec
    from petastorm_tpu.jax import DeviceInMemDataLoader
    from petastorm_tpu.parallel import make_mesh
    mesh = make_mesh()
    sharding = NamedSharding(mesh, PartitionSpec('data'))
    with make_reader(dataset.url, reader_pool_type='dummy', num_epochs=1) as reader:
        with pytest.raises(ValueError, match='sharding'):
            DeviceInMemDataLoader(reader, batch_size=16, sharding=sharding)


def test_num_local_rows_and_epoch_steps(dataset):
    """Uneven-shard guard: row counts from footers (fast-metadata pieces
    carry -1 and are lazily scanned) -> per-host step budget."""
    from petastorm_tpu.parallel import epoch_steps
    with make_reader(dataset.url, reader_pool_type='dummy') as reader:
        assert reader.num_local_rows() == 64
        assert epoch_steps(reader, batch_size=10) == 6
        assert epoch_steps(reader, batch_size=10, drop_last=False) == 7

    # Sharded: two "hosts" see disjoint piece subsets whose counts sum to 64.
    counts = []
    for shard in (0, 1):
        with make_reader(dataset.url, reader_pool_type='dummy',
                         cur_shard=shard, shard_count=2) as r:
            counts.append(r.num_local_rows())
    assert sum(counts) == 64


def test_min_over_hosts_multihost(monkeypatch):
    """Multi-host branch: min over the allgathered per-host values."""
    import petastorm_tpu.parallel.mesh as mesh_mod

    from jax.experimental import multihost_utils
    monkeypatch.setattr(multihost_utils, 'process_allgather',
                        lambda x: np.array([7, 3, 5]))
    monkeypatch.setattr(mesh_mod.jax, 'process_count', lambda: 3)
    assert mesh_mod.min_over_hosts(7) == 3


def test_epoch_steps_rejects_data_dependent_readers(dataset):
    from petastorm_tpu.ngram import NGram
    from petastorm_tpu.parallel import epoch_steps
    from petastorm_tpu.predicates import in_lambda
    with make_reader(dataset.url, reader_pool_type='dummy',
                     predicate=in_lambda(['id'], lambda id: id % 2 == 0)) as r:
        with pytest.raises(ValueError, match='predicate'):
            epoch_steps(r, 10)


def test_epoch_steps_rejects_row_dropping_transform(dataset):
    """A batch-path TransformSpec func runs at DataFrame level and may drop
    rows — the metadata-derived budget would overshoot and hang a host on
    every collective (ADVICE r1, medium).  Row-path funcs are per-row 1:1
    and must stay accepted."""
    from petastorm_tpu.reader import make_batch_reader
    from petastorm_tpu.parallel import epoch_steps
    from petastorm_tpu.transform import TransformSpec
    spec = TransformSpec(lambda df: df)
    with make_batch_reader(dataset.url, reader_pool_type='dummy',
                           transform_spec=spec) as r:
        with pytest.raises(ValueError, match='transform_spec'):
            epoch_steps(r, 10)
    # Row path: func(dict)->dict cannot change the row count: fine.
    with make_reader(dataset.url, reader_pool_type='dummy',
                     transform_spec=TransformSpec(lambda row: row)) as r:
        assert epoch_steps(r, 10) == 6
    # A spec with edit_fields only (no func) cannot change row counts: fine.
    spec_no_func = TransformSpec(None, removed_fields=['text'])
    with make_batch_reader(dataset.url, reader_pool_type='dummy',
                           transform_spec=spec_no_func) as r:
        assert epoch_steps(r, 10) == 6


def test_inmem_loader_rejects_multi_epoch_reader(dataset):
    """num_epochs=None would hang the cache build forever; >1 silently
    duplicates rows (ADVICE r1)."""
    from petastorm_tpu.jax import InMemDataLoader
    with make_reader(dataset.url, reader_pool_type='dummy',
                     num_epochs=None) as reader:
        with pytest.raises(ValueError, match='num_epochs'):
            InMemDataLoader(reader, batch_size=16)
    with make_reader(dataset.url, reader_pool_type='dummy',
                     num_epochs=2) as reader:
        with pytest.raises(ValueError, match='num_epochs'):
            InMemDataLoader(reader, batch_size=16)


def test_num_local_rows_from_footer_without_reopening_files(dataset):
    """Row counts are stamped in the footer at write time; sizing an epoch
    must not re-open data-file footers."""
    import fsspec

    class CountingFS:
        def __init__(self, real):
            self.real = real
            self.opened = []

        def open(self, path, *a, **kw):
            self.opened.append(path)
            return self.real.open(path, *a, **kw)

        def __getattr__(self, name):
            return getattr(self.real, name)

    fs = CountingFS(fsspec.filesystem('file'))
    with make_reader(dataset.url, reader_pool_type='dummy', filesystem=fs) as r:
        fs.opened.clear()
        assert r.num_local_rows() == 64
    assert fs.opened == []  # footer metadata satisfied the count


def test_num_local_rows_falls_back_to_scan_for_old_datasets(tmp_path):
    """Datasets written before ROW_GROUP_ROW_COUNTS_KEY existed (or by the
    reference) lazily scan footers instead."""
    import pyarrow.parquet as pq
    from petastorm_tpu.etl import dataset_metadata as dm

    ds = create_test_dataset('file://' + str(tmp_path / 'old'), num_rows=30,
                             rows_per_rowgroup=6)
    meta_path = ds.path + '/_common_metadata'
    schema = pq.read_schema(meta_path)
    md = {k: v for k, v in schema.metadata.items()
          if k != dm.ROW_GROUP_ROW_COUNTS_KEY}
    pq.write_metadata(schema.with_metadata(md), meta_path)

    with make_reader(ds.url, reader_pool_type='dummy') as r:
        assert r.num_local_rows() == 30
        assert r.num_local_rows() == 30  # memoized second call


# -- DiskCachedDataLoader (decoded-tensor disk cache tier) --------------------

def _disk_cached(dataset, cache_dir, **kw):
    from petastorm_tpu.jax import DiskCachedDataLoader
    return DiskCachedDataLoader(
        make_reader(dataset.url, reader_pool_type='dummy',
                    shuffle_row_groups=False, num_epochs=1),
        batch_size=16, decoded_cache_dir=str(cache_dir), **kw)


def test_disk_cache_epoch0_serves_and_builds(dataset, tmp_path):
    import os
    cache = tmp_path / 'c1'
    with _disk_cached(dataset, cache, num_epochs=1) as loader:
        ids = np.concatenate([np.asarray(b['id']) for b in loader])
    assert sorted(ids.tolist()) == list(range(64))
    assert os.path.exists(str(cache / '_COMPLETE'))
    assert os.path.exists(str(cache / 'manifest.json'))


def test_disk_cache_later_epochs_match_epoch0_content(dataset, tmp_path):
    cache = tmp_path / 'c2'
    with _disk_cached(dataset, cache, num_epochs=3, seed=0) as loader:
        epochs = [[] for _ in range(3)]
        i = 0
        for b in loader:
            epochs[i // 4].append(np.asarray(b['id']))
            i += 1
    assert i == 12  # 3 epochs x 4 batches
    flat = [sorted(np.concatenate(e).tolist()) for e in epochs]
    assert flat[0] == flat[1] == flat[2] == list(range(64))
    # shuffled epochs differ in order
    assert (np.concatenate(epochs[1]).tolist()
            != np.concatenate(epochs[2]).tolist())


def test_disk_cache_reused_without_reader_work(dataset, tmp_path):
    cache = tmp_path / 'c3'
    with _disk_cached(dataset, cache, num_epochs=1) as loader:
        list(loader)
    # Second loader over the complete cache: poison the reader so any
    # parquet/decode access would blow up — the cache must carry it all.
    from petastorm_tpu.jax import DiskCachedDataLoader

    class _PoisonReader:
        num_epochs = 1
        ngram = None
        batched_output = False

        def __iter__(self):
            raise AssertionError('reader touched despite complete cache')

        def stop(self):
            pass

        def join(self):
            pass

    with DiskCachedDataLoader(_PoisonReader(), batch_size=16,
                              decoded_cache_dir=str(cache),
                              num_epochs=2, seed=1) as loader:
        batches = list(loader)
    assert len(batches) == 8
    ids = np.concatenate([np.asarray(b['id']) for b in batches])
    assert sorted(ids[:64].tolist()) == list(range(64))
    # tensor contents survive the disk round-trip exactly
    expected = {r['id']: r for r in dataset.data}
    b0 = batches[0]
    for j in range(3):
        rid = int(np.asarray(b0['id'])[j])
        np.testing.assert_array_equal(np.asarray(b0['matrix'][j]),
                                      expected[rid]['matrix'])
        np.testing.assert_array_equal(np.asarray(b0['image_png'][j]),
                                      expected[rid]['image_png'])


def test_disk_cache_scan_batches_serves_what_iteration_serves(dataset, tmp_path):
    """The fused driver over a complete decoded cache with no reader: the
    chunks hold the seeded order ``__iter__`` serves, epoch after epoch."""
    from petastorm_tpu.jax import DiskCachedDataLoader
    cache = tmp_path / 'cscan'
    with _disk_cached(dataset, cache, num_epochs=1) as loader:
        list(loader)

    def served():
        return DiskCachedDataLoader(None, batch_size=16,
                                    decoded_cache_dir=str(cache),
                                    num_epochs=2, seed=3)

    def step(carry, batch):
        return carry + 1, batch['id']

    with served() as loader:
        want = [np.asarray(b['id']).tolist() for b in loader]
    with served() as loader:
        chunks = list(loader.scan_batches(step, np.int32(0), steps_per_call=3,
                                          donate_carry=False))
    got = [ids for _, outs in chunks for ids in np.asarray(outs).tolist()]
    assert got == want and len(want) == 8
    assert int(np.asarray(chunks[-1][0])) == 8


def test_disk_cache_partial_build_is_rebuilt(dataset, tmp_path):
    import os
    cache = tmp_path / 'c4'
    os.makedirs(str(cache))
    with open(str(cache / 'id.bin'), 'wb') as f:
        f.write(b'garbage')  # partial build, no _COMPLETE marker
    with _disk_cached(dataset, cache, num_epochs=2) as loader:
        ids = np.concatenate([np.asarray(b['id']) for b in loader])
    assert len(ids) == 128
    assert sorted(ids[:64].tolist()) == list(range(64))


def test_disk_cache_rejects_multiepoch_reader(dataset, tmp_path):
    from petastorm_tpu.jax import DiskCachedDataLoader
    reader = make_reader(dataset.url, reader_pool_type='dummy', num_epochs=2)
    try:
        with pytest.raises(ValueError, match='num_epochs=1'):
            DiskCachedDataLoader(reader, batch_size=16,
                                 decoded_cache_dir=str(tmp_path / 'c5'))
    finally:
        reader.stop()
        reader.join()


def test_device_inmem_reiterable(dataset):
    """A DeviceInMemDataLoader must replay its epochs on every fresh
    iteration (the resume baseline is static; the live epoch counter is
    per-pass) — regression for the round-4 epoch-boundary-resume change."""
    from petastorm_tpu.jax import DeviceInMemDataLoader

    reader = make_reader(dataset.url, reader_pool_type='dummy', num_epochs=1)
    with DeviceInMemDataLoader(reader, batch_size=8, num_epochs=2,
                               seed=3) as loader:
        first = [np.asarray(b['id']).tolist() for b in loader]
        second = [np.asarray(b['id']).tolist() for b in loader]
    assert first and first == second


def test_scan_batches_populates_stage_stats(dataset):
    """scan_batches must feed the same per-stage stats the advisor reads
    (host_batch_s / device_put_s), not just the batch count."""
    reader = make_reader(dataset.url, reader_pool_type='dummy', num_epochs=1,
                         columnar_decode=True)
    with DataLoader(reader, batch_size=8) as loader:
        for _ in loader.scan_batches(lambda c, b: (c, b['id']), 0,
                                     steps_per_call=2, donate_carry=False):
            pass
        assert loader.stats['batches'] > 0
        assert loader.stats['host_batch_s'] > 0.0
        assert loader.stats['device_put_s'] > 0.0
