"""Reader orchestration: ``make_reader`` / ``make_batch_reader`` / ``Reader``.

Parity: reference ``petastorm/reader.py :: make_reader, make_batch_reader,
Reader.__init__/__next__/stop/join/reset/diagnostics`` — row-group
enumeration from footer metadata, sharding, shuffling, epochs, worker-class/
pool selection, iterator protocol.

TPU-first differences:

* Sharding defaults to the JAX multi-host topology: when ``cur_shard``/
  ``shard_count`` are not given and ``jax.process_count() > 1``, row groups
  are sharded ``i % process_count == process_index`` automatically — the
  north-star behavior (BASELINE.json) replacing Horovod-rank plumbing.
* The ventilator position is a serializable resume token
  (:meth:`Reader.state_dict` / ``resume_state=``), which the reference lacks.
* Default pool is the ThreadPool (GIL-releasing decode); ProcessPool exists
  for parity but is rarely the right choice on TPU-VM hosts.
"""

import logging

from petastorm_tpu.cache import NullCache
from petastorm_tpu.errors import NoDataAvailableError
from petastorm_tpu.etl.dataset_metadata import (get_schema, infer_or_load_unischema,
                                                load_row_groups)
from petastorm_tpu.fs_utils import get_filesystem_and_path_or_paths
from petastorm_tpu.transform import transform_schema
from petastorm_tpu.unischema import match_unischema_fields
from petastorm_tpu.workers_pool import EmptyResultError
from petastorm_tpu.workers_pool.dummy_pool import DummyPool
from petastorm_tpu.workers_pool.thread_pool import ThreadPool
from petastorm_tpu.workers_pool.ventilator import ConcurrentVentilator

logger = logging.getLogger(__name__)


def _jax_default_shard():
    """(cur_shard, shard_count) from the JAX multihost topology, or (None, None).

    Always asks ``jax.process_count()``: on Cloud TPU pod slices the
    process topology comes from the TPU runtime itself (no explicit
    ``jax.distributed.initialize`` needed), so skipping it would silently
    de-shard a pod and feed every host the full dataset.  For the same
    reason a backend that fails to initialize raises here: only a missing
    ``jax`` means "no auto-shard".
    """
    try:
        import jax
    except ImportError:
        return None, None
    from petastorm_tpu.utils import apply_jax_platforms_env
    apply_jax_platforms_env()
    if jax.process_count() > 1:
        return jax.process_index(), jax.process_count()
    return None, None


def _make_pool(reader_pool_type, workers_count, results_queue_size, zmq_copy_buffers=True):
    if reader_pool_type == 'thread':
        return ThreadPool(workers_count, results_queue_size)
    if reader_pool_type == 'dummy':
        return DummyPool(workers_count)
    if reader_pool_type == 'process':
        from petastorm_tpu.workers_pool.process_pool import ProcessPool
        return ProcessPool(workers_count, results_queue_size, zmq_copy_buffers=zmq_copy_buffers)
    raise ValueError("reader_pool_type must be one of 'thread', 'process', 'dummy'; got %r"
                     % (reader_pool_type,))


def _resolve_cache(cache_type, cache_location, cache_size_limit, cache_row_size_estimate,
                   cache_extra_settings, plane_context=''):
    if cache_type in (None, 'null', 'none'):
        return NullCache()
    if cache_type == 'local-disk':
        from petastorm_tpu.local_disk_cache import LocalDiskCache
        return LocalDiskCache(cache_location, cache_size_limit, cache_row_size_estimate,
                              **(cache_extra_settings or {}))
    if cache_type == 'plane':
        # The tiered epoch-cache plane: shared across worker processes,
        # the data service, and consumer restarts; keyed by content
        # fingerprint so a rewritten dataset or changed transform misses
        # instead of serving stale rows (petastorm_tpu/cache_plane/).
        from petastorm_tpu.cache_plane import PlaneCache
        return PlaneCache(cache_location, cache_size_limit,
                          context=plane_context,
                          **(cache_extra_settings or {}))
    if hasattr(cache_type, 'get'):
        return cache_type  # user-provided CacheBase instance
    raise ValueError("cache_type must be 'null', 'local-disk' or 'plane', "
                     "got %r" % (cache_type,))


def _plane_context(cache_type, fs, pieces, schema_view, predicate,
                   transform_spec):
    """Content-fingerprint prefix for ``cache_type='plane'`` keys: dataset
    file identity (path+mtime+size) x decode identity (columns, predicate,
    transform).  Computed only when the plane is in play — it stats every
    distinct data file once."""
    if cache_type != 'plane':
        return ''
    from petastorm_tpu.cache_plane import dataset_fingerprint, spec_token
    return '%s:%s' % (dataset_fingerprint(fs, {p.path for p in pieces}),
                      spec_token(schema_view, predicate, transform_spec))


def _shard_indices(num_pieces, cur_shard, shard_count, shard_seed=None):
    """Global piece indices belonging to this shard (``i % shard_count ==
    cur_shard`` over a ``shard_seed``-permuted order).  Workers keep the
    GLOBAL piece list and work items carry global indices, so an
    elastic-reshard prologue (``elastic.py``) can hand any reader work
    from any former shard.

    ``shard_seed`` (reference parity: ``petastorm/reader.py ::
    make_reader(shard_seed=)``) deterministically permutes the row-group
    order BEFORE the modulo split, de-correlating shard membership from
    on-disk layout (e.g. time-ordered writes putting one class's row
    groups on one host).  Every host must pass the SAME value — shards
    stay disjoint and complete by construction, but only within one
    permutation.  ``elastic._local_items`` mirrors this exactly.
    """
    if shard_count is None:
        if cur_shard is not None:
            raise ValueError('cur_shard requires shard_count')
        return list(range(num_pieces))
    if cur_shard is None or not 0 <= cur_shard < shard_count:
        raise ValueError('cur_shard must be in [0, %d), got %r' % (shard_count, cur_shard))
    order = list(range(num_pieces))
    if shard_seed is not None:
        import numpy as _np
        # RandomState, not default_rng: the partition must be a pure
        # function of the seed ACROSS numpy versions (hosts in one job, or
        # a resume after an upgrade, may differ) — NumPy's stream-compat
        # guarantee covers the legacy RandomState, not Generator.
        order = _np.random.RandomState(int(shard_seed) & 0xffffffff) \
            .permutation(num_pieces).tolist()
    return [order[i] for i in range(num_pieces) if i % shard_count == cur_shard]


def make_reader(dataset_url,
                schema_fields=None,
                reader_pool_type='thread', workers_count=10, results_queue_size=50,
                shuffle_row_groups=True, shuffle_row_drop_partitions=1,
                predicate=None, rowgroup_selector=None,
                num_epochs=1,
                cur_shard=None, shard_count=None, shard_seed=None,
                cache_type='null', cache_location=None, cache_size_limit=None,
                cache_row_size_estimate=None, cache_extra_settings=None,
                transform_spec=None, filters=None,
                storage_options=None, filesystem=None, hdfs_driver='libhdfs',
                seed=None, resume_state=None, zmq_copy_buffers=True,
                columnar_decode=False, read_retries=2, retry_backoff_s=0.1,
                piece_indices=None, scheduling='auto', ingest='auto',
                ingest_window=None):
    """Reader over a petastorm-format dataset (codec-decoded rows).

    Parity: ``petastorm/reader.py :: make_reader`` (argument names kept,
    including ``hdfs_driver`` — see ``petastorm_tpu/hdfs/namenode.py``).
    Yields namedtuple rows.  See module docstring for TPU-first defaults.

    ``columnar_decode=True`` (extension): workers publish one stacked
    column-array batch per row group and iteration yields namedtuples of
    arrays (like ``make_batch_reader``, but with codec decoding) — the fast
    path for ``petastorm_tpu.jax.DataLoader``; no per-row python on the
    consumer thread.

    ``piece_indices`` (extension): read EXACTLY these global row-group
    indices (the ``load_row_groups`` order) instead of sharding — the
    hook the data-service decode workers use to turn a leased split into
    a reader.  Mutually exclusive with ``cur_shard``/``shard_count`` and
    with ``rowgroup_selector``/``filters`` (both renumber or prune the
    global piece list the indices refer to).

    ``scheduling`` (extension, ISSUE 9): dispatch-order policy of the
    decode plane.  ``'fifo'`` processes row groups in the epoch
    permutation order; ``'adaptive'`` launches predicted-slow row groups
    early within a bounded lookahead window (an online cost model fed by
    per-item decode timings, seeded from row-group sizes) while a
    bounded reorder stage keeps DELIVERY in exact epoch order — shuffle
    determinism and resume tokens are bit-unchanged.  ``'auto'``
    (default) picks ``'adaptive'`` when there is anything to gain
    (multi-worker pool, enough row groups) and ``'fifo'`` otherwise;
    ``PETASTORM_TPU_NO_ADAPTIVE_SCHED=1`` forces ``'fifo'`` everywhere.

    ``ingest`` (extension, ISSUE 14): the async byte-range ingest plane
    for object-store-class storage.  ``'plane'`` prefetches each
    dispatched row group's column-chunk byte ranges (selected columns
    only, coalesced into bounded GETs) on background fetch threads, in
    the ventilator's actual dispatch order, handing pyarrow an in-memory
    buffer — cold first-byte latency moves off the decode workers'
    clock.  ``'off'`` reads synchronously; ``'auto'`` (default) enables
    the plane only on filesystems that pay real first-byte latency
    (non-local fsspec protocols) and always stays off for ProcessPool
    readers.  ``PETASTORM_TPU_NO_INGEST_PLANE=1`` kills it everywhere;
    any fetch failure degrades per piece to the synchronous path.
    Delivery is bit-identical in every mode.  ``ingest_window`` bounds
    how many pieces may be prefetched ahead (default 8; the
    ``DataLoader`` autotuner moves it live from measured
    fetch-vs-decode overlap).
    """
    fs, path = get_filesystem_and_path_or_paths(
        dataset_url, storage_options=storage_options, filesystem=filesystem,
        hdfs_driver=hdfs_driver)
    stored_schema = get_schema(fs, path)

    return _make_reader_common(
        fs, path, stored_schema, dataset_url,
        schema_fields=schema_fields, reader_pool_type=reader_pool_type,
        workers_count=workers_count, results_queue_size=results_queue_size,
        shuffle_row_groups=shuffle_row_groups,
        shuffle_row_drop_partitions=shuffle_row_drop_partitions,
        predicate=predicate, rowgroup_selector=rowgroup_selector,
        num_epochs=num_epochs, cur_shard=cur_shard, shard_count=shard_count,
        shard_seed=shard_seed, cache_type=cache_type, cache_location=cache_location,
        cache_size_limit=cache_size_limit,
        cache_row_size_estimate=cache_row_size_estimate,
        cache_extra_settings=cache_extra_settings,
        transform_spec=transform_spec, filters=filters, seed=seed,
        resume_state=resume_state, zmq_copy_buffers=zmq_copy_buffers,
        columnar_decode=columnar_decode, read_retries=read_retries,
        retry_backoff_s=retry_backoff_s, piece_indices=piece_indices,
        scheduling=scheduling, ingest=ingest, ingest_window=ingest_window)


def _make_reader_common(fs, path, stored_schema, dataset_url, *, schema_fields,
                        reader_pool_type, workers_count, results_queue_size,
                        shuffle_row_groups, shuffle_row_drop_partitions,
                        predicate, rowgroup_selector, num_epochs, cur_shard,
                        shard_count, shard_seed,
                        cache_type, cache_location, cache_size_limit,
                        cache_row_size_estimate, cache_extra_settings,
                        transform_spec, filters, seed, resume_state, zmq_copy_buffers,
                        columnar_decode=False, read_retries=2, retry_backoff_s=0.1,
                        piece_indices=None, scheduling='auto', ingest='auto',
                        ingest_window=None):
    from petastorm_tpu.ngram import NGram
    from petastorm_tpu.py_dict_reader_worker import PyDictReaderWorker, RowWorkerArgs

    ngram = None
    if isinstance(schema_fields, NGram):
        ngram = schema_fields
        schema_view = stored_schema.create_schema_view(ngram.get_field_names_at_all_timesteps())
        ngram.resolve_regex_field_names(stored_schema)
    elif schema_fields is not None:
        schema_view = stored_schema.create_schema_view(schema_fields)
    else:
        schema_view = stored_schema

    pieces = load_row_groups(fs, path)
    # Selector first: stored index ordinals refer to the full, unfiltered
    # load_row_groups ordering.
    if rowgroup_selector is not None:
        from petastorm_tpu.etl.rowgroup_indexing import get_row_group_indexes
        indexes = get_row_group_indexes(fs, path)
        keep = rowgroup_selector.select_row_groups(indexes)
        pieces = [p for i, p in enumerate(pieces) if i in keep]
    if filters is not None:
        from petastorm_tpu.etl.rowgroup_filtering import apply_arrow_filters
        pieces = apply_arrow_filters(fs, pieces, filters, stored_schema)

    if piece_indices is not None:
        local_indices = _explicit_piece_indices(
            piece_indices, len(pieces), cur_shard, shard_count,
            pruned=(rowgroup_selector is not None or filters is not None))
    else:
        if cur_shard is None and shard_count is None:
            cur_shard, shard_count = _jax_default_shard()
            if shard_count is not None:
                logger.info('Auto-sharding by JAX process topology: shard %d of %d',
                            cur_shard, shard_count)
        local_indices = _shard_indices(len(pieces), cur_shard, shard_count,
                                       shard_seed=shard_seed)
    if not local_indices and 'prologue' not in (resume_state or {}):
        raise NoDataAvailableError(
            'No row groups to read from %r after sharding/selection' % (dataset_url,))

    cache = _resolve_cache(cache_type, cache_location, cache_size_limit,
                           cache_row_size_estimate, cache_extra_settings,
                           plane_context=_plane_context(
                               cache_type, fs, pieces, schema_view,
                               predicate, transform_spec))

    if columnar_decode and ngram is not None:
        raise ValueError('columnar_decode is incompatible with NGram windows')
    worker_args = RowWorkerArgs(
        filesystem=fs, pieces=pieces, schema=stored_schema, schema_view=schema_view,
        transform_spec=transform_spec, predicate=predicate, cache=cache, ngram=ngram,
        shuffle_row_drop_partitions=shuffle_row_drop_partitions,
        columnar_output=columnar_decode, read_retries=read_retries,
        retry_backoff_s=retry_backoff_s)

    # Work items: (global_piece_index, row_drop_partition).
    drop_partitions = max(1, shuffle_row_drop_partitions)
    items = [(i, p) for i in local_indices for p in range(drop_partitions)]
    topology = {'cur_shard': cur_shard, 'shard_count': shard_count,
                'shard_seed': None if shard_seed is None else int(shard_seed),
                'shard_scheme': None if shard_seed is None else 'rs-perm-v1',
                'num_global_pieces': len(pieces),
                'drop_partitions': drop_partitions,
                'shuffle': bool(shuffle_row_groups)}

    pool = _make_pool(reader_pool_type, workers_count, results_queue_size, zmq_copy_buffers)
    result_schema = transform_schema(schema_view, transform_spec) \
        if transform_spec is not None else schema_view

    converter = _ColumnarDictConverter(result_schema) if columnar_decode else None
    return Reader(pool=pool, worker_class=PyDictReaderWorker, worker_args=worker_args,
                  items=items, schema=result_schema, ngram=ngram,
                  shuffle_items=shuffle_row_groups, num_epochs=num_epochs,
                  seed=seed, resume_state=resume_state, cache=cache,
                  result_converter=converter, topology=topology,
                  scheduling=scheduling, ingest=ingest,
                  ingest_window=ingest_window)


class _ColumnarDictConverter(object):
    """Stacked-column dict (from the worker) -> namedtuple of arrays."""

    def __init__(self, schema):
        self._schema = schema

    def convert(self, columns):
        return self._schema.make_namedtuple_from_dict(columns)


def _explicit_piece_indices(piece_indices, num_pieces, cur_shard, shard_count,
                            pruned=False):
    """Validate an explicit row-group assignment (``piece_indices=``).

    The indices are positions in the GLOBAL ``load_row_groups`` order —
    the coordinate system the data-service dispatcher partitions — so any
    option that renumbers or prunes that list, or any concurrent sharding
    request, is a contract violation rather than a silent re-read.
    """
    if cur_shard is not None or shard_count is not None:
        raise ValueError('piece_indices is an explicit row-group assignment; '
                         'cur_shard/shard_count do not compose with it')
    if pruned:
        raise ValueError('piece_indices indexes the full load_row_groups '
                         'order; rowgroup_selector/filters would renumber it')
    indices = [int(i) for i in piece_indices]
    bad = [i for i in indices if not 0 <= i < num_pieces]
    if bad:
        raise ValueError('piece_indices %s out of range [0, %d)'
                         % (bad[:5], num_pieces))
    return indices


def make_batch_reader(dataset_url_or_urls,
                      schema_fields=None,
                      reader_pool_type='thread', workers_count=10, results_queue_size=50,
                      shuffle_row_groups=True,
                      predicate=None,
                      num_epochs=1,
                      cur_shard=None, shard_count=None, shard_seed=None,
                      cache_type='null', cache_location=None, cache_size_limit=None,
                      cache_row_size_estimate=None, cache_extra_settings=None,
                      transform_spec=None, filters=None,
                      storage_options=None, filesystem=None, hdfs_driver='libhdfs',
                      seed=None, resume_state=None, zmq_copy_buffers=True,
                      read_retries=2, retry_backoff_s=0.1, piece_indices=None,
                      scheduling='auto', ingest='auto', ingest_window=None):
    """Columnar reader over *any* Parquet store (no petastorm metadata needed).

    Parity: ``petastorm/reader.py :: make_batch_reader``.  Yields namedtuples
    of numpy arrays, one element per row-group-sized batch.

    ``piece_indices`` (extension): read exactly these global row-group
    indices instead of sharding — see :func:`make_reader`.
    ``scheduling`` (extension): dispatch-order policy — see
    :func:`make_reader`.  ``ingest`` / ``ingest_window`` (extension,
    ISSUE 14): the async byte-range ingest plane — see
    :func:`make_reader`.
    """
    from petastorm_tpu.arrow_reader_worker import (ArrowReaderWorker,
                                                   BatchWorkerArgs,
                                                   ArrowResultConverter)

    fs, path_or_paths = get_filesystem_and_path_or_paths(
        dataset_url_or_urls, storage_options=storage_options, filesystem=filesystem,
        hdfs_driver=hdfs_driver)
    paths = path_or_paths if isinstance(path_or_paths, list) else [path_or_paths]

    stored_schema = infer_or_load_unischema(fs, paths[0])
    if schema_fields is not None:
        if not all(isinstance(f, str) for f in schema_fields):
            raise ValueError('make_batch_reader schema_fields must be regex strings')
        matched = match_unischema_fields(stored_schema, schema_fields)
        schema_view = stored_schema.create_schema_view(matched) if matched else stored_schema
    else:
        schema_view = stored_schema

    pieces = []
    for p in paths:
        pieces.extend(load_row_groups(fs, p))
    if filters is not None:
        from petastorm_tpu.etl.rowgroup_filtering import apply_arrow_filters
        pieces = apply_arrow_filters(fs, pieces, filters, stored_schema)

    if piece_indices is not None:
        local_indices = _explicit_piece_indices(
            piece_indices, len(pieces), cur_shard, shard_count,
            pruned=filters is not None)
    else:
        if cur_shard is None and shard_count is None:
            cur_shard, shard_count = _jax_default_shard()
        local_indices = _shard_indices(len(pieces), cur_shard, shard_count,
                                       shard_seed=shard_seed)
    if not local_indices and 'prologue' not in (resume_state or {}):
        raise NoDataAvailableError(
            'No row groups to read from %r after sharding/selection' % (dataset_url_or_urls,))

    cache = _resolve_cache(cache_type, cache_location, cache_size_limit,
                           cache_row_size_estimate, cache_extra_settings,
                           plane_context=_plane_context(
                               cache_type, fs, pieces, schema_view,
                               predicate, transform_spec))
    worker_args = BatchWorkerArgs(filesystem=fs, pieces=pieces, schema=stored_schema,
                                  schema_view=schema_view, transform_spec=transform_spec,
                                  predicate=predicate, cache=cache,
                                  read_retries=read_retries,
                                  retry_backoff_s=retry_backoff_s)
    items = [(i, 0) for i in local_indices]
    topology = {'cur_shard': cur_shard, 'shard_count': shard_count,
                'shard_seed': None if shard_seed is None else int(shard_seed),
                'shard_scheme': None if shard_seed is None else 'rs-perm-v1',
                'num_global_pieces': len(pieces), 'drop_partitions': 1,
                'shuffle': bool(shuffle_row_groups)}
    pool = _make_pool(reader_pool_type, workers_count, results_queue_size, zmq_copy_buffers)
    result_schema = transform_schema(schema_view, transform_spec) \
        if transform_spec is not None else schema_view

    return Reader(pool=pool, worker_class=ArrowReaderWorker, worker_args=worker_args,
                  items=items, schema=result_schema, ngram=None,
                  shuffle_items=shuffle_row_groups, num_epochs=num_epochs,
                  seed=seed, resume_state=resume_state, cache=cache,
                  result_converter=ArrowResultConverter(result_schema),
                  topology=topology, scheduling=scheduling, ingest=ingest,
                  ingest_window=ingest_window)


class Reader(object):
    """Iterator over the dataset; owns pool + ventilator lifecycle.

    Parity: ``petastorm/reader.py :: Reader`` — iterator/context-manager
    protocol, ``stop/join/reset``, ``diagnostics``; plus ``state_dict`` resume
    tokens (TPU-first addition).
    """

    def __init__(self, *, pool, worker_class, worker_args, items, schema, ngram,
                 shuffle_items, num_epochs, seed, resume_state, cache,
                 result_converter=None, topology=None, scheduling='auto',
                 ingest='auto', ingest_window=None):
        from petastorm_tpu.ingest import resolve_ingest as _resolve_ingest
        from petastorm_tpu.workers_pool import scheduling as _sched
        #: requested mode; the EFFECTIVE mode (after 'auto' resolution and
        #: the kill switch) is the public ``scheduling`` attribute, set in
        #: _start.  Resolved per start so reset() re-evaluates the env.
        self._scheduling_requested = scheduling
        # validate eagerly — a typo must fail before threads spin up
        _sched.resolve_scheduling(scheduling, len(items),
                                  pool.workers_count)
        #: requested ingest mode (ISSUE 14); the EFFECTIVE mode after
        #: 'auto'/kill-switch resolution is the public ``ingest``
        #: attribute, set per _start (so reset() re-reads the env).
        self._ingest_requested = ingest
        self._ingest_window = ingest_window
        _resolve_ingest(ingest, worker_args.filesystem)  # eager validation
        self.ingest = None
        self.ingest_plane = None
        self.scheduling = None
        self.cost_model = None
        self._reorder = None
        self.schema = schema
        self.ngram = ngram
        #: True for the columnar (make_batch_reader) path: __next__ yields
        #: namedtuples of column arrays instead of single rows.
        self.batched_output = result_converter is not None
        self._ngram_schemas = (
            {offset: ngram.get_schema_at_timestep(schema, offset) for offset in ngram.fields}
            if ngram is not None else None)
        self._pool = pool
        self._cache = cache
        self._items = items
        self._shuffle_items = shuffle_items
        self._num_epochs = num_epochs
        self._seed = seed if seed is not None else 0
        self._result_converter = result_converter
        self._row_buffer = []
        self._stopped = False
        self.last_row_consumed = False

    # Deferred so reset() can rebuild the ventilator with the same args.
        self._worker_class = worker_class
        self._worker_args = worker_args
        self._topology = topology
        start_epoch = start_cursor = 0
        prologue = ()
        if resume_state is not None:
            # Checkpoint round-trips (orbax) restore int leaves as 0-d numpy
            # arrays; normalize here so callers pass tokens back verbatim.
            def as_int(value, default):
                return default if value is None else int(value)
            start_epoch = as_int(resume_state.get('epoch'), 0)
            start_cursor = as_int(resume_state.get('cursor'), 0)
            seed = resume_state.get('seed', self._seed)
            self._seed = seed if seed is None else int(seed)
            prologue = [(int(i), int(p)) for i, p in
                        (resume_state.get('prologue') or ())]
            self._check_resume_topology(resume_state)
        self._start(start_epoch, start_cursor, prologue)

    def _check_resume_topology(self, resume_state):
        """A token's position indexes a specific shard's permutation: resuming
        it under a different topology silently skips/rereads data.  Tokens
        carry their topology since the elastic-reshard work — compare it
        (tokens predating it, or foreign tokens, validate nothing)."""
        if self._topology is None or 'shard_count' not in resume_state:
            return
        def norm(v):
            return None if v is None else int(v)
        mismatches = [
            k for k in ('cur_shard', 'shard_count', 'num_global_pieces',
                        'drop_partitions')
            if norm(resume_state.get(k, self._topology.get(k))) != norm(self._topology.get(k))]
        # shard_seed: a token MISSING the key predates the feature and
        # indexes the UNPERMUTED order (None) — it must not default to the
        # reader's own seed, or the guard would wave through exactly the
        # mismatch it exists to catch.
        if norm(resume_state.get('shard_seed')) \
                != norm(self._topology.get('shard_seed')):
            mismatches.append('shard_seed')
        elif norm(resume_state.get('shard_seed')) is not None \
                and resume_state.get('shard_scheme') \
                != self._topology.get('shard_scheme'):
            # Same seed value but a different (or unmarked) PERMUTATION
            # SCHEME computes a different partition — the marker exists so
            # a future scheme change refuses old tokens instead of
            # silently mis-sharding.
            mismatches.append('shard_scheme')
        if bool(resume_state.get('shuffle', self._topology['shuffle'])) \
                != bool(self._topology['shuffle']):
            mismatches.append('shuffle')
        if mismatches:
            raise ValueError(
                'resume_state was taken under a different topology '
                '(mismatched: %s).  To move a checkpoint across shard '
                'counts, map ALL shards\' tokens through '
                'petastorm_tpu.elastic.reshard_reader_states — resuming a '
                'foreign token directly would silently skip or re-read '
                'data.' % ', '.join(mismatches))

    def _start(self, start_epoch=0, start_cursor=0, prologue=()):
        from petastorm_tpu import ingest as _ingest
        from petastorm_tpu.workers_pool import scheduling as _sched
        # Ingest plane (ISSUE 14): resolved per start so reset()
        # re-reads the kill switch; ProcessPool readers resolve off
        # (the plane cannot cross the worker pickle boundary).
        if self.ingest_plane is not None:
            self.ingest_plane.close()
            self.ingest_plane = None
        self.ingest = _ingest.resolve_ingest(
            self._ingest_requested, self._worker_args.filesystem,
            in_process_pool=type(self._pool).__name__ != 'ProcessPool')
        if self.ingest == 'plane':
            self.ingest_plane = _ingest.IngestPlane(
                self._worker_args.filesystem, self._worker_args.pieces,
                columns=self._ingest_columns(),
                registry=getattr(self._pool, 'metrics', None),
                window=self._ingest_window)
        self._worker_args.ingest = self.ingest_plane
        # Small in-flight window: keeps resume tokens tight and bounds memory;
        # large enough to never starve the workers.
        window = max(2 * self._pool.workers_count, 4)
        self.scheduling = _sched.resolve_scheduling(
            self._scheduling_requested, len(self._items),
            self._pool.workers_count)
        policy = None
        self._reorder = None
        self.cost_model = None
        if self.scheduling == 'adaptive':
            # Online cost model: seeded from row-group byte sizes so
            # epoch 0 already ranks pieces; every pool ack refines it.
            # The lookahead window scales with the pool (more workers =
            # more reordering headroom) inside the autotuner's clamps.
            self.cost_model = _sched.PieceCostModel()
            self.cost_model.seed(self._scheduling_weights())
            # Lookahead spans the whole epoch (clamped): the window is
            # only an ORDER-selection horizon — memory/latency are
            # bounded by the in-flight window, because ack-on-delivery
            # counts undelivered positions against it.  Deeper in-flight
            # than FIFO's 2x-workers: slow pieces launched early hold
            # their slot until their delivery turn.
            # early_limit: keep at least half the pool on the in-order
            # fast stream — front-loading every worker with slow pieces
            # would stall delivery until the first one lands.
            policy = _sched.AdaptiveDispatchPolicy(
                self.cost_model,
                window=min(_sched.MAX_WINDOW,
                           max(_sched.MIN_WINDOW, len(self._items))),
                early_limit=max(1, self._pool.workers_count // 2))
            # The in-flight bound counts UNDELIVERED positions, so it
            # must cover a straggler's worth of fast completions piling
            # up behind it — too shallow and the fast stream freezes
            # that many positions past a blocked early-permutation
            # straggler, idling the pool for the rest of its fetch (the
            # exact worker-idle stall the scheduler exists to kill).
            # 16x the pool (8x FIFO's 2x-workers window), capped at the
            # autotuner clamp ceiling: worst-case reorder memory is the
            # bound in completed row groups, so it must SCALE with the
            # decode resources the user already sized, not sit at a
            # flat 128 — bare make_reader consumers have no autotuner
            # to shrink it (a DataLoader's tuner moves it both ways
            # from measured skew).
            window = min(16 * self._pool.workers_count,
                         max(len(self._items), 1), _sched.MAX_INFLIGHT)
            n = max(len(self._items), 1)
            self._reorder = _sched.ReorderBuffer(
                start_position=start_epoch * n + start_cursor,
                prologue_count=len(prologue))
        self._ventilator = ConcurrentVentilator(
            ventilate_fn=self._pool.ventilate,
            items=self._items,
            iterations=self._num_epochs,
            randomize_item_order=self._shuffle_items,
            random_seed=self._seed,
            max_ventilation_queue_size=max(
                1, min(len(self._items) + len(prologue), window)),
            start_epoch=start_epoch, start_cursor=start_cursor,
            prologue_items=prologue, dispatch_policy=policy,
            dispatch_listener=(self.ingest_plane.observe_dispatch
                               if self.ingest_plane is not None else None))
        self._pool.start(self._worker_class, self._worker_args,
                         ventilator=self._ventilator, reorder=self._reorder)

    def _ingest_columns(self):
        """Column names one piece's decode may read: the selected view
        plus any predicate columns (the two-pass predicate read touches
        both) — the set the fetch planner restricts ranges to.  Names
        with no physical chunk (hive partition keys) simply match
        nothing at plan time."""
        wanted = set(self._worker_args.schema_view.fields)
        predicate = getattr(self._worker_args, 'predicate', None)
        if predicate is not None:
            try:
                wanted |= set(predicate.get_fields()) \
                    & set(self._worker_args.schema.fields)
            except Exception:  # noqa: BLE001 — over-fetch beats a missed page
                return None
        return wanted

    def _scheduling_weights(self):
        """Epoch-0 cost priors for the adaptive scheduler, cached across
        reset(): per-piece compressed byte sizes from a one-time threaded
        footer scan (the one cheap signal that separates a heavy
        mixed-resolution row group from its neighbors before anything is
        timed), falling back to row counts — then uniform — when the
        footers are unreachable."""
        if getattr(self, '_sched_weights', None) is not None:
            return self._sched_weights
        from petastorm_tpu.workers_pool import scheduling as _sched
        pieces = getattr(self._worker_args, 'pieces', ())
        weights = _sched.piece_weights(self._items, pieces)
        try:
            from petastorm_tpu.etl.dataset_metadata import \
                read_row_group_byte_sizes
            local = sorted({i for i, _ in self._items
                            if isinstance(i, int) and 0 <= i < len(pieces)})
            paths = {pieces[i].path for i in local}
            if len(paths) > _sched.MAX_PRIOR_SCAN_FILES:
                # one footer open per file: past the cap the scan itself
                # dominates reader startup (remote stores pay a GET per
                # file) — row-count priors + first-ack timings instead
                logger.debug(
                    'scheduling prior: %d files exceeds the footer-scan '
                    'cap (%d); using row-count priors', len(paths),
                    _sched.MAX_PRIOR_SCAN_FILES)
                self._sched_weights = weights
                return weights
            sizes = read_row_group_byte_sizes(
                self._worker_args.filesystem, paths)
            byte_weights = {
                i: sizes[(pieces[i].path, pieces[i].row_group)]
                for i in local
                if (pieces[i].path, pieces[i].row_group) in sizes}
            if byte_weights:
                weights = byte_weights
        except Exception:  # noqa: BLE001 — priors are best-effort
            logger.debug('row-group byte-size scan failed; cost priors '
                         'fall back to row counts', exc_info=True)
        self._sched_weights = weights
        return weights

    # -- resume --------------------------------------------------------------

    def state_dict(self):
        """Serializable mid-stream position (row-group granularity).

        For an EXACT no-loss snapshot, call :meth:`drain_in_flight` first
        (or use ``DataLoader.state_dict``, which does): the bare token
        replays any row group still outstanding, but results already
        published to the pool queue and not yet consumed are past the token.

        The token also carries the shard topology (``cur_shard``,
        ``shard_count``, ``num_global_pieces``, ``drop_partitions``,
        ``shuffle``, ``num_epochs``), which makes it re-shardable:
        ``petastorm_tpu.elastic.reshard_reader_states`` maps the tokens of
        K readers onto any new shard count.
        """
        state = self._ventilator.state_dict()
        if self._topology is not None:
            state.update(self._topology)
            state['num_epochs'] = self._num_epochs
        return state

    # -- introspection -------------------------------------------------------

    def num_local_rows(self):
        """Row count of this shard — an upper bound under ``predicate=`` /
        ``shuffle_row_drop_partitions`` / NGram windowing (all data-
        dependent).  Piece counts come from the footer scan when available;
        fast-metadata pieces lazily open their file footers here (threaded,
        memoized — the piece list is immutable).  Feeds
        ``parallel.epoch_steps`` — the uneven-shard guard for pjit loops."""
        if getattr(self, '_num_local_rows', None) is not None:
            return self._num_local_rows
        from petastorm_tpu.etl.dataset_metadata import read_row_group_num_rows
        # worker_args.pieces is the GLOBAL list (elastic prologues may touch
        # any piece); this shard's regular epoch covers only its own items.
        local = sorted({i for i, _ in self._items})
        total = 0
        unknown = {}
        for idx in local:
            piece = self._worker_args.pieces[idx]
            if piece.num_rows >= 0:
                total += piece.num_rows
            else:
                unknown.setdefault(piece.path, []).append(piece.row_group)
        total += read_row_group_num_rows(self._worker_args.filesystem, unknown)
        self._num_local_rows = total
        return total

    @property
    def predicate(self):
        """The worker-side row predicate, if any (data-dependent yield)."""
        return getattr(self._worker_args, 'predicate', None)

    @property
    def transform_spec(self):
        """The worker-side TransformSpec, if any.  A spec whose ``func`` drops
        rows makes the yield data-dependent (see ``parallel.epoch_steps``)."""
        return getattr(self._worker_args, 'transform_spec', None)

    @property
    def transform_may_change_row_count(self):
        """True when this reader's transform runs at DataFrame level (the
        batch worker), where ``func`` may filter rows.  The row worker applies
        ``func`` per row 1:1, so row-path transforms cannot change counts."""
        spec = self.transform_spec
        if spec is None or getattr(spec, 'func', None) is None:
            return False
        return getattr(self._worker_class, 'DATAFRAME_TRANSFORM', False)

    @property
    def num_epochs(self):
        """Epoch repetition count this reader was built with (None=infinite)."""
        return self._num_epochs

    # -- iteration -----------------------------------------------------------

    def __iter__(self):
        return self

    def __next__(self):
        if self._result_converter is not None:
            # Batch path: one result == one columnar batch.
            try:
                return self._result_converter.convert(self._pool.get_results())
            except EmptyResultError:
                self.last_row_consumed = True
                raise StopIteration from None
        while not self._row_buffer:
            try:
                rows = self._pool.get_results()
            except EmptyResultError:
                self.last_row_consumed = True
                raise StopIteration from None
            self._row_buffer = list(rows)
        return self._convert_row(self._row_buffer.pop(0))

    def _convert_row(self, row):
        if self.ngram is not None:
            # NGram rows are {offset: row-dict}; each offset gets its own
            # namedtuple type (the fields requested at that timestep).
            return {offset: self._ngram_schemas[offset].make_namedtuple_from_dict(v)
                    for offset, v in row.items()}
        return self.schema.make_namedtuple_from_dict(row)

    def next(self):
        return self.__next__()

    # -- exact-checkpoint support ---------------------------------------------

    def drain_in_flight(self):
        """Pause dispatch and consume EVERY in-flight result; returns them.

        After this returns, no row group is outstanding and no published
        row sits in a pool queue, so :meth:`state_dict` is an EXACT
        position: nothing delivered so far will replay, nothing undelivered
        is skipped.  (Without draining, the token is row-group granular:
        groups acked by workers whose rows still sit in the results queue
        would be lost, and partially-consumed groups would replay.)

        Returns a list of rows (row readers) or columnar batches (batch
        readers) in delivery order.  Call :meth:`resume_dispatch` to
        continue reading afterwards — the checkpoint-then-keep-training
        pattern.  Used by ``petastorm_tpu.jax.DataLoader.state_dict``.
        """
        from petastorm_tpu.workers_pool import TimeoutWaitingForResultError
        self._ventilator.pause()
        drained = []
        if self._result_converter is None and self._row_buffer:
            drained.extend(self._convert_row(r) for r in self._row_buffer)
            self._row_buffer = []
        # Deliverable only: under out-of-order dispatch, positions held
        # past an undispatched gap can never release while paused — the
        # token replays them, so waiting on them would spin forever.
        while self._ventilator.has_deliverable_outstanding():
            try:
                results = self._pool.get_results(timeout=0.2)
            except TimeoutWaitingForResultError:
                continue   # trailing ack still in flight; re-check
            except EmptyResultError:
                self.last_row_consumed = True
                return drained
            drained.extend(self._to_drained(results))
        # Final sweep: results published by groups that were acked before
        # the loop observed them (ack always follows publish, so once no
        # group is outstanding, everything published is already queued).
        try:
            while True:
                results = self._pool.get_results(timeout=0.05)
                drained.extend(self._to_drained(results))
        except TimeoutWaitingForResultError:
            pass
        except EmptyResultError:
            self.last_row_consumed = True
        return drained

    def _to_drained(self, results):
        if self._result_converter is not None:
            return [self._result_converter.convert(results)]
        return [self._convert_row(r) for r in results]

    def resume_dispatch(self):
        """Resume ventilation after :meth:`drain_in_flight`."""
        self._ventilator.unpause()

    # -- per-batch provenance (ISSUE 13) --------------------------------------

    def take_provenance(self):
        """Provenance records of the results delivered since the last
        call (delivery order): pieces (file + rowgroup), producing
        worker pid/host, scheduling decision, cache outcome, transport
        path, and decode/ipc stage windows.  The JAX loader drains this
        per host batch into its :class:`~petastorm_tpu.telemetry.
        provenance.ProvenanceJournal`; empty under
        ``PETASTORM_TPU_NO_PROVENANCE=1``."""
        take = getattr(self._pool, 'take_provenance', None)
        return take() if take is not None else []

    # -- lifecycle -----------------------------------------------------------

    def reset(self):
        """Restart iteration from epoch 0 (only after exhaustion).

        Parity: ``petastorm/reader.py :: Reader.reset``.
        """
        if not self.last_row_consumed:
            raise NotImplementedError(
                'reset() mid-iteration is not supported; drain the reader first '
                '(parity with the reference behavior)')
        self._pool.stop()
        self._pool.join()
        self._pool = _clone_pool(self._pool)
        self._row_buffer = []
        self.last_row_consumed = False
        self._start()

    def stop(self):
        self._pool.stop()
        if self.ingest_plane is not None:
            # after pool.stop: a worker blocked in a checkout unblocks
            # here and degrades to the sync path instead of wedging join
            self.ingest_plane.close()
        self._stopped = True

    def join(self):
        self._pool.join()
        self._cache.cleanup()

    @property
    def metrics(self):
        """The pool's ``telemetry.MetricsRegistry`` — the source of truth
        ``diagnostics`` (and the loader's merged view) is built from.
        For a ProcessPool reader the parent-side registry is merged with
        the child snapshots riding the ack channel
        (``ProcessPool.worker_telemetry``)."""
        return getattr(self._pool, 'metrics', None)

    @property
    def diagnostics(self):
        # A VIEW over the telemetry registries (ISSUE 5): the pool's
        # parent-side registry (+ merged child snapshots for the
        # ProcessPool) and the cache plane's — no counter lives in this
        # dict; it is rebuilt from the registries on every read.
        d = dict(self._pool.diagnostics)
        # Epoch-cache plane counters (cache_type='plane'): hit/miss/evict
        # gauges of THIS process's view of the shared plane (thread-pool
        # readers see every worker's traffic; ProcessPool children count
        # in their own processes — use the service/dispatcher stats for a
        # fleet-wide view).
        cache_stats = getattr(self._cache, 'stats', None)
        if cache_stats:
            d.update(cache_stats)
        d['ventilated_count'] = self._ventilator.ventilated_count
        d['scheduling'] = self.scheduling
        # Ingest plane (ISSUE 14): effective mode + live fetch counters.
        d['ingest'] = self.ingest
        if self.ingest_plane is not None:
            d.update(self.ingest_plane.stats)
        # results staged behind an earlier incomplete position (adaptive
        # only; 0 when idle/fifo) — the reorder stage's live depth
        d['reorder_pending'] = (self._reorder.pending_results
                                if self._reorder is not None else 0)
        token = self._ventilator.state_dict()
        # the prologue item list is data, not a gauge — report its length
        d['prologue_remaining'] = len(token.pop('prologue', ()))
        d.update(token)
        return d

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc_value, tb):
        self.stop()
        self.join()


def _clone_pool(pool):
    if isinstance(pool, DummyPool):
        return DummyPool()
    if isinstance(pool, ThreadPool):
        return ThreadPool(pool.workers_count, pool._results_queue.maxsize)
    from petastorm_tpu.workers_pool.process_pool import ProcessPool
    if isinstance(pool, ProcessPool):
        return ProcessPool(pool.workers_count, pool.results_queue_size)
    raise TypeError('Unknown pool type %r' % type(pool))
