"""Row-path decode worker: one work item = one row-group piece (slice).

Parity: reference ``petastorm/py_dict_reader_worker.py ::
PyDictReaderWorker.process, _load_rows, _read_with_shuffle_row_drop`` —
predicate pushdown (predicate columns first, remaining columns for passing
rows only), per-cell codec decode, TransformSpec, NGram window assembly,
result-cache integration.

Runs on host CPUs inside the L3 pool; pyarrow/zlib/cv2 release the GIL here,
which is what makes the ThreadPool the right default on TPU-VM hosts.
"""

from dataclasses import dataclass, field as dataclass_field

import numpy as np

from petastorm_tpu.cache import NullCache
from petastorm_tpu.errors import DecodeFieldError
from petastorm_tpu.reader_impl.parquet_worker_base import ParquetWorkerBase


@dataclass
class RowWorkerArgs:
    """Immutable per-reader setup shared by all workers."""
    filesystem: object
    pieces: list                  # list[RowGroupPiece]
    schema: object                # full stored Unischema (codec source)
    schema_view: object           # selected fields (what we read+decode)
    transform_spec: object = None
    predicate: object = None
    cache: object = dataclass_field(default_factory=NullCache)
    ngram: object = None
    shuffle_row_drop_partitions: int = 1
    #: Publish one dict of stacked column arrays per row group instead of a
    #: list of row dicts.  Column stacking happens here, in the worker pool
    #: (parallel, GIL-released in numpy), so the consumer thread does zero
    #: per-row python work — the row-path analog of the reference's
    #: BatchedDataLoader speedup, pushed one stage earlier.
    columnar_output: bool = False
    #: Transient-I/O retries per row group before PoisonedRowGroupError
    #: (SURVEY.md §5.3 build obligation; no reference equivalent).
    read_retries: int = 2
    retry_backoff_s: float = 0.1
    #: Ingest plane (ISSUE 14): the parent reader's IngestPlane, or None
    #: (synchronous reads).  Set by Reader._start after mode resolution;
    #: always None for ProcessPool readers (the plane cannot cross the
    #: worker pickle boundary).
    ingest: object = None


def piece_cache_key(piece, schema_view, transform_spec, row_drop_partition=0):
    """Result-cache key of one (piece, row-drop-partition) work item.

    Cached payloads are POST-transform on EVERY branch of ``process``
    (the fused-columnar resize, the per-row func path, and the
    opaque-func columnar fallback alike), so the key carries the
    transform's identity — different resize targets / funcs must not
    share entries (cache_type='local-disk' would otherwise serve stale
    rows at the old resolution across runs).

    Module-level because the service's cluster cache tier
    (``service/cluster.py``) must reproduce the exact key a reader would
    use for a piece WITHOUT constructing the reader — this function is
    the single source of truth for the format.
    """
    cache_key = '%s:%d:%d:%s' % (piece.path, piece.row_group,
                                 row_drop_partition,
                                 ','.join(sorted(schema_view.fields)))
    token = getattr(transform_spec, 'cache_token', None) \
        if transform_spec is not None else None
    if token:
        cache_key += ':t{%s}' % token
    return cache_key


def columnar_fast_path(transform_spec):
    """True when the columnar worker takes the stacked-columns path
    (cache key suffix ``:c``, cached value = the published columns
    dict); False routes through the per-row path (cached value = the
    post-transform rows list).  A declared-resize spec (ResizeImages)
    fuses into the columnar decode instead of forcing the per-row path
    an opaque func does."""
    ts = transform_spec
    return ts is None or ts.func is None \
        or bool(getattr(ts, 'columnar_fusable', False))


class PyDictReaderWorker(ParquetWorkerBase):

    # -- work item -----------------------------------------------------------

    def process(self, piece_index, row_drop_partition=0):
        piece = self._a.pieces[piece_index]
        cache_key = piece_cache_key(piece, self._a.schema_view,
                                    self._a.transform_spec,
                                    row_drop_partition)
        # Reads route through _read_piece: the ingest plane's prefetched
        # in-memory bytes when available, the cached handle otherwise.
        # _ingest_scope releases the plane's prefetched entry when a
        # result-cache HIT means no branch below ever reads Parquet.
        def read_columns():
            return self._read_piece(piece, lambda pf: self._load_columns(
                pf, piece, row_drop_partition))

        def read_rows():
            return self._read_piece(piece, lambda pf: self._load_rows(
                pf, piece, row_drop_partition))

        with self._ingest_scope(piece):
            if self._a.columnar_output and self._a.ngram is None:
                if columnar_fast_path(self._a.transform_spec):
                    # True columnar decode: no intermediate row dicts.
                    columns = self._a.cache.get(
                        cache_key + ':c',
                        lambda: self._read_with_retry(piece, read_columns))
                    if columns is not None \
                            and len(next(iter(columns.values()), ())) > 0:
                        self.publish_func(columns)
                    return
                rows = self._a.cache.get(
                    cache_key,
                    lambda: self._read_with_retry(piece, read_rows))
                if rows:
                    self.publish_func(_stack_columnar(rows))
                return
            rows = self._a.cache.get(
                cache_key,
                lambda: self._read_with_retry(piece, read_rows))
            if self._a.ngram is not None:
                rows = self._a.ngram.form_sequences(rows, self._a.schema_view)
            if rows:
                self.publish_func(rows)

    # -- columnar fast path ---------------------------------------------------

    def _load_columns(self, pf, piece, row_drop_partition):
        """Decode a row group column-wise into stacked arrays.

        Scalar codec-less columns come out of arrow as native numpy with no
        python loop; codec cells decode per value and stack once.  This is
        the decode-plane half of the loader's zero-per-row contract.
        ``pf`` comes from the caller (ingest buffer or cached handle).
        """
        wanted = set(self._a.schema_view.fields)
        predicate = self._a.predicate
        mask = None
        out = {}

        if predicate is not None:
            pred_fields = sorted(set(predicate.get_fields()) & set(self._a.schema.fields))
            if not pred_fields:
                raise ValueError('Predicate fields %s not in schema'
                                 % sorted(predicate.get_fields()))
            pred_cols = self._decode_columns(pf, piece, pred_fields)
            num_rows = len(next(iter(pred_cols.values())))
            mask = np.fromiter(
                (predicate.do_include({n: pred_cols[n][i] for n in pred_fields})
                 for i in range(num_rows)), dtype=bool, count=num_rows)
            if not mask.any():
                return None
            for name in pred_fields:
                if name in wanted:
                    out[name] = pred_cols[name][mask]
            remaining = sorted(wanted - set(pred_fields))
        else:
            remaining = sorted(wanted)

        decoded = self._decode_columns(pf, piece, remaining)
        for name, arr in decoded.items():
            out[name] = arr[mask] if mask is not None else arr

        n_drop = self._a.shuffle_row_drop_partitions
        if n_drop > 1:
            out = {k: v[row_drop_partition::n_drop] for k, v in out.items()}
        for key, value in piece.partition_values:
            if key in wanted:
                count = len(next(iter(out.values())))
                field = self._a.schema.fields.get(key)
                dtype = np.dtype(field.numpy_dtype) if field is not None else None
                if dtype is not None and dtype.kind not in ('U', 'S', 'O'):
                    out[key] = np.full(count, dtype.type(value))
                else:
                    col = np.empty(count, dtype=object)
                    col[:] = [value] * count
                    out[key] = col
        return out

    def _resize_target(self, name):
        """(h, w) for fields a fusable declared-resize transform covers."""
        ts = self._a.transform_spec
        if ts is None or not getattr(ts, 'columnar_fusable', False):
            return None
        return ts.resize_targets.get(name)

    def _codec_decode(self, column):
        """The stage that times one codec column of a row group (once a
        column, never once a cell), counting its cells and encoded bytes:
        ``codec_decode_s`` over ``codec_cells`` is the decode time of one
        image out of ``native/``."""
        stage = self._stage('codec_decode')
        metrics = self._stages.metrics
        metrics.counter('codec_cells').inc(len(column))
        metrics.counter('codec_bytes').inc(column.nbytes)
        return stage

    def _decode_columns(self, pf, piece, names):
        if not names:
            return {}
        table = self._read_row_group(pf, piece, list(names))
        out = {}
        for name in names:
            f = self._a.schema.fields.get(name) or self._a.schema_view.fields.get(name)
            column = table.column(name)
            target = self._resize_target(name) if f is not None else None
            if target is not None and hasattr(f.codec_or_default,
                                              'decode_batch_into_resized') \
                    and column.null_count == 0:
                # Fused decode+resize: the batch shape comes from the
                # DECLARED target, so even wildcard-shape (variable-size)
                # image fields take the preallocated zero-per-row path.
                shape = f.shape if f.shape is not None else ()
                channels = tuple(shape[2:]) if len(shape) > 2 else ()
                if all(s is not None for s in channels):
                    codec = f.codec_or_default
                    dst = np.empty((len(column),) + tuple(target) + channels,
                                   dtype=f.numpy_dtype)
                    try:
                        with self._codec_decode(column):
                            if not codec.decode_batch_into_resized(f, column,
                                                                   dst):
                                for i, cell in enumerate(column.to_pylist()):
                                    codec.decode_resized_into(f, cell, dst[i])
                    except Exception as e:
                        raise DecodeFieldError(
                            'Failed to decode+resize field %r: %s'
                            % (name, e)) from e
                    out[name] = dst
                    continue
            if f is not None and f.codec is None and not f.nullable:
                # Native scalar column: vectorized arrow -> numpy.
                arr = column.to_numpy(zero_copy_only=False)
                if np.dtype(f.numpy_dtype).kind not in ('U', 'S', 'O'):
                    arr = arr.astype(f.numpy_dtype, copy=False)
                out[name] = arr
                continue
            if f is None:
                out[name] = _stack_cells_np(column.to_pylist())
                continue
            codec = f.codec_or_default
            shape = f.shape if f.shape is not None else ()
            static = all(s is not None for s in shape) and \
                np.dtype(f.numpy_dtype).kind not in ('U', 'S', 'O')
            if static and shape and column.null_count == 0:
                # Preallocated batch: each cell decodes straight into its
                # (i, ...) slice — no per-cell allocation + no np.stack pass.
                dst = np.empty((len(column),) + tuple(shape), dtype=f.numpy_dtype)
                batch_decode = getattr(codec, 'decode_batch_into', None)
                try:
                    # The arrow column goes to the native plane as-is: cell
                    # pointers aim into arrow buffers, skipping the per-cell
                    # bytes copies a to_pylist materialization would pay
                    # (one native call for the whole column where it can).
                    with self._codec_decode(column):
                        if batch_decode is None \
                                or not batch_decode(f, column, dst):
                            for i, c in enumerate(column.to_pylist()):
                                codec.decode_into(f, c, dst[i])
                except Exception as e:
                    raise DecodeFieldError('Failed to decode field %r: %s' % (name, e)) from e
                out[name] = dst
                continue
            decode = codec.decode
            try:  # hoisted per-column error context; the loop stays lean
                with self._codec_decode(column):
                    decoded = [decode(f, c) if c is not None else None
                               for c in column.to_pylist()]
            except Exception as e:
                raise DecodeFieldError('Failed to decode field %r: %s' % (name, e)) from e
            out[name] = _stack_cells_np(decoded)
        # Declared-resize targets that could NOT fuse (nullable cells,
        # non-image codecs, object batches): resize post-decode so
        # ResizeImages semantics hold on every columnar branch.
        for name in out:
            target = self._resize_target(name)
            if target is None:
                continue
            batch = out[name]
            needs = batch.dtype == object or (
                batch.ndim >= 3 and tuple(batch.shape[1:3]) != tuple(target))
            if needs:
                out[name] = _resize_cells(batch, target)
        return out

    def _load_rows(self, pf, piece, row_drop_partition):
        wanted = set(self._a.schema_view.fields)
        predicate = self._a.predicate

        if predicate is not None:
            predicate_fields = set(predicate.get_fields())
            first_pass = sorted(predicate_fields & set(self._a.schema.fields))
            if not first_pass:
                raise ValueError('Predicate fields %s not in schema' % sorted(predicate_fields))
            table = self._read_row_group(pf, piece, first_pass)
            columns = {name: table.column(name).to_pylist() for name in first_pass}
            decoded_pred = [
                {name: self._decode_cell(name, columns[name][i]) for name in first_pass}
                for i in range(table.num_rows)
            ]
            mask = [predicate.do_include(vals) for vals in decoded_pred]
            if not any(mask):
                return []
            remaining = sorted(wanted - predicate_fields)
            rows = [dict(v) for v, keep in zip(decoded_pred, mask) if keep]
            if remaining:
                rest = self._read_row_group(pf, piece, remaining)
                rest_cols = {name: rest.column(name).to_pylist() for name in remaining}
                kept = 0
                for i, keep in enumerate(mask):
                    if keep:
                        for name in remaining:
                            rows[kept][name] = self._decode_cell(name, rest_cols[name][i])
                        kept += 1
            # Drop predicate-only fields not requested by the view.
            extra = predicate_fields - wanted
            if extra:
                rows = [{k: v for k, v in r.items() if k not in extra} for r in rows]
        else:
            columns = sorted(wanted)
            table = self._read_row_group(pf, piece, columns)
            cols = {name: table.column(name).to_pylist() for name in columns}
            rows = [
                {name: self._decode_cell(name, cols[name][i]) for name in columns}
                for i in range(table.num_rows)
            ]

        rows = self._apply_row_drop(rows, row_drop_partition)
        for key, value in piece.partition_values:
            if key in wanted:
                for r in rows:
                    r[key] = value
        if self._a.transform_spec is not None and self._a.transform_spec.func is not None:
            rows = [self._a.transform_spec.func(r) for r in rows]
        return rows

    def _decode_cell(self, name, value):
        f = self._a.schema.fields.get(name) or self._a.schema_view.fields.get(name)
        if value is None or f is None:
            return value
        try:
            return f.codec_or_default.decode(f, value)
        except Exception as e:
            raise DecodeFieldError('Failed to decode field %r: %s' % (name, e)) from e

    def _apply_row_drop(self, rows, row_drop_partition):
        """Keep the ``row_drop_partition``-th slice of N: approximate row-level
        shuffle at N× read cost (parity: ``shuffle_row_drop_partitions``)."""
        n = self._a.shuffle_row_drop_partitions
        if n <= 1:
            return rows
        return rows[row_drop_partition::n]


def _resize_cells(batch, target):
    """Per-cell resize of a decoded batch (ndarray or object array of
    variable-size cells) to ``target`` (h, w); the columnar fallback for
    declared resizes that couldn't fuse natively.  Delegates to the one
    semantic reference (``codecs.resize_image_cell``)."""
    from petastorm_tpu.codecs import resize_image_cell
    h, w = target
    return _stack_cells_np([resize_image_cell(a, h, w) for a in batch])


def _stack_columnar(rows):
    """List of decoded row dicts -> dict of (N, ...) arrays (strings/None ->
    1-D object arrays)."""
    return {name: _stack_cells_np([r[name] for r in rows]) for name in rows[0]}


def _stack_cells_np(cells):
    first = next((c for c in cells if c is not None), None)
    if isinstance(first, np.ndarray):
        try:
            return np.stack([c if c is not None else np.zeros_like(first)
                             for c in cells])
        except ValueError:  # ragged shapes (wildcard dims)
            pass
    elif first is not None and not isinstance(first, (str, bytes)):
        arr = np.asarray(cells)
        if arr.dtype != object:
            return arr
    obj = np.empty(len(cells), dtype=object)
    obj[:] = cells
    return obj
