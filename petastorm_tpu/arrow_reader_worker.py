"""Batch-path worker: one row group -> one columnar batch (arrow table).

Parity: reference ``petastorm/arrow_reader_worker.py :: ArrowReaderWorker,
ArrowReaderWorkerResultsQueueReader`` — whole-row-group arrow reads, column
predicates, pandas TransformSpec, namedtuple-of-numpy-arrays conversion.
This is the fast path: no per-row python loops; numpy columns go straight
into the JAX loader's collate.
"""

from dataclasses import dataclass, field as dataclass_field

import numpy as np
import pyarrow as pa

from petastorm_tpu.cache import NullCache
from petastorm_tpu.reader_impl.parquet_worker_base import ParquetWorkerBase


@dataclass
class BatchWorkerArgs:
    filesystem: object
    pieces: list
    schema: object
    schema_view: object
    transform_spec: object = None
    predicate: object = None
    cache: object = dataclass_field(default_factory=NullCache)
    #: Transient-I/O retries per row group before PoisonedRowGroupError
    #: (SURVEY.md §5.3 build obligation; no reference equivalent).
    read_retries: int = 2
    retry_backoff_s: float = 0.1
    #: Ingest plane (ISSUE 14): the parent reader's IngestPlane, or None
    #: (synchronous reads).  Set by Reader._start after mode resolution.
    ingest: object = None


def piece_cache_key(piece, schema_view, transform_spec):
    """Result-cache key of one batch-path row group.  ``_apply_transform``
    runs before the cache store: the payload is post-transform, so the
    key carries the transform identity.  Module-level for the same
    reason as ``py_dict_reader_worker.piece_cache_key`` — the cluster
    cache tier must reproduce it without constructing a reader."""
    cache_key = '%s:%d:batch:%s' % (piece.path, piece.row_group,
                                    ','.join(sorted(schema_view.fields)))
    token = getattr(transform_spec, 'cache_token', None) \
        if transform_spec is not None else None
    if token:
        cache_key += ':t{%s}' % token
    return cache_key


class ArrowReaderWorker(ParquetWorkerBase):

    #: TransformSpec.func runs at DataFrame level here and may drop rows —
    #: consumed by ``Reader.transform_may_change_row_count`` (epoch_steps
    #: guard).  The row worker applies func per row 1:1, so it stays False.
    DATAFRAME_TRANSFORM = True

    def process(self, piece_index, _row_drop_partition=0):
        piece = self._a.pieces[piece_index]
        cache_key = piece_cache_key(piece, self._a.schema_view,
                                    self._a.transform_spec)
        # The retry/poison classifier wraps only the I/O stage: an ArrowInvalid
        # out of a user transform (e.g. from_pandas on a mixed-type column)
        # must surface as the transform's own error, not as a corrupt file.
        # _ingest_scope releases the plane's prefetched entry on a
        # result-cache HIT (the lambda below never runs then).
        with self._ingest_scope(piece):
            table = self._a.cache.get(
                cache_key,
                lambda: self._apply_transform(
                    self._read_with_retry(piece, lambda: self._read_piece(
                        piece, lambda pf: self._load_table(pf, piece)))))
        if table is not None and table.num_rows > 0:
            self.publish_func(table)

    def _load_table(self, pf, piece):
        physical = set(pf.schema_arrow.names)
        wanted = [n for n in self._a.schema_view.fields if n in physical]
        predicate = self._a.predicate

        if predicate is not None:
            pred_fields = sorted(set(predicate.get_fields()) & physical)
            if not pred_fields:
                raise ValueError('Predicate fields %s not present in files'
                                 % sorted(predicate.get_fields()))
            pred_table = self._read_row_group(pf, piece, pred_fields)
            cols = {n: pred_table.column(n).to_pylist() for n in pred_fields}
            mask = np.array([
                predicate.do_include({n: cols[n][i] for n in pred_fields})
                for i in range(pred_table.num_rows)], dtype=bool)
            if not mask.any():
                return None
            table = self._read_row_group(pf, piece, wanted)
            table = table.filter(pa.array(mask))
        else:
            table = self._read_row_group(pf, piece, wanted)

        # Inject hive partition values as constant columns when requested.
        for key, value in piece.partition_values:
            if key in self._a.schema_view.fields and key not in table.column_names:
                field = self._a.schema_view.fields[key]
                dtype = np.dtype(field.numpy_dtype)
                cast = value if dtype.kind in ('U', 'S', 'O') else dtype.type(value)
                table = table.append_column(key, pa.array([cast] * table.num_rows))

        return table

    def _apply_transform(self, table):
        spec = self._a.transform_spec
        if table is None or spec is None:
            return table
        df = table.to_pandas()
        if spec.func is not None:
            df = spec.func(df)
        for name in spec.removed_fields:
            if name in df.columns:
                df = df.drop(columns=[name])
        if spec.selected_fields is not None:
            df = df[list(spec.selected_fields)]
        return pa.Table.from_pandas(df, preserve_index=False)


class ArrowResultConverter(object):
    """arrow table -> namedtuple of numpy arrays (one batch per row group).

    Parity: ``petastorm/arrow_reader_worker.py ::
    ArrowReaderWorkerResultsQueueReader``.
    """

    def __init__(self, schema):
        self._schema = schema

    def convert(self, table):
        out = {}
        for name in self._schema.fields:
            if name not in table.column_names:
                continue
            column = table.column(name).combine_chunks()
            out[name] = _column_to_numpy(column)
        # Fields produced by a transform but absent from the schema view are
        # still surfaced (schema already includes edit_fields via
        # transform_schema, so normally nothing is dropped here).
        return self._schema.make_namedtuple_from_dict(out)


def _column_to_numpy(column):
    ctype = column.type
    if pa.types.is_list(ctype) or pa.types.is_large_list(ctype):
        # Ragged lists -> 1-D object array of numpy arrays; rectangular when
        # all lengths equal -> 2-D array (the useful case for training).
        pylist = column.to_pylist()
        arrays = [np.asarray(x) if x is not None else None for x in pylist]
        lengths = {a.shape for a in arrays if a is not None}
        if len(lengths) == 1 and None not in pylist:
            return np.stack(arrays)
        out = np.empty(len(arrays), dtype=object)
        out[:] = arrays
        return out
    if pa.types.is_string(ctype) or pa.types.is_large_string(ctype) \
            or pa.types.is_binary(ctype) or pa.types.is_large_binary(ctype):
        return np.asarray(column.to_pylist(), dtype=object)
    return column.to_numpy(zero_copy_only=False)
