"""Shared base for the two L2 decode workers: per-worker LRU-bounded
ParquetFile handle cache, the ingest-plane checkout seam (ISSUE 14),
plus per-row-group retry with exponential backoff.

The handle cache mirrors what both reference workers do implicitly through
pyarrow dataset pieces (``petastorm/py_dict_reader_worker.py`` /
``petastorm/arrow_reader_worker.py``).  The retry layer is a TPU-build
addition (SURVEY.md §5.3 obligation): remote object stores (GCS) throw
transient ``OSError``s that the reference would surface as a dead epoch; here
the handle is evicted, the read retried with backoff, and only a row group
that *keeps* failing is surfaced — by id — as ``PoisonedRowGroupError``.
"""

import logging
import os
import time
from collections import OrderedDict

import pyarrow as pa
import pyarrow.parquet as pq

from petastorm_tpu.errors import PoisonedRowGroupError
from petastorm_tpu.telemetry import MetricsRegistry, Stages
from petastorm_tpu.workers_pool.worker_base import WorkerBase

logger = logging.getLogger(__name__)

#: Per-worker bound on cached ParquetFile handles (LRU, least recently
#: READ evicted + closed).  Unbounded, a 10k-file dataset pinned 10k fds
#: and mmaps per decode worker; 32 keeps the epoch-locality hit rate
#: (work items cluster by file) while a full pool stays well under
#: default fd ulimits.  ``PETASTORM_TPU_MAX_OPEN_FILES`` overrides.
DEFAULT_MAX_OPEN_FILES = 32

#: Exceptions treated as transient I/O failures.  pyarrow raises OSError
#: subclasses (ArrowIOError aliases OSError in modern pyarrow); fsspec remote
#: filesystems additionally raise EOFError/TimeoutError on truncated bodies.
TRANSIENT_IO_ERRORS = (OSError, EOFError, TimeoutError)

#: Permanent decode failures — a genuinely corrupt row group (bad magic,
#: malformed thrift, invalid page data).  pyarrow surfaces these as
#: ``ArrowInvalid`` (a ValueError subclass), which must NOT be retried but
#: must still carry the piece identity that PoisonedRowGroupError promises.
CORRUPT_DATA_ERRORS = (pa.ArrowInvalid,)

#: OSError subclasses that are *permanent* conditions — retrying them only
#: delays the inevitable and mislabels the failure.
PERMANENT_IO_ERRORS = (FileNotFoundError, PermissionError, IsADirectoryError,
                       NotADirectoryError)


def _is_plain_local(fs):
    """Exactly fsspec's LocalFileSystem — not a subclass or wrapper."""
    try:
        from fsspec.implementations.local import LocalFileSystem
    except ImportError:
        return False
    return type(fs) is LocalFileSystem


class ParquetWorkerBase(WorkerBase):
    """File-handle caching + retry; subclasses implement the decode logic."""

    def __init__(self, worker_id, publish_func, args):
        super(ParquetWorkerBase, self).__init__(worker_id, publish_func, args)
        self._a = args
        #: path -> (file handle, ParquetFile), LRU-bounded (see
        #: DEFAULT_MAX_OPEN_FILES).
        self._open_files = OrderedDict()
        try:
            self._max_open_files = max(1, int(os.environ.get(
                'PETASTORM_TPU_MAX_OPEN_FILES', DEFAULT_MAX_OPEN_FILES)))
        except ValueError:
            self._max_open_files = DEFAULT_MAX_OPEN_FILES
        #: Cumulative seconds spent in retry-backoff sleeps.  Pools subtract
        #: this from measured process() time so ``decode_utilization`` reflects
        #: decode work, not waiting (docs/performance.md tells operators to
        #: use it to distinguish decode-bound from I/O-bound).
        self.retry_sleep_s = 0.0
        self._stages = None

    def _stage(self, name):
        """One timed stage of this worker (``telemetry.Stages``), into the
        owning pool's registry: ``rowgroup_read`` (Parquet read +
        decompress), ``codec_decode`` (one codec column of a row group).
        The profiler spans are ``ptw/<name>``, outside ``pt/``
        (``telemetry.Stages`` says why)."""
        if self._stages is None:
            self._stages = Stages(
                self.metrics if self.metrics is not None
                else MetricsRegistry('reader_worker'), prefix='ptw/')
        return self._stages(name)

    def _read_row_group(self, pf, piece, columns):
        with self._stage('rowgroup_read'):
            return pf.read_row_group(piece.row_group, columns=columns)

    def _parquet_file(self, path):
        entry = self._open_files.get(path)
        if entry is None:
            fs = self._a.filesystem
            if _is_plain_local(fs):
                # Local files skip the python file-object layer entirely:
                # pyarrow mmaps the path natively (~2x on page reads).  Exact
                # type check — delegating wrappers (fault injection, tests)
                # must keep flowing through fs.open().
                entry = (None, pq.ParquetFile(path, memory_map=True))
            else:
                handle = fs.open(path, 'rb')
                entry = (handle, pq.ParquetFile(handle))
            self._open_files[path] = entry
            while len(self._open_files) > self._max_open_files:
                self._evict_file(next(iter(self._open_files)))
        else:
            self._open_files.move_to_end(path)
        return entry[1]

    def _read_piece(self, piece, read_fn):
        """Run ``read_fn(pf)`` against the ingest plane's prefetched
        in-memory buffer when one exists for ``piece`` (ISSUE 14),
        falling back per piece to the synchronous cached-handle path on
        ANY ingest failure — a plan that missed bytes, a corrupt buffer,
        a fetch that never landed.  Delivery stays bit-identical: the
        plane only changes where the bytes waited."""
        plane = getattr(self._a, 'ingest', None)
        if plane is not None:
            # mark the dispatch ref consumed for THIS work item: the
            # process()-level finally only discards when a result-cache
            # hit skipped the read entirely
            self._ingest_claimed = True
            pf = plane.checkout(piece.path, piece.row_group)
            if pf is not None:
                try:
                    return read_fn(pf)
                except Exception as e:  # noqa: BLE001 — degrade, then re-read
                    plane.degraded(e)
                finally:
                    # Deterministic close: a python-file-backed
                    # ParquetFile left to GC at interpreter exit aborts
                    # under pyarrow 22's shutdown destructor ordering.
                    try:
                        pf.close()
                    except Exception:  # noqa: BLE001 — buffer teardown
                        pass
        return read_fn(self._parquet_file(piece.path))

    def _evict_file(self, path):
        """Drop a possibly-wedged cached handle so the next attempt reopens."""
        entry = self._open_files.pop(path, None)
        if entry is not None:
            try:
                (entry[0] or entry[1]).close()
            except Exception:  # noqa: BLE001 — handle may already be broken
                pass

    def shutdown(self):
        for path, (handle, parquet_file) in self._open_files.items():
            try:
                # Local mmap entries have no fsspec handle; close the
                # ParquetFile itself so the mapped fd is released now, not
                # at GC time.
                (handle or parquet_file).close()
            except Exception as e:  # noqa: BLE001 — best-effort teardown
                # Still best-effort, but never silent (lint
                # swallowed-exception): a close that fails here usually
                # means a handle died mid-read — exactly the breadcrumb
                # wanted when a teardown segfault is being chased.
                logger.debug('shutdown: closing cached handle for %s '
                             'failed: %s', path, e)
        self._open_files.clear()

    def _ingest_scope(self, piece):
        """Context for one work item's read: guarantees the ingest
        plane's dispatch ref for ``piece`` is consumed exactly once —
        by the checkout inside :meth:`_read_piece`, or (when a
        result-cache HIT meant Parquet was never read) by a discard
        here.  Without the discard, a warm epoch's prefetched entries
        would leak and wedge the readahead window full."""
        worker = self

        class _Scope(object):
            def __enter__(self):
                worker._ingest_claimed = False
                return self

            def __exit__(self, *exc):
                plane = getattr(worker._a, 'ingest', None)
                if plane is not None and not worker._ingest_claimed:
                    plane.discard(piece.path, piece.row_group)

        return _Scope()

    def _read_with_retry(self, piece, read_fn):
        """Run ``read_fn()`` (which may open + read ``piece``), retrying
        transient I/O errors ``read_retries`` times with exponential backoff."""
        retries = getattr(self._a, 'read_retries', 0)
        backoff = getattr(self._a, 'retry_backoff_s', 0.1)
        attempt = 0
        while True:
            try:
                return read_fn()
            except CORRUPT_DATA_ERRORS as e:
                # Corrupt bytes, not a flaky wire: no retry, but keep the
                # piece-identity contract so the operator can quarantine it.
                # attempt counts any transient retries that preceded this.
                self._evict_file(piece.path)
                raise PoisonedRowGroupError(piece.path, piece.row_group,
                                            attempt + 1, e) from e
            except TRANSIENT_IO_ERRORS as e:
                self._evict_file(piece.path)
                if isinstance(e, PERMANENT_IO_ERRORS):
                    raise
                attempt += 1
                if attempt > retries:
                    raise PoisonedRowGroupError(piece.path, piece.row_group,
                                                attempt, e) from e
                delay = backoff * (2 ** (attempt - 1))
                logger.warning(
                    'Transient read failure on row group %d of %r '
                    '(attempt %d/%d, retrying in %.2fs): %s',
                    piece.row_group, piece.path, attempt, retries + 1, delay, e)
                self.retry_sleep_s += delay
                time.sleep(delay)
