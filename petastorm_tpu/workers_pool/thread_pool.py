"""Default pool: N python threads, GIL-releasing decode scales for I/O+CPU.

Parity: reference ``petastorm/workers_pool/thread_pool.py :: ThreadPool`` —
input queue + bounded results queue, worker exceptions re-raised in the
caller, ``VentilatedItemProcessedMessage`` acks flow back to the ventilator.

pyarrow Parquet decode, zlib, and cv2 imdecode all release the GIL, so a
thread pool saturates host cores without ProcessPool serialization overhead —
this is the recommended pool on TPU-VM hosts (see SURVEY.md §7 stage 9).
"""

import os
import queue
import sys
import threading
from petastorm_tpu.utils.locks import make_lock
import time
from collections import deque

from petastorm_tpu.telemetry import MetricsRegistry, provenance
from petastorm_tpu.telemetry.provenance import Provenanced
from petastorm_tpu.telemetry.registry import ms as _ms
from petastorm_tpu.workers_pool import (DEFAULT_TIMEOUT_S, EmptyResultError,
                                        TimeoutWaitingForResultError, VentilatedItem)

_SENTINEL = object()


class _WorkerError(object):
    """Exception captured in a worker thread, travelling the results queue."""

    def __init__(self, exc, tb_str):
        self.exc = exc
        self.tb_str = tb_str


class ThreadPool(object):  # ptlint: disable=pickle-unsafe-attrs — in-process pool; nothing about it ever crosses a pickle boundary
    def __init__(self, workers_count=10, results_queue_size=50, profiler=None):
        #: Uniform public attribute across all pool classes (reader sizing).
        self.workers_count = workers_count
        self._input_queue = queue.Queue()
        self._results_queue = queue.Queue(maxsize=results_queue_size)
        self._threads = []
        self._workers = []
        self._ventilator = None
        #: Optional scheduling.ReorderBuffer (ISSUE 9): results buffer per
        #: position and publish in exact epoch order; None = completion
        #: order (the legacy behavior, and the FIFO default).
        self._reorder = None
        #: serializes reorder release batches: complete() is atomic, but
        #: two workers publishing their released runs concurrently could
        #: interleave them on the results queue.
        self._flush_lock = make_lock('workers_pool.thread_pool.ThreadPool._flush_lock')
        self._tls = threading.local()  # per-worker-thread current position
        self._stop_event = threading.Event()
        self._inflight_lock = make_lock('workers_pool.thread_pool.ThreadPool._inflight_lock')
        self._inflight = 0  # ventilated but result-not-yet-consumed items
        #: Source of truth for the pool's counters (ISSUE 5):
        #: ``diagnostics`` — and through it ``Reader.diagnostics`` — is a
        #: view over this registry.
        self.metrics = MetricsRegistry('thread_pool')
        self._m_items = self.metrics.counter('items_processed')
        self._m_busy = self.metrics.counter('decode_busy_s')
        self._m_decode = self.metrics.histogram('decode')
        self._started_at = None
        self._stopped_at = None
        self._profiler = profiler
        #: Per-batch provenance plane (ISSUE 13): records of delivered
        #: results in delivery order, drained by Reader.take_provenance.
        self.provenance_out = deque(maxlen=256)
        self._prov_on = False
        self._worker_setup_args = None

    def start(self, worker_class, worker_setup_args=None, ventilator=None,
              reorder=None):
        self._ventilator = ventilator
        self._reorder = reorder
        # Resolved per start() (like the shm toggle) so the env kill
        # switch works per reader.
        self._prov_on = provenance.enabled()
        self._worker_setup_args = worker_setup_args
        self._started_at = time.monotonic()
        for worker_id in range(self.workers_count):
            worker = worker_class(worker_id, self._publish, worker_setup_args)
            worker.metrics = self.metrics
            self._workers.append(worker)
            thread = threading.Thread(target=self._worker_loop, args=(worker,),
                                      name='reader-worker-%d' % worker_id, daemon=True)
            self._threads.append(thread)
            thread.start()
        if ventilator is not None:
            ventilator.start()

    def ventilate(self, *args, **kwargs):
        with self._inflight_lock:
            self._inflight += 1
        self._input_queue.put((args, kwargs))

    def _publish(self, result):
        # With a reorder buffer, positioned results stage per position and
        # only reach the queue once every earlier position completed (the
        # worker's finally flushes).  Worker errors never pass through
        # here — the processing loop's except path puts _WorkerError on
        # the queue directly, preempting delivery as on the legacy path.
        position = getattr(self._tls, 'position', None)
        record = self._make_record(position)
        if record is not None:
            result = Provenanced(result, record)
        if self._reorder is not None and position is not None:
            self._reorder.add(position, result)
            return
        self._put_result(result)

    def _make_record(self, position):
        """Provenance record of the result being published, built AT
        publish time (all decode work for this publish is done; only the
        ack bookkeeping remains) so delivery pairing is exact."""
        if not self._prov_on:
            return None
        now = time.monotonic()
        started = getattr(self._tls, 'prov_started', None)
        record = provenance.make_record(
            'pool', position=position, worker_pid=os.getpid(),
            worker_host=provenance.host(),
            pieces=provenance.piece_info(self._worker_setup_args,
                                         getattr(self._tls, 'item_args',
                                                 None)),
            cache=provenance.cache_outcome(
                getattr(self._tls, 'cache_before', None),
                provenance.cache_stats(self._worker_setup_args)),
            transport='inline',
            stages=({'decode': [started, now]} if started is not None
                    else {}))
        record['_staged_t'] = now
        return record

    def _put_result(self, result):
        # Bounded put that stays responsive to stop(): a worker blocked on a
        # full results queue must not deadlock teardown.
        while not self._stop_event.is_set():
            try:
                self._results_queue.put(result, timeout=0.1)
                return
            except queue.Full:
                continue

    def _worker_loop(self, worker):
        try:
            while not self._stop_event.is_set():
                try:
                    item = self._input_queue.get(timeout=0.1)
                except queue.Empty:
                    continue
                if item is _SENTINEL:
                    break
                args, kwargs = item
                position = None
                if len(args) == 1 and isinstance(args[0], VentilatedItem):
                    position, args = args[0].position, tuple(args[0].args)
                self._tls.position = position
                started = time.monotonic()
                if self._prov_on:
                    # Per-item provenance context: decode start, the work
                    # item (for piece identity) and the cache counters
                    # before the item (best-effort under a shared cache:
                    # concurrent threads' traffic can blur the delta).
                    self._tls.prov_started = started
                    self._tls.item_args = args
                    self._tls.cache_before = provenance.cache_stats(
                        self._worker_setup_args)
                sleep_before = getattr(worker, 'retry_sleep_s', 0.0)
                try:
                    worker.process(*args, **kwargs)
                except Exception as e:  # noqa: BLE001 — travels to the caller
                    import traceback
                    # Same stop-responsive put as results: a bare put on the
                    # bounded queue could block forever during teardown and
                    # keep this thread (and its worker's files) alive.
                    self._put_result(_WorkerError(e, traceback.format_exc()))
                finally:
                    # Retry-backoff sleeps are waiting, not decoding —
                    # excluding them keeps decode_utilization an honest
                    # decode-work measure.
                    slept = getattr(worker, 'retry_sleep_s', 0.0) - sleep_before
                    elapsed = max(0.0, time.monotonic() - started - slept)
                    self._tls.position = None
                    with self._inflight_lock:
                        self._inflight -= 1
                    self._m_items.inc()
                    self._m_busy.inc(elapsed)
                    self._m_decode.observe(elapsed)
                    if self._reorder is not None and position is not None:
                        # Ack-on-delivery: ReorderBuffer.release holds
                        # the publish-then-ack drain invariant.  One
                        # release batch publishes atomically; the flush
                        # lock keeps two workers' batches from
                        # interleaving.
                        with self._flush_lock:
                            self._reorder.release(position, elapsed,
                                                  self._put_result,
                                                  self._ventilator)
                    elif self._ventilator is not None:
                        self._ventilator.processed_item(position, elapsed)
        finally:
            # The owning thread closes its own worker's files: shutdown from
            # any other thread (stop() used to do it) can close an
            # mmap-backed ParquetFile while process() is still inside a
            # native pyarrow read on it — a use-after-unmap segfault, not an
            # exception.
            worker.shutdown()

    def get_results(self, timeout=DEFAULT_TIMEOUT_S):
        """Next result; EmptyResultError when all work is drained.

        An item may publish multiple results (rows) or none, so 'drained'
        means: ventilator completed AND no in-flight items AND queue empty.
        """
        while True:
            try:
                result = self._results_queue.get(timeout=0.05)
            except queue.Empty:
                if self._all_done():
                    raise EmptyResultError()
                timeout -= 0.05
                if timeout <= 0:
                    raise TimeoutWaitingForResultError(
                        'No results within timeout; worker threads alive: %d'
                        % sum(t.is_alive() for t in self._threads))
                continue
            if isinstance(result, _WorkerError):
                sys.stderr.write(result.tb_str)
                raise result.exc
            if isinstance(result, Provenanced):
                self.provenance_out.append(provenance.finalize_delivery(
                    result.record, self._ventilator))
                result = result.result
            return result

    def take_provenance(self):
        """Provenance records of results delivered since the last call
        (delivery order; empty under the kill switch)."""
        out = list(self.provenance_out)
        self.provenance_out.clear()
        return out

    def _all_done(self):
        if self._ventilator is not None and not self._ventilator.completed():
            return False
        with self._inflight_lock:
            inflight = self._inflight
        return inflight == 0 and self._input_queue.empty() \
            and self._results_queue.empty() \
            and (self._reorder is None or self._reorder.empty())

    def stop(self):
        if self._stopped_at is None:
            self._stopped_at = time.monotonic()
        if self._ventilator is not None:
            self._ventilator.stop()
        self._stop_event.set()
        for _ in self._threads:
            self._input_queue.put(_SENTINEL)
        # Workers shut themselves down as their threads exit (_worker_loop's
        # finally) — closing their files here would race in-flight reads.

    def join(self):
        for thread in self._threads:
            thread.join()
        for worker in self._workers:
            worker.shutdown()  # idempotent; covers never-started threads

    @property
    def results_qsize(self):
        return self._results_queue.qsize()

    # Registry views — the attribute surface older callers (and
    # _clone_pool) read, now backed by the telemetry registry.
    @property
    def items_processed(self):
        return self._m_items.value

    @property
    def busy_time(self):
        return self._m_busy.value

    @property
    def diagnostics(self):
        # Wall clock ends at stop(): reading diagnostics long after teardown
        # must not decay utilization toward zero.
        end = self._stopped_at if self._stopped_at is not None else time.monotonic()
        wall = (end - self._started_at) if self._started_at else 0.0
        return {
            'pool': 'thread',
            'workers_count': self.workers_count,
            'items_processed': self.items_processed,
            'inflight': self._inflight,
            'input_qsize': self._input_queue.qsize(),
            'results_qsize': self._results_queue.qsize(),
            'decode_busy_s': round(self.busy_time, 4),
            # Fraction of total worker-thread time spent decoding: ~1.0 means
            # the decode plane is the bottleneck (add workers/hosts); low
            # values mean workers starve on I/O or the consumer backpressures.
            'decode_utilization': round(
                self.busy_time / (wall * self.workers_count), 4) if wall else 0.0,
            # Per-item decode latency from the registry histogram (log2
            # buckets): the shape behind decode_busy_s's average.
            'decode_p50_ms': _ms(self._m_decode.quantile(0.5)),
            'decode_p99_ms': _ms(self._m_decode.quantile(0.99)),
        }
