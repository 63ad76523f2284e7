"""Synchronous in-caller-thread pool: deterministic ordering for tests/debug.

Parity: reference ``petastorm/workers_pool/dummy_pool.py :: DummyPool`` —
work items execute lazily inside ``get_results``, one at a time, in
ventilation order.
"""

import os
import time
from collections import deque

from petastorm_tpu.telemetry import MetricsRegistry, provenance
from petastorm_tpu.telemetry.provenance import Provenanced
from petastorm_tpu.workers_pool import EmptyResultError, VentilatedItem


class DummyPool(object):
    def __init__(self, workers_count=1):
        # Always synchronous; the attribute is the uniform pool-sizing surface.
        self.workers_count = 1
        self._pending = deque()
        self._results = deque()
        self._worker = None
        self._ventilator = None
        self._reorder = None
        self._position = None
        self._stopped = False
        #: Uniform registry surface across pool classes (ISSUE 5).
        self.metrics = MetricsRegistry('dummy_pool')
        self._m_items = self.metrics.counter('items_processed')
        self._m_busy = self.metrics.counter('decode_busy_s')
        self._m_decode = self.metrics.histogram('decode')
        self._started_at = None
        self._stopped_at = None
        #: Per-batch provenance plane (ISSUE 13).
        self.provenance_out = deque(maxlen=256)
        self._prov_on = False
        self._worker_setup_args = None
        self._prov_ctx = None   # (started, item_args, cache_before)

    def start(self, worker_class, worker_setup_args=None, ventilator=None,
              reorder=None):
        self._worker = worker_class(0, self._publish, worker_setup_args)
        self._worker.metrics = self.metrics
        self._ventilator = ventilator
        self._reorder = reorder
        self._position = None
        self._prov_on = provenance.enabled()
        self._worker_setup_args = worker_setup_args
        self._started_at = time.monotonic()
        if ventilator is not None:
            ventilator.start()

    def _publish(self, result):
        # Single-threaded pool, but an out-of-order dispatch policy still
        # needs the reorder stage to restore epoch-order delivery.
        if self._prov_on and self._prov_ctx is not None:
            started, item_args, cache_before = self._prov_ctx
            now = time.monotonic()
            record = provenance.make_record(
                'pool', position=self._position, worker_pid=os.getpid(),
                worker_host=provenance.host(),
                pieces=provenance.piece_info(self._worker_setup_args,
                                             item_args),
                cache=provenance.cache_outcome(
                    cache_before,
                    provenance.cache_stats(self._worker_setup_args)),
                transport='inline',
                stages={'decode': [started, now]})
            record['_staged_t'] = now
            result = Provenanced(result, record)
        if self._reorder is not None and self._position is not None:
            self._reorder.add(self._position, result)
            return
        self._results.append(result)

    def ventilate(self, *args, **kwargs):
        self._pending.append((args, kwargs))

    def get_results(self, timeout=None):
        deadline = None if timeout is None else time.monotonic() + timeout
        while not self._results:
            if self._pending:
                args, kwargs = self._pending.popleft()
                position = None
                if len(args) == 1 and isinstance(args[0], VentilatedItem):
                    position, args = args[0].position, tuple(args[0].args)
                self._position = position
                started = time.monotonic()
                if self._prov_on:
                    self._prov_ctx = (started, args, provenance.cache_stats(
                        self._worker_setup_args))
                sleep_before = getattr(self._worker, 'retry_sleep_s', 0.0)
                try:
                    self._worker.process(*args, **kwargs)
                finally:
                    self._position = None
                    self._prov_ctx = None
                slept = getattr(self._worker, 'retry_sleep_s', 0.0) - sleep_before
                elapsed = max(0.0, time.monotonic() - started - slept)
                self._m_busy.inc(elapsed)
                self._m_decode.observe(elapsed)
                self._m_items.inc()
                if self._reorder is not None and position is not None:
                    # ack-on-delivery: ReorderBuffer.release holds the
                    # publish-then-ack drain invariant
                    self._reorder.release(position, elapsed,
                                          self._results.append,
                                          self._ventilator)
                elif self._ventilator is not None:
                    self._ventilator.processed_item(position, elapsed)
            elif self._ventilator is not None and not self._ventilator.completed():
                # Ventilator thread may still be filling us; spin briefly —
                # but honor the timeout (a PAUSED ventilator never completes,
                # and drain_in_flight probes with short timeouts).
                if deadline is not None and time.monotonic() >= deadline:
                    from petastorm_tpu.workers_pool import \
                        TimeoutWaitingForResultError
                    raise TimeoutWaitingForResultError(
                        'no results within %ss (ventilator idle or paused)'
                        % timeout)
                time.sleep(0.001)
            else:
                raise EmptyResultError()
        result = self._results.popleft()
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

    def stop(self):
        self._stopped = True
        if self._stopped_at is None:
            self._stopped_at = time.monotonic()
        if self._ventilator is not None:
            self._ventilator.stop()
        if self._worker is not None:
            self._worker.shutdown()

    def join(self):
        if not self._stopped:
            raise RuntimeError('join() called before stop()')

    @property
    def items_processed(self):
        return self._m_items.value

    @property
    def busy_time(self):
        return self._m_busy.value

    @property
    def diagnostics(self):
        end = self._stopped_at if self._stopped_at is not None else time.monotonic()
        wall = (end - self._started_at) if self._started_at else 0.0
        return {'pool': 'dummy', 'items_processed': self.items_processed,
                'pending': len(self._pending), 'results_ready': len(self._results),
                'decode_busy_s': round(self.busy_time, 4),
                'decode_utilization': round(self.busy_time / wall, 4) if wall else 0.0}
