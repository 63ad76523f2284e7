"""Double-buffered device loader: reader rows/batches -> jax.Array pytrees.

The TPU-native peer of the reference's framework adapters
(``petastorm/pytorch.py :: DataLoader/BatchedDataLoader``,
``petastorm/tf_utils.py :: make_petastorm_dataset``), designed for the XLA
execution model instead of translated from them:

* **Static shapes** — fixed ``batch_size``, ``drop_last=True`` by default, so
  every step hits the same compiled executable (no re-tracing).
* **Async dispatch double-buffering** — ``jax.device_put`` returns
  immediately while DMA proceeds; the loader keeps ``prefetch`` batches in
  flight so H2D transfer of batch N+1 overlaps the device step on batch N.
* **Pipelined transfer plane** (``petastorm_tpu.jax.transfer``) — on
  accelerator backends a background dispatch thread stages each batch
  into a reused ring slab (one coalesced ``device_put`` per batch, not
  one per column, opt-in bf16/uint8 wire narrowing, per-device parallel
  dispatch under a ``sharding``) so host staging, the link, and the
  step overlap as three pipeline stages; ``transfer=``/``wire_dtypes=``
  /``ring_slots=`` control it, unsupported shapes degrade bit-identical.
* **Multi-host global batches** — pass ``sharding`` (a ``NamedSharding``
  over a mesh) and each host contributes its local rows via
  ``jax.make_array_from_process_local_data``; the yielded pytree holds
  global jax.Arrays ready for pjit (every host must run the same number of
  steps — use ``drop_last=True`` and equal per-host shards, see
  SURVEY.md §7 risks).
* **Columnar fast path** — with a ``make_batch_reader`` underneath, arrow
  column chunks are re-batched with numpy concatenation; no per-row python
  loop (the analog of the reference's BatchedDataLoader speedup).
"""

import logging
import os
from collections import deque
from contextlib import contextmanager

import numpy as np

import jax

from petastorm_tpu.jax.transfer import _DONE, DispatchPump
from petastorm_tpu.parallel.mesh import global_batch_from_local

logger = logging.getLogger(__name__)


class DataLoader(object):
    """Iterate device-resident batches from a petastorm_tpu reader.

    Args:
        reader: ``make_reader``/``make_batch_reader`` result.
        batch_size: rows per (per-host) batch; with ``sharding`` this is the
            LOCAL batch — global batch = batch_size × process_count.
        shuffling_queue_capacity: >0 enables a host-side shuffling reservoir
            (row readers: row granularity; batch readers: columnar window).
        min_after_retrieve: minimum mixing radius once warm.
        transform_fn: host-side pytree hook applied to each numpy batch
            before transfer (casting, normalization, augmentation).
        drop_last: drop the trailing partial batch (default True: XLA static
            shapes; a ragged last batch would trigger recompilation).
        prefetch: device batches kept in flight (2 = double buffering).
        device / sharding: target placement. ``sharding`` wins and assembles
            global arrays from per-host local data.
        seed: shuffling seed.
        trace_recorder: optional ``benchmark.TraceRecorder`` — every timed
            section (host_batch / transform / device_put) is additionally
            recorded as a chrome-trace span (timeline view of the same
            time ``stats`` aggregates).
        transfer: the host→device transfer plane
            (``petastorm_tpu.jax.transfer``): ``'auto'`` (default) turns
            it on when an accelerator backend is live, ``True`` forces it
            on (CPU tests), ``False`` keeps the inline ``device_put``
            path.  When on, a background dispatch thread stages each
            batch into a reused ring slab (one coalesced ``device_put``
            per batch instead of one per column) so the link runs as its
            own overlapped pipeline stage; ``PETASTORM_TPU_NO_TRANSFER_
            PLANE=1`` kills it globally, and unsupported batch
            structures degrade per batch to the inline path with
            bit-identical results.
        wire_dtypes: opt-in wire narrowing for the transfer plane:
            ``'auto'`` ships float32/float64 leaves as bfloat16 and
            casts back on device (half/quarter the bytes on the link —
            values round to bf16), or a ``{field: dtype}`` dict for
            explicit control.  ``None`` (default) transfers every leaf
            at full width, bit-identical to ``jax.device_put``.
        ring_slots: device-buffer ring depth for the transfer plane
            (default ``prefetch + 1``): up to ``ring_slots - 1``
            transfers stay in flight while the step runs.
        autotune: stage autotuning (ISSUE 9).  ``'auto'`` (default)
            activates when the underlying reader runs the adaptive
            scheduler: a rate-limited, clamped tuner adjusts the
            ventilation lookahead window, the ventilator in-flight
            bound, and this loader's ``prefetch`` from measured stage
            p50/p99s (decode skew, host_batch vs device_put) and — when
            a ``StallMonitor`` is attached via
            :meth:`attach_stall_monitor` — the consumer's measured wait
            fraction.  Decisions export as ``sched_*`` gauges on
            ``self.metrics``.  ``True`` forces it on (FIFO readers tune
            prefetch only), ``False`` keeps every knob where you set it.
        batch_slo_ms: per-batch latency SLO (ISSUE 13).  When set (or
            via ``PETASTORM_TPU_BATCH_SLO_MS``), a sealed provenance
            record whose end-to-end wall exceeds the budget counts a
            ``slo_violations`` metric and auto-dumps the FULL journal
            (the whole causal chain) under ``PETASTORM_TPU_FLIGHT_DIR``
            for ``petastorm-tpu-explain``.  The journal itself
            (``self.provenance``) is on whenever provenance is
            (``PETASTORM_TPU_NO_PROVENANCE=1`` kills both).
    """

    def __init__(self, reader, batch_size, shuffling_queue_capacity=0,
                 min_after_retrieve=None, transform_fn=None, drop_last=True,
                 prefetch=2, device=None, sharding=None, seed=None,
                 resume_state=None, echo=1, trace_recorder=None,
                 transfer='auto', wire_dtypes=None, ring_slots=None,
                 autotune='auto', batch_slo_ms=None):
        if batch_size <= 0:
            raise ValueError('batch_size must be positive')
        if echo < 1:
            raise ValueError('echo must be >= 1')
        from petastorm_tpu.jax.transfer import validate_transfer
        validate_transfer(transfer)   # fail at construction, not first iter
        self.reader = reader
        self.batch_size = int(batch_size)
        self._shuffle_capacity = shuffling_queue_capacity
        self._min_after_retrieve = (min_after_retrieve if min_after_retrieve is not None
                                    else shuffling_queue_capacity // 2)
        self._transform_fn = transform_fn
        self._drop_last = drop_last
        self._echo = int(echo)
        self._prefetch = max(1, int(prefetch))
        self._device = device
        self._sharding = sharding
        self._seed = seed
        self._warned_fields = set()
        self._batched_input = getattr(reader, 'batched_output', False)
        # -- exact-resume machinery (see state_dict) --
        #: rows/chunks to serve BEFORE pulling from the reader: restored
        #: snapshot data first, then drained-but-unconsumed results that
        #: state_dict() reinjects so checkpointing never skips data locally.
        if resume_state is not None and 'batched' in resume_state \
                and bool(resume_state['batched']) != self._batched_input:
            raise ValueError(
                'resume_state came from a %s loader but this reader is %s — '
                'buffered data would be misinterpreted'
                % ('columnar' if resume_state['batched'] else 'row',
                   'columnar' if self._batched_input else 'row'))
        self._pushback = list((resume_state or {}).get('pushback', []))
        self._resume_state = resume_state
        self._pending = deque()
        self._shuffle_buf = None
        self._partial_rows = []
        self._col_chunks = None
        self._colsh = None
        #: Per-stage wall time (SURVEY.md §5.1 obligation): 'host_batch_s'
        #: covers waiting on the decode plane + collate, 'transform_s' the
        #: user hook, 'device_put_s' the H2D *dispatch* (the DMA itself is
        #: async and overlaps; on the transfer-plane path it covers the
        #: whole staged put — pack + dispatch + any ring commit wait —
        #: with the h2d_* histograms carrying the split).  Pair with StallMonitor for the consumer
        #: view and reader.diagnostics['decode_utilization'] for the
        #: worker-pool view (all three pools; the ZeroMQ pool ships child
        #: busy time back on each ack).  The source of truth is the
        #: telemetry registry (ISSUE 5): ``stats`` is a view over its
        #: counters, and each stage additionally feeds a log2-bucket
        #: latency histogram (``diagnostics`` reports the p50/p99s).
        from petastorm_tpu.telemetry import (MetricsRegistry, Stages, flight,
                                             process_registry)
        # Always-on flight recorder for the trainer process (ISSUE 7):
        # the stage histograms below snapshot into its bounded ring so a
        # postmortem sees the minutes before a hang, not final totals.
        flight.enable(label='trainer')
        self._gc_watched = False
        self.metrics = MetricsRegistry('loader')
        # One snapshot of ``metrics`` (what a benchmark takes its window
        # deltas of) also shows what the reader's pool timed, as
        # ``reader_*``, and what happened to the whole process (garbage
        # collections, the flight thread waking late), as ``process_*``.
        self.metrics.attach(
            'reader_', lambda: getattr(reader, 'metrics', None))
        self.metrics.attach('process_', process_registry())
        self._m_batches = self.metrics.counter('batches')
        #: THE way a stage is timed here (``telemetry.Stages``): one pair
        #: of clock readings feeds the ``<stage>_s`` counter, the
        #: ``<stage>`` histogram, the ``pt/<stage>`` profiler span (a
        #: nested part's is ``ptp/<stage>``), the ``trace_recorder`` span
        #: and the provenance window.
        self._stage = Stages(self.metrics, trace_recorder)
        for stage in ('host_batch', 'transform', 'device_put'):
            self._stage.instruments(stage)
        #: ``device_put`` above times only the async DISPATCH; this
        #: histogram samples TRUE transfer completion (a periodic
        #: ``block_until_ready``, plus every ring-slot reuse wait when
        #: the transfer plane is on) so ``diagnostics`` reports both
        #: dispatch and commit p50/p99.
        self.metrics.histogram('h2d_commit')
        self._commit_probe = 0
        # Per-batch provenance plane (ISSUE 13): every delivered batch
        # seals ONE record — the merge of its chunks' producer records
        # (pieces, worker pid/host, scheduling, cache, transport) with
        # this consumer's stage windows and the transfer-path outcome —
        # into a bounded journal; the stage histograms keep tail
        # exemplars ({'step': N}) pointing back into it, so any p99
        # resolves to the actual file/rowgroup/worker.
        from petastorm_tpu.telemetry import provenance as _provenance
        self._provenance_mod = _provenance
        self.provenance = None
        self._slo = None
        self._last_pull_window = None
        if _provenance.enabled():
            self.provenance = _provenance.ProvenanceJournal(label='loader')
            if batch_slo_ms is None:
                env_slo = os.environ.get('PETASTORM_TPU_BATCH_SLO_MS')
                if env_slo:
                    try:
                        batch_slo_ms = float(env_slo)
                    except ValueError:
                        batch_slo_ms = None
            if batch_slo_ms:
                self._slo = _provenance.SloWatchdog(
                    self.provenance, float(batch_slo_ms) / 1e3,
                    label='loader', metrics=self.metrics)
        self._transfer = transfer
        self._wire_dtypes = wire_dtypes
        self._ring_slots = ring_slots
        self._plane = None
        self._pump = None
        if autotune not in ('auto', True, False):
            raise ValueError("autotune must be 'auto', True or False; got %r"
                             % (autotune,))
        self._autotune = autotune
        self._tuner = None
        self._tuner_ventilator = None
        self._knobs = None
        self._stall_monitor = None
        self._trace = trace_recorder
        if trace_recorder is not None:
            # ProcessPool children ship their spans (pool/process,
            # pool/publish, cache/fill) on the ack channel; pointing the
            # pool at this recorder is what lands them on THIS timeline
            # — without it they sit in the pool's bounded remote_spans
            # buffer that nothing reads.  Same-host children share
            # CLOCK_MONOTONIC, so no offset is needed.
            pool = getattr(reader, '_pool', None)
            if pool is not None and hasattr(pool, 'trace_recorder'):
                pool.trace_recorder = trace_recorder

    def _seal_provenance(self, stages, transfer=None, residency=None):
        """Merge the reader records drained since the last batch with
        this batch's consumer-side stage windows, seal into the journal,
        and run the SLO watchdog.  ``residency`` is the resident tier's
        outcome for this batch (hit / admitted / evicted / bypass) when a
        residency-capable loader served it.  Returns the journal step,
        or None when provenance is off."""
        journal = self.provenance
        if journal is None:
            return None
        prov = self._provenance_mod
        records = []
        take = getattr(self.reader, 'take_provenance', None)
        if take is not None:
            try:
                records = take() or []
            except Exception:  # noqa: BLE001 — provenance is never load-bearing
                records = []
        record = prov.merge_records(records)
        for name, window in stages.items():
            if window is not None and window[1] > window[0]:
                record['stages'][name] = list(window)
        if transfer is not None:
            record['transfer'] = transfer
        if residency is not None:
            record['residency'] = residency
        record = journal.seal(record)
        # Back-annotate tail exemplars: the stage histograms observed
        # these windows before the step existed, so the refs attach
        # without re-counting — uniform across __iter__,
        # iter_host_batches and scan_batches consumption.
        ref = {'step': record['step']}
        for stage_name, hist_key in (('host_batch', 'host_batch'),
                                     ('transform', 'transform'),
                                     ('h2d_dispatch', 'device_put')):
            window = record['stages'].get(stage_name)
            if window is not None:
                self.metrics.histogram(hist_key).note_exemplar(
                    window[1] - window[0], ref)
        if self._slo is not None:
            self._slo.check(record)
        return record['step']

    def dump_provenance(self, path):
        """Persist the provenance journal (atomic JSON) — the file
        ``petastorm-tpu-explain --journal`` reads.  Returns the path, or
        None when provenance is off or the write failed."""
        if self.provenance is None:
            return None
        return self.provenance.persist(path)

    @property
    def stats(self):
        """Aggregate per-stage seconds + batch count — the historical
        dict surface, now a view over ``self.metrics``."""
        return {'host_batch_s': self.metrics.counter('host_batch_s').value,
                'transform_s': self.metrics.counter('transform_s').value,
                'device_put_s': self.metrics.counter('device_put_s').value,
                'batches': int(self._m_batches.value)}

    # -- iteration -----------------------------------------------------------

    def _transfer_plane(self):
        """The loader's transfer plane, or None when disabled (kill
        switch, ``transfer=False``, or ``'auto'`` on the CPU backend).
        Built once; shares the loader's registry and trace recorder, so
        its ``h2d_*`` stages land on the same surfaces as every other
        stage."""
        from petastorm_tpu.jax import transfer
        if not transfer.plane_enabled(self._transfer):
            return None
        if self._plane is None:
            ring = (self._ring_slots if self._ring_slots is not None
                    else self._prefetch + 1)
            self._plane = transfer.TransferPlane(
                device=self._device, sharding=self._sharding,
                wire_dtypes=self._wire_dtypes, ring_slots=ring,
                metrics=self.metrics, trace_recorder=self._trace)
        return self._plane

    def _sample_commit(self, dev, every=32):
        """Periodic true-completion sample for the INLINE path: 1-in-
        ``every`` device_puts additionally waits for the transfer to
        land, feeding the ``h2d_commit`` histogram (the plane path
        observes commits on every ring-slot reuse instead)."""
        self._commit_probe += 1
        if (self._commit_probe - 1) % every:
            return
        with self._stage('h2d_commit', span='ptp/h2d_commit',
                         event='h2d/commit', kind='sample'):
            jax.block_until_ready(dev)

    def __iter__(self):
        plane = self._transfer_plane()
        if plane is not None:
            if self._pump is not None and self._pump.alive:
                # A previous iteration's dispatch thread is still winding
                # down (a pull parked in the reader): never share a ring
                # with it — a fresh plane gets fresh slabs.
                self._plane = None
                plane = self._transfer_plane()
            return self._iter_pumped(plane)
        return self._iter_inline()

    def _waited(self, get):
        """Yield ``get()``'s results until it gives ``_DONE``, each call
        one ``next_wait`` sample under a ``ptc/next_wait`` profiler span:
        how long the consuming thread was blocked in the loader for its
        next batch, measured by the loader (the program's own stall share,
        beside a harness's stopwatch around ``next()``).  ``ptc/``, not
        ``pt/``: on the pumped path it covers the pump's spans in time."""
        while True:
            with self._stage('next_wait', span='ptc/next_wait') as wait:
                item = get()
                if item is _DONE:
                    wait.keep = False
                    return
            yield item

    def _ship(self, host_batch, plane=None):
        """Transform → device → provenance seal → count, for one pulled
        host batch: on the dispatch pump's thread through ``plane``, or
        inline (``plane=None``).  Same stages, values and accounting on
        both, so the ``pt/*`` spans sit on whichever thread does the
        work."""
        n = int(self._m_batches.value) + 1
        stages = {}
        if self._last_pull_window is not None:
            # _timed_pulls runs on this same thread right before, so the
            # stash is this batch's pull.
            stages['host_batch'] = list(self._last_pull_window)
        if self._transform_fn is not None:
            with self._stage('transform', event='transform',
                             batch=n) as transform:
                host_batch = self._transform_fn(host_batch)
            stages['transform'] = transform.window
        # device_put_s covers the whole put: on the plane, stage +
        # dispatch + any ring commit wait, each a stage of its own inside.
        with self._stage('device_put', batch=n) as put:
            dev = None
            if plane is not None:
                dev = plane.put(
                    _filter_numeric(host_batch, self._warned_fields))
            degraded = dev is None
            if degraded:   # no plane, or the structure degrades
                dev = self._to_device(host_batch)
                # Only the inline put records the recorder's generic
                # 'device_put' SPAN: a plane-handled batch already emitted
                # h2d/stage + h2d/dispatch (+ h2d/commit) inside this
                # window, and a wrapper span here would fold staging time
                # into the 'h2d' link component — h2d >= h2d_stage by
                # construction — so stall attribution could never name
                # staging as top.
                put.event = 'device_put'
        if self.provenance is not None:
            if degraded:
                stages['h2d_dispatch'] = put.window
                outcome = 'inline' if plane is None else 'degraded'
            else:
                stages.update(plane.last_put['stages'])
                outcome = plane.last_put['outcome']
            self._seal_provenance(stages, transfer=outcome)
        self._m_batches.inc()
        return dev

    def _iter_pumped(self, plane):
        """Transfer-plane iteration: a background dispatch thread pulls
        host batches, transforms, and ring-transfers them, so host
        staging, the H2D link, and the device step overlap as three
        pipeline stages.  Batch order, values, accounting surfaces and
        the exact-resume contract are identical to the inline path."""
        restored = []
        if self._resume_state and self._resume_state.get('pending'):
            restored = [self._to_device(b)
                        for b in self._resume_state['pending']]
            self._resume_state = dict(self._resume_state, pending=[])

        pump = DispatchPump(
            self._timed_pulls(self._echoed_host_batches()),
            lambda host_batch: self._ship(host_batch, plane), self._prefetch)
        for dev in restored:
            pump.pending.append(dev)
        self._pending = pump.pending
        self._pump = pump
        pump.start()
        try:
            yield from self._waited(pump.get)
        finally:
            # Keep self._pump referencing this (now stopping) pump:
            # __exit__'s plane-close guard must still see a thread that
            # outlived the bounded join below, and a paused/`state_dict`
            # call on a finished pump returns immediately.  The short
            # join keeps early `break`s cheap — a thread parked in a
            # slow reader pull is released by reader.stop() in __exit__.
            pump.stop(join_timeout_s=0.2)
            if not pump.alive:
                # Draining the ring under a still-shipping thread
                # (bounded join timed out on a slow/wedged backend)
                # would race _wait_slot/put, and block_until_ready
                # could hang this generator close.
                plane.drain()

    def _iter_inline(self):
        """Everything on the consuming thread: pull, transform and put
        until ``prefetch`` batches are ahead, then hand out the oldest.
        The ``pt/*`` spans make the data pipeline visible in
        ``jax.profiler`` device traces (SURVEY.md §5.1): when a step
        stalls, the trace shows whether the time went to the decode plane
        (pt/host_batch), the user hook (pt/transform), or the H2D dispatch
        (pt/device_put).  Overhead is negligible when no trace is
        active."""
        pending = self._pending = deque()
        if self._resume_state and self._resume_state.get('pending'):
            for host_batch in self._resume_state['pending']:
                pending.append(self._to_device(host_batch))
            self._resume_state = dict(self._resume_state, pending=[])
        pulls = self._timed_pulls(self._echoed_host_batches())

        def get():
            for host_batch in pulls:
                pending.append(self._ship(host_batch))
                self._sample_commit(pending[-1])
                if len(pending) > self._prefetch:
                    return pending.popleft()
            return pending.popleft() if pending else _DONE

        yield from self._waited(get)

    def _host_batches(self):
        gen = (self._columnar_batches() if self._batched_input
               else self._row_batches())
        gen = self._autotuned(gen)
        if self._trace is not None:
            gen = self._ingest_spans_drained(gen)
        return gen

    def _ingest_spans_drained(self, gen):
        """Merge the ingest plane's ``ingest/fetch`` / ``ingest/hedge``
        spans (ISSUE 14) onto this recorder's timeline, once per host
        batch.  Same process, same CLOCK_MONOTONIC — offset 0; so stall
        attribution can name ``ingest_fetch`` as a component."""
        from petastorm_tpu.telemetry.spans import merge_into_recorder
        for batch in gen:
            plane = getattr(self.reader, 'ingest_plane', None)
            if plane is not None:
                merge_into_recorder(self._trace, plane.spans.drain())
            yield batch

    # -- stage autotuning (ISSUE 9) ------------------------------------------

    def attach_stall_monitor(self, monitor):
        """Give the autotuner the consumer's ``StallMonitor``: its
        measured wait fraction over each tuning window is the strongest
        prefetch signal (the consumer actually starving vs merely skewed
        stage quantiles)."""
        self._stall_monitor = monitor
        if self._tuner is not None:
            self._tuner.attach_stall_monitor(monitor)

    def _set_prefetch(self, depth):
        # Read per batch by the inline path; the pumped path picks the
        # new depth up at its next iteration (the pump's bound is fixed
        # per run).
        self._prefetch = max(1, int(depth))

    def _build_autotuner(self):
        """The loader-side autotuner, or None (autotune off, or 'auto'
        with a FIFO reader).  Binds live setters for the three knobs it
        owns: adaptive window, ventilator in-flight bound, prefetch."""
        if self._autotune is False:
            return None
        from petastorm_tpu.workers_pool import scheduling as sched
        ventilator = getattr(self.reader, '_ventilator', None)
        # cache keyed on the ventilator INSTANCE: reader.reset() builds a
        # new pool/ventilator/policy/cost model, and a tuner bound to the
        # old ones would freeze (the fresh-samples gate reads the dead
        # cost model) while writing knobs into stopped objects
        if self._tuner is not None and ventilator is self._tuner_ventilator:
            return self._tuner
        self._tuner = None
        policy = getattr(ventilator, '_policy', None)
        adaptive = bool(getattr(policy, 'adaptive', False))
        if self._autotune == 'auto' and not adaptive:
            return None
        knobs = sched.SchedulerKnobs(
            window=getattr(policy, 'window', sched.MIN_WINDOW),
            max_inflight=getattr(ventilator, 'max_inflight',
                                 sched.MIN_INFLIGHT),
            prefetch=self._prefetch)
        if adaptive:
            knobs.bind('window',
                       lambda v, p=policy: setattr(p, 'window', v))
            # the in-flight bound doubles as the reorder-depth knob, so
            # it is only the tuner's to move on adaptive readers — on a
            # FIFO reader (autotune=True) shrinking it would just
            # throttle the pipeline below the pool size ("FIFO readers
            # tune prefetch only", the documented contract)
            if ventilator is not None \
                    and hasattr(ventilator, 'set_max_inflight'):
                knobs.bind('max_inflight', ventilator.set_max_inflight)
        knobs.bind('prefetch', self._set_prefetch)
        # Ingest plane (ISSUE 14): the readahead window is the fourth
        # knob — grown when decode measurably blocks on fetches, shrunk
        # gently when a window of fetches completed with zero waits.
        ingest_plane = getattr(self.reader, 'ingest_plane', None)
        if ingest_plane is not None:
            knobs.ingest_window = ingest_plane.window
            knobs.bind('ingest_window', ingest_plane.set_window)
        self._knobs = knobs
        # the no-skew shrink floor scales with the pool: the in-flight
        # bound counts undelivered positions (ack-on-delivery), so
        # dropping it below 2x workers would idle workers FIFO's own
        # default bound keeps busy
        workers = getattr(getattr(self.reader, '_pool', None),
                          'workers_count', 0) or 0
        self._tuner = sched.Autotuner(
            registry=self.metrics,
            cost_model=getattr(self.reader, 'cost_model', None),
            stall_monitor=self._stall_monitor,
            min_inflight=max(sched.MIN_INFLIGHT, 2 * workers))
        if ingest_plane is not None:
            self._tuner.attach_ingest(ingest_plane)
            self.metrics.gauge('sched_ingest_window').set(knobs.ingest_window)
        self._tuner_ventilator = ventilator
        # publish the starting point so the gauges tell the whole story
        self.metrics.gauge('sched_window').set(knobs.window)
        self.metrics.gauge('sched_max_inflight').set(knobs.max_inflight)
        self.metrics.gauge('sched_prefetch').set(knobs.prefetch)
        return self._tuner

    def _autotuned(self, gen):
        tuner = self._build_autotuner()
        if tuner is None:
            return gen
        reader_metrics = getattr(self.reader, 'metrics', None)
        decode_hist = (reader_metrics.histogram('decode')
                       if reader_metrics is not None else None)
        host_hist = self.metrics.histogram('host_batch')
        put_hist = self.metrics.histogram('device_put')

        def ticked():
            for batch in gen:
                yield batch
                tuner.maybe_tune(self._knobs, decode=decode_hist,
                                 host_batch=host_hist, device_put=put_hist)
        return ticked()

    def _echoed_host_batches(self):
        """Host batches with data echoing: each decoded batch repeats
        ``echo`` times consecutively (Choi et al., "Faster Neural Network
        Training with Data Echoing") — when the decode plane, not the
        chip, is the bottleneck, e echoes cut the required decode rate
        e-fold; device-side augmentation (``petastorm_tpu.jax.augment``
        inside the step, fresh rng per step) keeps echoes from being
        exact repeats.  A mid-echo checkpoint resumes at the batch, not
        the echo repeat (echo is a schedule over data, not data).

        Echo repeats are dict-level-recursive copies, so a ``transform_fn``
        that REBINDS keys (at any nesting level — ngram batches are
        dict-of-dicts) is applied freshly per echo (host augmentation
        varies across echoes).  Transforms must not mutate input arrays
        in place — with echo the same arrays are visible to every
        repeat, so in-place mutation would compound."""
        if self._echo <= 1:
            return self._host_batches()

        def copy_tree(node):
            if isinstance(node, dict):
                return {k: copy_tree(v) for k, v in node.items()}
            return node

        def gen():
            for host_batch in self._host_batches():
                yield host_batch
                for _ in range(self._echo - 1):
                    yield copy_tree(host_batch)
        return gen()

    def _source(self, convert):
        """Pushback (restored/drained) items first, then converted reader
        output — re-checking pushback before every reader pull so data
        reinjected by ``state_dict`` keeps stream order."""
        reader_iter = iter(self.reader)
        while True:
            if self._pushback:
                yield self._pushback.pop(0)
                continue
            try:
                item = next(reader_iter)
            except StopIteration:
                if self._pushback:
                    continue
                return
            yield convert(item)

    def _row_source(self):
        return self._source(_row_as_dict)

    def _chunk_source(self):
        return self._source(
            lambda c: c._asdict() if hasattr(c, '_asdict') else dict(c))

    def _row_batches(self):
        """Row readers: buffer namedtuple/pytree rows, stack per batch."""
        if self._shuffle_capacity > 0:
            from petastorm_tpu.reader_impl.shuffling_buffer import RandomShufflingBuffer
            buffer = RandomShufflingBuffer(self._shuffle_capacity,
                                           self._min_after_retrieve, seed=self._seed)
        else:
            from petastorm_tpu.reader_impl.shuffling_buffer import NoopShufflingBuffer
            buffer = NoopShufflingBuffer()
        if self._resume_state and self._resume_state.get('shuffle_buffer'):
            buffer.load_state_dict(self._resume_state['shuffle_buffer'])
        self._shuffle_buf = buffer
        self._partial_rows = list((self._resume_state or {}).get('partial_rows', []))

        # State is detached BEFORE each yield: the generator suspends at the
        # yield, and a state_dict() taken there must not see rows that are
        # already inside the yielded batch.
        bs = self.batch_size
        for row in self._row_source():
            buffer.add_many([row])
            while buffer.can_retrieve():
                self._partial_rows.append(buffer.retrieve())
                if len(self._partial_rows) >= bs:
                    out, self._partial_rows = (self._partial_rows[:bs],
                                               self._partial_rows[bs:])
                    yield self._stack_rows(out)
        buffer.finish()
        while not buffer.finished:
            self._partial_rows.append(buffer.retrieve())
            if len(self._partial_rows) >= bs:
                out, self._partial_rows = (self._partial_rows[:bs],
                                           self._partial_rows[bs:])
                yield self._stack_rows(out)
        if self._partial_rows and not self._drop_last:
            out, self._partial_rows = self._partial_rows, []
            yield self._stack_rows(out)

    def _stack_rows(self, rows):
        """Stack a list of row structures (namedtuples / ngram dicts) into one
        dict pytree of (B, ...) arrays.  Plain-python recursion rather than
        tree_map: None cells (nullable fields) are data here, not empty
        subtrees."""
        return _stack_dicts([_row_as_dict(r) for r in rows])

    def _columnar_batches(self):
        """Batch readers: re-batch column chunks; no per-row loop.

        Non-shuffle path is copy-free where possible: a chunk exactly
        batch_size long passes through untouched; otherwise batches are
        sliced views across a chunk deque with at most one concatenate per
        boundary-straddling batch.
        """
        if self._shuffle_capacity > 0:
            yield from self._columnar_batches_shuffled()
            return

        chunks = deque()   # (chunk_dict, start_offset); shared for snapshots
        self._col_chunks = chunks
        count = 0
        if self._resume_state and self._resume_state.get('chunks'):
            for chunk_dict in self._resume_state['chunks']:
                n = len(next(iter(chunk_dict.values())))
                chunks.append((chunk_dict, 0))
                count += n
        for chunk_dict in self._chunk_source():
            n = len(next(iter(chunk_dict.values())))
            if count == 0 and n == self.batch_size:
                yield chunk_dict  # zero-copy pass-through (the common case)
                continue
            chunks.append((chunk_dict, 0))
            count += n
            while count >= self.batch_size:
                yield self._take_front(chunks, self.batch_size)
                count -= self.batch_size
        if count and not self._drop_last:
            yield self._take_front(chunks, count)

    @staticmethod
    def _take_front(chunks, size):
        """Pop ``size`` rows off the front of the chunk deque; slices are
        views, concatenation only happens across chunk boundaries."""
        parts = []
        need = size
        while need > 0:
            chunk_dict, start = chunks.popleft()
            n = len(next(iter(chunk_dict.values())))
            avail = n - start
            take = min(avail, need)
            parts.append({k: v[start:start + take] for k, v in chunk_dict.items()})
            if take < avail:
                chunks.appendleft((chunk_dict, start + take))
            need -= take
        if len(parts) == 1:
            return parts[0]
        return {k: np.concatenate([p[k] for p in parts]) for k in parts[0]}

    def _columnar_batches_shuffled(self):
        """Windowed columnar shuffle: uniform draws from a >=capacity buffer.

        State (accumulated columns, row count, rng) lives in ``self._colsh``
        so ``state_dict`` can snapshot it mid-epoch."""
        st = self._colsh = {'rng': np.random.default_rng(self._seed),
                            'columns': None,  # field -> [np.ndarray]
                            'count': 0}
        if self._resume_state and self._resume_state.get('col_shuffle'):
            saved = self._resume_state['col_shuffle']
            st['rng'].bit_generator.state = saved['rng_state']
            if saved['columns'] is not None:
                st['columns'] = {k: [v] for k, v in saved['columns'].items()}
                st['count'] = len(next(iter(saved['columns'].values())))
        for chunk_dict in self._chunk_source():
            n = len(next(iter(chunk_dict.values())))
            if st['columns'] is None:
                st['columns'] = {k: [v] for k, v in chunk_dict.items()}
            else:
                for k, v in chunk_dict.items():
                    st['columns'][k].append(v)
            st['count'] += n
            threshold = max(self.batch_size, self._shuffle_capacity)
            while st['count'] >= threshold:
                st['columns'] = {k: [np.concatenate(v)] if len(v) > 1 else v
                                 for k, v in st['columns'].items()}
                take = st['rng'].permutation(st['count'])[:self.batch_size]
                batch = {k: np.take(v[0], take, axis=0)
                         for k, v in st['columns'].items()}
                keep = np.ones(st['count'], dtype=bool)
                keep[take] = False
                st['columns'] = {k: [v[0][keep]]
                                 for k, v in st['columns'].items()}
                st['count'] -= self.batch_size
                yield batch
        # Drain remainder.
        if st['count'] and st['columns']:
            st['columns'] = {k: [np.concatenate(v)] if len(v) > 1 else v
                             for k, v in st['columns'].items()}
            order = st['rng'].permutation(st['count'])
            start = 0
            while st['count'] - start >= self.batch_size:
                take = order[start:start + self.batch_size]
                yield {k: np.take(v[0], take, axis=0)
                       for k, v in st['columns'].items()}
                start += self.batch_size
            if st['count'] - start > 0 and not self._drop_last:
                take = order[start:]
                yield {k: np.take(v[0], take, axis=0)
                       for k, v in st['columns'].items()}

    # -- device transfer -----------------------------------------------------

    def _to_device(self, host_batch):
        numeric = _filter_numeric(host_batch, self._warned_fields)
        if self._sharding is not None:
            return global_batch_from_local(numeric, self._sharding)
        if self._device is not None:
            return jax.device_put(numeric, self._device)
        return jax.device_put(numeric)

    def iter_host_batches(self):
        """Yield the host-side numpy batch pytrees WITHOUT device transfer.

        The same batches ``__iter__`` would stage (shuffling, batching,
        ``transform_fn``, resume all apply) but stopping at the host
        boundary: for feeding non-JAX consumers, writing derived datasets,
        or measuring the host delivery plane in isolation (the doctor's
        host-plane section and ``benchmark.autotune`` do).

        Caveat on resume: batches restored from ``resume_state`` were
        snapshotted AFTER the device-transfer filter, so they carry only
        numeric fields (string/object columns are gone) — fresh batches
        that follow carry every field.  Consumers that need non-numeric
        columns for every row should checkpoint with the prefetch queue
        drained, or tolerate the narrower leading batches.
        """
        # Restored prefetched batches first (already transformed when
        # snapshotted — do not run the transform twice).
        if self._resume_state and self._resume_state.get('pending'):
            restored = self._resume_state['pending']
            self._resume_state = dict(self._resume_state, pending=[])
            for host_batch in restored:
                self._m_batches.inc()
                yield host_batch
        # Same per-stage accounting as __iter__ (minus device_put — there
        # is none here), so the bottleneck advisor and the doctor can
        # diagnose a host-boundary consumer too.
        for host_batch in self._timed_pulls(self._echoed_host_batches()):
            stages = {}
            if self._last_pull_window is not None:
                stages['host_batch'] = list(self._last_pull_window)
            if self._transform_fn is not None:
                with self._stage('transform', event='transform') as transform:
                    host_batch = self._transform_fn(host_batch)
                stages['transform'] = transform.window
            if self.provenance is not None:
                self._seal_provenance(stages)
            self._m_batches.inc()
            yield host_batch

    def _timed_pulls(self, gen):
        """Yield from ``gen``, accounting the wait on the decode plane
        into ``stats['host_batch_s']`` (+ a trace span) — the one place
        that owns pull accounting for every host-boundary consumer
        (``iter_host_batches``, ``scan_batches``)."""
        while True:
            try:
                with self._stage('host_batch', event='host_batch') as pull:
                    host_batch = next(gen)
            except StopIteration:
                return
            # Provenance: the pull window of the batch about to be
            # consumed (read by _ship() / the host-boundary consumers on
            # the same thread).
            self._last_pull_window = pull.window
            yield host_batch

    # -- fused multi-step consumption ----------------------------------------

    def scan_batches(self, step_fn, carry, steps_per_call=8,
                     donate_carry=True):
        """Consume the stream with ONE jitted dispatch per ``steps_per_call``
        steps instead of two per step.

        Host batches are collected in chunks of ``steps_per_call``, stacked
        to ``(k, batch, ...)``, transferred in a single ``device_put`` (same
        bytes, 1/k the transfer dispatches), and run through
        ``lax.scan(step_fn, carry, chunk)`` as one executable.  Per-step
        dispatch overhead — python + transport round-trips, the dominant
        stall for fast steps or high-latency links — shrinks by k×, while
        host decode of the next chunk still overlaps device compute (the
        scan call is async).

        ``step_fn(carry, batch) -> (carry, out)`` sees exactly the batches
        ``__iter__`` would deliver.  Yields ``(carry, outs)`` per chunk
        (``outs`` stacked along a leading axis of length k).  A trailing
        chunk shorter than ``steps_per_call`` triggers one extra compile
        for its size.  With ``sharding=``, each stacked leaf is assembled
        as a global array with a leading unsharded step axis.

        The HBM-cached sibling (``DeviceInMemDataLoader.scan_epochs``)
        removes host work entirely; this is the streaming-regime analog
        where data must flow host→device every step regardless.

        Checkpointing composes: batches restored from ``resume_state``
        (prefetched by the previous run) are served first, and every
        full-chunk ``yield`` has an empty fill buffer (each yield follows
        a flush), so a ``state_dict()`` taken between yields loses
        nothing under the default ``drop_last=True`` — the exact-resume
        contract survives switching between ``__iter__`` and
        ``scan_batches`` consumption.  One carve-out: with
        ``drop_last=False``, the yield forced by the ragged tail batch
        holds that tail outside the snapshot — checkpointing at exactly
        that yield (the stream's final flush) drops the tail rows; keep
        ``drop_last=True`` when mid-stream checkpoints must be exact.
        """
        from jax import lax

        if steps_per_call < 1:
            raise ValueError('steps_per_call must be >= 1')
        fn = jax.jit(lambda c, xs: lax.scan(step_fn, c, xs),
                     donate_argnums=(0,) if donate_carry else ())
        # The stacked chunk rides the transfer plane too (one coalesced
        # ring transfer per k-step chunk); the sharded scan spec shards
        # axis 1, not the leading axis, so it keeps the existing
        # assembly path.
        plane = self._transfer_plane() if self._sharding is None else None

        def put_stacked(chunk, transformed=False):
            # Same per-stage accounting as __iter__ (transform / stack +
            # upload), so the bottleneck advisor can diagnose a
            # scan_batches-consumed loader too.
            k = len(chunk)
            if self._transform_fn is not None and not transformed:
                with self._stage('transform', event='transform', chunk=k):
                    chunk = [self._transform_fn(b) for b in chunk]
            with self._stage('device_put', chunk=k) as put:
                stacked = jax.tree_util.tree_map(
                    lambda *xs: np.stack(xs), *chunk)
                numeric = _filter_numeric(stacked, self._warned_fields)
                out = None
                if self._sharding is not None:
                    from jax.sharding import NamedSharding, PartitionSpec
                    spec = PartitionSpec(None, *self._sharding.spec)
                    out = global_batch_from_local(
                        numeric, NamedSharding(self._sharding.mesh, spec))
                elif plane is not None:
                    out = plane.put(numeric)   # None: degrade to inline
                planed = out is not None and plane is not None
                if out is None:
                    if self._device is not None:
                        out = jax.device_put(numeric, self._device)
                    else:
                        out = jax.device_put(numeric)
                if not planed:
                    # Plane-handled chunks already emitted h2d/* spans in
                    # this window; a wrapper 'device_put' span would fold
                    # staging into the link component (see _ship()).
                    put.event = 'device_put'
            if not planed:
                self._sample_commit(out, every=4)
            return out

        def rows_of(batch):
            return len(next(iter(jax.tree_util.tree_leaves(batch))))

        # Batches the interrupted run had already prefetched come first —
        # one 1-step scan each.  They were snapshotted POST-transform and
        # post-filter (state_dict stores what __iter__ had staged for the
        # device), so the transform must not run again; sizes may vary,
        # and mixing their numeric-only structure into a fresh chunk would
        # break stacking — hence one call each.
        if self._resume_state and self._resume_state.get('pending'):
            restored = self._resume_state['pending']
            self._resume_state = dict(self._resume_state, pending=[])
            for host_batch in restored:
                self._m_batches.inc()
                carry, outs = fn(carry, put_stacked([host_batch],
                                                    transformed=True))
                yield carry, outs

        chunk = []
        for host_batch in self._timed_pulls(self._echoed_host_batches()):
            if chunk and rows_of(host_batch) != rows_of(chunk[0]):
                # ragged tail (drop_last=False): flush so stacking stays
                # rectangular — the tail becomes its own (shorter) chunk
                carry, outs = fn(carry, put_stacked(chunk))
                chunk = []
                yield carry, outs
            chunk.append(host_batch)
            if self.provenance is not None:
                self._seal_provenance(
                    {'host_batch': list(self._last_pull_window)}
                    if self._last_pull_window is not None else {})
            self._m_batches.inc()
            if len(chunk) == steps_per_call:
                carry, outs = fn(carry, put_stacked(chunk))
                chunk = []
                yield carry, outs
        if chunk:
            carry, outs = fn(carry, put_stacked(chunk))
            yield carry, outs

    # -- exact mid-epoch checkpoint/resume -----------------------------------

    def state_dict(self):
        """EXACT mid-stream snapshot; resume with ``DataLoader(reader',
        batch_size, ..., resume_state=state)`` where ``reader'`` is built
        with ``resume_state=state['reader']``.

        Exactness contract: the restored loader yields precisely the
        batches the uninterrupted run had not yet yielded — same row
        multiset always, same order/content for seeded single-threaded
        (``dummy`` pool) runs.  Achieved by DRAINING: the reader pauses
        dispatch and every in-flight result is pulled into the snapshot
        (in-flight rows would otherwise replay or be lost at row-group
        granularity), alongside the prefetched device batches, the
        shuffling-buffer contents + rng state, the partial batch, and
        columnar chunk residue.  Snapshot size is bounded by the reader's
        in-flight window plus loader buffers.

        Call between batches from the consuming thread.  The loader keeps
        serving afterwards (drained rows are reinjected locally), so
        checkpoint-then-keep-training works.  The state is picklable
        (plain dicts/numpy); pair it with the model state in orbax via
        ``ocp.args.Pickle`` or bytes.

        With the transfer plane on, the background dispatch pump is
        paused first (it otherwise advances the shuffle/chunk buffers
        this snapshot reads) and every in-flight ring batch is already
        in ``pending`` by the time the pump is quiescent — the snapshot
        drains the ring by construction.
        """
        with self._pump_paused():
            return self._state_dict_quiesced()

    @contextmanager
    def _pump_paused(self):
        """Freeze the dispatch pump (when one is live) around a state
        snapshot.  EVERY ``state_dict`` in the loader family must read
        loader buffers under this bracket — outside it the dispatch
        thread races the shuffle/chunk/packer state being snapshotted.
        Counting pause, so brackets nest (PackedDataLoader wraps the
        base snapshot plus its packer residue in one outer bracket)."""
        pump = self._pump
        if pump is not None:
            pump.pause()
        try:
            yield
        finally:
            if pump is not None:
                pump.resume()

    def _state_dict_quiesced(self):
        drained = self.reader.drain_in_flight()
        if not self._batched_input:
            drained = [_row_as_dict(r) for r in drained]
        else:
            drained = [r._asdict() if hasattr(r, '_asdict') else dict(r)
                       for r in drained]
        # A loader restored from resume_state consumes the restored pieces
        # LAZILY (pending at first __iter__, buffers at first host batch);
        # until then the snapshot must carry them forward, not drop them.
        rs = self._resume_state or {}
        iterating = self._shuffle_buf is not None or self._col_chunks is not None \
            or self._colsh is not None
        state = {
            'version': 1,
            'batched': self._batched_input,
            'reader': self.reader.state_dict(),
            'pending': ([jax.device_get(b) for b in self._pending]
                        + list(rs.get('pending', []))),
            'pushback': list(self._pushback) + drained,
            'partial_rows': (list(self._partial_rows) if iterating
                             else list(rs.get('partial_rows', []))),
            'shuffle_buffer': (self._shuffle_buf.state_dict()
                               if self._shuffle_buf is not None
                               else rs.get('shuffle_buffer')),
            'chunks': ([{k: v[start:] for k, v in chunk.items()}
                        for chunk, start in self._col_chunks]
                       if self._col_chunks is not None
                       else list(rs.get('chunks', []))),
            'col_shuffle': rs.get('col_shuffle'),
        }
        if self._colsh is not None:
            cols = self._colsh['columns']
            state['col_shuffle'] = {
                'rng_state': self._colsh['rng'].bit_generator.state,
                'columns': (None if cols is None else
                            {k: (np.concatenate(v) if len(v) > 1 else v[0])
                             for k, v in cols.items()}),
            }
        self._pushback.extend(drained)
        self.reader.resume_dispatch()
        return state

    # -- lifecycle -----------------------------------------------------------

    @property
    def diagnostics(self):
        """The loader's registry view (per-stage seconds + log2-histogram
        p50/p99s) merged with the reader's pool diagnostics — including
        the epoch-cache plane counters (``cache_hits`` / ``cache_misses``
        / ``cache_evictions``) when the underlying reader runs
        ``cache_type='plane'``, so one gauge read says whether this epoch
        decoded or served warm."""
        out = self.metrics.as_dict()
        out['batches'] = int(out.get('batches', 0))
        if self.reader is not None:
            out.update(getattr(self.reader, 'diagnostics', None) or {})
        return out

    def __enter__(self):
        if not self._gc_watched:
            # From here to __exit__ every garbage collection of the
            # process is timed (process_gc_*): one that stops the
            # training thread is a stall no stage of the loader explains.
            from petastorm_tpu.telemetry import flight
            self._gc_watched = True
            flight.watch_gc()
        return self

    def __exit__(self, exc_type, exc_value, tb):
        if self._gc_watched:
            from petastorm_tpu.telemetry import flight
            self._gc_watched = False
            flight.unwatch_gc()
        pump = self._pump
        if pump is not None:
            # Ask the dispatch thread out first; a pull blocked inside
            # the reader is released by reader.stop() below, after which
            # the (daemonic) thread exits without shipping.
            pump.stop(join_timeout_s=0.5)
        if self.reader is not None:   # DiskCachedDataLoader allows None
            self.reader.stop()
            self.reader.join()
        if pump is not None:
            pump.join()
        if self._plane is not None and (pump is None or not pump.alive):
            # Only reclaim the slabs once the dispatch thread is truly
            # out — closing under a still-shipping thread (wedged
            # backend) would race the ring; the slabs are plain numpy
            # arrays and fall to the GC with the loader either way.
            self._plane.close()


def _row_as_dict(row):
    if hasattr(row, '_asdict'):
        row = row._asdict()
    if isinstance(row, dict):
        return {k: _row_as_dict(v) for k, v in row.items()}
    return row


def _stack_dicts(dicts):
    out = {}
    for key in dicts[0]:
        values = [d[key] for d in dicts]
        out[key] = _stack_dicts(values) if isinstance(values[0], dict) \
            else _stack_cells(values)
    return out


def _stack_cells(cells):
    first = next((c for c in cells if c is not None), None)
    if first is None or isinstance(first, str) or isinstance(first, bytes):
        out = np.empty(len(cells), dtype=object)
        out[:] = list(cells)
        return out
    return np.stack([c if c is not None else np.zeros_like(first) for c in cells])


def _filter_numeric(tree, warned):
    """Drop object-dtype (string/None) leaves — they cannot live in HBM."""
    leaves_with_path = jax.tree_util.tree_flatten_with_path(tree)[0]
    drop = set()
    for path, leaf in leaves_with_path:
        arr = np.asarray(leaf)
        if arr.dtype == object or arr.dtype.kind in ('U', 'S'):
            key = jax.tree_util.keystr(path)
            drop.add(key)
            if key not in warned:
                warned.add(key)
                logger.warning('Field %s has non-numeric dtype %s; kept on host '
                               '(excluded from device batch)', key, arr.dtype)

    def prune(path, leaf):
        return None if jax.tree_util.keystr(path) in drop else leaf

    pruned = jax.tree_util.tree_map_with_path(prune, tree)
    return _strip_none_leaves(pruned)


def _strip_none_leaves(obj):
    """Recursively drop None leaves; namedtuples become plain dicts (a
    device batch is a pytree, the row type is irrelevant past this point)."""
    if hasattr(obj, '_asdict'):
        obj = obj._asdict()
    if isinstance(obj, dict):
        out = {k: _strip_none_leaves(v) for k, v in obj.items()}
        return {k: v for k, v in out.items() if v is not None}
    return obj


def _canonical_row_order(cache):
    """Reorder an ``(N, ...)`` pytree of rows into a content-defined
    canonical order: sort by a per-row digest over fields in name order.

    Any worker pool delivers the same row MULTISET; after this sort any
    pool also yields the same SEQUENCE — which is what makes an exact
    in-memory resume token valid across a process restart that rebuilds
    the cache through a differently-ordered pool.  Identical rows tie on
    digest, and identical rows are interchangeable, so ties are harmless.
    Cost: one hashing pass over the decoded dataset at build time."""
    import hashlib

    items = sorted(cache.items()) if isinstance(cache, dict) else None
    if items is None:  # non-dict pytree: flatten with stable path order
        paths = jax.tree_util.tree_flatten_with_path(cache)[0]
        items = [(jax.tree_util.keystr(p), leaf) for p, leaf in paths]
        items.sort()
    n = len(items[0][1])
    digests = []
    for i in range(n):
        h = hashlib.blake2b(digest_size=16)
        for _, leaf in items:
            h.update(np.ascontiguousarray(leaf[i]).tobytes())
        digests.append(h.digest())
    idx = np.asarray(sorted(range(n), key=digests.__getitem__))
    return jax.tree_util.tree_map(lambda v: v[idx], cache)


class InMemDataLoader(DataLoader):
    """Epoch-cached loader: reads the dataset once, then serves ``num_epochs``
    (re)shuffled epochs straight from host RAM — no Parquet re-read, no
    decode-plane work after epoch 0.

    Parity: ``petastorm/pytorch.py :: InMemBatchedDataLoader``.  The right
    tool when the (decoded) dataset fits in host memory and epochs are short
    — e.g. MNIST-scale fine-tuning where reader startup would dominate.
    Construct the underlying reader with ``num_epochs=1``; epoch repetition
    happens here.

    ``deterministic_cache_order=True`` sorts the built cache into a
    content-defined canonical order (:func:`_canonical_row_order`), which
    makes the epoch sequence a pure function of ``(dataset, seed)`` — any
    pool, any restart — and unlocks exact mid-epoch ``state_dict`` /
    ``resume_state``, same contract as :class:`DiskCachedDataLoader`.
    """

    def __init__(self, reader, batch_size, num_epochs=1, shuffle=True,
                 seed=None, deterministic_cache_order=False, **kwargs):
        if getattr(reader, 'ngram', None) is not None:
            raise ValueError('InMemDataLoader does not support NGram readers')
        if kwargs.get('echo', 1) != 1:
            # Epochs serve from the cache — nothing decodes per step, so
            # echo would just duplicate cached batches silently.  (Covers
            # DeviceInMemDataLoader too; echo addresses decode-bound
            # STREAMING, where DataLoader and DiskCachedDataLoader keep it.)
            raise ValueError('%s does not support echo (epochs serve from '
                             'an in-memory cache; echo addresses '
                             'decode-bound streaming)' % type(self).__name__)
        reader_epochs = getattr(reader, 'num_epochs', 1)
        if reader_epochs != 1:
            # num_epochs=None (infinite) would hang the one-time cache build
            # forever; >1 would silently duplicate every row in the cache.
            raise ValueError(
                'InMemDataLoader requires a reader built with num_epochs=1 '
                '(got num_epochs=%r); epoch repetition happens in the loader'
                % (reader_epochs,))
        super(InMemDataLoader, self).__init__(reader, batch_size, seed=seed, **kwargs)
        self._num_epochs = num_epochs
        self._shuffle = shuffle
        self._deterministic = bool(deterministic_cache_order)
        self._cache = None
        self._im = None  # mid-epoch cursor (deterministic order only)

    def _build_cache(self):
        """One-time read of the whole dataset into ``self._cache`` (a dict
        pytree of (N, ...) host arrays); returns it, or None when empty."""
        if self._cache is None:
            # The cache must hold EVERY row: drop_last applies per epoch, not
            # to the one-time read — otherwise a ragged tail would be
            # excluded from all epochs permanently.
            drop_last, self._drop_last = self._drop_last, False
            try:
                parts = list(super(InMemDataLoader, self)._host_batches())
            finally:
                self._drop_last = drop_last
            if not parts:
                return None
            cache = jax.tree_util.tree_map(
                lambda *xs: np.concatenate(xs), *parts)
            if self._deterministic:
                numeric = _filter_numeric(cache, self._warned_fields)
                if not jax.tree_util.tree_leaves(numeric):
                    raise ValueError(
                        'deterministic_cache_order=True requires at least '
                        'one numeric field (the canonical order hashes '
                        'numeric row content; every field here is '
                        'object/string-typed)')
                cache = _canonical_row_order(numeric)
            self._cache = cache
        return self._cache

    def _host_batches(self):
        if self._build_cache() is None:
            return
        n = len(next(iter(jax.tree_util.tree_leaves(self._cache))))
        if self._drop_last and n < self.batch_size:
            # num_epochs=None would otherwise spin forever yielding nothing
            logger.warning('epoch cache holds %d rows < batch_size=%d with '
                           'drop_last: no batches to serve', n, self.batch_size)
            return
        rng = np.random.default_rng(self._seed)
        epoch = 0
        order = None
        offset = 0
        resumed = (self._resume_state or {}).get('inmem_cache')
        if resumed:
            if not self._deterministic:
                raise ValueError(
                    'this resume token requires '
                    'deterministic_cache_order=True (the rebuilt cache '
                    'must reproduce the checkpointed row order)')
            rng.bit_generator.state = resumed['rng_state']
            epoch = int(resumed['epoch'])
            offset = int(resumed['offset'])
            order = (None if resumed['order'] is None
                     else np.asarray(resumed['order']))
        if self._deterministic:
            self._im = {'rng': rng, 'epoch': epoch, 'order': order,
                        'offset': offset}
        while self._num_epochs is None or epoch < self._num_epochs:
            if order is None:
                order = rng.permutation(n) if self._shuffle else np.arange(n)
            stop = n - self.batch_size + 1 if self._drop_last else n
            for start in range(offset, max(stop, 0), self.batch_size):
                if self._im is not None:
                    self._im.update(epoch=epoch, order=order,
                                    offset=start + self.batch_size)
                idx = order[start:start + self.batch_size]
                yield jax.tree_util.tree_map(lambda v: v[idx], self._cache)
            epoch += 1
            order = None
            offset = 0
            if self._im is not None:
                self._im.update(epoch=epoch, order=None, offset=0)

    def state_dict(self):
        """Exact mid-epoch resume token — requires
        ``deterministic_cache_order=True`` (the canonical cache order is
        what survives a restart; a pool-ordered cache does not)."""
        if not self._deterministic:
            raise NotImplementedError(
                'In-memory epoch caches are rebuilt from the reader, whose '
                'delivery order is pool-dependent, so an exact mid-epoch '
                'token cannot survive a process restart.  Build the loader '
                'with deterministic_cache_order=True (content-sorted cache, '
                'exact resume on any pool), checkpoint at epoch boundaries '
                '(rebuild with num_epochs reduced), or use '
                'DiskCachedDataLoader: its on-disk cache preserves row '
                'order and supports exact mid-epoch resume.')
        if self._im is None:
            raise ValueError('state_dict() is supported once iteration has '
                             'begun; call it between batches')
        with self._pump_paused():
            return self._inmem_state()

    def _inmem_state(self):
        im = self._im
        return {
            'version': 1,
            'pending': [jax.device_get(b) for b in self._pending],
            'inmem_cache': {
                'rng_state': im['rng'].bit_generator.state,
                'epoch': int(im['epoch']),
                'offset': int(im['offset']),
                'order': (None if im['order'] is None
                          else np.asarray(im['order'])),
            },
        }


class DeviceInMemDataLoader(InMemDataLoader):
    """Epoch cache in **device HBM**: decode the dataset once, then serve
    every subsequent batch with an on-device gather — zero host work per
    step after epoch 0.

    The TPU-native sibling of :class:`InMemDataLoader` (which caches in host
    RAM and still pays slice + H2D per batch).  When the decoded dataset fits
    in HBM (MNIST/CIFAR-scale, or a per-host ImageNet shard at low
    resolution), this is the idiomatic XLA pattern: the per-epoch shuffle is
    a device-side permutation (``jax.random.permutation``) and each batch is
    ``jnp.take`` over the resident arrays, so a fast chip is never throttled
    by host decode or PCIe latency.

    Single-placement only: the cache lives on ``device`` (default: first
    local device).  Multi-host training wants per-host shards anyway — build
    the reader with ``cur_shard``/``shard_count`` (or rely on JAX auto-shard)
    and each host caches only its shard.
    """

    def __init__(self, reader, batch_size, num_epochs=1, shuffle=True,
                 seed=None, device=None, **kwargs):
        for unsupported in ('transform_fn', 'shuffling_queue_capacity'):
            if kwargs.get(unsupported):
                # Batches never exist on the host here, so the host-side
                # hooks cannot run — reject rather than silently drop them.
                # Transform inside the jitted step instead (the TPU-native
                # place for normalization/augmentation).
                raise ValueError('DeviceInMemDataLoader does not support %s'
                                 % unsupported)
        super(DeviceInMemDataLoader, self).__init__(
            reader, batch_size, num_epochs=num_epochs, shuffle=shuffle,
            seed=seed, device=device, **kwargs)
        if self._sharding is not None:
            raise ValueError('DeviceInMemDataLoader caches on one device; '
                             'use InMemDataLoader with sharding= for global '
                             'batch assembly')
        self._dev_cache = None
        self._gather_fn = None
        self._steps_into_epoch = 0
        #: (epochs, steps) to SKIP at the head of every pass (from a resume
        #: token); static — re-iterating the loader replays this baseline.
        self._start_epoch = 0
        self._start_step = 0
        #: live position of the CURRENT pass (state_dict reads it); reset
        #: to the baseline whenever a fresh pass begins.
        self._epochs_done = 0
        #: ``drop_last`` of the run that TOOK the resume token (None when
        #: not resuming, or for pre-drop_last tokens).  The step cursor's
        #: meaning depends on it: only a drop_last=False per-step pass can
        #: legitimately park the cursor AT the full-batch count (inside the
        #: ragged tail), so scan_epochs keys its max-cursor bound off this,
        #: not off the resuming loader's own flag.
        self._token_drop_last = None
        resumed = (self._resume_state or {}).get('device_inmem')
        if resumed:
            if seed is None or int(resumed['seed']) != int(seed):
                raise ValueError(
                    'device_inmem resume token was taken with seed=%r; '
                    'rebuild the loader with that explicit seed (the '
                    'permutation stream is derived from it)'
                    % (resumed['seed'],))
            self._start_epoch = int(resumed['epochs_done'])
            self._start_step = int(resumed.get('steps_into_epoch', 0))
            if resumed.get('drop_last') is not None:
                self._token_drop_last = bool(resumed['drop_last'])
            token_bs = resumed.get('batch_size')
            if self._start_step and token_bs is not None \
                    and int(token_bs) != int(batch_size):
                # Only the MID-epoch cursor counts batches of a particular
                # size; an epoch-boundary token stays batch-size-independent
                # (resuming with a different batch_size there is valid).
                raise ValueError(
                    'device_inmem resume token was taken %d steps into an '
                    'epoch of batch_size=%d batches; resume with that '
                    'batch_size (got %d), or checkpoint at an epoch '
                    'boundary to change it'
                    % (self._start_step, int(token_bs), int(batch_size)))
            if self._start_step and not self._deterministic:
                raise ValueError(
                    'mid-epoch device_inmem resume requires '
                    'deterministic_cache_order=True: the step cursor indexes '
                    'into the cached row order, which only the canonical '
                    'content-sorted cache reproduces across restarts')
            self._epochs_done = self._start_epoch
            # A state_dict() taken BEFORE the first next() must re-emit the
            # restored cursor, not an epoch-start rewind of it.
            self._steps_into_epoch = self._start_step

    def _materialize(self):
        """Build the HBM-resident epoch cache (idempotent); returns the
        device pytree or None when the dataset is empty.

        The degenerate single-entry case of the residency LRU
        (``petastorm_tpu.jax.residency``): the whole dataset is one
        "entry", admitted once via :func:`residency.place_once` and never
        evicted.  Re-entry (a new pass, a new ``scan_epochs`` call)
        revalidates the cached buffers instead of re-issuing a
        dataset-sized ``device_put`` per epoch; buffers invalidated
        underneath us (donated or deleted) raise a clear error rather
        than failing deep inside a gather."""
        from petastorm_tpu.jax import residency

        if self._dev_cache is not None:
            if residency.device_cache_valid(self._dev_cache):
                return self._dev_cache
            # The host copy was released after placement, so the cache
            # cannot be rebuilt from here.
            raise RuntimeError(
                'DeviceInMemDataLoader device cache buffers were deleted '
                '(donated or explicitly freed) after materialization; '
                'rebuild the loader to re-read the dataset')
        # Build the host cache via the parent's one-time read, then move
        # it to HBM wholesale (one transfer for the whole dataset; the
        # transfer plane coalesces it into one staging put when enabled).
        if self._build_cache() is None:
            return None
        numeric = _filter_numeric(self._cache, self._warned_fields)
        self._dev_cache = residency.place_once(
            numeric, plane=self._transfer_plane(), device=self._device)
        # The host copy is never read again — release dataset-sized RAM.
        self._cache = None
        return self._dev_cache

    def __iter__(self):
        import jax.numpy as jnp

        cache = self._materialize()
        if cache is None:
            return iter(())
        n = len(next(iter(jax.tree_util.tree_leaves(cache))))

        if self._gather_fn is None:
            batch_size = self.batch_size

            def _gather(tree, order, start):
                idx = jax.lax.dynamic_slice_in_dim(order, start, batch_size)
                return jax.tree_util.tree_map(
                    lambda v: jnp.take(v, idx, axis=0), tree)

            # One fused dispatch per step (slice + every leaf's gather in a
            # single executable) instead of 1 + n_leaves op-by-op dispatches —
            # per-step dispatch overhead is what separates this loader from
            # the pure device floor.
            self._gather_fn = jax.jit(_gather)

        def gen():
            self._epochs_done = self._start_epoch  # fresh pass
            self._steps_into_epoch = self._start_step
            skip = self._start_step  # mid-epoch baseline: first epoch only
            for order in self._epoch_orders(n):
                stop = n - self.batch_size + 1 if self._drop_last else n
                starts = list(range(0, max(stop, 0), self.batch_size))
                if skip and skip >= len(starts):
                    raise ValueError(
                        'device_inmem resume token is %d steps into an epoch '
                        'of %d steps — the dataset or batch geometry changed '
                        'since the checkpoint' % (skip, len(starts)))
                for j, start in enumerate(starts):
                    if j < skip:
                        continue
                    if start + self.batch_size <= n:
                        batch = self._gather_fn(cache, order, start)
                    else:  # ragged tail (drop_last=False): plain gather
                        idx = order[start:]
                        batch = jax.tree_util.tree_map(
                            lambda v: jnp.take(v, idx, axis=0), cache)
                    self._m_batches.inc()
                    # Account BEFORE the yield: once the consumer holds the
                    # epoch's last batch, a state_dict() taken there must
                    # read as an epoch boundary (the generator stays
                    # suspended at the yield until the next pull).
                    if j + 1 == len(starts):
                        self._steps_into_epoch = 0
                        self._epochs_done += 1
                    else:
                        self._steps_into_epoch = j + 1
                    yield batch
                skip = 0
        return gen()

    def _epoch_orders(self, n):
        """Per-epoch index order stream shared by the per-step iterator and
        ``scan_epochs`` — one place owns num_epochs/shuffle/seed semantics
        (an explicit seed reproduces, seed=None draws fresh entropy per
        loader, same as the host-RAM sibling).  Starts at
        ``self._start_epoch``: an epoch-boundary resume burns the earlier
        permutations so the continuation is exactly the uninterrupted
        stream's tail.  The baseline is static, so re-iterating the
        loader replays the same pass (fresh-entropy seeds replay THEIR
        pass; an explicit seed reproduces across processes)."""
        import jax.numpy as jnp

        seed = self._seed if self._seed is not None \
            else int(np.random.default_rng().integers(2 ** 31))
        key = jax.random.PRNGKey(seed)
        identity = None  # shuffle=False: one device array, not one per epoch
        epoch = 0
        while self._num_epochs is None or epoch < self._num_epochs:
            if self._shuffle:
                key, sub = jax.random.split(key)
                order = jax.random.permutation(sub, n)
            else:
                if identity is None:
                    identity = jnp.arange(n)
                order = identity
            if epoch >= self._start_epoch:
                yield order
            epoch += 1

    def scan_epochs(self, step_fn, carry, donate_carry=True,
                    epochs_per_call=1):
        """Consume the epochs as ONE ``lax.scan`` dispatch per
        ``epochs_per_call`` epochs.

        The per-step iterator (``__iter__``) costs two host dispatches per
        step (gather + user step); with very fast steps that dispatch
        overhead IS the data stall.  This folds whole epochs — on-device batch gather and the
        training step — into a single jitted (nested) ``lax.scan``: zero
        host work and zero dispatch latency between steps, the idiomatic
        XLA consumption pattern for an HBM-resident epoch.  Raising
        ``epochs_per_call`` amortizes even the per-epoch dispatch.

        Args:
            step_fn: ``step_fn(carry, batch) -> (carry, out)``; ``batch``
                is the same dict pytree a per-step iteration would yield
                (leading dim ``batch_size``).  Traced once, so it must be
                jittable.
            carry: initial carry pytree (params/optimizer state/...).
            donate_carry: donate the carry buffers to each call (halves
                peak param memory; the yielded carry replaces it).
            epochs_per_call: epochs folded into each dispatch.

        Yields ``(carry, outs)`` per call: ``outs`` stacks the per-step
        ``out`` along a leading ``steps_per_epoch`` axis, with an extra
        leading epochs axis when ``epochs_per_call > 1`` (a trailing
        partial group yields with its smaller epoch count — one extra
        compile).  Epoch count and shuffling follow the loader's
        ``num_epochs`` / ``shuffle`` / ``seed`` exactly like the per-step
        iterator; partial trailing batches are always dropped
        (``lax.scan`` needs static shapes).

        **Mid-epoch resume**: a loader restored from a mid-epoch token
        (taken by the per-step iterator; needs
        ``deterministic_cache_order=True`` + the same explicit ``seed``)
        finishes the partial epoch as its own first dispatch — ``outs``
        carries the remaining ``steps - start_step`` steps (one extra
        compile) — then continues in full ``epochs_per_call`` groups.  A
        token taken inside an epoch's ragged tail (every full batch
        consumed; only a ``drop_last=False`` pass parks the cursor there)
        resumes at the next epoch: scan always drops partial trailing
        batches.  Checkpoints taken *between scan yields* are epoch-group
        boundaries — ``scan_epochs`` never exposes an intra-dispatch
        cursor (the whole group is one XLA execution).

        **Shapes under** ``epochs_per_call > 1`` are uniform: EVERY yield
        carries the leading epochs axis.  Full groups are
        ``(E, steps, ...)``, a trailing partial group is the same shape
        with a smaller ``E``, and the resume-tail yield (one partial
        epoch) is ``(1, steps - start_step, ...)`` — consumers indexing
        ``outs`` by epoch need no special case.  (Earlier versions
        yielded the resume tail WITHOUT the epochs axis — ADVICE r05 #2's
        shape foot-gun.)  With ``epochs_per_call == 1`` no yield has an
        epochs axis: full epochs are ``(steps, ...)`` and the resume tail
        ``(steps - start_step, ...)``.
        """
        import itertools

        import jax.numpy as jnp
        from jax import lax

        if epochs_per_call < 1:
            raise ValueError('epochs_per_call must be >= 1')
        cache = self._materialize()
        if cache is None:
            return
        n = len(next(iter(jax.tree_util.tree_leaves(cache))))
        steps = n // self.batch_size
        if steps == 0:
            logger.warning('epoch cache holds %d rows < batch_size=%d: no '
                           'batches to scan', n, self.batch_size)
            return
        batch_size = self.batch_size

        def body_for(cache, order):
            def body(c, i):
                idx = lax.dynamic_slice_in_dim(order, i * batch_size,
                                               batch_size)
                batch = jax.tree_util.tree_map(
                    lambda v: jnp.take(v, idx, axis=0), cache)
                return step_fn(c, batch)
            return body

        def run_epoch(carry, cache, order):
            return lax.scan(body_for(cache, order), carry, jnp.arange(steps))

        def run_epochs(carry, cache, orders):  # orders: (E, n)
            return lax.scan(lambda c, order: run_epoch(c, cache, order),
                            carry, orders)

        donate = (0,) if donate_carry else ()
        fn_one = jax.jit(run_epoch, donate_argnums=donate)
        fn_many = jax.jit(run_epochs, donate_argnums=donate)

        self._epochs_done = self._start_epoch  # fresh pass
        self._steps_into_epoch = 0
        orders = self._epoch_orders(n)
        start = self._start_step
        if start:
            # Finish the token's partial epoch as its own dispatch: the
            # remaining steps of epoch 0 scan from the step cursor.  The
            # cursor counts per-step-iterator batches, which (only under
            # drop_last=False, only when a ragged tail exists) include one
            # tail batch scan would drop — a cursor AT the full-batch count
            # then means every scannable step is done and the epoch
            # completes with no dispatch.  Only a drop_last=False pass can
            # legitimately produce that cursor, so the token must RECORD
            # drop_last=False to accept it (ADVICE r05 #1): a stale/forged
            # token from a drop_last=True run — or one predating the
            # recorded flag, whose provenance cannot be verified — would
            # otherwise silently complete the epoch with zero dispatched
            # steps.  Any cursor past the geometry's legitimate maximum is
            # a changed dataset/batch shape, the same error the per-step
            # iterator raises for it.
            ragged_tail = (bool(n % self.batch_size)
                           and self._token_drop_last is False)
            max_cursor = steps if ragged_tail else steps - 1
            if start > max_cursor:
                raise ValueError(
                    'device_inmem resume token is %d steps into an epoch '
                    'of %d full batches (max legitimate cursor %d for a '
                    'token taken with drop_last=%r) — the dataset or batch '
                    'geometry changed since the checkpoint'
                    % (start, steps, max_cursor, self._token_drop_last))
            first = list(itertools.islice(orders, 1))
            if not first:
                return
            if start < steps:
                def run_epoch_tail(carry, cache, order):
                    return lax.scan(body_for(cache, order), carry,
                                    jnp.arange(start, steps))
                fn_tail = jax.jit(run_epoch_tail, donate_argnums=donate)
                carry, outs = fn_tail(carry, cache, first[0])
                if epochs_per_call > 1:
                    # Grouped consumption: EVERY yield carries the leading
                    # epochs axis, the resume tail included — it is one
                    # (partial) epoch, so shape (1, steps - start, ...).
                    # (ADVICE r05 #2: the bare tail shape was a foot-gun
                    # for consumers indexing outs by epoch.)
                    outs = jax.tree_util.tree_map(lambda x: x[None], outs)
                self._m_batches.inc(steps - start)
                self._epochs_done += 1
                yield carry, outs
            else:
                self._epochs_done += 1
        while True:
            group = list(itertools.islice(orders, epochs_per_call))
            if not group:
                return
            if epochs_per_call == 1:
                carry, outs = fn_one(carry, cache, group[0])
            else:
                # Always the (E, steps, ...) shape when grouping was
                # requested — a trailing 1-epoch group must not silently
                # drop the epochs axis consumers index by.
                carry, outs = fn_many(carry, cache, jnp.stack(group))
            self._m_batches.inc(steps * len(group))
            self._epochs_done += len(group)  # group yields ARE boundaries
            yield carry, outs

    def state_dict(self):
        """Resume token.  The permutation stream is a pure function of the
        explicit ``seed``, so ``(epochs_done, steps_into_epoch)`` fully
        determines the continuation: resume with
        ``DeviceInMemDataLoader(reader', ..., seed=same_seed,
        num_epochs=same_total, resume_state=token)`` and the remaining
        stream replays exactly.

        Exactness across a process restart also needs the rebuilt cache to
        hold the rows in the checkpointed order (the permutation indexes
        into it): at an **epoch boundary** any complete cache works (the
        continuation is a seed-exact permutation over the same row set);
        **mid-epoch** the row order itself must reproduce, so a mid-epoch
        token requires ``deterministic_cache_order=True`` — without it,
        checkpoint at a boundary or use :class:`DiskCachedDataLoader`."""
        if self._seed is None:
            raise ValueError('resume needs an explicit seed= (the device '
                             'permutation stream must be re-derivable '
                             'after restart)')
        if self._steps_into_epoch and not self._deterministic:
            raise ValueError(
                'mid-epoch checkpoint (%d steps into the current epoch) '
                'needs deterministic_cache_order=True — the step cursor '
                'indexes into the cached row order, which a pool-ordered '
                'rebuild does not reproduce; consume the epoch, rebuild '
                'with deterministic_cache_order=True, or use '
                'DiskCachedDataLoader' % self._steps_into_epoch)
        return {'version': 1,
                'device_inmem': {'epochs_done': int(self._epochs_done),
                                 'steps_into_epoch':
                                     int(self._steps_into_epoch),
                                 'batch_size': int(self.batch_size),
                                 'drop_last': bool(self._drop_last),
                                 'seed': int(self._seed)}}


class ResidentDataLoader(InMemDataLoader):
    """Device-resident data plane: a compressed-in-HBM tier with an
    epoch-keyed on-device shuffle and a multi-epoch residency LRU
    (``petastorm_tpu.jax.residency``).

    Sits beyond :class:`DeviceInMemDataLoader` on the tier ladder: batches
    live on device in the transfer plane's narrowed **wire** dtypes (uint8
    stays uint8, float32 rides as bfloat16 under ``wire_dtypes='auto'``)
    and are widened inside the jitted gather, so HBM holds roughly 2-4x
    more samples than the full-width device cache.  Epoch 0 streams
    through a :class:`~petastorm_tpu.jax.transfer.DispatchPump` and admits
    each delivered batch into the :class:`~petastorm_tpu.jax.residency.
    ResidencyTier`; once every row is resident, warm epochs are served by
    a single jitted gather+widen per step and fetch **zero** host batches.

    Determinism contract: every epoch's order is
    ``epoch_permutation(seed, epoch, n)`` — a pure function of the pair,
    not of traversal history — so a resident epoch is bit-identical to
    the equivalent streamed epoch (both deliver ``widen(narrow(rows))``),
    and dropping the tier mid-epoch (:meth:`drop_resident_tier`) falls
    back to streaming with an unchanged delivery digest.

    Degrades to full-width streaming (no narrowing, no residency) under
    ``PETASTORM_TPU_NO_RESIDENCY=1`` or when any field's dtype is outside
    the wire support matrix; a ``hbm_budget_bytes`` too small for the
    dataset keeps streaming every epoch (the LRU churns, visible as
    ``residency_thrash``) rather than failing.  Unlike
    :class:`DeviceInMemDataLoader` the host cache is **retained**, so the
    fallbacks always have rows to stream from.
    """

    def __init__(self, reader, batch_size, num_epochs=1, shuffle=True,
                 seed=None, device=None, wire_dtypes='auto',
                 hbm_budget_bytes=None, **kwargs):
        from petastorm_tpu.jax import residency

        for unsupported in ('transform_fn', 'shuffling_queue_capacity'):
            if kwargs.get(unsupported):
                # Same contract as DeviceInMemDataLoader: warm batches
                # never exist on the host, so host-side hooks cannot run.
                raise ValueError('ResidentDataLoader does not support %s'
                                 % unsupported)
        super(ResidentDataLoader, self).__init__(
            reader, batch_size, num_epochs=num_epochs, shuffle=shuffle,
            seed=seed, device=device, wire_dtypes=wire_dtypes, **kwargs)
        if self._sharding is not None:
            raise ValueError('ResidentDataLoader caches on one device; use '
                             'InMemDataLoader with sharding= for global '
                             'batch assembly')
        self._budget = hbm_budget_bytes
        self._tier = None
        self._plan = None
        self._identity_order = None
        #: Full counter shape exists from construction — stats rollups see
        #: every residency_* counter at 0 even when the plane is off.
        self._res_counters = residency.ensure_counters(self.metrics)
        #: Resolved at first iteration; fixed per loader so re-iterating
        #: replays the same epoch-order stream.
        self._res_seed = None
        self._steps_into_epoch = 0
        self._start_epoch = 0
        self._start_step = 0
        self._epochs_done = 0
        resumed = (self._resume_state or {}).get('resident')
        if resumed:
            if seed is None or int(resumed['seed']) != int(seed):
                raise ValueError(
                    'resident resume token was taken with seed=%r; rebuild '
                    'the loader with that explicit seed (every epoch order '
                    'is derived from (seed, epoch))' % (resumed['seed'],))
            self._start_epoch = int(resumed['epochs_done'])
            self._start_step = int(resumed.get('steps_into_epoch', 0))
            token_bs = resumed.get('batch_size')
            if self._start_step and token_bs is not None \
                    and int(token_bs) != int(batch_size):
                raise ValueError(
                    'resident resume token was taken %d steps into an epoch '
                    'of batch_size=%d batches; resume with that batch_size '
                    '(got %d), or checkpoint at an epoch boundary to change '
                    'it' % (self._start_step, int(token_bs), int(batch_size)))
            if self._start_step and not self._deterministic:
                raise ValueError(
                    'mid-epoch resident resume requires '
                    'deterministic_cache_order=True: the step cursor indexes '
                    'into the cached row order, which only the canonical '
                    'content-sorted cache reproduces across restarts')
            self._epochs_done = self._start_epoch
            self._steps_into_epoch = self._start_step

    @property
    def residency_stats(self):
        """Counter snapshot — full shape regardless of plane state."""
        c = self._res_counters
        return {'admitted': int(c.admitted.value),
                'evictions': int(c.evictions.value),
                'hits': int(c.hits.value),
                'bypass': int(c.bypass.value),
                'thrash': int(c.thrash.value),
                'host_batches': int(c.host_batches.value),
                'rowcopy_fields': int(c.rowcopy_fields.value)}

    def drop_resident_tier(self):
        """Release the resident tier now (e.g. to reclaim HBM for a model
        that grew).  Safe mid-epoch: the remaining batches of the pass
        stream from the retained host cache with identical delivered
        values, so the delivery digest is unchanged."""
        if self._tier is not None:
            self._tier.drop()

    def _epoch_order(self, epoch, n):
        from petastorm_tpu.jax import residency
        import jax.numpy as jnp

        if not self._shuffle:
            if self._identity_order is None \
                    or len(self._identity_order) != n:
                self._identity_order = jnp.arange(n)
            return self._identity_order
        return residency.epoch_permutation(self._res_seed, epoch, n)

    def __iter__(self):
        from petastorm_tpu.jax import residency

        if self._build_cache() is None:
            return iter(())
        numeric = _filter_numeric(self._cache, self._warned_fields)
        leaves = jax.tree_util.tree_leaves(numeric)
        if not leaves:
            return iter(())
        n = len(leaves[0])
        if self._drop_last and n < self.batch_size:
            logger.warning('epoch cache holds %d rows < batch_size=%d with '
                           'drop_last: no batches to serve', n,
                           self.batch_size)
            return iter(())
        # Wire narrowing is TRANSFER-plane behavior (pre-residency
        # streaming already delivered widen(narrow(rows)) under 'auto'),
        # so the kill switch disables only the resident tier: a killed
        # loader must reproduce the pre-residency delivery exactly,
        # lossy wire dtypes included.
        plan = residency.wire_plan(numeric, self._wire_dtypes)
        tier = None
        if plan is not None and not residency.killed():
            if self._tier is None:
                self._tier = residency.ResidencyTier(
                    plan, n, self.batch_size, self._budget,
                    self._res_counters, device=self._device)
            tier = self._tier
        self._plan = plan
        if self._res_seed is None:
            self._res_seed = self._seed if self._seed is not None \
                else int(np.random.default_rng().integers(2 ** 31))
        return self._gen(numeric, n, plan, tier)

    def _gen(self, cache, n, plan, tier):
        self._epochs_done = self._start_epoch  # fresh pass
        self._steps_into_epoch = self._start_step
        skip = self._start_step  # mid-epoch baseline: first epoch only
        epoch = self._start_epoch
        while self._num_epochs is None or epoch < self._num_epochs:
            order_dev = self._epoch_order(epoch, n)
            stop = n - self.batch_size + 1 if self._drop_last else n
            starts = list(range(0, max(stop, 0), self.batch_size))
            if skip and skip >= len(starts):
                raise ValueError(
                    'resident resume token is %d steps into an epoch of %d '
                    'steps — the dataset or batch geometry changed since '
                    'the checkpoint' % (skip, len(starts)))
            if tier is not None and tier.serving_ok():
                batches = self._resident_epoch(cache, n, plan, tier,
                                               order_dev, starts, skip)
            else:
                batches = self._streamed_epoch(cache, n, plan, tier,
                                               order_dev, starts, skip)
            for j, batch in self._waited(lambda: next(batches, _DONE)):
                self._m_batches.inc()
                # Account BEFORE the yield (same contract as
                # DeviceInMemDataLoader): a state_dict() taken while the
                # consumer holds the epoch's last batch reads as an epoch
                # boundary.
                if j + 1 == len(starts):
                    self._steps_into_epoch = 0
                    self._epochs_done += 1
                else:
                    self._steps_into_epoch = j + 1
                yield batch
            if tier is not None and not tier.fully_resident:
                # drop_last never streams the ragged tail and a resume
                # never re-streams skipped batches; admit the leftovers
                # directly so the next epoch can serve warm.
                tier.backfill(cache, plan)
            skip = 0
            epoch += 1

    def _put_wire(self, wire):
        if self._device is not None:
            return {k: jax.device_put(v, self._device)
                    for k, v in wire.items()}
        return {k: jax.device_put(v) for k, v in wire.items()}

    def _stream_one(self, cache, n, plan, idx):
        """Slice, narrow, place, widen one batch — the streamed delivery.
        Identical values to a warm gather over the same rows: both
        deliver ``widen(narrow(rows))``."""
        with self._stage('host_batch', event='host_batch') as host:
            host_rows = {name: np.asarray(v)[idx]
                         for name, v in cache.items()}
            wire = plan.narrow(host_rows) if plan is not None else host_rows
        with self._stage('device_put', event='device_put') as put:
            wire_dev = self._put_wire(wire)
            batch = plan.widen(wire_dev) if plan is not None else wire_dev
        self._res_counters.host_batches.inc()
        return wire_dev, batch, host.window, put.window

    def _streamed_epoch(self, cache, n, plan, tier, order_dev, starts, skip):
        """One epoch through the dispatch ring: a DispatchPump background
        thread slices/narrows/places while the consumer steps, and each
        delivered batch is admitted into the tier."""
        order_np = np.asarray(order_dev)
        bs = self.batch_size

        def source():
            for j, start in enumerate(starts):
                if j < skip:
                    continue
                yield j, order_np[start:min(start + bs, n)]

        def ship(item):
            j, idx = item
            wire_dev, batch, w_host, w_put = self._stream_one(
                cache, n, plan, idx)
            outcome = tier.admit(idx, wire_dev) if tier is not None \
                else 'bypass'
            if self.provenance is not None:
                self._seal_provenance({'host_batch': w_host,
                                       'h2d_dispatch': w_put},
                                      residency=outcome)
            return j, batch

        pump = DispatchPump(source(), ship, self._prefetch)
        self._pump = pump
        pump.start()
        try:
            yield from iter(pump.get, _DONE)
        finally:
            pump.stop(join_timeout_s=0.2)

    def _resident_epoch(self, cache, n, plan, tier, order_dev, starts, skip):
        """One warm epoch: jitted gather+widen per step, zero host batches.
        If the tier is dropped mid-epoch, the remaining steps stream from
        the retained host cache — same values, digest intact."""
        order_np = None
        bs = self.batch_size
        for j, start in enumerate(starts):
            if j < skip:
                continue
            # One warm batch on the consuming thread: the tier's checks,
            # the gather's dispatch (``resident_gather``), the provenance
            # seal.  What is not the gather is bookkeeping.
            with self._stage('resident_serve',
                             event='resident_serve') as serve:
                if tier.serving_ok():
                    with self._stage('resident_gather',
                                     span='ptp/resident_gather'):
                        if start + bs <= n:
                            batch = tier.gather(order_dev, start)
                        else:  # ragged tail (drop_last=False)
                            batch = tier.gather_tail(order_dev, start)
                    outcome = 'hit'
                else:
                    serve.keep = False   # streamed: host_batch + device_put
                    if order_np is None:
                        order_np = np.asarray(order_dev)
                    idx = order_np[start:min(start + bs, n)]
                    _, batch, _, _ = self._stream_one(cache, n, plan, idx)
                    outcome = 'bypass'
                    self._res_counters.bypass.inc()
                if self.provenance is not None:
                    self._seal_provenance({}, residency=outcome)
            yield j, batch

    def state_dict(self):
        """Resume token.  Epoch orders are ``epoch_permutation(seed,
        epoch, n)`` — pure functions of the pair — so ``(epochs_done,
        steps_into_epoch)`` fully determines the continuation; resume
        with the same explicit ``seed`` and the remaining stream replays
        exactly (the tier rebuilds by streaming, values unchanged).
        Mid-epoch exactness across restarts additionally needs
        ``deterministic_cache_order=True``, same as the device-cache
        sibling."""
        if self._seed is None:
            raise ValueError('resume needs an explicit seed= (epoch orders '
                             'must be re-derivable after restart)')
        if self._steps_into_epoch and not self._deterministic:
            raise ValueError(
                'mid-epoch checkpoint (%d steps into the current epoch) '
                'needs deterministic_cache_order=True — the step cursor '
                'indexes into the cached row order, which a pool-ordered '
                'rebuild does not reproduce' % self._steps_into_epoch)
        return {'version': 1,
                'resident': {'epochs_done': int(self._epochs_done),
                             'steps_into_epoch': int(self._steps_into_epoch),
                             'batch_size': int(self.batch_size),
                             'drop_last': bool(self._drop_last),
                             'seed': int(self._seed)}}


class DiskCachedDataLoader(DataLoader):
    """Decoded-tensor disk cache tier: decode once, stream every later
    epoch from local disk at memory bandwidth.

    Fills the gap between :class:`DataLoader` (re-decode every epoch) and
    :class:`DeviceInMemDataLoader` (whole decoded epoch in HBM): epoch 0
    runs the normal decode path, serves its batches, AND appends every row
    to per-field row-major binary files under ``decoded_cache_dir``; every
    subsequent epoch memory-maps those files and serves (optionally
    reshuffled) batches with zero parquet/codec work — multi-epoch training
    over datasets far larger than HBM bypasses JPEG after the first pass.

    The reference's ``LocalDiskCache`` caches ENCODED row-group results
    (``petastorm/local_disk_arrow_table_cache.py``-style); a TPU-first
    pipeline caches POST-decode, because decode (not IO) is what a 1-core
    host cannot do at chip speed.  Layout matches the native decode plane's
    output: one contiguous ``[rows, *field_shape]`` buffer per field.

    Rules:

    * Construct the reader with ``num_epochs=1``; epoch repetition happens
      here (``num_epochs=None`` = forever).
    * Only fixed-shape numeric fields are cached (object/string leaves are
      dropped with the same warning as device transfer).
    * ``decoded_cache_dir`` identifies the DATASET (+ predicate/transform
      pipeline): point each distinct dataset/shard at its own directory.
      Multi-host: use per-host local paths — each host caches its shard.
    * A cache directory is reused only when its ``_COMPLETE`` marker
      exists; a partial build (crash mid-epoch-0) is re-built from scratch.
    * ``transform_fn`` still runs per served batch (cache holds
      pre-transform tensors, so random augmentation stays fresh per epoch).
    """

    _MANIFEST = 'manifest.json'
    _COMPLETE = '_COMPLETE'

    def __init__(self, reader, batch_size, decoded_cache_dir, num_epochs=1,
                 shuffle=True, seed=None, **kwargs):
        if kwargs.get('shuffling_queue_capacity'):
            raise ValueError('DiskCachedDataLoader shuffles via per-epoch '
                             'permutation; shuffling_queue_capacity is not '
                             'supported')
        if reader is not None:
            if getattr(reader, 'ngram', None) is not None:
                raise ValueError('DiskCachedDataLoader does not support '
                                 'NGram readers (windows are not '
                                 'fixed-shape rows)')
            reader_epochs = getattr(reader, 'num_epochs', 1)
            if reader_epochs != 1:
                raise ValueError(
                    'DiskCachedDataLoader requires a reader built with '
                    'num_epochs=1 (got num_epochs=%r); epoch repetition '
                    'happens in the loader' % (reader_epochs,))
        # ``reader=None`` serves a COMPLETE cache without touching parquet
        # at all (no worker pool decoding in the background — e.g. while a
        # training step loop is being timed).
        super(DiskCachedDataLoader, self).__init__(
            reader, batch_size, seed=seed, **kwargs)
        self._cache_dir = decoded_cache_dir
        self._num_epochs = num_epochs
        self._shuffle = shuffle

    # -- cache files ---------------------------------------------------------

    @classmethod
    def cache_complete(cls, decoded_cache_dir):
        """True when ``decoded_cache_dir`` holds a finished cache — i.e.
        a loader over it may be built with ``reader=None`` (no parquet or
        decode work at all).  Public so callers share the loader's own
        completeness rule instead of hardcoding marker names."""
        import os
        return os.path.exists(os.path.join(decoded_cache_dir, cls._COMPLETE))

    def _cache_complete(self):
        return self.cache_complete(self._cache_dir)

    def _manifest(self):
        import json
        import os
        with open(os.path.join(self._cache_dir, self._MANIFEST)) as f:
            return json.load(f)

    def _open_cache(self):
        """mmap every field buffer; returns ``(fields_dict, n_rows)``."""
        import os
        man = self._manifest()
        fields = {
            name: np.memmap(os.path.join(self._cache_dir, spec['file']),
                            dtype=np.dtype(spec['dtype']), mode='r',
                            shape=tuple([man['rows']] + spec['shape']))
            for name, spec in man['fields'].items()}
        return fields, man['rows']

    def _build_and_serve_epoch0(self):
        """Epoch 0: serve decoded batches while spilling rows to disk."""
        import json
        import os
        import shutil

        if os.path.isdir(self._cache_dir):
            # stale partial build (no _COMPLETE marker): start clean
            shutil.rmtree(self._cache_dir)
        os.makedirs(self._cache_dir)
        sinks = {}
        specs = {}
        rows = 0
        drop_last = self._drop_last
        self._drop_last = False     # the cache must hold EVERY row
        try:
            for batch in super(DiskCachedDataLoader, self)._host_batches():
                batch = _filter_numeric(batch, self._warned_fields)
                for name, value in batch.items():
                    value = np.ascontiguousarray(value)
                    if name not in sinks:
                        specs[name] = {'file': '%s.bin' % name,
                                       'dtype': value.dtype.str,
                                       'shape': list(value.shape[1:])}
                        sinks[name] = open(
                            os.path.join(self._cache_dir, specs[name]['file']),
                            'wb')
                    elif list(value.shape[1:]) != specs[name]['shape']:
                        raise ValueError(
                            'field %r changed shape %r -> %r; the decoded '
                            'cache requires fixed-shape fields'
                            % (name, specs[name]['shape'],
                               list(value.shape[1:])))
                    sinks[name].write(memoryview(value))
                n = len(next(iter(batch.values())))
                rows += n
                if n == self.batch_size or not drop_last:
                    yield batch
        finally:
            self._drop_last = drop_last
            for sink in sinks.values():
                sink.close()
        with open(os.path.join(self._cache_dir, self._MANIFEST), 'w') as f:
            json.dump({'version': 1, 'rows': rows, 'fields': specs}, f)
        # the marker is the atomicity boundary: no marker -> rebuild
        tmp = os.path.join(self._cache_dir, self._COMPLETE + '.tmp')
        with open(tmp, 'w') as f:
            f.write('%d rows\n' % rows)
        os.replace(tmp, os.path.join(self._cache_dir, self._COMPLETE))

    # -- epochs --------------------------------------------------------------

    def _host_batches(self):
        epochs_served = 0
        resumed = (self._resume_state or {}).get('disk_cache')
        if not self._cache_complete():
            if resumed:
                raise ValueError('resume_state requires the decoded cache '
                                 'to be complete; the epoch-0 build was '
                                 'interrupted — rebuild from scratch')
            if self.reader is None:
                raise ValueError('reader=None serves a COMPLETE cache only; '
                                 '%r has no _COMPLETE marker'
                                 % (self._cache_dir,))
            yield from self._build_and_serve_epoch0()
            epochs_served = 1
            if self._num_epochs is not None \
                    and epochs_served >= self._num_epochs:
                return
        fields, n = self._open_cache()
        if n == 0:
            return
        if self._drop_last and n < self.batch_size:
            # num_epochs=None would otherwise spin forever yielding nothing
            logger.warning('decoded cache holds %d rows < batch_size=%d with '
                           'drop_last: no batches to serve', n, self.batch_size)
            return
        rng = np.random.default_rng(self._seed)
        epoch = epochs_served
        order = None
        offset = 0
        if resumed:
            rng.bit_generator.state = resumed['rng_state']
            epoch = int(resumed['epoch'])
            offset = int(resumed['offset'])
            order = (None if resumed['order'] is None
                     else np.asarray(resumed['order']))
        self._dc = {'rng': rng, 'epoch': epoch, 'order': order,
                    'offset': offset}
        while self._num_epochs is None or epoch < self._num_epochs:
            if order is None:
                order = rng.permutation(n) if self._shuffle else np.arange(n)
            stop = n - self.batch_size + 1 if self._drop_last else n
            for start in range(offset, max(stop, 0), self.batch_size):
                self._dc.update(epoch=epoch, order=order,
                                offset=start + self.batch_size)
                idx = order[start:start + self.batch_size]
                # fancy-indexing a memmap materializes just this batch —
                # the per-step host cost is one batch-sized memcpy
                yield {name: np.asarray(buf[idx])
                       for name, buf in fields.items()}
            epoch += 1
            order = None
            offset = 0
            self._dc.update(epoch=epoch, order=None, offset=0)

    def state_dict(self):
        """Exact resume token over the complete cache: (epoch, offset,
        epoch order, rng state) + prefetched batches.  The on-disk cache IS
        the persisted row order, so restoration is exact regardless of the
        original reader's pool type."""
        if getattr(self, '_dc', None) is None:
            raise ValueError(
                'state_dict() is supported once the decoded cache is '
                'complete (from epoch 1 on); during the epoch-0 build, '
                'checkpoint at the epoch boundary instead')
        with self._pump_paused():
            return self._disk_cache_state()

    def _disk_cache_state(self):
        dc = self._dc
        return {
            'version': 1,
            'pending': [jax.device_get(b) for b in self._pending],
            'disk_cache': {
                'rng_state': dc['rng'].bit_generator.state,
                'epoch': int(dc['epoch']),
                'offset': int(dc['offset']),
                'order': (None if dc['order'] is None
                          else np.asarray(dc['order'])),
            },
        }


class PackedDataLoader(DataLoader):
    """Pack a variable-length sequence column into fixed-shape LM batches
    with the DataLoader's prefetch/device delivery.

    The loader-layer home of ``petastorm_tpu.jax.packing.pack_stream``:
    rows stream out of the reader, their ``tokens_field`` column is packed
    into ``(batch_size, max_len)`` batches with ``segment_ids`` /
    ``positions``, and batches ride the same double-buffered
    ``device_put`` path as :class:`DataLoader` (``prefetch`` /
    ``device`` / ``sharding`` / ``transform_fn`` all apply)::

        with make_reader(url, schema_fields=['doc_id', 'tokens']) as reader:
            loader = PackedDataLoader(reader, 'tokens', max_len=4096,
                                      batch_size=8, id_field='doc_id',
                                      sharding=sharding)
            for batch in loader:
                step(batch['tokens'], batch['segment_ids'],
                     batch['positions'])

    ``batch_size`` is the number of packed rows a batch, as every other
    loader spells it; ``rows_per_batch`` is its older synonym (give one, or
    both alike).  ``id_field`` names a scalar integer column that is carried
    through the packer: the batch gains ``doc_ids`` ``[batch_size, max_len]``,
    the id of the document each token belongs to
    (``packing.NO_DOCUMENT`` on padding; ``packing.document_ids(batch)``
    lists them on the host), and ``state_dict`` keeps the ids of the
    documents it holds back.

    Ordering comes from the reader (shuffle row groups there);
    ``shuffling_queue_capacity`` is rejected — reordering between packing
    and delivery would break nothing but adds no mixing the reader can't
    already provide.  With ``drop_last=False`` the final short batch is
    padded with all-padding rows (static shapes), not ragged.

    **At an epoch's end** the open rows are closed as they are, so no
    document of one epoch waits in an open row while the next epoch's are
    delivered: after any number of batches every document has been
    delivered ``n`` or ``n + 1`` times.  The loader counts the reader's rows
    against ``reader.num_local_rows()``; under a predicate, an NGram or a
    row-dropping transform that count is only an upper bound, and open rows
    are then closed at the end of the stream alone.  The rows closed early
    cost padding once an epoch.

    Packing is the stage ``pack`` (``pack_s``, histogram ``pack``, profiler
    span ``ptp/pack`` inside ``pt/host_batch``): one sample each time the
    packer emits (one batch in the steady state; the rows closed at an epoch's
    end come out as several), the packer's time over the documents since the
    emission before.
    Counters ``packed_rows``, ``packed_documents``, ``packed_tokens``,
    ``packed_pad_tokens`` and the gauge ``pack_open_rows`` say what was
    packed.
    """

    def __init__(self, reader, tokens_field, max_len, rows_per_batch=None,
                 pad_id=0, open_rows=32, id_field=None, batch_size=None,
                 **loader_kwargs):
        if loader_kwargs.get('shuffling_queue_capacity'):
            raise ValueError('PackedDataLoader does not support '
                             'shuffling_queue_capacity; shuffle in the '
                             'reader (shuffle_row_groups)')
        if getattr(reader, 'batched_output', False):
            raise ValueError('PackedDataLoader needs a ROW reader '
                             '(make_reader): batch readers yield columnar '
                             'chunks, not per-document sequences')
        if batch_size is None:
            batch_size = rows_per_batch
        if batch_size is None:
            raise TypeError('PackedDataLoader needs batch_size (packed rows '
                            'a batch)')
        if rows_per_batch is not None and rows_per_batch != batch_size:
            raise ValueError('batch_size=%r and its synonym rows_per_batch=%r '
                             'differ' % (batch_size, rows_per_batch))
        super().__init__(reader, batch_size=batch_size, **loader_kwargs)
        self._tokens_field = tokens_field
        self._id_field = id_field
        self._max_len = int(max_len)
        self._pad_id = pad_id
        self._open_rows = int(open_rows)
        self._packer = None
        self._epoch_row = 0
        self._stage.instruments('pack')
        self._m_packed = {name: self.metrics.counter('packed_' + name)
                          for name in ('rows', 'documents', 'tokens',
                                       'pad_tokens')}
        self._g_open_rows = self.metrics.gauge('pack_open_rows')

    def _rows_an_epoch(self):
        """Rows the reader delivers an epoch, where that is exact and the
        reader goes on to another epoch; else ``None``."""
        reader = self.reader
        if getattr(reader, 'num_epochs', 1) == 1 \
                or not hasattr(reader, 'num_local_rows') \
                or getattr(reader, 'predicate', None) is not None \
                or getattr(reader, 'ngram', None) is not None \
                or getattr(reader, 'transform_may_change_row_count', False):
            return None
        return reader.num_local_rows() or None

    def _ready_counted(self):
        """Hands out the staged batches, counting what each holds."""
        from petastorm_tpu.jax.packing import document_starts
        while self._packed_ready:
            batch = self._packed_ready.pop(0)
            segment_ids = batch['segment_ids']
            tokens = int(np.count_nonzero(segment_ids))
            self._m_packed['rows'].inc(len(segment_ids))
            self._m_packed['documents'].inc(
                int(np.count_nonzero(document_starts(segment_ids))))
            self._m_packed['tokens'].inc(tokens)
            self._m_packed['pad_tokens'].inc(segment_ids.size - tokens)
            yield batch

    def _host_batches(self):
        from petastorm_tpu.jax.packing import StreamPacker

        packer = StreamPacker(self._max_len, self.batch_size,
                              pad_id=self._pad_id, open_rows=self._open_rows,
                              drop_last=self._drop_last)
        resume = self._resume_state or {}
        if resume.get('packer'):
            packer.load_state_dict(resume['packer'])
        self._packer = packer
        self._epoch_row = int(resume.get('packed_epoch_row', 0))
        rows_an_epoch = self._rows_an_epoch()
        # Ready-but-unyielded batches stage here so a state_dict() taken
        # between two yields of the same add() loses nothing.
        self._packed_ready = list(resume.get('packed_ready', []))
        carried = 0.0     # packer seconds of the documents not yet emitted
        for row in self._row_source():
            with self._stage('pack', span='ptp/pack') as pack:
                pack.carried = carried
                ready = packer.add(row[self._tokens_field],
                                   None if self._id_field is None
                                   else row[self._id_field])
                self._epoch_row += 1
                if self._epoch_row == rows_an_epoch:
                    ready += packer.close_open()
                    self._epoch_row = 0
                pack.keep = bool(ready)
            carried = 0.0 if ready else carried + pack.seconds
            self._g_open_rows.set(packer.open_rows)
            self._packed_ready.extend(ready)
            yield from self._ready_counted()
        with self._stage('pack', span='ptp/pack') as pack:
            pack.carried = carried
            self._packed_ready.extend(packer.flush())
            pack.keep = bool(self._packed_ready)
        self._g_open_rows.set(0)
        yield from self._ready_counted()

    def state_dict(self):
        """Exact packed snapshot: DataLoader state + the packer residue
        (open rows, closed rows, their documents' ids, sticky dtype) +
        ready-but-unyielded batches + how far into its epoch the reader
        was.

        The pump stays paused across BOTH reads (the base snapshot and
        the packer residue): ``_pump_paused`` counts, so the nested
        pause inside ``super().state_dict()`` composes — resuming
        between the two would let the dispatch thread pack
        just-snapshotted pushback rows into the packer and duplicate
        them in the token."""
        with self._pump_paused():
            state = super().state_dict()
            rs = self._resume_state or {}
            if self._packer is not None:   # iteration started
                state['packer'] = self._packer.state_dict()
                state['packed_ready'] = list(self._packed_ready)
                state['packed_epoch_row'] = self._epoch_row
            else:                          # restored but not yet iterated
                state['packer'] = rs.get('packer')
                state['packed_ready'] = list(rs.get('packed_ready', []))
                state['packed_epoch_row'] = rs.get('packed_epoch_row', 0)
            return state


def make_jax_loader(dataset_url, batch_size, batched=True, loader_kwargs=None, **reader_kwargs):
    """Convenience: reader + DataLoader in one call.

    ``batched=True`` uses the columnar ``make_batch_reader`` path (fastest);
    ``False`` uses ``make_reader`` with codec decoding.
    """
    from petastorm_tpu.reader import make_batch_reader, make_reader
    factory = make_batch_reader if batched else make_reader
    reader = factory(dataset_url, **reader_kwargs)
    return DataLoader(reader, batch_size, **(loader_kwargs or {}))
