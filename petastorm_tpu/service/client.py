"""JAX-side client of the data service: ``ServiceDataLoader``.

A drop-in peer of ``petastorm_tpu.jax.DataLoader`` whose "reader" is the
service instead of a local decode pool: the connection subscribes to
every registered decode worker (rotated by consumer index so hosts
spread their first pulls — the ``jax.process_index()``-keyed round-robin
of the sharding contract), pulls serialized chunks under credit-based
backpressure, and commits *whole splits*:

* chunks of a split buffer until the worker's ``end`` marker arrives —
  a worker death mid-split leaves only a discarded partial buffer, never
  half-delivered rows;
* a completed split is ACKed to the worker (which only then reports
  ``complete`` to the dispatcher) and deduped by split id, so a split
  re-streamed after lease reassignment is delivered exactly once;
* ``ordered=True`` releases splits in ascending split-id order; the
  default releases them as workers finish (lowest latency).  Row order
  WITHIN a split follows the worker's per-split reader, so full
  determinism additionally needs a deterministic split reader
  (``reader_kwargs={'workers_count': 1}`` in the job config).

Resume follows the existing loader contract: ``state_dict()`` →
``resume_state=``.  The service part of the token is the set of split
ids this consumer has committed plus the partition-geometry fingerprint;
restoring against a fresh service run retires those splits at the
dispatcher (no re-decode) and the DataLoader machinery restores the
sub-split residue (partial batches, buffered chunks) exactly as the
local loaders do.
"""

import logging
import pickle
import queue
import threading
import time

from petastorm_tpu.errors import ServiceError
from petastorm_tpu.jax.loader import DataLoader
from petastorm_tpu.service import tenancy
from petastorm_tpu.service.worker import _Rpc, deserialize_chunk
from petastorm_tpu.telemetry import merge_into_recorder, provenance
from petastorm_tpu.utils import backoff

logger = logging.getLogger(__name__)


class _ServiceConnection(object):  # ptlint: disable=pickle-unsafe-attrs — one per consumer process; the resume token (state_dict) is the only thing that crosses processes
    """One consumer's connection: dispatcher RPCs + a DEALER per worker."""

    def __init__(self, dispatcher_addr, consumer=None, resume=None,
                 ordered=False, queue_splits=4, credits=None,
                 rpc_timeout_s=20.0, trace_recorder=None, tenant=None):
        import zmq

        self._zmq = zmq
        self._dispatcher_addr = dispatcher_addr
        #: Which tenant's job this connection consumes (ISSUE 16).  None
        #: asks for the dispatcher's own (default) job — the tenant-less
        #: wire shape every pre-tenancy client sends.
        self.tenant = None if tenant is None else str(tenant)
        self._context = zmq.Context()
        self._rpc_timeout_s = rpc_timeout_s
        #: optional ``benchmark.TraceRecorder``: worker spans riding the
        #: ``end`` headers merge into it after clock-offset alignment —
        #: one Perfetto timeline across client + every decode worker.
        self._trace = trace_recorder
        #: (client_clock - dispatcher_clock), refreshed from the 1 Hz
        #: ``workers`` discovery poll's send/recv midpoint.
        self._clock_offset = None
        self._worker_offsets = {}   # data addr -> (worker - dispatcher)
        self._labeled_pids = set()
        try:
            self._init(consumer, resume or {}, ordered, queue_splits,
                       credits)
        except Exception:
            from petastorm_tpu.workers_pool import shm_plane
            shm_plane.remove_probe(getattr(self, '_shm_probe', None))
            self._context.term()
            raise

    def _init(self, consumer, resume, ordered, queue_splits, credits):
        rpc = _Rpc(self._context, self._dispatcher_addr,
                   timeout_s=self._rpc_timeout_s)
        try:
            request = {'op': 'job'}
            if self.tenant is not None:
                request['tenant'] = self.tenant
            self.job = rpc.call(request)['job']
        finally:
            rpc.close()
        # The effective tenant (the job's own id) — subscribes and the
        # resume token carry THIS, so a tenant-less connection to the
        # default job round-trips as 'default' everywhere downstream.
        self.tenant = str(self.job.get('tenant') or tenancy.DEFAULT_TENANT)
        if consumer is None:
            consumer = _default_consumer(self.job['num_consumers'])
        if not 0 <= consumer < self.job['num_consumers']:
            raise ServiceError('consumer must be in [0, %d), got %r'
                               % (self.job['num_consumers'], consumer))
        self.consumer = int(consumer)
        # Geometry FIRST: a mismatched token's split ids index a different
        # partition, and the mark_consumed below would permanently retire
        # live splits of THIS job before the error could raise.
        _check_resume_geometry(resume, self)
        self._credits = int(credits if credits is not None
                            else self.job['credits'])
        self._ordered = bool(ordered)
        # Tenant jobs live in a GLOBAL split-id space starting at
        # split_base; the consumer-modulo shard is over the tenant-LOCAL
        # index so every tenant's consumers spread the same way the
        # single-tenant (base 0) job always did.
        base = int(self.job.get('split_base', 0))
        self._my_splits = [base + i for i in range(self.job['num_splits'])
                           if i % self.job['num_consumers'] == self.consumer]
        # Same-host shm delivery: create the /dev/shm probe whose
        # visibility proves to a worker that descriptors will map here.
        # Workers without sight of it (cross-host) keep the byte path.
        from petastorm_tpu.workers_pool import shm_plane
        self._shm_probe = None
        if self.job.get('shm', True) and shm_plane.available():
            try:
                self._shm_probe = shm_plane.make_probe()
            except OSError as e:
                # e.g. /dev/shm writable but full (ENOSPC): the fallback
                # matrix promises byte-path delivery, not a dead client.
                logger.warning('cannot create shm probe (%s); same-host '
                               'delivery will use the byte path', e)
        self.shm_chunks = 0
        #: Discovery-poll retries scheduled under the shared backoff
        #: policy (ISSUE 15) — nonzero means the dispatcher was
        #: unreachable at some point this connection rode through.
        self.retry_attempts = 0
        self.consumed = set(int(s) for s in resume.get('consumed') or ())
        unknown = self.consumed - set(self._my_splits)
        if unknown:
            raise ServiceError(
                'resume token holds split ids %s that do not belong to '
                'consumer %d of this job' % (sorted(unknown)[:5],
                                             self.consumer))
        if self.consumed:
            rpc = _Rpc(self._context, self._dispatcher_addr,
                       timeout_s=self._rpc_timeout_s)
            try:
                rpc.call({'op': 'mark_consumed',
                          'split_ids': sorted(self.consumed)})
            finally:
                rpc.close()
        #: complete splits ready for the reader: (split_id, [chunk dicts]);
        #: bounded — a full queue stops the receiver from reading sockets,
        #: which stops credit replenishment, which stalls the workers.
        self._ready = queue.Queue(maxsize=max(1, int(queue_splits)))
        self._error = None
        self._ended = threading.Event()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._recv_loop,
                                        name='service-client-recv',
                                        daemon=True)
        self._thread.start()

    # -- consumption (reader thread) -----------------------------------------

    def next_split(self):
        """Next complete, not-yet-delivered split: ``(split_id, chunks)``;
        None at end of stream.  A receive-loop failure raises here — a
        dead receiver must not masquerade as a clean (rows-missing) end
        of stream.  With a trace recorder attached the wait is recorded
        as a ``service/split_wait`` span — the 'no split was ready'
        component of a data stall (lease starvation, slow workers)."""
        t_wait = time.monotonic()
        item = self._next_split()
        if self._trace is not None:
            self._trace.event('service/split_wait', t_wait, time.monotonic())
        return item

    def _next_split(self):
        while True:
            if self._ended.is_set() and self._ready.empty():
                if self._error is not None:
                    raise ServiceError(
                        'service receive loop died: %s: %s'
                        % (type(self._error).__name__, self._error))
                return None
            try:
                return self._ready.get(timeout=0.1)
            except queue.Empty:
                if self._stop.is_set():
                    return None

    def drain_ready(self):
        """Pop every split currently buffered client-side (non-blocking) —
        the service leg of the loader's exact-checkpoint drain."""
        drained = []
        while True:
            try:
                drained.append(self._ready.get_nowait())
            except queue.Empty:
                return drained

    def commit(self, split_id):
        self.consumed.add(int(split_id))

    def stop(self):
        self._stop.set()

    def join(self):
        self._thread.join()
        self._context.term()

    # -- receive loop --------------------------------------------------------

    def _recv_loop(self):
        from petastorm_tpu.workers_pool import shm_plane

        zmq = self._zmq
        rpc = _Rpc(self._context, self._dispatcher_addr,
                   timeout_s=self._rpc_timeout_s)
        sockets = {}            # worker data addr -> DEALER
        poller = zmq.Poller()
        buffers = {}            # (split_id, attempt) -> {seq: (tag, payload)}
        received = set(self.consumed)
        remaining = set(self._my_splits) - received
        held = {}               # ordered mode: completed, awaiting turn
        order = [sid for sid in self._my_splits if sid not in received]
        next_refresh = 0.0
        #: Active backoff episode across consecutive discovery-poll
        #: failures (ISSUE 15): a healthy poll runs at a JITTERED ~1 Hz
        #: (a consumer fleet spreads over the second instead of
        #: arriving in phase), and a dead/restarting dispatcher sees
        #: exponentially-paced retries, not a synchronized 1 Hz hammer
        #: from every training host at once.
        discovery_retry = None
        addr_of = {}            # DEALER -> worker data addr (span origin)
        try:
            while remaining and not self._stop.is_set():
                now = time.monotonic()
                if now >= next_refresh:
                    try:
                        t_rpc0 = time.monotonic()
                        reply = rpc.call({'op': 'workers'})
                        t_rpc1 = time.monotonic()
                        workers = reply['workers']
                        if reply.get('t_mono') is not None:
                            # The discovery poll doubles as the clock
                            # handshake: (client - dispatcher) from the
                            # send/recv midpoint (ISSUE 5).  EWMA over
                            # the 1 Hz polls (ISSUE 7): one rtt-skewed
                            # poll must not yank the whole timeline, and
                            # a long run tracks genuine drift instead of
                            # freezing the first estimate.
                            estimate = ((t_rpc0 + t_rpc1) / 2.0
                                        - float(reply['t_mono']))
                            self._clock_offset = (
                                estimate if self._clock_offset is None
                                else 0.8 * self._clock_offset
                                + 0.2 * estimate)
                        for worker in workers:
                            if worker.get('clock_offset') is not None:
                                self._worker_offsets[worker['addr']] = \
                                    float(worker['clock_offset'])
                        discovery_retry = None
                        next_refresh = now + backoff.jittered(1.0, 0.2)
                    except ServiceError:
                        workers, reply = [], {}
                        discovery_retry = discovery_retry or \
                            backoff.DISCOVERY_POLICY.episode()
                        self.retry_attempts += 1
                        next_refresh = now + discovery_retry.next_delay()
                    failed = set(reply.get('failed_splits') or ()) & remaining
                    if failed:
                        # The dispatcher gave up on these (attempt ceiling):
                        # surface a terminal error instead of waiting on
                        # rows that will never stream.
                        raise ServiceError(
                            'split(s) %s of consumer %d failed every decode '
                            'attempt at the dispatcher'
                            % (sorted(failed)[:5], self.consumer))
                    stale = set(reply.get('retired_splits') or ()) \
                        & remaining
                    if stale:
                        # A ledger-restored dispatcher retired these in a
                        # PREVIOUS incarnation: they will never stream
                        # again, and this connection holds no token that
                        # accounts for them (a live client's remaining
                        # set already excludes everything it received) —
                        # raise instead of hanging forever.
                        raise ServiceError(
                            'split(s) %s of consumer %d were delivered '
                            'and retired before this dispatcher '
                            'restarted (restored ledger): resume with '
                            'the matching token, or point the '
                            'dispatcher at a fresh ledger_path for a '
                            'fresh epoch' % (sorted(stale)[:5],
                                             self.consumer))
                    # Rotate by consumer index: host c starts its pulls at
                    # worker c % W instead of every host hammering worker 0.
                    if workers:
                        c = self.consumer % len(workers)
                        workers = workers[c:] + workers[:c]
                    for worker in workers:
                        addr = worker['addr']
                        if addr in sockets:
                            continue
                        sock = self._context.socket(zmq.DEALER)
                        sock.setsockopt(zmq.LINGER, 0)
                        sock.set_hwm(0)
                        sock.connect(addr)
                        sock.send(pickle.dumps(
                            {'type': 'subscribe', 'consumer': self.consumer,
                             'tenant': self.tenant,
                             'credits': self._credits,
                             'shm_probe': self._shm_probe}, protocol=4))
                        sockets[addr] = sock
                        addr_of[sock] = addr
                        poller.register(sock, zmq.POLLIN)
                for sock in dict(poller.poll(100)):
                    while True:
                        try:
                            frames = sock.recv_multipart(zmq.NOBLOCK)
                        except zmq.Again:
                            break
                        header = pickle.loads(frames[0])
                        sid = int(header['split'])
                        attempt = int(header['attempt'])
                        if header['type'] == 'chunk':
                            # replenish immediately: in-flight chunks stay
                            # bounded by the credit window; backpressure
                            # comes from this loop blocking on _ready.put
                            sock.send(pickle.dumps({'type': 'credit', 'n': 1},
                                                   protocol=4))
                            if sid in received:
                                # duplicate stream: drop quietly — but a
                                # dropped shm descriptor must still return
                                # its segment to the writer.
                                if header['tag'] == b'S':
                                    shm_plane.release_descriptor(
                                        pickle.loads(frames[1]))
                                continue
                            if header['tag'] == b'S':
                                # Map NOW: the arrays are zero-copy views
                                # over the shared slab pages, and the
                                # slab returns to the worker the moment
                                # the last view dies (generation stamp
                                # from a weakref.finalize).
                                try:
                                    chunk = shm_plane.read_payload(
                                        pickle.loads(frames[1]))
                                except shm_plane.SegmentVanishedError:
                                    # Writer stopped/died before we
                                    # attached: the chunk is lost, the
                                    # count mismatch at 'end' requests a
                                    # resend.
                                    continue
                                self.shm_chunks += 1
                                buffers.setdefault((sid, attempt), {})[
                                    int(header['seq'])] = ('shm', chunk)
                                continue
                            buffers.setdefault((sid, attempt), {})[
                                int(header['seq'])] = (header['tag'],
                                                       frames[1])
                        elif header['type'] == 'end':
                            if sid in received:
                                # Duplicate stream: re-ack so the worker's
                                # completion bookkeeping settles (the
                                # dispatcher side is idempotent).
                                sock.send(pickle.dumps(
                                    {'type': 'ack', 'split': sid,
                                     'attempt': attempt}, protocol=4))
                                continue
                            parts = buffers.get((sid, attempt), {})
                            if len(parts) != int(header['chunks']):
                                # Chunks lost (routed to a stale identity
                                # across a client reconnect): NOT acked —
                                # an ack here would let the worker report
                                # complete on rows we never got.  Ask for
                                # a re-decode instead.
                                logger.warning(
                                    'split %d attempt %d: %d/%d chunks — '
                                    'discarding partial buffer and '
                                    'requesting resend', sid, attempt,
                                    len(parts), int(header['chunks']))
                                buffers.pop((sid, attempt), None)
                                sock.send(pickle.dumps(
                                    {'type': 'resend', 'split': sid,
                                     'attempt': attempt}, protocol=4))
                                continue
                            # Complete: ack — only now may the worker
                            # report the split complete to the dispatcher.
                            sock.send(pickle.dumps(
                                {'type': 'ack', 'split': sid,
                                 'attempt': attempt}, protocol=4))
                            self._merge_worker_spans(header,
                                                     addr_of.get(sock))
                            record = self._align_provenance(
                                header, addr_of.get(sock))
                            chunks = [parts[i][1] if parts[i][0] == 'shm'
                                      else deserialize_chunk(*parts[i])
                                      for i in sorted(parts)]
                            received.add(sid)
                            remaining.discard(sid)
                            for key in [k for k in buffers if k[0] == sid]:
                                del buffers[key]
                            if self._ordered:
                                held[sid] = (chunks, record)
                                while order and order[0] in held:
                                    nxt = order.pop(0)
                                    nxt_chunks, nxt_record = held.pop(nxt)
                                    self._put((nxt, nxt_chunks, nxt_record))
                            else:
                                self._put((sid, chunks, record))
        except Exception as e:  # noqa: BLE001 — re-raised in next_split
            # Without this, a crashed receiver would look exactly like a
            # clean (rows-missing!) end of stream to the consumer.
            self._error = e
        finally:
            self._ended.set()
            rpc.close()
            # Clean end of stream: the LAST split's ack may still sit in
            # ZMQ's outbound queue — a zero-linger close would discard it
            # and leave the worker replaying an already-delivered split.
            # User abort keeps the instant close.
            linger_ms = 0 if self._stop.is_set() else 1000
            for sock in sockets.values():
                sock.close(linger_ms)
            shm_plane.remove_probe(self._shm_probe)
            # Reclaim segments whose writer was SIGKILLed with descriptors
            # in flight (nothing else will ever unlink them); live
            # workers' segments are untouched.
            if self._shm_probe is not None:
                shm_plane.sweep_orphans()

    def _merge_worker_spans(self, header, addr):
        """Land a split's worker spans on this process's timeline: shift
        by the chained offsets (client-dispatcher from the discovery
        poll, worker-dispatcher from the worker's registration handshake
        — ``(C-D) - (W-D) = C-W``), label the worker's Perfetto track,
        merge.  Missing offsets (worker pre-first-heartbeat) fall back to
        0 — correct between same-host processes, where CLOCK_MONOTONIC is
        shared."""
        spans = header.get('spans')
        if not spans or self._trace is None:
            return
        shift = 0.0
        worker_offset = self._worker_offsets.get(addr)
        if self._clock_offset is not None and worker_offset is not None:
            shift = self._clock_offset - worker_offset
        pid = spans[0].get('pid')
        if pid is not None and pid not in self._labeled_pids:
            self._labeled_pids.add(pid)
            import os
            if pid != os.getpid():
                # In-process (thread) workers share our pid: labeling it
                # would rename the CLIENT's own track.
                self._trace.set_process_label(
                    pid, 'service worker %s' % (addr or '?'))
        merge_into_recorder(self._trace, spans, clock_offset_s=shift)

    def _align_provenance(self, header, addr):
        """The split's provenance record (ISSUE 13) with its stage
        windows shifted onto THIS process's monotonic clock — the same
        chained-offset math :meth:`_merge_worker_spans` applies — plus a
        receive timestamp so the consumer can account buffer-wait.

        Unlike the span path (which only renders timelines), provenance
        COMPUTES cross-clock differences (``latency_ms`` feeds the
        worst-K and the SLO watchdog), so an unalignable record is
        dropped rather than shifted by 0: a cross-host worker whose
        offset has not arrived yet (pre-first-heartbeat) would otherwise
        journal a latency equal to the inter-host boot skew, permanently
        poisoning the rolling worst-K.  Same-host workers (shared
        CLOCK_MONOTONIC) pass the sanity gate unshifted."""
        record = header.get('provenance')
        if record is None or not provenance.enabled():
            return None
        now = time.monotonic()
        worker_offset = self._worker_offsets.get(addr)
        if self._clock_offset is not None and worker_offset is not None:
            record = provenance.shift_stages(
                record, self._clock_offset - worker_offset)
        stages = record.get('stages') or {}
        latest = max((w[1] for w in stages.values()), default=now)
        if abs(now - latest) > 60.0:
            # Unaligned (or mis-aligned) clocks: the stage windows are
            # nowhere near this client's present — journaling them would
            # fabricate an hours-long batch.
            return None
        record['_received_t'] = now
        return record

    def _put(self, item):
        while not self._stop.is_set():
            try:
                self._ready.put(item, timeout=0.2)
                return
            except queue.Full:
                continue


def register_tenant_job(dispatcher_addr, tenant, config_kwargs, weight=1.0,
                        rpc_timeout_s=20.0, max_wait_s=120.0):
    """Register ``tenant``'s job on a running dispatcher (ISSUE 16).

    ``config_kwargs`` are :class:`~petastorm_tpu.service.config.
    ServiceConfig` keyword arguments (``dataset_url`` at minimum); the
    dispatcher builds the config, appends the tenant's splits to the
    global id space, and every registered worker starts serving them
    under the fair-share schedule — no new fleet.

    Admission is bounded (``max_tenant_jobs``): a refusal past the cap
    carries ``retry_after_s`` and this helper queues-with-backoff up to
    ``max_wait_s`` before raising a clear :class:`ServiceError`.  Any
    other refusal (duplicate tenant, bad config) raises immediately.

    Returns the registered job's ``job_info`` dict (``split_base``,
    ``num_splits``, ...), which a :class:`ServiceDataLoader` constructed
    with ``tenant=`` then consumes.
    """
    import zmq

    context = zmq.Context()
    try:
        rpc = _Rpc(context, dispatcher_addr, timeout_s=rpc_timeout_s)
        try:
            deadline = time.monotonic() + max_wait_s
            while True:
                # raw=True: an admission refusal is a structured reply
                # (error + retry_after_s), not an exception — we need to
                # read the retry hint before deciding to raise.
                reply = rpc.call(
                    {'op': 'register_job', 'tenant': str(tenant),
                     'weight': float(weight),
                     'config': dict(config_kwargs)}, raw=True)
                if isinstance(reply, dict) and reply.get('job') is not None:
                    return reply['job']
                error = (reply or {}).get('error', 'malformed reply')
                retry_after = (reply or {}).get('retry_after_s')
                if retry_after is None:
                    raise ServiceError(
                        'dispatcher %s refused tenant %r job: %s'
                        % (dispatcher_addr, tenant, error))
                delay = backoff.jittered(float(retry_after), 0.25)
                if time.monotonic() + delay > deadline:
                    raise ServiceError(
                        'dispatcher %s still refusing tenant %r job '
                        'after %.0fs (%s) — raise max_tenant_jobs or '
                        'retire a finished job' % (dispatcher_addr, tenant,
                                                   max_wait_s, error))
                time.sleep(delay)
        finally:
            rpc.close()
    finally:
        context.term()


def _default_consumer(num_consumers):
    """The sharding contract's default: this training host's index."""
    try:
        import jax
    except ImportError:   # no jax: a plain consumer 0; a backend failure raises
        return 0
    from petastorm_tpu.utils import apply_jax_platforms_env
    apply_jax_platforms_env()
    return jax.process_index() % num_consumers


class ServiceReader(object):
    """Reader-shaped adapter over a service connection.

    Implements exactly the surface ``petastorm_tpu.jax.DataLoader``
    consumes (iteration, ``batched_output``, ``stop``/``join``,
    ``drain_in_flight``/``resume_dispatch``/``state_dict``), yielding
    columnar chunk dicts.  A split's chunks are committed to the consumed
    set the moment they enter the loader machinery — from then on the
    loader's own snapshot carries any not-yet-yielded residue, which is
    what makes the combined token exact.
    """

    batched_output = True
    ngram = None
    num_epochs = 1

    def __init__(self, connection):
        self._conn = connection
        self._current = []
        #: Per-batch provenance (ISSUE 13): clock-aligned split records
        #: adopted as their chunks enter the loader, drained per host
        #: batch by ``DataLoader`` via :meth:`take_provenance`.
        self._pending_provenance = []
        self.last_row_consumed = False

    @property
    def job(self):
        return self._conn.job

    @property
    def consumer(self):
        return self._conn.consumer

    def __iter__(self):
        return self

    def __next__(self):
        while not self._current:
            item = self._conn.next_split()
            if item is None:
                self.last_row_consumed = True
                raise StopIteration
            split_id, chunks, record = item
            self._conn.commit(split_id)
            self._current = list(chunks)
            self._adopt_provenance(record)
        return self._current.pop(0)

    def _adopt_provenance(self, record):
        if record is None:
            return
        received = record.pop('_received_t', None)
        now = time.monotonic()
        if received is not None and now > received:
            # Time the complete split sat in the client buffer before
            # the consumer took it — part of the causal chain.
            record.setdefault('stages', {})['client_buffer'] = [received,
                                                                now]
        self._pending_provenance.append(record)
        del self._pending_provenance[:-64]

    def take_provenance(self):
        """Provenance records of the splits adopted since the last call
        (the loader-facing surface `Reader.take_provenance` also has)."""
        out = list(self._pending_provenance)
        self._pending_provenance = []
        return out

    # -- exact-checkpoint support -------------------------------------------

    def drain_in_flight(self):
        drained = list(self._current)
        self._current = []
        for split_id, chunks, record in self._conn.drain_ready():
            self._conn.commit(split_id)
            self._adopt_provenance(record)
            drained.extend(chunks)
        return drained

    def resume_dispatch(self):
        pass  # dispatch is remote; nothing was paused

    def state_dict(self):
        return {'service': {
            'version': 1,
            'consumer': self._conn.consumer,
            'tenant': self._conn.tenant,
            'consumed': sorted(self._conn.consumed),
            'num_splits': self._conn.job['num_splits'],
            'num_consumers': self._conn.job['num_consumers'],
            'fingerprint': self._conn.job['fingerprint'],
        }}

    def stop(self):
        self._conn.stop()

    def join(self):
        self._conn.join()


class ServiceDataLoader(DataLoader):
    """``petastorm_tpu.jax.DataLoader`` fed by the data service.

    Same constructor surface as ``DataLoader`` minus the reader (the
    service is the reader), plus:

    Args:
        dispatcher_addr: the dispatcher's control endpoint
            (``tcp://host:port``).
        consumer: which consumer shard this host is; defaults to
            ``jax.process_index() % num_consumers`` — the service analog
            of the readers' JAX auto-sharding.
        tenant: which tenant's job to consume on a shared fleet
            (ISSUE 16); None (the default) consumes the dispatcher's own
            job — exactly the pre-tenancy behavior.  Register other
            tenants' jobs first via :func:`register_tenant_job`.
        ordered: release splits in split-id order (deterministic) instead
            of completion order.
        queue_splits / credits / rpc_timeout_s: client-side flow control;
            ``credits`` defaults to the job's configured window.

    Everything else (``batch_size``, ``transform_fn``, ``drop_last``,
    ``prefetch``, ``device``/``sharding``, ``resume_state``, ``echo``,
    ``trace_recorder``) behaves exactly as on ``DataLoader``; resume
    tokens round-trip through ``state_dict()`` with the service position
    (committed split ids) in place of the ventilator cursor.
    """

    def __init__(self, dispatcher_addr, batch_size, consumer=None,
                 ordered=False, queue_splits=4, credits=None,
                 rpc_timeout_s=20.0, resume_state=None, tenant=None,
                 **kwargs):
        svc = ((resume_state or {}).get('reader') or {}).get('service') or {}
        if svc and consumer is None:
            consumer = svc.get('consumer')
        if svc and tenant is None:
            tenant = svc.get('tenant')
        connection = _ServiceConnection(
            dispatcher_addr, consumer=consumer, resume=svc,
            ordered=ordered, queue_splits=queue_splits, credits=credits,
            rpc_timeout_s=rpc_timeout_s, tenant=tenant,
            # The loader's recorder doubles as the merge target for the
            # workers' spans: ONE timeline from rowgroup decode to H2D.
            trace_recorder=kwargs.get('trace_recorder'))
        super(ServiceDataLoader, self).__init__(
            ServiceReader(connection), batch_size,
            resume_state=resume_state, **kwargs)

    def service_diagnostics(self):
        """Fleet-wide service metrics (dispatcher ``stats`` RPC): split
        queue depths, lease churn, per-worker rows/s."""
        conn = self.reader._conn
        rpc = _Rpc(conn._context, conn._dispatcher_addr,
                   timeout_s=conn._rpc_timeout_s)
        try:
            return rpc.call({'op': 'stats'})
        finally:
            rpc.close()


def _check_resume_geometry(svc, connection):
    """Service analog of ``Reader._check_resume_topology``: a token's
    split ids index one partition geometry; any drift (dataset, split
    size, consumer count) must raise, not silently skip/replay rows."""
    if not svc:
        return
    mismatches = [
        key for key, current in (
            ('fingerprint', connection.job['fingerprint']),
            ('num_splits', connection.job['num_splits']),
            ('num_consumers', connection.job['num_consumers']),
            ('consumer', connection.consumer),
            ('tenant', connection.tenant))
        if svc.get(key) is not None and svc[key] != current]
    if mismatches:
        raise ServiceError(
            'resume token was taken under a different service job '
            '(mismatched: %s) — its split ids do not index this '
            'partition geometry' % ', '.join(mismatches))
