"""Decode worker: leases splits, decodes them, streams batches to clients.

A worker is a thin shell around the existing reader machinery: each
leased split becomes a short-lived ``make_reader(columnar_decode=True)``
/ ``make_batch_reader`` over exactly that split's row groups
(``piece_indices=``), so the L2–L4 decode plane (pools, codecs, retries,
predicates, transform specs) runs unchanged — just on a different machine
than the accelerators.

Threads:

* the **event loop** owns every ZeroMQ socket: a ROUTER data socket that
  clients subscribe to, and a REQ control socket to the dispatcher
  (register / lease / heartbeat / complete).  Heartbeats renew all held
  leases; losing them (process death) is the failure signal the
  dispatcher acts on.
* the **decode thread** turns split descriptions into serialized chunks
  (Arrow IPC via ``reader_impl/arrow_table_serializer.py`` when the
  chunk is a flat table, pickle otherwise — the same dual framing the
  ProcessPool wire uses) through a bounded queue, which is what pauses
  decode when clients stop granting credits.  Consumers that proved
  same-host residence (a ``/dev/shm`` probe named in their subscribe —
  see ``workers_pool/shm_plane.py``) instead get **shm descriptors**:
  the chunk's columns are placed in a shared-memory segment and only
  ``(segment, offset, shape, dtype)`` metadata rides the socket, with
  transparent per-chunk fallback to the byte path (full arena, tiny
  chunk, cross-host consumer).

Delivery is credit-based: each subscriber grants a chunk budget and
replenishes it as it pulls chunks off its socket; ``end``-of-split
markers ride for free.  A split counts as done only after the owning
client ACKS the complete split — only then does the worker report
``complete`` to the dispatcher.  A worker killed at ANY point before the
ack therefore leaves the split leased, the lease expires, and the split
is reassigned: at-least-once streaming, which the client's whole-split
dedupe turns into exactly-once delivery.
"""

import logging
import os
import pickle
import queue
import threading
import time
import traceback

import numpy as np

from petastorm_tpu.errors import ServiceError, ServiceRpcTimeoutError
from petastorm_tpu.service import tenancy
from petastorm_tpu.telemetry import MetricsRegistry, provenance
from petastorm_tpu.test_util import chaos
from petastorm_tpu.utils import backoff

logger = logging.getLogger(__name__)

#: Per-split span-list bound shipped on the ``end`` header: enough for
#: every chunk of a sane split (serialize + shm publish + cache fills),
#: small enough that a pathological split can't bloat the control frames.
_MAX_SPANS_PER_SPLIT = 2048

_DEFAULT_RPC_TIMEOUT_S = 20.0

#: Zero baseline for per-split cache-outcome classification: a per-split
#: plane instance's lifetime totals ARE the split's delta.
_ZERO_CACHE = {'cache_hits': 0, 'cache_ram_hits': 0, 'cache_misses': 0,
               'cache_degraded': 0}


class _Rpc(object):  # ptlint: disable=pickle-unsafe-attrs — one per owning thread; sockets are rebuilt, never shipped
    """REQ-socket RPC client with timeout + socket recycling.

    A REQ socket wedges in send-state when a reply never comes; on
    timeout the socket is rebuilt so the caller can simply retry."""

    def __init__(self, context, addr, timeout_s=_DEFAULT_RPC_TIMEOUT_S):
        import zmq
        self._zmq = zmq
        self._context = context
        self._addr = addr
        self._timeout_s = timeout_s
        self._socket = None
        self._connect()

    def _connect(self):
        self._socket = self._context.socket(self._zmq.REQ)
        self._socket.setsockopt(self._zmq.LINGER, 0)
        self._socket.connect(self._addr)

    def call(self, request, timeout_s=None, raw=False):
        """``raw=True`` returns error replies instead of raising — for
        callers that read structured refusals (e.g. an admission
        refusal's ``retry_after_s``)."""
        from petastorm_tpu.errors import ServiceError
        timeout_s = self._timeout_s if timeout_s is None else timeout_s
        # Chaos seam (ISSUE 15): a dropped control-plane request
        # surfaces exactly what a lost request surfaces — a timeout on
        # a recycled socket — without waiting the full window (the
        # caller's retry/backoff path is what the fault exercises).
        if chaos.inject('rpc.request', op=request.get('op')) == 'drop':
            self._socket.close(0)
            self._connect()
            raise ServiceRpcTimeoutError(
                'chaos: dropped %r to %s' % (request.get('op'),
                                             self._addr))
        self._socket.send(pickle.dumps(request, protocol=4))
        if not self._socket.poll(int(timeout_s * 1000)):
            self._socket.close(0)
            self._connect()
            raise ServiceRpcTimeoutError(
                'no reply from %s to %r within %.1fs'
                % (self._addr, request.get('op'), timeout_s))
        reply = pickle.loads(self._socket.recv())
        if not raw and isinstance(reply, dict) and reply.get('error'):
            raise ServiceError('%s rejected %r: %s'
                               % (self._addr, request.get('op'),
                                  reply['error']))
        return reply

    def close(self):
        if self._socket is not None:
            self._socket.close(0)
            self._socket = None


def serialize_chunk(chunk):
    """dict-of-arrays -> (tag, payload): Arrow IPC for flat tables (the
    zero-copy-able format every Arrow consumer can read), pickle for
    multi-dim/ragged columns Arrow tables can't hold losslessly.  The
    Arrow payload is the ``pa.Buffer`` itself (buffer protocol — ZMQ
    sends it without the full extra copy ``to_pybytes()`` would force)."""
    import pyarrow as pa

    from petastorm_tpu.reader_impl.arrow_table_serializer import \
        ArrowTableSerializer

    flat = all(isinstance(v, np.ndarray) and v.ndim == 1
               and v.dtype != np.dtype(object) for v in chunk.values())
    if flat:
        try:
            table = pa.table({k: pa.array(v) for k, v in chunk.items()})
            return b'A', ArrowTableSerializer().serialize(table)
        except pa.ArrowInvalid:
            pass
    return b'R', pickle.dumps(chunk, protocol=4)


def deserialize_chunk(tag, payload):
    """Inverse of :func:`serialize_chunk`; always returns dict-of-numpy."""
    from petastorm_tpu.reader_impl.arrow_table_serializer import \
        ArrowTableSerializer

    if tag == b'A':
        table = ArrowTableSerializer().deserialize(payload)
        return {name: table.column(name).to_numpy(zero_copy_only=False)
                for name in table.column_names}
    if tag == b'R':
        return pickle.loads(payload)
    # Explicit dispatch (wire-protocol-conformance): an unknown tag is a
    # framing bug, not a pickle payload — naming it beats unpickling
    # garbage.
    raise ValueError('unknown chunk frame tag %r' % (tag,))


class Worker(object):  # ptlint: disable=pickle-unsafe-attrs — a worker IS a process/thread; jobs reach it via the dispatcher RPC, never by pickling the object
    """One decode worker process/thread.

    Args:
        dispatcher_addr: the dispatcher's REP endpoint.
        data_bind: bind spec for this worker's ROUTER data socket;
            ``tcp://host:*`` picks a free port (the resolved address is
            advertised to the dispatcher, so clients can connect).
        advertise_host: hostname/IP published to the dispatcher in place
            of the bind host.  Required in spirit whenever ``data_bind``
            uses a wildcard host: ``tcp://0.0.0.0:PORT`` is unroutable
            from other machines, so without this the worker substitutes
            ``socket.gethostname()`` and logs what it chose.
        max_inflight_splits / max_buffered_chunks: see ``ServiceConfig``.
        trace_recorder: optional ``benchmark.TraceRecorder`` — each
            decoded split is recorded as a ``service/decode_split`` span.
        cache_plane_dir: override the job's ``cache_plane_dir`` for THIS
            worker.  The plane is a host-local asset: workers on
            different machines naturally resolve the job's path on their
            own filesystems, but co-hosted workers that must NOT share a
            plane (tests, benches simulating a multi-host fleet, tiered
            storage layouts) point each at its own directory here.
    """

    def __init__(self, dispatcher_addr, data_bind='tcp://127.0.0.1:*',
                 advertise_host=None, max_inflight_splits=3,
                 max_buffered_chunks=32, trace_recorder=None,
                 cache_plane_dir=None):
        self._dispatcher_addr = dispatcher_addr
        self._data_bind = data_bind
        self._advertise_host = advertise_host
        self._max_inflight = int(max_inflight_splits)
        self._max_buffered = int(max_buffered_chunks)
        self._trace = trace_recorder
        self._stop = threading.Event()
        #: Graceful drain (ISSUE 15): set by :meth:`drain`, a SIGTERM
        #: (see :meth:`install_signal_handlers`), or a dispatcher
        #: ``drain`` RPC arriving on a heartbeat reply.  The event loop
        #: then stops leasing, hands back splits it never started,
        #: finishes streaming the rest, and deregisters — zero lost
        #: splits, zero residue.
        self._drain = threading.Event()
        #: True once the drain path completed (diagnostics surface).
        self.drained = False
        #: True when the drain deadline passed with splits in flight.
        self.drain_timed_out = False
        self._thread = None
        self._t_start = None
        self._decode_out = None
        self.worker_id = None
        self.data_addr = None
        self._ready = threading.Event()
        #: Source of truth for the worker's counters (ISSUE 5):
        #: ``diagnostics`` is a view, and the full snapshot (including
        #: the stage latency histograms) rides every heartbeat so the
        #: dispatcher's ``stats`` RPC can roll the fleet up by addition.
        self.metrics = MetricsRegistry('service_worker')
        self._m_rows = self.metrics.counter('rows_decoded')
        self._m_splits = self.metrics.counter('splits_decoded')
        self._m_shm_chunks = self.metrics.counter('shm_chunks')
        self._m_decode_hist = self.metrics.histogram('decode_split')
        self._m_serialize_hist = self.metrics.histogram('serialize')
        self._m_shm_pub_hist = self.metrics.histogram('shm_publish')
        #: (this_worker_monotonic - dispatcher_monotonic), measured at
        #: registration (reply midpoint handshake), then RE-measured on
        #: every heartbeat and EWMA-smoothed (ISSUE 7 satellite: a
        #: long-lived worker drifts off its one registration-time
        #: estimate and skews every merged timeline).  Shipped on every
        #: heartbeat; the client chains it with ITS dispatcher offset to
        #: land this worker's spans on its own timeline.
        self.clock_offset = None
        #: EWMA offset minus the registration-time offset, in ms — the
        #: drift signal `stats`/doctor surface (a same-host fleet should
        #: sit at ~0; growth means monotonic clocks diverging or rtt
        #: asymmetry corrupting the midpoint estimate).
        self.clock_drift_ms = 0.0
        self._clock_offset_initial = None
        #: shm result plane (None when the job or host disables it);
        #: written only by the decode thread, stopped after it joins.
        self._arena = None
        #: consumer -> True when its subscribe proved same-host residence
        #: (read by the decode thread, written by the event loop — a plain
        #: dict is safe under the GIL for this flag traffic).
        self._shm_consumers = {}
        #: epoch-cache plane counters accumulated across per-split
        #: readers (job['cache_plane']) into the registry; shipped in
        #: every heartbeat (see ``diagnostics``).
        self._m_cache = {key: self.metrics.counter(key)
                         for key in ('cache_hits', 'cache_misses',
                                     'cache_evictions', 'cache_ram_hits',
                                     'cache_degraded')}
        #: Cluster cache tier (ISSUE 10): remote_hits counts pieces of a
        #: leased split streamed straight from the local plane (no
        #: reader constructed); peer_fills counts entries fetched from a
        #: peer's plane instead of re-decoded; peer_degraded counts
        #: fetches that failed (dead/slow/absent peer -> direct decode).
        self._m_cluster = {key: self.metrics.counter(key)
                           for key in ('cache_remote_hits',
                                       'cache_peer_fills',
                                       'cache_peer_degraded')}
        self._m_serve_hist = self.metrics.histogram('serve_cached_split')
        #: Unified backoff telemetry (ISSUE 15): every control-plane
        #: retry this worker schedules (heartbeat, re-register, peer
        #: fetch) and every episode that exhausted its budget.  Ride the
        #: heartbeats like every counter, summed fleet-wide in `stats`'s
        #: control_plane rollup — a retry storm is a fleet phenomenon.
        self._m_retry = {key: self.metrics.counter(key)
                         for key in ('retry_attempts', 'retry_giveups')}
        #: ClusterWorkerState when the job opts in (None otherwise /
        #: killed); owned by run(), read by the event + decode threads.
        self._cluster = None
        self._cache_plane_dir = cache_plane_dir
        # -- multi-tenant serving (ISSUE 16) ---------------------------------
        #: tenant -> job_info, fetched lazily on the first lease naming
        #: an unknown tenant (the register reply seeds the default).
        self._tenant_jobs = {}
        #: tenant -> resolved reader factory (datasets differ per job).
        self._reader_factories = {}
        #: Per-tenant byte budgets (job_info's tenant_*_quota_bytes).
        #: shm: outstanding descriptor bytes, refunded when the split's
        #: ack retires them; over budget the chunk takes the byte path.
        #: cache: cumulative fill bytes this worker pushed into the
        #: plane; over budget the tenant's readers are built WITHOUT the
        #: plane (direct decode).  Both degrade, neither stalls.
        self._shm_quota = tenancy.QuotaLedger(label='shm')
        self._cache_quota = tenancy.QuotaLedger(label='cache')
        #: (split_id, attempt) -> shm bytes charged; refunded on ack /
        #: replay / decode error so a lost ack cannot leak budget.
        self._shm_split_bytes = {}
        #: tenants whose cache-plane budget is exhausted (sticky for the
        #: worker's lifetime: the plane's files persist on disk).
        self._cache_over_budget = set()
        self._m_quota = {key: self.metrics.counter(key)
                         for key in ('shm_quota_degraded',
                                     'cache_quota_degraded')}

    # -- lifecycle -----------------------------------------------------------

    def start(self):
        """Run the worker in a daemon thread (in-process deployments:
        tests).  The CLI calls :meth:`run`."""
        self._thread = threading.Thread(target=self.run,
                                        name='service-worker', daemon=True)
        self._thread.start()
        # _ready is also set on an early run() failure (so start() never
        # hangs); a set event with no worker_id means registration failed.
        if not self._ready.wait(timeout=30) or self.worker_id is None:
            raise RuntimeError('worker failed to register with %r'
                               % (self._dispatcher_addr,))
        return self

    def stop(self):
        self._stop.set()

    def drain(self):
        """Begin a graceful drain (ISSUE 15): stop taking leases, hand
        back splits never started (``release`` RPC, attempt intact),
        finish streaming + awaiting acks for the rest, flush/retire shm
        slabs, then ``deregister`` and exit the event loop.  Bounded by
        the job's ``drain_timeout_s``; past it the worker deregisters
        as ``timed_out`` and the dispatcher requeues the remainder
        immediately.  Idempotent; safe from any thread and from a
        signal handler (it only sets an Event)."""
        self._drain.set()

    def install_signal_handlers(self):
        """SIGTERM -> :meth:`drain` (the scale-in half of autoscaling:
        an orchestrator's terminationGracePeriod maps onto the drain
        deadline).  Main-thread only by the stdlib's rules; the CLI
        path calls this, in-process deployments call :meth:`drain`."""
        import signal

        def on_sigterm(signum, frame):
            logger.info('SIGTERM: draining worker %s', self.worker_id)
            self.drain()

        signal.signal(signal.SIGTERM, on_sigterm)

    def join(self):
        if self._thread is not None:
            self._thread.join()

    def __enter__(self):
        return self.start()

    def __exit__(self, exc_type, exc_value, tb):
        self.stop()
        self.join()

    # -- main loop -----------------------------------------------------------

    def run(self):
        import zmq

        context = zmq.Context()
        data = context.socket(zmq.ROUTER)
        data.setsockopt(zmq.LINGER, 0)
        data.set_hwm(0)  # credits bound in-flight data, not the HWM
        if self._data_bind.startswith('tcp') and (
                self._data_bind.endswith(':*')
                or self._data_bind.endswith(':0')):
            base = self._data_bind.rsplit(':', 1)[0]
            port = data.bind_to_random_port(base)
            self.data_addr = '%s:%d' % (base, port)
        else:
            data.bind(self._data_bind)
            self.data_addr = self._data_bind
        self.data_addr = self._advertised(self.data_addr)
        rpc = _Rpc(context, self._dispatcher_addr)
        decode_in = queue.Queue()
        decode_out = queue.Queue(maxsize=self._max_buffered)
        self._decode_out = decode_out
        decode_thread = None
        try:
            t_reg0 = time.monotonic()
            reply = rpc.call({'op': 'register_worker',
                              'data_addr': self.data_addr})
            t_reg1 = time.monotonic()
            self.worker_id = reply['worker_id']
            job = reply['job']
            if self._cache_plane_dir is not None:
                # Host-local override applied in ONE place: every
                # downstream consumer (per-split readers, the cluster
                # identity) sees the same resolved path.
                job = dict(job, cache_plane_dir=self._cache_plane_dir)
            # The register reply's job IS the default tenant's; further
            # tenants' jobs are fetched lazily on their first lease.
            self._adopt_tenant_job(job)
            # Clock handshake (ISSUE 5): dispatcher monotonic against
            # the local send/recv midpoint — wrong by at most rtt/2,
            # which orders spans fine on any LAN.  Heartbeats repeat it
            # (ISSUE 7: drift EWMA).
            self._update_clock(reply.get('t_mono'), t_reg0, t_reg1)
            from petastorm_tpu.service import cluster
            if cluster.enabled(job):
                # Identity build is a footer scan — background it so a
                # big dataset cannot delay registration/first lease.
                self._cluster = cluster.ClusterWorkerState(job)
            from petastorm_tpu.telemetry import flight
            # Always-on flight recorder for this process: the minutes
            # before a worker death persist when a flight dir is set.
            flight.enable(label='service_worker')
            from petastorm_tpu.workers_pool import shm_plane
            if job.get('shm', True) and shm_plane.available():
                self._arena = shm_plane.ShmArena(
                    capacity_bytes=job.get(
                        'shm_capacity_bytes',
                        shm_plane.DEFAULT_CAPACITY_BYTES),
                    metrics=self.metrics)
            self._t_start = time.monotonic()
            #: shared zmq context for the decode thread's peer fetcher
            #: (contexts are thread-safe; the fetcher's sockets live and
            #: die on the decode thread alone).
            self._zmq_context = context
            self._ready.set()
            decode_thread = threading.Thread(
                target=self._decode_loop, args=(job, decode_in, decode_out),
                name='service-worker-decode', daemon=True)
            decode_thread.start()
            self._event_loop(zmq, data, rpc, job, decode_in, decode_out)
        finally:
            self._ready.set()  # unblock start() on early failure
            decode_in.put(None)
            if decode_thread is not None:
                # Unstick a decode blocked on the bounded output queue.
                while decode_thread.is_alive():
                    try:
                        decode_out.get_nowait()
                    except queue.Empty:
                        decode_thread.join(timeout=0.05)
            if self._arena is not None:
                # After the decode thread: unlink every segment no client
                # mapped, so a clean shutdown leaves zero /dev/shm residue
                # (descriptors dropped above go with their segments).
                self._arena.stop()
            rpc.close()
            data.close(0)
            context.term()

    #: EWMA weight of each new midpoint estimate: heavy enough to track
    #: genuine drift within ~10 beats, light enough that one rtt-skewed
    #: beat cannot yank every span's alignment.
    _CLOCK_EWMA_ALPHA = 0.2

    def _update_clock(self, t_mono, t0, t1):
        """Fold one (reply ``t_mono``, local send/recv window) clock
        handshake into the EWMA offset + drift estimate."""
        if t_mono is None:
            return
        estimate = (t0 + t1) / 2.0 - float(t_mono)
        if self.clock_offset is None:
            self._clock_offset_initial = estimate
            self.clock_offset = round(estimate, 6)
            return
        alpha = self._CLOCK_EWMA_ALPHA
        ewma = (1.0 - alpha) * self.clock_offset + alpha * estimate
        self.clock_offset = round(ewma, 6)
        self.clock_drift_ms = round(
            1e3 * (ewma - self._clock_offset_initial), 3)

    def _count_retry(self, episode):
        """Count one heartbeat-class retry; an EXHAUSTED episode counts
        one ``retry_giveups`` (the dead-dispatcher signal the
        control-plane-degraded regime reads) and rolls into a fresh
        episode — the worker never stops trying, only the telemetry
        marks the budget boundary."""
        episode = episode or backoff.HEARTBEAT_POLICY.episode()
        self._m_retry['retry_attempts'].inc()
        if episode.give_up():
            self._m_retry['retry_giveups'].inc()
            episode = backoff.HEARTBEAT_POLICY.episode()
        return episode

    def _advertised(self, addr):
        """The address published to the dispatcher: clients on OTHER
        machines connect to it, so a wildcard bind host must be replaced
        with something routable."""
        scheme, rest = addr.split('://', 1)
        host, port = rest.rsplit(':', 1)
        if self._advertise_host is not None:
            host = self._advertise_host
        elif host in ('0.0.0.0', '*', '::'):
            import socket
            host = socket.gethostname()
            logger.warning(
                'data_bind host %r is unroutable from other machines; '
                'advertising %r instead (pass advertise_host/'
                '--advertise-host to override)', '0.0.0.0', host)
        return '%s://%s:%s' % (scheme, host, port)

    # -- multi-tenant job table (ISSUE 16) -----------------------------------

    def _adopt_tenant_job(self, job):
        """Enter one tenant's job_info into the worker's table and arm
        its quota budgets.  Returns the tenant id."""
        tenant = str(job.get('tenant') or tenancy.DEFAULT_TENANT)
        self._tenant_jobs[tenant] = job
        self._shm_quota.set_budget(tenant,
                                   job.get('tenant_shm_quota_bytes'))
        self._cache_quota.set_budget(tenant,
                                     job.get('tenant_cache_quota_bytes'))
        return tenant

    def _job_for(self, split):
        """The owning tenant's job_info for a leased split (the decode
        thread reads dataset_url / reader_kwargs from it).  Known by the
        time the split is queued — ``_event_loop`` fetches unknown
        tenants' jobs before queueing; the default job is the fallback
        for pre-tenancy dispatchers that ship splits without the key."""
        tenant = str(split.get('tenant') or tenancy.DEFAULT_TENANT)
        return self._tenant_jobs.get(
            tenant, self._tenant_jobs[tenancy.DEFAULT_TENANT])

    def _fetch_tenant_job(self, rpc, tenant):
        """Fetch + adopt an unknown tenant's job_info from the
        dispatcher; False when the RPC fails (the caller releases the
        split instead of decoding it against the wrong config)."""
        if tenant in self._tenant_jobs:
            return True
        try:
            reply = rpc.call({'op': 'job', 'tenant': tenant})
        except ServiceError as e:
            logger.warning('job fetch for tenant %r failed: %s', tenant, e)
            return False
        job = reply['job']
        if self._cache_plane_dir is not None:
            job = dict(job, cache_plane_dir=self._cache_plane_dir)
        self._adopt_tenant_job(job)
        logger.info('adopted tenant %r job (%s)', tenant,
                    job.get('dataset_url'))
        return True

    @staticmethod
    def _split_tenant(split):
        return str(split.get('tenant') or tenancy.DEFAULT_TENANT)

    def _refund_shm_quota(self, split):
        """Return a split's outstanding shm-descriptor bytes to its
        tenant's budget (ack arrived / stream abandoned)."""
        key = (int(split['split_id']), int(split['attempt']))
        nbytes = self._shm_split_bytes.pop(key, 0)
        if nbytes:
            self._shm_quota.refund(self._split_tenant(split), nbytes)

    def _event_loop(self, zmq, data, rpc, job, decode_in, decode_out):
        heartbeat_every = max(0.2, job['lease_ttl_s'] / 3.0)
        next_heartbeat = 0.0
        #: Active backoff episode across consecutive heartbeat /
        #: re-register failures (None while healthy) — the unified
        #: jittered-exponential policy (ISSUE 15) in place of the old
        #: fixed-interval retry that had the whole fleet hammering a
        #: restarted dispatcher in lockstep.
        hb_retry = None
        draining = False
        drain_deadline = None
        next_lease_probe = 0.0
        subscribers = {}      # (tenant, consumer) -> identity
        credits = {}          # identity -> remaining chunk budget
        sendq = {}            # (tenant, consumer) -> deque of
        #                       (header, payload|None)
        inflight = {}         # split_id -> split description
        awaiting_ack = {}     # (split_id, attempt) -> split description
        ack_deadline = {}     # (split_id, attempt) -> monotonic deadline
        ack_timeout = 3.0 * job['lease_ttl_s']
        decoding = set()      # split ids queued/being decoded

        def replay(key):
            """Re-decode a streamed-but-never-acked split: its frames went
            to an identity that is gone (client restart) or the ack was
            lost; without this it would sit in inflight forever, its lease
            renewing on every heartbeat."""
            split = awaiting_ack.pop(key, None)
            ack_deadline.pop(key, None)
            if split is not None and split['split_id'] not in decoding:
                # The abandoned stream's shm descriptors will never be
                # acked: return their bytes before the re-decode
                # re-charges the tenant's budget.
                self._refund_shm_quota(split)
                decoding.add(split['split_id'])
                decode_in.put(split)
        poller = zmq.Poller()
        poller.register(data, zmq.POLLIN)
        from collections import deque

        while not self._stop.is_set():
            now = time.monotonic()
            # 1. client control messages (subscribe / credit / ack)
            if dict(poller.poll(20)):
                while True:
                    try:
                        identity, raw = data.recv_multipart(zmq.NOBLOCK)
                    except zmq.Again:
                        break
                    msg = pickle.loads(raw)
                    kind = msg.get('type')
                    if kind == 'subscribe':
                        consumer = int(msg['consumer'])
                        # Tenant-qualified subscription (ISSUE 16): a
                        # subscribe without the field is a pre-tenancy
                        # client on the default tenant's job.
                        ckey = (str(msg.get('tenant')
                                    or tenancy.DEFAULT_TENANT), consumer)
                        previous = subscribers.get(ckey)
                        if previous is not None and previous != identity:
                            # The consumer reconnected under a new ZMQ
                            # identity: anything streamed to the old one
                            # (including 'end' markers) is gone — replay
                            # its un-acked splits to the new identity.
                            credits.pop(previous, None)
                            for key in [k for k, s in awaiting_ack.items()
                                        if (self._split_tenant(s),
                                            s['consumer']) == ckey]:
                                replay(key)
                        subscribers[ckey] = identity
                        credits[identity] = int(msg.get('credits', 8))
                        # Same-host handshake: the client names a probe
                        # file it created in ITS /dev/shm; seeing the file
                        # proves shared shm (hostname checks get
                        # containers wrong in both directions).
                        from petastorm_tpu.workers_pool import shm_plane
                        self._shm_consumers[ckey] = bool(
                            self._arena is not None
                            and shm_plane.probe_exists(
                                msg.get('shm_probe')))
                    elif kind == 'credit':
                        if identity in credits:
                            credits[identity] += int(msg.get('n', 1))
                    elif kind == 'fetch':
                        # Cluster cache tier (ISSUE 10): a peer worker
                        # asks for one encoded plane entry by digest.
                        # Request/reply on the spot — fetches are not
                        # credit-gated chunks, and the entry read is a
                        # bounded mmap copy, not a decode.
                        from petastorm_tpu.service import cluster
                        state = self._cluster
                        plane = (state.identity.plane
                                 if state is not None and state.ready()
                                 else None)
                        data.send_multipart(cluster.fetch_reply(
                            identity, msg, plane, arena=self._arena))
                    elif kind == 'ack':
                        key = (int(msg['split']), int(msg['attempt']))
                        split = awaiting_ack.pop(key, None)
                        ack_deadline.pop(key, None)
                        if split is not None:
                            inflight.pop(split['split_id'], None)
                            # The ack retires the split's shm
                            # descriptors: their bytes return to the
                            # tenant's outstanding-shm budget.
                            self._refund_shm_quota(split)
                            try:
                                rpc.call({'op': 'complete',
                                          'worker_id': self.worker_id,
                                          'split_id': split['split_id'],
                                          'attempt': split['attempt']})
                            except ServiceError as e:
                                logger.warning('complete(%d) RPC failed: %s',
                                               split['split_id'], e)
                    elif kind == 'resend':
                        # The client lost chunks of this stream and
                        # discarded its partial buffer: decode + stream the
                        # split again.  It stays in inflight, so the lease
                        # keeps renewing.
                        replay((int(msg['split']), int(msg['attempt'])))
            # 1b. drain trigger (ISSUE 15): hand back every split still
            # sitting in the decode queue (never started — `release`
            # requeues it at the dispatcher, attempt intact), stop
            # leasing, and let the rest finish streaming.  The split
            # currently decoding, anything buffered, and every
            # streamed-but-unacked split complete through the normal
            # chunk/end/ack/complete path — zero lost splits.
            if not draining and self._drain.is_set():
                draining = True
                drain_deadline = now + float(job.get('drain_timeout_s',
                                                     30.0))
                handed = 0
                while True:
                    try:
                        item = decode_in.get_nowait()
                    except queue.Empty:
                        break
                    if item is None:
                        # run()'s stop sentinel: shutdown outranks the
                        # drain — re-queue it for the decode thread and
                        # stop handing back (popping it again here
                        # would spin this loop forever).
                        decode_in.put(None)
                        break
                    inflight.pop(item['split_id'], None)
                    decoding.discard(item['split_id'])
                    handed += 1
                    try:
                        rpc.call({'op': 'release',
                                  'worker_id': self.worker_id,
                                  'split_id': item['split_id'],
                                  'attempt': item['attempt']})
                    except ServiceError:
                        # The lease expires instead (attempt+1) — the
                        # slow path, but still zero lost splits.
                        pass
                logger.info('draining: handed back %d unstarted '
                            'split(s), %d still in flight', handed,
                            len(inflight))
            # 2. move decoded chunks into per-consumer send queues — but
            # only while fewer than max_buffered_chunks wait for credits:
            # leaving the rest in the bounded decode_out queue is what
            # pauses _decode_loop when consumers are slow or absent.
            while sum(len(q) for q in sendq.values()) < self._max_buffered:
                try:
                    item = decode_out.get_nowait()
                except queue.Empty:
                    break
                kind, split = item[0], item[1]
                ckey = (self._split_tenant(split), split['consumer'])
                if kind == 'chunk':
                    _, _, seq, tag, payload = item
                    header = {'type': 'chunk', 'split': split['split_id'],
                              'attempt': split['attempt'], 'seq': seq,
                              'tag': tag}
                    sendq.setdefault(ckey, deque()).append(
                        (header, payload))
                elif kind == 'end':
                    _, _, nchunks, nrows, chunk_spans = item[:5]
                    decoding.discard(split['split_id'])
                    header = {'type': 'end', 'split': split['split_id'],
                              'attempt': split['attempt'],
                              'chunks': nchunks, 'rows': nrows,
                              # Correlated spans of this split's decode
                              # (ISSUE 5): the client aligns them onto its
                              # clock via the chained dispatcher offsets
                              # and merges them into its TraceRecorder.
                              'spans': chunk_spans}
                    if len(item) > 5 and item[5] is not None:
                        # Per-split provenance record (ISSUE 13): rides
                        # the end header like the spans; the client
                        # aligns its stage windows onto its own clock.
                        header['provenance'] = item[5]
                    sendq.setdefault(ckey, deque()).append((header, None))
                    key = (split['split_id'], split['attempt'])
                    awaiting_ack[key] = split
                    ack_deadline[key] = time.monotonic() + ack_timeout
                else:  # decode error: log, drop — the lease will expire
                    decoding.discard(split['split_id'])
                    inflight.pop(split['split_id'], None)
                    self._refund_shm_quota(split)
                    logger.error('decode of split %d failed:\n%s',
                                 split['split_id'], item[2])
            # 3. flush send queues under credit control
            for ckey, q in sendq.items():
                identity = subscribers.get(ckey)
                if identity is None:
                    continue
                while q:
                    header, payload = q[0]
                    if header['type'] == 'chunk':
                        if credits.get(identity, 0) < 1:
                            break
                        # Chaos seam (ISSUE 15): drop/duplicate/delay a
                        # data-plane chunk.  Byte-path frames only — a
                        # duplicated shm descriptor would double-release
                        # its slab generation.  A dropped chunk keeps
                        # its credit with the client (the fault models
                        # identity loss, and exactly-once must stay
                        # LIVE under injection: the client's chunk-count
                        # mismatch at `end` requests the resend).
                        action = (chaos.inject('worker.chunk',
                                               split=header['split'],
                                               seq=header['seq'])
                                  if header['tag'] != b'S' else None)
                        if action != 'drop':
                            credits[identity] -= 1
                            data.send_multipart(
                                [identity,
                                 pickle.dumps(header, protocol=4),
                                 payload])
                            if action == 'dup':
                                data.send_multipart(
                                    [identity,
                                     pickle.dumps(header, protocol=4),
                                     payload])
                    else:
                        data.send_multipart(
                            [identity, pickle.dumps(header, protocol=4)])
                    q.popleft()
            # 3b. acks that never came (lost to a vanished identity with no
            # re-subscribe): replay to the current subscriber rather than
            # holding the split — and its lease — forever.
            if ack_deadline:
                for key in [k for k, d in ack_deadline.items() if now > d]:
                    split = awaiting_ack.get(key)
                    if split is None or subscribers.get(
                            (self._split_tenant(split),
                             split['consumer'])) is None:
                        # no subscriber to replay to: push the deadline out
                        # instead of spinning on decode
                        ack_deadline[key] = now + ack_timeout
                        continue
                    logger.warning('split %d attempt %d un-acked for %.0fs; '
                                   'replaying', key[0], key[1], ack_timeout)
                    replay(key)
            # 4. heartbeat (renews the leases this worker still claims).
            # Cadence is jittered (a same-TTL fleet must not beat in
            # phase) and failures retry on the shared
            # jittered-exponential policy (ISSUE 15) instead of the old
            # fixed-interval lockstep: a restarted dispatcher sees the
            # fleet's retries spread out, not as one synchronized storm.
            if now >= next_heartbeat:
                try:
                    t_hb0 = time.monotonic()
                    request = {'op': 'heartbeat',
                               'worker_id': self.worker_id,
                               'stats': self.heartbeat_stats(),
                               'held': list(inflight)}
                    if draining:
                        request['draining'] = True
                    # Cluster cache advertisement rides the heartbeat
                    # (ISSUE 10): the compact held-digest set when it
                    # changed, and the once-per-job piece-digest map
                    # until the dispatcher confirms it has one.
                    sent_pieces = False
                    if self._cluster is not None:
                        fields = self._cluster.heartbeat_fields()
                        sent_pieces = 'piece_digests' in fields
                        request.update(fields)
                    reply = rpc.call(request)
                    if self._cluster is not None:
                        if sent_pieces and reply.get('ok'):
                            self._cluster.advertised_pieces = True
                        if reply.get('need_piece_digests'):
                            self._cluster.advertised_pieces = False
                    if reply.get('drain'):
                        # Dispatcher-initiated drain (the `drain` RPC)
                        # arrives here, on the channel we already poll.
                        self._drain.set()
                    # Opportunistic clock re-handshake (ISSUE 7): the
                    # beat's send/recv midpoint EWMAs into clock_offset
                    # so a long-lived worker tracks drift instead of
                    # freezing its registration-time estimate.
                    self._update_clock(reply.get('t_mono'), t_hb0,
                                       time.monotonic())
                    hb_retry = None
                    next_heartbeat = now + backoff.jittered(
                        heartbeat_every, 0.1)
                except ServiceRpcTimeoutError:
                    logger.warning('heartbeat to %s timed out',
                                   self._dispatcher_addr)
                    hb_retry = self._count_retry(hb_retry)
                    # Never slower than the healthy cadence: a worker
                    # "backing off" past the TTL would lose its leases
                    # to expiry while politely waiting.
                    next_heartbeat = now + min(heartbeat_every,
                                               hb_retry.next_delay())
                except ServiceError:
                    # The dispatcher lost our registration (restart):
                    # re-register under a fresh id rather than dying.
                    try:
                        reply = rpc.call({'op': 'register_worker',
                                          'data_addr': self.data_addr})
                        logger.warning('re-registered with %s as %s (was %s)',
                                       self._dispatcher_addr,
                                       reply['worker_id'], self.worker_id)
                        self.worker_id = reply['worker_id']
                        if self._cluster is not None:
                            # A restarted dispatcher lost the directory:
                            # re-advertise everything on the next beat.
                            self._cluster.reset_advertisement()
                        hb_retry = None
                        # Beat immediately under the fresh id: the
                        # `held` claims on that beat are what lets a
                        # ledger-restored dispatcher ADOPT our leases
                        # before their grace TTL expires them.
                        next_heartbeat = now
                    except ServiceError:  # incl. timeout
                        hb_retry = self._count_retry(hb_retry)
                        next_heartbeat = now + min(heartbeat_every,
                                                   hb_retry.next_delay())
            # 4b. drain completion (ISSUE 15): once nothing is in
            # flight (every split acked+completed or handed back) and
            # nothing is buffered, deregister and leave; past the
            # deadline deregister as timed_out — the dispatcher
            # requeues the remainder immediately.
            if draining:
                idle = not inflight and decode_out.empty() \
                    and not any(sendq.values())
                if idle or now > drain_deadline:
                    self.drain_timed_out = not idle
                    if not idle:
                        logger.warning(
                            'drain deadline passed with %d split(s) '
                            'still in flight; deregistering timed_out',
                            len(inflight))
                    try:
                        rpc.call({'op': 'deregister',
                                  'worker_id': self.worker_id,
                                  'timed_out': not idle})
                    except ServiceError:
                        pass  # heartbeats stop; leases expire instead
                    self.drained = True
                    break
            # 5. lease more work — only for consumers with a live
            # subscriber here, so an absent training host's splits don't
            # occupy this worker's decode plane and send buffer.  A
            # draining worker takes nothing new, by contract.
            if not draining and subscribers \
                    and len(inflight) < self._max_inflight \
                    and now >= next_lease_probe:
                try:
                    # (tenant, consumer) pairs — the dispatcher's WDRR
                    # scheduler leases only work these subscribers can
                    # actually drain.
                    reply = rpc.call({'op': 'lease',
                                      'worker_id': self.worker_id,
                                      'consumers': sorted(subscribers)})
                except ServiceError:  # timeout or not-yet-re-registered
                    reply = {'wait': True}
                if reply.get('drain'):
                    # Dispatcher-initiated drain also rides lease
                    # refusals — a lease-hungry worker must not wait a
                    # heartbeat interval to learn it.
                    self._drain.set()
                if reply.get('split'):
                    split = reply['split']
                    # Cluster tier: the dispatcher's directory hints at
                    # which peers hold this split's entries (cdigest ->
                    # [data addr]); the decode thread uses them for peer
                    # fill.  Advisory: absent/stale hints just decode.
                    if reply.get('holders'):
                        split['holders'] = reply['holders']
                    # First lease for an unknown tenant: fetch its job
                    # BEFORE queueing (the decode thread must read the
                    # right dataset/config).  A failed fetch hands the
                    # split back rather than decoding it wrong.
                    if self._fetch_tenant_job(rpc,
                                              self._split_tenant(split)):
                        inflight[split['split_id']] = split
                        decoding.add(split['split_id'])
                        decode_in.put(split)
                    else:
                        try:
                            rpc.call({'op': 'release',
                                      'worker_id': self.worker_id,
                                      'split_id': split['split_id'],
                                      'attempt': split['attempt']})
                        except ServiceError:
                            pass  # the lease expires instead
                        next_lease_probe = now + min(
                            1.0, max(0.05, job['lease_ttl_s'] / 10.0))
                else:
                    # nothing assignable right now (all leased or all done)
                    next_lease_probe = now + min(
                        1.0, max(0.05, job['lease_ttl_s'] / 10.0))

    # -- decode --------------------------------------------------------------

    def _resolve_factory(self, job):
        """'auto': petastorm metadata -> codec reader (columnar output),
        plain Parquet -> batch reader.  Resolved once per worker."""
        from petastorm_tpu.errors import MetadataError
        from petastorm_tpu.reader import make_batch_reader, make_reader

        def codec_reader(url, **kwargs):
            return make_reader(url, columnar_decode=True, **kwargs)

        choice = job['reader_factory']
        if choice == 'reader':
            return codec_reader
        if choice == 'batch_reader':
            return make_batch_reader
        try:
            reader = codec_reader(job['dataset_url'], num_epochs=1,
                                  piece_indices=[0], shuffle_row_groups=False,
                                  **job['reader_kwargs'])
            reader.stop()
            reader.join()
            return codec_reader
        except MetadataError:
            return make_batch_reader

    def _serialize_split_chunk(self, split, chunk, cid, spans):
        """(tag, payload) for one chunk: shm descriptors (tag ``b'S'``)
        for consumers that proved same-host residence, degrading per-chunk
        to the byte framing (arena full, chunk under the segment-worthy
        floor, or a cross-host consumer).  Each chunk's serialize/publish
        time feeds the stage histograms and, correlation-id'd by
        ``split/seq``, the span list riding the split's ``end`` header."""
        t0 = time.monotonic()
        tenant = self._split_tenant(split)
        if self._arena is not None \
                and self._shm_consumers.get((tenant, split['consumer'])):
            # Per-tenant shm budget (ISSUE 16), enforced at publish: a
            # chunk that would push the tenant's OUTSTANDING descriptor
            # bytes past its quota takes the byte path instead — degrade,
            # never stall.  Charged bytes return when the split's ack
            # retires its descriptors.
            nbytes = sum(int(getattr(v, 'nbytes', 0))
                         for v in chunk.values())
            if not self._shm_quota.charge(tenant, nbytes):
                self._m_quota['shm_quota_degraded'].inc()
            else:
                from petastorm_tpu.workers_pool import shm_plane
                desc = shm_plane.write_columns(self._arena, chunk)
                if desc is not None:
                    key = (int(split['split_id']), int(split['attempt']))
                    self._shm_split_bytes[key] = \
                        self._shm_split_bytes.get(key, 0) + nbytes
                    t1 = time.monotonic()
                    self._m_shm_chunks.inc()
                    self._m_shm_pub_hist.observe(t1 - t0)
                    spans.append({'name': 'service/shm_publish', 't0': t0,
                                  't1': t1, 'pid': os.getpid(),
                                  'tid': threading.get_ident(),
                                  'cid': cid})
                    return b'S', pickle.dumps(desc, protocol=4)
                self._shm_quota.refund(tenant, nbytes)
        tag, payload = serialize_chunk(chunk)
        t1 = time.monotonic()
        self._m_serialize_hist.observe(t1 - t0)
        spans.append({'name': 'service/serialize', 't0': t0, 't1': t1,
                      'pid': os.getpid(), 'tid': threading.get_ident(),
                      'cid': cid})
        return tag, payload

    def _split_record(self, split, stages, serialize_spans, tags, cache,
                      worker_args=None, sched=None):
        """Per-split provenance record (ISSUE 13), shipped on the split's
        ``end`` header next to the spans.  Stage windows are THIS
        worker's monotonic clock; the client re-aligns them via the
        chained clock offsets before journaling."""
        stages = dict(stages)
        busy_ms = {}
        for stage, names in (('serialize', ('service/serialize',
                                            'service/shm_publish')),
                             ('cache_fill', ('cache/fill',))):
            windows = [s for s in serialize_spans if s.get('name') in names]
            if windows:
                stages[stage] = [min(s['t0'] for s in windows),
                                 max(s['t1'] for s in windows)]
                # Per-chunk spans interleave with decode, so the window
                # is an ENVELOPE spanning most of the split: ship the
                # summed busy time too, which is what explain's dur_ms /
                # %-of-wall columns report (the envelope alone would
                # misattribute the whole split wall to serialization).
                busy_ms[stage] = round(
                    1e3 * sum(s['t1'] - s['t0'] for s in windows), 3)
        transport = None
        if tags:
            if tags <= {b'S'}:
                transport = 'shm'
            elif b'S' in tags:
                transport = 'mixed'
            else:
                transport = 'bytes'
        return provenance.make_record(
            'service', worker_pid=os.getpid(),
            worker_host=provenance.host(),
            pieces=provenance.pieces_for_indices(
                worker_args, split.get('indices') or ()),
            cache=cache, transport=transport, sched=sched, stages=stages,
            stage_busy_ms=busy_ms or None,
            split=int(split['split_id']), attempt=int(split['attempt']),
            # Cost attribution (ISSUE 16): every service record names
            # the tenant whose job paid for this split's decode.
            tenant=self._split_tenant(split))

    def _reader_kwargs(self, job):
        """Per-split reader kwargs; with ``job['cache_plane']`` the reader
        consults the shared epoch-cache plane before hitting Parquet —
        the cache-hit half of the ownership contract (the dispatcher's
        lease is the decode half: each piece is DECODED by exactly one
        worker per epoch, and any worker can SERVE it warm afterwards).
        Explicit cache settings in ``reader_kwargs`` win."""
        kwargs = dict(job['reader_kwargs'])
        # Per-split readers inherit the job's dispatch policy (ISSUE 9);
        # an explicit reader_kwargs['scheduling'] wins, and 'auto' still
        # degrades to fifo on splits too small to reorder.
        kwargs.setdefault('scheduling', job.get('scheduling', 'auto'))
        # ...and the job's ingest-plane mode (ISSUE 14): decode workers
        # are exactly the processes that pay object-store first-byte
        # latency, so the per-split reader mounts the same async
        # byte-range plane a local reader would ('auto' still stays off
        # on local filesystems and under the kill switch).
        kwargs.setdefault('ingest', job.get('ingest', 'auto'))
        tenant = str(job.get('tenant') or tenancy.DEFAULT_TENANT)
        if tenant in self._cache_over_budget \
                and 'cache_type' not in kwargs:
            # Per-tenant cache budget exhausted (ISSUE 16): this
            # tenant's readers run WITHOUT the plane — direct decode,
            # no new fills, never a stall.
            self._m_quota['cache_quota_degraded'].inc()
            return kwargs
        if job.get('cache_plane') and 'cache_type' not in kwargs:
            kwargs['cache_type'] = 'plane'
            kwargs.setdefault('cache_location', job['cache_plane_dir'])
            kwargs.setdefault('cache_size_limit',
                              job.get('cache_plane_disk_bytes'))
            extra = dict(kwargs.get('cache_extra_settings') or {})
            extra.setdefault('ram_bytes', job.get('cache_plane_ram_bytes'))
            kwargs['cache_extra_settings'] = extra
        return kwargs

    def _accumulate_cache_stats(self, reader):
        """Fold one (per-split, hence fresh) plane instance's counters
        and its ``cache_fill`` latency histogram into the worker
        registry, so fill time reaches the fleet ``stages`` rollup like
        every other stage.  Counters are accumulated explicitly (their
        names collide with the heartbeat keys) — merge ONLY the
        histograms from the plane snapshot."""
        cache = getattr(reader, '_cache', None)
        stats = getattr(cache, 'stats', None)
        if not stats:
            return
        for key, counter in self._m_cache.items():
            counter.inc(int(stats.get(key, 0)))
        plane_metrics = getattr(cache, 'metrics', None)
        if plane_metrics is not None:
            self.metrics.merge(
                {'histograms': plane_metrics.snapshot()['histograms']})

    def _accumulate_ingest_stats(self, reader):
        """Fold one per-split reader's ingest-plane activity (ISSUE 14)
        into the worker registry: the ``ingest_fetch``/``ingest_wait``
        histograms reach the fleet ``stages`` rollup, the counters feed
        the ``fetch-bound`` health regime's degrade ratio."""
        plane = getattr(reader, 'ingest_plane', None)
        if plane is None:
            return
        for name, value in plane.stats.items():
            if name in ('ingest_fetches', 'ingest_fetch_bytes',
                        'ingest_gets', 'ingest_degraded', 'ingest_hedges',
                        'ingest_hedge_wins'):
                self.metrics.counter(name).inc(int(value))
        self.metrics.merge(
            {'histograms': {name: hist for name, hist
                            in plane.metrics.snapshot()['histograms'].items()
                            if name.startswith('ingest_')}})

    def _cluster_chunks(self, split, fetcher):
        """Try the cluster cache tier for a leased split: peer-fill any
        local misses the lease's holder hints cover, then look the whole
        split up in the local plane.  Returns ``(chunks, fetcher)`` —
        ``chunks`` is None when the split (still) cannot be served
        cache-only, in which case NOTHING has been emitted and the
        caller falls through to the reader path (which itself benefits
        from whatever peer fill just published).  Never raises: every
        failure here is a degrade back to decode."""
        from petastorm_tpu.service import cluster
        state = self._cluster
        if state is None or not state.ready():
            return None, fetcher
        identity = state.identity
        try:
            indices = split['indices']
            missing = identity.missing_digests(indices)
            holders = split.get('holders') or {}
            filled = []
            for digest in missing:
                addrs = holders.get(cluster.cdigest(digest)) or ()
                if not addrs:
                    continue  # nobody holds it: plain cold decode, no
                    # counter — degrade counts FAILED fetches only
                if fetcher is None:
                    fetcher = cluster.PeerFetcher(self._zmq_context)
                blob = None
                # Every advertised holder is tried back to back (a
                # delay earned by holder A buys nothing against holder
                # B, and this runs on the decode thread); the unified
                # retry telemetry (ISSUE 15) counts the extra attempts
                # and an all-holders-failed walk as one giveup.
                for i, addr in enumerate(addrs):
                    if i:
                        self._m_retry['retry_attempts'].inc()
                    blob = fetcher.fetch(addr, digest)
                    if blob is not None:
                        break
                if blob is None:
                    self._m_retry['retry_giveups'].inc()
                if blob is not None \
                        and identity.plane.publish_blob(digest, blob):
                    self._m_cluster['cache_peer_fills'].inc()
                    filled.append(digest)
                else:
                    self._m_cluster['cache_peer_degraded'].inc()
            if filled:
                state.note_published(filled)
            chunks = identity.serve_chunks(indices)
            if chunks is not None:
                self._m_cluster['cache_remote_hits'].inc(
                    len(identity.split_digests(indices)))
            return chunks, fetcher
        except Exception:  # noqa: BLE001 — cluster tier degrades, never blocks
            logger.warning('cluster cache: serving split %s degraded to '
                           'direct decode', split.get('split_id'),
                           exc_info=True)
            return None, fetcher

    def _decode_loop(self, job, decode_in, decode_out):
        ship_spans = bool(job.get('telemetry_spans', True))
        try:
            self._decode_loop_inner(job, decode_in, decode_out, ship_spans)
        finally:
            # Peer-fetch sockets die with their owning thread, BEFORE
            # run()'s context.term() (which would otherwise block on
            # them forever).
            fetcher, self._fetcher = self._fetcher, None
            if fetcher is not None:
                fetcher.close()

    _fetcher = None

    def _serve_cached_split(self, split, chunks, decode_out, ship_spans,
                            t0, cache_outcome='remote_hit'):
        """Stream an entirely-cached split through the normal chunk
        protocol (same serialization, shm fallback matrix, credits, end
        marker, ack/complete flow — only the decode is gone)."""
        seq = 0
        rows = 0
        spans = []
        tags = set()
        for chunk in chunks:
            cid = '%d/%d' % (split['split_id'], seq)
            tag, payload = self._serialize_split_chunk(split, chunk, cid,
                                                       spans)
            tags.add(tag)
            rows += len(next(iter(chunk.values())))
            decode_out.put(('chunk', split, seq, tag, payload))
            seq += 1
        t1 = time.monotonic()
        self._m_serve_hist.observe(t1 - t0)
        record = None
        if provenance.enabled():
            record = self._split_record(split, {'serve_cached': [t0, t1]},
                                        spans, tags, cache_outcome)
        spans.append({'name': 'service/serve_cached_split', 't0': t0,
                      't1': t1, 'pid': os.getpid(),
                      'tid': threading.get_ident(),
                      'cid': str(split['split_id']),
                      'args': {'rows': rows}})
        if not ship_spans:
            spans = []
        decode_out.put(('end', split, seq, rows,
                        spans[-_MAX_SPANS_PER_SPLIT:], record))
        self._m_rows.inc(rows)
        self._m_splits.inc()
        if self._trace is not None:
            self._trace.event('service/serve_cached_split', t0, t1,
                              split=split['split_id'], rows=rows)

    def _decode_loop_inner(self, job, decode_in, decode_out, ship_spans):
        while True:
            split = decode_in.get()
            if split is None:
                return
            t0 = time.monotonic()
            spans = []
            try:
                # Chaos seam (ISSUE 15): per-split decode latency spikes
                # and injected decode failures (the lease-expiry path).
                chaos.inject('worker.decode', split=split['split_id'])
                prov_on = provenance.enabled()
                peer_fills_before = (
                    int(self._m_cluster['cache_peer_fills'].value)
                    if prov_on else 0)
                tenant = self._split_tenant(split)
                tjob = self._job_for(split)
                # Cluster cache tier (ISSUE 10): a split the local plane
                # fully holds (natively or after peer fill) streams
                # without constructing a reader — no Parquet open, no
                # decode, no per-split pool spin-up.  The tier's
                # identity is built over the REGISTRATION job's dataset,
                # so a co-tenant rides it exactly when its job reads the
                # same dataset (the fleet-compounding case: its splits
                # serve warm from entries the first tenant decoded).
                chunks = None
                if tjob.get('dataset_url') == job.get('dataset_url'):
                    chunks, self._fetcher = self._cluster_chunks(
                        split, self._fetcher)
                if chunks is not None:
                    outcome = 'remote_hit'
                    if prov_on and int(self._m_cluster[
                            'cache_peer_fills'].value) > peer_fills_before:
                        outcome = 'peer_fill'
                    self._serve_cached_split(split, chunks, decode_out,
                                             ship_spans, t0, outcome)
                    continue
                factory = self._reader_factories.get(tenant)
                if factory is None:
                    factory = self._resolve_factory(tjob)
                    self._reader_factories[tenant] = factory
                reader = factory(
                    tjob['dataset_url'], piece_indices=split['indices'],
                    num_epochs=1, shuffle_row_groups=False,
                    **self._reader_kwargs(tjob))
                seq = 0
                rows = 0
                out_bytes = 0
                tags = set()
                with reader:
                    for item in reader:
                        chunk = (item._asdict() if hasattr(item, '_asdict')
                                 else dict(item))
                        cid = '%d/%d' % (split['split_id'], seq)
                        tag, payload = self._serialize_split_chunk(
                            split, chunk, cid, spans)
                        tags.add(tag)
                        rows += len(next(iter(chunk.values())))
                        out_bytes += len(payload)
                        decode_out.put(('chunk', split, seq, tag, payload))
                        seq += 1
                t1 = time.monotonic()
                self._m_decode_hist.observe(t1 - t0)
                # Per-tenant cache-plane budget (ISSUE 16): the split's
                # serialized bytes approximate what its reader filled
                # into the plane; the charge that crosses the budget
                # turns the tenant's FUTURE readers plane-less (the
                # files already on disk stay — they are the plane's to
                # evict).
                if tjob.get('cache_plane') \
                        and tenant not in self._cache_over_budget \
                        and self._cache_quota.budget(tenant) is not None \
                        and not self._cache_quota.charge(tenant,
                                                         out_bytes):
                    self._cache_over_budget.add(tenant)
                    logger.warning(
                        'tenant %r cache-plane budget exhausted; its '
                        'readers degrade to direct decode', tenant)
                spans.append({'name': 'service/decode_split', 't0': t0,
                              't1': t1, 'pid': os.getpid(),
                              'tid': threading.get_ident(),
                              'cid': str(split['split_id']),
                              'args': {'rows': rows}})
                # Cache-plane fills land in the PLANE's own span buffer,
                # and the plane instance is per-split — draining it here
                # claims exactly this split's fills, even with several
                # in-process workers sharing the process (the global
                # singleton would race them).
                plane_spans = getattr(
                    getattr(reader, '_cache', None), 'spans', None)
                if plane_spans is not None:
                    spans.extend(plane_spans.drain())
                # Ingest-plane fetch/hedge spans (ISSUE 14) ride the same
                # split 'end' header — the per-split plane's buffer is
                # this split's fetch activity, exactly.
                ingest_spans = getattr(
                    getattr(reader, 'ingest_plane', None), 'spans', None)
                if ingest_spans is not None:
                    spans.extend(ingest_spans.drain())
                record = None
                if prov_on:
                    # The plane instance is per-split, so its lifetime
                    # totals ARE this split's cache outcome.
                    cache_stats = getattr(
                        getattr(reader, '_cache', None), 'stats', None)
                    record = self._split_record(
                        split, {'decode': [t0, t1]}, spans, tags,
                        provenance.cache_outcome(_ZERO_CACHE, cache_stats),
                        worker_args=getattr(reader, '_worker_args', None),
                        sched={'policy': getattr(reader, 'scheduling',
                                                 None)})
                if not ship_spans:
                    spans = []
                decode_out.put(('end', split, seq, rows,
                                spans[-_MAX_SPANS_PER_SPLIT:], record))
                self._accumulate_cache_stats(reader)
                self._accumulate_ingest_stats(reader)
                if self._cluster is not None and self._cluster.ready() \
                        and tjob.get('dataset_url') == job.get(
                            'dataset_url'):
                    # The per-split reader's plane just published this
                    # split's entries: advertise them on the next beat
                    # without waiting for the listdir refresh.
                    self._cluster.note_published(
                        self._cluster.identity.split_digests(
                            split['indices']))
                self._m_rows.inc(rows)
                self._m_splits.inc()
                if self._trace is not None:
                    self._trace.event('service/decode_split', t0, t1,
                                      split=split['split_id'], rows=rows)
            except Exception:  # noqa: BLE001 — shipped to the event loop
                decode_out.put(('error', split, traceback.format_exc()))

    # -- metrics -------------------------------------------------------------

    @property
    def diagnostics(self):
        """Per-worker metrics — a view over ``self.metrics`` (ISSUE 5),
        also shipped to the dispatcher on every heartbeat (``stats`` RPC
        surfaces them fleet-wide)."""
        elapsed = (time.monotonic() - self._t_start) if self._t_start else 0.0
        rows = int(self._m_rows.value)
        return {
            'rows_decoded': rows,
            'splits_decoded': int(self._m_splits.value),
            'rows_per_s': round(rows / elapsed, 1) if elapsed > 0 else 0.0,
            'queue_depth': (self._decode_out.qsize()
                            if self._decode_out is not None else 0),
            # shm result-plane traffic INCLUDING the degrades: a worker
            # silently on the byte path (arena full, /dev/shm gone) must
            # be visible fleet-wide, not only in its own process.  The
            # arena shares this registry, so its refusals land here.
            'shm_chunks': int(self._m_shm_chunks.value),
            'shm_degraded': int(self.metrics.counter('shm_degraded').value),
            # Epoch-cache plane traffic of this worker's split readers
            # (all zero unless the job enables cache_plane).
            # cache_degraded matters most fleet-wide: it is the only
            # signal that a plane is silently OFF (unwritable dir, full
            # tiers) while hits/misses still look plausible.
            'cache_hits': int(self._m_cache['cache_hits'].value),
            'cache_misses': int(self._m_cache['cache_misses'].value),
            'cache_evictions': int(self._m_cache['cache_evictions'].value),
            'cache_ram_hits': int(self._m_cache['cache_ram_hits'].value),
            'cache_degraded': int(self._m_cache['cache_degraded'].value),
            # Cluster cache tier (ISSUE 10): served-from-plane pieces,
            # peer fetches that replaced a decode, and peer fetches that
            # failed back to direct decode.  peer_degraded is the fleet
            # signal that entries exist somewhere but cannot flow.
            'cache_remote_hits':
                int(self._m_cluster['cache_remote_hits'].value),
            'cache_peer_fills':
                int(self._m_cluster['cache_peer_fills'].value),
            'cache_peer_degraded':
                int(self._m_cluster['cache_peer_degraded'].value),
            # Unified backoff telemetry (ISSUE 15): summed fleet-wide in
            # the dispatcher's control_plane rollup — climbing giveups
            # fleet-wide is the retry-storm / dead-control-plane signal.
            'retry_attempts': int(self._m_retry['retry_attempts'].value),
            'retry_giveups': int(self._m_retry['retry_giveups'].value),
            # Per-tenant quota enforcement (ISSUE 16): chunks pushed to
            # the byte path by an shm budget and readers built without
            # the cache plane by a cache budget — degrades, not stalls,
            # so only these counters make them visible fleet-wide.
            'shm_quota_degraded':
                int(self._m_quota['shm_quota_degraded'].value),
            'cache_quota_degraded':
                int(self._m_quota['cache_quota_degraded'].value),
            'draining': bool(self._drain.is_set()),
        }

    def heartbeat_stats(self):
        """The heartbeat payload: ``diagnostics`` plus the telemetry
        piggyback — the full registry snapshot (stage histograms merge
        fleet-wide by addition in the dispatcher), the EWMA clock offset
        for span alignment with its drift-vs-registration estimate,
        this process's decision-journal payload (ISSUE 20 — worker-side
        quota/hedge/autotuner/residency decisions reach the dispatcher
        rollup on the channel that already exists), and the pid for
        timeline labels."""
        from petastorm_tpu.telemetry import decisions as _decisions
        return dict(self.diagnostics,
                    registry=self.metrics.snapshot(),
                    clock_offset=self.clock_offset,
                    clock_drift_ms=self.clock_drift_ms,
                    decisions=_decisions.heartbeat_payload(),
                    pid=os.getpid())
