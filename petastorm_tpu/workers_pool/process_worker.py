"""Child-process main loop for :class:`ProcessPool`.

Connects back to the parent's ZeroMQ sockets, receives pickled work items,
publishes serialized results, and acks each item so the parent's ventilator
can refill.  Message framing (multipart):

  work (parent->worker):  [pickle((position, args, kwargs))] | [b'', b'STOP']
  sink (worker->parent):  [tag, payload]
      tag b'R'  pickle-serialized result
      tag b'A'  arrow-IPC-serialized pyarrow.Table result
      tag b'P'  shm descriptor for a protocol-5 pickled result (raw array
                buffers live in a /dev/shm segment; see
                ``workers_pool/shm_plane.py``)
      tag b'T'  shm descriptor for an arrow-IPC-written pyarrow.Table
      tag b'K'  ack: pickle((position or None, busy_seconds, worker_id,
                registry_snapshot, spans)) — busy is the worker.process
                wall time net of retry-backoff sleeps, feeding the parent
                pool's decode_utilization; the trailing telemetry fields
                (ISSUE 5) are the child's full MetricsRegistry snapshot
                (parent REPLACES its per-child slot, so re-sends never
                double-count; the cache plane's histograms are folded
                in) and the drained spans (pool/process, pool/publish
                from the child buffer; cache/fill from the plane's own
                buffer), correlation-id'd by the ventilator position
      tag b'E'  error: pickle((exception, traceback_str))

The shm tags are best-effort per message: a small result, a full arena
(parent consuming slowly), or an unavailable ``/dev/shm`` degrade that
message to the matching byte tag — the parent speaks all four framings
at all times.

Result messages optionally grow trailing frames: a pickled POSITION
frame (reorder delivery, ISSUE 9 — also present whenever provenance is
on, so the parent can pair records with results) and a pickled
PROVENANCE RECORD frame (ISSUE 13: pieces, worker pid/host, cache
outcome, transport, decode/ipc stage windows).  Both are killed by
``PETASTORM_TPU_NO_PROVENANCE=1`` / reorder-off respectively; payload
frames are byte-identical either way.
"""

import os
import pickle
import traceback


def worker_main(setup_payload, worker_id):
    import pyarrow as pa
    import zmq

    from petastorm_tpu import telemetry
    from petastorm_tpu.reader_impl.arrow_table_serializer import ArrowTableSerializer
    from petastorm_tpu.reader_impl.pickle_serializer import PickleSerializer
    from petastorm_tpu.workers_pool import shm_plane

    payload = pickle.loads(setup_payload)
    worker_class, worker_args, work_addr, sink_addr, copy_buffers, \
        use_shm, shm_capacity, parent_pid = payload[:8]
    # Positioned result framing (ISSUE 9 reorder stage): every result
    # message grows a trailing pickled-position frame so the parent can
    # restore epoch-order delivery.  Old-style 8-tuple payloads (none in
    # tree, but the framing is feature-flagged either way) default off.
    reorder = payload[8] if len(payload) > 8 else False

    # Child-side telemetry (ISSUE 5): one registry + the process-local
    # span buffer (shared with the cache plane's fill spans); both ride
    # every b'K' ack back to the parent pool.
    metrics = telemetry.MetricsRegistry('pool_worker')
    decode_hist = metrics.histogram('decode')
    spans = telemetry.current_buffer()
    # Always-on flight recorder (ISSUE 7): a child killed mid-epoch
    # leaves its last periodic frame dump behind when a flight dir is
    # configured; costs nothing on the ack path (2 s daemon tick).
    telemetry.flight.enable(label='pool_worker')
    current_position = [None]
    # Per-batch provenance (ISSUE 13): each result message grows a
    # position frame + a compact record frame when enabled — the kill
    # switch (PETASTORM_TPU_NO_PROVENANCE=1) keeps the legacy framing
    # and delivery bit-identical.
    prov_on = telemetry.provenance.enabled()
    current_started = [None]
    current_args = [None]
    current_publish_t = [None]
    cache_before = [None]

    context = zmq.Context()
    work_socket = context.socket(zmq.PULL)
    work_socket.connect(work_addr)
    sink_socket = context.socket(zmq.PUSH)
    sink_socket.connect(sink_addr)

    pickle_ser = PickleSerializer()
    arrow_ser = ArrowTableSerializer()
    # stale_after_s=None: the parent is the single consumer and drains at
    # user-code pace (it may sit on queued descriptors for minutes); the
    # pool has no resend path, so retiring an unread slab would lose rows.
    arena = (shm_plane.ShmArena(capacity_bytes=shm_capacity,
                                stale_after_s=None, metrics=metrics)
             if use_shm and shm_plane.available() else None)

    def publish(result):
        t_pub = time.monotonic()
        current_publish_t[0] = t_pub
        try:
            _publish(result)
        finally:
            spans.span('pool/publish', t_pub, time.monotonic(),
                       cid=current_position[0])

    def _cache_stats():
        # the reader workers hang their WorkerArgs dataclass on `_a`
        return telemetry.provenance.cache_stats(getattr(worker, '_a', None))

    def _send(frames, transport=None, **kwargs):
        # Positioned framing: reorder mode needs the position to restore
        # epoch order; provenance (ISSUE 13) needs it to pair the record
        # with its result at the parent — either one appends the frame.
        if reorder or prov_on:
            frames = frames + [pickle.dumps(current_position[0], protocol=4)]
        if prov_on:
            prov = telemetry.provenance
            now = time.monotonic()
            t_pub = current_publish_t[0] or now
            stages = {'ipc': [t_pub, now]}
            if current_started[0] is not None:
                stages['decode'] = [current_started[0], t_pub]
            record = prov.make_record(
                'pool', position=current_position[0],
                worker_pid=os.getpid(), worker_host=prov.host(),
                pieces=prov.piece_info(getattr(worker, '_a', None),
                                       current_args[0]),
                cache=prov.cache_outcome(cache_before[0], _cache_stats()),
                transport=transport, stages=stages)
            record['_staged_t'] = now
            frames = frames + [pickle.dumps(record, protocol=4)]
        sink_socket.send_multipart(frames, **kwargs)

    def _publish(result):
        if isinstance(result, pa.Table):
            if arena is not None:
                desc = shm_plane.write_table(arena, result, arrow_ser)
                if desc is not None:
                    _send([b'T', pickle.dumps(desc, protocol=4)],
                          transport='shm')
                    return
            _send([b'A', arrow_ser.serialize(result)], transport='bytes',
                  copy=copy_buffers)
        else:
            if arena is not None:
                desc = shm_plane.write_pickled(arena, result, pickle_ser)
                if desc is not None:
                    _send([b'P', pickle.dumps(desc, protocol=4)],
                          transport='shm')
                    return
            _send([b'R', pickle_ser.serialize(result)], transport='bytes',
                  copy=copy_buffers)

    import time

    worker = worker_class(worker_id, publish, worker_args)
    worker.metrics = metrics   # what the worker times rides every ack too
    # The reader workers carry their cache in the setup-args dataclass
    # (`worker._a.cache`); when it is a PlaneCache, its fill telemetry
    # lives on per-instance surfaces (plane registry + plane span
    # buffer) that THIS channel must ship — nothing else ever drains
    # them in a child process.  Duck-typed: NullCache/local-disk have
    # neither attribute.
    cache = getattr(getattr(worker, '_a', None), 'cache', None)
    cache_metrics = getattr(cache, 'metrics', None)
    cache_spans = getattr(cache, 'spans', None)

    def ack_snapshot():
        """Full-state composite snapshot: the child registry plus the
        cache plane's histograms (both cumulative — the parent REPLACES
        its per-child slot, so full state never double-counts)."""
        snap = metrics.snapshot()
        if cache_metrics is not None:
            snap['histograms'].update(
                cache_metrics.snapshot()['histograms'])
        return snap
    # A SIGKILLed parent can never send STOP: without a bounded wait the
    # child parks in recv forever — an orphan pinning its /dev/shm arena
    # and a CPU slot (lint unbounded-recv).  Poll with a timeout and exit
    # when the parent is gone: getppid() stops matching the pool pid the
    # parent embedded in the payload (reparenting to init/a reaper), a
    # check that works even when the parent died before this point.
    poller = zmq.Poller()
    poller.register(work_socket, zmq.POLLIN)
    try:
        while True:
            if not dict(poller.poll(2000)):
                if os.getppid() != parent_pid:
                    break  # orphaned: clean up as if STOP had arrived
                continue
            frames = work_socket.recv_multipart()
            if frames[-1] == b'STOP':
                break
            position, args, kwargs = pickle.loads(frames[0])
            current_position[0] = position
            started = time.monotonic()
            if prov_on:
                current_started[0] = started
                current_args[0] = args
                current_publish_t[0] = None
                cache_before[0] = _cache_stats()
            sleep_before = getattr(worker, 'retry_sleep_s', 0.0)
            try:
                worker.process(*args, **kwargs)
            except Exception as e:  # noqa: BLE001 — shipped to the parent
                sink_socket.send_multipart(
                    [b'E', pickle.dumps((e, traceback.format_exc()))])
            finally:
                # Ack carries this item's decode time (minus retry-backoff
                # sleeps) so the parent pool can report decode_utilization
                # like the in-process pools do — plus the telemetry
                # piggyback: registry snapshot + drained spans (ISSUE 5).
                slept = getattr(worker, 'retry_sleep_s', 0.0) - sleep_before
                busy = max(0.0, time.monotonic() - started - slept)
                decode_hist.observe(busy)
                spans.span('pool/process', started, time.monotonic(),
                           cid=position)
                item_spans = spans.drain()
                if cache_spans is not None:
                    item_spans.extend(cache_spans.drain())
                sink_socket.send_multipart(
                    [b'K', pickle.dumps((position, busy, worker_id,
                                         ack_snapshot(), item_spans))])
    finally:
        worker.shutdown()
        if arena is not None:
            # Unlink every slab: a clean shutdown must leave zero /dev/shm
            # residue (the parent's mappings keep any pages it still
            # reads; in-flight results are dropped with the sockets
            # either way).
            arena.stop()
        work_socket.close(0)
        sink_socket.close(0)
        context.term()
