"""``petastorm-tpu-doctor`` — one-command pipeline diagnostics.

The reference leaves operators to correlate logs by hand when a training
job starves; this framework already measures every plane separately
(backend probe, native decode plane, host delivery, H2D transport, the
bottleneck advisor).  The doctor runs them in dependency order and emits
one report, so "why is my chip idle" is a single command on any host:

    petastorm-tpu-doctor                         # environment planes only
    petastorm-tpu-doctor --dataset-url file:///data/imagenet --json

Sections (each contained — a dead plane is reported, not fatal):

* **backend** — does the configured JAX backend initialize in this
  process (``utils.ensure_jax_backend``)?  Platform, device kind and
  count when it does; the error it raised when it does not.
* **native** — is the C++ decode plane (``native/pt_decode.cc``) loaded,
  and what does it accelerate?
* **host_plane** — with ``--dataset-url``: images(rows)/s of the pure
  host pipeline (reader -> decode -> collate, no device), the number the
  chip's feed rate is bounded by.
* **h2d** — device_put bandwidth of one training-shaped batch (needs a
  live backend): the transport term of streaming stall.
* **advisor** — with both planes measured: the bottleneck verdict +
  prescriptions (``benchmark.diagnose``) for a short stall-free pass.
* **cache_plane** — the tiered epoch-cache plane's environment: tier
  directories writable (``--cache-plane-dir``), ``/dev/shm`` headroom
  for the hot tier and the shm result plane, and a crash-residue sweep
  report (orphaned result-plane slabs, dead writers' tmp files).
* **cluster_cache** — the cluster cache tier's environment (ISSUE 10):
  kill-switch state, a real loopback peer-fetch round-trip on a
  synthetic entry (same ``fetch_reply``/``PeerFetcher`` pair the
  workers run, byte equality asserted), and — with ``--dispatcher`` —
  the live fleet's cache-directory footprint from one ``stats`` RPC.
* **telemetry** — the cross-process observability plane (ISSUE 5):
  registry round-trip + Prometheus rendering, a real 2-process
  ``time.monotonic()`` clock-offset handshake (span alignment sanity),
  and a span-buffer residue report (spans recorded but not drained by
  an ack/heartbeat channel).
* **autoscaler** — the closed-loop fleet autoscaler (ISSUE 16):
  kill-switch state, and a fake-launcher control-law round-trip with an
  injected clock — sustained starvation must scale out, the cooldown
  must suppress the immediate follow-up, sustained idleness must name a
  least-coverage drain victim — plus a damping-config sanity check
  (min <= max, positive step/cooldown).
* **ingest** — the async byte-range ingest plane (ISSUE 14):
  kill-switch state, a coalescing-plan sanity check against a real
  synthetic Parquet footer (ranges sorted, in-bounds, column subsets
  shrink the fetch), a loopback range-fetch round-trip through the same
  ``IngestPlane`` the readers mount (table equality asserted against a
  direct pyarrow read), and the hedge-deadline state.
* **residency** — the device-resident data plane (ISSUE 17, needs a
  live backend): kill-switch state, whether buffer donation actually
  recycles HBM here (it is a copy on CPU), the compressed-in-HBM
  budget estimate on a training-shaped probe batch (narrowed bytes/row
  must shrink), and a widen round-trip through a real tier admit +
  gather (uint8 exact, bf16 error bounded).
"""

import argparse
import json
import os
import sys
import time

import numpy as np

__all__ = ['run_doctor', 'main']


def _contained(report, name, fn):
    t0 = time.monotonic()
    try:
        report[name] = fn()
    except Exception as e:  # noqa: BLE001 — a dead plane is a FINDING
        report[name] = {'error': '%s: %s' % (type(e).__name__, str(e)[:200])}
    report[name]['elapsed_s'] = round(time.monotonic() - t0, 2)


def _check_backend():
    from petastorm_tpu.utils import ensure_jax_backend
    devices = ensure_jax_backend()   # raises -> reported as this section's error
    return {'backend': devices[0].platform,
            'device_kind': devices[0].device_kind,
            'device_count': len(devices)}


def _check_native():
    from petastorm_tpu import native
    lib = native.get_lib()
    out = {'loaded': lib is not None}
    if lib is not None:
        out['accelerates'] = ['jpeg_decode_batch (fused resize)',
                              'png_decode_batch',
                              'zlib_npy_decompress_batch',
                              'npy_copy_batch']
    else:
        out['note'] = ('C++ plane unavailable (no compiler or build '
                       'failure); python/cv2 fallbacks active — expect a '
                       'slower delivery plane')
    return out


def _check_host_plane(dataset_url, seconds, batch_size, advisor_out=None):
    """Rows/s of reader -> decode -> collate with NO device in the loop.

    The same pass feeds the bottleneck advisor (``advisor_out`` receives
    its verdict): one dataset open, one decode window, two sections —
    remote URLs must not pay the read twice.  ``num_epochs=None`` so a
    dataset smaller than one batch still produces full (wrapping)
    batches; the deadline bounds the pass either way.
    """
    from petastorm_tpu.benchmark import diagnose
    from petastorm_tpu.benchmark.hostplane import (open_host_reader,
                                                   pump_host_batches)
    from petastorm_tpu.jax import DataLoader

    reader, info = open_host_reader(dataset_url, num_epochs=None,
                                    shuffle_row_groups=False)
    kind = info['kind']
    with reader:
        loader = DataLoader(reader, batch_size=batch_size)
        # warmup_batches=1 matches benchmark.autotune, so a doctor report's
        # host-plane rows/s and an autotune sweep's are comparable (pool
        # spin-up + first row-group read excluded from both).
        rows, dt = pump_host_batches(loader, seconds, warmup_batches=1)
        stats = dict(loader.stats)
        # Scheduling surface (ISSUE 9): the effective dispatch policy
        # after 'auto' resolution, plus the measured per-item decode
        # skew — p99/p50 >= 8x with idle workers is the skew-bound
        # regime scheduling='adaptive' exists for (see diagnose).
        diag = dict(getattr(reader, 'diagnostics', None) or {})
        sched = diag.get('scheduling')
        p50, p99 = diag.get('decode_p50_ms'), diag.get('decode_p99_ms')
        if advisor_out is not None:
            verdict = diagnose(loader)
            advisor_out.update({
                'regime': verdict['regime'],
                'evidence': verdict['evidence'],
                'suggestions': verdict.get('suggestions', []),
                'note': 'host-boundary pass (no chip in the loop); '
                        'chip-side regimes need a training loop — see '
                        'examples/imagenet',
            })
    out = {'reader': kind, 'rows_per_s': round(rows / dt, 1), 'rows': rows,
           'scheduling': sched,
           'decode_skew_p99_over_p50': (round(p99 / p50, 1)
                                        if p50 and p99 else None),
           'stage_seconds': {k: round(v, 3) for k, v in stats.items()
                             if k.endswith('_s')},
           # rows_per_s is measured AFTER the one-batch warmup;
           # stage_seconds accumulates over the whole loader lifetime
           # (warmup included) — don't cross-divide the two windows.
           'stage_seconds_window': 'loader lifetime incl. warmup batch '
                                   '(rows_per_s window excludes it)'}
    return out


def _check_h2d(batch_mb):
    import jax
    x = np.zeros((int(batch_mb) << 20,), np.uint8)
    jax.block_until_ready(jax.device_put(x))  # warm the path
    t0 = time.monotonic()
    jax.block_until_ready(jax.device_put(x))
    dt = time.monotonic() - t0
    out = {'bytes_per_s': round(x.nbytes / dt) if dt > 0 else None,
           'mb': int(batch_mb),
           'note': 'streaming feed rate is bounded by '
                   'min(host_plane.rows_per_s, h2d/bytes_per_row)'}
    out['transfer_plane'] = _probe_transfer_plane(x)
    return out


def _probe_transfer_plane(raw):
    """Transfer-plane environment (ISSUE 6): can the ring + staging slab
    be allocated and cycled, does the narrowing policy round-trip uint8
    and bfloat16 bit-exact, and what bandwidth does the coalesced path
    measure next to the raw ``device_put`` number above."""
    import os

    import jax
    import jax.numpy as jnp

    from petastorm_tpu.jax import transfer

    out = {'kill_switch': bool(os.environ.get(transfer.KILL_SWITCH))}
    plane = transfer.TransferPlane(ring_slots=2)
    # A training-shaped two-column probe: ring allocation, slab pack,
    # on-device unpack, and a second lap (slot reuse) all exercised.
    probe = {'image': np.arange(4096, dtype=np.uint8).reshape(16, 256),
             'vec': np.linspace(0.0, 1.0, 64, dtype=np.float32)
                      .reshape(16, 4)}
    devs = [plane.put(probe), plane.put(probe), plane.put(probe)]
    ok = all(d is not None for d in devs) and all(
        np.array_equal(np.asarray(d[k]), probe[k])
        for d in devs for k in probe)
    out['ring_ok'] = bool(ok)
    out['staging_slab_ok'] = bool(devs[0] is not None)
    # Narrowing round-trip exactness: uint8 must pass through untouched,
    # and a bfloat16 source is already wire-width (bf16 → bf16 → bf16).
    narrow = transfer.TransferPlane(ring_slots=2, wire_dtypes='auto')
    nprobe = {'image': probe['image'],
              'bf': np.arange(32, dtype=np.float32).astype(jnp.bfloat16)
                      .reshape(16, 2)}
    dev = narrow.put(nprobe)
    out['narrow_roundtrip_exact'] = bool(
        dev is not None
        and np.array_equal(np.asarray(dev['image']), nprobe['image'])
        and np.asarray(dev['bf']).dtype == np.dtype(jnp.bfloat16)
        and np.array_equal(np.asarray(dev['bf']), np.asarray(nprobe['bf'])))
    # Coalesced-path bandwidth over the same byte volume as the raw
    # number: two leaves so coalescing applies, one warm lap first.  A
    # degraded put (slab over the staging cap — oversized --h2d-mb or a
    # lowered PETASTORM_TPU_TRANSFER_MAX_STAGING_MB) must report AS
    # degraded, not fabricate a bandwidth from a no-op timing.
    half = raw.reshape(2, -1)
    big = {'a': half[0], 'b': half[1]}
    warm = plane.put(big)
    if warm is None:
        out['plane_bytes_per_s'] = None
        out['plane_bandwidth_note'] = (
            'probe degraded (staging slab over the cap for this probe '
            'size) — raise PETASTORM_TPU_TRANSFER_MAX_STAGING_MB or '
            'lower the probe size')
    else:
        # Warm BOTH ring slots: the small probes above left the other
        # slot holding a tiny slab, and a timed put landing there would
        # pay a fresh allocation + first-touch faults (~20x the memcpy
        # on virtualized kernels) inside the window, understating the
        # plane next to the raw number above.
        jax.block_until_ready(warm)
        jax.block_until_ready(plane.put(big))
        t0 = time.monotonic()
        jax.block_until_ready(plane.put(big))
        dt = time.monotonic() - t0
        out['plane_bytes_per_s'] = round(raw.nbytes / dt) if dt > 0 else None
    plane.close()
    narrow.close()
    return out


def _check_cache_plane(plane_dir):
    """Environment of the tiered epoch-cache plane (``cache_plane/``):
    can the tiers actually be written, is there ``/dev/shm`` headroom
    for the hot tier, and what crash residue did the sweep reclaim.
    Runs without ``--cache-plane-dir`` too — the headroom and orphan
    sweep describe the host, not one plane."""
    import os

    from petastorm_tpu.cache_plane import sweep_residue
    from petastorm_tpu.cache_plane.plane import default_ram_dir
    from petastorm_tpu.workers_pool import shm_plane

    out = {}
    if shm_plane.available():
        st = os.statvfs(shm_plane.SHM_DIR)
        free = st.f_bavail * st.f_frsize
        out['shm_free_bytes'] = free
        out['shm_headroom_ok'] = bool(free >= 128 << 20)
        if not out['shm_headroom_ok']:
            out['shm_note'] = ('< 128 MiB free in /dev/shm: the hot tier '
                               'and the shm result plane will degrade; '
                               'sweep or shrink ram_bytes')
    else:
        out['shm_note'] = ('/dev/shm unusable or PETASTORM_TPU_NO_SHM=1: '
                           'plane runs disk-only')
    if plane_dir:
        tiers = {'disk_tier': plane_dir, 'ram_tier': default_ram_dir(plane_dir)}
        for label, root in tiers.items():
            try:
                os.makedirs(root, exist_ok=True)
                probe = os.path.join(root, '.doctor-probe')
                with open(probe, 'w'):
                    pass
                os.unlink(probe)
                writable = True
            except OSError as e:
                writable = False
                out[label + '_error'] = str(e)
            out[label] = root
            out[label + '_writable'] = writable
        try:
            out['disk_tier_entries'] = len(
                [f for f in os.listdir(plane_dir) if f.endswith('.cpe')])
        except OSError:
            # The unwritable/uncreatable dir IS the finding — the probe
            # results above must survive, not be replaced by this error.
            pass
    swept = sweep_residue(plane_dir)
    out['swept_tmp_files'] = len(swept['removed'])
    out['swept_orphan_slabs'] = len(swept['shm_slabs'])
    if swept['removed'] or swept['shm_slabs']:
        out['sweep_note'] = ('reclaimed crash residue: %d tmp file(s), '
                             '%d orphaned shm slab(s)'
                             % (len(swept['removed']),
                                len(swept['shm_slabs'])))
    return out


def _check_cluster_cache(plane_dir, dispatcher_addr=None):
    """Environment of the CLUSTER cache tier (``service/cluster.py``):
    kill-switch state, a real peer-fetch round-trip over a loopback
    ROUTER socket (a synthetic entry published into a throwaway plane,
    served by the same ``fetch_reply`` the worker event loop calls,
    fetched by the same ``PeerFetcher`` workers use — byte equality
    asserted), and — when ``--dispatcher`` names a live fleet — the
    directory's reachability and footprint from its ``stats`` RPC."""
    import os
    import pickle
    import shutil
    import tempfile
    import threading

    import numpy as np
    import zmq

    from petastorm_tpu.cache_plane import CachePlane
    from petastorm_tpu.cache_plane.plane import encode_entry
    from petastorm_tpu.service import cluster

    out = {'kill_switch': cluster.killed()}
    if out['kill_switch']:
        out['note'] = ('PETASTORM_TPU_NO_CLUSTER_CACHE=1: no affinity '
                       'routing, remote HIT serving, or peer fill on '
                       'this host')

    # Peer-fetch round trip on a synthetic entry (loopback).
    root = plane_dir or tempfile.mkdtemp(prefix='pstpu-doctor-cluster-')
    # The throwaway plane dir is OURS to delete; never derive the
    # cleanup path from the plane object (an init-degraded plane has
    # disk=None, and the fallback must not point at the USER'S dir).
    doctor_dir = os.path.join(root, '.doctor-cluster')
    plane = CachePlane(doctor_dir, ram_capacity_bytes=0)
    try:
        blob = bytes(encode_entry({'probe': np.arange(64)}))
        digest = plane.digest('doctor-cluster-probe')
        if not plane.publish_blob(digest, blob):
            out['peer_fetch_ok'] = False
            out['peer_fetch_error'] = 'publish_blob degraded (full/ro dir)'
            return out
        stop = threading.Event()
        context = zmq.Context()
        sock = context.socket(zmq.ROUTER)
        sock.setsockopt(zmq.LINGER, 0)
        port = sock.bind_to_random_port('tcp://127.0.0.1')

        def serve():
            while not stop.is_set():
                if not sock.poll(50):
                    continue
                identity, raw = sock.recv_multipart()
                sock.send_multipart(cluster.fetch_reply(
                    identity, pickle.loads(raw), plane))

        peer = threading.Thread(target=serve, daemon=True)
        peer.start()
        fetcher = cluster.PeerFetcher(context, timeout_s=5.0)
        try:
            fetched = fetcher.fetch('tcp://127.0.0.1:%d' % port, digest)
            out['peer_fetch_ok'] = fetched == blob
            out['peer_fetch_bytes'] = len(blob)
        finally:
            fetcher.close()
            stop.set()
            peer.join(5)
            sock.close(0)
            context.term()
    finally:
        shutil.rmtree(doctor_dir, ignore_errors=True)
        if plane_dir is None:
            shutil.rmtree(root, ignore_errors=True)

    # Live directory reachability (optional).
    if dispatcher_addr:
        from petastorm_tpu.service.worker import _Rpc
        context = zmq.Context()
        rpc = _Rpc(context, dispatcher_addr, timeout_s=10.0)
        try:
            rollup = rpc.call({'op': 'stats'}).get('cluster_cache') or {}
            out['directory_reachable'] = True
            for key in ('directory_workers', 'directory_digests',
                        'piece_map', 'cache_affinity_routed',
                        'cache_remote_hits', 'cache_peer_fills',
                        'cache_peer_degraded'):
                out[key] = rollup.get(key)
        except Exception as e:  # noqa: BLE001 — reported, not raised
            out['directory_reachable'] = False
            out['directory_error'] = '%s: %s' % (type(e).__name__, e)
        finally:
            rpc.close()
            context.term()
    return out


def _check_ingest():
    """Environment of the async byte-range ingest plane (ISSUE 14): can
    a footer be planned into coalesced ranges, does a real loopback
    fetch round-trip through the same ``IngestPlane`` readers mount
    reproduce a direct pyarrow read bit for bit, and how does the hedge
    deadline currently stand."""
    import os
    import shutil
    import tempfile

    import fsspec
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from petastorm_tpu import ingest

    out = {'kill_switch': os.environ.get(ingest.KILL_SWITCH) == '1'}
    if out['kill_switch']:
        out['note'] = ('PETASTORM_TPU_NO_INGEST_PLANE=1: every reader '
                       'reads synchronously on this host')

    root = tempfile.mkdtemp(prefix='pstpu-doctor-ingest-')
    path = os.path.join(root, 'probe.parquet')
    try:
        table = pa.table({
            'idx': pa.array(np.arange(64, dtype=np.int64)),
            'payload': pa.array([np.random.default_rng(i).bytes(2048)
                                 for i in range(64)], type=pa.binary()),
        })
        pq.write_table(table, path, row_group_size=16)

        # Coalescing-plan sanity against the real footer.
        size = os.path.getsize(path)
        with open(path, 'rb') as handle:
            metadata, _, _ = ingest.read_footer(handle, size)
        full = ingest.coalesce(ingest.column_chunk_ranges(metadata, 0, None))
        subset = ingest.coalesce(
            ingest.column_chunk_ranges(metadata, 0, {'idx'}))
        out['plan_ranges_full'] = len(full)
        out['plan_bytes_full'] = sum(n for _, n in full)
        out['plan_bytes_idx_only'] = sum(n for _, n in subset)
        out['plan_ok'] = bool(
            full and subset
            and all(0 <= off and off + n <= size for off, n in full)
            and full == sorted(full)
            and out['plan_bytes_idx_only'] < out['plan_bytes_full'])

        # Loopback round trip through the live plane (no kill-switch
        # bypass: a killed plane is reported above, not probed around).
        class _Piece(object):
            def __init__(self, p, rg):
                self.path, self.row_group = p, rg

        pieces = [_Piece(path, 0), _Piece(path, 1)]
        plane = ingest.IngestPlane(fsspec.filesystem('file'), pieces,
                                   columns=None, fetch_threads=2)
        try:
            for index in range(len(pieces)):
                plane.observe_dispatch((index,))
            fetched = []
            for piece in pieces:
                pf = plane.checkout(piece.path, piece.row_group)
                fetched.append(None if pf is None
                               else pf.read_row_group(piece.row_group))
            direct = pq.ParquetFile(path)
            out['fetch_roundtrip_ok'] = bool(all(
                got is not None and got.equals(direct.read_row_group(i))
                for i, got in enumerate(fetched)))
            out['hedge'] = plane.hedge_state()
            out['degraded'] = plane.stats['ingest_degraded']
            out['plan_waste_pct'] = plane.stats['ingest_plan_waste_pct']
        finally:
            plane.close()
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def _check_materialize():
    """Materialization round trip (ISSUE 18): one real piece through
    materialize -> wire-format publish -> readerless remote-hit serve on
    this host, reporting the achieved skip stages — ``skip_decode`` (the
    serve came straight off the plane, no reader, no Parquet open),
    ``skip_collate`` (the entry is already stacked columns), and
    ``skip_narrow`` (a wire-format sibling exists whose host widen
    matches the jitted contract)."""
    import os
    import shutil
    import tempfile

    import numpy as np

    from petastorm_tpu import materialize as mat
    from petastorm_tpu.cache_plane.plane import MISS
    from petastorm_tpu.codecs import NdarrayCodec, ScalarCodec
    from petastorm_tpu.etl.dataset_metadata import DatasetWriter
    from petastorm_tpu.materialize.controller import wire_digests
    from petastorm_tpu.materialize.transcode import (is_wire_entry,
                                                     widen_entry)
    from petastorm_tpu.unischema import Unischema, UnischemaField

    out = {'kill_switch': mat.killed()}
    if out['kill_switch']:
        out['note'] = ('PETASTORM_TPU_NO_MATERIALIZE=1: warming, wire '
                       'transcode, and layout rewrite all disabled on '
                       'this host')
        return out

    root = tempfile.mkdtemp(prefix='pstpu-doctor-materialize-')
    try:
        schema = Unischema('DoctorMat', [
            UnischemaField('id', np.int64, (), ScalarCodec('int64'), False),
            UnischemaField('vec', np.float32, (16,), NdarrayCodec(), False),
        ])
        url = 'file://' + os.path.join(root, 'ds')
        with DatasetWriter(url, schema, rows_per_rowgroup=4) as writer:
            for i in range(8):
                writer.write({'id': i,
                              'vec': np.full(16, i, dtype=np.float32)})
        controller = mat.MaterializeController(
            url, os.path.join(root, 'plane'),
            ledger_path=os.path.join(root, 'ledger.json'))
        try:
            summary = controller.run()
            out['warmed_pieces'] = summary.get('done', 0)
            out['wire_published'] = summary.get('wire_published', 0)
            out['admission_refused'] = summary.get('admission_refused', 0)
            identity = controller.identity
            # Readerless remote-HIT serve: ALL lookups off the plane.
            chunks = identity.serve_chunks(range(identity.num_pieces))
            served = (sorted(int(v) for chunk in chunks
                             for v in np.atleast_1d(chunk['id']))
                      if chunks is not None else None)
            out['skip_decode'] = served == list(range(8))
            out['skip_collate'] = bool(chunks) and all(
                isinstance(chunk['vec'], np.ndarray)
                and chunk['vec'].ndim == 2 for chunk in chunks)
            wire = identity.plane.lookup_digest(
                wire_digests(identity, 0)[0]) \
                if wire_digests(identity, 0) else MISS
            out['skip_narrow'] = False
            if wire is not MISS and is_wire_entry(wire):
                widened = widen_entry(wire)
                raw = identity.plane.lookup_digest(
                    identity.piece_digests(0)[0])
                out['skip_narrow'] = bool(
                    raw is not MISS and np.array_equal(
                        widened['vec'],
                        raw['vec'].astype(widened['vec'].dtype)))
            out['roundtrip_ok'] = bool(out['skip_decode']
                                       and out['skip_collate']
                                       and out['skip_narrow'])
        finally:
            controller.close()
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return out


def _check_autoscaler():
    """Environment + control-law sanity of the fleet autoscaler
    (``service/autoscaler.py``, ISSUE 16): kill-switch state, then a
    deterministic fake-launcher round-trip with an injected clock —
    sustained lease starvation must produce exactly one scale-out, the
    cooldown must suppress the immediate retry, and sustained idleness
    must name the least-cache-covered worker as the drain victim."""
    from petastorm_tpu.service.autoscaler import Autoscaler, WorkerLauncher
    from petastorm_tpu.service.config import ServiceConfig

    out = {'kill_switch': False}
    from petastorm_tpu.service import autoscaler as _mod
    out['kill_switch'] = _mod.killed()
    if out['kill_switch']:
        out['note'] = ('PETASTORM_TPU_NO_AUTOSCALE=1: controllers '
                       'construct but never act on this host')

    class _FakeLauncher(WorkerLauncher):
        def __init__(self):
            self.spawned, self.drains = [], []

        def spawn(self, addr):
            self.spawned.append(addr)
            return len(self.spawned)

        def notify_drain(self, worker_id):
            self.drains.append(worker_id)

    config = ServiceConfig(dataset_url='file:///dev/null',
                           autoscale=True, autoscale_min_workers=1,
                           autoscale_max_workers=4, autoscale_step=1,
                           autoscale_cooldown_s=5.0,
                           autoscale_starve_s=2.0, autoscale_idle_s=10.0)
    out['damping_config_ok'] = bool(
        config.autoscale_min_workers <= config.autoscale_max_workers
        and config.autoscale_step >= 1
        and config.autoscale_cooldown_s > 0)
    launcher = _FakeLauncher()
    scaler = Autoscaler(config, launcher, now=0.0)
    # An env kill switch makes the round-trip vacuous — report and skip.
    if not scaler.enabled:
        out['control_law_ok'] = None
        return out
    starving = {'pending': 5, 'leased': 2, 'alive': ['w0'],
                'free_slots': 0, 'coverage': {'w0': 3},
                'dispatcher_addr': 'tcp://127.0.0.1:1'}
    first = scaler.maybe_tick(starving, now=0.0)          # starve starts
    sustained = scaler.maybe_tick(starving, now=2.5)      # past starve_s
    scaler.maybe_tick(starving, now=4.0)                  # starve restarts
    cooled = scaler.maybe_tick(starving, now=6.5)         # sustained again,
    #                                                       inside cooldown
    idle = {'pending': 0, 'leased': 0, 'alive': ['w0', 'w1'],
            'free_slots': 6, 'coverage': {'w0': 3, 'w1': 0},
            'dispatcher_addr': 'tcp://127.0.0.1:1'}
    scaler.maybe_tick(idle, now=20.0)                     # idle starts
    drained = scaler.maybe_tick(idle, now=31.0)           # past idle_s
    out['scale_out_fired'] = sustained == ('scale_out', 1)
    out['cooldown_suppressed'] = bool(first is None and cooled is None
                                      and scaler.suppressed >= 1)
    out['drain_victim_least_coverage'] = drained == ('scale_in', 'w1')
    out['control_law_ok'] = bool(out['scale_out_fired']
                                 and out['cooldown_suppressed']
                                 and out['drain_victim_least_coverage']
                                 and launcher.spawned
                                 and launcher.drains == ['w1'])
    return out


def _check_residency():
    """Environment + widen-path sanity of the device-resident data plane
    (``jax/residency.py``, ISSUE 17): kill-switch state, whether buffer
    donation actually recycles HBM on this backend, the budget estimate
    on a training-shaped probe batch (narrowed bytes/row must shrink),
    and the widen round-trip — uint8 exact, bf16 error bounded — through
    a real ``ResidencyTier`` admit + gather."""
    import jax
    import jax.numpy as jnp

    from petastorm_tpu import telemetry
    from petastorm_tpu.jax import residency

    out = {'kill_switch': residency.killed()}
    if out['kill_switch']:
        out['note'] = ('PETASTORM_TPU_NO_RESIDENCY=1: ResidentDataLoader '
                       'streams full-width every epoch on this host')
    out['backend'] = jax.default_backend()
    out['donation_supported'] = residency.donation_supported()

    probe = {'image': (np.arange(8 * 16 * 16 * 3, dtype=np.int64) % 251)
             .astype(np.uint8).reshape(8, 16, 16, 3),
             'feat': np.linspace(-1.0, 1.0, 8 * 32,
                                 dtype=np.float32).reshape(8, 32)}
    est = residency.estimate_budget(probe, 'auto')
    out['wire_bytes_per_row'] = est['wire_bytes_per_row']
    out['logical_bytes_per_row'] = est['logical_bytes_per_row']
    out['hbm_ratio'] = round(est['hbm_ratio'], 2)
    # uint8 rides unchanged and float32 halves to bf16, so the ratio must
    # sit strictly between 1x (nothing narrowed) and 4x (the best case of
    # an all-float32 batch would be 2x; 4x needs future int narrowing).
    out['budget_estimate_ok'] = bool(
        est['narrowed']
        and est['wire_bytes_per_row'] < est['logical_bytes_per_row']
        and 1.0 < est['hbm_ratio'] <= 4.0)

    plan = residency.wire_plan(probe, 'auto')
    counters = residency.ensure_counters(
        telemetry.MetricsRegistry('doctor_residency'))
    tier = residency.ResidencyTier(plan, 8, 4, None, counters)
    wire = plan.narrow(probe)
    for start in (0, 4):
        tier.admit(np.arange(start, start + 4),
                   {k: jax.device_put(v[start:start + 4])
                    for k, v in wire.items()})
    out['tier_fully_resident'] = tier.fully_resident
    order = jnp.arange(8)
    parts = [tier.gather(order, start) for start in (0, 4)]
    got = {k: np.concatenate([np.asarray(p[k]) for p in parts])
           for k in probe}
    out['widen_uint8_exact'] = bool((got['image'] == probe['image']).all())
    err = float(np.max(np.abs(got['feat'] - probe['feat'])))
    out['widen_bf16_max_err'] = round(err, 6)
    # bf16 keeps 8 significand bits: |err| <= 2^-8 relative, and the
    # probe values sit in [-1, 1], so 1/128 is a safe absolute bound.
    out['widen_bf16_bounded'] = bool(err <= 1.0 / 128.0)
    tier.drop()
    return out


def _check_telemetry():
    """Environment of the telemetry plane (``petastorm_tpu/telemetry``):
    does a registry round-trip and render, is the cross-process clock
    offset sane (same-host processes share CLOCK_MONOTONIC on Linux, so
    anything past the handshake rtt means span alignment is broken on
    this host), and how many spans sit undrained in the process buffer
    (residue means a subsystem records spans no channel ships)."""
    import subprocess

    from petastorm_tpu import telemetry

    out = {}
    registry = telemetry.MetricsRegistry('doctor')
    registry.counter('probe').inc()
    registry.histogram('probe_hist').observe(0.002)
    snapshot = telemetry.merge_snapshots([registry.snapshot()])
    rendered = registry.render_prometheus()
    out['registry_ok'] = bool(
        snapshot['counters'].get('probe') == 1
        and 'petastorm_tpu_doctor_probe 1' in rendered
        and 'probe_hist_seconds_bucket' in rendered)

    def child_clock():
        probe = subprocess.run(
            [sys.executable, '-c', 'import time; print(time.monotonic())'],
            capture_output=True, text=True, timeout=60)
        return float(probe.stdout.strip())

    offset, rtt = telemetry.measure_clock_offset(child_clock)
    out['clock_offset_s'] = round(offset, 4)
    out['clock_handshake_rtt_s'] = round(rtt, 4)
    # The child reads its clock at the END of its interpreter startup, so
    # the midpoint estimate is biased by up to rtt/2 — the gate allows
    # that plus scheduling slack.  Anything bigger means monotonic is NOT
    # shared the way span alignment assumes on this host.
    out['clock_offset_ok'] = bool(abs(offset) <= max(1.0, rtt))
    # Drift probe (ISSUE 7 satellite): a SECOND handshake — two midpoint
    # estimates of the same same-host clock pair should agree to within
    # their rtts; disagreement is the per-worker `clock_drift_ms` signal
    # the dispatcher `stats` rows track for long-lived fleets.
    offset2, rtt2 = telemetry.measure_clock_offset(child_clock)
    out['clock_drift_ms'] = round(1e3 * (offset2 - offset), 3)
    out['clock_drift_ok'] = bool(
        abs(offset2 - offset) <= max(1.0, rtt + rtt2))
    # Flight recorder (ISSUE 7): armed state + ring depth of THIS
    # process, and the kill-switch/persist env that governs it.
    recorder = telemetry.flight.get()
    out['flight_enabled'] = recorder is not None
    if recorder is not None:
        out['flight_frames'] = len(recorder.frames())
        out['flight_persist_path'] = recorder.persist_path
    out['flight_dir_env'] = os.environ.get('PETASTORM_TPU_FLIGHT_DIR')
    if out['flight_dir_env']:
        # Flight-dump hygiene (ISSUE 13 satellite): dead-pid, age-gated
        # sweep of accumulated flight_*/provenance_slo_* dumps — the
        # doctor both reclaims and REPORTS the residue, so an operator
        # sees how much a long-lived dump dir had rotted.
        out['flight_residue'] = telemetry.flight.sweep_dumps(
            out['flight_dir_env'])
    # peek, never drain: run_doctor() is importable from a LIVE process,
    # and consuming its pending spans would steal them from the real
    # drain channel.  The buffer is bounded, so reporting is enough.
    residue = telemetry.current_buffer().peek()
    out['span_residue'] = len(residue)
    if residue:
        out['span_residue_note'] = (
            'spans recorded but not yet drained by any ack/heartbeat '
            'channel (first: %r) — persistent growth means an '
            'instrumented subsystem runs without its return channel'
            % (residue[0].get('name'),))
    return out


def run_doctor(dataset_url=None, sample_seconds=5.0,
               batch_size=64, h2d_mb=32, cache_plane_dir=None,
               dispatcher_addr=None):
    """Run every applicable section; returns the report dict."""
    report = {}
    _contained(report, 'backend', _check_backend)
    _contained(report, 'native', _check_native)
    _contained(report, 'cache_plane',
               lambda: _check_cache_plane(cache_plane_dir))
    _contained(report, 'cluster_cache',
               lambda: _check_cluster_cache(cache_plane_dir,
                                            dispatcher_addr))
    _contained(report, 'autoscaler', _check_autoscaler)
    _contained(report, 'telemetry', _check_telemetry)
    _contained(report, 'ingest', _check_ingest)
    _contained(report, 'materialize', _check_materialize)
    if dataset_url:
        advisor = {}
        _contained(report, 'host_plane',
                   lambda: _check_host_plane(dataset_url, sample_seconds,
                                             batch_size,
                                             advisor_out=advisor))
        if advisor:  # empty when the host-plane pass itself failed
            report['advisor'] = advisor
    if 'error' not in report['backend']:   # both need a live backend
        _contained(report, 'h2d', lambda: _check_h2d(h2d_mb))
        _contained(report, 'residency', _check_residency)
    return report


def _check_autotune(dataset_url, batch_size, seconds_per_config):
    from petastorm_tpu.benchmark import autotune
    return autotune(dataset_url, batch_size=batch_size,
                    seconds_per_config=seconds_per_config)


def _format(report):
    lines = []
    for section, data in report.items():
        data = dict(data)
        elapsed = data.pop('elapsed_s', None)
        status = 'FAIL' if 'error' in data else 'ok'
        lines.append('%-11s %-5s %s' % (section, status,
                                        '(%.1fs)' % elapsed
                                        if elapsed is not None else ''))
        for k, v in data.items():
            lines.append('    %s: %s' % (k, v))
    return '\n'.join(lines)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    parser.add_argument('--dataset-url', default=None,
                        help='petastorm or plain-parquet URL to exercise '
                             'the host plane + advisor against')
    parser.add_argument('--json', action='store_true',
                        help='emit one machine-readable JSON line instead '
                             'of the human report')
    parser.add_argument('--seconds', type=float, default=5.0,
                        help='host-plane sampling window')
    parser.add_argument('--batch-size', type=int, default=64)
    parser.add_argument('--cache-plane-dir', default=None,
                        help='epoch-cache plane directory to check '
                             '(tier writability + entry count); the '
                             '/dev/shm headroom and orphan-sweep report '
                             'run either way')
    parser.add_argument('--dispatcher', default=None,
                        help='live data-service dispatcher '
                             '(tcp://host:port) to check the cluster '
                             'cache directory against (one stats RPC)')
    parser.add_argument('--autotune', action='store_true',
                        help='also sweep reader configurations '
                             '(workers_count grid) on this host and '
                             'recommend the fastest — needs --dataset-url')
    args = parser.parse_args(argv)
    if args.autotune and not args.dataset_url:
        parser.error('--autotune needs --dataset-url')

    report = run_doctor(dataset_url=args.dataset_url,
                        sample_seconds=args.seconds,
                        batch_size=args.batch_size,
                        cache_plane_dir=args.cache_plane_dir,
                        dispatcher_addr=args.dispatcher)
    if args.autotune:
        _contained(report, 'autotune',
                   lambda: _check_autotune(args.dataset_url,
                                           args.batch_size,
                                           max(1.0, args.seconds / 2)))
    if args.json:
        print(json.dumps(report, default=str))
    else:
        print(_format(report))
    # Exit 1 when ANY plane failed — a backend that did not initialize IS
    # a failed plane (the scriptable `doctor && launch` contract).
    return 1 if any('error' in v for v in report.values()) else 0


if __name__ == '__main__':
    sys.exit(main())
