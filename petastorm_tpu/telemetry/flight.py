"""Always-on flight recorder: the minutes BEFORE a failure, per process.

The telemetry plane (ISSUE 5) made the fleet measurable, but every
surface is *current state*: a hang investigated after the watchdog fires
ships the final registry totals and whatever spans were still buffered —
the trajectory that led there is gone.  This module keeps it: a bounded
ring of periodic **frames**, each one merged-registry snapshot (every
live registry in the process, merged by the same bucket-addition
machinery the fleet rollups use) plus the spans that completed since the
last frame, stamped with monotonic AND wall-clock time.  Consecutive
frames subtract into windowed deltas (``registry.snapshot_delta``) — the
input the health engine (``telemetry/health.py``) and
``petastorm-tpu-diagnose`` classify regimes from.

Cheap enough to leave on: a frame is one ``snapshot_all()`` merge + a
bounded span peek every ``interval_s`` (default 2 s) on a daemon thread
— nothing rides any data-plane hot path, so the ProcessPool ack path
pays zero per-item cost (measured: the host-plane leg is within run
noise with the recorder on; see ``docs/observability.md``).

Crash-safety is WRITE-AHEAD, not at-exit: with a ``persist_path`` the
ring overwrites one JSON file every ``persist_every`` frames (atomic
tmp+rename), so a SIGKILL/segfault leaves the last periodic write on
disk — a postmortem artifact nobody had to remember to request.
``persist()`` additionally writes on demand (watchdog fire, clean exit).

Span capture PEEKS with a time watermark, never drains: a process's span
buffer belongs to its real return channel (ack payloads, end headers) —
the doctor learned this the hard way — so the recorder copies spans
newer than its last frame and leaves the buffer intact.

Process wiring: :func:`enable` is a pid-keyed singleton (like
``spans.current_buffer``) armed by the long-lived processes — service
workers, ProcessPool children, ``DataLoader`` trainers, the test suite —
and killed globally by ``PETASTORM_TPU_NO_FLIGHT=1``.  The dispatcher
instead owns a dedicated instance whose ``source`` merges the fleet's
heartbeat snapshots (see ``service/dispatcher.py``): same ring, fleet
scope.
"""

import fcntl
import gc
import os
import re
import threading
from petastorm_tpu.utils.locks import make_lock
import time

from petastorm_tpu.telemetry import decisions, provenance
from petastorm_tpu.telemetry.registry import (merge_snapshots,
                                              process_registry, snapshot_all)
from petastorm_tpu.telemetry.spans import (Stages, current_buffer,
                                           profiler_span)
from petastorm_tpu.utils import ipc

__all__ = ['FlightRecorder', 'window_frames', 'enable', 'get', 'disable',
           'dump_current', 'default_persist_path', 'sweep_dumps',
           'watch_gc', 'unwatch_gc']


def window_frames(frames, seconds=None):
    """THE frame-windowing rule, shared by every consumer (recorder,
    health engine, dispatcher stats, diagnose): ``(baseline, newest)``
    pair for delta computation over a frame list.  ``newest`` is the
    last frame; ``baseline`` is the newest frame at or behind the
    ``seconds`` horizon (the oldest frame when the ring is younger than
    the window), or None when fewer than two frames exist.
    ``seconds=None`` spans the whole list.  Returns ``(None, None)``
    for an empty list."""
    if not frames:
        return None, None
    newest = frames[-1]
    if len(frames) == 1:
        return None, newest
    if seconds is None:
        return frames[0], newest
    horizon = newest['t_mono'] - float(seconds)
    baseline = frames[0]
    for frame in frames[:-1]:
        if frame['t_mono'] <= horizon:
            baseline = frame
        else:
            break
    return baseline, newest

#: ~8 minutes of history at the default cadence — "the minutes before
#: the failure", bounded.
DEFAULT_INTERVAL_S = 2.0
DEFAULT_MAX_FRAMES = 240

#: Span bound per frame: a pathological burst must not bloat the ring.
_MAX_SPANS_PER_FRAME = 256


class FlightRecorder(object):  # ptlint: disable=pickle-unsafe-attrs — per-process diagnostic state; dumps (plain dicts) are what cross boundaries
    """Bounded ring of periodic telemetry frames.

    Args:
        interval_s: target seconds between frames.
        max_frames: ring bound (oldest frames drop first).
        source: zero-arg callable returning a merged registry snapshot;
            defaults to merging every live registry in this process.
            The dispatcher passes its fleet-heartbeat merge here.
        label: human tag carried in dumps ('service_worker', 'trainer').
        persist_path: when set, the ring overwrites this file every
            ``persist_every`` frames and on :meth:`persist` — the
            crash-survivable artifact.
        persist_every: frames between periodic persists.

    Drive it either with :meth:`start` (daemon thread) or by calling
    :meth:`maybe_tick` from a loop the process already runs (the
    dispatcher's serve loop does this — no extra thread in the control
    plane).
    """

    def __init__(self, interval_s=None, max_frames=None, source=None,
                 label=None, persist_path=None, persist_every=8):
        self.interval_s = float(interval_s if interval_s is not None
                                else DEFAULT_INTERVAL_S)
        self.max_frames = int(max_frames if max_frames is not None
                              else DEFAULT_MAX_FRAMES)
        self.label = label
        self.persist_path = persist_path
        self.persist_every = max(1, int(persist_every))
        self._source = source
        self._frames = []
        self._lock = make_lock('telemetry.flight.FlightRecorder._lock')
        self._stop = threading.Event()
        self._thread = None
        self._last_tick = 0.0
        self._span_watermark = 0.0
        self._ticks = 0
        self._started_monotonic = time.monotonic()
        self._started_unix = time.time()
        self._stage = Stages(process_registry())

    # -- recording -----------------------------------------------------------

    def tick(self):
        """Record one frame.  Contained: a diagnostic must never take the
        process it is diagnosing down with it.  A stage of its own
        (``flight_tick`` in the process registry, ``pt/flight_tick`` in a
        profile), the periodic persist included: a tick merges every
        registry and at times writes JSON, under the GIL."""
        with self._stage('flight_tick'):
            try:
                frame = self._build_frame()
            except Exception:  # noqa: BLE001 — diagnostics are best-effort
                return None
            with self._lock:
                self._frames.append(frame)
                del self._frames[:-self.max_frames]
                self._ticks += 1
                ticks = self._ticks
            self._last_tick = time.monotonic()
            if self.persist_path and ticks % self.persist_every == 0:
                self.persist(reason='periodic')
        return frame

    def maybe_tick(self):
        """Tick iff ``interval_s`` elapsed since the last frame — for
        host loops that already wake frequently (dispatcher serve loop)."""
        if time.monotonic() - self._last_tick >= self.interval_s:
            return self.tick()
        return None

    def _build_frame(self):
        snapshot = (self._source() if self._source is not None
                    else merge_snapshots(snapshot_all()))
        # Peek-with-watermark: copy spans that COMPLETED since the last
        # frame, leave the buffer for its real drain channel.
        pending = current_buffer().peek()
        fresh = [s for s in pending if s.get('t1', 0.0) > self._span_watermark]
        if fresh:
            self._span_watermark = max(s['t1'] for s in fresh)
        frame = {
            't_mono': time.monotonic(),
            'unix_time': time.time(),
            'snapshot': snapshot,
            'spans': fresh[-_MAX_SPANS_PER_FRAME:],
            'span_residue': len(pending),
        }
        # Per-batch provenance (ISSUE 13): the rolling worst-K batch
        # summaries of every live journal — compact refs (step/latency/
        # worker/piece), never full records, so the bounded ring stays
        # bounded; the full journals ride `dump()`.
        worst = provenance.worst_summaries()
        if worst:
            frame['provenance_worst'] = worst
        # Control-plane decisions (ISSUE 20): the last few decision
        # summaries from every live journal — same compact-refs-in-frames /
        # full-journals-in-dump() split as provenance.
        recent = decisions.recent_summaries()
        if recent:
            frame['decisions_recent'] = recent
        return frame

    # -- thread lifecycle ----------------------------------------------------

    def start(self):
        """Arm the daemon tick thread (idempotent).  The thread is
        import-free by construction — everything it touches is imported
        at module load on the arming thread (the timer-thread
        first-import segfault class, see tests/conftest.py)."""
        if self._thread is not None and self._thread.is_alive():
            return self
        self._stop.clear()
        self._thread = threading.Thread(target=self._run,
                                        name='telemetry-flight', daemon=True)
        self._thread.start()
        return self

    def _run(self):
        # The thread only sleeps, so it wakes later than asked exactly when
        # the process, or the GIL, stood still: ``tick_late`` is the witness
        # that tells "everything paused" from "one thread waited".
        late_s, late = self._stage.instruments('tick_late')
        asleep = time.monotonic()
        while not self._stop.wait(self.interval_s):
            seconds = max(0.0, time.monotonic() - asleep - self.interval_s)
            late_s.inc(seconds)
            late.observe(seconds)
            self.tick()
            asleep = time.monotonic()

    def stop(self):
        self._stop.set()
        # Release the sidecar lock + fd: a stopped recorder must not pin
        # one fd (and hold LOCK_SH) per enable/persist/disable cycle for
        # the rest of the process.  The sidecar FILE goes too — an
        # unlocked .owner left on disk would read as "owner provably
        # gone" at the next sweep and take the dump of this still-alive
        # process with it (the sweep only falls back to pid_alive when
        # no sidecar exists).
        with self._lock:
            # Same lock _hold_owner takes: after this block no racing
            # persist can re-create the sidecar (it sees _stop set).
            fd, self._owner_fd = self._owner_fd, None
        if fd is not None:
            try:
                os.close(fd)
            except OSError:
                pass
            if self.persist_path:
                try:
                    os.unlink(self.persist_path + '.owner')
                except OSError:
                    pass

    # -- reading -------------------------------------------------------------

    def frames(self):
        with self._lock:
            return list(self._frames)

    def window(self, seconds=None):
        """:func:`window_frames` over this ring's current frames."""
        return window_frames(self.frames(), seconds)

    def dump(self):
        """JSON-able dump of the whole ring + identity/provenance."""
        return {
            'kind': 'flight_recorder',
            'pid': os.getpid(),
            'label': self.label,
            'interval_s': self.interval_s,
            'started_monotonic': self._started_monotonic,
            'started_unix': self._started_unix,
            'frames': self.frames(),
            # Full per-batch provenance journals (ISSUE 13): the dump is
            # unbounded-once (not a ring frame), so the complete causal
            # chains ship with the crash artifact.
            'provenance': provenance.dump_journals(),
            # Full decision journals (ISSUE 20): same unbounded-once
            # treatment, so `petastorm-tpu-why` can ingest a flight dump.
            'decisions': decisions.dump_journals(),
        }

    _owner_fd = None

    def _hold_owner(self, path):
        """Lifetime shared flock on ``<path>.owner`` — the liveness
        signal :func:`sweep_dumps` probes (the ``utils/ipc.py`` idiom:
        a kernel-released lock is the only signal that survives pid
        namespaces; the dump itself gets a fresh inode on every atomic
        replace, so the lock must live on a stable sidecar)."""
        with self._lock:
            # Under the lock, re-checking _stop: a stop() racing an
            # in-flight periodic persist must not let the tick thread
            # re-create the sidecar (and leak a locked fd) right after
            # stop() cleaned both up.
            if self._owner_fd is not None or self._stop.is_set():
                return
        fd = None
        try:
            fd = os.open(path + '.owner', os.O_CREAT | os.O_RDWR, 0o644)
            fcntl.flock(fd, fcntl.LOCK_SH | fcntl.LOCK_NB)
            with self._lock:
                if self._stop.is_set():
                    raise OSError('recorder stopped during owner setup')
                # Held (as an attribute) for the recorder's lifetime;
                # the kernel releases it on ANY death, SIGKILL included.
                self._owner_fd = fd
        except OSError:
            # Close the fd (no leak) AND remove the unlocked sidecar: an
            # .owner file with a free flock would later read as "owner
            # provably gone" and get the LIVE dump swept — the exact
            # inversion of its purpose.  The name is pid-scoped, so this
            # never unlinks another process's sidecar.
            if fd is not None:
                os.close(fd)
                try:
                    os.unlink(path + '.owner')
                except OSError:
                    pass
            self._owner_fd = None

    def persist(self, path=None, reason=None):
        """Atomic write of :meth:`dump` (tmp + ``os.replace``).  Returns
        the path on success, None on any failure — persistence is
        best-effort by contract."""
        path = path or self.persist_path
        if not path:
            return None
        try:
            state = self.dump()
            if reason is not None:
                state['reason'] = reason
            directory = os.path.dirname(os.path.abspath(path))
            os.makedirs(directory, exist_ok=True)
            self._hold_owner(path)
        except Exception:  # noqa: BLE001 — a failed artifact beats a dead process
            return None
        # THE one artifact-write idiom (tmp + replace + tmp cleanup).
        return provenance.atomic_json_dump(path, state)


# -- dump-directory hygiene (ISSUE 13 satellite) ------------------------------

#: One dump file per (label, pid): ``flight_<label>_<pid>.json`` plus the
#: SLO watchdog's ``provenance_slo_<label>_<pid>.json`` twins.
_DUMP_NAME = re.compile(
    r'^(?:flight|provenance_slo)_.+_(\d+)\.json(?P<owner>\.owner)?$')

#: tmp residue from `atomic_json_dump` writers killed mid-persist —
#: scoped to OUR naming scheme, exactly like `_DUMP_NAME`: the sweep
#: runs automatically (doctor, first enable()) and must never reclaim
#: third-party ``*.tmp`` files in a shared dump directory.
_TMP_NAME = re.compile(
    r'^(?:flight|provenance_slo)_.+\.json\.\d+\.tmp$')

#: Age gate: residue younger than this is never touched — a dump is a
#: postmortem artifact, and "the process died a minute ago" is exactly
#: when someone wants to read it.
DEFAULT_SWEEP_MIN_AGE_S = 24 * 3600.0


def sweep_dumps(directory=None, min_age_s=DEFAULT_SWEEP_MIN_AGE_S):
    """Dead-pid, age-gated sweep of accumulated flight/provenance dumps
    under ``directory`` (default ``PETASTORM_TPU_FLIGHT_DIR``).

    ``flight_<label>_<pid>.json`` files accumulate forever otherwise
    (one per process, per run, for the life of the directory).  A dump
    is reclaimed only when it is older than ``min_age_s`` AND its owner
    is provably gone: the ``.owner`` sidecar's lifetime flock is free
    (``utils/ipc.flock_probe_unlink`` — crosses pid namespaces), or,
    for pre-sidecar dumps, the embedded pid is dead.  Stale ``.tmp``
    residue from writers killed mid-persist sweeps under the same age
    gate.  Returns ``{'swept', 'kept', 'tmp_swept'}``; never raises.
    """
    directory = directory or os.environ.get('PETASTORM_TPU_FLIGHT_DIR')
    result = {'swept': 0, 'kept': 0, 'tmp_swept': 0}
    if not directory or not os.path.isdir(directory):
        return result
    now = time.time()
    try:
        names = sorted(os.listdir(directory))
    except OSError:
        return result
    for name in names:
        path = os.path.join(directory, name)
        try:
            age = now - os.stat(path).st_mtime
        except OSError:
            continue  # vanished under us (concurrent sweep)
        if age < min_age_s:
            if _DUMP_NAME.match(name):
                result['kept'] += 1
            continue
        if name.endswith('.tmp'):
            if _TMP_NAME.match(name) and ipc.flock_probe_unlink(path):
                result['tmp_swept'] += 1
            continue
        match = _DUMP_NAME.match(name)
        if not match:
            continue
        if match.group('owner'):
            # Orphaned sidecar (its dump already swept): same probe.
            if ipc.flock_probe_unlink(path):
                result['swept'] += 1
            continue
        owner = path + '.owner'
        if os.path.exists(owner):
            if not ipc.flock_probe_unlink(owner):
                result['kept'] += 1  # owner lives (maybe another pid ns)
                continue
        elif ipc.pid_alive(int(match.group(1))):
            result['kept'] += 1
            continue
        try:
            os.unlink(path)
            result['swept'] += 1
        except OSError:
            result['kept'] += 1
    return result


# -- garbage collections -------------------------------------------------------

class _GcWatch(object):  # ptlint: disable=pickle-unsafe-attrs — one per process, holding that process's hook in gc.callbacks; never pickled
    """The ``gc.callbacks`` hook behind :func:`watch_gc`: every collection
    timed into the process registry from 'start' to 'stop', under a
    ``pt/gc`` profiler span on the thread it runs on.  The hook takes no
    lock: a collection starts wherever an allocation happens, also inside
    a registry's own lock, and its instruments are the registry's unlocked
    ones (collections never overlap, so they have one writer at a time)."""

    def __init__(self):
        self._lock = make_lock('telemetry.flight._GcWatch._lock')
        self._watchers = 0
        self._open = None     # (t0, profiler span) of the running collection
        registry = process_registry()
        self._collections = registry.counter('gc_collections')
        self._pause_s = registry.counter('gc_pause_s')
        self._pause = registry.histogram('gc_pause')

    def _on_gc(self, phase, info):
        if phase == 'start':
            # A span only beyond the youngest generation: those are the
            # collections that can take long enough to own an idle gap of
            # the device, a tenth of all (the trace reduction pays for
            # every pt/* span); the counters count every collection.
            span = profiler_span('pt/gc') if info['generation'] else None
            if span is not None:
                span.__enter__()
            self._open = (time.monotonic(), span)
        elif self._open is not None:
            (t0, span), self._open = self._open, None
            seconds = time.monotonic() - t0
            if span is not None:
                span.__exit__(None, None, None)
            self._collections.inc()
            self._pause_s.inc(seconds)
            self._pause.observe(seconds)

    def watch(self):
        with self._lock:
            self._watchers += 1
            if self._watchers == 1:
                gc.callbacks.append(self._on_gc)

    def unwatch(self):
        with self._lock:
            if self._watchers == 0:
                return
            self._watchers -= 1
            if self._watchers == 0:
                gc.callbacks.remove(self._on_gc)


_GC_WATCH = _GcWatch()


def watch_gc():
    """Time every garbage collection of this process from now on (a loader
    that is entered calls this; between collections nothing is paid).
    Counting: the hook leaves ``gc.callbacks`` with the last
    :func:`unwatch_gc`."""
    _GC_WATCH.watch()


def unwatch_gc():
    _GC_WATCH.unwatch()


# -- process singleton --------------------------------------------------------

_RECORDER = None
_RECORDER_PID = None
_SINGLETON_LOCK = make_lock('telemetry.flight._SINGLETON_LOCK')


def _disabled_by_env():
    return os.environ.get('PETASTORM_TPU_NO_FLIGHT', '') not in ('', '0')


def default_persist_path(label=None):
    """Where this process's crash artifact lands when
    ``PETASTORM_TPU_FLIGHT_DIR`` is set (None otherwise): one file per
    (label, pid) so concurrent processes never clobber each other."""
    directory = os.environ.get('PETASTORM_TPU_FLIGHT_DIR')
    if not directory:
        return None
    name = 'flight_%s_%d.json' % (label or 'proc', os.getpid())
    return os.path.join(directory, name)


def enable(label=None, interval_s=None, persist_path=None, source=None):
    """Arm (or return) the process-local always-on recorder.

    Pid-keyed like ``spans.current_buffer`` — a fork gets a fresh ring,
    never its parent's frames.  The FIRST enabler's label/interval win;
    later calls return the live recorder unchanged.  Returns None when
    ``PETASTORM_TPU_NO_FLIGHT=1`` (the kill switch for hosts where even
    a 2 s tick thread is unwelcome).
    """
    global _RECORDER, _RECORDER_PID
    if _disabled_by_env():
        return None
    pid = os.getpid()
    with _SINGLETON_LOCK:
        if _RECORDER is None or _RECORDER_PID != pid:
            env_interval = os.environ.get('PETASTORM_TPU_FLIGHT_INTERVAL_S')
            if interval_s is None and env_interval:
                try:
                    interval_s = float(env_interval)
                except ValueError:
                    interval_s = None
            if persist_path is None:
                persist_path = default_persist_path(label)
            if persist_path is not None:
                # Opportunistic hygiene (ISSUE 13 satellite): the first
                # recorder of a process reclaims ancient dead-owner
                # residue so the dump dir stops growing forever.
                try:
                    sweep_dumps(os.path.dirname(persist_path))
                except Exception:  # noqa: BLE001 — hygiene is best-effort
                    pass
            _RECORDER = FlightRecorder(interval_s=interval_s, label=label,
                                       persist_path=persist_path,
                                       source=source)
            _RECORDER_PID = pid
            _RECORDER.start()
        return _RECORDER


def get():
    """The live process recorder, or None (disabled / never enabled /
    different process after fork)."""
    with _SINGLETON_LOCK:
        if _RECORDER is not None and _RECORDER_PID == os.getpid():
            return _RECORDER
        return None


def disable():
    """Stop and forget the process recorder (tests; explicit opt-out)."""
    global _RECORDER, _RECORDER_PID
    with _SINGLETON_LOCK:
        if _RECORDER is not None:
            _RECORDER.stop()
        _RECORDER = None
        _RECORDER_PID = None


def dump_current():
    """The process recorder's dump, or None — the hook
    ``telemetry.dump_state`` includes in every crash artifact."""
    recorder = get()
    return recorder.dump() if recorder is not None else None
