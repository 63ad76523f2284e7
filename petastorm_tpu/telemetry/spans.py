"""Correlated data-plane spans across process boundaries.

The driving process already records a Chrome-trace timeline
(``benchmark.TraceRecorder``); this module extends it across the four
process boundaries the data plane spans.  Worker processes record spans
(rowgroup decode, serialize, shm publish, cache fill) into a bounded
per-process :class:`SpanBuffer`, keyed by a **correlation id** — the
ventilator item position for ProcessPool work, ``"split/seq"`` for
service chunks — and the spans ride the ZMQ frames the data already
travels on (ProcessPool ack payloads, service ``end`` headers).  The
parent/client merges them into ONE recorder with per-process
``time.monotonic()`` clock-offset alignment, so a ``data_wait`` stall on
the trainer thread visually decomposes into lease-wait, decode, IPC and
H2D spans in Perfetto.

Span dicts are deliberately flat and tiny (picklable, JSON-able)::

    {'name': 'service/serialize', 't0': <monotonic s>, 't1': <monotonic s>,
     'pid': 1234, 'tid': <thread ident>, 'cid': '7/3'}

Clock offsets: ``time.monotonic()`` is per-process in general (per-boot
on Linux, so ~0 between same-host processes — the ProcessPool case), and
arbitrary between hosts.  :func:`measure_clock_offset` does the RPC
handshake (remote timestamp against the local send/recv midpoint); the
service chains client->dispatcher and dispatcher->worker offsets so the
client can align every worker's spans without talking clocks to each
worker directly.
"""

import contextlib
import os
import sys
import threading
from petastorm_tpu.utils.locks import make_lock
import time
from collections import deque

__all__ = ['SpanBuffer', 'current_buffer', 'merge_into_recorder',
           'measure_clock_offset', 'attribute_stalls', 'STALL_COMPONENTS',
           'Stages', 'profiler_span']


def profiler_span(name):
    """A ``jax.profiler.TraceAnnotation(name)``: a span on this thread's
    line of the profiler's trace, on the device trace's clock, and nothing
    but a flag test while no trace is being taken.  A process that never
    imported jax cannot be taking one, so it gets a null context and is
    not made to import jax for this (reader pool children)."""
    jax = sys.modules.get('jax')
    if jax is None:
        return contextlib.nullcontext()
    return jax.profiler.TraceAnnotation(name)


class Stages(object):
    """The one way a stage of the data path is timed.

    ``with stages('host_batch', event='host_batch') as stage: ...`` holds a
    profiler span ``pt/host_batch`` open for the block and, from ONE pair
    of ``time.monotonic()`` readings taken inside it, feeds the
    ``host_batch_s`` counter and the ``host_batch`` histogram of
    ``metrics`` and, where a ``TraceRecorder`` is attached and ``event``
    names one, that recorder's span.  ``stage.window`` is then the
    ``[t0, t1]`` that a provenance record wants.  So the two span systems
    (the profiler's, which the benchmark reads; the recorder's, see
    ``docs/observability.md``) and the registry cannot drift apart.

    Instruments are made on a stage's first use.  A block left by an
    exception, or with ``stage.keep = False``, leaves the profiler span
    and nothing else (the pull that met the end of the stream is no
    sample).  Such a block can still be read (``stage.seconds``), and the
    block that closes the sample takes the seconds of the ones before it as
    ``stage.carried``: a stage whose work for one sample comes in several
    blocks (the packer's, one a document) is still one sample.

    ``pt/`` is for the spans that may own an idle gap of the device: the
    top-level stages of the thread that feeds it, and the pauses of the
    whole process.  ``benchmarks/trace_reduce.py`` gives each gap to the
    ``pt/*`` span that covers most of it, and walks every ``pt/*`` span for
    every gap, so a span that could never rightly own one is named outside
    the prefix (``span=``, or another ``prefix``): a part nested in a stage
    (``ptp/h2d_stage`` inside ``pt/device_put``: the stage covers whatever
    its part covers), the consumer's wait (``ptc/next_wait``: it covers the
    pump's spans in time), a reader worker's (``ptw/codec_decode``: seconds
    long on ten threads, it covers every gap without having caused it).
    """

    def __init__(self, metrics, recorder=None, prefix='pt/'):
        self.metrics = metrics
        self.recorder = recorder
        self.prefix = prefix
        self._instruments = {}

    def instruments(self, name):
        """``(<name>_s counter, <name> histogram)`` of a stage."""
        found = self._instruments.get(name)
        if found is None:
            found = self._instruments[name] = (
                self.metrics.counter(name + '_s'),
                self.metrics.histogram(name))
        return found

    def __call__(self, name, span=None, event=None, **event_args):
        return _Stage(self, name, span or self.prefix + name, event,
                      event_args)


class _Stage(object):
    __slots__ = ('_stages', 'name', 'span', 'event', 'event_args', 'keep',
                 'carried', 'window', '_profiler_span')

    def __init__(self, stages, name, span, event, event_args):
        self._stages = stages
        self.name = name
        self.span = span
        self.event = event
        self.event_args = event_args
        self.keep = True
        self.carried = 0.0
        self.window = None

    @property
    def seconds(self):
        return self.window[1] - self.window[0]

    def __enter__(self):
        self._profiler_span = profiler_span(self.span)
        self._profiler_span.__enter__()
        self.window = [time.monotonic(), None]
        return self

    def __exit__(self, exc_type, exc, tb):
        t0, t1 = self.window[0], time.monotonic()
        self._profiler_span.__exit__(exc_type, exc, tb)
        self.window[1] = t1
        if exc_type is None and self.keep:
            counter, hist = self._stages.instruments(self.name)
            counter.inc(t1 - t0 + self.carried)
            hist.observe(t1 - t0 + self.carried)
            recorder = self._stages.recorder
            if recorder is not None and self.event is not None:
                recorder.event(self.event, t0, t1, **self.event_args)
        return False


class SpanBuffer(object):
    """Bounded per-process buffer of completed spans.

    ``drain()`` hands the accumulated spans to whatever return channel
    ships them (ack payload, end header) and empties the buffer; the
    bound means a worker whose channel never drains (absent consumer)
    keeps the LATEST spans and constant memory.
    """

    def __init__(self, max_spans=4096):
        self._spans = deque(maxlen=int(max_spans))
        self._lock = make_lock('telemetry.spans.SpanBuffer._lock')

    # Buffers are per-process by contract (current_buffer re-keys on pid);
    # shipping one across a boundary ships the pending spans only.
    def __getstate__(self):
        return {'spans': self.peek(), 'maxlen': self._spans.maxlen}

    def __setstate__(self, state):
        self.__init__(state['maxlen'])
        self._spans.extend(state['spans'])

    def span(self, name, t0, t1, cid=None, **args):
        ev = {'name': name, 't0': t0, 't1': t1, 'pid': os.getpid(),
              'tid': threading.get_ident()}
        if cid is not None:
            ev['cid'] = str(cid)
        if args:
            ev['args'] = args
        with self._lock:
            self._spans.append(ev)

    def drain(self):
        with self._lock:
            out = list(self._spans)
            self._spans.clear()
        return out

    def peek(self):
        with self._lock:
            return list(self._spans)

    def __len__(self):
        return len(self._spans)


_BUFFER = None
_BUFFER_PID = None
_BUFFER_LOCK = make_lock('telemetry.spans._BUFFER_LOCK')


def current_buffer():
    """The process-local span buffer singleton (re-created after fork, so
    a child never drains spans its parent recorded).  For processes with
    exactly ONE drain channel (a ProcessPool child's ack sender); a
    subsystem that can be shared by several in-process drainers (the
    cache plane) must keep its own ``SpanBuffer`` instead — concurrent
    drains on a shared buffer drop or mis-attribute spans."""
    global _BUFFER, _BUFFER_PID
    pid = os.getpid()
    with _BUFFER_LOCK:
        if _BUFFER is None or _BUFFER_PID != pid:
            _BUFFER = SpanBuffer()
            _BUFFER_PID = pid
        return _BUFFER


def merge_into_recorder(recorder, spans, clock_offset_s=0.0, pid=None):
    """Append remote span dicts to a ``TraceRecorder`` timeline.

    ``clock_offset_s`` is (local_clock - remote_clock): adding it to the
    remote timestamps lands them on this process's monotonic timeline.
    Returns the number of spans merged."""
    if recorder is None or not spans:
        return 0
    for span in spans:
        args = dict(span.get('args') or {})
        if span.get('cid') is not None:
            args['cid'] = span['cid']
        recorder.event(span['name'],
                       span['t0'] + clock_offset_s,
                       span['t1'] + clock_offset_s,
                       pid=pid if pid is not None else span.get('pid'),
                       # Keep the RECORDING thread's ident: concurrent
                       # threads of one remote process must land on
                       # separate Perfetto tracks, not collapse onto the
                       # merging thread's row as overlapping slices.
                       tid=span.get('tid'),
                       **args)
    return len(spans)


def measure_clock_offset(call):
    """One clock handshake: ``call()`` must return the REMOTE process's
    ``time.monotonic()`` (an RPC round-trip).  Returns
    ``(local - remote, rtt_s)``: add the offset to remote timestamps to
    get local ones.  The midpoint estimate is wrong by at most rtt/2 —
    sub-ms on a LAN, which is below the log2 histogram resolution and
    good enough to ORDER spans across processes."""
    t0 = time.monotonic()
    remote = call()
    t1 = time.monotonic()
    return (t0 + t1) / 2.0 - float(remote), t1 - t0


#: Stall-attribution catalogue: component -> span names that evidence it.
#: ``data_wait`` time overlapping a component's spans (any process, after
#: clock alignment) is attributed to that component.  Parallel stages can
#: overlap the same wait, so percentages may sum past 100 — that is the
#: honest answer for a pipelined plane (each number is "this stage was
#: active for N% of the stalled time").
STALL_COMPONENTS = {
    'decode': ('service/decode_split', 'pool/process'),
    'ipc': ('service/serialize', 'service/shm_publish', 'pool/publish'),
    'cache_fill': ('cache/fill',),
    # h2d splits into the LINK (async dispatch + observed commit waits —
    # 'device_put' is the inline loader's dispatch span, 'h2d/dispatch'
    # and 'h2d/commit' the transfer plane's) vs the host-side STAGING
    # copy ('h2d/stage': packing columns into the wire slab) — a
    # staging-bound stall wants fewer/narrower columns, a link-bound
    # stall wants narrowing/overlap, so the breakdown keeps them apart.
    'h2d': ('device_put', 'h2d/dispatch', 'h2d/commit'),
    'h2d_stage': ('h2d/stage',),
    # Ingest plane (ISSUE 14): an async range fetch (or its hedge)
    # active while the consumer waited — when the overlap machinery is
    # working, these spans run UNDER decode time and never intersect a
    # data_wait; a high share here means cold-read latency is NOT being
    # hidden (the fetch-bound regime).
    'ingest_fetch': ('ingest/fetch', 'ingest/hedge'),
}

#: Wait-wrapper spans: ``service/split_wait`` covers the WHOLE client
#: wait by construction (next_split records its own blocking time), so
#: counting its raw overlap would crown lease_wait the top component of
#: every service stall.  ``lease_wait`` is instead defined as TRUE
#: starvation: wait time inside these spans that NO catalogued stage
#: covers — nobody was decoding, serializing, filling, or transferring.
_WAIT_WRAPPERS = ('service/split_wait', 'service/lease_wait')


def attribute_stalls(events, wait_name='data_wait'):
    """Decompose ``data_wait`` stall time by pipeline component.

    ``events`` are Chrome-trace dicts (``TraceRecorder.events``, i.e.
    AFTER any cross-process merge).  Returns::

        {'total_wait_s': ..., 'pct': {component: pct, ..., 'other': pct},
         'top': 'decode'}

    or None when no wait spans exist.  ``other`` is the wait time no
    catalogued span overlaps (scheduler gaps, un-instrumented stages).
    """
    waits = _intervals(events, (wait_name,))
    if not waits:
        return None
    total = sum(e - s for s, e in waits)
    if total <= 0.0:
        return None
    pct = {}
    covered = []
    for component, names in STALL_COMPONENTS.items():
        overlap_ivals = _clip(_intervals(events, names), waits)
        covered.extend(overlap_ivals)
        pct[component] = round(
            100.0 * sum(e - s for s, e in overlap_ivals) / total, 2)
    stage_union = _union(covered)
    # lease_wait = starvation: split_wait time no stage accounts for.
    starved = _subtract(_clip(_intervals(events, _WAIT_WRAPPERS), waits),
                        stage_union)
    pct['lease_wait'] = round(
        100.0 * sum(e - s for s, e in starved) / total, 2)
    # 'other' = wait NOTHING accounts for — stages AND starvation both
    # count as accounted, else other >= lease_wait by construction and
    # starvation could never be the top component.
    accounted = _union(stage_union + starved)
    uncovered = total - sum(e - s for s, e in accounted)
    pct['other'] = round(100.0 * max(0.0, uncovered) / total, 2)
    top = max(pct, key=pct.get)
    return {'total_wait_s': round(total / 1e6, 4), 'pct': pct, 'top': top}


def _intervals(events, names):
    """Merged [start, end) µs intervals of the named 'X' spans."""
    ivals = [(ev['ts'], ev['ts'] + ev['dur']) for ev in events
             if ev.get('ph') == 'X' and ev.get('name') in names]
    return _union(ivals)


def _union(ivals):
    out = []
    for start, end in sorted(ivals):
        if out and start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], end))
        else:
            out.append((start, end))
    return out


def _clip(ivals, windows):
    """Intersect merged intervals with merged windows."""
    out = []
    for start, end in ivals:
        for w0, w1 in windows:
            lo, hi = max(start, w0), min(end, w1)
            if hi > lo:
                out.append((lo, hi))
    return _union(out)


def _subtract(ivals, holes):
    """Merged intervals minus merged holes."""
    out = []
    for start, end in ivals:
        cursor = start
        for h0, h1 in holes:
            if h1 <= cursor or h0 >= end:
                continue
            if h0 > cursor:
                out.append((cursor, h0))
            cursor = max(cursor, h1)
            if cursor >= end:
                break
        if cursor < end:
            out.append((cursor, end))
    return out
