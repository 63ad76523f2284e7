"""Unified metrics registry — the source of truth the diagnostics dicts view.

Design constraints, in order:

* **Merging is addition.**  Histograms use FIXED log2 buckets (bucket
  ``i`` counts observations in ``[2**i, 2**(i+1))`` microseconds), so a
  fleet rollup — dispatcher summing worker heartbeats, a ProcessPool
  parent summing child acks — is elementwise addition with no rebinning
  and no per-process bucket negotiation.
* **Snapshots are plain dicts.**  They ride the channels the data plane
  already has (pickled ProcessPool acks, service heartbeat stats) and
  survive ``json.dumps`` for the status CLI, so no process ever pickles
  a registry object across a boundary — only its snapshot.
* **Cheap enough to leave on.**  Instruments are created once and held;
  the hot path is one lock + one int add.  Instrumented code observes
  per *batch/item/split*, never per row.

A registry is process-local state; pickling one (e.g. riding inside a
``PlaneCache`` crossing the ProcessPool boundary) transfers the counts
and rebuilds the lock in the child — from there the two copies diverge,
exactly like the plane counters they replaced, and the parent-side merge
channels are how the halves reunite.
"""

import bisect
import contextlib
import math
from petastorm_tpu.utils.locks import make_lock
import weakref

__all__ = ['MetricsRegistry', 'Counter', 'Gauge', 'Histogram',
           'merge_snapshots', 'hist_quantile', 'snapshot_all', 'ms',
           'summarize_hist', 'snapshot_delta', 'process_registry']


def ms(seconds):
    """None-propagating seconds → milliseconds (3 dp): the ONE rounding
    every diagnostics view applies to histogram quantiles."""
    return None if seconds is None else round(seconds * 1e3, 3)

#: log2 buckets over microseconds: 1 µs .. ~2.4 hours (2**43 µs); index 0
#: absorbs sub-µs observations, the last bucket absorbs the tail.
BUCKETS = 44

#: Tail exemplars kept per histogram (ISSUE 13): the worst observations
#: carry bounded refs (e.g. ``{'step': N}`` into a provenance journal),
#: so a p99 read anywhere resolves to the batch that caused it.
EXEMPLARS_KEPT = 4

#: Every live registry, so a crash dump (`telemetry.dump_state`) can
#: report the whole process without the subsystems registering anywhere.
_LIVE = weakref.WeakSet()


class Counter(object):
    """Monotonic accumulator (int or float)."""

    __slots__ = ('_lock', 'value')

    def __init__(self, lock):
        self._lock = lock
        self.value = 0

    def inc(self, n=1):
        with self._lock:
            self.value += n


class Gauge(object):
    """Last-write-wins sample (queue depth, offset, ...)."""

    __slots__ = ('_lock', 'value')

    def __init__(self, lock):
        self._lock = lock
        self.value = 0

    def set(self, v):
        with self._lock:
            self.value = v


class Histogram(object):
    """Fixed log2-bucket latency histogram; merge = bucket addition.

    ``observe(..., exemplar=ref)`` additionally maintains **tail
    exemplars** (ISSUE 13): the :data:`EXEMPLARS_KEPT` slowest observed
    samples keep their ref (a small JSON-able dict, e.g. ``{'step': N}``
    pointing into a provenance journal) so the top bucket is never
    anonymous.  Exemplars ride snapshots and re-rank on merge; they are
    evidence refs, not counts, so merging keeps the worst K rather than
    adding."""

    __slots__ = ('_lock', 'counts', 'sum', 'count', 'exemplars')

    def __init__(self, lock):
        self._lock = lock
        self.counts = [0] * BUCKETS
        self.sum = 0.0
        self.count = 0
        self.exemplars = []

    def observe(self, seconds, exemplar=None):
        us = seconds * 1e6
        index = 0 if us < 1.0 else min(BUCKETS - 1, int(math.log2(us)))
        with self._lock:
            self.counts[index] += 1
            self.sum += seconds
            self.count += 1
            if exemplar is not None:
                self._note_exemplar_locked(index, seconds, exemplar)

    def note_exemplar(self, seconds, ref):
        """Attach a tail-exemplar ref WITHOUT counting an observation —
        for surfaces whose sample was observed earlier, before its
        journal step existed (the loader observes per stage, then seals
        the batch record and back-annotates)."""
        us = seconds * 1e6
        index = 0 if us < 1.0 else min(BUCKETS - 1, int(math.log2(us)))
        with self._lock:
            self._note_exemplar_locked(index, seconds, ref)

    def _note_exemplar_locked(self, index, seconds, ref):
        self.exemplars.append({'bucket': index,
                               'seconds': round(seconds, 6),
                               'ref': ref})
        self.exemplars.sort(key=lambda e: e['seconds'])
        del self.exemplars[:-EXEMPLARS_KEPT]

    def quantile(self, q):
        """Bucket-upper-bound estimate of quantile ``q`` in SECONDS (None
        when empty) — the resolution is the log2 bucket, which is what a
        'which stage, which worker' question needs."""
        return hist_quantile({'counts': self.counts, 'count': self.count}, q)


class MetricsRegistry(object):
    """Named instruments under one namespace + one lock.

    ``counter``/``gauge``/``histogram`` are get-or-create and return the
    SAME instrument for the same name, so subsystems can share a registry
    without coordinating construction order.
    """

    def __init__(self, namespace=''):
        self.namespace = namespace
        self._lock = make_lock('telemetry.registry.MetricsRegistry._lock')
        self._counters = {}
        self._gauges = {}
        self._histograms = {}
        self._attached = []
        _LIVE.add(self)

    # Registries cross the ProcessPool boundary inside PlaneCache-holding
    # readers: ship the counts, rebuild the lock (process-local) in the
    # child — the copies then diverge and reunite through the snapshot
    # merge channels, like every other per-process counter.
    def __getstate__(self):
        return {'namespace': self.namespace,
                'snapshot': self.snapshot(attached=False)}

    def __setstate__(self, state):
        self.__init__(state['namespace'])
        self.merge(state['snapshot'])

    def _get(self, table, name, factory):
        with self._lock:
            instrument = table.get(name)
            if instrument is None:
                instrument = table[name] = factory(self._lock)
            return instrument

    def counter(self, name):
        return self._get(self._counters, name, Counter)

    def gauge(self, name):
        return self._get(self._gauges, name, Gauge)

    def histogram(self, name):
        return self._get(self._histograms, name, Histogram)

    # -- snapshot / merge ----------------------------------------------------

    def attach(self, prefix, registry):
        """Show another registry's instruments in this one's
        :meth:`snapshot` as ``<prefix><name>``.  By reference: nothing is
        copied until a snapshot is taken, and the other registry stays the
        source of truth (its owner keeps observing into it).  ``registry``
        may be a zero-argument callable that returns the registry or None
        (a reader builds a new pool, and with it a new registry, on
        ``reset()``).  The loader attaches its reader's pool as ``reader_``
        and the process's (:func:`process_registry`) as ``process_``, so one
        window delta of ``loader.metrics`` covers all three."""
        self._attached.append((prefix, registry))

    def snapshot(self, attached=True):
        """Plain-dict copy of every instrument — picklable, JSON-able,
        and addition-mergeable (`merge_snapshots`) — with the attached
        registries' under their prefixes.  ``attached=False`` gives this
        registry's own alone: what a process-wide rollup
        (:func:`snapshot_all`, the scrape endpoint) wants, since it meets
        the attached registries under their own names anyway."""
        with self._lock:
            snap = {
                'namespace': self.namespace,
                'counters': {k: c.value for k, c in self._counters.items()},
                'gauges': {k: g.value for k, g in self._gauges.items()},
                'histograms': {
                    k: _hist_dict(h) for k, h in self._histograms.items()},
            }
            others = list(self._attached) if attached else ()
        for prefix, registry in others:   # outside the lock: theirs is taken
            if callable(registry):
                registry = registry()
            if registry is None:
                continue
            other = registry.snapshot(attached=False)
            for table in ('counters', 'gauges', 'histograms'):
                snap[table].update((prefix + k, v)
                                   for k, v in other[table].items())
        return snap

    def merge(self, snapshot):
        """Add a snapshot's counts into this registry (counters and
        histogram buckets add; gauges last-write-win)."""
        if not snapshot:
            return
        for name, value in (snapshot.get('counters') or {}).items():
            self.counter(name).inc(value)
        for name, value in (snapshot.get('gauges') or {}).items():
            self.gauge(name).set(value)
        for name, hist in (snapshot.get('histograms') or {}).items():
            mine = self.histogram(name)
            with self._lock:
                for i, n in enumerate(hist.get('counts', ())):
                    if i < BUCKETS:
                        mine.counts[i] += n
                mine.sum += hist.get('sum', 0.0)
                mine.count += hist.get('count', 0)
                incoming = hist.get('exemplars')
                if incoming:
                    mine.exemplars = _merge_exemplars(
                        [mine.exemplars, incoming])

    # -- views ---------------------------------------------------------------

    def as_dict(self):
        """Flat ``name -> value`` view (counters + gauges), plus
        ``<hist>_p50_ms`` / ``<hist>_p99_ms`` / ``<hist>_count`` per
        histogram — the shape the diagnostics dicts are built from.
        This registry's own instruments only."""
        snap = self.snapshot(attached=False)
        out = dict(snap['counters'])
        out.update(snap['gauges'])
        for name, hist in snap['histograms'].items():
            out[name + '_count'] = hist['count']
            for label, q in (('p50', 0.5), ('p99', 0.99)):
                out['%s_%s_ms' % (name, label)] = ms(hist_quantile(hist, q))
        return out

    def render_prometheus(self):
        """Text exposition format (one scrape target per process); the
        namespace becomes the metric prefix."""
        snap = self.snapshot(attached=False)
        prefix = 'petastorm_tpu_'
        if snap['namespace']:
            prefix += _sanitize(snap['namespace']) + '_'
        lines = []
        for name, value in sorted(snap['counters'].items()):
            metric = prefix + _sanitize(name)
            lines += ['# TYPE %s counter' % metric,
                      '%s %s' % (metric, _fmt(value))]
        for name, value in sorted(snap['gauges'].items()):
            metric = prefix + _sanitize(name)
            lines += ['# TYPE %s gauge' % metric,
                      '%s %s' % (metric, _fmt(value))]
        for name, hist in sorted(snap['histograms'].items()):
            metric = prefix + _sanitize(name) + '_seconds'
            lines.append('# TYPE %s histogram' % metric)
            cumulative = 0
            for i, n in enumerate(hist['counts']):
                cumulative += n
                if n:
                    lines.append('%s_bucket{le="%g"} %d'
                                 % (metric, (2.0 ** (i + 1)) / 1e6,
                                    cumulative))
            lines.append('%s_bucket{le="+Inf"} %d' % (metric, hist['count']))
            lines.append('%s_sum %s' % (metric, _fmt(hist['sum'])))
            lines.append('%s_count %d' % (metric, hist['count']))
        return '\n'.join(lines) + '\n'


def _hist_dict(hist):
    """Plain-dict snapshot of one Histogram; 'exemplars' rides only when
    present so pre-ISSUE-13 snapshot shapes stay unchanged."""
    out = {'counts': list(hist.counts), 'sum': hist.sum,
           'count': hist.count}
    if hist.exemplars:
        out['exemplars'] = list(hist.exemplars)
    return out


def _merge_exemplars(exemplar_lists):
    """Worst-:data:`EXEMPLARS_KEPT` across exemplar lists, ascending by
    seconds (the Histogram-internal order) — exemplars are evidence
    refs, so merging re-ranks instead of adding."""
    merged = [e for exemplars in exemplar_lists for e in exemplars or ()]
    merged.sort(key=lambda e: e.get('seconds', 0.0))
    return merged[-EXEMPLARS_KEPT:]


def _sanitize(name):
    return ''.join(c if (c.isalnum() or c == '_') else '_' for c in name)


def _fmt(value):
    if isinstance(value, float):
        return repr(round(value, 6))
    return str(value)


def merge_snapshots(snapshots):
    """Pure fleet rollup: sum counters and histogram buckets across
    snapshots (gauges: last wins).  Stateless on purpose — the dispatcher
    re-merges the CURRENT heartbeat snapshots on every ``stats`` call, so
    nothing double-counts across calls."""
    merged = {'namespace': 'fleet', 'counters': {}, 'gauges': {},
              'histograms': {}}
    for snap in snapshots:
        if not snap:
            continue
        for name, value in (snap.get('counters') or {}).items():
            merged['counters'][name] = merged['counters'].get(name, 0) + value
        for name, value in (snap.get('gauges') or {}).items():
            merged['gauges'][name] = value
        for name, hist in (snap.get('histograms') or {}).items():
            mine = merged['histograms'].setdefault(
                name, {'counts': [0] * BUCKETS, 'sum': 0.0, 'count': 0})
            for i, n in enumerate(hist.get('counts', ())):
                if i < BUCKETS:
                    mine['counts'][i] += n
            mine['sum'] += hist.get('sum', 0.0)
            mine['count'] += hist.get('count', 0)
            if hist.get('exemplars'):
                mine['exemplars'] = _merge_exemplars(
                    [mine.get('exemplars'), hist['exemplars']])
    return merged


def hist_quantile(hist, q):
    """Quantile (seconds) of a histogram SNAPSHOT dict; None when empty.
    Returns the matched bucket's upper bound — a deliberate over-estimate
    that can never hide a slow stage under its bucket floor."""
    count = hist.get('count', 0)
    if not count:
        return None
    rank = max(1, int(math.ceil(q * count)))
    cumulative = []
    total = 0
    for n in hist['counts']:
        total += n
        cumulative.append(total)
    index = bisect.bisect_left(cumulative, rank)
    return (2.0 ** (index + 1)) / 1e6


def summarize_hist(hist):
    """The ONE canonical summary of a histogram snapshot dict:
    ``{'count', 'p50_ms', 'p99_ms', 'max_ms'}`` with the standard
    :func:`ms` rounding.  ``top``, ``petastorm-tpu-diagnose``, and the
    dispatcher ``stats`` rollup all print THESE numbers, so the same
    snapshot can never summarize three different ways downstream
    (quantiles are bucket upper bounds, like :func:`hist_quantile`;
    ``max_ms`` is the highest non-empty bucket's upper bound)."""
    count = int(hist.get('count', 0) or 0)
    out = {'count': count,
           'p50_ms': ms(hist_quantile(hist, 0.5)),
           'p99_ms': ms(hist_quantile(hist, 0.99)),
           'max_ms': None}
    counts = hist.get('counts') or ()
    for i in range(len(counts) - 1, -1, -1):
        if counts[i]:
            out['max_ms'] = ms((2.0 ** (i + 1)) / 1e6)
            break
    exemplars = hist.get('exemplars')
    if exemplars:
        # The worst observation's evidence ref (ISSUE 13) — present only
        # when the source histogram recorded exemplars, so pre-existing
        # summary consumers see the exact historical shape.
        worst = exemplars[-1]
        out['exemplar'] = {'ref': worst.get('ref'),
                           'ms': ms(worst.get('seconds'))}
    return out


def snapshot_delta(new, old):
    """``new - old`` for two snapshots of the SAME (cumulative) source:
    counters and histogram buckets subtract, gauges take ``new``'s value
    (they are instantaneous).  Negative deltas clamp to zero per
    instrument — a restarted worker resets its counters mid-window, and
    a clamped zero ("no progress seen") is the honest reading where a
    negative count would poison every ratio downstream.  ``old=None``
    returns ``new`` unchanged (delta from process start)."""
    if not new:
        return merge_snapshots([])
    if not old:
        return merge_snapshots([new])
    out = {'namespace': new.get('namespace', ''), 'counters': {},
           'gauges': dict(new.get('gauges') or {}), 'histograms': {}}
    old_counters = old.get('counters') or {}
    for name, value in (new.get('counters') or {}).items():
        out['counters'][name] = max(0, value - old_counters.get(name, 0))
    old_hists = old.get('histograms') or {}
    for name, hist in (new.get('histograms') or {}).items():
        prev = old_hists.get(name) or {}
        prev_counts = prev.get('counts') or ()
        counts = [max(0, n - (prev_counts[i] if i < len(prev_counts) else 0))
                  for i, n in enumerate(hist.get('counts') or ())]
        out['histograms'][name] = {
            'counts': counts,
            'sum': max(0.0, hist.get('sum', 0.0) - prev.get('sum', 0.0)),
            'count': max(0, hist.get('count', 0) - prev.get('count', 0)),
        }
        fresh = [e for e in hist.get('exemplars') or ()
                 if e not in (prev.get('exemplars') or ())]
        if fresh:
            # Exemplars are refs, not counts: a delta keeps only the
            # refs that APPEARED in this window — the cumulative worst-K
            # would cite an hours-stale batch as the window's p99
            # evidence.
            out['histograms'][name]['exemplars'] = fresh
    return out


def snapshot_all():
    """Snapshots of every live registry in this process (crash dumps)."""
    return [r.snapshot(attached=False) for r in list(_LIVE)]


def _process_instruments():
    registry = MetricsRegistry('process')
    # A collection's callback runs wherever an allocation happens, also on
    # a thread that holds this registry's lock (``snapshot`` builds dicts
    # under it): its instruments take none.  Collections never overlap.
    unlocked = contextlib.nullcontext()
    for name in ('gc_pause_s', 'gc_collections'):
        registry._counters[name] = Counter(unlocked)
    registry._histograms['gc_pause'] = Histogram(unlocked)
    for name in ('flight_tick', 'tick_late'):
        registry.counter(name + '_s')
        registry.histogram(name)
    return registry


_PROCESS = _process_instruments()


def process_registry():
    """The one registry of what happens to the process as a whole, not to
    a loader or a pool: garbage collections that stop every thread
    (``gc_pause_s``, ``gc_pause``, ``gc_collections``; ``flight.watch_gc``)
    and the flight recorder's own thread (``flight_tick``, how long a tick
    took; ``tick_late``, how much later than its interval the thread woke,
    which a thread that only sleeps does exactly when the process or the
    GIL stood still; each a histogram with its ``_s`` counter of seconds).
    Every instrument exists from the start, so a window with no collection
    reads 0 and not nothing."""
    return _PROCESS
