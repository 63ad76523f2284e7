"""Perf-trend store + regression gate over ``BENCH_HISTORY.jsonl``.

Every completed ``bench.py`` run appends its compact machine line (plus
a timestamp and round number) to ``BENCH_HISTORY.jsonl`` at the repo
root — an append-only trajectory of the repo's measured performance.

``--check`` compares a run (by default the newest history entry) against
the **median of the prior rounds** per tracked field, with a noise band
sized from the run-to-run variance of a shared bench host (A/B swings
±30% even at 9 interleaved repeats — a tighter band would alarm on
weather, a looser one would sleep through a real regression).  Only
host-plane throughput fields are tracked: the stable perf statements the
compact line exists for.

The gate FLIPS ON at history depth: with fewer than
``MIN_ROUNDS_TO_GATE`` prior rounds carrying a field, the check
annotates and exits 0 (a 1-round "trend" is a coin flip); from then on
a tracked field below ``median * (1 - band)`` exits 1.  Rounds that
recorded an error (``error`` / ``throughput_error`` / ``legs_failed``)
neither append cleanly nor count as history — a failed run must not
drag the median down and mask the next real regression.

Deliberately **stdlib-only and runnable as a bare file**
(``python petastorm_tpu/benchmark/trend.py --check``): the CI step runs
it from the checkout before any install, like the lint gate.
"""

import argparse
import json
import os
import sys
import time

__all__ = ['append_entry', 'load_history', 'check', 'check_integrity',
           'main', 'TRACKED_FIELDS', 'NOISE_BAND', 'MIN_ROUNDS_TO_GATE',
           'BACKEND_VOCABULARY']

#: Higher-is-better host-plane throughput fields from the compact line.
#: Scalars only (ipc_bytes_per_s is a dict on the compact line and is
#: represented here by its delivery-plane consumers instead).
TRACKED_FIELDS = (
    'value',
    'delivery_plane_images_per_sec_host',
    'delivery_plane_processpool_images_per_sec_host_shm',
    'delivery_plane_service_images_per_sec_host_w1',
    'epoch_cache_streaming_warm_images_per_sec',
    'transfer_plane_images_per_sec_coalesced',
    'adaptive_sched_images_per_sec_adaptive',
    'object_store_ingest_images_per_sec_plane',
    'cluster_cache_images_per_sec_warm',
    'dlrm_host_rows_per_s',
    # ISSUE 15: ledger-restored over cold dispatcher-restart TTFB — a
    # ratio, so host-load noise on the absolute TTFBs largely cancels.
    'control_plane_recovery_speedup',
    # ISSUE 16: burst-over-default row rate while both tenants are
    # active — a ratio (weight target 3.0), so host-load noise on the
    # absolute rates largely cancels.
    'multi_tenant_fair_share_ratio',
    # ISSUE 17: warm resident epoch over cold streamed+admitting epoch
    # wall-clock — a ratio from one pass, so host-load noise on the
    # absolute rates largely cancels.
    'device_residency_warm_over_cold',
    # ISSUE 18: pre-materialized first epoch over cold first epoch — a
    # ratio of interleaved passes, so host-load noise largely cancels.
    'first_epoch_warm_over_cold',
)

#: The ONLY backend labels ``bench.py`` ever emits: JAX platform names,
#: naming the platform the run really used.  A label outside this
#: vocabulary — a platform with a story attached — is proof the round did
#: not come from ``append_entry`` at the end of a real run, and the check
#: rejects it.
BACKEND_VOCABULARY = frozenset(('cpu', 'gpu', 'tpu'))

#: Fractional drop below the history median that counts as a regression.
NOISE_BAND = 0.30

#: Prior rounds a field needs before its check can gate (exit nonzero).
MIN_ROUNDS_TO_GATE = 3

#: Keys that mark a round as degraded — excluded from history medians.
_ERROR_KEYS = ('error', 'throughput_error', 'legs_failed')

_DEFAULT_HISTORY = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), 'BENCH_HISTORY.jsonl')


def history_path(path=None):
    return path or os.environ.get('PETASTORM_TPU_BENCH_HISTORY',
                                  _DEFAULT_HISTORY)


def load_history(path=None):
    """Every parseable entry, in file order.  Unparseable lines are
    skipped (an interrupted append must not wedge every future check)."""
    entries = []
    try:
        with open(history_path(path)) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    entry = json.loads(line)
                except ValueError:
                    continue
                if isinstance(entry, dict):
                    entries.append(entry)
    except OSError:
        pass
    return entries


def append_entry(compact, path=None):
    """Append one compact bench line to the history (best-effort: the
    trend store must never cost the bench artifact).  Degraded rounds
    (error keys set) are NOT appended — they would poison the medians.
    Returns the entry on append, None otherwise."""
    try:
        if not isinstance(compact, dict) or compact.get('value') is None:
            return None
        if any(compact.get(k) for k in _ERROR_KEYS):
            return None
        path = history_path(path)
        entry = dict(compact)
        # Microsecond resolution: the integrity rule treats an EXACT
        # duplicate ts as proof of a hand-copied round, so honest
        # appends (including rapid test appends) must never collide.
        now = time.time()
        entry['ts'] = (time.strftime('%Y-%m-%dT%H:%M:%S', time.gmtime(now))
                       + '.%06dZ' % int(round((now % 1.0) * 1e6) % 1000000))
        entry['round'] = len(load_history(path)) + 1
        with open(path, 'a') as f:
            f.write(json.dumps(entry, sort_keys=True, default=str) + '\n')
        return entry
    except Exception:  # noqa: BLE001 — history is memory, not the artifact
        return None


def check_integrity(entries):
    """Violation strings for rounds that cannot have grown through
    ``append_entry`` at the end of a real ``bench.py`` run.

    Two rules, each matching a pattern of the fabricated rounds this
    repo's history has actually carried (and purged) twice:

    * **duplicate timestamps** — ``append_entry`` stamps wall-clock
      seconds at append time and a bench run takes minutes, so two
      rounds sharing a ``ts`` means one was hand-copied;
    * **backend label outside the emitter vocabulary** — ``bench.py``
      emits the JAX platform name and nothing else; invented labels
      mean hand-written rounds.

    The check gates on these unconditionally (no minimum-rounds grace):
    an untrustworthy history makes every median it produces meaningless.
    """
    violations = []
    seen_ts = {}
    for entry in entries:
        label = 'round %s' % entry.get('round', '?')
        ts = entry.get('ts')
        if ts is not None:
            if ts in seen_ts:
                violations.append(
                    '%s: duplicate ts %s (also on round %s) — history '
                    'may only grow through append_entry at the end of a '
                    'real bench.py run' % (label, ts, seen_ts[ts]))
            else:
                seen_ts[ts] = entry.get('round', '?')
        backend = entry.get('backend')
        if backend is not None and backend not in BACKEND_VOCABULARY:
            violations.append(
                '%s: backend label %r is not one bench.py emits '
                '(truncated/hand-written round)' % (label, backend))
    return violations


def _median(values):
    ordered = sorted(values)
    mid = len(ordered) // 2
    if len(ordered) % 2:
        return float(ordered[mid])
    return (ordered[mid - 1] + ordered[mid]) / 2.0


def check(current=None, history=None, path=None, band=NOISE_BAND,
          min_rounds=MIN_ROUNDS_TO_GATE):
    """Compare ``current`` (default: newest history entry) against the
    median of the prior clean rounds per tracked field.

    Returns a report dict::

        {'rounds': <clean prior rounds>, 'gating': bool, 'band': band,
         'fields': {name: {'current', 'median', 'floor', 'rounds',
                           'gating', 'below_floor', 'ok'}},
         'regressions': [field, ...], 'integrity': [violation, ...],
         'ok': bool}

    Per-field ``ok`` is gate-aware (a below-floor value on a field whose
    gate is still off is annotated via ``below_floor`` but stays ok —
    the tool deliberately waved it through, and must say so
    consistently in text and JSON).  ``integrity`` violations
    (:func:`check_integrity` over the whole store, current included)
    fail the check regardless of the per-field gates.
    """
    entries = load_history(path) if history is None else list(history)
    if current is None:
        if not entries:
            return {'rounds': 0, 'gating': False, 'band': band,
                    'fields': {}, 'regressions': [], 'integrity': [],
                    'ok': True,
                    'note': 'no history yet — run bench.py to record '
                            'round 1'}
        current = entries[-1]
        entries = entries[:-1]
    integrity = check_integrity(entries + [current])
    clean = [e for e in entries if not any(e.get(k) for k in _ERROR_KEYS)]
    fields = {}
    regressions = []
    for name in TRACKED_FIELDS:
        value = current.get(name)
        if not isinstance(value, (int, float)) or isinstance(value, bool):
            continue
        prior = [e[name] for e in clean
                 if isinstance(e.get(name), (int, float))
                 and not isinstance(e.get(name), bool)]
        if not prior:
            fields[name] = {'current': value, 'median': None, 'floor': None,
                            'rounds': 0, 'gating': False,
                            'below_floor': False, 'ok': True}
            continue
        median = _median(prior)
        floor = median * (1.0 - band)
        gating = len(prior) >= min_rounds
        below = value < floor
        ok = (not gating) or not below
        fields[name] = {'current': value, 'median': round(median, 3),
                        'floor': round(floor, 3), 'rounds': len(prior),
                        'gating': gating, 'below_floor': below, 'ok': ok}
        if not ok:
            regressions.append(name)
    gating = any(f['gating'] for f in fields.values())
    return {'rounds': len(clean), 'gating': gating, 'band': band,
            'fields': fields, 'regressions': regressions,
            'integrity': integrity,
            'ok': not regressions and not integrity}


def _render(report):
    lines = ['bench-trend: %d clean prior round(s); gate %s'
             % (report['rounds'],
                'ON' if report['gating'] else
                'OFF (flips on at %d rounds per field)' % MIN_ROUNDS_TO_GATE)]
    if report.get('note'):
        lines.append('  ' + report['note'])
    for name, field in sorted(report['fields'].items()):
        if field['median'] is None:
            lines.append('  %-55s %12s  (no prior rounds)'
                         % (name, field['current']))
            continue
        if not field['below_floor']:
            status = 'OK'
        elif field['gating']:
            status = 'REGRESSION'
        else:
            status = 'below floor (not gating yet)'
        lines.append(
            '  %-55s %12s  vs median %s (floor %s, %d rounds%s) %s'
            % (name, field['current'], field['median'], field['floor'],
               field['rounds'], '' if field['gating'] else ', not gating',
               status))
    if report['regressions']:
        lines.append('REGRESSION in gating field(s): %s (below median '
                     'minus the %.0f%% noise band)'
                     % (', '.join(report['regressions']),
                        100 * report.get('band', NOISE_BAND)))
    for violation in report.get('integrity', ()):
        lines.append('INTEGRITY: ' + violation)
    return '\n'.join(lines)


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog='petastorm-tpu-bench-trend',
        description=__doc__.split('\n\n')[0])
    parser.add_argument('--check', action='store_true',
                        help='compare the newest (or --current) round '
                             'against the history medians')
    parser.add_argument('--history', default=None,
                        help='history file (default: repo '
                             'BENCH_HISTORY.jsonl)')
    parser.add_argument('--current', default=None,
                        help='JSON file holding the compact line of the '
                             'run to check (default: newest history '
                             'entry)')
    parser.add_argument('--band', type=float, default=NOISE_BAND,
                        help='noise band as a fraction (default %.2f, '
                             'the measured host A/B variance)'
                             % NOISE_BAND)
    parser.add_argument('--json', action='store_true',
                        help='emit the report as JSON')
    args = parser.parse_args(argv)
    if not args.check:
        parser.error('nothing to do: pass --check')
    current = None
    if args.current:
        try:
            with open(args.current) as f:
                current = json.load(f)
        except (OSError, ValueError) as e:
            print('cannot read --current %s: %s' % (args.current, e),
                  file=sys.stderr)
            return 2
    report = check(current=current, path=args.history, band=args.band)
    if args.json:
        print(json.dumps(report, sort_keys=True))
    else:
        print(_render(report))
    return 0 if report['ok'] else 1


if __name__ == '__main__':
    sys.exit(main())
