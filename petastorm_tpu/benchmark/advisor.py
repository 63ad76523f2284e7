"""Pipeline bottleneck advisor: name the regime, point at the fix.

The reference leaves diagnosis to the user (its only signal is
``Reader.diagnostics`` counters); tf.data's AUTOTUNE showed that the
pipeline itself has enough information to say WHERE time goes.  This is
the analysis half of that idea, deliberately without the knob-twiddling
half: TPU input pipelines have a small, discrete set of regimes, each
with a known best response in this framework (see
``docs/performance.md``), so a report that names the regime — with the
numbers that prove it — beats a controller silently nudging thread
counts.

Usage::

    monitor = StallMonitor()
    for batch in monitor.wrap(loader):
        step(batch)
    print(format_report(diagnose(loader, monitor)))

Every signal is already collected in the hot path (``DataLoader.stats``
per-stage wall time, pool ``decode_utilization``, ``StallMonitor``
wall-vs-step time); diagnose() only reads them.
"""

__all__ = ['diagnose', 'format_report']

#: stall_pct at or below this is "the chip is the bottleneck" — the
#: BASELINE.json north-star target.
HEALTHY_STALL_PCT = 2.0


def diagnose(loader, monitor=None):
    """Classify the pipeline's bottleneck regime from live counters.

    Args:
        loader: a ``petastorm_tpu.jax`` loader that has been iterated
            (its ``stats`` are populated) — its ``reader`` supplies pool
            diagnostics when still alive.
        monitor: optional ``StallMonitor`` that wrapped the iteration;
            without it the report covers stage balance only (no
            chip-vs-host verdict).

    Returns a dict: ``regime`` (one of ``chip_bound``, ``decode_bound``,
    ``io_bound``, ``transport_bound``, ``transform_bound``, ``unknown``),
    ``evidence`` (the numbers that picked it), and ``suggestions``
    (ordered, most effective first).
    """
    stats = dict(getattr(loader, 'stats', None) or {})
    batches = stats.get('batches', 0)
    evidence = {'batches': batches}
    if not batches:
        return {'regime': 'unknown', 'evidence': evidence,
                'suggestions': ['iterate the loader before diagnosing']}

    per_batch = {
        'host_batch_ms': 1000.0 * stats.get('host_batch_s', 0.0) / batches,
        'transform_ms': 1000.0 * stats.get('transform_s', 0.0) / batches,
        'device_put_ms': 1000.0 * stats.get('device_put_s', 0.0) / batches,
    }
    evidence.update({k: round(v, 3) for k, v in per_batch.items()})

    decode_util = None
    reader = getattr(loader, 'reader', None)
    if reader is not None:
        try:
            diag = reader.diagnostics
            decode_util = diag.get('decode_utilization')
            evidence['decode_utilization'] = decode_util
            evidence['pool'] = diag.get('pool')
        except Exception:  # noqa: BLE001 — reader may be stopped
            pass

    stall_pct = None
    if monitor is not None:
        report = monitor.report()
        stall_pct = report.get('stall_pct')
        evidence['stall_pct'] = stall_pct
        if report.get('steps'):
            evidence['step_ms'] = round(
                1000.0 * report['step_s'] / report['steps'], 3)

    if stall_pct is not None and stall_pct <= HEALTHY_STALL_PCT:
        return {'regime': 'chip_bound', 'evidence': evidence,
                'suggestions': ['healthy: the device is the bottleneck; '
                                'spend effort on the model, not the loader']}

    # Stage balance decides the host-side regime.
    dominant = max(per_batch, key=per_batch.get)
    total_host = sum(per_batch.values())
    if total_host <= 0:
        return {'regime': 'unknown', 'evidence': evidence,
                'suggestions': ['no host time recorded; wrap the iteration '
                                'with StallMonitor for a chip-side verdict']}

    if dominant == 'host_batch_ms':
        if decode_util is not None and decode_util < 0.5:
            return {'regime': 'io_bound', 'evidence': evidence, 'suggestions': [
                'decode threads are starved (decode_utilization %.2f): raise '
                'workers_count / results_queue_size' % decode_util,
                "cache remote row groups locally: cache_type='local-disk'",
                'check storage throughput (GCS egress, disk)']}
        return {'regime': 'decode_bound', 'evidence': evidence, 'suggestions': [
            'decode saturates the host: more host cores scale it linearly',
            'declared resizes fuse natively: ResizeImages (keeps the '
            'columnar plane; DCT-scaled decode for >=4x reductions)',
            'multi-epoch runs: DiskCachedDataLoader (decode once, stream '
            'later epochs) or DeviceInMemDataLoader if the shard fits HBM',
            'echo=e divides the required decode rate by e (data echoing; '
            'augment on device so echoes differ)']}
    if dominant == 'transform_ms':
        return {'regime': 'transform_bound', 'evidence': evidence, 'suggestions': [
            'move the transform into the worker pool (TransformSpec) so it '
            'parallelizes and overlaps the step',
            'image resizes: ResizeImages fuses into the native decode',
            'normalization/augmentation: do it on device inside the jitted '
            'step (petastorm_tpu.jax.augment) — bandwidth-trivial there']}
    # device_put dominates
    return {'regime': 'transport_bound', 'evidence': evidence, 'suggestions': [
        'fuse steps per dispatch: scan_batches(step_fn, carry, k) cuts '
        'dispatch overhead k-fold; scan_epochs removes it entirely for '
        'HBM-cached epochs',
        'transfer the smallest dtype (uint8 images; cast/normalize on '
        'device), and check the host-device link (PCIe generation)']}


def format_report(result):
    """One human-readable block from a :func:`diagnose` result."""
    lines = ['pipeline regime: %s' % result['regime']]
    ev = result['evidence']
    lines.append('  evidence: ' + ', '.join(
        '%s=%s' % (k, ev[k]) for k in sorted(ev) if ev[k] is not None))
    for i, s in enumerate(result['suggestions'], 1):
        lines.append('  %d. %s' % (i, s))
    return '\n'.join(lines)
