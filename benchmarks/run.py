"""One run of one cell of the benchmark.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; everything that
belongs to its configuration, its traffic mix or a per-layer metric is found
by name under ``benchmarks/`` (see ``benchmarks/README.md``) and never by an
``if`` on a name.  The last line of stdout is the result; every earlier line is
one JSON object naming the device.  Without a TPU the run fails and prints no
result line.  ``--tiny`` rehearses the same path on the CPU at the
configuration's ``tiny`` sizes and prints a line with no ``metrics`` in it.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, HERE]

import catalog  # noqa: E402
import oracle  # noqa: E402
from timed_path import STEP_NAME, TimedPath  # noqa: E402

CACHE = os.path.join(HERE, '.cache')
WINDOW_SPAN = 'bench/window'


class Failed(Exception):
    """The run cannot give a result: no chip, or fewer chips than asked."""


def say(device, **facts):
    """One line of stdout: the facts, when (seconds since the process started)
    and on which device."""
    print(json.dumps(dict(facts, at_s=round(time.monotonic() - T_START, 3),
                          device=device), default=str), flush=True)


class CompileMeter(object):
    """Executables this process got (compiled, or read back from the
    persistent cache) and the seconds that took (``chip_smoke.py``)."""

    def __init__(self):
        import jax.monitoring
        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == '/jax/core/compile/backend_compile_duration':
            self.seconds += seconds
            self.programs += 1

    def _event(self, event, **_):
        if event == '/jax/compilation_cache/cache_hits':
            self.cache_hits += 1


def find_device(chips, tiny):
    import jax
    devices = jax.devices()
    device = {'platform': devices[0].platform, 'kind': devices[0].device_kind,
              'count': len(devices)}
    if tiny:
        if device['platform'] != 'cpu':
            raise Failed('--tiny is a CPU rehearsal: set JAX_PLATFORMS=cpu')
    elif device['platform'] != 'tpu' or device['count'] < chips:
        raise Failed('this cell needs %d TPU chip(s); jax found %r'
                     % (chips, device))
    return device


def ensure_dataset(config, name, seed):
    """The cell's dataset, written on the first run with this seed into a
    fixed directory of the checkout and reused afterwards."""
    path = os.path.join(CACHE, 'data', '%s-%d-%d' % (name, config.rows, seed))
    if not os.path.isdir(path):
        partial = path + '.partial'
        shutil.rmtree(partial, ignore_errors=True)
        config.write_dataset(partial, seed)
        os.rename(partial, path)
    return path


def use_compile_cache(tiny):
    """JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR`` says, else at
    one fixed path of the checkout; small programs are cached too."""
    import jax
    cache_dir = os.environ.get('JAX_COMPILATION_CACHE_DIR') \
        or os.path.join(CACHE, 'jax-tiny' if tiny else 'jax')
    jax.config.update('jax_compilation_cache_dir', cache_dir)
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)
    return cache_dir


def metric_deltas(before, after):
    """What the loader's counters and histograms gained between two snapshots."""
    counters = {k: v - before['counters'].get(k, 0)
                for k, v in after['counters'].items()}
    hists = {}
    for name, h in after['histograms'].items():
        b = before['histograms'].get(name, {'counts': [0] * len(h['counts']),
                                            'sum': 0.0, 'count': 0})
        hists[name] = {'counts': [x - y for x, y in zip(h['counts'], b['counts'])],
                       'sum': h['sum'] - b['sum'], 'count': h['count'] - b['count']}
    return counters, hists


def run(args):
    cell, spec, module, traffic = catalog.cell(args.workload)
    import jax
    device = find_device(cell['chips'], args.tiny)
    cache_dir = use_compile_cache(args.tiny)
    meter = CompileMeter()
    from petastorm_tpu import native
    if native.get_lib() is None:
        raise Failed('the native decode library did not build or load')

    config = module.Config(spec, tiny=args.tiny)
    peaks = None if args.tiny else catalog.peaks(device['kind'])
    key = oracle.key_of(args.seed)
    rng = np.random.default_rng(args.seed)
    data = ensure_dataset(config, spec['name'], args.seed)
    t_data = time.monotonic()

    # one object: the compiled step with its state, warmed by its first steps
    # through the window's own feed and handed on to the window
    with TimedPath(config, traffic, data, args.seed, key, args.tiny) as path:
        path.first_steps(spec['correct']['steps'])
        path.settle()
        before = path.loader.metrics.snapshot()
        programs_before = meter.programs
        setup_s = time.monotonic() - T_START
        say(device, phase='setup', setup_s=setup_s, dataset_s=t_data - T_START,
            compile_seconds=meter.seconds, programs=meter.programs,
            cache_hits=meter.cache_hits, cache_dir=cache_dir)

        trace_dir = os.path.join(CACHE, 'trace', cell['name'])
        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                window = path.window(args.seconds, rng)
        finally:
            if args.trace:
                jax.profiler.stop_trace()
        after = path.loader.metrics.snapshot()
        in_window_programs = meter.programs - programs_before
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        # the allocator counts a program's temporaries as reserved, not in use
        memory_peak = max(s.get('peak_bytes_in_use', 0)
                          + s.get('peak_bytes_reserved', 0) for s in stats)
        # what the window delivered, to the host before the loader goes
        ids = [np.asarray(x) for x in path.ids]
        sampled = [jax.device_get(b) for b in window.pop('sampled')]
        program_readings, first_host = path.readings()
        path.free()
    del path
    counters, hists = metric_deltas(before, after)
    slowest = sorted(enumerate(window['step_s']), key=lambda kv: -kv[1])[:3]
    say(device, phase='window', steps=window['steps'], window_s=window['window_s'],
        in_window_programs=in_window_programs, memory_stats=stats[0],
        slowest_steps_ms=[[i, round(1e3 * s, 1)] for i, s in slowest],
        counters=counters)

    # correct: the delivered rows against the files, the loader's own counts,
    # and the first steps against the plain reference (program state freed)
    numbers = oracle.Numbers(spec['correct']['limits'])
    numbers.add('rows_miscounted', oracle.miscounted(
        np.concatenate(ids), config.all_row_ids(data)), limit=0)
    numbers.add('delivered_error', max(
        config.delivered_error(data, b) for b in sampled + first_host))
    for name in traffic['expect_zero']:
        numbers.add(name, counters.get(name, 0), limit=0)
    for name in traffic['expect_positive']:
        numbers.add(name + '_missing', int(counters.get(name, 0) <= 0), limit=0)
    t_ref = time.monotonic()
    ref_batches = config.reference_batches(
        data, [np.asarray(config.row_ids(b)) for b in first_host])
    reference = config.reference(key, ref_batches)
    oracle.compare_training(numbers, program_readings, reference)
    say(device, phase='reference', seconds=time.monotonic() - t_ref)

    result = {'correct': numbers.correct(), 'attempted': window['steps'], 'failed': 0}
    if args.tiny:
        result.update(tiny=True, platform=device['platform'])
    else:
        context = {
            'cell': cell, 'config': config, 'peaks': peaks, 'counters': counters,
            'histograms': hists, 'window_s': window['window_s'],
            'steps': window['steps'], 'samples': window['steps'] * config.batch,
            'wait_s': window['wait_s'], 'step_s': window['step_s'],
            'setup_s': setup_s, 'trace': None}
        device_out = dict(device, memory_peak_bytes=memory_peak)
        if args.trace:
            import trace_reduce
            reduced = trace_reduce.reduce_dir(trace_dir, 'jit_' + STEP_NAME,
                                              WINDOW_SPAN)
            shutil.rmtree(trace_dir, ignore_errors=True)
            context['trace'] = reduced
            device_out.update(busy_s=reduced['busy_s'], window_s=reduced['window_s'])
            result['breakdown'] = {'device_ops': reduced['device_ops'][:10],
                                   'idle_gaps': reduced['idle_gaps'][:10]}
        wanted = catalog.metrics_of(
            cell['name'], 'per_layer' if args.trace else 'end_to_end')
        metrics = {}
        for m in wanted:
            module = catalog.metric_module(m['name'])
            value = module.read(context)
            if value is not None:
                metrics[m['name']] = {'value': float(value), 'unit': m['unit']}
                if hasattr(module, 'explain'):
                    say(device, metric=m['name'], **module.explain(context))
        result.update(metrics=metrics, device=device_out)
    say(device, phase='done')
    result['checks'] = numbers.as_dict()
    numbers.print_last(sys.stderr)
    return result


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seed', type=int, default=0)
    parser.add_argument('--seconds', type=float, default=10.0)
    parser.add_argument('--trace', type=int, choices=(0, 1), default=0)
    parser.add_argument('--tiny', action='store_true',
                        help='CPU rehearsal at the tiny sizes; no metrics printed')
    args = parser.parse_args(argv)
    try:
        result = run(args)
    except Failed as e:
        print('benchmarks/run.py: %s' % e, file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
