"""Bare reader throughput: rows/sec after warmup, no training in the loop.

Parity: reference ``petastorm/benchmark/throughput.py :: reader_throughput,
BenchmarkResult`` — knobs mirror ``make_reader`` (pool type, workers count).
Every accepted knob is honored: ``loaders_count`` runs N concurrent readers
and reports aggregate throughput, ``spawn_new_process`` re-runs the
measurement in a fresh interpreter (clean caches/GIL state), and unknown
``read_method`` values raise instead of being silently ignored.
"""

import threading
import time
from collections import namedtuple

BenchmarkResult = namedtuple('BenchmarkResult',
                             ['rows_per_second', 'rows_read', 'duration_s', 'warmup_rows'])


def _one_reader_throughput(dataset_url, field_regex, warmup_rows, measure_rows,
                           pool_type, workers_count, storage_options, reader_kwargs):
    from petastorm_tpu.reader import make_reader

    with make_reader(dataset_url, schema_fields=field_regex,
                     reader_pool_type=pool_type, workers_count=workers_count,
                     num_epochs=None, storage_options=storage_options,
                     **reader_kwargs) as reader:
        read = 0
        for _ in reader:
            read += 1
            if read >= warmup_rows:
                break
        start = time.monotonic()
        measured = 0
        for _ in reader:
            measured += 1
            if measured >= measure_rows:
                break
        duration = time.monotonic() - start
    return measured, duration


def reader_throughput(dataset_url, field_regex=None, warmup_rows=100, measure_rows=1000,
                      pool_type='thread', loaders_count=1, workers_count=10,
                      read_method='read', spawn_new_process=False, storage_options=None,
                      **reader_kwargs):
    """Measure rows/sec of the bare reader.

    ``loaders_count``: number of concurrent readers (each with its own pool);
    aggregate = total rows / wall time from common start to last finish.
    ``spawn_new_process``: run the whole measurement in a freshly exec'd
    interpreter so importer/allocator state from this process can't skew it.
    ``read_method``: ``'read'`` (iterate rows; the only method a petastorm
    reader has — kept for reference-CLI parity).
    """
    if read_method != 'read':
        raise NotImplementedError(
            'read_method=%r is not supported (only "read"); refusing to '
            'silently measure something else' % (read_method,))
    if loaders_count is None:
        loaders_count = 1
    if loaders_count < 1:
        raise ValueError('loaders_count must be >= 1')

    if spawn_new_process:
        return _throughput_in_subprocess(
            dataset_url, field_regex, warmup_rows, measure_rows, pool_type,
            loaders_count, workers_count, storage_options, reader_kwargs)

    if loaders_count == 1:
        measured, duration = _one_reader_throughput(
            dataset_url, field_regex, warmup_rows, measure_rows, pool_type,
            workers_count, storage_options, reader_kwargs)
        return BenchmarkResult(
            rows_per_second=measured / duration if duration else float('inf'),
            rows_read=measured, duration_s=duration, warmup_rows=warmup_rows)

    # N concurrent loaders: construct + warm all readers first, release them
    # into the timed window together, clock from the common start to the last
    # finish (conservative: includes straggler tail).  Warmup runs one thread
    # per reader so no reader sits idle pre-buffering while siblings warm
    # (each pool's bounded results queue caps residual pre-buffer to
    # results_queue_size rows — keep measure_rows well above it).
    from petastorm_tpu.reader import make_reader

    readers = [make_reader(dataset_url, schema_fields=field_regex,
                           reader_pool_type=pool_type, workers_count=workers_count,
                           num_epochs=None, storage_options=storage_options,
                           **reader_kwargs)
               for _ in range(loaders_count)]
    try:
        def warm(reader):
            read = 0
            for _ in reader:
                read += 1
                if read >= warmup_rows:
                    break

        warmers = [threading.Thread(target=warm, args=(r,), daemon=True)
                   for r in readers]
        for t in warmers:
            t.start()
        for t in warmers:
            t.join()
        barrier = threading.Barrier(loaders_count + 1)
        counts = [0] * loaders_count
        errors = []

        def drain(i, reader):
            try:
                barrier.wait()
                for _ in reader:
                    counts[i] += 1
                    if counts[i] >= measure_rows:
                        break
            except Exception as e:  # noqa: BLE001 — re-raised in caller
                errors.append(e)

        threads = [threading.Thread(target=drain, args=(i, r), daemon=True)
                   for i, r in enumerate(readers)]
        for t in threads:
            t.start()
        barrier.wait()
        start = time.monotonic()
        for t in threads:
            t.join()
        duration = time.monotonic() - start
        if errors:
            raise errors[0]
    finally:
        for reader in readers:
            reader.stop()
        for reader in readers:
            reader.join()
    total = sum(counts)
    return BenchmarkResult(
        rows_per_second=total / duration if duration else float('inf'),
        rows_read=total, duration_s=duration, warmup_rows=warmup_rows)


def _throughput_in_subprocess(dataset_url, field_regex, warmup_rows, measure_rows,
                              pool_type, loaders_count, workers_count,
                              storage_options, reader_kwargs):
    """Fresh-interpreter measurement; kwargs must be JSON-serializable."""
    import json
    import os
    import subprocess
    import sys

    try:
        payload = json.dumps({
            'dataset_url': dataset_url, 'field_regex': field_regex,
            'warmup_rows': warmup_rows, 'measure_rows': measure_rows,
            'pool_type': pool_type, 'loaders_count': loaders_count,
            'workers_count': workers_count, 'storage_options': storage_options,
            'reader_kwargs': reader_kwargs,
        })
    except TypeError as e:
        raise NotImplementedError(
            'spawn_new_process requires JSON-serializable reader kwargs '
            '(custom filesystem/predicate objects cannot cross the exec '
            'boundary): %s' % e) from e
    code = (
        'import json, sys\n'
        'from petastorm_tpu.benchmark.throughput import reader_throughput\n'
        'a = json.loads(sys.stdin.read())\n'
        'r = reader_throughput(a["dataset_url"], field_regex=a["field_regex"],\n'
        '                      warmup_rows=a["warmup_rows"], measure_rows=a["measure_rows"],\n'
        '                      pool_type=a["pool_type"], loaders_count=a["loaders_count"],\n'
        '                      workers_count=a["workers_count"],\n'
        '                      storage_options=a["storage_options"], **a["reader_kwargs"])\n'
        'print(json.dumps(r._asdict()))\n'
    )
    env = dict(os.environ)
    # The child measures host-side reader throughput only: a chip belongs
    # to one process, and that process is the parent — same discipline as
    # workers_pool/exec_in_new_process.py.
    env['JAX_PLATFORMS'] = 'cpu'
    proc = subprocess.run([sys.executable, '-c', code], input=payload,
                          capture_output=True, text=True, env=env)
    if proc.returncode != 0:
        raise RuntimeError('spawned benchmark process failed:\n%s'
                           % proc.stderr[-4000:])
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    return BenchmarkResult(**result)
