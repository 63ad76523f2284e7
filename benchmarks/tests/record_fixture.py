"""Records the small trace that ``test_trace_reduce.py`` checks the reduction
against, on a chip, and prints what the trace holds (planes, lines, first
events) for a look by hand.

    chiprun -- python3 benchmarks/tests/record_fixture.py

Writes ``chiprun_out/fixture/fixture.xplane.pb``; a copy of it is kept as
``benchmarks/tests/data/fixture.xplane.pb``.
"""

import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import trace_reduce  # noqa: E402


def main():
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData, ProfileOptions, TraceAnnotation

    def pt_bench_train_step(x):
        for _ in range(8):
            x = jnp.tanh(x @ x) * 0.5
        return x

    step = jax.jit(pt_bench_train_step)
    x = jnp.full((2048, 2048), 0.01, jnp.bfloat16)
    step(x).block_until_ready()
    out = os.path.abspath('chiprun_out/fixture')
    shutil.rmtree(out, ignore_errors=True)
    options = ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=options)
    with TraceAnnotation('bench/window'):
        for i in range(4):
            with TraceAnnotation('pt/host_batch'):
                time.sleep(0.004)
            with TraceAnnotation('pt/device_put'):
                time.sleep(0.001)
            x = step(x)
            if i == 1:
                x.block_until_ready()
                time.sleep(0.003)          # idle under no span: "train loop"
        x.block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(out, 'plugins', 'profile', '*', '*.xplane.pb'))[0]
    kept = os.path.join(out, 'fixture.xplane.pb')
    shutil.copy(path, kept)
    shutil.rmtree(os.path.join(out, 'plugins'))
    print(json.dumps({'device': jax.devices()[0].device_kind,
                      'bytes': os.path.getsize(kept)}))
    for plane in ProfileData.from_file(kept).planes:
        print('PLANE %r' % plane.name)
        for line in plane.lines:
            events = list(line.events)
            print('  LINE %r: %d events; first: %s' % (
                line.name, len(events),
                [(e.name, e.start_ns, e.duration_ns) for e in events[:4]]))
    try:
        print(json.dumps(trace_reduce.reduce(
            trace_reduce.extract(kept, ('bench/window',)),
            'jit_pt_bench_train_step', 'bench/window')))
    except Exception as e:        # shown, not hidden: this script is for looking
        print('REDUCE FAILED: %r' % (e,))


if __name__ == '__main__':
    main()
