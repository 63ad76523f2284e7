"""Compile every configuration's programs for a described TPU v5e, no chip.

    JAX_PLATFORMS=cpu python3 benchmarks/rehearse_compile.py [--config <name>]

For each configuration of ``BENCHMARK.json`` (or the named one, which need not
be there yet) it compiles the programs that the configuration lists under
``rehearsal_programs`` (the step at the cell's shapes, the plain reference and
its control) with the chip's own compiler and prints ``memory_analysis()``.
What the compiler refuses here costs no chip time.  A compile that passes is
not a chip run: nothing here is a time or a rate.
"""

import argparse
import json
import os
import sys
import time

os.environ.setdefault('TPU_LOG_DIR', 'disabled')
os.environ.setdefault('JAX_PLATFORMS', 'cpu')

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

import catalog  # noqa: E402
import oracle  # noqa: E402


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    parser.add_argument('--config', action='append')
    parser.add_argument('--only', help='compile only the program of this name')
    parser.add_argument('--sizes', default='{}',
                        help='JSON of sizes to try in place of the file\'s, '
                             'e.g. {"table_shards": 8}')
    args = parser.parse_args(argv)
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update('jax_enable_compilation_cache', False)
    topo = topologies.get_topology_desc(platform='tpu', topology_name='v5e:2x2')
    one_chip = SingleDeviceSharding(topo.devices[0])
    names = args.config or [c['name'] for c in catalog.benchmark()['configs']]
    for name in names:
        base = os.path.join(HERE, 'configs', name)
        spec = catalog._json(base + '.json')
        module = catalog._module(base + '.py')
        config = module.Config(spec, **json.loads(args.sizes))
        key = jax.eval_shape(lambda: oracle.key_of(0))
        state = jax.eval_shape(config.init_state, key)
        norms = oracle.LeafNorms(config)
        harness_programs = [
            ('init_state', config.init_state, (key,), ()),
            ('first_gradient_norms', norms.first_gradient, (state, key), ()),
            ('change_norms', norms.change, (state, key), ())]
        for program, fn, shapes, donated in \
                config.rehearsal_programs(key) + harness_programs:
            if args.only and program not in args.only.split(','):
                continue
            placed = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
                shapes)
            t0 = time.monotonic()
            compiled = jax.jit(fn, donate_argnums=donated).lower(*placed).compile()
            m = compiled.memory_analysis()
            print(json.dumps({
                'config': name, 'program': program,
                'compiled_for': str(topo.devices[0].device_kind),
                'compile_seconds': round(time.monotonic() - t0, 1),
                'argument_bytes': m.argument_size_in_bytes,
                'output_bytes': m.output_size_in_bytes,
                'alias_bytes': m.alias_size_in_bytes,
                'temp_bytes': m.temp_size_in_bytes,
                'peak_bytes': m.argument_size_in_bytes + m.output_size_in_bytes
                - m.alias_size_in_bytes + m.temp_size_in_bytes}), flush=True)


if __name__ == '__main__':
    main()
