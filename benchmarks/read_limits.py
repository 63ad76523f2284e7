"""Reads, on the chip, what the limits of a cell's training numbers are set from.

    python3 benchmarks/read_limits.py --workload <cell> --seeds 12 [--controls 3]

For each seed, in one process: the cell's timed path (its reader, loader and
jitted step, at the cell's batch and widths, over a dataset just long enough
for the first steps) is driven through its first steps; then, with the
program's state freed, the plain reference follows the same steps on the rows
as the files hold them.  The gaps between the two are the *lower* readings.  On
the first ``--controls`` seeds the control (the reference in the next precision
down) and the planted faults are put in the program's place and read against
the reference: the *upper* readings.  One JSON line a seed, a summary at the
end; ``PERF.md`` lists the readings beside each limit.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

import catalog  # noqa: E402
import oracle  # noqa: E402
import run as harness  # noqa: E402
from timed_path import TimedPath  # noqa: E402

NUMBERS = ('loss_gap', 'first_sample_loss_gap', 'sample_loss_gap', 'grad_gap_median',
           'change_gap_median', 'grad_gap', 'change_gap')


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--seeds', type=int, default=12)
    parser.add_argument('--first-seed', type=int, default=2_500_000_000)
    parser.add_argument('--controls', type=int, default=3)
    parser.add_argument('--tiny', action='store_true')
    parser.add_argument('--no-witnesses', action='store_true')
    args = parser.parse_args(argv)
    cell, spec, module, traffic = catalog.cell(args.workload)
    import numpy as np
    device = harness.find_device(cell['chips'], args.tiny)
    harness.use_compile_cache(args.tiny)
    steps = spec['correct']['steps']
    rows = []
    config = module.Config(spec, tiny=args.tiny, **spec['correct']['reading_sizes'])
    for index in range(args.seeds):
        seed = args.first_seed + 7919 * index
        key = oracle.key_of(seed)
        data = harness.ensure_dataset(config, spec['name'], seed)
        with TimedPath(config, traffic, data, seed, key, args.tiny) as path:
            path.first_steps(steps)
            program, first_host = path.readings()
            path.free()
        del path
        batches = config.reference_batches(
            data, [np.asarray(config.row_ids(b)) for b in first_host])
        reference = config.reference(key, batches)
        row = {'seed': seed, 'device': device,
               'program': oracle.training_gaps(program, reference)}
        if index < args.controls:
            row['control'] = oracle.training_gaps(
                config.reference(key, batches, precision=spec['correct']['control']),
                reference)
            for fault in spec['correct']['faults']:
                row[fault] = oracle.training_gaps(
                    config.reference(key, batches, fault=fault), reference)
            for witness in ([] if args.no_witnesses
                            else spec['correct'].get('witnesses', [])):
                row[witness] = oracle.training_gaps(
                    config.reference(key, batches, precision=witness), reference)
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {'workload': args.workload, 'device': device, 'seeds': len(rows)}
    for name in NUMBERS:
        summary[name] = {
            'lower_max_over_seeds': max(r['program'][name] for r in rows),
            'program_all': sorted(r['program'][name] for r in rows)}
        for kind in ['control'] + spec['correct']['faults'] \
                + spec['correct'].get('witnesses', []):
            got = [r[kind][name] for r in rows if kind in r]
            if got:
                summary[name][kind + '_min'] = min(got)
    print(json.dumps(summary), flush=True)


if __name__ == '__main__':
    main()
