"""Chooses the ``layout_seed`` of a configuration under the ``packed`` traffic,
on the CPU, with no chip.

    JAX_PLATFORMS=cpu python3 benchmarks/choose_layout.py --workload <cell> [--candidates 32]

Under ``packed`` a step's length follows its batch: the flash kernels visit
only the tiles that can hold a pair inside a document.  A run times one fixed
sample of the configuration's length law (``dataset.layout_seed`` fixes the
lengths and the reader's order), and that sample should be a middling one, so
that the cell's medians stand for the law and not for a lucky draw.  For each
candidate the cell's own reader and loader pack the window's batches (those
after the compared and the settling steps), ``ops.flash_attention.tile_visits``
counts at the kernels' own tile size what share of the causal triangle each
visits, and the candidate whose window's mean and 90th-percentile share lie
nearest the candidates' medians is taken.  One JSON line a candidate, the
choice last; write it into the configuration's file by hand.
"""

import argparse
import importlib
import itertools
import json
import math
import os
import shutil
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]

import catalog  # noqa: E402
import run as harness  # noqa: E402
from timed_path import open_loader  # noqa: E402


def visit_shares(config, traffic, data, skip, steps):
    """The share of the causal triangle's tiles that the flash kernels visit in
    each of the ``steps`` batches after the first ``skip``, packed by the
    traffic's own reader and loader."""
    import numpy as np
    # the module: ``petastorm_tpu.ops`` exports the function under its name
    flash_attention = importlib.import_module('petastorm_tpu.ops.flash_attention')
    block = flash_attention.block_default(config.max_len)
    # tiny: the mix's CPU arguments (the transfer plane on, as on the chip)
    with open_loader(config, traffic, data, seed=0, tiny=True) as loader:
        batches = [np.asarray(b['segment_ids'])
                   for b in itertools.islice(loader, skip, skip + steps)]
    counts = [flash_attention.tile_visits(b, block, block) for b in batches]
    return [visited / triangle for visited, triangle, _ in counts]


def nearest_rank(values, q):
    """The ``q`` quantile by the rule ``metrics/step_p90_ms.py`` reads a
    window's steps by."""
    ordered = sorted(values)
    return ordered[min(len(ordered), math.ceil(q * len(ordered))) - 1]


def choose(readings):
    """``readings``: {candidate: (mean share, 90th-percentile share)}.  The
    candidate whose two shares lie nearest the candidates' medians, by the sum
    of the two relative distances."""
    medians = [statistics.median(r[i] for r in readings.values()) for i in (0, 1)]
    return min(readings, key=lambda k: sum(
        abs(readings[k][i] - medians[i]) / medians[i] for i in (0, 1))), medians


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    parser.add_argument('--workload', required=True)
    parser.add_argument('--candidates', type=int, default=32)
    parser.add_argument('--window-steps', type=int, default=34,
                        help='steps a window of run_seconds holds (the ledger)')
    parser.add_argument('--tiny', action='store_true')
    args = parser.parse_args(argv)
    _, spec, module, traffic = catalog.cell(args.workload)
    harness.find_device(1, tiny=True)       # the CPU: this packs, nothing is timed
    skip = spec['correct']['steps'] + traffic['settle_steps']
    readings = {}
    for candidate in range(args.candidates):
        config = module.Config(dict(spec, dataset=dict(
            spec['dataset'], layout_seed=candidate)), tiny=args.tiny)
        data = os.path.join(harness.CACHE, 'layout', '%s-%d' % (spec['name'], candidate))
        shutil.rmtree(data, ignore_errors=True)
        config.write_dataset(data, 0)
        shares = visit_shares(config, traffic, data, skip, args.window_steps)
        shutil.rmtree(data, ignore_errors=True)
        readings[candidate] = (statistics.fmean(shares), nearest_rank(shares, 0.9))
        print(json.dumps({'layout_seed': candidate, 'mean_share': readings[candidate][0],
                          'p90_share': readings[candidate][1],
                          'min_share': min(shares), 'max_share': max(shares)}), flush=True)
    chosen, medians = choose(readings)
    print(json.dumps({'chosen_layout_seed': chosen, 'median_mean_share': medians[0],
                      'median_p90_share': medians[1], 'its_mean_share': readings[chosen][0],
                      'its_p90_share': readings[chosen][1]}), flush=True)
    return chosen


if __name__ == '__main__':
    main()
