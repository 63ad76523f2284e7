"""A later PR adds a configuration, a traffic mix, a cell and a per-layer metric
as new files plus new entries of ``BENCHMARK.json``, editing no file that is
there (``benchmarks/README.md``).  Shown on a temporary copy."""

import filecmp
import json
import os
import shutil

import catalog


def copy_of_the_benchmark(tmp_path):
    root = str(tmp_path / 'checkout')
    shutil.copytree(catalog.HERE, os.path.join(root, 'benchmarks'),
                    ignore=shutil.ignore_patterns('.cache', '__pycache__', 'tests'))
    shutil.copy(os.path.join(catalog.ROOT, 'BENCHMARK.json'), root)
    return root


def test_every_cell_of_the_benchmark_is_found_with_its_files():
    rows = catalog.listing()
    bench = catalog.benchmark()
    assert [r['cell'] for r in rows] == [w['name'] for w in bench['workloads']]
    for row in rows:
        assert 'setup_s' in row['metrics'] and len(row['metrics']) >= 3


def test_a_throw_away_cell_is_listed_without_an_edit_to_a_file_that_is_there(tmp_path):
    root = copy_of_the_benchmark(tmp_path)
    before = copy_of_the_benchmark(tmp_path / 'before')
    bdir = os.path.join(root, 'benchmarks')
    # a configuration: its sizes and its plain reference, as two new files
    spec = catalog._json(os.path.join(bdir, 'configs', 'resnet50-imagenet.json'))
    spec['name'] = 'throwaway-net'
    with open(os.path.join(bdir, 'configs', 'throwaway-net.json'), 'w') as f:
        json.dump(spec, f)
    shutil.copy(os.path.join(bdir, 'configs', 'resnet50-imagenet.py'),
                os.path.join(bdir, 'configs', 'throwaway-net.py'))
    # a traffic mix: parameters only
    mix = catalog._json(os.path.join(bdir, 'traffic', 'stream.json'))
    mix.update(name='throwaway-mix', loader_args={'prefetch': 4})
    with open(os.path.join(bdir, 'traffic', 'throwaway-mix.json'), 'w') as f:
        json.dump(mix, f)
    # a per-layer metric: a reader of its own
    with open(os.path.join(bdir, 'metrics', 'throwaway_batches.py'), 'w') as f:
        f.write("def read(c):\n    return c['counters'].get('batches') or None\n")
    # and the new entries
    bench = catalog.benchmark(root)
    bench['configs'].append({'name': 'throwaway-net', 'source': 'nowhere',
                             'file': 'benchmarks/configs/throwaway-net.json',
                             'reduced': [], 'why': 'test'})
    bench['workloads'].append({'name': 'throwaway.cell', 'config': 'throwaway-net',
                               'traffic': 'throwaway-mix', 'chips': 1, 'why': 'test'})
    bench['per_layer'].append({'name': 'throwaway_batches', 'unit': 'batches',
                               'better': 'higher', 'source': 'program_counter',
                               'layer': 'loader', 'moves': 'samples_per_s',
                               'workloads': ['throwaway.cell']})
    with open(os.path.join(root, 'BENCHMARK.json'), 'w') as f:
        json.dump(bench, f)

    rows = {r['cell']: r for r in catalog.listing(root)}
    new = rows['throwaway.cell']
    assert (new['config'], new['traffic']) == ('throwaway-net', 'throwaway-mix')
    assert 'throwaway_batches' in new['metrics']
    # metrics with no ``workloads`` key are every cell's, the new one's too
    assert 'samples_per_s' in new['metrics'] and 'setup_s' in new['metrics']
    assert 'throwaway_batches' not in rows['resnet50.stream']['metrics']
    assert catalog.metric_reader('throwaway_batches', root)(
        {'counters': {'batches': 7}}) == 7
    # nothing that was there was edited
    same = filecmp.dircmp(os.path.join(before, 'benchmarks'), bdir)
    stack, changed = [same], []
    while stack:
        d = stack.pop()
        changed += d.diff_files + d.left_only
        stack += d.subdirs.values()
    assert changed == []
