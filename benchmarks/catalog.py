"""Finds what a cell is made of, by name.

``BENCHMARK.json`` (at the root of the checkout) names the cells, the
configurations and the metrics; each name leads to files of its own under
``benchmarks/``:

    configs/<config>.json + configs/<config>.py    a configuration
    traffic/<traffic>.json                         a traffic mix
    metrics/<metric>.py                            one metric's reader
    peaks.json                                     the chips' published peaks

A later PR adds entries and files; nothing here knows a name.
"""

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _json(path):
    with open(path) as f:
        return json.load(f)


def _module(path):
    name = 'pt_bench_' + os.path.basename(path)[:-3].replace('-', '_').replace('.', '_')
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def benchmark(root=ROOT):
    return _json(os.path.join(root, 'BENCHMARK.json'))


def cell(name, root=ROOT):
    """(cell, configuration's spec, configuration's module, traffic mix)."""
    bench = benchmark(root)
    cells = {w['name']: w for w in bench['workloads']}
    if name not in cells:
        raise SystemExit('no cell %r in BENCHMARK.json (it has: %s)'
                         % (name, ', '.join(sorted(cells))))
    found = cells[name]
    config = {c['name']: c for c in bench['configs']}[found['config']]
    spec = _json(os.path.join(root, config['file']))
    module = _module(os.path.join(root, config['file'][:-len('.json')] + '.py'))
    traffic = _json(os.path.join(root, 'benchmarks', 'traffic',
                                 found['traffic'] + '.json'))
    require(spec, traffic, config['file'])
    return found, spec, module, traffic


def require(spec, traffic, file):
    """Fails, naming the key, where the configuration's file lacks a key that
    the traffic mix asks of it (``config_requires``, dotted paths)."""
    for key in traffic.get('config_requires', []):
        node = spec
        for part in key.split('.'):
            if not isinstance(node, dict) or part not in node:
                raise SystemExit(
                    'the traffic mix %r needs %r in %s: see benchmarks/README.md'
                    % (traffic['name'], key, file))
            node = node[part]


def metric_module(name, root=ROOT):
    """A metric's file: ``read(context)`` gives the number (``None`` where there
    is nothing to read); ``explain(context)``, where it has one, gives what a
    run prints on an earlier line."""
    return _module(os.path.join(root, 'benchmarks', 'metrics', name + '.py'))


def metric_reader(name, root=ROOT):
    return metric_module(name, root).read


def metrics_of(cell_name, group, root=ROOT):
    """The metrics of ``group`` ('end_to_end' or 'per_layer') that this cell
    reports: those that list it, and those that list no cell at all."""
    return [m for m in benchmark(root)[group]
            if cell_name in m.get('workloads', [cell_name])]


def peaks(device_kind, root=ROOT):
    table = _json(os.path.join(root, 'benchmarks', 'peaks.json'))['devices']
    if device_kind not in table:
        raise SystemExit('no peaks for device kind %r in benchmarks/peaks.json'
                         % device_kind)
    return table[device_kind]


def listing(root=ROOT):
    """Every cell with the files it was found in; raises where one is missing."""
    out = []
    for w in benchmark(root)['workloads']:
        _, spec, module, traffic = cell(w['name'], root)
        readers = [m['name'] for group in ('end_to_end', 'per_layer')
                   for m in metrics_of(w['name'], group, root)
                   if metric_reader(m['name'], root)]
        out.append({'cell': w['name'], 'config': spec['name'],
                    'traffic': traffic['name'], 'chips': w['chips'],
                    'metrics': readers})
    return out


if __name__ == '__main__':
    for row in listing():
        print(json.dumps(row))
