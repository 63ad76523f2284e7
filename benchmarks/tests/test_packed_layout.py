"""Under the ``packed`` traffic the documents' layout belongs to the
configuration and not to the run (``benchmarks/README.md``, "A configuration
under the ``packed`` traffic"): ``dataset.layout_seed`` fixes the lengths and
the order the reader delivers them in, ``--seed`` draws the token ids and the
weights.  Shown at the ``tiny`` sizes on the CPU for every cell whose traffic
mix asks its configuration for a ``layout_seed``."""

import itertools

import numpy as np
import pytest

import catalog
import choose_layout
from test_catalog import copy_of_the_benchmark
from test_correct import tiny_run
from timed_path import open_loader

KEY = 'dataset.layout_seed'
SEEDS = (2_147_483_777, 3_000_000_019)


CELLS = [w['name'] for w in catalog.benchmark()['workloads']
         if KEY in catalog.cell(w['name'])[3].get('config_requires', [])]


def documents(config, data):
    ids = config.all_row_ids(data)
    stored = config.stored_rows(data, ids)
    return [stored[int(i)] for i in ids]


def first_batches(config, traffic, data, seed, n=12):
    with open_loader(config, traffic, data, seed, tiny=True) as loader:
        return [{k: np.asarray(v) for k, v in b.items()}
                for b in itertools.islice(loader, n)]


@pytest.fixture(scope='module', params=CELLS)
def two_seeds(request, tmp_path_factory):
    """(config, traffic, one dataset a seed) of a packed cell, tiny."""
    _, spec, module, traffic = catalog.cell(request.param)
    config = module.Config(spec, tiny=True)
    root = tmp_path_factory.mktemp('layout')
    data = []
    for seed in SEEDS:
        data.append(str(root / str(seed)))
        config.write_dataset(data[-1], seed)
    return config, traffic, data


def test_there_is_a_packed_cell():
    assert CELLS


def test_two_seeds_give_the_same_lengths_and_other_ids(two_seeds):
    config, _, data = two_seeds
    one, other = (documents(config, d) for d in data)
    assert [len(d) for d in one] == [len(d) for d in other]
    assert len(set(len(d) for d in one)) > 8          # lengths do vary
    differing = sum(not np.array_equal(a, b) for a, b in zip(one, other))
    assert differing > 0.9 * len(one)


def test_two_seeds_pack_the_same_layout_step_for_step(two_seeds):
    config, traffic, data = two_seeds
    one, other = (first_batches(config, traffic, d, seed)
                  for d, seed in zip(data, SEEDS))
    assert len(one) == len(other) == 12
    for a, b in zip(one, other):
        for leaf in ('segment_ids', 'positions', 'doc_ids'):
            assert np.array_equal(a[leaf], b[leaf]), leaf
    assert any(not np.array_equal(a['tokens'], b['tokens']) for a, b in zip(one, other))
    # the batches differ from one another: one layout repeated would pass above
    assert len({a['segment_ids'].tobytes() for a in one}) > 6


@pytest.mark.parametrize('cell', CELLS)
def test_another_layout_seed_is_another_layout(cell, tmp_path):
    _, spec, module, _ = catalog.cell(cell)
    lengths = []
    for layout_seed in (spec['dataset']['layout_seed'], spec['dataset']['layout_seed'] + 1):
        config = module.Config(dict(spec, dataset=dict(
            spec['dataset'], layout_seed=layout_seed)), tiny=True)
        data = str(tmp_path / str(layout_seed))
        config.write_dataset(data, SEEDS[0])
        lengths.append([len(d) for d in documents(config, data)])
    assert lengths[0] != lengths[1]


@pytest.mark.parametrize('seed', SEEDS)
@pytest.mark.parametrize('cell', CELLS)
def test_the_rehearsal_is_correct_on_two_seeds(cell, seed):
    result = tiny_run(cell, seed)
    assert result['correct'] is True, result['checks']
    assert result['checks']['rows_miscounted']['value'] == 0


@pytest.mark.parametrize('cell', CELLS)
def test_a_configuration_without_layout_seed_is_refused_by_name(cell, tmp_path):
    root = copy_of_the_benchmark(tmp_path)
    found, spec, _, _ = catalog.cell(cell, root)
    file = 'benchmarks/configs/%s.json' % found['config']
    del spec['dataset']['layout_seed']
    with open(catalog.os.path.join(root, file), 'w') as f:
        catalog.json.dump(spec, f)
    with pytest.raises(SystemExit) as refused:
        catalog.cell(cell, root)
    assert KEY in str(refused.value) and file in str(refused.value)


def test_a_mix_that_asks_nothing_refuses_nothing():
    catalog.require({'name': 'x'}, {'name': 'stream'}, 'x.json')
    with pytest.raises(SystemExit):
        catalog.require({'dataset': 3}, {'name': 'm', 'config_requires': [KEY]}, 'x.json')


def test_choose_takes_the_candidate_nearest_both_medians():
    readings = {0: (0.50, 0.70), 1: (0.58, 0.75), 2: (0.62, 0.81), 3: (0.59, 0.70),
                4: (0.66, 0.90)}
    chosen, medians = choose_layout.choose(readings)
    assert medians == [0.59, 0.75]
    assert chosen == 1          # 3 has the median mean but a 90th percentile far off
    assert choose_layout.nearest_rank(range(1, 35), 0.9) == 31
    assert choose_layout.nearest_rank([5.0], 0.9) == 5.0


@pytest.mark.parametrize('cell', CELLS)
def test_the_chooser_packs_through_the_cells_own_reader_and_loader(cell, capsys):
    """At the ``tiny`` sizes a row is one tile, so every share is 1 and the
    first candidate is as near the medians as any."""
    assert choose_layout.main(['--workload', cell, '--candidates', '2', '--tiny']) == 0
    lines = [catalog.json.loads(line) for line in capsys.readouterr().out.splitlines()
             if line.startswith('{')]
    assert [line.get('layout_seed') for line in lines[:2]] == [0, 1]
    assert lines[0]['max_share'] == 1.0 and lines[-1]['chosen_layout_seed'] == 0
