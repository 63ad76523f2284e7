"""``correct`` comes out true for the timed path as it stands and false with the
path broken underneath, at the configurations' ``tiny`` sizes on the CPU.

Each test drives a whole run (``run.run``) but for the look for a chip, which
``--tiny`` replaces by a look for the CPU.  The faults are planted where the
timed path produces its results: in the step program (``TimedPath.program``)
or in what the loader hands over (``TimedPath.iterate``).  The control, the
plain reference in the precision below the stated one put in the program's
place, has to fail one of the cell's numbers too.
"""

import argparse

import pytest

import catalog
import oracle
import run as harness
from timed_path import TimedPath

CELLS = [w['name'] for w in catalog.benchmark()['workloads']]
CONFIGS = [c['name'] for c in catalog.benchmark()['configs']]
#: the numbers that compare the first steps with the reference
TRAINING = ('loss_gap', 'first_sample_loss_gap', 'sample_loss_gap', 'grad_gap_median',
            'change_gap_median')


def tiny_run(cell, seed=2_147_483_777):
    return harness.run(argparse.Namespace(workload=cell, seed=seed, seconds=1.0,
                                          trace=0, tiny=True))


def state_unchanged(step):
    return lambda state, batch: (state, step(state, batch)[1])


def half_batch(step):
    import jax
    return lambda state, batch: step(state, jax.tree_util.tree_map(
        lambda x: x[:len(x) // 2], batch))


def alter_one_answer(batches):
    """One element of the largest leaf of every batch but the first, altered
    after the loader produced it."""
    for index, batch in enumerate(batches):
        if index >= 1:
            name = max(batch, key=lambda k: batch[k].size)
            batch = dict(batch, **{
                name: batch[name].at[(0,) * batch[name].ndim].add(7)})
        yield batch


@pytest.mark.parametrize('cell', CELLS)
def test_the_timed_path_as_it_stands_is_correct(cell):
    result = tiny_run(cell)
    assert set(TRAINING) & set(result['checks'])
    assert result['correct'] is True, result['checks']
    assert 'metrics' not in result and result['platform'] == 'cpu'


@pytest.mark.parametrize('fault', [state_unchanged, half_batch])
@pytest.mark.parametrize('cell', CELLS)
def test_a_broken_step_is_not_correct(cell, fault, monkeypatch):
    whole = TimedPath.program
    monkeypatch.setattr(TimedPath, 'program', lambda self: fault(whole(self)))
    result = tiny_run(cell)
    assert result['correct'] is False
    failed = [n for n, c in result['checks'].items() if not c['value'] <= c['limit']]
    assert set(failed) & set(TRAINING), result['checks']


@pytest.mark.parametrize('cell', CELLS)
def test_an_altered_answer_is_not_correct(cell, monkeypatch):
    whole = TimedPath.iterate
    monkeypatch.setattr(TimedPath, 'iterate',
                        lambda self, loader: alter_one_answer(whole(self, loader)))
    result = tiny_run(cell)
    assert result['correct'] is False
    check = result['checks']['delivered_error']
    assert check['value'] > check['limit']


@pytest.mark.parametrize('name', CONFIGS)
def test_the_control_is_not_correct(name):
    """The reference in the next precision down, in the program's place."""
    import numpy as np
    entry = {c['name']: c for c in catalog.benchmark()['configs']}[name]
    spec = catalog._json(catalog.os.path.join(catalog.ROOT, entry['file']))
    module = catalog._module(catalog.os.path.join(
        catalog.ROOT, entry['file'][:-len('.json')] + '.py'))
    config = module.Config(spec, tiny=True)
    seed = 2_147_483_777
    data = harness.ensure_dataset(config, spec['name'], seed)
    ids = config.all_row_ids(data)[:3 * config.batch].reshape(3, config.batch)
    batches = config.reference_batches(data, list(ids))
    key = oracle.key_of(seed)
    reference = config.reference(key, batches)
    control = config.reference(key, batches, precision=spec['correct']['control'])
    numbers = oracle.Numbers(spec['correct']['limits'])
    oracle.compare_training(numbers, control, reference)
    assert numbers.correct() is False, numbers.as_dict()
    sound = oracle.Numbers(spec['correct']['limits'])
    oracle.compare_training(sound, config.reference(key, batches), reference)
    assert sound.correct() is True
    assert np.isfinite(control['losses']).all()
