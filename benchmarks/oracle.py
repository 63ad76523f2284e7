"""The comparison that decides ``correct``: its arithmetic and its printing.

Each number compared has a name, a reading and a limit; a run is correct when
no reading passes its limit.  The limits of the training numbers are in the
configuration's file (``correct.limits``), set from chip readings that
``PERF.md`` lists; the exact comparisons have the limit 0.
"""

import json

import numpy as np


def key_of(seed):
    """A threefry key from any whole number (``--seed`` passes 2**31)."""
    import jax
    return jax.random.wrap_key_data(
        np.random.SeedSequence(seed).generate_state(2).astype(np.uint32))


def operand_rounding(precision):
    """What a plain float32 reference does to a matrix operand to stand for a
    lower precision: ``'float32'`` nothing; ``'bf16'`` rounds it to bfloat16 on
    the way forward and its cotangent on the way back, as a bfloat16 program
    stores both; ``'fp8'`` rounds the bfloat16 operand on to float8_e4m3fn
    under a per-tensor scale (straight through on the way back)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    @jax.custom_vjp
    def as_stored(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    as_stored.defvjp(lambda x: (as_stored(x), None),
                     lambda _, g: (g.astype(jnp.bfloat16).astype(jnp.float32),))

    def as_fp8(x):
        scale = lax.stop_gradient(jnp.max(jnp.abs(x)) / 448.0 + 1e-30)
        rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
        return x + lax.stop_gradient(rounded - x)

    return {'float32': lambda x: x, 'bf16': as_stored,
            'fp8': lambda x: as_fp8(as_stored(x))}[precision]


class Numbers(object):
    def __init__(self, limits):
        self.limits = limits
        self._rows = []

    def add(self, name, reading, limit=None):
        """``limit`` given: an exact or stated one.  Otherwise the file's."""
        if limit is None:
            limit = self.limits[name]
        self._rows.append((name, float(reading), limit))

    def correct(self):
        return all(np.isfinite(reading) and reading <= limit
                   for _, reading, limit in self._rows)

    def as_dict(self):
        return {name: {'value': reading, 'limit': limit}
                for name, reading, limit in self._rows}

    def print_last(self, stream):
        for name, reading, limit in self._rows:
            verdict = 'ok' if np.isfinite(reading) and reading <= limit else 'FAILED'
            print('check %s = %.6g (limit %s) %s' % (name, reading, limit, verdict),
                  file=stream)
        stream.flush()


class LeafNorms(object):
    """Leaf-by-leaf norms of the program's first gradient and of its
    parameters' change, each one jitted program on the device."""

    def __init__(self, config):
        import jax
        import jax.numpy as jnp

        def norm(x):
            return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

        self.first_gradient = jax.jit(lambda state, key: jax.tree_util.tree_map(
            norm, config.first_gradient(state, key)))
        self.change = jax.jit(lambda state, key: jax.tree_util.tree_map(
            lambda now, first: norm(now - first),
            config.params_of(state), config.init_params(key)))


def leaves(tree):
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(path): float(value) for path, value in flat}


def worst_leaf_gap(program, reference):
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger.  The median is taken over the leaves that the
    reference moves at all: a leaf behind a zero scale has no first gradient
    on either side and reads a gap of 0.  Returns (the worst leaf's gap, the
    median leaf's gap, which leaf was worst)."""
    floor = float(np.median([v for v in reference.values() if v > 0]))
    gaps = {name: abs(program[name] - want) / max(want, floor)
            for name, want in reference.items()}
    # a NaN is the worst there is
    where = max(gaps, key=lambda name: np.inf if np.isnan(gaps[name]) else gaps[name])
    moved = [gaps[name] for name, want in reference.items() if want > 0]
    return gaps[where], float(np.median(moved)), \
        '%s: %.6g against %.6g, median leaf %.6g' % (
            where, program[where], reference[where], floor)


def sample_loss_gap(program, reference):
    """How far one step's per-row losses lie from the reference's, as a vector,
    against how far the reference's lie from their own mean: the share of what
    tells the rows apart that is noise.  Rows that one side left out count on
    neither."""
    n = min(len(program), len(reference))
    p, r = np.asarray(program[:n], np.float64), np.asarray(reference[:n], np.float64)
    return float(np.linalg.norm(p - r) / np.linalg.norm(r - r.mean()))


def training_gaps(program, reference):
    """The numbers of a program's first steps against the reference's: the
    widest gap of a step's loss; the gap of the per-row losses, of the first
    step (before any update: the forward pass alone) and the widest of all; the
    gap of the first gradient's norm and of the parameters' change, by the
    median leaf and by the worst leaf (and where)."""
    loss_gap = max(abs(p - r) / abs(r)
                   for p, r in zip(program['losses'], reference['losses']))
    sample_gaps = [sample_loss_gap(p, r) for p, r in zip(
        program['sample_losses'], reference['sample_losses'])]
    grad_gap, grad_median, grad_leaf = worst_leaf_gap(
        leaves(program['grad_norms']), leaves(reference['grad_norms']))
    change_gap, change_median, change_leaf = worst_leaf_gap(
        leaves(program['change_norms']), leaves(reference['change_norms']))
    return {'loss_gap': loss_gap, 'first_sample_loss_gap': sample_gaps[0],
            'sample_loss_gap': max(sample_gaps),
            'grad_gap': grad_gap, 'change_gap': change_gap,
            'grad_gap_median': grad_median, 'change_gap_median': change_median,
            'grad_leaf': grad_leaf, 'change_leaf': change_leaf}


def compare_training(numbers, program, reference):
    """Prints every training number and compares those that the
    configuration's file gives a limit: the others have no upper reading at
    that configuration (PERF.md, "How the limits were set")."""
    gaps = training_gaps(program, reference)
    print(json.dumps({'phase': 'compare', 'program_losses': program['losses'],
                      'reference_losses': reference['losses'], **gaps}), flush=True)
    for name in sorted(set(gaps) & set(numbers.limits)):
        numbers.add(name, gaps[name])
    return gaps


def miscounted(delivered_ids, stored_ids):
    """Rows not delivered as often as epochs without end deliver them: after
    ``n`` rows of a dataset of ``r``, every row has come ``n // r`` times or
    once more, and ``n % r`` of them once more.  Returns how many rows break
    that (an id the files do not hold counts too).  It holds the reader to its
    stated guarantee that delivery is in exact epoch order (adaptive
    scheduling's reorder stage: eight row groups or more, several workers)."""
    stored = np.asarray(stored_ids)
    order = {int(v): i for i, v in enumerate(stored)}
    index = np.fromiter((order.get(int(v), -1) for v in delivered_ids), np.int64,
                        len(delivered_ids))
    unknown = int((index < 0).sum())
    counts = np.bincount(index[index >= 0], minlength=len(stored))
    base = len(delivered_ids) // len(stored)
    return unknown + int(((counts < base) | (counts > base + 1)).sum())
