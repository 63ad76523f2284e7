"""``dlrm-mlperf-criteo``: everything that belongs to this configuration alone.

The harness loads this file by the configuration's name and talks to
:class:`Config` only (see ``resnet50-imagenet.py`` for the three parts).  The
chip holds its share of a deployment in which ``table_shards`` chips share every
embedding table by rows: ``max(1, ceil(rows / table_shards))`` rows of each
table, ids drawn from that slice, and ``global_batch / table_shards`` rows a
step.  Every width is as published.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import oracle

NUM_DENSE, NUM_CAT = 13, 26


def mlp_shapes(widths_in, sizes):
    """[(fan_in, fan_out)] of an MLP that takes ``widths_in`` features."""
    return list(zip([widths_in] + list(sizes[:-1]), sizes))


def power_law_ids(rng, rows, exponent, n):
    """``n`` ids in [0, rows) with P(id = k) ~ (k + 1) ** -exponent (inverse
    CDF of the continuous law on [1, rows + 1))."""
    u = rng.random(n)
    a = 1.0 - exponent
    x = ((float(rows + 1) ** a - 1.0) * u + 1.0) ** (1.0 / a)
    return np.minimum(x.astype(np.int64) - 1, rows - 1).astype(np.int32)


class Config(object):
    def __init__(self, spec, tiny=False, **sizes_for_a_reading):
        """``sizes_for_a_reading``: a smaller dataset for ``read_limits.py``,
        which needs the first steps only; a run never passes any."""
        self.spec = spec
        model = spec['model']
        sizes = dict(spec['dataset'], table_shards=spec['table_shards'])
        if tiny:
            sizes.update(spec['tiny'])
        sizes.update(sizes_for_a_reading)
        self.shards = sizes['table_shards']
        # the file states ``batch`` and ``table_rows`` as they are run; the tiny
        # rehearsal cuts the tables further and works both out again
        self.batch = sizes.get('batch', spec['batch'])
        self.rows = sizes['dataset_rows']
        self.rows_per_rowgroup = sizes['rows_per_rowgroup']
        self.table_rows = [max(1, -(-rows // self.shards))
                           for rows in model['table_cardinalities']]
        self.width = model['embedding_dim']
        self.bottom, self.top = model['bottom_mlp'], model['top_mlp']
        self.features = NUM_CAT + 1
        self.pairs = self.features * (self.features - 1) // 2
        self.lr = spec['optimizer']['learning_rate']
        self.compute_dtype = sizes.get('compute_dtype', model['compute_dtype'])
        self.donate_state = True
        self._jits = {}

    # -- dataset ---------------------------------------------------------------

    def write_dataset(self, path, seed):
        """Plain Parquet, ``row_id`` + the 40 four-byte Criteo columns, in four
        files; every column from a stream of its own."""
        import pyarrow as pa
        import pyarrow.parquet as pq
        data = self.spec['dataset']
        names = ['label'] + ['dense_%d' % i for i in range(NUM_DENSE)] \
            + ['cat_%d' % i for i in range(NUM_CAT)]
        streams = dict(zip(names, np.random.SeedSequence(seed).spawn(len(names))))

        def column(name):
            rng = np.random.default_rng(streams[name])
            if name == 'label':
                return (rng.random(self.rows) < data['label_rate']).astype(np.int32)
            if name.startswith('dense_'):
                return rng.lognormal(0, 1, self.rows).astype(np.float32)
            return power_law_ids(rng, self.table_rows[int(name[4:])],
                                 data['id_power_law_exponent'], self.rows)

        with ThreadPoolExecutor(os.cpu_count() or 1) as pool:
            columns = dict(zip(names, pool.map(column, names)))
        columns['row_id'] = np.arange(self.rows, dtype=np.int32)
        table = pa.table(columns)
        os.makedirs(path, exist_ok=True)
        rows_per_file = max(self.rows_per_rowgroup, self.rows // 4)
        for part, start in enumerate(range(0, self.rows, rows_per_file)):
            pq.write_table(table.slice(start, rows_per_file),
                           os.path.join(path, 'part_%05d.parquet' % part),
                           row_group_size=self.rows_per_rowgroup)

    def open_reader(self, url, seed, num_epochs):
        from petastorm_tpu import make_batch_reader
        return make_batch_reader(url, num_epochs=num_epochs, seed=seed % (2 ** 31))

    def loader_kwargs(self):
        def stack_columns(batch):
            # dense counts go in as log(x + 1), as the reference's data loader
            # feeds them
            return {'dense': np.log1p(np.stack([batch['dense_%d' % i]
                                                for i in range(NUM_DENSE)], axis=1)),
                    'cat': np.stack([batch['cat_%d' % i]
                                     for i in range(NUM_CAT)], axis=1),
                    'label': batch['label'].astype(np.float32),
                    'row_id': batch['row_id']}
        return {'transform_fn': stack_columns}

    # -- weights, from the seed, in one jitted call ----------------------------

    def init_params(self, key):
        """Embeddings normal(0.01) as the model's own initialiser has them,
        MLP kernels LeCun-normal, biases 0; named as flax names the modules of
        ``DLRM``."""
        import jax
        import jax.numpy as jnp

        def mlp(index, shapes):
            return {'Dense_%d' % i: {
                'kernel': jax.random.normal(
                    jax.random.fold_in(key, index + i), (fan_in, fan_out), jnp.float32)
                * np.float32(np.sqrt(1.0 / fan_in)),
                'bias': jnp.zeros((fan_out,), jnp.float32)}
                for i, (fan_in, fan_out) in enumerate(shapes)}
        params = {'MLP_0': mlp(100, mlp_shapes(NUM_DENSE, self.bottom)),
                  'MLP_1': mlp(200, mlp_shapes(
                      self.width + self.pairs, self.top))}
        for i, rows in enumerate(self.table_rows):
            params['table_%d' % i] = {'embedding': np.float32(0.01) * jax.random.normal(
                jax.random.fold_in(key, i), (rows, self.width), jnp.float32)}
        return params

    def init_state(self, key):
        return (self.init_params(key),)

    # -- the program's step ----------------------------------------------------

    def train_step(self):
        """DLRM SGD step around ``petastorm_tpu.models.dlrm.DLRM``, as
        ``examples/criteo/jax_example.py`` drives it."""
        import jax
        import jax.numpy as jnp
        import optax
        from petastorm_tpu.models.dlrm import DLRM

        model = DLRM(vocab_sizes=tuple(self.table_rows), embedding_dim=self.width,
                     bottom_mlp=tuple(self.bottom), top_mlp=tuple(self.top),
                     dtype=jnp.dtype(self.compute_dtype))
        lr = np.float32(self.lr)

        def step(state, batch):
            (params,) = state

            def loss_fn(p):
                logits = model.apply({'params': p}, batch['dense'], batch['cat'])
                losses = optax.sigmoid_binary_cross_entropy(logits, batch['label'])
                return losses.mean(), losses

            (loss, losses), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
            return (jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads),), \
                {'loss': loss, 'sample_loss': losses}
        return step

    def row_ids(self, batch):
        return batch['row_id']

    def params_of(self, state):
        return state[0]

    def first_gradient(self, state_after_one_step, key):
        """Plain SGD keeps no state: the first gradient is what the first step
        took off the seed's weights, over the learning rate."""
        import jax
        lr = np.float32(self.lr)
        return jax.tree_util.tree_map(lambda now, first: (first - now) / lr,
                                      state_after_one_step[0], self.init_params(key))

    # -- yardstick: what the Parquet files hold ---------------------------------

    def stored_rows(self, path, ids):
        import pyarrow.dataset as ds
        table = ds.dataset(path, format='parquet').to_table(
            filter=ds.field('row_id').isin(sorted(set(int(i) for i in ids))))
        columns = {name: table.column(name).to_numpy() for name in table.column_names}
        order = np.argsort(columns['row_id'])
        return {name: values[order] for name, values in columns.items()}

    def all_row_ids(self, path):
        import pyarrow.parquet as pq
        return pq.read_table(path, columns=['row_id']).column('row_id').to_numpy()

    def _as_batch(self, stored, ids):
        at = np.searchsorted(stored['row_id'], ids)
        return {'dense': np.log1p(np.stack([stored['dense_%d' % i][at]
                                            for i in range(NUM_DENSE)], axis=1)),
                'cat': np.stack([stored['cat_%d' % i][at]
                                 for i in range(NUM_CAT)], axis=1),
                'label': stored['label'][at].astype(np.float32),
                'row_id': np.asarray(ids, np.int32)}

    def delivered_error(self, path, delivered):
        """Largest difference between a delivered batch (host copy) and the
        stored rows of the same ids, over every column: exact, so 0."""
        ids = np.asarray(delivered['row_id'])
        want = self._as_batch(self.stored_rows(path, ids), ids)
        return max(float(np.max(np.abs(np.asarray(delivered[name], np.float64)
                                       - want[name]))) for name in want)

    def reference_batches(self, path, ids_per_step):
        stored = self.stored_rows(path, np.concatenate(ids_per_step))
        return [self._as_batch(stored, ids) for ids in ids_per_step]

    # -- yardstick: the plain reference -----------------------------------------

    def reference_step(self, precision='float32'):
        """Plain ``jax.numpy`` DLRM in float32 at ``highest`` matmul precision:
        one SGD step ``(params, dense, cat, label) -> (params, loss, the first
        gradient's norm leaf by leaf)``, the loss being ``(mean, per-row losses)``.
        ``precision='fp8'`` is the control: the operands of every MLP layer and
        of the interaction rounded to float8_e4m3fn under a per-tensor scale
        (cotangents to bfloat16); ``'bf16'`` rounds the same operands and their
        cotangents to bfloat16, the stated precision's own rounding.  The
        embedding tables stay float32 in all three, as in the program."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        highest = lax.Precision.HIGHEST
        q = oracle.operand_rounding(precision)
        iu, ju = np.triu_indices(self.features, k=1)

        def mlp(p, x):
            for i in range(len(p)):
                layer = p['Dense_%d' % i]
                x = jnp.dot(q(x), q(layer['kernel']), precision=highest) + layer['bias']
                if i < len(p) - 1:
                    x = jax.nn.relu(x)
            return x

        def loss_fn(params, dense, cat, label):
            dense_emb = mlp(params['MLP_0'], dense)
            feats = jnp.stack(
                [dense_emb] + [params['table_%d' % i]['embedding'][cat[:, i]]
                               for i in range(NUM_CAT)], axis=1)
            feats = q(feats)
            pairwise = jnp.einsum('bfd,bgd->bfg', feats, feats,
                                  precision=highest)[:, iu, ju]
            logit = mlp(params['MLP_1'],
                        jnp.concatenate([dense_emb, pairwise], axis=1))[:, 0]
            losses = jax.nn.softplus(logit) - label * logit
            return jnp.mean(losses), losses

        lr = np.float32(self.lr)

        def step(params, dense, cat, label):
            (loss, losses), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, dense, cat, label)
            params = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)
            return params, (loss, losses), jax.tree_util.tree_map(
                lambda g: jnp.sqrt(jnp.sum(jnp.square(g))), grads)
        return step

    def reference(self, key, batches, precision='float32', fault=None):
        """``len(batches)`` reference steps from the seed's weights: per-step
        losses and, leaf by leaf, the norm of the first gradient and of the
        parameters' change.  ``fault='half_batch'`` leaves the second half of
        every batch out."""
        import jax
        import jax.numpy as jnp

        if precision not in self._jits:
            self._jits[precision] = jax.jit(self.reference_step(precision),
                                            donate_argnums=(0,))
            self._jits['init'] = jax.jit(self.init_params)
            self._jits['change'] = jax.jit(lambda now, key: jax.tree_util.tree_map(
                lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))),
                now, self.init_params(key)))
        step, init, change = (self._jits[k] for k in (precision, 'init', 'change'))
        params = init(key)
        losses, sample_losses, grad_norms = [], [], None
        for batch in batches:
            n = len(batch['label']) // 2 if fault == 'half_batch' else None
            params, (loss, per_row), norms = step(
                params, batch['dense'][:n], batch['cat'][:n], batch['label'][:n])
            losses.append(float(loss))
            sample_losses.append(np.asarray(per_row))
            if grad_norms is None:
                grad_norms = jax.device_get(norms)
        return {'losses': losses, 'sample_losses': sample_losses,
                'grad_norms': grad_norms,
                'change_norms': jax.device_get(change(params, key))}

    def rehearsal_programs(self, key):
        import jax
        import jax.numpy as jnp
        b = self.batch
        batch = {'dense': jax.ShapeDtypeStruct((b, NUM_DENSE), jnp.float32),
                 'cat': jax.ShapeDtypeStruct((b, NUM_CAT), jnp.int32),
                 'label': jax.ShapeDtypeStruct((b,), jnp.float32),
                 'row_id': jax.ShapeDtypeStruct((b,), jnp.int32)}
        state = jax.eval_shape(self.init_state, key)
        ref_args = (state[0], batch['dense'], batch['cat'], batch['label'])
        return [('step', self.train_step(), (state, batch), (0,)),
                ('reference_step', self.reference_step(), ref_args, (0,)),
                ('control_step', self.reference_step('fp8'), ref_args, (0,))]

    # -- yardstick: what the algorithm needs ------------------------------------

    def mlp_macs(self):
        return sum(a * b for a, b in mlp_shapes(NUM_DENSE, self.bottom)
                   + mlp_shapes(self.width + self.pairs, self.top))

    def needed_flops_per_sample(self):
        """Forward and backward (three passes of two operations a
        multiply-accumulate) of the two MLPs and of the 351 distinct pairwise
        dot products; lookups move bytes and are not counted here."""
        return 3 * 2 * (self.mlp_macs() + self.pairs * self.width)

    def needed_bytes_per_step(self):
        """What the algorithm needs whatever implements it: the embedding rows
        a batch touches read once and written once (not the whole table), the
        MLPs' parameters read and written once, and the batch's columns."""
        mlp_params = sum(a * b + b for a, b in mlp_shapes(NUM_DENSE, self.bottom)
                         + mlp_shapes(self.width + self.pairs, self.top))
        touched = self.batch * NUM_CAT * self.width * 4
        return 2 * touched + 2 * 4 * mlp_params \
            + self.batch * (NUM_DENSE + NUM_CAT + 2) * 4
