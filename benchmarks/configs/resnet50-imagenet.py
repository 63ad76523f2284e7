"""``resnet50-imagenet``: everything that belongs to this configuration alone.

The harness (``benchmarks/run.py``) loads this file by the configuration's
name and talks to :class:`Config` only.  Three parts:

* the system under test, driven through its normal entry points: the dataset
  in petastorm's own Unischema Parquet, ``make_reader(columnar_decode=True)``
  and the jitted ResNet-50 SGD step of ``chip_smoke.py`` (PR 22) around
  ``petastorm_tpu.models.resnet.ResNet50``;
* the weights, made by the benchmark from ``--seed`` in one jitted call;
* the yardstick: the plain float32 reference of the same three steps, the
  stored-bytes decode by pyarrow and cv2, and the needed FLOPs and bytes.
  The reference imports nothing of ``petastorm_tpu``.
"""

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import oracle

#: (filters, blocks) of the four stages; bottleneck width x4 on the way out.
STAGES = ((64, 3), (128, 4), (256, 6), (512, 3))
BN_EPS = 1e-5
LABEL_MODULUS = 1000


# -- shapes of the network, shared by the weights, the reference and the FLOPs

def conv_layers(image_hw, num_classes=1000):
    """Every convolution and the classifier as ``(path, kernel_hw, c_in,
    c_out, stride, out_hw)``, in forward order; ``path`` is the leaf's place
    in the parameter tree."""
    layers = []
    hw = -(-image_hw // 2)
    layers.append((('Conv_0',), 7, 3, 64, 2, hw))
    hw = -(-hw // 2)                                     # 3x3/2 max pool
    c_in, block = 64, 0
    for stage, (filters, blocks) in enumerate(STAGES):
        for j in range(blocks):
            stride = 2 if stage > 0 and j == 0 else 1
            name = 'BottleneckBlock_%d' % block
            out_hw = -(-hw // stride)
            layers.append(((name, 'Conv_0'), 1, c_in, filters, 1, hw))
            layers.append(((name, 'Conv_1'), 3, filters, filters, stride, out_hw))
            layers.append(((name, 'Conv_2'), 1, filters, filters * 4, 1, out_hw))
            if j == 0:
                layers.append(((name, 'Conv_3'), 1, c_in, filters * 4, stride,
                               out_hw))
            hw, c_in, block = out_hw, filters * 4, block + 1
    layers.append((('Dense_0',), 1, c_in, num_classes, 1, 1))
    return layers


def forward_macs(image_hw, num_classes=1000):
    """Multiply-accumulates of one image's forward pass: convolutions and the
    classifier (normalisation, pooling and activations are not counted)."""
    return sum(k * k * c_in * c_out * out * out
               for _, k, c_in, c_out, _, out in conv_layers(image_hw, num_classes))


def synthetic_images(rng, n, hw):
    """Smooth gradient + block colour + pixel noise: compresses like a
    photograph (``chip_smoke.py::synthetic_images``)."""
    base = np.linspace(0, 255, hw * hw * 3, dtype=np.float32).reshape(hw, hw, 3)
    blocks = rng.integers(0, 64, (n, 8, 8, 3), np.int16) \
        .repeat(hw // 8, 1).repeat(hw // 8, 2)
    noise = rng.integers(-12, 13, (n, hw, hw, 3), np.int16)
    return np.clip(base + blocks + noise, 0, 255).astype(np.uint8)


def plain_decode(jpeg):
    import cv2
    return cv2.cvtColor(cv2.imdecode(np.frombuffer(jpeg, np.uint8),
                                     cv2.IMREAD_COLOR), cv2.COLOR_BGR2RGB)


class Config(object):
    def __init__(self, spec, tiny=False, **sizes_for_a_reading):
        """``sizes_for_a_reading``: a smaller dataset for ``read_limits.py``,
        which needs the first steps only; a run never passes any."""
        self.spec = spec
        sizes = dict(spec['dataset'], batch=spec['batch'],
                     image_size=spec['model']['image_size'])
        if tiny:
            sizes.update(spec['tiny'])
        sizes.update(sizes_for_a_reading)
        self.hw = sizes['image_size']
        self.batch = sizes['batch']
        self.distinct = sizes['distinct_images']
        self.rows = self.distinct * sizes['copies']
        self.rows_per_rowgroup = sizes['rows_per_rowgroup']
        self.num_classes = spec['model']['num_classes']
        # at the tiny sizes three steps at 0.1 amplify float32 rounding a
        # thousandfold; the tiny rehearsal takes smaller ones
        self.lr = sizes.get('learning_rate', spec['optimizer']['learning_rate'])
        self.momentum = spec['optimizer']['momentum']
        self.compute_dtype = sizes.get('compute_dtype',
                                       spec['model']['compute_dtype'])
        self.donate_state = False
        self._jits = {}

    # -- dataset ---------------------------------------------------------------

    def _schema(self):
        from petastorm_tpu.codecs import CompressedImageCodec
        from petastorm_tpu.unischema import Unischema, UnischemaField
        return Unischema('ImagenetLike', [
            UnischemaField('noun_id', np.int64, (), None, False),
            UnischemaField('image', np.uint8, (self.hw, self.hw, 3),
                           CompressedImageCodec(
                               'jpeg', quality=self.spec['dataset']['jpeg_quality']),
                           False)])

    def write_dataset(self, path, seed):
        """``distinct`` seeded images, encoded once on every core and stored
        ``copies`` times each under a row id of its own (row ``i`` holds image
        ``i % distinct``), written with pyarrow inside the package's own
        ``materialize_dataset_pyarrow`` stamp."""
        import pyarrow as pa
        import pyarrow.parquet as pq
        from petastorm_tpu.etl.dataset_metadata import materialize_dataset_pyarrow

        schema = self._schema()
        field = schema.fields['image']
        chunks = [(start, min(256, self.distinct - start))
                  for start in range(0, self.distinct, 256)]
        seeds = np.random.SeedSequence(seed).spawn(len(chunks))

        def encode_chunk(args):
            (start, n), chunk_seed = args
            images = synthetic_images(np.random.default_rng(chunk_seed), n, self.hw)
            return [bytes(field.codec.encode(field, image)) for image in images]

        with ThreadPoolExecutor(os.cpu_count() or 1) as pool:
            jpegs = [j for chunk in pool.map(encode_chunk, zip(chunks, seeds))
                     for j in chunk]
        arrow = schema.as_arrow_schema()
        os.makedirs(path, exist_ok=True)
        rows_per_file = self.rows_per_rowgroup * 3
        with materialize_dataset_pyarrow('file://' + path, schema):
            for part, file_start in enumerate(range(0, self.rows, rows_per_file)):
                with pq.ParquetWriter(
                        os.path.join(path, 'part_%05d.parquet' % part), arrow,
                        compression={'noun_id': 'snappy', 'image': 'NONE'}) as w:
                    stop = min(file_start + rows_per_file, self.rows)
                    for start in range(file_start, stop, self.rows_per_rowgroup):
                        ids = range(start, min(start + self.rows_per_rowgroup, stop))
                        w.write_table(pa.table(
                            {'noun_id': pa.array(list(ids), arrow.field('noun_id').type),
                             'image': pa.array([jpegs[i % self.distinct] for i in ids],
                                               arrow.field('image').type)},
                            schema=arrow))

    def open_reader(self, url, seed, num_epochs):
        from petastorm_tpu import make_reader
        return make_reader(url, num_epochs=num_epochs, columnar_decode=True,
                           seed=seed % (2 ** 31))

    def loader_kwargs(self):
        return {}

    # -- weights, from the seed, in one jitted call ----------------------------

    def init_params(self, key):
        """He-normal kernels; BatchNorm scales 1 and biases 0, but the last
        scale of each block 0, as the model's own initialiser has it (Goyal et
        al. 2017): each block starts as the identity, and SGD at 0.1 is steady
        from the first step.  Named as flax names the modules of ``ResNet50``."""
        import jax
        import jax.numpy as jnp
        params = {}
        for index, (path, k, c_in, c_out, _, _) in enumerate(
                conv_layers(self.hw, self.num_classes)):
            node = params
            for name in path[:-1]:
                node = node.setdefault(name, {})
            leaf_key = jax.random.fold_in(key, index)
            if path[-1] == 'Dense_0':
                node['Dense_0'] = {
                    'kernel': jax.random.normal(leaf_key, (c_in, c_out), jnp.float32)
                    * np.float32(self.spec['assumed']['classifier_init_std']),
                    'bias': jnp.zeros((c_out,), jnp.float32)}
                continue
            node[path[-1]] = {
                'kernel': jax.random.normal(leaf_key, (k, k, c_in, c_out), jnp.float32)
                * np.float32(np.sqrt(2.0 / (k * k * c_in)))}
            node['BatchNorm_' + path[-1].split('_')[1]] = {
                'scale': (jnp.zeros if path[-1] == 'Conv_2' else jnp.ones)(
                    (c_out,), jnp.float32),
                'bias': jnp.zeros((c_out,), jnp.float32)}
        return params

    def init_state(self, key):
        """(params, batch_stats, momentum) for the program's step."""
        import jax
        import jax.numpy as jnp
        params = self.init_params(key)

        def stats(node):
            return {name: ({'mean': jnp.zeros_like(sub['scale']),
                            'var': jnp.ones_like(sub['scale'])}
                           if name.startswith('BatchNorm_') else stats(sub))
                    for name, sub in node.items()
                    if name.startswith(('BatchNorm_', 'BottleneckBlock_'))}
        return params, stats(params), self._tx().init(params)

    def _tx(self):
        import optax
        return optax.sgd(self.lr, momentum=self.momentum)

    # -- the program's step ----------------------------------------------------

    def train_step(self):
        """ResNet-50 SGD step, uint8 batch in; normalisation and the bf16 cast
        happen on the device (``chip_smoke.py::make_resnet_step``)."""
        import jax
        import jax.numpy as jnp
        import optax
        from petastorm_tpu.models.resnet import ResNet50

        dtype = jnp.dtype(self.compute_dtype)
        model = ResNet50(num_classes=self.num_classes, dtype=dtype)
        tx = self._tx()

        def step(state, batch):
            params, batch_stats, opt_state = state
            images = batch['image'].astype(dtype) / 255.0
            labels = batch['noun_id'] % LABEL_MODULUS

            def loss_fn(p):
                logits, mutated = model.apply(
                    {'params': p, 'batch_stats': batch_stats}, images, train=True,
                    mutable=['batch_stats'])
                losses = optax.softmax_cross_entropy_with_integer_labels(
                    logits.astype(jnp.float32), labels)
                return losses.mean(), (mutated['batch_stats'], losses)

            (loss, (new_stats, losses)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            updates, new_opt = tx.update(grads, opt_state)
            return (optax.apply_updates(params, updates), new_stats, new_opt), \
                {'loss': loss, 'sample_loss': losses}
        return step

    def row_ids(self, batch):
        return batch['noun_id']

    def params_of(self, state):
        return state[0]

    def first_gradient(self, state_after_one_step, key):
        """The first gradient as the optimizer got it: with momentum the trace
        after one step is that gradient."""
        return state_after_one_step[2][0].trace

    # -- yardstick: what the Parquet files hold ---------------------------------

    def stored_rows(self, path, ids):
        """{row id: decoded image} of ``ids``, from the files by pyarrow and
        cv2 alone."""
        import pyarrow.dataset as ds
        wanted = sorted(set(int(i) for i in ids))
        table = ds.dataset(path, format='parquet').to_table(
            columns=['noun_id', 'image'],
            filter=ds.field('noun_id').isin(wanted))
        got = dict(zip(table.column('noun_id').to_pylist(),
                       table.column('image').to_pylist()))
        with ThreadPoolExecutor(os.cpu_count() or 1) as pool:
            return dict(zip(wanted, pool.map(lambda i: plain_decode(got[i]), wanted)))

    def all_row_ids(self, path):
        import pyarrow.parquet as pq
        return np.asarray(pq.read_table(path, columns=['noun_id'])
                          .column('noun_id').to_numpy())

    def delivered_error(self, path, delivered):
        """Largest difference, in the image's own units (LSB), between a
        delivered batch (host copy) and the stored rows of the same ids."""
        ids = np.asarray(delivered['noun_id'])
        stored = self.stored_rows(path, ids)
        worst = 0
        for row_id, image in zip(ids, np.asarray(delivered['image'])):
            diff = np.abs(image.astype(np.int16) - stored[int(row_id)])
            worst = max(worst, int(diff.max()))
        return worst

    def reference_batches(self, path, ids_per_step):
        """The batches of the first steps as the files hold them."""
        stored = self.stored_rows(path, np.concatenate(ids_per_step))
        return [{'image': np.stack([stored[int(i)] for i in ids]),
                 'noun_id': np.asarray(ids, np.int32)} for ids in ids_per_step]

    # -- yardstick: the plain reference -----------------------------------------

    def reference_step(self, precision='float32'):
        """Plain ``jax.numpy`` ResNet-50 in float32 at ``highest`` matmul
        precision: one SGD-momentum step ``(params, trace, images, ids) ->
        (params, trace, (loss, per-row losses), the gradient's norm leaf by leaf)``.

        ``precision='fp8'`` is the control: every convolution's and the
        classifier's operands rounded to float8_e4m3fn under a per-tensor
        scale (cotangents to bfloat16), the precision below the bfloat16 the
        configuration states.  ``precision='bf16'`` rounds the same operands and
        their cotangents to bfloat16 and nothing else: it shows how much of a
        gap is the stated precision's own rounding.
        """
        import jax
        import jax.numpy as jnp
        from jax import lax

        highest = lax.Precision.HIGHEST
        q = oracle.operand_rounding(precision)

        def conv(x, p, stride, padding='SAME'):
            return lax.conv_general_dilated(
                q(x), q(p['kernel']), (stride, stride), padding,
                dimension_numbers=('NHWC', 'HWIO', 'NHWC'), precision=highest)

        def norm(x, p):
            mean = jnp.mean(x, (0, 1, 2))
            var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
            return (x - mean) * lax.rsqrt(var + BN_EPS) * p['scale'] + p['bias']

        def block(p, x, stride):
            y = jax.nn.relu(norm(conv(x, p['Conv_0'], 1), p['BatchNorm_0']))
            y = jax.nn.relu(norm(conv(y, p['Conv_1'], stride), p['BatchNorm_1']))
            y = norm(conv(y, p['Conv_2'], 1), p['BatchNorm_2'])
            if 'Conv_3' in p:
                x = norm(conv(x, p['Conv_3'], stride), p['BatchNorm_3'])
            return jax.nn.relu(y + x)

        def loss_fn(params, images_u8, ids):
            x = images_u8.astype(jnp.float32) / 255.0
            x = conv(x, params['Conv_0'], 2, [(3, 3), (3, 3)])
            x = jax.nn.relu(norm(x, params['BatchNorm_0']))
            x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                                  ((0, 0), (1, 1), (1, 1), (0, 0)))
            index = 0
            for stage, (_, blocks) in enumerate(STAGES):
                for j in range(blocks):
                    stride = 2 if stage > 0 and j == 0 else 1
                    # one block's activations at a time: float32 at batch 256
                    # does not fit the chip otherwise
                    x = jax.checkpoint(block, static_argnums=(2,))(
                        params['BottleneckBlock_%d' % index], x, stride)
                    index += 1
            x = jnp.mean(x, (1, 2))
            dense = params['Dense_0']
            logits = jnp.dot(q(x), q(dense['kernel']), precision=highest) + dense['bias']
            picked = jnp.take_along_axis(
                jax.nn.log_softmax(logits), (ids % LABEL_MODULUS)[:, None], axis=1)
            return -jnp.mean(picked), -picked[:, 0]

        lr, momentum = np.float32(self.lr), np.float32(self.momentum)

        def step(params, trace, images, ids):
            (loss, losses), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, images, ids)
            trace = jax.tree_util.tree_map(lambda g, t: g + momentum * t, grads, trace)
            params = jax.tree_util.tree_map(lambda p, t: p - lr * t, params, trace)
            return params, trace, (loss, losses), jax.tree_util.tree_map(
                lambda g: jnp.sqrt(jnp.sum(jnp.square(g))), grads)
        return step

    def reference(self, key, batches, precision='float32', fault=None):
        """``len(batches)`` reference steps from the seed's weights.  Returns
        per-step losses and, leaf by leaf, the norm of the first gradient and of
        the parameters' change after the last step.  ``fault='half_batch'``
        leaves the second half of every batch out."""
        import jax
        import jax.numpy as jnp

        if precision not in self._jits:
            self._jits[precision] = jax.jit(self.reference_step(precision),
                                            donate_argnums=(0, 1))
            self._jits['init'] = jax.jit(self.init_params)
            self._jits['change'] = jax.jit(lambda a, b: jax.tree_util.tree_map(
                lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))), a, b))
        step, init, change = (self._jits[k] for k in (precision, 'init', 'change'))
        params = init(key)
        trace = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses, sample_losses, grad_norms = [], [], None
        for batch in batches:
            images, ids = batch['image'], batch['noun_id']
            if fault == 'half_batch':
                images, ids = images[:len(ids) // 2], ids[:len(ids) // 2]
            params, trace, (loss, per_row), norms = step(params, trace, images, ids)
            losses.append(float(loss))
            sample_losses.append(np.asarray(per_row))
            if grad_norms is None:
                grad_norms = jax.device_get(norms)
        return {'losses': losses, 'sample_losses': sample_losses,
                'grad_norms': grad_norms,
                'change_norms': jax.device_get(change(params, init(key)))}

    def rehearsal_programs(self, key):
        """(name, function, argument shapes, donated arguments) of the programs
        worth compiling for a described chip before a chip run."""
        import jax
        import jax.numpy as jnp
        batch = {'image': jax.ShapeDtypeStruct((self.batch, self.hw, self.hw, 3),
                                               jnp.uint8),
                 'noun_id': jax.ShapeDtypeStruct((self.batch,), jnp.int32)}
        state = jax.eval_shape(self.init_state, key)
        params = state[0]
        return [('step', self.train_step(), (state, batch), ()),
                ('reference_step', self.reference_step(),
                 (params, params, batch['image'], batch['noun_id']), (0, 1)),
                ('control_step', self.reference_step('fp8'),
                 (params, params, batch['image'], batch['noun_id']), (0, 1))]

    # -- yardstick: what the algorithm needs ------------------------------------

    def needed_flops_per_sample(self):
        """Forward and backward: the backward pass multiplies each layer's
        cotangent once with the weights and once with the activations, so
        three passes of two operations a multiply-accumulate."""
        return 3 * 2 * forward_macs(self.hw, self.num_classes)

    def needed_bytes_per_step(self):
        """What one step has to move whatever implements it: the uint8 batch
        read once, and parameters and momentum (float32) each read and written
        once.  Activations are the implementation's."""
        params = sum(k * k * c_in * c_out + 2 * c_out
                     for _, k, c_in, c_out, _, _ in
                     conv_layers(self.hw, self.num_classes)) - self.num_classes
        return self.batch * self.hw * self.hw * 3 + 4 * 4 * params
