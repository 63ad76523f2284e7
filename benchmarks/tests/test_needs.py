"""The needed-FLOPs and needed-bytes functions, pinned to their hand counts,
and the table of peaks."""

import json
import os

import catalog
import oracle

HERE = os.path.dirname(os.path.abspath(__file__))


def config_of(name):
    base = os.path.join(catalog.HERE, 'configs', name)
    return catalog._module(base + '.py'), catalog._json(base + '.json')


def test_resnet50_forward_is_about_4_1_gmac():
    module, spec = config_of('resnet50-imagenet')
    # by hand: stem 118,013,952; stages 667,942,912 + 1,027,604,480
    # + 1,464,336,384 + 809,238,528 (projections included); classifier 2,048,000
    assert module.forward_macs(224) == 4_089_184_256
    assert 4.0e9 < module.forward_macs(224) < 4.2e9
    config = module.Config(spec)
    assert config.needed_flops_per_sample() == 6 * module.forward_macs(224)


def test_resnet50_stem_and_first_block_by_hand():
    module, _ = config_of('resnet50-imagenet')
    layers = {path: (k, c_in, c_out, stride, out)
              for path, k, c_in, c_out, stride, out in module.conv_layers(224)}
    assert layers[('Conv_0',)] == (7, 3, 64, 2, 112)
    assert layers[('BottleneckBlock_0', 'Conv_1')] == (3, 64, 64, 1, 56)
    assert layers[('BottleneckBlock_3', 'Conv_1')] == (3, 128, 128, 2, 28)
    assert layers[('BottleneckBlock_3', 'Conv_3')] == (1, 256, 512, 2, 28)
    assert layers[('Dense_0',)] == (1, 2048, 1000, 1, 1)
    assert len(layers) == 1 + 16 * 3 + 4 + 1


def test_resnet50_needed_bytes_are_the_batch_and_the_optimizer_state():
    module, spec = config_of('resnet50-imagenet')
    config = module.Config(spec)
    params = 25_557_032       # torchvision's count of ResNet-50's parameters
    assert config.needed_bytes_per_step() == 256 * 224 * 224 * 3 + 16 * params


def test_dlrm_mlps_and_interaction_by_hand():
    module, spec = config_of('dlrm-mlperf-criteo')
    config = module.Config(spec)
    bottom = 13 * 512 + 512 * 256 + 256 * 128                    # 170,496
    top = (128 + 351) * 1024 + 1024 * 1024 + 1024 * 512 + 512 * 256 + 256 * 1
    assert (bottom, top) == (170_496, 2_194_688)
    assert config.pairs == 27 * 26 // 2 == 351
    assert config.mlp_macs() == bottom + top == 2_365_184
    assert config.needed_flops_per_sample() == 6 * (bottom + top + 351 * 128)
    # the chip's share: 1/8 of every table's rows, 55296 / 8 rows a step
    assert config.batch == spec['batch'] == 55296 // spec['table_shards'] == 6912
    assert config.table_rows == spec['table_rows']
    assert sum(config.table_rows) == 23_470_935
    assert min(config.table_rows) == 1 and max(config.table_rows) == 4_997_472
    # needed bytes: touched rows read and written once, never the whole table
    touched = 6912 * 26 * 128 * 4
    mlp_params = bottom + top + (512 + 256 + 128) + (1024 + 1024 + 512 + 256 + 1)
    assert config.needed_bytes_per_step() == 2 * touched + 8 * mlp_params \
        + 6912 * 41 * 4
    assert config.needed_bytes_per_step() < 0.02 * sum(config.table_rows) * 512


def test_dlrm_weights_have_the_shapes_the_model_asks_for():
    import jax
    import jax.numpy as jnp
    from petastorm_tpu.models.dlrm import DLRM
    module, spec = config_of('dlrm-mlperf-criteo')
    config = module.Config(spec, tiny=True)
    key = jax.eval_shape(lambda: oracle.key_of(0))
    mine = jax.eval_shape(config.init_params, key)
    model = DLRM(vocab_sizes=tuple(config.table_rows), embedding_dim=128,
                 bottom_mlp=(512, 256, 128), top_mlp=(1024, 1024, 512, 256, 1))
    theirs = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 13)), jnp.zeros((2, 26), jnp.int32)))
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), tree)
    assert shapes(mine) == shapes(theirs['params'])


def test_weights_have_the_shapes_the_model_asks_for():
    import jax
    import jax.numpy as jnp
    from petastorm_tpu.models.resnet import ResNet50
    module, spec = config_of('resnet50-imagenet')
    config = module.Config(spec, tiny=True)
    key = jax.eval_shape(lambda: oracle.key_of(0))
    mine = jax.eval_shape(config.init_state, key)
    theirs = jax.eval_shape(
        lambda: ResNet50(num_classes=1000).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3), jnp.bfloat16), train=True))
    shapes = lambda tree: jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), tree)
    assert shapes(mine[0]) == shapes(theirs['params'])
    assert shapes(mine[1]) == shapes(theirs['batch_stats'])


def test_peaks_name_their_source_and_refuse_an_unknown_chip():
    import pytest
    with open(os.path.join(catalog.HERE, 'peaks.json')) as f:
        table = json.load(f)
    assert 'TPU v5e' in table['source']
    v5e = catalog.peaks('TPU v5 lite')
    assert (v5e['bf16_flops_per_s'], v5e['hbm_bytes_per_s']) == (197e12, 819e9)
    with pytest.raises(SystemExit):
        catalog.peaks('TPU v9 imaginary')
