"""Compile the main path's kernels and jitted pieces for a *described* TPU
v5e, at real widths — what the Pallas interpreter and the CPU backend cannot
see: tile alignment, scoped-VMEM limits, whether a kernel is there at all.

Nothing runs and no chip is attached: ``jax.experimental.topologies``
describes a ``v5e:2x2`` host and the installed TPU compiler compiles for it.
A compile that passes is not a chip run (``python chip_smoke.py`` is); it is
the cheapest filter before one, and it guards every later PR at no chip time.

One file on purpose: the worker that runs it loads the TPU library and keeps
it until it exits, so the topology is described inside a module-scoped
fixture — never at import, in a ``skipif`` or a ``parametrize`` argument —
and every compile happens in the test's own process.
"""

import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

SHAPE = (4, 2048, 16, 128)
#: A length on the chunked path for each dtype (``seq > kv_chunk_default``).
#: float32 at head_dim 128 was refused here before the default chunk was
#: sized from bytes: "Scoped allocation with size 16.13M and limit 16.00M".
CHUNKED_SHAPE = {'bfloat16': (1, 16384, 8, 128), 'float32': (1, 8192, 16, 128)}
ROWS, BATCH, IMAGE = 3328, 256, (224, 224, 3)


@pytest.fixture(scope='module')
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform='tpu',
                                            topology_name='v5e:2x2')
    except Exception as e:  # noqa: BLE001 — whatever it raises: no compiler here
        pytest.skip('no v5e:2x2 topology can be described here: %s' % e)
    # An executable compiled for a described chip is written to the
    # persistent cache but cannot be read back without one: keep it off.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update('jax_enable_compilation_cache', enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope='module')
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def flash(monkeypatch):
    """``flash_attention`` as it lowers on the chip: the wrapper picks the
    interpreter (and skips the 128-lane block rounding) from
    ``jax.default_backend()``, which here is still the CPU."""
    import petastorm_tpu.ops.flash_attention   # noqa: F401 — the module, not the op
    module = sys.modules['petastorm_tpu.ops.flash_attention']
    monkeypatch.setattr(module, '_auto_interpret', lambda: False)
    return module.flash_attention


#: ``lfm2.packed``'s own call: 4 packed rows of 8,192 tokens, 32 heads of 64.
CELL_SHAPE = (4, 8192, 32, 64)


def _flash_program(flash, mode):
    if mode == 'fwd':
        return lambda q, k, v: flash(q, k, v, causal=True)
    if mode == 'bwd':
        return jax.grad(lambda q, k, v: flash(q, k, v, causal=True)
                        .astype(jnp.float32).sum(), argnums=(0, 1, 2))
    if mode == 'packed_fwd':
        return lambda q, k, v, seg: flash(q, k, v, causal=True, segment_ids=seg)
    return jax.grad(lambda q, k, v, seg: flash(q, k, v, causal=True,
                                               segment_ids=seg)
                    .astype(jnp.float32).sum(), argnums=(0, 1, 2))


@pytest.mark.parametrize('length,dtype,mode', [
    (length, dtype, mode) for length in ('2048', 'chunked')
    for dtype in ('bfloat16', 'float32') for mode in ('fwd', 'bwd', 'packed')
] + [('cell', 'bfloat16', 'packed_fwd'), ('cell', 'bfloat16', 'packed')])
def test_flash_attention_compiles_at_its_defaults(one_chip, flash, length,
                                                  dtype, mode):
    from petastorm_tpu.ops.flash_attention import kv_chunk_default
    shape = {'2048': SHAPE, 'cell': CELL_SHAPE}.get(length) or CHUNKED_SHAPE[dtype]
    if length == 'chunked':
        assert shape[1] > kv_chunk_default(shape[3], dtype)
    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)
    args = (x, x, x)
    if mode.startswith('packed'):
        args += (jax.ShapeDtypeStruct(shape[:2], jnp.int32, sharding=one_chip),)
    compiled = jax.jit(_flash_program(flash, mode)).lower(*args).compile()
    assert 'tpu_custom_call' in compiled.as_text()


@pytest.mark.parametrize('rows', [64, BATCH, ROWS],
                         ids=['device_shard', 'batch', 'epoch_put_once'])
def test_transfer_plane_unpack_compiles_compactly(one_chip, rows):
    """The coalesced ImageNet slab -> ``image`` + ``noun_id``, at the sizes
    the plane ships it: one chip's share of a sharded batch, a batch, and a
    whole epoch (``DeviceInMemDataLoader`` via ``put_once``).

    Both faults this pins were invisible off the chip's compiler.  Sliced
    without a barrier, the program took the compiler ~4 s *per image* (22 min
    at batch 256).  Reshaped directly, the image passed through a row-major
    tiled copy that pads 3 channels to 128 lanes: 1.6 GB of scratch a batch,
    and the epoch refused ("Allocation (size=21374173184) would exceed
    memory")."""
    import time

    from petastorm_tpu.jax.transfer import TransferPlane
    plane = TransferPlane(max_staging_bytes=1 << 30)
    batch = {'image': np.zeros((rows,) + IMAGE, np.uint8),
             'noun_id': np.zeros((rows,), np.int64)}
    layout, unpack, plan = plane._prepare(batch)
    assert plan is None and len(layout.fields) == 2
    slab = jax.ShapeDtypeStruct((layout.slab_nbytes,), jnp.uint8,
                                sharding=one_chip)
    t0 = time.monotonic()
    compiled = unpack.lower(slab).compile()
    assert time.monotonic() - t0 < 120
    memory = compiled.memory_analysis()
    image_bytes = rows * int(np.prod(IMAGE))
    assert memory.output_size_in_bytes >= image_bytes
    assert memory.temp_size_in_bytes <= 4 * image_bytes


def test_device_inmem_gather_compiles_at_epoch_size(one_chip, tmp_path):
    """``DeviceInMemDataLoader``'s fused per-step gather over an HBM-resident
    epoch of real size.  The loader is built over a tiny dataset (its jitted
    gather closes over the batch size only) and lowered at the real shapes."""
    from petastorm_tpu import make_reader
    from petastorm_tpu.etl.dataset_metadata import DatasetWriter
    from petastorm_tpu.jax import DeviceInMemDataLoader
    from petastorm_tpu.unischema import Unischema, UnischemaField

    url = 'file://' + str(tmp_path / 'tiny')
    schema = Unischema('Tiny', [UnischemaField('noun_id', np.int64, (), None,
                                               False)])
    with DatasetWriter(url, schema) as writer:
        writer.write_many({'noun_id': np.int64(i)} for i in range(2 * BATCH))
    reader = make_reader(url, num_epochs=1, columnar_decode=True,
                         workers_count=1)
    with DeviceInMemDataLoader(reader, batch_size=BATCH, seed=0) as loader:
        assert next(iter(loader))['noun_id'].shape == (BATCH,)
        gather = loader._gather_fn
    cache = {'image': jax.ShapeDtypeStruct((ROWS,) + IMAGE, jnp.uint8,
                                           sharding=one_chip),
             'noun_id': jax.ShapeDtypeStruct((ROWS,), jnp.int32,
                                             sharding=one_chip)}
    order = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    start = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = gather.lower(cache, order, start).compile()
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes >= BATCH * int(np.prod(IMAGE))
    # the epoch cache plus one batch, far inside one chip's 16 GB
    assert (memory.argument_size_in_bytes + memory.output_size_in_bytes
            + memory.temp_size_in_bytes) < 2 << 30


#: The tier's fields beside which it is compiled: ImageNet's row alone, and
#: with a narrow multi-byte field (DLRM's 13 dense float32, riding as bf16).
TIER_FIELDS = {'imagenet': {'image': (np.uint8, IMAGE), 'noun_id': (np.int64, ())},
               'imagenet_and_dense': {'image': (np.uint8, IMAGE),
                                      'noun_id': (np.int64, ()),
                                      'dense': (np.float32, (13,))}}


@pytest.mark.parametrize('program', ['gather', 'update'])
@pytest.mark.parametrize('fields,capacity', [('imagenet', 9984),
                                             ('imagenet', 19968),
                                             ('imagenet_and_dense', 9984)])
def test_residency_programs_need_no_scratch_that_grows_with_the_tier(
        one_chip, monkeypatch, fields, capacity, program):
    """The resident tier's warm gather and its admission, over a tier of
    ImageNet rows as large as the benchmark's and twice that.

    Stored as ``u8[capacity, 224, 224, 3]`` the slab took the device's NHWC
    layout, batch axis in the lanes, and the gather re-laid all of it into a
    temporary of its size every step (1.50 GB at 9,984 rows, 3.0 GB at
    19,968; 7.4 ms a step on the v5e).  Stored flat, ``take`` still sliced the
    whole slab by columns (1.2 GB)."""
    import time

    from petastorm_tpu.jax import residency
    from petastorm_tpu.telemetry import MetricsRegistry
    # as it lowers on the chip: the row copies through Mosaic, not interpreted
    monkeypatch.setattr(residency, '_auto_interpret', lambda: False)
    host = {name: np.zeros((BATCH,) + shape, dtype)
            for name, (dtype, shape) in TIER_FIELDS[fields].items()}
    plan = residency.wire_plan(host, 'auto')
    tier = residency.ResidencyTier(
        plan, capacity, BATCH, None,
        residency.ensure_counters(MetricsRegistry('compile')))
    tier._donate = True                # what the tier reads on the chip
    assert tier._slab_rows['image'] == (1176, 128)

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)
    slabs = {name: on_chip((capacity,) + tier._slab_rows[name], f.wire)
             for name, f in plan.fields.items()}
    start = on_chip((), jnp.int32)
    t0 = time.monotonic()
    if program == 'gather':
        compiled = tier._gather_program(BATCH).lower(
            slabs, on_chip((capacity,), jnp.int32),
            on_chip((capacity,), jnp.int32), start).compile()
        assert 'tpu_custom_call' in compiled.as_text()
    else:
        batch = {name: on_chip((BATCH,) + f.row_shape, f.wire)
                 for name, f in plan.fields.items()}
        compiled = tier._update_program().lower(slabs, batch, start).compile()
    assert time.monotonic() - t0 < 60
    memory = compiled.memory_analysis()
    batch_bytes = BATCH * plan.wire_row_nbytes
    assert memory.temp_size_in_bytes <= 4 * batch_bytes
    if program == 'gather':
        assert memory.output_size_in_bytes >= batch_bytes
    else:                               # the donated slabs are the output
        assert memory.alias_size_in_bytes >= capacity * plan.wire_row_nbytes


#: What the v5e's allocator offers a program (``memory_stats()['bytes_limit']``
#: on the chip; PERF.md section 4).
V5E_BYTES_LIMIT = 16.91e9


def _kernel_calls(text, kinds):
    """The custom calls in ``text`` of the kernels ``pt_<kind>_*`` for the
    ``kinds`` (a regular expression), sorted, without XLA's ``.<n>``."""
    import re
    return sorted(re.findall(
        r'^\s*(?:ROOT )?%%?(pt_(?:%s)_\w+?)(?:\.\d+)? = .* custom-call\(' % kinds,
        text, re.M))


def _peak(compiled):
    """What the program holds at once: state, outputs not donated into it,
    and temporaries."""
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def test_lfm2_step_compiles_at_its_published_sizes_inside_the_chips_memory(
        one_chip, flash):
    """``lfm2.packed``'s step as the benchmark jits it (state donated, 4
    packed rows of 8,192 tokens, every width as published, recomputation a
    layer): the flash kernels and the grouped expert products lower for the
    chip under the names the per-layer metrics read, the attention layer's
    forward kernel once (the recomputation keeps its ``out`` and ``lse``), and
    parameters, Adam's moments and the step's temporaries fit the chip (the
    compiler's count: 14.42 GB, 14.15 while the recomputation called the
    forward kernel again)."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, 'benchmarks')
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import catalog
    base = os.path.join(bench, 'configs', 'lfm2-24b-a2b')
    with open(base + '.json') as f:
        config = catalog._module(base + '.py').Config(json.load(f))
    assert (config.batch, config.max_len, config.hidden) == (4, 8192, 2048)
    name, step, shapes, donated = config.rehearsal_programs(
        jax.eval_shape(lambda: jax.random.key(0)))[0]
    assert name == 'step'
    placed = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)
    compiled = jax.jit(step, donate_argnums=donated).lower(*placed).compile()
    text = compiled.as_text()
    for kernel in ('pt_flash_fwd', 'pt_flash_bwd_dq', 'pt_flash_bwd_dkv',
                   'ragged-dot'):
        assert kernel in text, kernel
    # the attention layer's forward call once: its recomputation keeps it
    assert _kernel_calls(text, 'flash') == sorted(
        ['pt_flash_fwd', 'pt_flash_bwd_dq', 'pt_flash_bwd_dkv'])
    # no loop: ``kda_scan_ms`` reads a step's ``while`` operations as the delta
    # rule's, and this step has none
    assert ' while(' not in text
    state, peak = compiled.memory_analysis().argument_size_in_bytes, _peak(compiled)
    # parameters and Adam's two moments in float32: 12 B a parameter
    assert state == pytest.approx(12 * config.parameter_count(), rel=0.01)
    assert 0.6 * V5E_BYTES_LIMIT < peak < V5E_BYTES_LIMIT, peak


#: ``kimilinear.packed``'s own call: 2 packed rows of 8,192 tokens, 32 heads,
#: q and k of 128 + 64, v of 128.
LATENT_SHAPES = ((2, 8192, 32, 192), (2, 8192, 32, 128))


@pytest.mark.parametrize('mode', ['packed_fwd', 'packed'])
def test_flash_attention_compiles_at_two_head_sizes(one_chip, flash, mode):
    """q/k at 192 (one and a half lane tiles) and v at 128, neither padded to
    the other: the three kernels lower through Mosaic at 512 x 512 tiles.  K
    at 256 lanes and V at 128 hold 5,461 rows in the kernels' VMEM share, so
    a row of 8,192 tokens streams them in two chunks."""
    from petastorm_tpu.ops.flash_attention import kv_chunk_default
    qk, v = (jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
             for shape in LATENT_SHAPES)
    assert kv_chunk_default(192, 'bfloat16', 128) == 5461
    assert kv_chunk_default(128, 'bfloat16') == kv_chunk_default(128, 'bfloat16', 128) \
        == 8192
    seg = jax.ShapeDtypeStruct(LATENT_SHAPES[0][:2], jnp.int32, sharding=one_chip)
    compiled = jax.jit(_flash_program(flash, mode)).lower(qk, qk, v, seg).compile()
    text = compiled.as_text()
    assert 'pt_flash_fwd' in text
    assert ('pt_flash_bwd_dkv' in text) == (mode == 'packed')


#: ``kimilinear.packed``'s delta-rule convolutions: 2 packed rows of 8,192
#: tokens, 32 heads of 128.
CONV_SHAPE = (2, 8192, 32 * 128)


@pytest.mark.parametrize('mode', ['fwd', 'grad'])
def test_the_delta_rule_convolutions_compile_at_the_cells_sizes(one_chip, flash, mode):
    """``qkv_conv``'s kernels through Mosaic at ``kimilinear.packed``'s
    sizes: the tiles with their halos, the rolls and the lane reductions of
    a head lower, and nothing of float32 at the projections' size is left
    to XLA under the op's scope."""
    from petastorm_tpu.ops.qkv_conv import qkv_conv
    u = jax.ShapeDtypeStruct(CONV_SHAPE, jnp.bfloat16, sharding=one_chip)
    taps = jax.ShapeDtypeStruct((4, CONV_SHAPE[2]), jnp.float32, sharding=one_chip)
    seg = jax.ShapeDtypeStruct(CONV_SHAPE[:2], jnp.int32, sharding=one_chip)

    def forward(u, taps, seg):
        with jax.named_scope('pt/kda_conv'):
            return qkv_conv(u, taps, seg)

    def grad(u, taps, seg):
        return jax.grad(lambda u, taps: sum(jnp.sum(jnp.square(y.astype(jnp.float32)))
                                            for y in forward(u, taps, seg)),
                        argnums=(0, 1))(u, taps)
    compiled = jax.jit(forward if mode == 'fwd' else grad).lower(
        (u,) * 3, (taps,) * 3, seg).compile()
    text = compiled.as_text()
    assert _kernel_calls(text, 'taps') == (
        ['pt_taps_fwd'] if mode == 'fwd' else ['pt_taps_bwd', 'pt_taps_fwd'])
    assert not _wide_float32_under(text, 'pt/kda_conv')


def _wide_float32_under(text, scope):
    """Instructions under ``scope`` whose result holds a float32 ``[2, 8192,
    4096]``."""
    import re
    from petastorm_tpu.telemetry import device_scopes
    table = device_scopes.scope_table(text)
    found = []
    for name, rest in re.findall(r'^\s+(?:ROOT\s+)?%?([^\s=]+)\s+=\s+(.*)$', text, re.M):
        entry = table.get(name)
        if entry and entry[0] == scope and 'f32[2,8192,4096]' in rest[:rest.find(
                entry[2] + '(')]:
            found.append(name)
    return found


def test_kimi_linear_step_compiles_at_its_published_sizes_inside_the_chips_memory(
        one_chip, flash):
    """``kimilinear.packed``'s step as the benchmark jits it (state donated, 2
    packed rows of 8,192 tokens, published layers 1-5 at every published
    width, recomputation a layer): the flash kernels at 192 / 128 and the
    grouped expert products lower under the names the per-layer metrics read,
    the MLA layer's forward kernel runs once for each of its two K/V chunks
    (the recomputation keeps the folded ``out`` and ``lse``), the delta rule
    runs as its kernels (two forward calls and a backward one
    for each of the four KDA layers; so do its convolutions, with no float32
    of the projections' size under their scope) and the step holds no loop, so that
    ``kda_scan_ms`` counts no kernel twice, and parameters, Adam's moments and
    the step's temporaries fit the chip."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, 'benchmarks')
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import catalog
    base = os.path.join(bench, 'configs', 'kimi-linear-48b-a3b')
    with open(base + '.json') as f:
        config = catalog._module(base + '.py').Config(json.load(f))
    assert (config.batch, config.max_len, len(config.layers)) == (2, 8192, 5)
    name, step, shapes, donated = config.rehearsal_programs(
        jax.eval_shape(lambda: jax.random.key(0)))[0]
    assert name == 'step'
    placed = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)
    compiled = jax.jit(step, donate_argnums=donated).lower(*placed).compile()
    text = compiled.as_text()
    for kernel in ('pt_flash_fwd', 'pt_flash_bwd_dq', 'pt_flash_bwd_dkv',
                   'ragged-dot'):
        assert kernel in text, kernel
    calls = _kernel_calls(text, 'kda')
    # forward, the layer's recomputed forward, and the backward: 3 x 4 layers
    assert calls == ['pt_kda_bwd'] * 4 + ['pt_kda_fwd'] * 8, calls
    # the layers' convolutions likewise, as their own kernels, and no float32
    # of the projections' size under their scope
    calls = _kernel_calls(text, 'taps')
    assert calls == ['pt_taps_bwd'] * 4 + ['pt_taps_fwd'] * 8, calls
    assert not _wide_float32_under(text, 'pt/kda_conv')
    # the MLA layer's forward once for each of its two K/V chunks: the
    # recomputation keeps the folded result
    calls = _kernel_calls(text, 'flash')
    assert calls == sorted(['pt_flash_fwd'] * 2 + ['pt_flash_bwd_dq'] * 2
                           + ['pt_flash_bwd_dkv'] * 3), calls
    assert ' while(' not in text
    state, peak = compiled.memory_analysis().argument_size_in_bytes, _peak(compiled)
    assert state == pytest.approx(12 * config.parameter_count(), rel=0.01)
    # 14.57 GB with the kernels through Mosaic (14.16 while the convolutions
    # were XLA's fusions, 14.54 while the delta rule was a scan; the same to
    # the byte with and without the flash forward's results kept across the
    # recomputation): the peak lies in the last block's expert layer, where
    # as many bytes are live as before and the temporaries pack less tightly.
    # A sixth layer (+1.65 GB of state and gradient) would not fit
    assert 0.6 * V5E_BYTES_LIMIT < peak < 14.7e9, peak


def test_phi4flash_step_compiles_at_its_published_sizes_inside_the_chips_memory(
        one_chip, flash):
    """``phi4flash.packed``'s step as the benchmark jits it (state donated, 2
    packed rows of 8,192 tokens, published layers 0, 1, 16, 17, 18, 19 at every
    published width, recomputation a layer): the selective scans run as their
    kernels (a forward call, the layer's recomputed one and a backward call
    for each of the two Mamba layers), differential attention as the flash
    kernels (the window layer, the full layer and the cross layer: a forward
    call, whose ``out`` and ``lse`` the recomputation keeps, and the two
    backward calls each), no loop, and parameters, Adam's moments and the
    step's temporaries fit the chip at 2 rows (the compiler's count: 14.35 GB,
    14.17 while the recomputation called the forward kernel again; under the
    15.5 GB at which the cell would drop to 1 row)."""
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, 'benchmarks')
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import catalog
    base = os.path.join(bench, 'configs', 'phi-4-mini-flash-reasoning')
    with open(base + '.json') as f:
        config = catalog._module(base + '.py').Config(json.load(f))
    assert (config.batch, config.max_len, len(config.kinds)) == (2, 8192, 6)
    name, step, shapes, donated = config.rehearsal_programs(
        jax.eval_shape(lambda: jax.random.key(0)))[0]
    assert name == 'step'
    placed = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), shapes)
    compiled = jax.jit(step, donate_argnums=donated).lower(*placed).compile()
    text = compiled.as_text()
    calls = _kernel_calls(text, 'ssm_scan|flash')
    assert calls == sorted(['pt_ssm_scan_fwd'] * 4 + ['pt_ssm_scan_bwd'] * 2
                           + ['pt_flash_fwd'] * 3 + ['pt_flash_bwd_dq'] * 3
                           + ['pt_flash_bwd_dkv'] * 3), calls
    assert ' while(' not in text
    state, peak = compiled.memory_analysis().argument_size_in_bytes, _peak(compiled)
    assert state == pytest.approx(12 * config.parameter_count(), rel=0.01)
    assert 0.6 * V5E_BYTES_LIMIT < peak < 15.5e9, peak
