"""The quickest proof that petastorm-tpu still starts on the chip.

One process, one TPU chip.  Drives the flagship path once at real size —
ImageNet-shaped JPEG Parquet -> ``make_reader(columnar_decode=True)`` ->
``petastorm_tpu.jax.DataLoader`` (transfer plane at its default) -> a jitted
ResNet-50 train step — plus the resident loaders and the attention kernels,
and checks what comes out by the repo's own means.  Every phase that fails
ends the run with a non-zero exit; nothing is caught and continued.

    python chip_smoke.py              # one chip, every phase
    python chip_smoke.py --chips 4    # four chips, only the across-chip phase

All data is generated from ``--seed`` inside the run.  Without a TPU the run
fails at its first phase and prints no result line.  The last line of stdout
is ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
every earlier line is one JSON object per phase (seconds, counters, errors).
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

#: Real sizes: a few thousand 224x224 JPEGs in row groups of the writer's
#: default size, batch 256, 13 optimizer steps an epoch.
ROWS, BATCH, IMAGE_HW = 3328, 256, (224, 224)
#: The four-chip phase: the same global batch, 64 rows a chip, four steps;
#: ring attention with 2048 rows of the sequence a chip.
ROWS_4, RING_SHAPE = 1024, (2, 8192, 16, 128)

#: bf16 rounds to 8 bits of mantissa; flash accumulates in float32 from the
#: same bf16 inputs the float32 oracle is given, so what is left is the
#: rounding of the output (forward) and of the cotangents (backward, taken
#: relative to the largest oracle gradient).
KERNEL_FWD_TOL, KERNEL_BWD_RTOL = 2e-2, 3e-2
#: Data-parallel vs one-device ResNet-50 loss on the same global batch from
#: the same state: the same math in another reduction order, in bf16.
DP_LOSS_RTOL = 5e-3


class CheckFailed(Exception):
    """A phase's check did not hold.  Never caught: it ends the run."""


def check(cond, message):
    if not cond:
        raise CheckFailed(message)


def report(phase, t0, **facts):
    print(json.dumps(dict(phase=phase, seconds=round(time.monotonic() - t0, 2),
                          **facts), default=str), flush=True)


class CompileMeter(object):
    """Seconds this process spent getting executables (compiling, or reading
    them back from the persistent cache) and how often the cache answered."""

    def __init__(self):
        import jax.monitoring
        self.seconds, self.programs, self.cache_hits, self.cache_misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == '/jax/core/compile/backend_compile_duration':
            self.seconds += seconds
            self.programs += 1

    def _event(self, event, **_):
        if event == '/jax/compilation_cache/cache_hits':
            self.cache_hits += 1
        elif event == '/jax/compilation_cache/cache_misses':
            self.cache_misses += 1

    def facts(self):
        return {'compile_seconds': round(self.seconds, 2),
                'programs': self.programs, 'cache_hits': self.cache_hits,
                'cache_misses': self.cache_misses}


# -- phase 1: device ---------------------------------------------------------

def phase_device(chips):
    from petastorm_tpu.utils import ensure_jax_backend
    t0 = time.monotonic()
    devices = ensure_jax_backend()
    device = {'platform': devices[0].platform, 'kind': devices[0].device_kind,
              'count': len(devices)}
    check(device['platform'] == 'tpu',
          'no TPU: jax found %r; this script has no CPU branch' % (device,))
    check(device['count'] == chips,
          'asked for %d chip(s), jax sees %d' % (chips, device['count']))
    report('device', t0, **device)
    return device


# -- phase 2: native decode plane, built in this run -------------------------

def phase_native():
    """Compile ``libpt_decode.so`` from ``pt_decode.cc`` now.  The binary is
    ignored by git and ``get_lib`` rebuilds only a missing or outdated one —
    a stale one that travelled with the checkout would be loaded as is, and
    a failed build would degrade to cv2 decode with a warning."""
    from petastorm_tpu import native
    t0 = time.monotonic()
    so = os.path.join(os.path.dirname(native.__file__), 'libpt_decode.so')
    started = time.time()
    native.build()
    check(os.path.getmtime(so) >= started - 1.0,
          '%s was not built in this run' % so)
    check(native.get_lib() is not None, 'the native decode library built '
                                        'but did not load')
    report('native', t0, built=so)


# -- dataset -----------------------------------------------------------------

def synthetic_images(rng, n, hw):
    """Smooth gradient + block colour + pixel noise: compresses like a
    photograph (pure noise would make JPEG decode artificially cheap, flat
    colour artificially small)."""
    h, w = hw
    base = np.linspace(0, 255, h * w * 3, dtype=np.float32).reshape(h, w, 3)
    blocks = rng.integers(0, 64, (n, 8, 8, 3), np.int16) \
        .repeat(h // 8, 1).repeat(w // 8, 2)
    noise = rng.integers(-12, 13, (n, h, w, 3), np.int16)
    return np.clip(base + blocks + noise, 0, 255).astype(np.uint8)


def write_dataset(url, rows, hw, seed):
    from petastorm_tpu.codecs import CompressedImageCodec
    from petastorm_tpu.etl.dataset_metadata import DatasetWriter
    from petastorm_tpu.unischema import Unischema, UnischemaField

    schema = Unischema('ImagenetLike', [
        UnischemaField('noun_id', np.int64, (), None, False),
        UnischemaField('image', np.uint8, (hw[0], hw[1], 3),
                       CompressedImageCodec('jpeg', quality=85), False),
    ])
    rng = np.random.default_rng(seed)
    with DatasetWriter(url, schema, workers=os.cpu_count() or 1) as writer:
        for start in range(0, rows, 256):
            images = synthetic_images(rng, min(256, rows - start), hw)
            for i, image in enumerate(images):
                writer.write({'noun_id': np.int64(start + i), 'image': image})


def stored_jpegs(url):
    """{row id: stored JPEG bytes}, read with pyarrow alone."""
    import pyarrow.parquet as pq
    table = pq.read_table(url[len('file://'):], columns=['noun_id', 'image'])
    return dict(zip(table.column('noun_id').to_pylist(),
                    table.column('image').to_pylist()))


def plain_decode(jpeg):
    import cv2
    return cv2.cvtColor(cv2.imdecode(np.frombuffer(jpeg, np.uint8),
                                     cv2.IMREAD_COLOR), cv2.COLOR_BGR2RGB)


# -- the consumer: ResNet-50, bf16 compute / f32 params ----------------------

def make_resnet_step(hw, seed):
    """Jitted ResNet-50 SGD step, uint8 batch in; normalization and the bf16
    cast happen on device.  Labels are ``noun_id % 1000``."""
    import jax
    import jax.numpy as jnp
    import optax
    from petastorm_tpu.models.resnet import ResNet50

    model = ResNet50(num_classes=1000)
    variables = model.init(jax.random.PRNGKey(seed),
                           jnp.zeros((1, hw[0], hw[1], 3), jnp.bfloat16),
                           train=True)
    params, batch_stats = variables['params'], variables['batch_stats']
    tx = optax.sgd(0.1, momentum=0.9)

    def train_step(state, images_u8, noun_id):
        params, batch_stats, opt_state = state
        images = images_u8.astype(jnp.bfloat16) / 255.0

        def loss_fn(p):
            logits, mutated = model.apply(
                {'params': p, 'batch_stats': batch_stats}, images, train=True,
                mutable=['batch_stats'])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), noun_id % 1000).mean()
            return loss, mutated['batch_stats']

        (loss, new_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, new_opt = tx.update(grads, opt_state)
        return (optax.apply_updates(params, updates), new_stats, new_opt), loss

    return train_step, (params, batch_stats, tx.init(params))


def open_reader(url):
    from petastorm_tpu import make_reader
    return make_reader(url, num_epochs=1, shuffle_row_groups=False,
                       columnar_decode=True)


def counters(loader, *names):
    return {name: int(loader.metrics.counter(name).value) for name in names}


# -- phase 3: write + stream + train -----------------------------------------

def stream_epoch(url, batch, step, state, stored=None, **loader_kwargs):
    """One epoch make_reader -> DataLoader -> ``step``; returns (losses, row
    ids in arrival order, max decode error vs ``stored``, loader counters)."""
    import jax
    from petastorm_tpu.jax import DataLoader

    device = {jax.devices()[0]}
    losses, ids, decode_err = [], [], 0
    with DataLoader(open_reader(url), batch_size=batch,
                    **loader_kwargs) as loader:
        for dev_batch in loader:
            check(all(leaf.devices() == device
                      for leaf in jax.tree_util.tree_leaves(dev_batch)),
                  'a batch leaf does not live on %s' % device)
            batch_ids = np.asarray(dev_batch['noun_id'])
            ids.extend(int(i) for i in batch_ids)
            if stored is not None:
                images = np.asarray(dev_batch['image'])
                for row_id, image in zip(batch_ids, images):
                    diff = np.abs(image.astype(np.int16)
                                  - plain_decode(stored[int(row_id)]))
                    decode_err = max(decode_err, int(diff.max()))
            state, loss = step(state, dev_batch['image'], dev_batch['noun_id'])
            losses.append(loss)
        plane = counters(loader, 'batches', 'h2d_batches', 'h2d_degraded',
                         'h2d_bytes_wire')
    return [float(x) for x in losses], ids, decode_err, plane


def phase_stream_train(url, rows, batch, hw, seed):
    import jax
    from petastorm_tpu.jax.transfer import plane_enabled

    t0 = time.monotonic()
    write_dataset(url, rows, hw, seed)
    stored = stored_jpegs(url)
    check(sorted(stored) == list(range(rows)), 'dataset does not hold every row')
    t_written = time.monotonic()

    check(plane_enabled('auto') is True,
          "plane_enabled('auto') is not True: the transfer plane would be off")
    train_step, state = make_resnet_step(hw, seed)
    step = jax.jit(train_step)

    losses, ids, decode_err, plane = stream_epoch(
        url, batch, step, state, stored=stored)     # transfer at its default
    check(sorted(ids) == list(range(rows)),
          'rows did not arrive exactly once (%d arrivals, %d distinct, %d '
          'expected)' % (len(ids), len(set(ids)), rows))
    check(plane['h2d_batches'] > 0, 'no batch rode the transfer plane')
    check(plane['h2d_degraded'] == 0,
          'h2d_degraded == %d: batches fell back to the inline path'
          % plane['h2d_degraded'])
    # +/-1 LSB, as tests/test_native_decode.py: the system libjpeg and cv2's
    # bundled build may round IDCT/upsampling differently.
    check(decode_err <= 1, 'delivered images differ from a plain cv2 decode '
                           'of the stored bytes by %d LSB' % decode_err)
    check(len(losses) >= 12 and np.isfinite(losses).all(),
          'losses not finite over >= 12 steps: %r' % (losses,))

    # Compared with: the same batches, same order, inline device_put.
    inline_losses, inline_ids, _, inline = stream_epoch(
        url, batch, step, state, transfer=False)
    check(inline['h2d_batches'] == 0, 'transfer=False rode the plane')
    check(inline_ids == ids, 'the two passes delivered rows in another order')
    check(inline_losses == losses,
          'plane and inline loss sequences differ: max |d| = %g'
          % np.max(np.abs(np.subtract(inline_losses, losses))))
    # What the step needs of the chip's memory, by the compiler's own count
    # (an executable the run above already built), next to what the
    # allocator saw in use.
    batch_spec = (jax.ShapeDtypeStruct((batch, hw[0], hw[1], 3), np.uint8),
                  jax.ShapeDtypeStruct((batch,), np.int32))
    needs = step.lower(state, *batch_spec).compile().memory_analysis()
    stats = jax.devices()[0].memory_stats() or {}
    report('stream_train', t0, rows=rows, batch=batch, steps=len(losses),
           write_seconds=round(t_written - t0, 2), decode_max_err_lsb=decode_err,
           plane_equals_inline=True, loss_first=losses[0], loss_last=losses[-1],
           step_bytes={'arguments': needs.argument_size_in_bytes,
                       'outputs': needs.output_size_in_bytes,
                       'temporaries': needs.temp_size_in_bytes},
           peak_bytes_in_use=stats.get('peak_bytes_in_use'),
           bytes_limit=stats.get('bytes_limit'), **plane)
    return step, state, ids


# -- phase 4: resident -------------------------------------------------------

def phase_resident(url, rows, batch, step, state, seed):
    """One cold and one warm epoch through ``DeviceInMemDataLoader.scan_epochs``
    (gather + train step fused into one donated ``lax.scan`` dispatch) and
    through ``ResidentDataLoader`` (donated tier admission, jitted warm
    gather).  Each epoch must deliver every row once — checked by the sum of
    the row ids and of the pixels — with finite losses, and the warm epoch
    must fetch nothing from the host."""
    import jax
    import jax.numpy as jnp
    from petastorm_tpu.jax import DeviceInMemDataLoader, ResidentDataLoader

    t0 = time.monotonic()
    steps = rows // batch
    want_ids = rows * (rows - 1) // 2

    def sums(b):
        return (jnp.sum(b['noun_id']),
                jnp.sum(b['image'].astype(jnp.uint32), dtype=jnp.uint32))

    def scan_step(carry, b):
        carry, loss = step(carry, b['image'], b['noun_id'])
        return carry, (loss,) + sums(b)

    def check_epoch(name, losses, id_sums, pixel_sums, want_pixels=None):
        losses, id_sums = np.asarray(losses), np.asarray(id_sums)
        pixels = int(np.asarray(pixel_sums, np.uint64).sum() % (1 << 32))
        check(losses.shape == (steps,) and np.isfinite(losses).all(),
              '%s: losses not finite over %d steps' % (name, steps))
        check(int(id_sums.sum()) == want_ids,
              '%s: row ids sum to %d, not %d: not every row once'
              % (name, id_sums.sum(), want_ids))
        check(want_pixels is None or pixels == want_pixels,
              '%s: pixel checksum differs from the cold epoch' % name)
        return pixels

    # DeviceInMemDataLoader.scan_epochs.  The carry is donated, so it gets
    # its own copy of the state; the reader is closed before the warm epoch,
    # so nothing on the host is left to fetch from.
    carry = jax.tree_util.tree_map(jnp.copy, state)
    with DeviceInMemDataLoader(open_reader(url), batch_size=batch,
                               num_epochs=2, seed=seed) as loader:
        epochs = loader.scan_epochs(scan_step, carry)
        carry, outs = next(epochs)
        pixels = check_epoch('scan_epochs cold', *outs)
    carry, outs = next(epochs)
    check_epoch('scan_epochs warm', *outs, want_pixels=pixels)
    check(next(epochs, None) is None, 'scan_epochs ran a third epoch')
    scan_plane = counters(loader, 'h2d_batches', 'h2d_degraded')
    t_scan = time.monotonic()

    # ResidentDataLoader, per-step, with the already compiled train step.
    jit_sums = jax.jit(sums)
    host_batches = []
    with ResidentDataLoader(open_reader(url), batch_size=batch, num_epochs=2,
                            seed=seed) as loader:
        outs, cur = [], state
        for i, b in enumerate(loader):
            cur, loss = step(cur, b['image'], b['noun_id'])
            outs.append((loss,) + jit_sums(b))
            if (i + 1) % steps == 0:
                host_batches.append(loader.residency_stats['host_batches'])
        resident = loader.residency_stats
    check(len(outs) == 2 * steps, 'ResidentDataLoader yielded %d batches, not '
                                  '%d' % (len(outs), 2 * steps))
    cold, warm = zip(*outs[:steps]), zip(*outs[steps:])
    check(check_epoch('resident cold', *cold) == pixels,
          'resident cold: pixel checksum differs from scan_epochs')
    check_epoch('resident warm', *warm, want_pixels=pixels)
    check(host_batches[0] == steps and host_batches[1] == host_batches[0],
          'warm resident epoch fetched %d host batches (cold: %d)'
          % (host_batches[1] - host_batches[0], host_batches[0]))
    check(resident['hits'] == steps and resident['bypass'] == 0,
          'warm epoch was not served from the tier: %r' % (resident,))
    report('resident', t0, scan_epochs_seconds=round(t_scan - t0, 2),
           samples_per_epoch=steps * batch, warm_host_batches=0,
           scan_epochs_plane=scan_plane, residency=resident)


# -- phase 5: kernels, compiled, not interpreted ------------------------------

def attention_errors(shape, dtype, packed, seed):
    """(forward max |err|, backward max |err| over the largest oracle
    gradient, lowered text) of causal ``flash_attention`` against
    ``parallel.full_attention`` in float32 at highest matmul precision, on the
    same (dtype-rounded) inputs.  The oracle runs one head at a time, so its
    [seq, seq] scores fit where flash is meant to be used."""
    import jax
    import jax.numpy as jnp
    from petastorm_tpu.ops import flash_attention
    from petastorm_tpu.parallel import full_attention

    b, s, h, d = shape
    rng = np.random.default_rng(seed)
    q, k, v, dout = (jnp.asarray(rng.standard_normal(shape), dtype)
                     for _ in range(4))
    seg = None
    if packed:   # three documents of uneven length, then a padded tail
        bounds = [0, s // 5, s // 2, s - s // 8, s]
        seg = jnp.asarray(np.repeat([1, 2, 3, 0], np.diff(bounds))[None]
                          .repeat(b, 0), jnp.int32)

    def out_and_grads(fn):   # grads of sum(out * dout) are the vjp against dout
        def weighted(q, k, v, dout):
            out = fn(q, k, v, causal=True, segment_ids=seg).astype(jnp.float32)
            return (out * dout).sum(), out
        return jax.grad(weighted, argnums=(0, 1, 2), has_aux=True)

    f32 = [x.astype(jnp.float32) for x in (q, k, v, dout)]
    flash = jax.jit(out_and_grads(flash_attention))
    lowered = flash.lower(q, k, v, f32[3]).as_text()
    grads, out = flash(q, k, v, f32[3])

    @jax.jit
    def oracle(q, k, v, dout):   # one head: [b, s, 1, d]
        with jax.default_matmul_precision('highest'):
            return out_and_grads(full_attention)(q, k, v, dout)

    fwd_err, bwd_err, g_scale = 0.0, 0.0, 0.0
    for head in range(h):
        g_want, want = oracle(*(x[:, :, head:head + 1] for x in f32))
        fwd_err = max(fwd_err, float(jnp.max(jnp.abs(
            out[:, :, head:head + 1] - want))))
        for got, w in zip(grads, g_want):
            bwd_err = max(bwd_err, float(jnp.max(jnp.abs(
                got[:, :, head:head + 1].astype(jnp.float32) - w))))
            g_scale = max(g_scale, float(jnp.max(jnp.abs(w))))
    return fwd_err, bwd_err / g_scale, lowered


#: (name, shape, dtype, packed).  The last two take the chunked path at the
#: default chunk (seq > kv_chunk_default): bf16 in two 8192-row chunks,
#: float32 — the dtype whose default the compiler used to refuse — in two of
#: 4096.
KERNEL_CASES = (('dense', (4, 2048, 16, 128), 'bfloat16', False),
                ('packed', (4, 2048, 16, 128), 'bfloat16', True),
                ('chunked', (1, 16384, 2, 128), 'bfloat16', False),
                ('chunked_f32', (1, 8192, 2, 128), 'float32', False))


def phase_kernels(seed):
    import jax.numpy as jnp
    from petastorm_tpu.ops.flash_attention import (_auto_interpret,
                                                   kv_chunk_default)
    t0 = time.monotonic()
    check(_auto_interpret() is False,
          'flash_attention would run in the Pallas interpreter here')
    for name, shape, dtype, packed in KERNEL_CASES:
        dtype = jnp.dtype(dtype)
        if name.startswith('chunked'):
            check(shape[1] > kv_chunk_default(shape[3], dtype),
                  '%s: seq %d does not take the chunked path' % (name, shape[1]))
        fwd, bwd, lowered = attention_errors(shape, dtype, packed, seed)
        check('tpu_custom_call' in lowered,
              '%s: the lowered program holds no tpu_custom_call' % name)
        # printed before it is judged: a failed run still shows the numbers
        print(json.dumps({'kernel': name, 'shape': shape, 'dtype': dtype.name,
                          'fwd_max_err': fwd, 'bwd_max_rel_err': bwd,
                          'lowered': 'tpu_custom_call'}), flush=True)
        check(fwd <= KERNEL_FWD_TOL and bwd <= KERNEL_BWD_RTOL,
              '%s: error against the float32 oracle too large' % name)
    report('kernels', t0, kernels=len(KERNEL_CASES), fwd_tol=KERNEL_FWD_TOL,
           bwd_rtol=KERNEL_BWD_RTOL)


# -- --chips 4: the across-chip path and what it is compared with --------------

def phase_across_chips(url, rows, batch, hw, seed, ring_shape):
    """``DataLoader(sharding=NamedSharding(mesh, P('data')))`` over every
    device — the transfer plane's per-device dispatch — feeding a
    data-parallel ResNet-50 step; compared with the one-device step on the
    same global batches.  Then ring attention over the same devices against
    single-device flash at the same shape."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from petastorm_tpu.jax import DataLoader
    from petastorm_tpu.jax.transfer import plane_enabled
    from petastorm_tpu.ops import flash_attention
    from petastorm_tpu.parallel import make_mesh, make_ring_attention

    t0 = time.monotonic()
    devices = jax.devices()
    n = len(devices)
    write_dataset(url, rows, hw, seed)
    check(plane_enabled('auto') is True,
          "plane_enabled('auto') is not True: the transfer plane would be off")
    mesh = make_mesh({'data': n}, devices=devices)
    sharding = NamedSharding(mesh, P('data'))
    train_step, state = make_resnet_step(hw, seed)
    step = jax.jit(train_step)
    dp_state = jax.device_put(state, NamedSharding(mesh, P()))

    dp_losses, one_losses, ranges = [], [], None
    with DataLoader(open_reader(url), batch_size=batch,
                    sharding=sharding) as loader:
        for dev_batch in loader:
            ids = dev_batch['noun_id']
            check(ids.sharding.is_equivalent_to(sharding, ids.ndim),
                  'the loader did not place the batch as asked')
            shards = {s.device: np.asarray(s.data) for s in ids.addressable_shards}
            held = [set(int(i) for i in rows_) for rows_ in shards.values()]
            check(len(shards) == n and all(len(h) == batch // n for h in held)
                  and len(set().union(*held)) == batch,
                  'the %d devices do not each hold a different %d-row range of '
                  'the batch: %r' % (n, batch // n, sorted(map(sorted, held))))
            check(all(s.data.shape[0] == batch // n
                      for s in dev_batch['image'].addressable_shards),
                  'an image shard holds more than its rows')
            ranges = {str(d): [int(r.min()), int(r.max())]
                      for d, r in shards.items()}
            # Compared with: the one-device step from the same state on the
            # same global batch.
            _, loss = step(
                jax.device_put(dp_state, devices[0]),
                jax.device_put(np.asarray(dev_batch['image']), devices[0]),
                jax.device_put(np.asarray(ids), devices[0]))
            one_losses.append(loss)
            dp_state, loss = step(dp_state, dev_batch['image'], ids)
            dp_losses.append(loss)
        plane = counters(loader, 'batches', 'h2d_batches', 'h2d_degraded')
    dp_losses = [float(x) for x in dp_losses]
    one_losses = [float(x) for x in one_losses]
    check(len(dp_losses) == rows // batch and np.isfinite(dp_losses).all(),
          'data-parallel losses not finite: %r' % (dp_losses,))
    check(plane['h2d_batches'] > 0 and plane['h2d_degraded'] == 0,
          'the sharded batches did not ride the transfer plane: %r' % plane)
    loss_rel = float(np.max(np.abs(np.subtract(dp_losses, one_losses))
                            / np.abs(one_losses)))
    check(loss_rel <= DP_LOSS_RTOL,
          'data-parallel and one-device losses differ by %g (> %g): %r vs %r'
          % (loss_rel, DP_LOSS_RTOL, dp_losses, one_losses))
    report('sharded_stream_train', t0, devices=n, rows_per_device=batch // n,
           row_ranges_last_batch=ranges, dp_losses=dp_losses,
           one_device_losses=one_losses, loss_max_rel_diff=loss_rel,
           loss_rtol=DP_LOSS_RTOL, **plane)

    t1 = time.monotonic()
    ring_mesh = make_mesh({'data': 1, 'seq': n}, devices=devices)
    ring, ring_sharding = make_ring_attention(ring_mesh, causal=True)
    rng = np.random.default_rng(seed)
    q, k, v = (rng.standard_normal(ring_shape).astype(np.float32)
               for _ in range(3))
    got = jax.jit(ring)(*(jax.device_put(jnp.asarray(x, jnp.bfloat16),
                                         ring_sharding) for x in (q, k, v)))
    check(len(got.sharding.device_set) == n, 'ring output is not on every device')
    want = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(
        *(jax.device_put(jnp.asarray(x, jnp.bfloat16), devices[0])
          for x in (q, k, v)))
    ring_err = float(np.max(np.abs(np.asarray(got, np.float32)
                                   - np.asarray(want, np.float32))))
    check(ring_err <= KERNEL_FWD_TOL,
          'ring attention differs from single-device flash by %g' % ring_err)
    report('ring_attention', t1, shape=ring_shape, seq_shards=n,
           max_err_vs_flash=ring_err, tol=KERNEL_FWD_TOL)


# -- main ----------------------------------------------------------------------

def run(chips, seed):
    device = phase_device(chips)
    from petastorm_tpu.utils import enable_compile_cache
    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    phase_native()
    workdir = tempfile.mkdtemp(prefix='chip_smoke_')
    try:
        url = 'file://' + os.path.join(workdir, 'imagenet_like')
        if chips == 1:
            step, state, _ = phase_stream_train(url, ROWS, BATCH, IMAGE_HW, seed)
            phase_resident(url, ROWS, BATCH, step, state, seed)
            phase_kernels(seed)
        else:
            phase_across_chips(url, ROWS_4, BATCH, IMAGE_HW, seed, RING_SHAPE)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps(dict(
        phase='compile', cache_dir=cache_dir,
        cache_placed_by_env=bool(os.environ.get('JAX_COMPILATION_CACHE_DIR')),
        **meter.facts())), flush=True)
    return device


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split('\n\n')[0])
    parser.add_argument('--chips', type=int, choices=(1, 4), default=1,
                        help='4: run only the across-chip phase and what it '
                             'is compared with (needs four chips)')
    parser.add_argument('--seed', type=int, default=0,
                        help='seed of the generated dataset, weights and '
                             'kernel inputs')
    args = parser.parse_args(argv)
    device = run(args.chips, args.seed)
    print(json.dumps({'ok': True, 'device': device}), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main())
