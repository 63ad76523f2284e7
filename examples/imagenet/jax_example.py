"""ImageNet-Parquet -> ResNet-50 through the TPU-native loader (config #3).

The north-star flow (BASELINE.json): JPEG/PNG decode + resize run in the
reader's worker pool (TransformSpec), batches are assembled columnar,
double-buffered onto the device mesh as pjit global arrays, and the
StallMonitor reports the step-time data-stall percentage that the <=2%
target refers to.
"""

# -- run from a source checkout without installation -------------------------
import os as _os, sys as _sys
_d = _os.path.dirname(_os.path.abspath(__file__))
while _d != _os.path.dirname(_d) and not _os.path.isdir(_os.path.join(_d, 'petastorm_tpu')):
    _d = _os.path.dirname(_d)
if _os.path.isdir(_os.path.join(_d, 'petastorm_tpu')) and _d not in _sys.path:
    _sys.path.insert(0, _d)

import argparse
import time

import numpy as np
import optax

import jax
import jax.numpy as jnp

from petastorm_tpu import make_reader
from petastorm_tpu.benchmark import StallMonitor
from petastorm_tpu.jax import DataLoader, augment
from petastorm_tpu.models.resnet import ResNet50
from petastorm_tpu.models.vit import ViT
from petastorm_tpu.parallel import data_parallel_sharding, make_mesh
from petastorm_tpu.transform import TransformSpec


def make_transform(image_hw):
    import cv2

    def fix_row(row):
        row = dict(row)
        img = row.pop('image')
        if img.shape[:2] != image_hw:
            img = cv2.resize(img, (image_hw[1], image_hw[0]))
        row['image'] = img
        row['label'] = np.int32(hash(row.pop('noun_id')) % 1000)
        return row

    return TransformSpec(fix_row,
                         edit_fields=[('image', np.uint8, image_hw + (3,), False),
                                      ('label', np.int32, (), False)],
                         removed_fields=['noun_id'])


def train(dataset_url, steps=50, batch_size=64, image_hw=(224, 224), lr=0.1,
          model_name='resnet50', decoded_cache_dir=None, hbm_cache=False,
          scan_steps=0, trace_path=None):
    mesh = make_mesh()
    sharding = data_parallel_sharding(mesh)
    # --trace: record every host-side span (host_batch/transform/device_put
    # from the loader, data_wait/step from the monitor) into a
    # chrome://tracing timeline — the per-event view of the same time the
    # stall report aggregates.
    from petastorm_tpu.benchmark import TraceRecorder
    tracer = TraceRecorder() if trace_path else None
    stateless = model_name == 'vit'
    if stateless:
        # ViT-S/16 on the same pipeline; no BatchNorm state, so batch_stats
        # stays an empty dict threaded through the shared step signature.
        model = ViT(num_classes=1000, patch_size=16, d_model=384,
                    num_heads=6, num_layers=12, d_ff=1536)
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1,) + image_hw + (3,), jnp.float32))
        params, batch_stats = variables['params'], {}
    else:
        model = ResNet50(num_classes=1000)
        variables = model.init(jax.random.PRNGKey(0),
                               jnp.zeros((1,) + image_hw + (3,), jnp.float32),
                               train=True)
        params, batch_stats = variables['params'], variables['batch_stats']
    tx = optax.sgd(lr, momentum=0.9)
    opt_state = tx.init(params)

    @jax.jit
    def train_step(params, batch_stats, opt_state, images, labels, key):
        # Augmentation runs ON DEVICE (petastorm_tpu.jax.augment): the host
        # pool only decodes; flips/crops are bandwidth-trivial for the chip
        # and fuse into the first conv under XLA.
        k_crop, k_flip = jax.random.split(key)
        images = augment.random_crop(k_crop, images, images.shape[1:3],
                                     padding=4)
        images = augment.random_flip_left_right(k_flip, images)
        images = augment.normalize(images, dtype=jnp.float32)

        def loss_fn(p):
            if stateless:
                logits = model.apply({'params': p}, images)
                new_stats = batch_stats
            else:
                logits, mutated = model.apply(
                    {'params': p, 'batch_stats': batch_stats}, images,
                    train=True, mutable=['batch_stats'])
                new_stats = mutated['batch_stats']
            loss = optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()
            return loss, new_stats

        (loss, new_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, new_opt = tx.update(grads, opt_state)
        return optax.apply_updates(params, updates), new_stats, new_opt, loss

    def scan_step(carry, batch):
        # Shared by both fused-consumption modes (scan_epochs over the HBM
        # cache, scan_batches over a stream): per-step augmentation
        # randomness rides in the carry.
        params, batch_stats, opt_state, key = carry
        key, sub = jax.random.split(key)
        params, batch_stats, opt_state, loss = train_step(
            params, batch_stats, opt_state, batch['image'], batch['label'],
            sub)
        return (params, batch_stats, opt_state, key), loss

    if hbm_cache:
        # Decoded shard fits HBM: cache it on device and run whole epochs
        # as ONE lax.scan dispatch each (DeviceInMemDataLoader.scan_epochs)
        # — zero per-step host work, so data stall is structurally ~0.
        # Per-step augmentation randomness rides in the carry.
        from petastorm_tpu.jax import DeviceInMemDataLoader
        with make_reader(dataset_url, schema_fields=['image', 'noun_id'],
                         transform_spec=make_transform(image_hw),
                         columnar_decode=True, num_epochs=1,
                         workers_count=8) as reader:
            loader = DeviceInMemDataLoader(reader, batch_size=batch_size,
                                           num_epochs=None, seed=17)
            carry = (params, batch_stats, opt_state, jax.random.PRNGKey(17))
            done = 0
            loss = None
            t0 = time.monotonic()
            for carry, losses in loader.scan_epochs(scan_step, carry):
                done += int(losses.shape[0])
                loss = losses[-1]
                if done >= steps:
                    break
        jax.block_until_ready(loss)
        dt = time.monotonic() - t0
        print('steps=%d loss=%.3f images/s=%.1f (hbm scan: no per-step host '
              'work)' % (done, float(loss), done * batch_size / dt))
        if tracer is not None:
            # Say it out loud rather than leaving the user waiting for a
            # file that never appears: the fused path has no host-side
            # spans to record.
            print('trace skipped: --hbm-cache folds whole epochs into '
                  'on-device scans (no host-side spans); no trace file '
                  'written to %s' % trace_path)
        return {'stall_pct': 0.0, 'steps': done}

    monitor = StallMonitor(warmup_steps=2, trace_recorder=tracer)
    done = 0
    t0 = time.monotonic()
    # Multi-epoch beyond-HBM datasets: --decoded-cache-dir spills decoded
    # tensors to local disk on epoch 0 and streams later epochs from the
    # mmap'd cache — no parquet/JPEG work after the first pass.  A cache
    # that is already complete needs NO reader at all (no background
    # decode pool).
    import contextlib
    from petastorm_tpu.jax import DiskCachedDataLoader
    cache_done = decoded_cache_dir and DiskCachedDataLoader.cache_complete(
        decoded_cache_dir)
    reader_cm = contextlib.nullcontext(None) if cache_done else make_reader(
        dataset_url, schema_fields=['image', 'noun_id'],
        transform_spec=make_transform(image_hw), columnar_decode=True,
        num_epochs=1 if decoded_cache_dir else None, workers_count=8)
    with reader_cm as reader:
        if decoded_cache_dir:
            loader = DiskCachedDataLoader(reader, batch_size=batch_size,
                                          decoded_cache_dir=decoded_cache_dir,
                                          num_epochs=None, sharding=sharding,
                                          trace_recorder=tracer)
        else:
            loader = DataLoader(reader, batch_size=batch_size,
                                sharding=sharding, trace_recorder=tracer)
        if scan_steps >= 1:
            # Fused streaming consumption: k host batches stack into one
            # device_put + one lax.scan dispatch (DataLoader.scan_batches)
            # — the countermeasure when per-dispatch latency, not decode,
            # is the stall (high-latency links, very fast steps).
            carry = (params, batch_stats, opt_state, jax.random.PRNGKey(17))
            loss = None
            for carry, losses in loader.scan_batches(
                    scan_step, carry, steps_per_call=scan_steps,
                    donate_carry=False):
                done += int(losses.shape[0])
                loss = losses[-1]
                if done >= steps:
                    break
            jax.block_until_ready(loss)
            dt = time.monotonic() - t0
            print('steps=%d loss=%.3f images/s=%.1f (scan_batches k=%d: '
                  'fused dispatch)'
                  % (done, float(loss), done * batch_size / dt, scan_steps))
            # scan_batches populates the same per-stage stats, so the
            # bottleneck advisor still gets a verdict (no StallMonitor —
            # per-batch wrapping doesn't apply to fused consumption).
            from petastorm_tpu.benchmark import diagnose, format_report
            print(format_report(diagnose(loader)))
            if tracer is not None:
                print('trace: %d spans -> %s (open in chrome://tracing)'
                      % (tracer.dump(trace_path), trace_path))
            return {'steps': done, 'stall_pct': None}
        step_key = jax.random.PRNGKey(17)
        for batch in monitor.wrap(loader):
            step_key, key = jax.random.split(step_key)
            params, batch_stats, opt_state, loss = train_step(
                params, batch_stats, opt_state, batch['image'], batch['label'],
                key)
            done += 1
            if done >= steps:
                break
    jax.block_until_ready(loss)
    dt = time.monotonic() - t0
    report = monitor.report()
    print('steps=%d loss=%.3f images/s=%.1f stall=%.2f%%'
          % (done, float(loss), done * batch_size / dt, report['stall_pct']))
    # Name the bottleneck regime and what to do about it (benchmark.diagnose)
    from petastorm_tpu.benchmark import diagnose, format_report
    print(format_report(diagnose(loader, monitor)))
    if tracer is not None:
        print('trace: %d spans -> %s (open in chrome://tracing)'
              % (tracer.dump(trace_path), trace_path))
    return report


if __name__ == '__main__':
    from petastorm_tpu.utils import enable_compile_cache, ensure_jax_backend
    ensure_jax_backend()  # applies JAX_PLATFORMS; raises if the backend cannot start
    enable_compile_cache()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--dataset-url', default='file:///tmp/imagenet_petastorm')
    parser.add_argument('--steps', type=int, default=50)
    parser.add_argument('--batch-size', type=int, default=64)
    parser.add_argument('--model', choices=['resnet50', 'vit'],
                        default='resnet50')
    parser.add_argument('--decoded-cache-dir', default=None,
                        help='decode once, stream later epochs from this '
                             'local decoded-tensor cache (multi-epoch '
                             'datasets bigger than HBM)')
    parser.add_argument('--hbm-cache', action='store_true',
                        help='decode once into device HBM and run each '
                             'epoch as one fused lax.scan dispatch '
                             '(single-device; shard per host on pods)')
    parser.add_argument('--scan-steps', type=int, default=0,
                        help='consume the streaming (or disk-cached) loader '
                             'via scan_batches: K steps per stacked '
                             'device_put + lax.scan dispatch — use when '
                             'dispatch/transport latency, not decode, is '
                             'the stall')
    parser.add_argument('--trace', default=None, metavar='PATH',
                        help='dump a chrome://tracing timeline of every '
                             'host-side span (loader stages + data_wait/'
                             'step) to PATH — per-event view of the stall '
                             'report (not applicable to --hbm-cache, whose '
                             'epochs have no host-side work to trace)')
    args = parser.parse_args()
    train(args.dataset_url, args.steps, args.batch_size,
          model_name=args.model, decoded_cache_dir=args.decoded_cache_dir,
          hbm_cache=args.hbm_cache, scan_steps=args.scan_steps,
          trace_path=args.trace)
