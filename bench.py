"""Benchmark: ImageNet-shaped JPEG Parquet -> device batches + ResNet-50 step.

Two measurements, one JSON line:

* **images/s/host** (the `value`): thread-pool decode -> columnar collate ->
  double-buffered `device_put`, whole-epoch wall clock.
* **stall_pct** (the BASELINE.json north-star metric): a jitted ResNet-50
  train step consumes `DataLoader` batches; stall is measured as
  `(wall_per_step - device_floor) / wall_per_step`, where the device floor
  is the same step chained on a resident batch with no data pipeline
  (target <= 2%).  This wall-vs-floor form is exact under JAX async
  dispatch and needs no per-step device syncs.

`vs_baseline` is measured, not quoted — the reference publishes no numbers
(BASELINE.json "published": {}).  The baseline leg re-reads the same dataset
through a faithful reimplementation of the reference's delivery strategy:
per-row codec decode (cv2, native plane force-disabled via
`native.disabled()`), per-row python collate, synchronous `device_put`, no
prefetch overlap — its pytorch `DataLoader` hot loop.  Same hardware, same
process, interleaved runs.

Prints TWO JSON lines — a full-detail line first (also written to
``BENCH_DETAIL_LAST.json``), then a COMPACT machine line LAST
({"metric", "value", "unit", "value_spread", "runs", "vs_baseline",
"stall_pct", "stall_pct_source", "stall_regime", "backend", per-regime
stall fields, "step_dtype", "mfu_pct"}).  A capture of the end of the
output parses the final stdout line, so that line stays small.

Runs on the TPU in this process, or not at all: without a TPU it exits
non-zero unless ``JAX_PLATFORMS=cpu`` asked for a CPU rehearsal, whose lines
all say ``cpu``.  A failed phase or leg is recorded in the lines and makes
the exit code non-zero.
"""

import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BENCH_DIR = os.environ.get(
    'PETASTORM_TPU_BENCH_DIR',
    os.path.join(tempfile.gettempdir(), 'petastorm_tpu_bench'))
DATASET_URL = 'file://' + BENCH_DIR + '/imagenet_like_v2'  # v2: image column
# stored with parquet compression NONE (JPEG bytes are incompressible; the
# writer now defaults codec-compressed columns to NONE)
RAW_DATASET_URL = 'file://' + BENCH_DIR + '/imagenet_raw_v1'  # pre-decoded u8
NUM_IMAGES = int(os.environ.get('PETASTORM_TPU_BENCH_ROWS', '768'))
IMAGE_HW = (224, 224)
BATCH = int(os.environ.get('PETASTORM_TPU_BENCH_BATCH', '64'))
# Decode threads scale with host cores (TPU-VM hosts have many); measured on
# a 1-core sandbox, 8 still beats 4 because pyarrow/libjpeg release the GIL
# during I/O waits, while >12 thrashes.
WORKERS = min(32, max(8, os.cpu_count() or 8))
TRAIN_STEPS = int(os.environ.get('PETASTORM_TPU_BENCH_TRAIN_STEPS', '36'))


def ensure_dataset():
    from petastorm_tpu.codecs import CompressedImageCodec
    from petastorm_tpu.etl.dataset_metadata import DatasetWriter
    from petastorm_tpu.fs_utils import get_filesystem_and_path_or_paths
    from petastorm_tpu.unischema import Unischema, UnischemaField

    fs, path = get_filesystem_and_path_or_paths(DATASET_URL)
    if fs.exists(path + '/_common_metadata'):
        return

    schema = Unischema('ImagenetLike', [
        UnischemaField('noun_id', np.int64, (), None, False),
        UnischemaField('image', np.uint8, (IMAGE_HW[0], IMAGE_HW[1], 3),
                       CompressedImageCodec('jpeg', quality=85), False),
    ])
    rng = np.random.default_rng(0)
    # Smooth gradients compress like natural images (pure noise would make
    # JPEG decode artificially cheap).
    base = np.linspace(0, 255, IMAGE_HW[0] * IMAGE_HW[1] * 3, dtype=np.float32)
    base = base.reshape(IMAGE_HW[0], IMAGE_HW[1], 3)

    def rows():
        for i in range(NUM_IMAGES):
            jitter = rng.integers(0, 64, (8, 8, 3)).repeat(28, 0).repeat(28, 1)
            img = np.clip(base + jitter, 0, 255).astype(np.uint8)
            yield {'noun_id': np.int64(i), 'image': img}

    with DatasetWriter(DATASET_URL, schema, rows_per_rowgroup=64) as w:
        w.write_many(rows())


def ensure_raw_dataset():
    """Pre-decoded uint8 tensors in parquet (no JPEG, compression NONE).

    The delivery-bound leg reads this through the full streaming path:
    row-group read -> columnar collate -> double-buffered device_put, with
    zero image-decode work.  It isolates the delivery plane (the
    framework's own machinery) from decode economics (host-core bound) —
    SURVEY §7's "data-stall <=2%" risk split into its two causes.
    """
    from petastorm_tpu.codecs import NdarrayCodec
    from petastorm_tpu.etl.dataset_metadata import DatasetWriter
    from petastorm_tpu.fs_utils import get_filesystem_and_path_or_paths
    from petastorm_tpu.unischema import Unischema, UnischemaField

    fs, path = get_filesystem_and_path_or_paths(RAW_DATASET_URL)
    if fs.exists(path + '/_common_metadata'):
        return

    schema = Unischema('ImagenetRaw', [
        UnischemaField('noun_id', np.int64, (), None, False),
        UnischemaField('image', np.uint8, (IMAGE_HW[0], IMAGE_HW[1], 3),
                       NdarrayCodec(), False),
    ])
    rng = np.random.default_rng(0)

    def rows():
        for i in range(NUM_IMAGES):
            yield {'noun_id': np.int64(i),
                   'image': rng.integers(0, 256, (IMAGE_HW[0], IMAGE_HW[1], 3),
                                         np.uint8)}

    with DatasetWriter(RAW_DATASET_URL, schema, rows_per_rowgroup=64,
                       compression='none') as w:
        w.write_many(rows())


def tpu_native_epoch():
    """Our path: thread-pool decode -> columnar collate -> double-buffered
    device_put."""
    import jax
    from petastorm_tpu import make_reader
    from petastorm_tpu.jax import DataLoader

    with make_reader(DATASET_URL, num_epochs=1, workers_count=WORKERS,
                     shuffle_row_groups=False, columnar_decode=True) as reader:
        loader = DataLoader(reader, batch_size=BATCH, prefetch=2)
        n = 0
        last = None
        t0 = time.monotonic()
        for batch in loader:
            n += batch['image'].shape[0]
            last = batch
        jax.block_until_ready(last)
        dt = time.monotonic() - t0
    return n / dt


def reference_strategy_epoch():
    """Reference-style delivery: per-row cv2 decode (native plane OFF), per-row
    python collate into a batch list, synchronous put, no prefetch overlap."""
    import jax
    from petastorm_tpu import make_reader, native

    with native.disabled():
        with make_reader(DATASET_URL, num_epochs=1, workers_count=WORKERS,
                         shuffle_row_groups=False) as reader:
            n = 0
            t0 = time.monotonic()
            batch_rows = []
            for row in reader:
                batch_rows.append(row.image)
                if len(batch_rows) == BATCH:
                    dev = jax.device_put(np.stack(batch_rows))
                    jax.block_until_ready(dev)
                    n += BATCH
                    batch_rows = []
            dt = time.monotonic() - t0
    return n / dt


def _make_resnet_step():
    """Jitted ResNet-50 SGD step: uint8 batch in (4x cheaper H2D than f32);
    normalization + bf16 cast happen on device, fused into the first conv."""
    import jax
    import jax.numpy as jnp
    import optax
    from petastorm_tpu.models.resnet import ResNet50

    model = ResNet50(num_classes=1000)
    rng = jax.random.PRNGKey(0)
    variables = model.init(rng, jnp.zeros((1, IMAGE_HW[0], IMAGE_HW[1], 3),
                                          jnp.bfloat16), train=True)
    params, batch_stats = variables['params'], variables['batch_stats']
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)

    @jax.jit
    def train_step(params, batch_stats, opt_state, images_u8, labels):
        images = images_u8.astype(jnp.bfloat16) / 255.0

        def loss_fn(p):
            logits, mutated = model.apply(
                {'params': p, 'batch_stats': batch_stats}, images, train=True,
                mutable=['batch_stats'])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), labels).mean()
            return loss, mutated['batch_stats']

        (loss, new_stats), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, new_opt = tx.update(grads, opt_state)
        return optax.apply_updates(params, updates), new_stats, new_opt, loss

    return train_step, params, batch_stats, opt_state


def _device_floor_ms(state, steps):
    """Pure device step time: one resident batch, ``steps`` chained
    executions, a single terminal D2H sync.  No data pipeline and no
    per-step host syncs — the denominator for stall%."""
    import jax

    train_step, params, batch_stats, opt_state = state
    x = jax.device_put(np.zeros((BATCH, IMAGE_HW[0], IMAGE_HW[1], 3), np.uint8))
    y = jax.device_put(np.zeros((BATCH,), np.int64))
    params, batch_stats, opt_state, loss = train_step(
        params, batch_stats, opt_state, x, y)
    float(loss)  # compile + settle
    t0 = time.monotonic()
    for _ in range(steps):
        params, batch_stats, opt_state, loss = train_step(
            params, batch_stats, opt_state, x, y)
    float(loss)  # forces the whole chain
    return 1000.0 * (time.monotonic() - t0) / steps


def _run_stall(loader, state, max_steps, floor_ms):
    """Wall-clock ``max_steps`` async-dispatched steps over ``loader`` (one
    terminal sync), then ``stall% = (wall - device_floor) / wall``.

    Per-step ``block_until_ready``/value pulls would put a host round-trip
    into every step; measuring the whole window against a device-only
    floor needs none."""
    warmup = 3
    train_step, params, batch_stats, opt_state = state
    steps = 0
    loss = None
    t0 = None
    for batch in loader:
        params, batch_stats, opt_state, loss = train_step(
            params, batch_stats, opt_state, batch['image'], batch['noun_id'])
        steps += 1
        if steps == warmup:
            float(loss)  # drain pipeline-fill + any compile before timing
            t0 = time.monotonic()
        if steps >= max_steps + warmup:
            break
    loss_val = float(loss)  # forces every chained timed step
    assert t0 is not None and steps > warmup, 'loader too short for the run'
    assert np.isfinite(loss_val), 'non-finite loss'
    wall_ms = 1000.0 * (time.monotonic() - t0) / (steps - warmup)
    stall_pct = max(0.0, 100.0 * (wall_ms - floor_ms) / wall_ms)
    return round(stall_pct, 2), wall_ms


def _run_scan_stall(loader, state, max_steps, floor_ms):
    """Stall of the fused driver: ``DeviceInMemDataLoader.scan_epochs``
    with the whole measured window folded into ONE dispatch
    (``epochs_per_call``) — per-epoch dispatch amortized to nothing.
    The first call is the compile+settle warmup; the second is the timed
    window, closed by one terminal D2H."""
    train_step, params, batch_stats, opt_state = state

    def scan_step(carry, batch):
        p, bs, opt = carry
        p, bs, opt, loss = train_step(p, bs, opt, batch['image'],
                                      batch['noun_id'])
        return (p, bs, opt), loss

    steps_per_epoch = max(1, NUM_IMAGES // BATCH)
    epochs_needed = -(-max_steps // steps_per_epoch)
    gen = loader.scan_epochs(scan_step, (params, batch_stats, opt_state),
                             donate_carry=False,
                             epochs_per_call=epochs_needed)
    _, outs = next(gen)                      # compile + warmup window
    float(np.asarray(outs).ravel()[-1])      # settle the warmup chain
    t0 = time.monotonic()
    _, last = next(gen)                      # the timed window: ONE dispatch
    final = np.asarray(last)                 # terminal D2H forces the chain
    wall_ms = 1000.0 * (time.monotonic() - t0) / (epochs_needed * steps_per_epoch)
    assert np.isfinite(final).all(), 'non-finite loss in scan epochs'
    stall_pct = max(0.0, 100.0 * (wall_ms - floor_ms) / wall_ms)
    return round(stall_pct, 2), wall_ms


def _run_scan_batches_stall(loader, state, max_steps, floor_ms,
                            steps_per_call):
    """Stall of the fused STREAMING driver: ``DataLoader.scan_batches``
    folds ``steps_per_call`` steps into ONE stacked ``device_put`` + ONE
    ``lax.scan`` dispatch — per-step dispatch/transport round-trips are
    amortized k-fold while host decode of the next chunk overlaps the
    scan.  The first chunk is the compile+fill warmup; the timed window is
    the following full chunks, closed by one terminal D2H."""
    train_step, params, batch_stats, opt_state = state

    def scan_step(carry, batch):
        p, bs, opt = carry
        p, bs, opt, loss = train_step(p, bs, opt, batch['image'],
                                      batch['noun_id'])
        return (p, bs, opt), loss

    gen = loader.scan_batches(scan_step, (params, batch_stats, opt_state),
                              steps_per_call=steps_per_call,
                              donate_carry=False)
    chunks = 0
    steps_timed = 0
    t0 = None
    outs = None
    for _, outs in gen:
        chunks += 1
        if chunks == 1:
            # drain compile + pipeline fill before opening the timer
            float(np.asarray(outs).ravel()[-1])
            t0 = time.monotonic()
            continue
        steps_timed += int(outs.shape[0])  # metadata only — no device sync
        if steps_timed >= max_steps:
            break
    assert t0 is not None and steps_timed > 0, 'loader too short for scan run'
    final = np.asarray(outs)  # terminal D2H forces the whole chained window
    wall_ms = 1000.0 * (time.monotonic() - t0) / steps_timed
    assert np.isfinite(final).all(), 'non-finite loss in scan_batches window'
    stall_pct = max(0.0, 100.0 * (wall_ms - floor_ms) / wall_ms)
    return round(stall_pct, 2), wall_ms


def _h2d_probe(k=4):
    """Raw host→device bandwidth for one stacked uint8 chunk — the
    irreducible transport term of the fused streaming path.  At
    ``steps_per_call`` → ∞ the per-step wall is bounded below by
    ``max(device_step, batch_bytes / h2d_bytes_per_s)`` (overlapped) and
    above by their sum (serialized); reporting the measured bandwidth lets
    the artifact say whether a residual streaming stall is transport-bound
    physics or framework overhead."""
    import jax

    x = np.zeros((k, BATCH, IMAGE_HW[0], IMAGE_HW[1], 3), np.uint8)
    dev = jax.device_put(x)
    jax.block_until_ready(dev)  # warm the transfer path
    del dev
    t0 = time.monotonic()
    dev = jax.device_put(x)
    jax.block_until_ready(dev)
    dt = time.monotonic() - t0
    bytes_per_s = x.nbytes / dt if dt > 0 else 0.0
    batch_bytes = BATCH * IMAGE_HW[0] * IMAGE_HW[1] * 3
    return {
        'h2d_bytes_per_s': round(bytes_per_s),
        'transport_ms_per_step': round(1000.0 * batch_bytes / bytes_per_s, 2)
                                 if bytes_per_s else None,
    }


def _step_dtype_info(state):
    """Anchor the perf claim at training precision: read the compute dtype
    off the LOWERED STEP ITSELF (conv/dot op result types in the StableHLO
    text), not off model-config intent.  Reports how many matmul-class ops
    run in bf16 so 'the step is bf16' is evidence, not assertion."""
    train_step, params, batch_stats, opt_state = state
    x = np.zeros((BATCH, IMAGE_HW[0], IMAGE_HW[1], 3), np.uint8)
    y = np.zeros((BATCH,), np.int64)
    try:
        txt = train_step.lower(params, batch_stats, opt_state, x, y).as_text()
    except Exception:
        return {'step_dtype': 'unknown (lowering failed)'}
    mm_lines = [l for l in txt.splitlines()
                if 'convolution' in l or 'dot_general' in l]
    n_bf16 = sum('bf16' in l for l in mm_lines)
    if mm_lines and n_bf16 >= 0.9 * len(mm_lines):
        dtype = 'bf16-compute/f32-params'
    elif n_bf16:
        dtype = 'mixed bf16/f32'
    else:
        dtype = 'f32'
    return {'step_dtype': dtype,
            'matmul_class_ops': len(mm_lines),
            'matmul_class_ops_bf16': n_bf16}


# Peak dense bf16 TFLOP/s keyed by ``jax.Device.device_kind`` (Google Cloud
# TPU documentation, per-chip figures); the MFU denominator.  Exact match: a
# kind that is not in the table is an error, never a neighbour's number.
_PEAK_BF16_TFLOPS = {
    'TPU v4': 275.0,
    'TPU v5 lite': 197.0,
    'TPU v5p': 459.0,
    'TPU v6 lite': 918.0,
}


def _device_peak_tflops():
    """(peak bf16 TFLOP/s, device_kind).  The peak is None on the CPU (a
    rehearsal has no MFU); an accelerator kind the table lacks raises."""
    import jax
    dev = jax.devices()[0]
    if dev.platform == 'cpu':
        return None, dev.device_kind
    if dev.device_kind not in _PEAK_BF16_TFLOPS:
        raise RuntimeError('no peak FLOP/s on record for device_kind %r; add '
                           'it to _PEAK_BF16_TFLOPS with its source'
                           % dev.device_kind)
    return _PEAK_BF16_TFLOPS[dev.device_kind], dev.device_kind


def _device_hbm_bytes():
    """Memory capacity of the device the legs run on, as it reports it: an
    accelerator's ``memory_stats()`` limit, the host's physical RAM for the
    CPU device.  A device that reports none raises."""
    import jax
    dev = jax.devices()[0]
    if dev.platform == 'cpu':
        return os.sysconf('SC_PHYS_PAGES') * os.sysconf('SC_PAGE_SIZE')
    stats = dev.memory_stats() or {}
    cap = stats.get('bytes_limit') or stats.get('bytes_reservable_limit')
    if not cap:
        raise RuntimeError('device %r reports no memory capacity '
                           '(memory_stats=%r)' % (dev.device_kind, stats))
    return int(cap)


#: Live result fields, filled leg by leg (train_stall_legs).  Module-level
#: so the watchdog can emit everything measured so far when a later leg
#: outlives the budget.
_PARTIAL = {}
#: throughput-phase results, stashed by main() the moment they're measured
#: (train_stall_legs clears _PARTIAL for retries; the watchdog merges both)
_PARTIAL_BASE = {}
_T0 = time.monotonic()
_BUDGET_S = None


def _budget_left_s():
    """Seconds before the watchdog fires (inf when no watchdog is armed)."""
    if _BUDGET_S is None:
        return float('inf')
    return _BUDGET_S - (time.monotonic() - _T0)


def train_stall_legs():
    """North-star metric, three regimes — all reported, top-level
    ``stall_pct`` is the regime this dataset actually REQUIRES (a decoded
    epoch that fits device HBM may use the cached loader; one that doesn't
    must stream):

    * **streaming** — thread-pool JPEG decode feeding the step live.  Whether
      this stalls is a host-cores : chip-speed ratio; on a 1-core sandbox
      host with a datacenter chip it necessarily will (no host decode plane
      sustains tens of kimg/s on one core) — reported for transparency.
    * **delivery_bound** — the same streaming loader over PRE-DECODED uint8
      parquet (no JPEG): isolates the framework's delivery plane from
      decode economics.  If this leg is fast, a streaming stall is decode
      cost, not the loader.
    * **hbm_cached** — DeviceInMemDataLoader: decode once, epoch cache in
      device HBM, per-epoch device-side reshuffle, jnp.take per batch.  Zero
      host work per step: the framework's TPU-native answer when the decoded
      shard fits in HBM.
    """
    import shutil

    from petastorm_tpu import make_reader
    from petastorm_tpu.benchmark import (HEALTHY_STALL_PCT, diagnose,
                                         fused_dispatch_window)
    from petastorm_tpu.jax import (DataLoader, DeviceInMemDataLoader,
                                   DiskCachedDataLoader)

    _PARTIAL.clear()  # a retry must not inherit a previous call's numbers
    out = _PARTIAL  # module-level alias: the watchdog reports whatever
    errors = {}     # legs completed even if a later leg outlives the budget

    def leg(name, fn):
        """Containment boundary: a failed leg is recorded under its name
        and the remaining legs still measure (main() exits non-zero when
        any failed).  A leg is skipped — also a recorded failure — when
        less than ~2 min of watchdog budget remains: better an explicit
        skip than a truncated artifact."""
        if _budget_left_s() < 120:
            errors[name] = ('skipped: %.0fs of watchdog budget left'
                            % _budget_left_s())
            return
        t_leg = time.monotonic()
        try:
            out.update(fn())
        except Exception as e:  # noqa: BLE001 — record and keep measuring
            errors[name] = '%s: %s' % (type(e).__name__, str(e)[:160])
            sys.stderr.write('bench: leg %r failed: %s\n'
                             % (name, errors[name]))
        finally:
            out.setdefault('leg_elapsed_s', {})[name] = round(
                time.monotonic() - t_leg, 1)

    def diag_of(stall, loader):
        # The advisor's verdict goes into the artifact: WHICH regime
        # caused whatever stall was measured.  The bare stage-balance
        # diagnosis can't see the chip side, so gate it on the measured
        # stall (a healthy leg IS chip_bound regardless of which host
        # stage dominates its tiny host time).
        if stall <= HEALTHY_STALL_PCT:
            return {'regime': 'chip_bound', 'evidence': {'stall_pct': stall}}
        d = diagnose(loader)
        return {'regime': d['regime'], 'evidence': d['evidence']}

    state = _make_resnet_step()
    # The cached leg and the floor are cheap (no host work): run a
    # multiple of the steps so (a) the wall-vs-floor difference — the
    # stall signal — sits above run-to-run timer noise, and (b) the ONE
    # dispatch round-trip the fused scan window pays is amortized below
    # the phantom-stall budget (auto-sized by fused_dispatch_window from
    # the measured floor; the bootstrap call has no floor yet).
    # The streaming legs pay full host work per step, so they keep the
    # base count.
    cached_steps = fused_dispatch_window(TRAIN_STEPS)
    # No containment for the floor: every stall% needs this denominator.
    floor_ms = _device_floor_ms(state, cached_steps)
    cached_steps = fused_dispatch_window(TRAIN_STEPS, step_floor_ms=floor_ms)
    out['device_step_ms'] = round(floor_ms, 2)

    # Size by FULL batches per epoch (drop_last): epochs of ragged-tail rows
    # never become steps, so dividing by row count would undershoot.
    batches_per_epoch = max(1, NUM_IMAGES // BATCH)
    epochs = -(-(TRAIN_STEPS + 4) // batches_per_epoch)
    scan_k = max(1, min(12, TRAIN_STEPS))

    def leg_streaming():
        with make_reader(DATASET_URL, num_epochs=epochs,
                         workers_count=WORKERS, shuffle_row_groups=False,
                         columnar_decode=True) as reader:
            loader = DataLoader(reader, batch_size=BATCH, prefetch=2)
            stall, step_ms = _run_stall(loader, state, TRAIN_STEPS, floor_ms)
            return {'stall_pct_streaming': stall,
                    'step_ms_streaming': round(step_ms, 2),
                    'streaming_diagnosis': diag_of(stall, loader)}

    def leg_streaming_scan():
        # SAME live-JPEG streaming pipeline, consumed through scan_batches
        # — k steps per stacked device_put + lax.scan dispatch, the
        # countermeasure to per-dispatch latency, measured on the regime
        # it was written for.
        scan_chunks = 1 + -(-TRAIN_STEPS // scan_k)
        epochs_scan = -(-(scan_k * scan_chunks + 2) // batches_per_epoch)
        with make_reader(DATASET_URL, num_epochs=epochs_scan,
                         workers_count=WORKERS, shuffle_row_groups=False,
                         columnar_decode=True) as reader:
            loader = DataLoader(reader, batch_size=BATCH, prefetch=2)
            stall, step_ms = _run_scan_batches_stall(
                loader, state, TRAIN_STEPS, floor_ms, steps_per_call=scan_k)
            return {'stall_pct_streaming_scan': stall,
                    'step_ms_streaming_scan': round(step_ms, 2),
                    'streaming_scan_steps_per_call': scan_k,
                    'streaming_scan_diagnosis': diag_of(stall, loader)}

    def leg_delivery_bound():
        ensure_raw_dataset()
        with make_reader(RAW_DATASET_URL, num_epochs=epochs,
                         workers_count=WORKERS, shuffle_row_groups=False,
                         columnar_decode=True) as reader:
            loader = DataLoader(reader, batch_size=BATCH, prefetch=2)
            stall, step_ms = _run_stall(loader, state, TRAIN_STEPS, floor_ms)
            return {'stall_pct_delivery_bound': stall,
                    'step_ms_delivery_bound': round(step_ms, 2)}

    def leg_host_plane():
        fields = imagenet_host_plane_leg(epochs=epochs)
        # >= BATCH/floor_ms implies streaming stalls are decode- or
        # transport-bound, not loader-bound.
        rate = fields['delivery_plane_images_per_sec_host']
        fields['delivery_plane_keeps_chip_fed'] = bool(
            rate >= 1000.0 * BATCH / floor_ms)
        return fields

    def leg_hbm():
        with make_reader(DATASET_URL, num_epochs=1, workers_count=WORKERS,
                         shuffle_row_groups=False,
                         columnar_decode=True) as reader:
            loader = DeviceInMemDataLoader(reader, batch_size=BATCH,
                                           num_epochs=None, seed=0)
            stall, step_ms = _run_stall(loader, state, cached_steps,
                                        floor_ms)
            # Save the per-step result NOW: if the scan half below fails,
            # the completed measurement must still ship.
            out.update({'stall_pct_hbm_cached': stall,
                        'step_ms_hbm_cached': round(step_ms, 2)})
            fields = {}
            # hbm_scan: same HBM cache, gather + train step fused into ONE
            # lax.scan dispatch per epoch (scan_epochs) — zero per-step
            # host dispatch, so per-dispatch transport latency cannot
            # become data stall.  The recommended consumption pattern for
            # an HBM-resident epoch and the headline for this regime.
            scan_stall, scan_ms = _run_scan_stall(loader, state,
                                                  cached_steps, floor_ms)
            fields.update({'stall_pct_hbm_scan': scan_stall,
                           'step_ms_hbm_scan': round(scan_ms, 2)})
            return fields

    def leg_decoded_cache():
        # decoded-cache tier: epoch 0 decodes JPEG once and spills raw
        # tensors to local disk (untimed build pass); the measured epochs
        # stream from the mmap'd cache — the multi-epoch answer for
        # datasets >> HBM.
        cache_dir = os.path.join(BENCH_DIR, 'decoded_cache_v1')
        shutil.rmtree(cache_dir, ignore_errors=True)
        with make_reader(DATASET_URL, num_epochs=1, workers_count=WORKERS,
                         shuffle_row_groups=False,
                         columnar_decode=True) as reader:
            build = DiskCachedDataLoader(reader, batch_size=BATCH,
                                         decoded_cache_dir=cache_dir,
                                         num_epochs=1, shuffle=False)
            for _ in build:
                pass
        # Measured legs over the complete cache with reader=None: no worker
        # pool decoding JPEG in the background to contaminate the timing.
        loader = DiskCachedDataLoader(None, batch_size=BATCH,
                                      decoded_cache_dir=cache_dir,
                                      num_epochs=None, seed=0)
        stall, step_ms = _run_stall(loader, state, cached_steps, floor_ms)
        out.update({'stall_pct_decoded_cache': stall,
                    'step_ms_decoded_cache': round(step_ms, 2)})
        fields = {}
        # decoded_cache_scan: the same complete cache consumed through
        # scan_batches — mmap'd batch gather on the host, k steps per
        # fused dispatch.  The multi-epoch >HBM regime, dispatch amortized.
        scan_loader = DiskCachedDataLoader(None, batch_size=BATCH,
                                           decoded_cache_dir=cache_dir,
                                           num_epochs=None, seed=0)
        scan_stall, scan_ms = _run_scan_batches_stall(
            scan_loader, state, cached_steps, floor_ms, steps_per_call=scan_k)
        fields.update({'stall_pct_decoded_cache_scan': scan_stall,
                       'step_ms_decoded_cache_scan': round(scan_ms, 2)})
        return fields

    def leg_transport():
        h2d = _h2d_probe()
        # Irreducible transport bound of the fused streaming path: even at
        # steps_per_call -> inf, per-step wall >= max(device_step,
        # batch_bytes/bandwidth) when transfer overlaps compute.
        if h2d.get('transport_ms_per_step'):
            t_ms = h2d['transport_ms_per_step']
            bound_ms = max(floor_ms, t_ms)
            h2d['streaming_scan_floor_stall_pct'] = round(
                max(0.0, 100.0 * (bound_ms - floor_ms) / bound_ms), 2)
            h2d['transport_bound'] = bool(t_ms > floor_ms)
        return h2d

    # transport FIRST: it is one device_put, and its h2d_bytes_per_s is
    # the link term every streaming leg's number is read against.
    leg('transport', leg_transport)
    leg('streaming', leg_streaming)
    leg('streaming_scan', leg_streaming_scan)
    leg('delivery_bound', leg_delivery_bound)
    leg('host_plane', leg_host_plane)
    leg('hbm', leg_hbm)
    leg('decoded_cache', leg_decoded_cache)

    decoded_epoch_bytes = NUM_IMAGES * IMAGE_HW[0] * IMAGE_HW[1] * 3
    hbm = _device_hbm_bytes()
    fits_hbm = decoded_epoch_bytes < 0.6 * hbm  # leave room for model+step
    out['stall_regime'] = 'hbm_cached' if fits_hbm else 'decoded_cache'
    out['stall_regime_note'] = (
        'decoded epoch %.2f GiB %s %.0f GiB device HBM; multi-epoch > '
        'HBM runs the decoded disk cache, single-pass runs streaming'
        % (decoded_epoch_bytes / 2**30,
           'fits in' if fits_hbm else 'exceeds', hbm / 2**30))
    flops = _model_flops_per_step(state)
    peak_tflops, device_kind = _device_peak_tflops()
    tflops_per_s = flops / 1e12 / (floor_ms / 1000.0)
    out.update({
        'model_step_tflop': round(flops / 1e12, 4),
        'model_tflops_per_s': round(tflops_per_s, 2),
        'device_kind': device_kind,
        'device_peak_tflops_bf16': peak_tflops,
        'mfu_pct': (round(100.0 * tflops_per_s / peak_tflops, 1)
                    if peak_tflops else None),
    })
    out.update(_step_dtype_info(state))

    # The headline is the best measured driver of the regime this dataset
    # REQUIRES; a missing (failed) leg simply doesn't compete.  If BOTH
    # preferred drivers failed, fall back to the
    # other cache tier rather than shipping no headline at all — the
    # source field says which driver actually produced the number.
    hbm_pair = (('stall_pct_hbm_cached', 'hbm_cached'),
                ('stall_pct_hbm_scan', 'hbm_scan'))
    disk_pair = (('stall_pct_decoded_cache', 'decoded_cache'),
                 ('stall_pct_decoded_cache_scan', 'decoded_cache_scan'))
    for pair in ((hbm_pair, disk_pair) if fits_hbm
                 else (disk_pair, hbm_pair)):
        candidates = [(out[k], src) for k, src in pair if k in out]
        if candidates:
            out['stall_pct'], out['stall_pct_source'] = min(candidates)
            break
    if errors:
        out['leg_errors'] = errors
        out['legs_failed'] = sorted(errors)
    return out


CRITEO_URL = 'file://' + BENCH_DIR + '/criteo_like_v1'
DLRM_ROWS = int(os.environ.get('PETASTORM_TPU_BENCH_DLRM_ROWS', '65536'))
DLRM_BATCH = int(os.environ.get('PETASTORM_TPU_BENCH_DLRM_BATCH', '4096'))
DLRM_DENSE, DLRM_CAT = 13, 26
DLRM_VOCAB = int(os.environ.get('PETASTORM_TPU_BENCH_DLRM_VOCAB', '100000'))


def ensure_criteo_dataset():
    """Criteo-shaped plain Parquet (13 dense f32 + 26 hashed-categorical
    i32 + click label), read through ``make_batch_reader`` — the
    BASELINE config-#4 acceptance surface (``examples/criteo``)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    from petastorm_tpu.fs_utils import get_filesystem_and_path_or_paths

    fs, path = get_filesystem_and_path_or_paths(CRITEO_URL)
    if fs.exists(path + '/data.parquet'):
        return
    fs.makedirs(path, exist_ok=True)
    rng = np.random.default_rng(1)
    cols = {'dense_%d' % i: rng.standard_normal(DLRM_ROWS).astype(np.float32)
            for i in range(DLRM_DENSE)}
    cols.update({'cat_%d' % i: rng.integers(0, DLRM_VOCAB, DLRM_ROWS)
                                  .astype(np.int32)
                 for i in range(DLRM_CAT)})
    cols['clicked'] = (rng.random(DLRM_ROWS) < 0.03).astype(np.int32)
    pq.write_table(pa.table(cols), path + '/data.parquet',
                   row_group_size=2 * DLRM_BATCH)


def _dlrm_pack_columns(batch):
    """Columnar host work of the DLRM pipeline: stack 13 dense + 26
    categorical columns into the model's two input arrays."""
    dense = np.stack([batch['dense_%d' % i] for i in range(DLRM_DENSE)],
                     axis=1).astype(np.float32)
    cat = np.stack([batch['cat_%d' % i] for i in range(DLRM_CAT)],
                   axis=1).astype(np.int32)
    return {'dense': dense, 'cat': cat,
            'clicked': batch['clicked'].astype(np.float32)}


def imagenet_host_plane_leg(epochs=4):
    """Host delivery plane in ISOLATION (no device in the loop): the
    streaming loader over pre-decoded uint8, consumed at the host
    boundary.  Proves whether the framework's own machinery (parquet read
    -> columnar collate -> batch assembly) sustains chip rate independent
    of transport bandwidth."""
    from petastorm_tpu import make_reader
    from petastorm_tpu.jax import DataLoader

    ensure_raw_dataset()
    with make_reader(RAW_DATASET_URL, num_epochs=epochs,
                     workers_count=WORKERS, shuffle_row_groups=False,
                     columnar_decode=True) as reader:
        loader = DataLoader(reader, batch_size=BATCH, prefetch=2)
        n_host = 0
        warmup_batches = 2  # pool spin-up + first row-group latency
        t0 = None           # are not steady-state; exclude them
        for i, host_batch in enumerate(loader.iter_host_batches()):
            if i == warmup_batches:
                t0 = time.monotonic()
            elif i > warmup_batches:
                n_host += len(host_batch['noun_id'])
        rate = (n_host / (time.monotonic() - t0)
                if t0 is not None and n_host else 0.0)
    return {'delivery_plane_images_per_sec_host': round(rate, 1)}


def ipc_microbench(n_batches=24):
    """Same-host IPC result plane in isolation: bytes/s of one
    64×224×224×3 uint8 batch stream crossing a REAL ProcessPool process
    boundary, shm descriptors (``workers_pool/shm_plane.py``) vs the
    serialized pickle-over-ZMQ byte path.  The consumer touches one byte
    per 4 KiB page of every delivered batch — the cost of making the
    bytes resident (the shm number pays its page faults there, where a
    real consumer's first pass pays them) without a full-bandwidth read
    that would swamp the delivery-plane difference on a
    memory-bandwidth-bound host."""
    from petastorm_tpu.benchmark.hostplane import IpcBenchWorker
    from petastorm_tpu.workers_pool.process_pool import ProcessPool

    shape = (BATCH, IMAGE_HW[0], IMAGE_HW[1], 3)
    batch_bytes = int(np.prod(shape))
    fields = {}
    shm_used = False
    for label, use_shm in (('shm', True), ('serialized', False)):
        pool = ProcessPool(workers_count=1, results_queue_size=8,
                           use_shm=use_shm)
        pool.start(IpcBenchWorker, worker_setup_args=shape)
        try:
            pool.ventilate(2)  # warmup: child imports, allocator, pages
            for _ in range(2):
                pool.get_results()[0].ravel()[::4096].sum()
            t0 = time.monotonic()
            pool.ventilate(n_batches)
            for _ in range(n_batches):
                pool.get_results()[0].ravel()[::4096].sum()
            dt = time.monotonic() - t0
        finally:
            pool.stop()
            pool.join()
        fields[label] = round(n_batches * batch_bytes / dt) if dt else 0
        if use_shm and pool.shm_results:
            shm_used = True
    fields['ratio'] = (round(fields['shm'] / fields['serialized'], 2)
                       if fields.get('serialized') else None)
    if not shm_used:
        fields['note'] = 'shm plane unavailable: both legs ran serialized'
    return {'ipc_bytes_per_s': fields}


def processpool_host_plane_leg(seconds=6.0):
    """ProcessPool host delivery plane, shm result plane ON vs OFF: host
    images/s of the streaming loader over pre-decoded uint8 parquet with
    ``reader_pool_type='process'`` — every decoded batch crosses the
    child→parent boundary, so the delta between the two fields is exactly
    what the shm descriptors buy on a real pipeline (the thread-pool twin
    of this leg is ``delivery_plane_images_per_sec_host``)."""
    from petastorm_tpu import make_reader
    from petastorm_tpu.benchmark.hostplane import pump_host_batches
    from petastorm_tpu.jax import DataLoader

    ensure_raw_dataset()
    fields = {}
    shm_used = False
    for label, no_shm in (('shm', None), ('bytes', '1')):
        # The 'shm' variant leaves the environment alone: an operator's
        # PETASTORM_TPU_NO_SHM=1 (the documented kill switch) must win,
        # in which case both variants run serialized and the note below
        # says so.  Only the 'bytes' variant forces the flag.
        prev = os.environ.get('PETASTORM_TPU_NO_SHM')
        if no_shm:
            os.environ['PETASTORM_TPU_NO_SHM'] = no_shm
        try:
            with make_reader(RAW_DATASET_URL, num_epochs=None,
                             reader_pool_type='process',
                             workers_count=min(4, WORKERS),
                             shuffle_row_groups=False,
                             columnar_decode=True) as reader:
                loader = DataLoader(reader, batch_size=BATCH, prefetch=2)
                rows, dt = pump_host_batches(loader, seconds,
                                             warmup_batches=2)
                if label == 'shm' and reader.diagnostics['shm_results']:
                    shm_used = True
            fields['delivery_plane_processpool_images_per_sec_host_%s'
                   % label] = round(rows / dt, 1)
        finally:
            if no_shm:
                if prev is not None:
                    os.environ['PETASTORM_TPU_NO_SHM'] = prev
                else:
                    os.environ.pop('PETASTORM_TPU_NO_SHM', None)
    if not shm_used:
        # Never present a bytes-vs-bytes ~1.0x as a real shm measurement.
        fields['delivery_plane_processpool_note'] = \
            'shm plane unavailable: both variants ran serialized'
    return fields


SVC_ROWS = int(os.environ.get('PETASTORM_TPU_BENCH_SVC_ROWS', '2048'))
# Row count in the path: changing PETASTORM_TPU_BENCH_SVC_ROWS must build
# a matching dataset, not silently reuse the cached default-size one.
SVC_DATASET_URL = 'file://%s/imagenet_raw_svc_v1_r%d' % (BENCH_DIR, SVC_ROWS)


def ensure_raw_svc_dataset():
    """A larger pre-decoded uint8 dataset (default 2048 rows -> 32 host
    batches) for the service-plane legs: at the base dataset's 768 rows
    the whole exactly-once stream is ~12 batches, and the measurement
    window times lease fill + slab first-touch instead of steady-state
    delivery."""
    from petastorm_tpu.codecs import NdarrayCodec
    from petastorm_tpu.etl.dataset_metadata import DatasetWriter
    from petastorm_tpu.fs_utils import get_filesystem_and_path_or_paths
    from petastorm_tpu.unischema import Unischema, UnischemaField

    fs, path = get_filesystem_and_path_or_paths(SVC_DATASET_URL)
    if fs.exists(path + '/_common_metadata'):
        return

    schema = Unischema('ImagenetRawSvc', [
        UnischemaField('noun_id', np.int64, (), None, False),
        UnischemaField('image', np.uint8, (IMAGE_HW[0], IMAGE_HW[1], 3),
                       NdarrayCodec(), False),
    ])
    rng = np.random.default_rng(0)

    def rows():
        for i in range(SVC_ROWS):
            yield {'noun_id': np.int64(i),
                   'image': rng.integers(0, 256, (IMAGE_HW[0], IMAGE_HW[1], 3),
                                         np.uint8)}

    with DatasetWriter(SVC_DATASET_URL, schema, rows_per_rowgroup=64,
                       compression='none') as w:
        w.write_many(rows())


def delivery_plane_service_leg(worker_counts=(1, 2, 4), shm_pairs=3):
    """Disaggregated delivery plane (``petastorm_tpu/service``): host
    images/s of ONE consumer fed by N in-process decode workers over the
    pre-decoded uint8 service dataset, at N = 1 -> 2 -> 4.  The
    horizontal-scaling answer to the delivery-bound regime r05 measured
    (``stall_pct_delivery_bound`` ~95%: one host's decode/collate plane
    can't feed the chip) — the slope across worker counts is the evidence
    that the decode plane now scales independently of the training host.
    Backend-independent (no device in the loop); in-process workers, so
    this measures the service machinery (lease protocol, ZMQ streaming,
    credit flow, client reassembly), not extra silicon.

    The w1 number is measured as ``shm_pairs`` interleaved pairs against
    its byte-path twin (``ServiceConfig(shm=False)`` ->
    ``..._w1_bytes``), medians reported — the same adjacent-runs
    discipline the headline img/s uses, because single service runs on a
    shared 1-core host swing 2-3x with transient load."""
    from petastorm_tpu.service import (Dispatcher, ServiceConfig,
                                       ServiceDataLoader, Worker)

    ensure_raw_svc_dataset()
    fields = {}
    # Split the fixed decode-thread budget across the worker fleet so a
    # bigger fleet wins on service-plane parallelism, not on extra threads.
    def measure(n_workers, shm=True):
        config = ServiceConfig(
            SVC_DATASET_URL, num_consumers=1, rowgroups_per_split=2,
            lease_ttl_s=30.0, shm=shm,
            reader_kwargs={'workers_count':
                           max(2, WORKERS // max(n_workers, 1))})
        with Dispatcher(config) as dispatcher:
            workers = [Worker(dispatcher.addr).start()
                       for _ in range(n_workers)]
            try:
                loader = ServiceDataLoader(dispatcher.addr, batch_size=BATCH,
                                           consumer=0, drop_last=False,
                                           prefetch=2)
                n_host = 0
                # Worker registration, first leases, and (shm) slab
                # first-touch faults are not steady-state; exclude them.
                warmup_batches = 6
                t0 = t_end = None
                with loader:
                    for i, batch in enumerate(loader.iter_host_batches()):
                        if i == warmup_batches:
                            t0 = time.monotonic()
                        elif i > warmup_batches:
                            n_host += len(batch['noun_id'])
                            # window closes at the last counted batch, NOT
                            # after __exit__: teardown (recv-thread join,
                            # ZMQ context term) is not delivery time and
                            # would skew the w1->w4 scaling slope.
                            t_end = time.monotonic()
                rate = (n_host / (t_end - t0)
                        if n_host and t_end is not None and t_end > t0
                        else 0.0)
                churn = dispatcher._op_stats({})['lease_churn']
            finally:
                for w in workers:
                    w.stop()
                for w in workers:
                    w.join()
        return rate, churn

    # w1 + its byte-path twin (ServiceConfig(shm=False)): interleaved
    # pairs, medians — the service-plane view of what the shm result
    # plane buys (vs the serialized TCP framing every cross-host client
    # pays), measured under the same transient host conditions.
    shm_rates, byte_rates = [], []
    churn = 0
    for _ in range(max(1, int(shm_pairs))):
        rate, pair_churn = measure(1)
        shm_rates.append(rate)
        churn += pair_churn
        byte_rates.append(measure(1, shm=False)[0])
    fields['delivery_plane_service_images_per_sec_host_w1'] = \
        round(float(np.median(shm_rates)), 1)
    fields['delivery_plane_service_images_per_sec_host_w1_bytes'] = \
        round(float(np.median(byte_rates)), 1)
    if churn:
        fields['delivery_plane_service_lease_churn_w1'] = churn
    for n_workers in [n for n in worker_counts if n != 1]:
        rate, churn = measure(n_workers)
        fields['delivery_plane_service_images_per_sec_host_w%d'
               % n_workers] = round(rate, 1)
        if churn:
            fields['delivery_plane_service_lease_churn_w%d'
                   % n_workers] = churn

    # Stall attribution (ISSUE 5 satellite): one short instrumented pass
    # — TraceRecorder on the client merges the workers' correlated spans
    # (decode/serialize/shm publish) onto the consumer timeline, and the
    # StallMonitor's data_wait windows decompose by component.  The top
    # component rides the compact line; the full pct map is detail.
    # Contained: a failure here may lose only these two fields, never
    # the scaling measurements already sitting in `fields`.
    try:
        from petastorm_tpu.benchmark import StallMonitor, TraceRecorder
        recorder = TraceRecorder()
        config = ServiceConfig(
            SVC_DATASET_URL, num_consumers=1, rowgroups_per_split=2,
            lease_ttl_s=30.0,
            reader_kwargs={'workers_count': max(2, WORKERS // 2)})
        monitor = StallMonitor(warmup_steps=4, trace_recorder=recorder)
        with Dispatcher(config) as dispatcher:
            workers = [Worker(dispatcher.addr).start() for _ in range(2)]
            try:
                loader = ServiceDataLoader(dispatcher.addr,
                                           batch_size=BATCH,
                                           consumer=0, drop_last=False,
                                           prefetch=2,
                                           trace_recorder=recorder)
                with loader:
                    for _ in monitor.wrap(loader.iter_host_batches()):
                        pass
            finally:
                for w in workers:
                    w.stop()
                for w in workers:
                    w.join()
        report = monitor.report()
        if 'stall_breakdown' in report:
            fields['stall_breakdown_service'] = report['stall_breakdown']
            fields['stall_top_component'] = report['stall_top_component']
    except Exception as e:  # noqa: BLE001 — diagnostic add-on only
        fields['stall_breakdown_error'] = '%s: %s' % (type(e).__name__, e)
    return fields


def control_plane_recovery_leg(pairs=2, consume_batches=10):
    """Crash-survivable control plane (ISSUE 15): time-to-first-batch
    after a dispatcher restart, ledger-restored vs cold, measured on a
    LIVE client (no resume token — the mid-training scenario a
    dispatcher crash actually interrupts).

    Procedure per run: serve ~``consume_batches`` host batches of the
    pre-decoded service dataset, quiesce (worker down, client drained
    and waiting), then bring up a NEW dispatcher on the same port + a
    fresh worker and time until the client delivers its first
    not-yet-seen row.  Cold restart forgets the ledger: the fleet
    re-decodes (and the client dedupes) every already-delivered split
    before new rows flow.  Ledger-restored skips straight to the
    remaining work.  Interleaved pairs, medians; exactly-once asserted
    in-leg on every run (restart must never cost correctness, only
    latency)."""
    import socket
    import tempfile
    import threading

    from petastorm_tpu.service import (Dispatcher, ServiceConfig,
                                       ServiceDataLoader, Worker)

    ensure_raw_svc_dataset()
    workdir = tempfile.mkdtemp(prefix='ptcp-recovery-')

    def measure(with_ledger, tag):
        with socket.socket() as s:
            s.bind(('127.0.0.1', 0))
            addr = 'tcp://127.0.0.1:%d' % s.getsockname()[1]
        ledger_path = (os.path.join(workdir, 'ledger_%s.json' % tag)
                       if with_ledger else None)
        config = ServiceConfig(
            SVC_DATASET_URL, num_consumers=1, rowgroups_per_split=2,
            lease_ttl_s=10.0, ledger_path=ledger_path,
            reader_kwargs={'workers_count': max(2, WORKERS // 2)})
        d1 = Dispatcher(config, bind=addr).start()
        w1 = Worker(addr).start()
        deliveries = []   # (t_mono, [row ids]) per host batch
        pump_errors = []  # surfaced in the driver loop — a dead pump
        done = threading.Event()     # must name ITS error, not wedge
                                     # the leg into a misleading timeout

        loaders = []

        def pump():
            try:
                loader = ServiceDataLoader(addr, batch_size=BATCH,
                                           consumer=0, drop_last=False,
                                           queue_splits=1, credits=4)
                loaders.append(loader)
                with loader:
                    for batch in loader.iter_host_batches():
                        deliveries.append(
                            (time.monotonic(),
                             np.asarray(batch['noun_id']).tolist()))
            except Exception as e:  # noqa: BLE001 — re-raised below
                pump_errors.append(e)
            finally:
                done.set()

        def check_pump():
            if pump_errors:
                raise pump_errors[0]

        def stop_loaders():
            for loader in loaders:
                try:
                    loader.reader.stop()
                except Exception:  # noqa: BLE001 — teardown best-effort
                    pass

        consumer = threading.Thread(target=pump, daemon=True)
        consumer.start()
        try:
            deadline = time.monotonic() + 300.0
            while len(deliveries) < consume_batches \
                    and not done.is_set():
                if time.monotonic() > deadline:
                    raise RuntimeError('recovery leg: first phase '
                                       'wedged')
                time.sleep(0.05)
            check_pump()
        except BaseException:
            stop_loaders()
            raise
        finally:
            # Quiesce on the happy path AND teardown on error: the
            # phase-1 service must never outlive measure() — a leaked
            # live worker/dispatcher would contaminate every later
            # bench leg's measurements.  (Also part of the protocol:
            # no pre-restart decode may feed the TTFB — the worker's
            # buffers die with it, the client drains to a steady wait.)
            w1.stop()
            w1.join()
            d1.stop()
            d1.join()
        while deliveries and time.monotonic() - deliveries[-1][0] < 0.75:
            time.sleep(0.05)
        seen_before = {i for _, ids in deliveries for i in ids}
        t0 = time.monotonic()
        d2 = Dispatcher(config, bind=addr).start()
        w2 = Worker(addr).start()
        ttfb = None
        try:
            while True:
                check_pump()
                fresh = [(t, ids) for t, ids in deliveries if t > t0
                         and set(ids) - seen_before]
                if fresh:
                    ttfb = fresh[0][0] - t0
                    break
                if done.is_set():
                    raise RuntimeError('recovery leg: epoch ended with '
                                       'no new rows after restart')
                if time.monotonic() > deadline:
                    raise RuntimeError('recovery leg: no new rows after '
                                       'restart')
                time.sleep(0.02)
            done.wait(timeout=max(1.0, deadline - time.monotonic()))
            check_pump()
            if not done.is_set():
                raise RuntimeError('recovery leg: epoch wedged after '
                                   'restart')
            delivered = sorted(i for _, ids in deliveries for i in ids)
            exactly_once = delivered == list(range(SVC_ROWS))
            restores = d2.ledger_restores
        except BaseException:
            stop_loaders()
            raise
        finally:
            w2.stop()
            w2.join()
            d2.stop()
            d2.join()
        return ttfb, exactly_once, restores

    cold, restored = [], []
    exact = True
    try:
        for pair in range(max(1, int(pairs))):
            ttfb, ok, restores = measure(True, 'restored_%d' % pair)
            assert restores == 1, \
                'ledger arm never restored (restores=%r)' % restores
            restored.append(ttfb)
            exact = exact and ok
            ttfb, ok, _ = measure(False, 'cold_%d' % pair)
            cold.append(ttfb)
            exact = exact and ok
    finally:
        import shutil
        shutil.rmtree(workdir, ignore_errors=True)
    cold_s = float(np.median(cold))
    restored_s = float(np.median(restored))
    return {
        'control_plane_ttfb_cold_s': round(cold_s, 3),
        'control_plane_ttfb_restored_s': round(restored_s, 3),
        'control_plane_recovery_speedup':
            round(cold_s / restored_s, 2) if restored_s else None,
        'control_plane_exactly_once': bool(exact),
    }


def _make_light_step():
    """A cheap jitted step with the SAME state/signature as
    ``_make_resnet_step`` (so ``_device_floor_ms`` / ``_run_stall`` /
    ``_run_scan_batches_stall`` run unchanged): one flattened matmul over
    the uint8 batch.  Fast enough to give the scan_batches drivers a
    measurable device floor on ANY backend — including a CPU rehearsal,
    where the ResNet step (~30 s/step) makes the fused-dispatch stall
    legs unrunnable."""
    import jax
    import jax.numpy as jnp

    features = IMAGE_HW[0] * IMAGE_HW[1] * 3
    params = jnp.full((features, 8), 0.01, jnp.float32)
    batch_stats, opt_state = jnp.zeros(()), jnp.zeros(())

    @jax.jit
    def train_step(params, batch_stats, opt_state, images_u8, labels):
        x = images_u8.astype(jnp.float32).reshape(
            (images_u8.shape[0], -1)) / 255.0
        loss = jnp.mean((x @ params) ** 2) \
            + 0.0 * jnp.mean(labels.astype(jnp.float32))
        # Chain the carry through the loss so every step in a scanned /
        # async-dispatched window must actually execute before the
        # terminal D2H settles.
        return params + 0.0 * loss, batch_stats, opt_state, loss

    return train_step, params, batch_stats, opt_state


def _wipe_plane(plane_dir):
    import shutil

    from petastorm_tpu.cache_plane.plane import default_ram_dir
    shutil.rmtree(plane_dir, ignore_errors=True)
    shutil.rmtree(default_ram_dir(plane_dir), ignore_errors=True)


def _plane_epoch_rate(cache_kwargs):
    """Host images/s of ONE full epoch of the JPEG (decode-bound) dataset
    through the streaming loader; the timer opens at the first delivered
    batch so pool spin-up is excluded identically cold and warm."""
    from petastorm_tpu import make_reader
    from petastorm_tpu.jax import DataLoader

    with make_reader(DATASET_URL, num_epochs=1, workers_count=WORKERS,
                     shuffle_row_groups=False, columnar_decode=True,
                     **cache_kwargs) as reader:
        loader = DataLoader(reader, batch_size=BATCH, prefetch=2)
        n_host, t0, t_end = 0, None, None
        for i, batch in enumerate(loader.iter_host_batches()):
            if i == 0:
                t0 = time.monotonic()
            else:
                n_host += len(batch['noun_id'])
                t_end = time.monotonic()
    return (n_host / (t_end - t0)
            if n_host and t_end is not None and t_end > t0 else 0.0)


def _plane_service_epoch_rate(plane_dir):
    """Host images/s of one service pass over the JPEG dataset with the
    epoch-cache plane enabled; run once cold and once warm against the
    same plane dir, the delta is what the plane buys the service path."""
    from petastorm_tpu.service import (Dispatcher, ServiceConfig,
                                       ServiceDataLoader, Worker)

    # One decode thread per split reader: the decode-bound regime this
    # plane exists for (the worker's decode plane saturated, delivery
    # not) — on the 1-2 core bench host extra threads only time thread
    # churn, and a deterministic split reader rides along for free.
    # 4 row groups per split amortizes per-split reader construction,
    # which warm runs would otherwise pay as protocol noise.
    config = ServiceConfig(
        DATASET_URL, num_consumers=1, rowgroups_per_split=4,
        lease_ttl_s=30.0,
        reader_kwargs={'workers_count': 1},
        cache_plane=True, cache_plane_dir=plane_dir)
    with Dispatcher(config) as dispatcher:
        worker = Worker(dispatcher.addr).start()
        try:
            loader = ServiceDataLoader(dispatcher.addr, batch_size=BATCH,
                                       consumer=0, drop_last=False,
                                       prefetch=2)
            n_host, t0, t_end = 0, None, None
            with loader:
                for i, batch in enumerate(loader.iter_host_batches()):
                    if i == 0:
                        t0 = time.monotonic()
                    else:
                        n_host += len(batch['noun_id'])
                        t_end = time.monotonic()
        finally:
            worker.stop()
            worker.join()
    return (n_host / (t_end - t0)
            if n_host and t_end is not None and t_end > t0 else 0.0)


def epoch_cache_plane_leg(pairs=3):
    """Tiered epoch-cache plane (``petastorm_tpu/cache_plane``): cold
    (epoch 1, full JPEG decode) vs warm (epoch 2+, plane-served) host
    throughput on the decode-bound dataset, for the streaming reader
    (``cache_type='plane'``) and the data service
    (``ServiceConfig(cache_plane=True)``) — the evidence that epoch >= 2
    cost is independent of decode cost.  Cold/warm runs are interleaved
    pairs with medians (single runs on a shared 1-core host swing 2-3x).

    Also measures the ``scan_batches`` fused dispatch on this pipeline
    with the light step (see ``_make_light_step``): the cold/streaming
    number fills ``stall_pct_streaming_scan`` when no on-chip leg
    measured it this run, and the warm-plane twin ships as
    ``stall_pct_epoch_cache_warm_scan``.
    """
    from petastorm_tpu.jax import DataLoader  # noqa: F401 — warm import

    plane_dir = os.path.join(BENCH_DIR, 'epoch_cache_plane_v1')
    cache_kwargs = {'cache_type': 'plane', 'cache_location': plane_dir}
    cold_rates, warm_rates = [], []
    for _ in range(max(1, int(pairs))):
        _wipe_plane(plane_dir)
        cold_rates.append(_plane_epoch_rate(cache_kwargs))
        warm_rates.append(_plane_epoch_rate(cache_kwargs))
    cold = float(np.median(cold_rates))
    warm = float(np.median(warm_rates))
    fields = {
        'epoch_cache_streaming_cold_images_per_sec': round(cold, 1),
        'epoch_cache_streaming_warm_images_per_sec': round(warm, 1),
        'epoch_cache_streaming_warm_over_cold':
            round(warm / cold, 2) if cold else None,
    }

    svc_cold, svc_warm = [], []
    for _ in range(2):
        _wipe_plane(plane_dir)
        svc_cold.append(_plane_service_epoch_rate(plane_dir))
        svc_warm.append(_plane_service_epoch_rate(plane_dir))
    cold = float(np.median(svc_cold))
    warm = float(np.median(svc_warm))
    fields.update({
        'epoch_cache_service_cold_images_per_sec': round(cold, 1),
        'epoch_cache_service_warm_images_per_sec': round(warm, 1),
        'epoch_cache_service_warm_over_cold':
            round(warm / cold, 2) if cold else None,
    })

    # scan_batches fused dispatch, measured (not just written): light-step
    # floor on whatever backend this process has.  Unlike the throughput
    # halves above, this half IS device-coupled (jit + device_put).
    from petastorm_tpu import make_reader
    state = _make_light_step()
    floor_ms = _device_floor_ms(state, 64)
    scan_k = max(1, min(12, TRAIN_STEPS))
    scan_steps = 2 * max(1, NUM_IMAGES // BATCH)
    epochs_scan = -(-(scan_k * (2 + -(-scan_steps // scan_k)))
                    // max(1, NUM_IMAGES // BATCH))
    fields['epoch_cache_scan_floor_ms'] = round(floor_ms, 2)
    # Guarantee warmth for the warm-scan number: one untimed streaming
    # epoch (re)fills the plane with THIS reader config's keys — the
    # service pairs above were the last writers and nothing pins their
    # keys to the streaming reader's across future edits.
    _plane_epoch_rate(cache_kwargs)
    with make_reader(DATASET_URL, num_epochs=epochs_scan,
                     workers_count=WORKERS, shuffle_row_groups=False,
                     columnar_decode=True, **cache_kwargs) as reader:
        loader = DataLoader(reader, batch_size=BATCH, prefetch=2)
        stall, step_ms = _run_scan_batches_stall(
            loader, state, scan_steps, floor_ms, steps_per_call=scan_k)
    fields.update({'stall_pct_epoch_cache_warm_scan': stall,
                   'step_ms_epoch_cache_warm_scan': round(step_ms, 2)})
    return fields


def first_epoch_warm_leg(pairs=2):
    """Proactive materialization (ISSUE 18): the FIRST epoch a consumer
    ever runs, cold (every JPEG decoded on the consumer's clock) vs
    pre-warmed (a :class:`MaterializeController` decoded the dataset
    into the plane before the consumer arrived).  The epoch-cache leg
    above measures epoch 2+ of one tenant; this leg measures what
    materialization moves — the cold start itself — for a brand-new
    consumer whose plane was warmed off its clock.

    Asserted in-leg, not just reported: the warm epoch performs ZERO
    host decodes (plane misses == 0), and the cold and warm delivery
    digests are identical (warming changes when rows are decoded,
    never what is delivered)."""
    from petastorm_tpu import make_reader
    from petastorm_tpu.jax import DataLoader
    from petastorm_tpu.materialize import MaterializeController
    from petastorm_tpu.test_util.chaos import DeliveryDigest

    plane_dir = os.path.join(BENCH_DIR, 'first_epoch_warm_v1')
    cache_kwargs = {'cache_type': 'plane', 'cache_location': plane_dir}

    def first_epoch(digest=None, **extra):
        """One first-epoch pass; same timer protocol as
        ``_plane_epoch_rate`` (opens at the first delivered batch), plus
        the reader's plane counters.  ``digest`` (untimed verification
        passes only — per-row hashing would cap the measured rate)
        accumulates the delivery digest."""
        with make_reader(DATASET_URL, num_epochs=1, workers_count=WORKERS,
                         shuffle_row_groups=False, columnar_decode=True,
                         **extra) as reader:
            loader = DataLoader(reader, batch_size=BATCH, prefetch=2)
            n_host, t0, t_end = 0, None, None
            for i, batch in enumerate(loader.iter_host_batches()):
                if digest is not None:
                    digest.update({k: np.asarray(v)
                                   for k, v in batch.items()})
                if i == 0:
                    t0 = time.monotonic()
                else:
                    n_host += len(batch['noun_id'])
                    t_end = time.monotonic()
            diag = reader.diagnostics
        return (n_host / (t_end - t0)
                if n_host and t_end is not None and t_end > t0 else 0.0,
                diag)

    cold_rates, warm_rates, mat_times = [], [], []
    warm_decodes = 0
    for _ in range(max(1, int(pairs))):
        _wipe_plane(plane_dir)
        cold_rates.append(first_epoch(**cache_kwargs)[0])
        # Warming must pay the full decode itself: the cold pass above
        # populated the plane as a side effect, so wipe before timing it.
        _wipe_plane(plane_dir)
        t0 = time.monotonic()
        with MaterializeController(DATASET_URL, plane_dir) as controller:
            summary = controller.run()
        mat_times.append(time.monotonic() - t0)
        if summary.get('done') != summary.get('total_pieces') \
                or summary.get('failed_pieces'):
            raise AssertionError('materialize pass incomplete: %r'
                                 % (summary,))
        rate, diag = first_epoch(**cache_kwargs)
        warm_rates.append(rate)
        warm_decodes = max(warm_decodes, int(diag.get('cache_misses', -1)))
    # Delivery identity, asserted on untimed verification passes: the
    # plane left warm by the last pair vs a decode-direct (cache-off)
    # ground-truth epoch.
    warm_digest, cold_digest = DeliveryDigest(), DeliveryDigest()
    first_epoch(warm_digest, **cache_kwargs)
    first_epoch(cold_digest)
    if warm_digest.hexdigest() != cold_digest.hexdigest():
        raise AssertionError(
            'pre-warmed first epoch delivered %s, decode-direct delivered '
            '%s' % (warm_digest.hexdigest(), cold_digest.hexdigest()))
    if warm_decodes != 0:
        raise AssertionError('pre-warmed first epoch decoded %d piece(s) '
                             'on the host (expected 0: every piece was '
                             'materialized)' % warm_decodes)
    cold = float(np.median(cold_rates))
    warm = float(np.median(warm_rates))
    return {
        'first_epoch_cold_images_per_sec': round(cold, 1),
        'first_epoch_warm_images_per_sec': round(warm, 1),
        'first_epoch_warm_over_cold':
            round(warm / cold, 2) if cold else None,
        'first_epoch_warm_decodes': int(warm_decodes),
        'first_epoch_materialize_s':
            round(float(np.median(mat_times)), 2),
        'first_epoch_wire_entries': int(summary.get('wire_published', 0)),
        'first_epoch_digest_identical': True,
    }


def _cluster_fleet_pass(shared_plane, worker_planes, collect_digest=False,
                        wait_digests=0):
    """One ordered client pass over the JPEG dataset against a fresh
    dispatcher with one worker per plane dir (distinct dirs = a
    simulated multi-host fleet; the per-worker ``cache_plane_dir``
    override exists for exactly this).  Returns ``(rate, digest,
    worker_diags)`` — the digest hashes every delivered row's id + image
    bytes in delivery order (``ordered=True`` + ``workers_count=1``
    split readers make the sequence deterministic regardless of which
    worker serves), so two passes are bit-identical iff digests match."""
    import hashlib

    from petastorm_tpu.service import (Dispatcher, ServiceConfig,
                                       ServiceDataLoader, Worker)
    from petastorm_tpu.service.worker import _Rpc

    config = ServiceConfig(
        DATASET_URL, num_consumers=1, rowgroups_per_split=2,
        lease_ttl_s=30.0, reader_kwargs={'workers_count': 1},
        cache_plane=True, cache_plane_dir=shared_plane)
    with Dispatcher(config) as dispatcher:
        workers = [Worker(dispatcher.addr, cache_plane_dir=p).start()
                   for p in worker_planes]
        try:
            if wait_digests:
                # The warm worker's digest advertisement + the piece map
                # ride heartbeats; let them land before granting leases
                # so the measured pass is the WARM path, not a race.
                import zmq
                context = zmq.Context()
                rpc = _Rpc(context, dispatcher.addr)
                try:
                    deadline = time.monotonic() + 30
                    while time.monotonic() < deadline:
                        rollup = rpc.call({'op': 'stats'})['cluster_cache']
                        if rollup['piece_map'] \
                                and rollup['directory_digests'] \
                                >= wait_digests:
                            break
                        time.sleep(0.2)
                finally:
                    rpc.close()
                    context.term()
            loader = ServiceDataLoader(dispatcher.addr, batch_size=BATCH,
                                       consumer=0, drop_last=False,
                                       prefetch=2, ordered=True)
            h = hashlib.blake2b(digest_size=16) if collect_digest else None
            n_host, t0, t_end = 0, None, None
            with loader:
                for i, batch in enumerate(loader.iter_host_batches()):
                    if i == 0:
                        t0 = time.monotonic()
                    else:
                        n_host += len(batch['noun_id'])
                        t_end = time.monotonic()
                    if h is not None:
                        h.update(np.ascontiguousarray(
                            batch['noun_id']).tobytes())
                        h.update(np.ascontiguousarray(
                            batch['image']).tobytes())
            diags = [w.diagnostics for w in workers]
        finally:
            for w in workers:
                w.stop()
            for w in workers:
                w.join()
    rate = (n_host / (t_end - t0)
            if n_host and t_end is not None and t_end > t0 else 0.0)
    return rate, (h.hexdigest() if h is not None else None), diags


def cluster_cache_leg(pairs=3):
    """Cluster cache tier (ISSUE 10): three interleaved fleet passes
    over the JPEG (decode-bound) dataset, medians reported —

    * ``cold_join``: ONE worker, cold plane — what a lone host achieves
      by decoding everything itself ("its own cold-decode throughput",
      the acceptance denominator);
    * ``cold_fleet``: TWO workers, both planes cold — the fair
      same-topology control for the warm fleet;
    * ``warm``: TWO workers, one plane decoded ELSEWHERE (a prior run's
      plane; the other worker cold — the "worker joining a fleet that
      already decoded the dataset" scenario): splits stream as remote
      HITs out of the plane (no reader constructed), peer fill covering
      any lease the cold joiner wins.

    ``warm_over_cold_join`` is the acceptance ratio (a joining host
    sustains this multiple of what it could decode alone);
    ``warm_over_cold_fleet`` is the topology-controlled fleet ratio
    (ceilinged by the single consumer's delivery bandwidth, so it
    compresses on fast-decode hosts).  Warm delivery is asserted
    bit-identical to the single-worker direct-decode reference in-leg —
    an ordering or content regression fails the leg loudly rather than
    shipping a quietly-wrong ratio."""
    base = os.path.join(BENCH_DIR, 'cluster_cache_v1')
    prep = os.path.join(base, 'plane_prep')
    pieces = -(-NUM_IMAGES // 64)
    _wipe_plane(prep)
    _cluster_fleet_pass(prep, [prep])      # untimed: decode once into prep
    rates = {'cold_join': [], 'cold_fleet': [], 'warm': []}
    ref_digest = warm_digest = None
    totals = {'cache_remote_hits': 0, 'cache_peer_fills': 0,
              'cache_peer_degraded': 0}
    for pair in range(max(1, int(pairs))):
        cold_a = os.path.join(base, 'cold_a')
        cold_b = os.path.join(base, 'cold_b')
        _wipe_plane(cold_a)
        _wipe_plane(cold_b)
        rate, digest, _ = _cluster_fleet_pass(
            cold_a, [cold_a], collect_digest=(pair == 0))
        rates['cold_join'].append(rate)
        if pair == 0:
            ref_digest = digest
        _wipe_plane(cold_a)
        rate, _, _ = _cluster_fleet_pass(cold_a, [cold_a, cold_b])
        rates['cold_fleet'].append(rate)
        warm_b = os.path.join(base, 'warm_b')
        _wipe_plane(warm_b)
        rate, digest, diags = _cluster_fleet_pass(
            prep, [prep, warm_b], collect_digest=(pair == 0),
            wait_digests=pieces)
        rates['warm'].append(rate)
        if pair == 0:
            warm_digest = digest
        for diag in diags:
            for key in totals:
                totals[key] += diag[key]
    if ref_digest != warm_digest:
        # In-leg assertion (transfer/adaptive-leg discipline): the
        # compact-line boolean gates nothing by itself.
        raise AssertionError(
            'cluster-cache warm delivery diverged from the direct-decode '
            'reference (%s vs %s)' % (warm_digest, ref_digest))
    med = {k: float(np.median(v)) for k, v in rates.items()}
    return {
        'cluster_cache_images_per_sec_cold_join':
            round(med['cold_join'], 1),
        'cluster_cache_images_per_sec_cold_fleet':
            round(med['cold_fleet'], 1),
        'cluster_cache_images_per_sec_warm': round(med['warm'], 1),
        'cluster_cache_warm_over_cold_join':
            round(med['warm'] / med['cold_join'], 2)
            if med['cold_join'] else None,
        'cluster_cache_warm_over_cold_fleet':
            round(med['warm'] / med['cold_fleet'], 2)
            if med['cold_fleet'] else None,
        'cluster_cache_remote_hits': totals['cache_remote_hits'],
        'cluster_cache_peer_fills': totals['cache_peer_fills'],
        'cluster_cache_peer_degraded': totals['cache_peer_degraded'],
        'cluster_cache_bit_identical': True,
    }


def transfer_plane_leg(pairs=3, reps=8):
    """Host→device transfer plane (ISSUE 6): delivered-images/s of the
    coalesced ring path and its wire-narrowed variant vs the inline
    per-column ``device_put`` baseline, on a multi-column image batch
    (96×96×3 uint8 image + 96 16-wide float32 feature columns + int64
    label — the wide-table regime transfer coalescing targets, where the
    per-put fixed dispatch cost dominates; that regime is also the one
    that measures meaningfully on ANY backend, including the CPU, where
    the link itself is a memcpy).  Variants run interleaved round-robin
    ``pairs`` times with medians reported (single runs on a shared host
    swing 2-3x).  Plane-off equivalence (the kill-switch/degrade matrix)
    is asserted bit-identical here rather than timed."""
    import jax

    from petastorm_tpu.jax.transfer import TransferPlane

    rng = np.random.default_rng(0)
    batch = {'image': rng.integers(0, 256, (BATCH, 96, 96, 3))
                         .astype(np.uint8)}
    for i in range(96):
        batch['feat_%02d' % i] = rng.standard_normal(
            (BATCH, 16)).astype(np.float32)
    batch['label'] = rng.integers(0, 1000, (BATCH,)).astype(np.int64)

    def run_inline():
        t0 = time.monotonic()
        outs = [jax.device_put(batch) for _ in range(reps)]
        jax.block_until_ready(outs)
        return reps * BATCH / (time.monotonic() - t0)

    planes = {'coalesced': TransferPlane(ring_slots=3),
              'narrowed': TransferPlane(ring_slots=3, wire_dtypes='auto')}

    def run_plane(plane):
        t0 = time.monotonic()
        outs = [plane.put(batch) for _ in range(reps)]
        assert outs[0] is not None, 'plane degraded on the bench batch'
        jax.block_until_ready(outs)
        return reps * BATCH / (time.monotonic() - t0)

    # Untimed warmup for every variant: device_put path, slab first-touch
    # faults, and the unpack executables compile outside the window.
    jax.block_until_ready(jax.device_put(batch))
    for plane in planes.values():
        jax.block_until_ready(plane.put(batch))
    rates = {'inline': [], 'coalesced': [], 'narrowed': []}
    for _ in range(max(1, int(pairs))):
        rates['inline'].append(run_inline())
        rates['coalesced'].append(run_plane(planes['coalesced']))
        rates['narrowed'].append(run_plane(planes['narrowed']))
    med = {k: float(np.median(v)) for k, v in rates.items()}
    wire = planes['narrowed'].metrics.counter('h2d_bytes_wire').value
    logical = planes['narrowed'].metrics.counter('h2d_bytes_logical').value
    fields = {
        'transfer_plane_images_per_sec_inline': round(med['inline'], 1),
        'transfer_plane_images_per_sec_coalesced':
            round(med['coalesced'], 1),
        'transfer_plane_images_per_sec_narrowed': round(med['narrowed'], 1),
        'transfer_plane_coalesced_over_inline':
            round(med['coalesced'] / med['inline'], 2) if med['inline']
            else None,
        'transfer_plane_narrowed_over_inline':
            round(med['narrowed'] / med['inline'], 2) if med['inline']
            else None,
        'transfer_plane_wire_bytes_ratio':
            round(wire / logical, 3) if logical else None,
    }
    # Degrade-matrix equivalence, asserted on the same batch: the exact
    # (no-narrowing) plane output must be bit-identical to the inline
    # path — the contract that makes 'auto' safe to leave on.
    exact = planes['coalesced'].put(batch)
    ref = jax.device_put(batch)
    identical = all(
        np.asarray(exact[k]).dtype == np.asarray(ref[k]).dtype
        and np.array_equal(np.asarray(exact[k]), np.asarray(ref[k]))
        for k in batch)
    fields['transfer_plane_bit_identical'] = bool(identical)
    for plane in planes.values():
        plane.close()
    return fields


SKEW_DATASET_URL = 'file://' + BENCH_DIR + '/skew_mixed_jpeg_v2'
SKEW_UNIFORM_URL = 'file://' + BENCH_DIR + '/skew_uniform_jpeg_v2'
#: Emulated cold storage for the scheduling leg: plenty of streaming
#: bandwidth (fast ~100 KB groups fetch in ~2.5 ms), but each multi-MB
#: straggler FILE pays a cold-object first-read latency (a cold-tier
#: GET/recall) — a pure GIL-released wait, so a straggler's wall time is
#: comparable to the whole fast epoch while consuming almost no CPU.
#: That is the regime the scheduler targets: FIFO pays the straggler
#: wherever the shuffle lands it (an idle-pool epoch tail when late),
#: adaptive launches it at t=0 and hides it under the fast stream.
SKEW_COLD_BPS = 40e6
#: Sized so the straggler wall (~1.25 s with the open + decode) stays
#: comparable to, but safely under, the fast-epoch duration across
#: host-speed swings: a straggler much shorter than the epoch
#: compresses the measured win toward 1; one LONGER than the fast
#: stream's in-flight horizon stalls adaptive too.
SKEW_COLD_LATENCY_S = 1.2
#: 200 fast groups + 2 stragglers: the epoch must be LONG relative to
#: FIFO's own in-flight lookahead (2x workers), or FIFO accidentally
#: launches stragglers early too and the comparison measures nothing.
_SKEW_GROUPS, _SKEW_SLOW_EVERY = 202, 101
_SKEW_ROWS_PER_GROUP, _SKEW_SLOW_HW, _SKEW_FAST_HW = 8, 512, 224
#: Straggler rows additionally carry an incompressible pad column that
#: the leg never reads: it inflates the straggler FILE past the cold
#: gate (and past every fast file for the byte-size cost prior) without
#: adding decode work — the straggler is latency-dominated, like a real
#: cold-tier object, not CPU-heavy (early-launching CPU-heavy pieces
#: would just move their decode into contention with the fast stream).
_SKEW_PAD_BYTES, _SKEW_FAST_PAD_BYTES = 1 << 18, 8


def _ensure_skew_dataset(url, groups, slow_every):
    """Mixed-resolution JPEG dataset for the scheduling leg: fast groups
    are 224² low-entropy JPEGs (~100 KB/group), slow groups are 512²
    per-pixel-noise JPEGs padded to multi-MB cold-tier objects by an
    unread, incompressible ``pad`` column.  One row group per FILE
    (``rows_per_file``): the cold filesystem's size gate must see each
    straggler as its own multi-MB object.  ``slow_every=None`` builds
    the uniform twin (no stragglers — the noise-band control)."""
    from petastorm_tpu.codecs import CompressedImageCodec, NdarrayCodec
    from petastorm_tpu.etl.dataset_metadata import DatasetWriter
    from petastorm_tpu.fs_utils import get_filesystem_and_path_or_paths
    from petastorm_tpu.unischema import Unischema, UnischemaField

    fs, path = get_filesystem_and_path_or_paths(url)
    if fs.exists(path + '/_common_metadata'):
        return
    schema = Unischema('SkewBench', [
        UnischemaField('noun_id', np.int64, (), None, False),
        UnischemaField('image', np.uint8, (None, None, 3),
                       CompressedImageCodec('jpeg', quality=85), False),
        UnischemaField('pad', np.uint8, (None,), NdarrayCodec(), False),
    ])
    rng = np.random.default_rng(0)

    def img(hw, noisy):
        base = np.linspace(0, 200, hw * hw * 3,
                           dtype=np.float32).reshape(hw, hw, 3)
        if noisy:  # per-pixel noise: many JPEG bytes per pixel
            tex = rng.integers(0, 160, (hw, hw, 3))
        else:      # 4x4-blocked jitter: natural-ish, compact
            tex = rng.integers(0, 56, (hw // 4, hw // 4, 3)) \
                     .repeat(4, 0).repeat(4, 1)
        return np.clip(base + tex, 0, 255).astype(np.uint8)

    def rows():
        i = 0
        for g in range(groups):
            slow = slow_every is not None and g % slow_every == 0
            hw = _SKEW_SLOW_HW if slow else _SKEW_FAST_HW
            pad_n = _SKEW_PAD_BYTES if slow else _SKEW_FAST_PAD_BYTES
            for _ in range(_SKEW_ROWS_PER_GROUP):
                pad = rng.integers(0, 255, pad_n).astype(np.uint8)
                yield {'noun_id': np.int64(i), 'image': img(hw, slow),
                       'pad': pad}
                i += 1

    with DatasetWriter(url, schema,
                       rows_per_rowgroup=_SKEW_ROWS_PER_GROUP,
                       rows_per_file=_SKEW_ROWS_PER_GROUP) as w:
        w.write_many(rows())


def adaptive_sched_leg(pairs=4, seeds_per=3):
    """Adaptive out-of-order scheduler (ISSUE 9): epoch images/s of
    ``scheduling='adaptive'`` vs ``'fifo'`` on the skew-heavy
    mixed-resolution JPEG dataset behind an emulated cold filesystem
    (``BandwidthLimitedFilesystem`` — bandwidth + cold-object first-read
    latency, both GIL-released waits that parallelize across the pool
    like real remote storage), plus the uniform-twin control where
    adaptive must measure within the host's ±30% noise band.

    Protocol: interleaved fifo/adaptive pairs over a FIXED seed set
    (per-seed straggler placement is part of what FIFO pays for, so the
    seed set must be identical across variants and pairs — otherwise
    placement variance swamps the policy effect), one epoch per reader
    (epoch throughput: FIFO's cost IS the epoch tail), medians
    reported.  Timing covers ITERATION only — reader setup is per-job,
    not per-epoch, and the adaptive footer scan pays the emulated
    cold-object latency at setup.  Delivery-order bit-identity is
    asserted in-leg against the serialized dummy-pool reference
    (multi-worker FIFO delivers in COMPLETION order — epoch-order
    delivery is the adaptive reorder stage's contract, not the legacy
    pool's)."""
    from petastorm_tpu import make_reader
    from petastorm_tpu.benchmark.hostplane import BandwidthLimitedFilesystem
    from petastorm_tpu.transform import ResizeImages

    stragglers = (_SKEW_GROUPS + _SKEW_SLOW_EVERY - 1) // _SKEW_SLOW_EVERY
    _ensure_skew_dataset(SKEW_DATASET_URL, _SKEW_GROUPS, _SKEW_SLOW_EVERY)
    _ensure_skew_dataset(SKEW_UNIFORM_URL, _SKEW_GROUPS - stragglers, None)
    import fsspec
    cold_fs = BandwidthLimitedFilesystem(fsspec.filesystem('file'),
                                         SKEW_COLD_BPS,
                                         cold_latency=SKEW_COLD_LATENCY_S)
    seeds = list(range(seeds_per))
    sched_workers = 8  # straggler fetches must parallelize across the pool

    def epoch_sweep(url, scheduling, collect_ids=False, **overrides):
        ids = [] if collect_ids else None
        n = 0
        elapsed = 0.0
        kwargs = dict(filesystem=cold_fs, workers_count=sched_workers,
                      columnar_decode=True,
                      transform_spec=ResizeImages({'image': (224, 224)}),
                      shuffle_row_groups=True, num_epochs=1,
                      scheduling=scheduling,
                      # this leg measures the SCHEDULER: the ingest plane
                      # would hide the very cold-fetch skew it reorders
                      # around (the object_store_ingest leg measures that)
                      ingest='off')
        kwargs.update(overrides)
        for seed in seeds:
            with make_reader(url, seed=seed, **kwargs) as r:
                t0 = time.monotonic()
                for batch in r:
                    n += len(batch.noun_id)
                    if ids is not None:
                        ids.extend(int(x) for x in batch.noun_id)
                elapsed += time.monotonic() - t0
        return n / elapsed, ids

    epoch_sweep(SKEW_DATASET_URL, 'fifo')  # warmup: page cache, pools
    rates = {'fifo': [], 'adaptive': []}
    adaptive_ids = None
    for i in range(max(1, int(pairs))):
        rates['fifo'].append(
            epoch_sweep(SKEW_DATASET_URL, 'fifo')[0])
        rate, adaptive_ids_i = epoch_sweep(SKEW_DATASET_URL, 'adaptive',
                                           collect_ids=(i == 0))
        rates['adaptive'].append(rate)
        if i == 0:
            adaptive_ids = adaptive_ids_i
    med = {k: float(np.median(v)) for k, v in rates.items()}
    # Delivery-order contract, end to end on the real bench dataset:
    # adaptive delivery must be bit-identical to the serialized epoch
    # order (dummy pool = the deterministic reference; multi-worker FIFO
    # delivers in completion order, so it is not the reference).
    ref_ids = epoch_sweep(SKEW_DATASET_URL, 'fifo', collect_ids=True,
                          reader_pool_type='dummy', workers_count=1)[1]
    if ref_ids != adaptive_ids:
        # in-leg assertion, like the transfer leg's bit-identity check:
        # the compact-line boolean alone gates nothing (trend tracks the
        # throughput fields), so an ordering regression must fail the
        # leg loudly, not ship as a quietly-false field
        raise AssertionError(
            'adaptive delivery order diverged from the serialized epoch '
            'order (%d vs %d rows)' % (len(adaptive_ids or ()),
                                       len(ref_ids or ())))
    # Uniform control: adaptive on equal-cost groups must be a wash.
    uniform = {'fifo': [], 'adaptive': []}
    for _ in range(2):
        uniform['fifo'].append(
            epoch_sweep(SKEW_UNIFORM_URL, 'fifo')[0])
        uniform['adaptive'].append(
            epoch_sweep(SKEW_UNIFORM_URL, 'adaptive')[0])
    uniform_ratio = (float(np.median(uniform['adaptive']))
                     / float(np.median(uniform['fifo']))
                     if np.median(uniform['fifo']) else None)
    return {
        'adaptive_sched_images_per_sec_fifo': round(med['fifo'], 1),
        'adaptive_sched_images_per_sec_adaptive':
            round(med['adaptive'], 1),
        'adaptive_sched_adaptive_over_fifo':
            round(med['adaptive'] / med['fifo'], 2) if med['fifo']
            else None,
        'adaptive_sched_uniform_over_fifo':
            round(uniform_ratio, 2) if uniform_ratio else None,
        # processing order moves, delivery order must not
        'adaptive_sched_delivery_identical': ref_ids == adaptive_ids,
    }


INGEST_DATASET_URL = 'file://' + BENCH_DIR + '/ingest_cold_jpeg_v1'
#: Every group is its own multi-MB cold-tier file (slow_every=1): the
#: object-store shape where EVERY first read pays the cold GET.
_INGEST_GROUPS = 16
_INGEST_WORKERS = 4


def object_store_ingest_leg(pairs=2):
    """Latency-hiding ingest plane (ISSUE 14): cold-epoch images/s of
    ``ingest='plane'`` vs the synchronous path on an all-cold dataset
    (every row group its own >1 MiB file) behind
    ``BandwidthLimitedFilesystem(cold_latency=1.2)`` — the emulated
    object store where every first read pays a cold GET.

    The synchronous path parallelizes cold latency only as wide as the
    decode pool (workers block in the GET); the plane parallelizes it
    across its fetch threads and overlaps it with decode, which is the
    whole latency-hiding claim — measured here, not asserted.

    Protocol: interleaved sync/plane pairs, one epoch each, medians;
    both variants run ``scheduling='adaptive'`` (epoch-order delivery,
    so the content digest below is order-exact) with a fixed seed and
    the same 4-worker pool.  Delivery is digest-asserted IN-LEG: sha1
    over every delivered row's id + decoded image bytes, sync vs plane
    — an ordering or content divergence fails the leg loudly rather
    than shipping as a quietly-false field."""
    import hashlib

    import fsspec

    from petastorm_tpu import make_reader
    from petastorm_tpu.test_util import BandwidthLimitedFilesystem
    from petastorm_tpu.transform import ResizeImages

    _ensure_skew_dataset(INGEST_DATASET_URL, _INGEST_GROUPS, 1)
    cold_fs = BandwidthLimitedFilesystem(fsspec.filesystem('file'),
                                         SKEW_COLD_BPS,
                                         cold_latency=SKEW_COLD_LATENCY_S)

    def epoch(ingest_mode, digest=False):
        sha = hashlib.sha1() if digest else None
        n = 0
        with make_reader(INGEST_DATASET_URL, filesystem=cold_fs,
                         schema_fields=['noun_id', 'image'],
                         workers_count=_INGEST_WORKERS, columnar_decode=True,
                         transform_spec=ResizeImages({'image': (224, 224)}),
                         shuffle_row_groups=True, seed=5, num_epochs=1,
                         scheduling='adaptive', ingest=ingest_mode,
                         ingest_window=_INGEST_GROUPS) as reader:
            t0 = time.monotonic()
            for batch in reader:
                n += len(batch.noun_id)
                if sha is not None:
                    sha.update(np.ascontiguousarray(batch.noun_id).tobytes())
                    sha.update(np.ascontiguousarray(batch.image).tobytes())
            elapsed = time.monotonic() - t0
            diag = reader.diagnostics
        return (n / elapsed, sha.hexdigest() if sha else None,
                int(diag.get('ingest_degraded', 0) or 0))

    epoch('off')  # warmup: page cache, pool spin-up
    rates = {'off': [], 'plane': []}
    digests = {}
    degraded = 0
    for i in range(max(1, int(pairs))):
        for mode in ('off', 'plane'):
            rate, digest, deg = epoch(mode, digest=(i == 0))
            rates[mode].append(rate)
            degraded += deg
            if i == 0:
                digests[mode] = digest
    if digests['off'] != digests['plane']:
        # in-leg assertion, like the transfer/adaptive legs: delivery
        # through the plane must be bit-identical (same epoch order,
        # same decoded bytes) to the synchronous path
        raise AssertionError(
            'ingest-plane delivery diverged from the synchronous path '
            '(%s vs %s)' % (digests['plane'], digests['off']))
    sync = float(np.median(rates['off']))
    plane = float(np.median(rates['plane']))
    return {
        'object_store_ingest_images_per_sec_sync': round(sync, 1),
        'object_store_ingest_images_per_sec_plane': round(plane, 1),
        'object_store_ingest_plane_over_sync':
            round(plane / sync, 2) if sync else None,
        'object_store_ingest_delivery_identical':
            digests['off'] == digests['plane'],
        'object_store_ingest_degraded': degraded,
    }


def provenance_overhead_leg(pairs=3, seconds=3.0):
    """Per-batch provenance plane (ISSUE 13): enabled-path cost on the
    ProcessPool host-plane leg — the path that pays the most (a record
    built + pickled per result message, a journal seal per batch).

    Protocol: interleaved on/off pairs (``PETASTORM_TPU_NO_PROVENANCE``
    toggled per variant, operator env restored), medians, same
    pre-decoded dataset and pool shape as the shm host-plane leg.
    ``provenance_overhead_pct`` = (off − on) / off × 100: positive means
    the enabled path is slower; the acceptance bar is ≤1%.  The field
    rides the compact line into BENCH_HISTORY like every other leg."""
    from petastorm_tpu import make_reader
    from petastorm_tpu.benchmark.hostplane import pump_host_batches
    from petastorm_tpu.jax import DataLoader

    ensure_raw_dataset()
    rates = {'on': [], 'off': []}
    for _ in range(max(1, int(pairs))):
        for label, forced in (('on', None), ('off', '1')):
            prev = os.environ.get('PETASTORM_TPU_NO_PROVENANCE')
            if forced is None:
                os.environ.pop('PETASTORM_TPU_NO_PROVENANCE', None)
            else:
                os.environ['PETASTORM_TPU_NO_PROVENANCE'] = forced
            try:
                with make_reader(RAW_DATASET_URL, num_epochs=None,
                                 reader_pool_type='process',
                                 workers_count=min(4, WORKERS),
                                 shuffle_row_groups=False,
                                 columnar_decode=True) as reader:
                    loader = DataLoader(reader, batch_size=BATCH,
                                        prefetch=2)
                    rows, dt = pump_host_batches(loader, seconds,
                                                 warmup_batches=2)
                rates[label].append(rows / dt)
            finally:
                if prev is not None:
                    os.environ['PETASTORM_TPU_NO_PROVENANCE'] = prev
                else:
                    os.environ.pop('PETASTORM_TPU_NO_PROVENANCE', None)
    on = float(np.median(rates['on']))
    off = float(np.median(rates['off']))
    return {
        'provenance_images_per_sec_on': round(on, 1),
        'provenance_images_per_sec_off': round(off, 1),
        'provenance_overhead_pct':
            round(100.0 * (off - on) / off, 2) if off else None,
    }


def multi_tenant_leg(pairs=2):
    """Multi-tenant serving tier (ISSUE 16): two tenants with weights
    1:3 sharing one 2-worker fleet over the JPEG dataset, against a
    cluster cache plane warmed by a prior single-tenant epoch.

    Passes per pair (fresh dispatcher each, medians reported):

    * ``warm_solo``: the default tenant alone on the warm plane — the
      warm-fleet throughput reference;
    * ``duo``: the default tenant (weight 1) plus a registered ``burst``
      tenant (weight 3) consuming the SAME dataset concurrently on the
      warm plane — the co-tenant compounding evidence;
    * ``fair``: the same 1:3 pair, but cache plane OFF and decode-bound
      — the only regime where the WDRR grant share is visible in row
      rates (on a warm plane each stream is capped by its own consumer,
      not the contended fleet, and every ratio reads ~1).  The
      fair-share ratio is burst-rows over default-rows inside the
      window where BOTH streams were active (outside it the survivor
      takes the whole fleet and the ratio means nothing); the WDRR
      target is the weight ratio 3.0, trend-gated within the usual
      noise band.

    Correctness is asserted in-leg, not reported-and-ignored: every
    stream must deliver exactly-once (sorted ids == the full dataset)
    and bit-identical content (order-independent DeliveryDigest equal to
    the cold direct-serve reference).  Co-tenant compounding (the
    acceptance criterion: a second tenant on an already-decoded dataset
    rides the cluster cache instead of re-decoding) shows up as
    ``multi_tenant_remote_hits`` > 0 and the duo's combined rate
    relative to warm-solo."""
    import threading

    from petastorm_tpu.service import (Dispatcher, ServiceConfig,
                                       ServiceDataLoader, Worker)
    from petastorm_tpu.service.client import register_tenant_job
    from petastorm_tpu.test_util.chaos import DeliveryDigest

    ensure_dataset()
    plane = os.path.join(BENCH_DIR, 'multi_tenant_v1', 'plane')
    _wipe_plane(plane)
    # One rowgroup per split = 12 grants per tenant epoch: enough lease
    # granularity that the 3:1 WDRR share is measurable, not quantized.
    fair_kwargs = dict(dataset_url=DATASET_URL, num_consumers=1,
                       rowgroups_per_split=1, lease_ttl_s=30.0,
                       reader_kwargs={'workers_count': 1})
    job_kwargs = dict(fair_kwargs, cache_plane=True,
                      cache_plane_dir=plane)

    def fleet_pass(tenants, kwargs):
        """``tenants``: [(tenant_or_None, weight), ...] consumed
        concurrently against a fresh dispatcher built from ``kwargs``
        (co-tenant jobs register the same kwargs); returns
        (streams, worker_diags)."""
        config = ServiceConfig(**kwargs)
        streams = [{'tenant': t, 'weight': w, 'deliveries': [],
                    'ids': [], 'digest': None, 'error': None}
                   for t, w in tenants]

        def consume(stream):
            try:
                digest = DeliveryDigest()
                loader = ServiceDataLoader(
                    addr, batch_size=BATCH, consumer=0, drop_last=False,
                    prefetch=2, tenant=stream['tenant'])
                with loader:
                    for batch in loader.iter_host_batches():
                        digest.update(batch)
                        stream['deliveries'].append(
                            (time.monotonic(), len(batch['noun_id'])))
                        stream['ids'].extend(
                            np.asarray(batch['noun_id']).tolist())
                stream['digest'] = digest.hexdigest()
            except Exception as e:  # noqa: BLE001 — re-raised below
                stream['error'] = e

        with Dispatcher(config) as dispatcher:
            addr = dispatcher.addr
            workers = [Worker(addr).start() for _ in range(2)]
            try:
                for stream in streams:
                    if stream['tenant'] is not None:
                        register_tenant_job(addr, stream['tenant'],
                                            kwargs,
                                            weight=stream['weight'])
                threads = [threading.Thread(target=consume, args=(s,),
                                            daemon=True) for s in streams]
                for t in threads:
                    t.start()
                deadline = time.monotonic() + 600.0
                for t in threads:
                    t.join(max(1.0, deadline - time.monotonic()))
                    if t.is_alive():
                        raise RuntimeError('multi-tenant leg: consumer '
                                           'wedged')
                for stream in streams:
                    if stream['error'] is not None:
                        raise stream['error']
                diags = [w.diagnostics for w in workers]
            finally:
                for w in workers:
                    w.stop()
                for w in workers:
                    w.join()
        return streams, diags

    def check_stream(stream, ref_digest):
        tag = stream['tenant'] or 'default'
        if sorted(stream['ids']) != list(range(NUM_IMAGES)):
            raise AssertionError(
                'multi-tenant leg: tenant %r delivery was not '
                'exactly-once (%d rows)' % (tag, len(stream['ids'])))
        if ref_digest is not None and stream['digest'] != ref_digest:
            raise AssertionError(
                'multi-tenant leg: tenant %r content diverged from the '
                'reference (%s vs %s)' % (tag, stream['digest'],
                                          ref_digest))

    def solo_rate(stream):
        deliveries = stream['deliveries']
        if len(deliveries) < 2:
            return 0.0
        t0, t_end = deliveries[0][0], deliveries[-1][0]
        rows = sum(n for _, n in deliveries[1:])
        return rows / (t_end - t0) if t_end > t0 else 0.0

    def window_ratio(default, burst):
        """Burst-over-default rows inside the both-streams-active
        window."""
        start = max(s['deliveries'][0][0] for s in (default, burst))
        end = min(s['deliveries'][-1][0] for s in (default, burst))
        in_window = [sum(n for t, n in s['deliveries']
                         if start < t <= end) for s in (default, burst)]
        return (in_window[1] / in_window[0]) if in_window[0] else None

    # Untimed cold pass: decodes the epoch into the plane AND supplies
    # the content reference every later stream must match.
    (ref,), _ = fleet_pass([(None, 1.0)], job_kwargs)
    check_stream(ref, None)
    ref_digest = ref['digest']

    rates = {'warm_solo': [], 'duo': []}
    ratios = []
    remote_hits = 0
    for _ in range(max(1, int(pairs))):
        (solo,), _ = fleet_pass([(None, 1.0)], job_kwargs)
        check_stream(solo, ref_digest)
        rates['warm_solo'].append(solo_rate(solo))

        streams, diags = fleet_pass([(None, 1.0), ('burst', 3.0)],
                                    job_kwargs)
        for stream in streams:
            check_stream(stream, ref_digest)
        remote_hits += sum(d['cache_remote_hits'] for d in diags)
        merged = sorted(t for s in streams for t, _ in s['deliveries'])
        total = sum(n for s in streams for _, n in s['deliveries'])
        rates['duo'].append(total / (merged[-1] - merged[0])
                            if merged[-1] > merged[0] else 0.0)

        streams, _ = fleet_pass([(None, 1.0), ('burst', 3.0)],
                                fair_kwargs)
        for stream in streams:
            check_stream(stream, ref_digest)
        ratios.append(window_ratio(*streams))

    med = {k: float(np.median(v)) for k, v in rates.items()}
    measured = [r for r in ratios if r is not None]
    ratio = float(np.median(measured)) if measured else None
    return {
        'multi_tenant_images_per_sec_warm_solo':
            round(med['warm_solo'], 1),
        'multi_tenant_images_per_sec_duo': round(med['duo'], 1),
        'multi_tenant_fair_share_ratio':
            round(ratio, 2) if ratio is not None else None,
        'multi_tenant_duo_over_warm_solo':
            round(med['duo'] / med['warm_solo'], 2)
            if med['warm_solo'] else None,
        'multi_tenant_remote_hits': remote_hits,
        'multi_tenant_exactly_once': True,
    }


def device_residency_leg(pairs=2):
    """Device-resident data plane (``petastorm_tpu/jax/residency``),
    CPU-emulated: epoch 0 streams through the dispatch ring and admits
    every batch into the compressed-in-HBM tier; epoch 1 serves warm from
    the tier's jitted gather+widen.  Asserts in-leg that the warm epoch
    fetched **zero** host batches and that its delivery digest is
    bit-identical to a residency-off streamed epoch under the same
    ``(seed, epoch)`` shuffle key (the dataset is uint8+int, so 'auto'
    narrowing is exact and the kill-switch run is a valid reference; both
    runs content-sort their caches via ``deterministic_cache_order`` so
    the permutation indexes the same row order despite thread-pool read
    order).  Cold/warm come from the same pass (interleaved by
    construction); ``pairs`` independent passes give medians."""
    import hashlib

    from petastorm_tpu import make_reader
    from petastorm_tpu.jax import ResidentDataLoader, residency

    ensure_dataset()
    steps = max(1, NUM_IMAGES // BATCH)

    def digest_of(batches):
        h = hashlib.blake2b(digest_size=16)
        for batch in batches:
            for key in sorted(batch):
                h.update(np.ascontiguousarray(batch[key]).tobytes())
        return h.hexdigest()

    def run_pass():
        """One 2-epoch pass; returns (cold_s, warm_s, warm_digest,
        warm_host_batches, warm_hits)."""
        with make_reader(DATASET_URL, num_epochs=1, workers_count=WORKERS,
                         shuffle_row_groups=False,
                         columnar_decode=True) as reader:
            with ResidentDataLoader(reader, batch_size=BATCH, num_epochs=2,
                                    seed=0, wire_dtypes='auto', prefetch=2,
                                    deterministic_cache_order=True) as loader:
                it = iter(loader)

                def pull():
                    return {k: np.asarray(v) for k, v in next(it).items()}

                t0 = time.monotonic()
                for _ in range(steps):
                    pull()
                cold_s = time.monotonic() - t0
                before = loader.residency_stats
                warm = []
                t0 = time.monotonic()
                for _ in range(steps):
                    warm.append(pull())
                warm_s = time.monotonic() - t0
                after = loader.residency_stats
                return (cold_s, warm_s, digest_of(warm),
                        after['host_batches'] - before['host_batches'],
                        after['hits'] - before['hits'])

    colds, warms = [], []
    warm_digest = warm_host = warm_hits = None
    for _ in range(max(1, int(pairs))):
        cold_s, warm_s, warm_digest, warm_host, warm_hits = run_pass()
        colds.append(cold_s)
        warms.append(warm_s)

    # Reference: the identical schedule with the plane killed — epoch 1
    # streams full-width, deriving the SAME (seed, epoch)=(0, 1) order.
    os.environ[residency.KILL_SWITCH] = '1'
    try:
        with make_reader(DATASET_URL, num_epochs=1, workers_count=WORKERS,
                         shuffle_row_groups=False,
                         columnar_decode=True) as reader:
            with ResidentDataLoader(reader, batch_size=BATCH, num_epochs=2,
                                    seed=0, wire_dtypes='auto', prefetch=2,
                                    deterministic_cache_order=True) as loader:
                it = iter(loader)
                for _ in range(steps):
                    next(it)
                ref = [{k: np.asarray(v) for k, v in next(it).items()}
                       for _ in range(steps)]
    finally:
        os.environ.pop(residency.KILL_SWITCH, None)
    bit_identical = digest_of(ref) == warm_digest

    if warm_host != 0:
        raise AssertionError('warm resident epoch fetched %d host batches '
                             '(expected 0; hits=%r)' % (warm_host, warm_hits))
    if not bit_identical:
        raise AssertionError('warm resident epoch digest differs from the '
                             'residency-off streamed epoch under the same '
                             '(seed, epoch) key')
    cold = float(np.median(colds))
    warm = float(np.median(warms))
    return {
        'device_residency_images_per_sec_cold':
            round(steps * BATCH / cold, 1) if cold else None,
        'device_residency_images_per_sec_warm':
            round(steps * BATCH / warm, 1) if warm else None,
        'device_residency_warm_over_cold':
            round(cold / warm, 2) if warm else None,
        'device_residency_host_batches_warm': int(warm_host),
        'device_residency_bit_identical': bool(bit_identical),
    }


#: Host-only IPC/transfer-plane legs (the shm result plane's and the
#: transfer plane's evidence sets).
_IPC_PLANE_LEGS = (
    ('ipc', ipc_microbench),
    ('processpool_plane', processpool_host_plane_leg),
    ('delivery_plane_service', delivery_plane_service_leg),
    ('epoch_cache_plane', epoch_cache_plane_leg),
    ('first_epoch_warm', first_epoch_warm_leg),
    ('cluster_cache', cluster_cache_leg),
    ('transfer_plane', transfer_plane_leg),
    ('adaptive_sched', adaptive_sched_leg),
    ('object_store_ingest', object_store_ingest_leg),
    ('provenance_overhead', provenance_overhead_leg),
    ('control_plane_recovery', control_plane_recovery_leg),
    ('multi_tenant', multi_tenant_leg),
    ('device_residency', device_residency_leg),
)


def dlrm_host_plane_leg(seconds=6.0):
    """Host-boundary DLRM delivery (no device in the loop): the criteo
    columnar plane (``make_batch_reader`` -> 39-column stack) consumed at
    ``iter_host_batches`` — BASELINE config #4's analog of
    ``delivery_plane_images_per_sec_host``."""
    from petastorm_tpu import make_batch_reader
    from petastorm_tpu.benchmark.hostplane import pump_host_batches
    from petastorm_tpu.jax import DataLoader

    ensure_criteo_dataset()
    with make_batch_reader(CRITEO_URL, num_epochs=None,
                           workers_count=WORKERS,
                           shuffle_row_groups=False) as reader:
        loader = DataLoader(reader, batch_size=DLRM_BATCH, prefetch=2,
                            transform_fn=_dlrm_pack_columns)
        rows, dt = pump_host_batches(loader, seconds, warmup_batches=1)
    return {'dlrm_host_rows_per_s': round(rows / dt)}


def dlrm_stall_leg():
    """Criteo->DLRM stall: a gather-bound step (26 vocab-100k embedding
    tables + small MLPs — memory traffic, not MXU FLOPs) consuming the
    columnar plane (``make_batch_reader`` -> ``DataLoader(transform_fn=)``),
    per-step and fused.  The regime the ResNet legs can't show: tiny
    device step, wide rows, host work = pure column stacking."""
    import jax
    import jax.numpy as jnp
    import optax

    from petastorm_tpu import make_batch_reader
    from petastorm_tpu.jax import DataLoader
    from petastorm_tpu.models.dlrm import DLRM

    ensure_criteo_dataset()
    model = DLRM(vocab_sizes=(DLRM_VOCAB,) * DLRM_CAT, embedding_dim=16,
                 bottom_mlp=(64, 16), top_mlp=(64, 1), dtype=jnp.float32)
    rng = jax.random.PRNGKey(0)
    params = model.init(rng, jnp.zeros((1, DLRM_DENSE)),
                        jnp.zeros((1, DLRM_CAT), jnp.int32))['params']
    tx = optax.adagrad(0.01)  # the canonical DLRM optimizer
    opt_state = tx.init(params)

    @jax.jit
    def train_step(params, opt_state, batch):
        def loss_fn(p):
            # model output is already (B,) — see models/dlrm.py __call__
            logits = model.apply({'params': p}, batch['dense'], batch['cat'])
            return optax.sigmoid_binary_cross_entropy(
                logits, batch['clicked']).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, new_opt = tx.update(grads, opt_state)
        return optax.apply_updates(params, updates), new_opt, loss

    # Device floor: the same step chained on one resident batch.
    gen = np.random.default_rng(2)
    resident = jax.device_put({
        'dense': gen.standard_normal((DLRM_BATCH, DLRM_DENSE))
                    .astype(np.float32),
        'cat': gen.integers(0, DLRM_VOCAB, (DLRM_BATCH, DLRM_CAT))
                  .astype(np.int32),
        'clicked': (gen.random(DLRM_BATCH) < 0.03).astype(np.float32),
    })
    floor_steps = 48
    p, o, loss = params, opt_state, None
    for i in range(floor_steps + 8):
        p, o, loss = train_step(p, o, resident)
        if i == 7:
            float(loss)  # compile + pipeline fill drained; open the timer
            t0 = time.monotonic()
    float(loss)
    floor_ms = 1000.0 * (time.monotonic() - t0) / floor_steps

    steps_per_epoch = DLRM_ROWS // DLRM_BATCH
    if steps_per_epoch == 0:
        raise ValueError('DLRM_ROWS=%d < DLRM_BATCH=%d: no full batch per '
                         'epoch (drop_last) — raise rows or lower batch'
                         % (DLRM_ROWS, DLRM_BATCH))
    max_steps = 2 * steps_per_epoch

    def run(fused):
        warmup = 2
        epochs = -(-(max_steps + warmup + 1) // steps_per_epoch)
        with make_batch_reader(CRITEO_URL, num_epochs=epochs,
                               workers_count=WORKERS,
                               shuffle_row_groups=False) as reader:
            loader = DataLoader(reader, batch_size=DLRM_BATCH, prefetch=2,
                                transform_fn=_dlrm_pack_columns)
            if fused:
                def scan_step(carry, batch):
                    p, o = carry
                    p, o, loss = train_step(p, o, batch)
                    return (p, o), loss
                gen = loader.scan_batches(scan_step, (params, opt_state),
                                          steps_per_call=8,
                                          donate_carry=False)
                t0 = None
                steps = 0
                for _, outs in gen:
                    if t0 is None:
                        float(np.asarray(outs).ravel()[-1])  # compile+fill
                        t0 = time.monotonic()
                        continue
                    steps += int(outs.shape[0])
                    if steps >= max_steps:
                        break
                # Guard BEFORE touching outs/loss: a too-short stream must
                # say so, not die UnboundLocalError below.
                assert t0 is not None and steps > 0, 'criteo stream too short'
                final = np.asarray(outs)
            else:
                p, o, loss = params, opt_state, None
                t0 = None
                steps = -warmup
                for batch in loader:
                    p, o, loss = train_step(p, o, batch)
                    steps += 1
                    if steps == 0:
                        float(loss)
                        t0 = time.monotonic()
                    if steps >= max_steps:
                        break
                assert t0 is not None and steps > 0, 'criteo stream too short'
                final = np.asarray(float(loss))
            assert np.isfinite(final).all(), 'non-finite DLRM loss'
            wall_ms = 1000.0 * (time.monotonic() - t0) / steps
            return max(0.0, 100.0 * (wall_ms - floor_ms) / wall_ms), wall_ms

    stall, wall_ms = run(fused=False)
    scan_stall, scan_ms = run(fused=True)
    best_ms = min(wall_ms, scan_ms)
    return {
        'stall_pct_dlrm': round(stall, 2),
        'stall_pct_dlrm_scan': round(scan_stall, 2),
        'dlrm_step_ms_floor': round(floor_ms, 2),
        'dlrm_rows_per_s': round(DLRM_BATCH / (best_ms / 1000.0)),
        'dlrm_config': '%dx dense, %dx cat vocab=%d emb=16, batch=%d '
                       '(make_batch_reader columnar plane)'
                       % (DLRM_DENSE, DLRM_CAT, DLRM_VOCAB, DLRM_BATCH),
    }


def _model_flops_per_step(state):
    """Exact per-step FLOPs from XLA's own cost model — the absolute anchor
    for stall% (a slow device floor would otherwise flatter the loader)."""
    train_step, params, batch_stats, opt_state = state
    x = np.zeros((BATCH, IMAGE_HW[0], IMAGE_HW[1], 3), np.uint8)
    y = np.zeros((BATCH,), np.int64)
    compiled = train_step.lower(params, batch_stats, opt_state, x, y).compile()
    return float(compiled.cost_analysis()['flops'])


def kernel_certification():
    """Certify the attention kernels on THIS backend, numbers into the JSON.

    Flash (fwd+bwd, dense and packed) runs the real Mosaic kernels on TPU
    (the Pallas interpreter elsewhere); ring/Ulysses run their shard_map
    wrappers over the full device mesh.  All compared against the dense
    oracle at highest matmul precision — CI runs the same asserts
    (tests/test_flash_attention.py), but only a driver-visible on-chip run
    proves the Mosaic lowering (block alignment etc.) every round.
    """
    import jax
    import jax.numpy as jnp

    from petastorm_tpu.ops import flash_attention
    from petastorm_tpu.parallel import full_attention, make_mesh
    from petastorm_tpu.parallel.ring_attention import (make_ring_attention,
                                                       make_ulysses_attention)

    errs = {}
    prev = jax.config.jax_default_matmul_precision
    jax.config.update('jax_default_matmul_precision', 'highest')
    try:
        rng = np.random.default_rng(0)
        b, s, h, d = 2, 256, 2, 64
        q, k, v = (jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
                   for _ in range(3))
        dout = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)

        def max_err(a, b_):
            return float(jnp.max(jnp.abs(a - b_)))

        want = full_attention(q, k, v, causal=True)
        errs['flash_fwd'] = max_err(flash_attention(q, k, v, causal=True),
                                    want)
        g_want = jax.grad(
            lambda t: (full_attention(*t, causal=True) * dout).sum())((q, k, v))
        g_got = jax.grad(
            lambda t: (flash_attention(*t, causal=True) * dout).sum())((q, k, v))
        errs['flash_bwd'] = max(max_err(a, w) for a, w in zip(g_got, g_want))

        seg = jnp.asarray(
            np.repeat([1, 2], s // 2)[None, :].repeat(b, 0), jnp.int32)
        want_p = full_attention(q, k, v, causal=True, segment_ids=seg)
        errs['flash_packed_fwd'] = max_err(
            flash_attention(q, k, v, causal=True, segment_ids=seg), want_p)
        gp_want = jax.grad(lambda t: (full_attention(
            *t, causal=True, segment_ids=seg) * dout).sum())((q, k, v))
        gp_got = jax.grad(lambda t: (flash_attention(
            *t, causal=True, segment_ids=seg) * dout).sum())((q, k, v))
        errs['flash_packed_bwd'] = max(
            max_err(a, w) for a, w in zip(gp_got, gp_want))

        n_dev = len(jax.devices())
        mesh = make_mesh({'data': 1, 'seq': n_dev})
        ring_fn, _ = make_ring_attention(mesh, causal=True)
        errs['ring_fwd'] = max_err(ring_fn(q, k, v), want)
        ulys_fn, _ = make_ulysses_attention(mesh, causal=True)
        errs['ulysses_fwd'] = max_err(ulys_fn(q, k, v), want)
    finally:
        jax.config.update('jax_default_matmul_precision', prev)
    return {name: round(e, 8) for name, e in errs.items()}


_COMPACT_KEYS = (
    'metric', 'value', 'unit', 'value_spread', 'value_iqr', 'runs',
    'vs_baseline', 'vs_baseline_range',
    'backend', 'stall_pct', 'stall_pct_source', 'stall_regime',
    'stall_pct_hbm_cached', 'stall_pct_hbm_scan', 'stall_pct_streaming',
    'stall_pct_streaming_scan', 'stall_pct_delivery_bound',
    'stall_pct_decoded_cache', 'stall_pct_decoded_cache_scan',
    'stall_pct_dlrm', 'stall_pct_dlrm_scan', 'dlrm_rows_per_s',
    'dlrm_host_rows_per_s',
    'streaming_scan_floor_stall_pct', 'transport_bound', 'device_step_ms',
    'step_dtype', 'model_tflops_per_s', 'device_peak_tflops_bf16',
    'mfu_pct', 'delivery_plane_images_per_sec_host',
    'delivery_plane_processpool_images_per_sec_host_shm',
    'delivery_plane_processpool_images_per_sec_host_bytes',
    'delivery_plane_service_images_per_sec_host_w1',
    'delivery_plane_service_images_per_sec_host_w1_bytes',
    'delivery_plane_service_images_per_sec_host_w2',
    'delivery_plane_service_images_per_sec_host_w4',
    'epoch_cache_streaming_cold_images_per_sec',
    'epoch_cache_streaming_warm_images_per_sec',
    'epoch_cache_streaming_warm_over_cold',
    'epoch_cache_service_cold_images_per_sec',
    'epoch_cache_service_warm_images_per_sec',
    'epoch_cache_service_warm_over_cold',
    'stall_pct_epoch_cache_warm_scan',
    'first_epoch_cold_images_per_sec',
    'first_epoch_warm_images_per_sec',
    'first_epoch_warm_over_cold',
    'first_epoch_warm_decodes',
    'first_epoch_materialize_s',
    'first_epoch_wire_entries',
    'first_epoch_digest_identical',
    'cluster_cache_images_per_sec_cold_join',
    'cluster_cache_images_per_sec_cold_fleet',
    'cluster_cache_images_per_sec_warm',
    'cluster_cache_warm_over_cold_join',
    'cluster_cache_warm_over_cold_fleet',
    'cluster_cache_remote_hits',
    'cluster_cache_peer_fills',
    'cluster_cache_peer_degraded',
    'cluster_cache_bit_identical',
    'stall_top_component',
    'transfer_plane_images_per_sec_inline',
    'transfer_plane_images_per_sec_coalesced',
    'transfer_plane_images_per_sec_narrowed',
    'transfer_plane_coalesced_over_inline',
    'transfer_plane_narrowed_over_inline',
    'transfer_plane_wire_bytes_ratio',
    'transfer_plane_bit_identical',
    'adaptive_sched_images_per_sec_fifo',
    'adaptive_sched_images_per_sec_adaptive',
    'adaptive_sched_adaptive_over_fifo',
    'adaptive_sched_uniform_over_fifo',
    'adaptive_sched_delivery_identical',
    'object_store_ingest_images_per_sec_sync',
    'object_store_ingest_images_per_sec_plane',
    'object_store_ingest_plane_over_sync',
    'object_store_ingest_delivery_identical',
    'object_store_ingest_degraded',
    'provenance_images_per_sec_on',
    'provenance_images_per_sec_off',
    'provenance_overhead_pct',
    'control_plane_ttfb_cold_s',
    'control_plane_ttfb_restored_s',
    'control_plane_recovery_speedup',
    'control_plane_exactly_once',
    'multi_tenant_images_per_sec_warm_solo',
    'multi_tenant_images_per_sec_duo',
    'multi_tenant_fair_share_ratio',
    'multi_tenant_duo_over_warm_solo',
    'multi_tenant_remote_hits',
    'multi_tenant_exactly_once',
    'device_residency_images_per_sec_cold',
    'device_residency_images_per_sec_warm',
    'device_residency_warm_over_cold',
    'device_residency_host_batches_warm',
    'device_residency_bit_identical',
    'ipc_bytes_per_s', 'h2d_bytes_per_s',
    'kernel_backend', 'kernel_max_err',
    'legs_failed', 'throughput_error', 'error',
)


_DETAIL_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            'BENCH_DETAIL_LAST.json')


#: Honest labeling of the headline: on a 1-core shared host the whole-epoch
#: img/s number swings with transient load even at 9 repeats; the host-plane
#: field is the stable perf statement (no device in the loop, bandwidth-
#: bound).  vs_baseline should be read with its IQR range beside it.
_VALUE_NOTE = (
    'value = median of `runs` interleaved whole-epoch measurements; NOISY '
    'on shared 1-core hosts (see value_iqr / runs_raw). '
    'delivery_plane_images_per_sec_host is the stable host-pipeline number '
    '(bandwidth-bound, no device transfer in the loop); read vs_baseline '
    'with vs_baseline_range ([q25, q75] of pairwise ratios).')


def _emit(result):
    """Two JSON lines + a detail file.

    The FULL result (prose notes, diagnoses, kernel table) goes to
    ``BENCH_DETAIL_LAST.json`` and an early stdout line; the FINAL stdout
    line is a COMPACT numbers-only subset, small enough that a tail
    capture of the output always holds it whole."""
    try:
        with open(_DETAIL_PATH, 'w') as f:
            json.dump(result, f, indent=1, sort_keys=True)
    except OSError:
        pass
    print(json.dumps(result), flush=True)
    compact = {k: result[k] for k in _COMPACT_KEYS
               if result.get(k) is not None}
    print(json.dumps(compact), flush=True)
    # Perf-trend store (ISSUE 7): every clean completed run appends its
    # compact line to BENCH_HISTORY.jsonl so `trend.py --check` can gate
    # future rounds against the recorded trajectory.  AFTER the machine
    # line — the line is the artifact, the history is memory; degraded
    # runs (error keys set) are skipped inside append_entry.
    try:
        from petastorm_tpu.benchmark import trend
        trend.append_entry(compact)
    except Exception:  # noqa: BLE001 — history must never cost the line
        pass


def _certify_into(result, backend_label):
    """Run kernel certification into ``result`` — or record WHY not.

    Certification compiles ~8 more executables; only start it with the
    watchdog budget to finish."""
    if _budget_left_s() < 420:
        result['kernel_cert_error'] = (
            'skipped: %.0fs of watchdog budget left (certs need ~7 min '
            'of compiles)' % _budget_left_s())
        return
    try:
        result['kernel_max_err'] = kernel_certification()
        result['kernel_backend'] = backend_label
    except Exception as e:  # noqa: BLE001 — recorded; main() exits non-zero
        result['kernel_cert_error'] = '%s: %s' % (type(e).__name__,
                                                  str(e)[:160])


def _start_watchdog(budget_s):
    """Print a diagnostic JSON line and hard-exit (code 3) if the run
    outlives its budget: a bench that never prints is worse than one that
    reports what it measured and where it stopped."""
    import faulthandler
    import threading

    def fire():
        # Everything measured before the deadline still ships: merge the
        # compact subset of the partial leg results into the error line.
        # The throughput phase stashes into _PARTIAL_BASE the moment its
        # medians exist.
        #
        # This runs on the timer THREAD while the main thread may still be
        # mutating _PARTIAL (budget expiring on a slow-but-alive leg), so
        # every step is contained: a failed snapshot/serialization must
        # still print SOMETHING and must still os._exit — a dead handler
        # on a hung run would mean no artifact and no exit at all.
        err = ('watchdog: run exceeded %ds; stacks on stderr; stall fields '
               'above are the legs that completed' % budget_s)
        backend = _PARTIAL_BASE.get('backend')
        try:
            try:
                merged = dict(_PARTIAL_BASE)
                merged.update(_PARTIAL)
            except RuntimeError:  # dict resized mid-copy by the main thread
                merged = {}
                for src in (_PARTIAL_BASE, _PARTIAL):
                    for k in list(src):
                        try:
                            merged[k] = src[k]
                        except KeyError:
                            pass
            partial = {k: merged[k] for k in _COMPACT_KEYS
                       if merged.get(k) is not None}
            partial.setdefault('value', 0.0)
            partial.setdefault('vs_baseline', 0.0)
            for k in ('value', 'vs_baseline'):
                # The machine line CONTRACTS these as numbers; a stray
                # non-numeric (half-built state mid-run) must not ship.
                if not isinstance(partial[k], (int, float)) \
                        or isinstance(partial[k], bool):
                    partial[k] = 0.0
            partial.update({
                'metric': 'imagenet_jpeg_parquet_images_per_sec_host',
                'unit': 'images/s',
                'backend': backend,
                'error': err,
            })
            print(json.dumps(partial, default=str), flush=True)
            # The detail file must reflect THIS run too — otherwise a
            # cut run leaves the previous run's detail on disk, silently
            # stale.  AFTER the compact line: the line is the artifact.
            try:
                with open(_DETAIL_PATH, 'w') as f:
                    json.dump(dict(merged, **partial), f, indent=1,
                              sort_keys=True, default=str)
            except Exception:  # noqa: BLE001 — detail is best-effort
                pass
        except Exception:  # noqa: BLE001 — minimal line beats no line
            print(json.dumps({
                'metric': 'imagenet_jpeg_parquet_images_per_sec_host',
                'value': 0.0, 'unit': 'images/s', 'vs_baseline': 0.0,
                'backend': backend,
                'error': err + ' (partial assembly failed)',
            }), flush=True)
        finally:
            # The stacks are the only diagnostic of WHERE the run stopped —
            # they must ship with the minimal line too (the line promises
            # them).
            try:
                faulthandler.dump_traceback(file=sys.stderr)
            except Exception:  # noqa: BLE001
                pass
            os._exit(3)

    global _T0, _BUDGET_S
    _T0 = time.monotonic()
    _BUDGET_S = budget_s
    timer = threading.Timer(budget_s, fire)
    timer.daemon = True
    timer.start()
    return timer


def _require_device():
    """Initialize the backend in THIS process and return its platform.

    The numbers this script prints are device numbers.  Without a TPU it
    fails — unless ``JAX_PLATFORMS=cpu`` was asked for (a rehearsal of the
    control flow), in which case every line it prints says ``cpu``."""
    from petastorm_tpu.utils import ensure_jax_backend
    platform = ensure_jax_backend()[0].platform
    asked = os.environ.get('JAX_PLATFORMS', '').strip().lower()
    if platform != 'tpu' and not (platform == 'cpu' and asked == 'cpu'):
        sys.exit('bench: no TPU (jax found platform %r); set JAX_PLATFORMS=cpu '
                 'to rehearse on the CPU under a cpu label' % platform)
    return platform


def main():
    platform = _require_device()
    _PARTIAL_BASE['backend'] = platform
    from petastorm_tpu.utils import enable_compile_cache
    enable_compile_cache()
    # 2400s: floor + streaming + streaming_scan + delivery-bound +
    # disk-cache build/serve/scan + HBM-cached/scan + 6-kernel
    # certification compile ~10 executables when the compile cache is cold.
    watchdog = _start_watchdog(
        int(os.environ.get('PETASTORM_TPU_BENCH_BUDGET_S', '2400')))
    ensure_dataset()
    import jax
    jax.jit(lambda x: x + 1)(np.zeros(8))  # backend warmup outside timing

    # Interleaved repeats: single-host timings are noisy (shared cores);
    # alternating runs equalizes page-cache and pool warmth.  The reported
    # value is the MEDIAN of 9 repeats (sub-second epochs on this dataset
    # size make extra repeats nearly free) with the IQR beside it, and
    # vs_baseline is the median of PAIRWISE ratios (each ratio compares two
    # adjacent runs under the same transient host conditions) with its own
    # IQR range.  A failure here is recorded, the stall legs still run, and
    # the run exits non-zero.
    repeats = int(os.environ.get('PETASTORM_TPU_BENCH_REPEATS', '9'))
    ours_runs, theirs_runs = [], []
    throughput_error = None
    try:
        tpu_native_epoch()           # warmup (page cache, pools)
        reference_strategy_epoch()   # warm the reference path identically
        for _ in range(repeats):
            ours_runs.append(tpu_native_epoch())
            theirs_runs.append(reference_strategy_epoch())
    except Exception as e:  # noqa: BLE001 — keep whatever runs completed
        throughput_error = '%s: %s' % (type(e).__name__, str(e)[:160])
        sys.stderr.write('bench: throughput phase failed: %s\n'
                         % throughput_error)
    pairs = list(zip(ours_runs, theirs_runs))
    ratios = [o / t for o, t in pairs]
    ours = float(np.median(ours_runs)) if ours_runs else 0.0
    theirs = float(np.median(theirs_runs)) if theirs_runs else 0.0
    ratio = float(np.median(ratios)) if ratios else 0.0
    spread = (max(ours_runs) - min(ours_runs)) if ours_runs else 0.0
    iqr = (float(np.subtract(*np.percentile(ours_runs, [75, 25])))
           if ours_runs else 0.0)
    ratio_range = ([round(float(r), 2)
                    for r in np.percentile(ratios, [25, 75])]
                   if ratios else None)
    # Stash NOW: a watchdog partial fired during the train legs must still
    # carry the (already measured) throughput phase.
    _PARTIAL_BASE.update({
        'value': round(ours, 1), 'value_spread': round(spread, 1),
        'value_iqr': round(iqr, 1), 'runs': repeats,
        'vs_baseline': round(ratio, 2), 'vs_baseline_range': ratio_range,
        'throughput_error': throughput_error,
    })

    try:
        stall = train_stall_legs()
    except Exception as e:  # noqa: BLE001 — e.g. the device floor failed
        stall = dict(_PARTIAL)
        stall.setdefault('leg_errors', {})['train_legs'] = \
            '%s: %s' % (type(e).__name__, str(e)[:160])
        stall['legs_failed'] = sorted(stall['leg_errors'])
        sys.stderr.write('bench: train legs aborted: %s\n'
                         % stall['leg_errors']['train_legs'])

    result = {
        'metric': 'imagenet_jpeg_parquet_images_per_sec_host',
        'value': round(ours, 1),
        'unit': 'images/s',
        'value_spread': round(spread, 1),
        'value_iqr': round(iqr, 1),
        'runs': repeats,
        'runs_raw': [round(r, 1) for r in ours_runs],
        'baseline_runs_raw': [round(r, 1) for r in theirs_runs],
        'vs_baseline': round(ratio, 2),
        'vs_baseline_range': ratio_range,
        'value_note': _VALUE_NOTE,
        'throughput_error': throughput_error,
        'host_cores': os.cpu_count(),
        'backend': platform,
        'baseline': 'same dataset+hardware via reference delivery strategy: '
                    'per-row cv2 decode (native plane disabled), per-row '
                    'python collate, sync device_put, no prefetch '
                    '(%.1f images/s median)' % theirs,
        'stall_note': 'stall_pct = the regime stall_regime names, from the '
                      'leg stall_pct_source names (the better of the two '
                      'drivers when both apply); stall_pct_hbm_cached = HBM '
                      'epoch cache, per-step iterator (DeviceInMemDataLoader)'
                      '; stall_pct_hbm_scan = same cache, gather+step fused '
                      'into one lax.scan dispatch per epoch (scan_epochs); '
                      'stall_pct_streaming = live thread-pool JPEG decode, '
                      'per-step dispatch; stall_pct_streaming_scan = same '
                      'pipeline via scan_batches (k steps per stacked '
                      'device_put + scan dispatch); stall_pct_delivery_bound '
                      '= streaming loader over pre-decoded uint8 parquet '
                      '(no JPEG) — isolates delivery from decode economics; '
                      'stall_pct_decoded_cache[_scan] = mmap decoded-tensor '
                      'disk cache, per-step / fused',
    }
    result.update(stall)
    # Criteo->DLRM leg (BASELINE config #4): a second model family and
    # regime (gather-bound embeddings over the columnar plane).  Gated on
    # the watchdog budget like certification — it compiles 2 more
    # executables and streams two full passes.
    if _budget_left_s() < 600:
        result['dlrm_error'] = ('skipped: %.0fs of watchdog budget left'
                                % _budget_left_s())
    else:
        try:
            dlrm = dlrm_stall_leg()
            result.update(dlrm)
            _PARTIAL.update(dlrm)  # a later watchdog partial must carry it
        except Exception as e:  # noqa: BLE001 — recorded; exit is non-zero
            result['dlrm_error'] = '%s: %s' % (type(e).__name__,
                                               str(e)[:160])
    # Host-boundary DLRM delivery — needs no device; AFTER the chip-coupled
    # legs so its cost can never flip their budget gate.
    if _budget_left_s() > 300:
        try:
            host_leg = dlrm_host_plane_leg()
            result.update(host_leg)
            _PARTIAL.update(host_leg)
        except Exception as e:  # noqa: BLE001 — recorded; exit is non-zero
            result['dlrm_host_error'] = '%s: %s' % (type(e).__name__,
                                                    str(e)[:160])
    # Host-only IPC-plane legs: the shm-vs-bytes microbench, the
    # ProcessPool twin of the host plane, and the disaggregated delivery
    # plane (worker counts 1 -> 2 -> 4, plus the w1 byte-path twin) —
    # the shm result plane's evidence set.
    for leg_name, leg_fn in _IPC_PLANE_LEGS:
        if _budget_left_s() <= 300:
            break
        try:
            host_leg = leg_fn()
            result.update(host_leg)
            _PARTIAL.update(host_leg)
        except Exception as e:  # noqa: BLE001 — recorded; exit is non-zero
            result[leg_name + '_error'] = '%s: %s' % (type(e).__name__,
                                                      str(e)[:160])
    _certify_into(result, 'tpu (Mosaic)' if platform == 'tpu'
                  else platform + ' (Pallas interpreter)')
    watchdog.cancel()
    _emit(result)
    # Every failure above was recorded so the lines could still print what
    # WAS measured; none of them is a success.
    failed = sorted(k for k in result
                    if result[k] and (k in ('legs_failed', 'throughput_error')
                                      or k.endswith('_error')))
    if failed:
        sys.stderr.write('bench: FAILED — %s\n' % ', '.join(failed))
        return 1
    return 0


if __name__ == '__main__':
    sys.exit(main())
