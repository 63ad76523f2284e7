"""Pipelined host→device transfer plane (ISSUE 6).

Everything between host memory and HBM should be hidden behind the step,
not paid inline.  This module makes the transfer a first-class pipeline
stage:

* **Ring-buffered staging** — a fixed ring of reused host staging slabs
  (reuse matters: a fresh slab pays a first-touch page fault a page
  before the memcpy).  A slot is rewritten only after the batch
  it last carried is committed on device (``jax.block_until_ready`` on
  slot reuse), so with ``ring_slots`` slots up to ``ring_slots - 1``
  transfers are in flight while the step runs — batch N+1's DMA
  overlaps batch N's compute.
* **Transfer coalescing** — the many small per-column arrays of a batch
  are packed into ONE C-contiguous staging slab per step: one
  ``device_put`` instead of one per column, then a jitted on-device
  unpack slices/bitcasts the slab back into the pytree.  The win is the
  per-dispatch fixed cost (python + transport round-trip per put), which
  dominates for wide-table batches.
* **Wire-dtype narrowing** — opt-in (``wire_dtypes='auto'`` or a
  ``{field: dtype}`` map): float32/float64 leaves travel as bfloat16
  and are cast back inside the jitted unpack, halving/quartering
  bytes-on-wire.  uint8 images already travel at their natural width
  and pass through bit-exact.  Without the opt-in every leaf travels at
  its canonical width and the result is bit-identical to
  ``jax.device_put``.
* **Sharded parallel transfer** — with a ``sharding`` whose spec shards
  only the leading (batch) axis, per-device slices of the staging batch
  are dispatched concurrently (one ``device_put`` per device — the DMAs
  overlap) and reassembled with
  ``jax.make_array_from_single_device_arrays`` instead of funneling the
  whole global batch through one host-thread call.

**Degrade matrix** (the plane NEVER changes delivered values; every
fallback is the existing inline path, bit-identical):

=====================================  =====================================
condition                              behaviour
=====================================  =====================================
``PETASTORM_TPU_NO_TRANSFER_PLANE=1``  plane off (inline ``device_put``)
``transfer='auto'`` on the CPU         plane off — the "link" is a memcpy
backend                                and the staging pass buys nothing
unsupported leaf dtype (datetime64,    that batch structure degrades to the
strings already filtered upstream)     inline path (``h2d_degraded`` counts)
single already-full-width leaf         inline path (coalescing is a no-op
                                       and the staging copy isn't free)
staging slab over the cap              inline path (a slab is a second host
(``PETASTORM_TPU_TRANSFER_MAX_        copy of the batch)
STAGING_MB``, default 512)
sharding not leading-axis /            ``global_batch_from_local`` as today
multi-host
=====================================  =====================================

Telemetry: every transfer times three stages through the one stage
primitive (``telemetry.Stages``): ``h2d_stage`` (host pack),
``h2d_dispatch`` (async put + unpack dispatch) and ``h2d_commit``
(observed wait for true transfer completion: ring-slot reuse waits, plus
a periodic 1-in-32 full sample, which alone also feeds
``h2d_commit_sampled``).  Each is a ``ptp/h2d_*`` profiler span inside the
loader's ``pt/device_put``, a histogram and a seconds counter on the
loader's metrics registry, and an ``h2d/stage`` / ``h2d/dispatch`` /
``h2d/commit`` span of the loader's ``TraceRecorder`` —
``attribute_stalls`` can split staging-copy time from link time
(components ``h2d_stage`` vs ``h2d``).  The jitted unpack is
``jit_pt_h2d_unpack`` in the device trace.
"""

import logging
import os
import threading
from petastorm_tpu.utils.locks import make_condition
from collections import deque

import numpy as np

import jax
import jax.numpy as jnp

logger = logging.getLogger(__name__)

__all__ = ['TransferPlane', 'DispatchPump', 'plane_enabled', 'KILL_SWITCH',
           'wire_dtype_for']

#: Environment kill switch: set to any non-empty value to force every
#: loader onto the inline ``device_put`` path regardless of ``transfer=``.
KILL_SWITCH = 'PETASTORM_TPU_NO_TRANSFER_PLANE'

#: Staging slabs above this bound degrade to the inline path — a slab is
#: a second host-side copy of the batch, and a whole-dataset transfer
#: (DeviceInMemDataLoader._materialize) must not double host RAM.
MAX_STAGING_BYTES = int(os.environ.get(
    'PETASTORM_TPU_TRANSFER_MAX_STAGING_MB', '512')) << 20

#: Per-field slab alignment: keeps every wire-dtype view aligned and the
#: per-device segments cache-line separated.
_ALIGN = 64

#: 1-in-N full commit sample (dispatch → device-ready wall time); ring
#: reuse additionally observes the *residual* commit wait on every slot.
_COMMIT_SAMPLE_EVERY = 32

_BF16 = np.dtype(jnp.bfloat16)


#: Accepted ``transfer=`` values — ONE place, validated both at loader
#: construction (fail fast) and in :func:`plane_enabled` (direct users).
_TRANSFER_MODES = (True, False, None, 'auto')


def validate_transfer(transfer):
    """Strict on purpose: 'off'/'false'/'disabled' from a config parse
    are truthy and would silently ENABLE the plane under a
    fall-through-to-auto reading."""
    if transfer not in _TRANSFER_MODES:
        raise ValueError("transfer must be True, False, None, or 'auto' "
                         '(got %r)' % (transfer,))


def plane_enabled(transfer):
    """Resolve a loader's ``transfer=`` kwarg against the environment.

    ``False``/``None`` → off; ``True`` → on (tests force the plane on the
    CPU backend this way); ``'auto'`` → on unless the backend is the CPU,
    where the "link" is a memcpy and the extra staging pass buys nothing.
    The kill switch wins over everything.  A backend that cannot
    initialize raises here.
    """
    validate_transfer(transfer)
    if os.environ.get(KILL_SWITCH):
        return False
    if transfer is True:
        return True
    if not transfer:
        return False
    return jax.default_backend() != 'cpu'


def _supported(dtype):
    """Wire-packable dtypes: fixed-width bool/int/uint/float (bfloat16
    included).  datetime64/timedelta64/object/str degrade."""
    return dtype.kind in 'biuf' or dtype == _BF16


def _leaf_name(path):
    """Last path component name ('image' from "['image']") — the key the
    ``wire_dtypes`` dict matches on."""
    last = path[-1]
    key = getattr(last, 'key', None)
    if key is None:
        key = getattr(last, 'name', None)
    if key is None:
        key = getattr(last, 'idx', None)
    return str(key)


def _resolve_wire(name, out_dtype, policy):
    """Wire dtype for one leaf: the canonical dtype unchanged (exact), or
    the policy's narrowed dtype.  ``'auto'`` narrows >=32-bit floats to
    bfloat16; a dict names fields explicitly (absent fields stay exact).
    """
    if not policy:
        return out_dtype
    if policy == 'auto':
        if out_dtype.kind == 'f' and out_dtype.itemsize >= 4:
            return _BF16
        return out_dtype
    want = policy.get(name)
    return np.dtype(want) if want is not None else out_dtype


def wire_dtype_for(name, out_dtype, policy):
    """Public form of the wire-narrowing rule for one named leaf.

    The residency tier (``petastorm_tpu.jax.residency``) stores batches
    on device in exactly these wire dtypes, so the compressed-in-HBM
    budget math and the H2D link both follow one policy.
    """
    return _resolve_wire(name, np.dtype(out_dtype), policy)


class _Unsupported(Exception):
    """This batch structure cannot ride the plane; fall back inline."""


class _Field(object):
    __slots__ = ('offset', 'nbytes', 'wire', 'out', 'shape')

    def __init__(self, offset, nbytes, wire, out, shape):
        self.offset = offset
        self.nbytes = nbytes
        self.wire = wire
        self.out = out
        self.shape = shape


def _align(n):
    return -(-n // _ALIGN) * _ALIGN


def _signature(tree):
    """Cheap per-batch structure key: path + shape + source dtype per
    leaf.  Layouts, unpack executables and shard plans cache under it."""
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return tuple((jax.tree_util.keystr(path), np.asarray(leaf).shape,
                  np.asarray(leaf).dtype.str) for path, leaf in paths)


class _Layout(object):
    """Static packing plan for one batch structure: per-leaf slab offset,
    wire dtype (narrowed or canonical) and on-device output dtype.  The
    output dtype is ``jax.dtypes.canonicalize_dtype`` of the source —
    exactly what ``jax.device_put`` itself would deliver (int64 → int32
    under default x64-disabled JAX), so the no-narrowing plane output is
    bit-identical to the inline path."""

    def __init__(self, tree, policy):
        paths, self.treedef = jax.tree_util.tree_flatten_with_path(tree)
        if not paths:
            raise _Unsupported('empty pytree')
        self.fields = []
        offset = 0
        logical = 0
        for path, leaf in paths:
            arr = np.asarray(leaf)
            if arr.size == 0:
                raise _Unsupported('zero-size leaf %s'
                                   % jax.tree_util.keystr(path))
            if not _supported(arr.dtype):
                raise _Unsupported('leaf %s dtype %s is not wire-packable'
                                   % (jax.tree_util.keystr(path), arr.dtype))
            out = np.dtype(jax.dtypes.canonicalize_dtype(arr.dtype))
            wire = np.dtype(_resolve_wire(_leaf_name(path), out, policy))
            if not _supported(wire):
                raise _Unsupported('wire dtype %s for leaf %s is not '
                                   'packable'
                                   % (wire, jax.tree_util.keystr(path)))
            offset = _align(offset)
            nbytes = arr.size * wire.itemsize
            self.fields.append(_Field(offset, nbytes, wire, out, arr.shape))
            offset += nbytes
            logical += arr.size * out.itemsize
        self.slab_nbytes = offset
        self.logical_nbytes = logical
        #: True when the wire policy narrows at least one leaf — the
        #: provenance 'transfer' outcome distinguishes narrowed from
        #: plain coalesced batches (ISSUE 13).
        self.narrowed = any(f.wire != f.out for f in self.fields)
        if len(self.fields) == 1 and self.fields[0].wire == self.fields[0].out:
            # One full-width leaf: coalescing is a no-op and the staging
            # memcpy is pure cost — the inline put is already one dispatch.
            raise _Unsupported('single full-width leaf')

    def pack(self, tree, slab):
        """One cast-or-copy pass per leaf into the staging slab (numpy
        assignment casts unsafely — the same canonicalization/narrowing
        semantics the unpack side expects)."""
        for field, leaf in zip(self.fields, jax.tree_util.tree_leaves(tree)):
            dst = slab[field.offset:field.offset + field.nbytes]
            dst.view(field.wire)[...] = np.asarray(leaf).reshape(-1)

    def build_unpack(self):
        """The on-device inverse: slice each leaf's bytes out of the slab,
        bitcast to the wire dtype, reshape, and cast back to the output
        dtype when the wire was narrowed.  Jitted by the plane, so the
        whole batch materializes in ONE executable."""
        fields = list(self.fields)
        treedef = self.treedef

        @jax.named_scope('pt/h2d_unpack')
        def pt_h2d_unpack(slab):
            leaves = []
            for f in fields:
                # The barrier keeps a field's ops on that field's bytes.
                # Without it XLA hoists the byte-regrouping reshape above
                # the slice and reshapes the WHOLE slab once per multi-byte
                # field: the TPU compiler then takes ~4 s per image of an
                # ImageNet batch to compile image + one int32 column (22
                # min at batch 256) and the program regroups 38 MB to read
                # 1 KB.
                seg = jax.lax.optimization_barrier(
                    slab[f.offset:f.offset + f.nbytes])
                if f.wire == np.uint8:
                    arr = seg
                elif f.wire.kind == 'b':
                    arr = seg.astype(jnp.bool_)
                elif f.wire.itemsize == 1:
                    arr = jax.lax.bitcast_convert_type(seg, jnp.dtype(f.wire))
                else:
                    arr = jax.lax.bitcast_convert_type(
                        seg.reshape(-1, f.wire.itemsize), jnp.dtype(f.wire))
                arr = _reshape_rows_minor(arr, f.shape)
                if f.wire != f.out:
                    arr = arr.astype(jnp.dtype(f.out))
                leaves.append(arr)
            return jax.tree_util.tree_unflatten(treedef, leaves)

        return pt_h2d_unpack


def _reshape_rows_minor(flat, shape):
    """``flat.reshape(shape)``, written so that no row-major copy of the leaf
    has to exist on the way.

    On a TPU an array is tiled over its two minor dims, and a row-major
    ``uint8[N, 224, 224, 3]`` pads its 3 channels to 128 lanes — 42x its
    bytes: 1.6 GB of scratch for one batch of 256, and a whole epoch
    (``put_once``) refused outright.  The layouts the TPU compiler prefers
    for such a leaf keep another axis minor; routing the reshape through
    ``[rest..., N]`` lets it get there by layout assignment alone, where the
    direct reshape forces the padded row-major intermediate first.  The
    values are those of the direct reshape on every backend.
    """
    if len(shape) < 2:
        return flat.reshape(shape)
    rows = shape[0]
    by_row = flat.reshape(rows, -1).T.reshape(tuple(shape[1:]) + (rows,))
    return jnp.moveaxis(by_row, -1, 0)


def _slab_bytes(prepared):
    """Host staging bytes a prepared (layout, unpack, plan) needs."""
    layout, _, plan = prepared
    return layout.slab_nbytes if plan is None else plan.total_nbytes


class _ShardPlan(object):
    """Per-device split of one layout: unique leading-axis row ranges (a
    replicated mesh axis maps several devices to one range), the
    per-shard sub-layout, and the device order the reassembly uses."""

    __slots__ = ('devices', 'ranges', 'uniq', 'seg_offsets', 'shard_layout',
                 'total_nbytes')

    def __init__(self, devices, ranges, uniq, seg_offsets, shard_layout,
                 total_nbytes):
        self.devices = devices
        self.ranges = ranges
        self.uniq = uniq
        self.seg_offsets = seg_offsets
        self.shard_layout = shard_layout
        self.total_nbytes = total_nbytes


class TransferPlane(object):
    """Coalescing, narrowing, ring-buffered host→device transfer.

    ``put`` returns the device pytree — or ``None`` when this batch
    structure degrades, in which case the caller runs its existing
    inline path (the plane never guesses; the fallback is the code that
    was already correct).  One plane instance serves one loader: the
    ring slabs, layout caches and unpack executables are all keyed by
    batch structure and reused across steps.
    """

    def __init__(self, device=None, sharding=None, wire_dtypes=None,
                 ring_slots=3, metrics=None, trace_recorder=None,
                 max_staging_bytes=None):
        if wire_dtypes not in (None, 'auto') \
                and not isinstance(wire_dtypes, dict):
            raise ValueError("wire_dtypes must be None, 'auto', or a "
                             '{field: dtype} dict (got %r)' % (wire_dtypes,))
        self._device = device
        self._sharding = sharding
        self._policy = wire_dtypes
        nslots = max(2, int(ring_slots))
        self._slabs = [None] * nslots
        self._inflight = [None] * nslots
        self._turn = 0
        self._max_staging = (MAX_STAGING_BYTES if max_staging_bytes is None
                             else int(max_staging_bytes))
        self._prepared = {}   # signature -> (layout, unpack, plan) | None
        from petastorm_tpu.telemetry import MetricsRegistry, Stages
        if metrics is None:
            metrics = MetricsRegistry('transfer')
        self.metrics = metrics
        # parts of the loader's pt/device_put, so ptp/ (see Stages)
        self._stage = Stages(metrics, trace_recorder, prefix='ptp/')
        self._m_batches = metrics.counter('h2d_batches')
        self._m_degraded = metrics.counter('h2d_degraded')
        self._m_wire = metrics.counter('h2d_bytes_wire')
        self._m_logical = metrics.counter('h2d_bytes_logical')
        for stage in ('h2d_stage', 'h2d_dispatch', 'h2d_commit'):
            self._stage.instruments(stage)
        #: ``h2d_commit`` mixes the residual wait at ring-slot reuse (near 0
        #: when the ring keeps up) with the 1-in-32 full sample; this one
        #: holds the full samples alone: the true dispatch-to-ready time.
        self._h_sampled = metrics.histogram('h2d_commit_sampled')
        #: Per-batch provenance (ISSUE 13): outcome + stage windows of
        #: the most recent put — ``{'outcome': 'coalesced'|'narrowed'|
        #: 'degraded', 'stages': {'h2d_stage'/'h2d_dispatch'/
        #: 'h2d_commit': [t0, t1]}}`` — read by the loader right after
        #: ``put`` returns (the plane is single-consumer by contract).
        self.last_put = None

    # -- public API ----------------------------------------------------------

    def put(self, tree):
        """Ring-buffered coalesced transfer of one batch pytree; returns
        the device pytree, or None when the structure degrades."""
        prepared = self._prepare(tree)
        if prepared is None:
            self._m_degraded.inc()
            self.last_put = {'outcome': 'degraded'}
            return None
        slot = self._turn % len(self._slabs)
        self._turn += 1
        commit_window = self._wait_slot(slot)
        slab = self._slot_slab(slot, _slab_bytes(prepared))
        batch = self._staged_put(prepared, tree, slab)
        if commit_window is not None:
            # The ring-slot reuse barrier is observed link time of this
            # put's wall — part of its causal chain.
            self.last_put['stages']['h2d_commit'] = commit_window
        self._inflight[slot] = batch
        return batch

    def put_once(self, tree):
        """One-shot coalesced transfer outside the ring (whole-dataset
        placement: ``DeviceInMemDataLoader._materialize``).  The
        transient slab is released immediately after the dispatch."""
        prepared = self._prepare(tree)
        if prepared is None:
            self._m_degraded.inc()
            self.last_put = {'outcome': 'degraded'}
            return None
        slab = np.empty(_slab_bytes(prepared), np.uint8)
        return self._staged_put(prepared, tree, slab, sample_commit=False)

    def _staged_put(self, prepared, tree, slab, sample_commit=True):
        """Pack → dispatch → on-device unpack + accounting — the shared
        core of ``put`` (ring slab) and ``put_once`` (transient slab)."""
        layout, unpack, plan = prepared
        if plan is None:
            with self._stage('h2d_stage', event='h2d/stage') as staged:
                layout.pack(tree, slab)
            with self._stage('h2d_dispatch',
                             event='h2d/dispatch') as dispatched:
                dev_slab = (jax.device_put(slab, self._device)
                            if self._device is not None
                            else jax.device_put(slab))
                batch = unpack(dev_slab)
            wire = layout.slab_nbytes
        else:
            staged, dispatched, batch = self._put_sharded(
                layout, unpack, plan, tree, slab)
            # One device_put PER DEVICE: a replicated mesh axis ships the
            # same segment to every replica, and those bytes are on the
            # link too.
            wire = plan.shard_layout.slab_nbytes * len(plan.devices)
        self._m_batches.inc()
        self._m_wire.inc(wire)
        self._m_logical.inc(layout.logical_nbytes)
        self.last_put = {
            'outcome': 'narrowed' if layout.narrowed else 'coalesced',
            'stages': {'h2d_stage': staged.window,
                       'h2d_dispatch': dispatched.window}}
        if sample_commit \
                and int(self._m_batches.value) % _COMMIT_SAMPLE_EVERY == 1:
            # Periodic FULL commit sample: dispatch → device-ready wall
            # time of the batch just put (the ring wait in _wait_slot
            # only ever sees the residual after a full lap of overlap).
            with self._stage('h2d_commit', event='h2d/commit',
                             kind='sample') as sampled:
                jax.block_until_ready(batch)
            self._h_sampled.observe(sampled.seconds)
        return batch

    def drain(self):
        """Block until every in-flight ring transfer is committed (the
        checkpoint / teardown quiesce); host slabs stay for reuse."""
        for i, batch in enumerate(self._inflight):
            if batch is not None:
                jax.block_until_ready(batch)
                self._inflight[i] = None

    def close(self):
        """Drain the ring and release the staging slabs."""
        self.drain()
        self._slabs = [None] * len(self._slabs)

    # -- ring ----------------------------------------------------------------

    def _wait_slot(self, slot):
        """Commit barrier for slab reuse: the batch this slot last staged
        must be device-resident before the slab is rewritten (the H2D
        copy reads the host slab asynchronously).  The observed wait is
        the ring's view of true link time → ``h2d/commit``.  Returns the
        wait window (or None when the slot was free)."""
        batch = self._inflight[slot]
        if batch is None:
            return None
        with self._stage('h2d_commit', event='h2d/commit',
                         kind='ring') as waited:
            jax.block_until_ready(batch)
        self._inflight[slot] = None
        return waited.window

    def _slot_slab(self, slot, nbytes):
        slab = self._slabs[slot]
        if slab is None or slab.nbytes < nbytes:
            slab = self._slabs[slot] = np.empty(nbytes, np.uint8)
        return slab[:nbytes]

    # -- layout / plan cache -------------------------------------------------

    def _prepare(self, tree):
        sig = _signature(tree)
        if sig in self._prepared:
            return self._prepared[sig]
        try:
            layout = _Layout(tree, self._policy)
            plan = None
            if self._sharding is not None:
                plan = self._plan_shards(tree)
                total = plan.total_nbytes
            else:
                total = layout.slab_nbytes
            if total > self._max_staging:
                raise _Unsupported('staging slab %d B exceeds the %d B cap'
                                   % (total, self._max_staging))
            # No donation: a 1-D uint8 slab can alias none of the shaped
            # leaves ("Some donated buffers were not usable"), and it is
            # released after the dispatch either way.
            unpack = jax.jit((layout if plan is None
                              else plan.shard_layout).build_unpack())
            prepared = (layout, unpack, plan)
        except _Unsupported as e:
            logger.debug('transfer plane degrades for this batch '
                         'structure: %s', e)
            prepared = None
        self._prepared[sig] = prepared
        return prepared

    # -- sharded parallel transfer -------------------------------------------

    def _plan_shards(self, tree):
        """Validate that the sharding splits only the leading axis of
        every leaf (replication over other mesh axes allowed) and build
        the per-device packing plan.  Anything else degrades to
        ``global_batch_from_local``."""
        sharding = self._sharding
        if jax.process_count() != 1:
            raise _Unsupported('multi-host sharding assembles via '
                               'make_array_from_process_local_data')
        leaves = [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]
        ref_ranges = None
        for arr in leaves:
            if arr.ndim == 0:
                raise _Unsupported('scalar leaf cannot shard a batch axis')
            try:
                index_map = sharding.addressable_devices_indices_map(
                    arr.shape)
            except Exception as e:  # noqa: BLE001 — e.g. indivisible dim
                raise _Unsupported('sharding rejects leaf shape %s: %s'
                                   % (arr.shape, e))
            ranges = {}
            for dev, idx in index_map.items():
                idx = idx if isinstance(idx, tuple) else (idx,)
                start, stop, step = (idx[0] if idx else slice(None)) \
                    .indices(arr.shape[0])
                if step != 1:
                    raise _Unsupported('strided shard index')
                for dim, sub in zip(arr.shape[1:], idx[1:]):
                    lo, hi, st = sub.indices(dim)
                    if (lo, hi, st) != (0, dim, 1):
                        raise _Unsupported('sharding splits a non-leading '
                                           'axis')
                ranges[dev] = (start, stop)
            if ref_ranges is None:
                ref_ranges = ranges
            elif ranges != ref_ranges:
                raise _Unsupported('leaves shard to different row ranges')
        uniq = sorted(set(ref_ranges.values()))
        rows = {stop - start for start, stop in uniq}
        if len(rows) != 1 or 0 in rows:
            raise _Unsupported('unequal shard row counts')
        rows = rows.pop()
        devices = sorted(ref_ranges, key=lambda d: (ref_ranges[d][0], d.id))
        shard_tree = jax.tree_util.tree_map(
            lambda v: np.asarray(v)[:rows], tree)
        shard_layout = _Layout(shard_tree, self._policy)
        stride = _align(shard_layout.slab_nbytes)
        seg_offsets = {rng: i * stride for i, rng in enumerate(uniq)}
        return _ShardPlan(devices, ref_ranges, uniq, seg_offsets,
                          shard_layout, stride * len(uniq))

    def _put_sharded(self, layout, unpack, plan, tree, slab):
        """Pack each unique row range once, dispatch every device's slice
        concurrently (async ``device_put`` per device — the DMAs
        overlap), unpack on-device per shard, and reassemble each leaf
        as one global array."""
        nbytes = plan.shard_layout.slab_nbytes
        with self._stage('h2d_stage', event='h2d/stage') as staged:
            for start, stop in plan.uniq:
                seg = slab[plan.seg_offsets[(start, stop)]:]
                plan.shard_layout.pack(
                    jax.tree_util.tree_map(
                        lambda v: np.asarray(v)[start:stop], tree),
                    seg[:nbytes])
        with self._stage('h2d_dispatch', event='h2d/dispatch') as dispatched:
            shards = {}
            for dev in plan.devices:   # all dispatches before any unpack
                off = plan.seg_offsets[plan.ranges[dev]]
                shards[dev] = jax.device_put(slab[off:off + nbytes], dev)
            per_dev = [jax.tree_util.tree_leaves(unpack(shards[dev]))
                       for dev in plan.devices]
            out_leaves = []
            for li, field in enumerate(layout.fields):
                out_leaves.append(jax.make_array_from_single_device_arrays(
                    field.shape, self._sharding,
                    [per_dev[di][li] for di in range(len(plan.devices))]))
        return staged, dispatched, jax.tree_util.tree_unflatten(
            layout.treedef, out_leaves)


_DONE = object()


class DispatchPump(object):  # ptlint: disable=pickle-unsafe-attrs — the pump lives and dies inside one loader iteration in the consuming process; it is never pickled (resume tokens carry drained host batches, not the pump)
    """Background H2D dispatch thread: pulls host batches from the
    loader's (single-consumer) host-batch generator, ships each through
    the transfer plane, and appends the resulting device batches to the
    shared ``pending`` deque the loader yields from — so host staging,
    the link, and the device step run as three overlapped pipeline
    stages instead of one serial loop.

    Checkpoint contract: ``pause()`` blocks until the thread is
    quiescent (not touching the generator, the plane, or ``pending``) —
    ``DataLoader.state_dict`` brackets its snapshot with
    ``pause()``/``resume()`` so the exact-resume machinery (reader
    drain, shuffle-buffer snapshot, pending drain) sees a frozen
    pipeline.  ``stop()`` ends the thread; a pull blocked inside the
    reader cannot be interrupted mid-call, so the thread is daemonic and
    exits right after that pull returns (the loader's ``reader.stop()``
    is what unblocks it during teardown).
    """

    def __init__(self, source, ship, prefetch):
        self._source = source
        self._ship = ship
        self._cap = max(1, int(prefetch))
        self.pending = deque()
        self._cond = make_condition('jax.transfer.DispatchPump._cond')
        self._idle = False
        self._pause = 0
        self._stopped = False
        self._done = False
        self._error = None
        self._thread = threading.Thread(target=self._run,
                                        name='petastorm-tpu-h2d-dispatch',
                                        daemon=True)

    def start(self):
        self._thread.start()
        return self

    def _run(self):
        try:
            while True:
                with self._cond:
                    while (self._pause or len(self.pending) >= self._cap) \
                            and not self._stopped:
                        self._idle = True
                        self._cond.notify_all()
                        self._cond.wait()
                    self._idle = False
                    if self._stopped:
                        return
                item = next(self._source)   # outside the lock: may block
                with self._cond:
                    if self._stopped:
                        return
                dev = self._ship(item)
                with self._cond:
                    self.pending.append(dev)
                    self._cond.notify_all()
        except StopIteration:
            pass
        except BaseException as e:  # noqa: BLE001 — re-raised by get()
            self._error = e
        finally:
            with self._cond:
                self._done = True
                self._idle = True
                self._cond.notify_all()

    def get(self):
        """Next device batch in stream order; raises the pump's pending
        error once the buffered batches are served; the module-level
        ``_DONE`` sentinel ends the stream."""
        with self._cond:
            while not self.pending and not self._done:
                self._cond.wait()
            if self.pending:
                item = self.pending.popleft()
                self._cond.notify_all()
                return item
            if self._error is not None:
                raise self._error
            return _DONE

    def pause(self):
        """Checkpoint barrier: returns once the pump thread is parked
        (or finished) and guaranteed not to advance the generator or
        mutate ``pending`` until ``resume()``.  Counting, so brackets
        nest (PackedDataLoader wraps the base snapshot).

        A pull already in progress must complete first — an in-flight
        ``next()`` cannot be snapshotted consistently — so on a starved
        source a checkpoint waits out the current batch wait.  That is
        the same wall-clock position the inline path puts the caller
        in: without the pump, the consuming thread sits inside
        ``next(loader)`` for that same stall and cannot call
        ``state_dict`` at all until it returns."""
        with self._cond:
            self._pause += 1
            self._cond.notify_all()
            while not (self._idle or self._done):
                self._cond.wait()

    def resume(self):
        with self._cond:
            self._pause = max(0, self._pause - 1)
            self._cond.notify_all()

    def stop(self, join_timeout_s=2.0):
        with self._cond:
            self._stopped = True
            self._cond.notify_all()
        self._thread.join(join_timeout_s)

    def join(self, timeout_s=2.0):
        self._thread.join(timeout_s)

    @property
    def alive(self):
        return self._thread.is_alive()
