"""Device-resident data plane: compressed-in-HBM tier + epoch-keyed shuffle + LRU.

Three composing pieces (ISSUE 17):

* **Compressed-in-HBM tier** — batches live on device in the transfer
  plane's narrowed *wire* dtypes (uint8 stays uint8, float32 rides as
  bfloat16 under the ``'auto'`` policy) and are widened inside the jitted
  step.  HBM holds roughly 2-4x more samples than a full-width
  ``DeviceInMemDataLoader`` cache, so "dataset too big for device" often
  becomes "fits".
* **On-device epoch shuffle** — :func:`epoch_permutation` derives each
  epoch's order from ``(seed, epoch)`` alone via ``jax.random.fold_in``,
  so a resident epoch is bit-identical to the equivalent streamed epoch
  and an order can be recomputed from a resume token without replaying
  history.  This is the forward-compatibility hook for the ROADMAP's
  cluster-wide global permutation: any worker can derive any epoch's
  order from the shared seed.
* **Multi-epoch residency LRU** — :class:`ResidencyTier` is a
  budget-bounded slab of wire-dtype rows.  Batches are admitted as they
  are delivered on streamed epochs; admission writes through a jitted
  ``dynamic_update_slice`` whose slab argument is *donated* off-CPU, so
  evicted rows are recycled in place rather than freed-and-reallocated.
  Once every dataset row is resident, warm epochs are served by a single
  jitted gather+widen and fetch **zero** host batches.

How rows are stored (ISSUE 27), and why.  A field's slab is never
``(capacity,) + row_shape``: the TPU's own layout for an NHWC uint8 array
keeps the *batch* axis in the lanes, so a slab of that shape interleaves
its rows byte by byte, and ``take`` had to re-lay the whole slab (1.5 GB
at 9,984 ImageNet rows: 7.4 ms a step on the v5e, and growing with the
tier) before it could pick 256 of them.  Flat ``(capacity, elems)``
storage is not enough for a wide row either: ``take`` over rows of more
than 32,768 elements is split by the TPU compiler into column slices of
the *whole* slab, and because eight rows share every ``(8, 128)`` tile no
single row can be copied alone.  So a field of up to 32,768 elements a
row lies flat and is read with ``take``; a wider one lies as ``(capacity,
ceil(elems / 128), 128)``, every row whole tiles of its own, and is read
by one HBM-to-HBM DMA a row.  Both then reach ``row_shape`` through the
transfer plane's ``_reshape_rows_minor``, the way a streamed batch gets
to the device's layout.  A warm step moves the bytes of one batch and
nothing that grows with ``capacity``.

Degrade matrix (mirrors the transfer plane's conventions):

* ``PETASTORM_TPU_NO_RESIDENCY=1`` — kill switch; the loader streams
  full-width every epoch, reproducing the pre-residency schedule and
  delivery exactly.
* unsupported dtype anywhere in the batch — :func:`wire_plan` returns
  ``None`` and the loader degrades to full-width streaming (passthrough:
  no narrowing, no residency).
* budget too small for the dataset — streamed epochs still admit (the
  LRU churns, visible as ``residency_thrash``), but warm serving never
  activates; every epoch streams.

The module also hosts the degenerate single-entry case shared with
``DeviceInMemDataLoader`` (:func:`place_once` / :func:`device_cache_valid`),
so the full-width device cache and the resident tier validate buffers the
same way.
"""

import os
from collections import OrderedDict

import numpy as np

import jax
import jax.numpy as jnp

from petastorm_tpu.jax.transfer import (_reshape_rows_minor, _supported,
                                        wire_dtype_for)
from petastorm_tpu.telemetry import decisions as _decisions

#: Kill switch: set to any non-empty value to disable the resident tier.
#: The loader then streams full-width batches every epoch — byte-for-byte
#: the pre-residency schedule and delivery (PR 16 convention).
KILL_SWITCH = 'PETASTORM_TPU_NO_RESIDENCY'

#: Counter names created eagerly so stats rollups carry the full shape
#: even when the plane is off (kill switch, unsupported dtypes).
COUNTER_NAMES = (
    'residency_admitted',
    'residency_evictions',
    'residency_hits',
    'residency_bypass',
    'residency_thrash',
    'residency_host_batches',
)

GAUGE_NAMES = (
    'residency_rows',
    'residency_bytes',
    'residency_budget_bytes',
    'residency_rowcopy_fields',
)

#: A field whose row has more elements than this is read one copy a row;
#: up to it, with ``take``.  Compiled for the v5e, ``take`` over a
#: row-major ``(capacity, elems)`` slab needs no scratch up to 32,768
#: elements a row, in uint8, bfloat16 and int32 alike, and from 32,896 on
#: it slices the whole slab by columns into temporaries that grow with
#: ``capacity`` (330 MB at 20,000 uint8 rows, 660 MB at 40,000).
_TAKE_MAX_ROW_ELEMS = 32768

#: The lanes of a TPU tile.  A 2-D slab is tiled ``(8, 128)`` over (row,
#: element), so eight rows (thirty-two of uint8) share every tile and one
#: row cannot be copied without the others: a loop of ``dynamic_slice``
#: over ``u8[9984, 150528]`` took 4.1 ms a batch of 256 on the v5e.  A
#: wide row is therefore stored as ``(ceil(elems / 128), 128)``, tiles of
#: its own and contiguous, padded with at most 127 elements.
_LANES = 128

#: Row copies the gather keeps in flight.  8 and 32 read the same on the
#: v5e: 0.05 ms for 256 rows of 150,528 bytes.
_COPIES_IN_FLIGHT = 8


def killed():
    """True when the ``PETASTORM_TPU_NO_RESIDENCY`` kill switch is set."""
    return bool(os.environ.get(KILL_SWITCH))


def donation_supported():
    """Whether buffer donation actually recycles memory on this backend.

    ``jax.jit(..., donate_argnums=...)`` is a no-op (a copy) on CPU; the
    tier still runs there — the tests exercise the exact same code
    path — but the in-place recycling story only holds on accelerators.
    """
    return jax.default_backend() != 'cpu'


# ---------------------------------------------------------------------------
# Epoch-keyed shuffle
# ---------------------------------------------------------------------------

def epoch_key(seed, epoch):
    """PRNG key for one epoch: ``fold_in(PRNGKey(seed), epoch)``.

    A pure function of ``(seed, epoch)`` — no split chain, no history —
    so resident and streamed epochs derive identical orders and a resume
    token only needs the pair, not the traversal that led to it.
    """
    return jax.random.fold_in(jax.random.PRNGKey(int(seed)), int(epoch))


def epoch_permutation(seed, epoch, n):
    """On-device permutation of ``n`` rows keyed by ``(seed, epoch)``."""
    return jax.random.permutation(epoch_key(seed, epoch), int(n))


# ---------------------------------------------------------------------------
# Wire plan: narrow on host, widen in the jitted step
# ---------------------------------------------------------------------------

class _WireField(object):
    __slots__ = ('wire', 'out', 'row_shape')

    def __init__(self, wire, out, row_shape):
        self.wire = wire
        self.out = out
        self.row_shape = row_shape


class WirePlan(object):
    """Per-field wire/output dtypes for a flat dict of ``(N, ...)`` arrays.

    ``narrow`` runs on host (numpy ``astype`` to the wire dtype, identity
    for already-narrow fields); ``widen`` runs on device and is the jitted
    inverse ``astype`` back to the canonical output dtype.  For uint8 and
    other exact wires the round trip is bit-exact; for float32→bf16 it is
    lossy on the narrow side only — widening stored bf16 back to float32
    is exact, which is what makes resident and streamed epochs
    bit-identical (both deliver ``widen(narrow(rows))``).
    """

    def __init__(self, fields, wire_row_nbytes, logical_row_nbytes):
        self.fields = fields
        self.wire_row_nbytes = wire_row_nbytes
        self.logical_row_nbytes = logical_row_nbytes
        self.narrowed = any(f.wire != f.out for f in fields.values())
        self._widen_fn = None

    def narrow(self, host_rows):
        """Cast a host batch to wire dtypes (no copy when already narrow)."""
        return {name: np.asarray(host_rows[name]).astype(f.wire, copy=False)
                for name, f in self.fields.items()}

    def widen(self, wire_dev):
        """Widen a device batch of wire arrays back to canonical dtypes.

        Not donating: for exact fields widen is the identity, so the
        delivered batch aliases the wire arrays (which the resident tier
        may also hold) — donation would invalidate live aliases.
        """
        if not self.narrowed:
            return wire_dev
        if self._widen_fn is None:
            outs = {name: jnp.dtype(f.out) for name, f in self.fields.items()}

            @jax.named_scope('pt/residency_widen')
            def pt_residency_widen(tree):
                return {name: tree[name].astype(outs[name]) for name in tree}

            self._widen_fn = jax.jit(pt_residency_widen)
        return self._widen_fn(wire_dev)


def wire_plan(tree, policy):
    """Build a :class:`WirePlan` for a flat dict of host arrays.

    Returns ``None`` when the batch cannot ride the tier — empty tree, a
    dtype outside the transfer plane's support matrix, or the kill switch
    via the caller — in which case the loader degrades to full-width
    streaming rather than failing.
    """
    if not tree:
        return None
    fields = {}
    wire_row = 0
    logical_row = 0
    for name in sorted(tree):
        arr = np.asarray(tree[name])
        if arr.ndim < 1 or not _supported(arr.dtype):
            return None
        out = jnp.dtype(jax.dtypes.canonicalize_dtype(arr.dtype))
        wire = wire_dtype_for(name, out, policy)
        if not _supported(wire):
            return None
        row_shape = arr.shape[1:]
        row_elems = int(np.prod(row_shape, dtype=np.int64)) if row_shape else 1
        fields[name] = _WireField(np.dtype(wire), np.dtype(out), row_shape)
        wire_row += row_elems * np.dtype(wire).itemsize
        logical_row += row_elems * np.dtype(out).itemsize
    return WirePlan(fields, wire_row, logical_row)


def estimate_budget(tree, policy='auto'):
    """Budget math for the doctor: bytes/row on the wire vs full width.

    ``hbm_ratio`` is how many more rows the narrowed tier holds per byte
    of HBM compared to a full-width device cache (>= 1.0; 1.0 when
    nothing narrows).
    """
    plan = wire_plan(tree, policy)
    if plan is None:
        return None
    return {
        'wire_bytes_per_row': plan.wire_row_nbytes,
        'logical_bytes_per_row': plan.logical_row_nbytes,
        'hbm_ratio': (float(plan.logical_row_nbytes) / plan.wire_row_nbytes
                      if plan.wire_row_nbytes else 1.0),
        'narrowed': plan.narrowed,
    }


# ---------------------------------------------------------------------------
# Shared device-cache validity helpers (degenerate single-entry case)
# ---------------------------------------------------------------------------

def device_cache_valid(tree):
    """True when every leaf of a placed device pytree holds live buffers.

    Donated or explicitly ``delete()``-ed jax arrays report
    ``is_deleted() == True``; serving from them raises deep inside a
    gather with an opaque runtime error, so callers check here first.
    """
    if tree is None:
        return False
    for leaf in jax.tree_util.tree_leaves(tree):
        is_deleted = getattr(leaf, 'is_deleted', None)
        if callable(is_deleted):
            try:
                if is_deleted():
                    return False
            except Exception:
                return False
    return True


def place_once(numeric, plane=None, device=None):
    """Place a host pytree on device once (plane fast path, device_put else).

    The single-entry degenerate case of the residency LRU:
    ``DeviceInMemDataLoader`` holds exactly one "entry" (the whole
    dataset) that is admitted once and never evicted, so it shares this
    placement + :func:`device_cache_valid` revalidation path with the
    tier instead of re-issuing ``device_put`` per epoch.
    """
    if plane is not None:
        placed = plane.put_once(numeric)
        if placed is not None:
            return placed
    if device is not None:
        return {k: jax.device_put(v, device) for k, v in numeric.items()}
    return {k: jax.device_put(v) for k, v in numeric.items()}


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------

class ResidencyCounters(object):
    """Eagerly-registered residency counters/gauges on a MetricsRegistry."""

    def __init__(self, metrics):
        self.admitted = metrics.counter('residency_admitted')
        self.evictions = metrics.counter('residency_evictions')
        self.hits = metrics.counter('residency_hits')
        self.bypass = metrics.counter('residency_bypass')
        self.thrash = metrics.counter('residency_thrash')
        self.host_batches = metrics.counter('residency_host_batches')
        self.rows = metrics.gauge('residency_rows')
        self.bytes = metrics.gauge('residency_bytes')
        self.budget = metrics.gauge('residency_budget_bytes')
        self.rowcopy_fields = metrics.gauge('residency_rowcopy_fields')


def ensure_counters(metrics):
    """Create the full residency counter shape (all zeros when plane off)."""
    return ResidencyCounters(metrics)


# ---------------------------------------------------------------------------
# The residency LRU tier
# ---------------------------------------------------------------------------

def _auto_interpret():
    return jax.default_backend() != 'tpu'


def _slab_row_shape(field):
    """How one row of ``field`` lies in its slab: a scalar as itself, a
    narrow row flat, a wide row as whole ``(8, 128)`` tiles of its own."""
    if not field.row_shape:
        return ()
    elems = int(np.prod(field.row_shape, dtype=np.int64))
    if elems <= _TAKE_MAX_ROW_ELEMS:
        return (elems,)
    return (-(-elems // _LANES), _LANES)


def _to_slab_rows(batch, slab_row):
    """``(n,) + row_shape`` -> ``(n,) + slab_row``: the inverse of
    ``_reshape_rows_minor`` (through ``[rest..., n]``, so that no padded
    row-major copy of the batch has to exist), then the lanes' padding."""
    n = batch.shape[0]
    if batch.ndim > 2:
        batch = jnp.moveaxis(batch, 0, -1).reshape(-1, n).T
    if len(slab_row) == 2:
        pad = slab_row[0] * _LANES - batch.shape[1]
        if pad:
            batch = jnp.pad(batch, ((0, 0), (0, pad)))
        batch = batch.reshape((n,) + slab_row)
    return batch


def _from_slab_rows(rows, row_shape):
    """``(n,) + slab_row`` -> ``(n,) + row_shape``, the way the transfer
    plane's unpack gets there from flat bytes."""
    n = rows.shape[0]
    if rows.ndim == 3:
        elems = int(np.prod(row_shape, dtype=np.int64))
        rows = rows.reshape(n, -1)[:, :elems]
    if rows.shape[1:] == row_shape:
        return rows
    return _reshape_rows_minor(rows, (n,) + row_shape)


def _copy_rows(slab, slots):
    """``slab[slots]`` for a slab of wide rows, one DMA a row from HBM to
    HBM with ``_COPIES_IN_FLIGHT`` of them in flight: the slots are
    prefetched as scalars and no byte passes through the core."""
    # Imported where a wide field is first traced: Pallas takes 0.9 s to
    # import, a third of ``import petastorm_tpu.jax``, and a loader with no
    # tier or with narrow fields never gets here.
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = slots.shape[0]
    in_flight = min(_COPIES_IN_FLIGHT, n)
    # ``take`` clamps a slot that is out of range; a DMA would follow it
    slots = jnp.clip(slots, 0, slab.shape[0] - 1)

    def kernel(slots_ref, slab_ref, out_ref, sems):
        def copy(i):
            return pltpu.make_async_copy(slab_ref.at[slots_ref[i]],
                                         out_ref.at[i],
                                         sems.at[i % in_flight])

        for i in range(in_flight):
            copy(i).start()

        def wait_and_refill(i, carry):
            copy(i).wait()

            @pl.when(i + in_flight < n)
            def _():
                copy(i + in_flight).start()
            return carry

        jax.lax.fori_loop(0, n, wait_and_refill, 0)

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((n,) + slab.shape[1:], slab.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA((in_flight,))]),
        interpret=_auto_interpret(),
        name='pt_residency_copy_rows',
    )(slots, slab)


class ResidencyTier(object):
    """Budget-bounded device-resident slab of wire-dtype rows with batch LRU.

    Rows live in per-field slabs in the wire dtype, each row where one
    copy can reach it (module docstring): a field of up to
    ``_TAKE_MAX_ROW_ELEMS`` elements a row as ``(capacity, elems)`` (a 1-D
    field as ``(capacity,)``), a wider one as ``(capacity, ceil(elems /
    128), 128)``.  Each admitted batch occupies a contiguous slot range
    tracked as one LRU entry; ``slot_of_row`` maps dataset row id →
    slab slot (-1 when not resident).  Admission writes through a jitted
    ``dynamic_update_slice_in_dim`` with the slab donated off-CPU, so an
    "eviction" is just the LRU entry releasing its slot range — the bytes
    are overwritten in place by the next donated admission.

    Warm serving is one jitted program: slice ``batch_size`` row ids out
    of the epoch permutation, map them through the device copy of
    ``slot_of_row``, read those rows of each slab (``take`` for the narrow
    fields, one DMA a row for the wide ones), restore ``row_shape`` and
    widen — no host work at all, and no device work that grows with
    ``capacity``.
    """

    def __init__(self, plan, n_rows, batch_size, budget_bytes, counters,
                 device=None):
        self._plan = plan
        self._n = int(n_rows)
        self._bs = int(batch_size)
        self._device = device
        self._slab_rows = {name: _slab_row_shape(f)
                           for name, f in plan.fields.items()}
        # what a row takes in the slabs: its wire bytes, and the lanes'
        # padding of a wide row whose elements are no multiple of 128
        row_bytes = self._row_bytes = max(1, sum(
            int(np.prod(self._slab_rows[name], dtype=np.int64))
            * f.wire.itemsize for name, f in plan.fields.items()))
        if budget_bytes is None:
            self._capacity = self._n
        else:
            self._capacity = min(self._n, max(0, int(budget_bytes) // row_bytes))
        self._c = counters
        counters.budget.set(int(budget_bytes) if budget_bytes is not None
                            else self._capacity * row_bytes)
        counters.rowcopy_fields.set(
            sum(len(shape) == 2 for shape in self._slab_rows.values()))
        self._slabs = None
        self._entries = OrderedDict()   # seq -> (slot, rows)
        self._seq = 0
        self._free = []                 # list of (slot, rows) released ranges
        self._bump = 0
        self._slot_of_row = np.full(self._n, -1, dtype=np.int32)
        self._slot_map_dev = None
        self._write_fns = {}
        self._gather_fns = {}           # rows of the batch -> jitted program
        self._dropped = False
        self._donate = donation_supported()

    @property
    def capacity_rows(self):
        return self._capacity

    @property
    def can_hold_dataset(self):
        return self._capacity >= self._n

    @property
    def resident_rows(self):
        return int((self._slot_of_row >= 0).sum())

    @property
    def fully_resident(self):
        return (not self._dropped and self._slabs is not None
                and self.resident_rows == self._n)

    @property
    def dropped(self):
        return self._dropped

    def serving_ok(self):
        """Gatherable right now: fully resident with live slab buffers."""
        return self.fully_resident and device_cache_valid(self._slabs)

    # -- slot management ----------------------------------------------------

    def _ensure_slabs(self):
        if self._slabs is not None:
            return
        def _zeros():
            return {name: jnp.zeros((self._capacity,) + self._slab_rows[name],
                                    dtype=jnp.dtype(f.wire))
                    for name, f in self._plan.fields.items()}
        if self._device is not None:
            with jax.default_device(self._device):
                self._slabs = _zeros()
        else:
            self._slabs = _zeros()

    def _alloc(self, rows):
        for i, (slot, free_rows) in enumerate(self._free):
            if free_rows == rows:
                del self._free[i]
                return slot
        if self._bump + rows <= self._capacity:
            slot = self._bump
            self._bump += rows
            return slot
        return None

    def _evict_lru(self):
        _, (slot, rows) = self._entries.popitem(last=False)
        # Clear only mappings still pointing into the evicted range — a row
        # re-admitted elsewhere keeps its newer slot.
        mask = (self._slot_of_row >= slot) & (self._slot_of_row < slot + rows)
        self._slot_of_row[mask] = -1
        self._free.append((slot, rows))
        self._slot_map_dev = None
        self._c.evictions.inc()

    def _update_gauges(self):
        rows = self.resident_rows
        self._c.rows.set(rows)
        self._c.bytes.set(rows * self._plan.wire_row_nbytes)

    # -- admission ----------------------------------------------------------

    def admit(self, row_ids, wire_dev):
        """Admit one batch of wire-dtype device arrays for the given rows.

        Returns the provenance outcome: ``'admitted'`` (fit without
        displacing anything, or rows already resident), ``'evicted'``
        (admitted, displacing the LRU entry — also counts a thrash), or
        ``'bypass'`` (tier dropped or batch larger than the whole budget).
        """
        row_ids = np.asarray(row_ids)
        rows = len(row_ids)
        if self._dropped or rows == 0 or rows > self._capacity:
            self._c.bypass.inc()
            _decisions.record_decision(
                'residency', 'bypass', 'residency_budget',
                {'rows': rows, 'capacity': self._capacity,
                 'dropped': bool(self._dropped)},
                suppressed=True)
            return 'bypass'
        if (self._slot_of_row[row_ids] >= 0).all():
            # Warm re-sight of already-resident rows: nothing is allocated or
            # displaced, so no decision record (this path runs every batch on
            # warm epochs and would flood the journal with non-decisions).
            return 'admitted'
        # Snapshot the allocator state the admission rule reads *before* the
        # evict loop mutates it, so the decision replay can re-derive the
        # outcome (admitted / evicted / bypass) from inputs alone.
        _inputs = {
            'rows': rows,
            'capacity': self._capacity,
            'bump': self._bump,
            'free_rows': [r for _, r in self._free],
            'entry_rows': [r for _, r in self._entries.values()],
        }
        self._ensure_slabs()
        evicted = False
        slot = self._alloc(rows)
        while slot is None and self._entries:
            self._evict_lru()
            evicted = True
            slot = self._alloc(rows)
        if slot is None:
            self._c.bypass.inc()
            _decisions.record_decision(
                'residency', 'bypass', 'residency_budget', _inputs,
                suppressed=True)
            return 'bypass'
        self._write(slot, rows, wire_dev)
        self._entries[self._seq] = (slot, rows)
        self._seq += 1
        self._slot_of_row[row_ids] = np.arange(slot, slot + rows,
                                               dtype=np.int32)
        self._slot_map_dev = None
        self._c.admitted.inc()
        if evicted:
            self._c.thrash.inc()
        self._update_gauges()
        outcome = 'evicted' if evicted else 'admitted'
        _decisions.record_decision(
            'residency', outcome, 'residency_budget', _inputs, slot=slot)
        return outcome

    def _update_program(self):
        """``(slabs, batch, start) -> slabs`` with the batch's rows written
        from ``start`` on, jitted, the slabs donated where that recycles."""
        slab_rows = self._slab_rows

        @jax.named_scope('pt/residency_update')
        def pt_residency_update(slabs, batch, start):
            return {name: jax.lax.dynamic_update_slice_in_dim(
                        slabs[name],
                        _to_slab_rows(batch[name], slab_rows[name]),
                        start, axis=0)
                    for name in slabs}

        return jax.jit(pt_residency_update,
                       donate_argnums=(0,) if self._donate else ())

    def _write(self, slot, rows, wire_dev):
        fn = self._write_fns.get(rows)
        if fn is None:
            fn = self._write_fns[rows] = self._update_program()
        self._slabs = fn(self._slabs, wire_dev, slot)

    def backfill(self, cache, plan):
        """Directly admit every row that no streamed delivery covered.

        With ``drop_last`` the epoch never ships the ragged tail, and a
        mid-epoch resume never re-ships skipped batches — but warm
        serving needs *every* row resident (any row can land anywhere in
        the next epoch's permutation).  Only runs when the budget can
        hold the whole dataset; otherwise admission churn would evict
        rows as fast as it fills them.
        """
        if self._dropped or not self.can_hold_dataset:
            return
        missing = np.flatnonzero(self._slot_of_row < 0)
        for i in range(0, len(missing), self._bs):
            idx = missing[i:i + self._bs]
            host_rows = {name: np.asarray(cache[name])[idx]
                         for name in plan.fields}
            wire = plan.narrow(host_rows)
            if self._device is not None:
                wire_dev = {k: jax.device_put(v, self._device)
                            for k, v in wire.items()}
            else:
                wire_dev = {k: jax.device_put(v) for k, v in wire.items()}
            self.admit(idx, wire_dev)

    # -- warm serving -------------------------------------------------------

    def _slot_map(self):
        if self._slot_map_dev is None:
            self._slot_map_dev = jnp.asarray(self._slot_of_row)
        return self._slot_map_dev

    def _gather_program(self, rows):
        """``(slabs, slot_map, order, start) -> batch`` of ``rows`` rows,
        jitted.  Named for the device trace: the program is
        ``jit_pt_residency_gather`` in ``XLA Modules`` and its operations
        carry the two scopes."""
        if rows in self._gather_fns:
            return self._gather_fns[rows]
        fields = self._plan.fields

        def pt_residency_gather(slabs, slot_map, order, start):
            with jax.named_scope('pt/residency_gather'):
                idx = jax.lax.dynamic_slice_in_dim(order, start, rows)
                slots = jnp.take(slot_map, idx)
                batch = {}
                for name, slab in slabs.items():
                    read = (_copy_rows(slab, slots) if slab.ndim == 3
                            else jnp.take(slab, slots, axis=0))
                    batch[name] = _from_slab_rows(read,
                                                  fields[name].row_shape)
            with jax.named_scope('pt/residency_widen'):
                return {name: batch[name].astype(jnp.dtype(fields[name].out))
                        for name in batch}

        fn = self._gather_fns[rows] = jax.jit(pt_residency_gather)
        return fn

    def gather(self, order_dev, start):
        """One warm full batch: jitted slice→map→read→widen, zero host work."""
        self._c.hits.inc()
        return self._gather_program(self._bs)(
            self._slabs, self._slot_map(), order_dev, start)

    def gather_tail(self, order_dev, start):
        """Ragged final batch (``drop_last=False``): the same program at the
        tail's length, once per epoch."""
        self._c.hits.inc()
        return self._gather_program(self._n - int(start))(
            self._slabs, self._slot_map(), order_dev, start)

    # -- teardown -----------------------------------------------------------

    def drop(self):
        """Release the tier (explicit buffer delete); loader falls back to
        streaming.  Safe to call mid-epoch and more than once."""
        if self._dropped:
            return
        _decisions.record_decision(
            'residency', 'drop', 'residency_budget',
            {'entries': len(self._entries),
             'resident_rows': self.resident_rows,
             'capacity': self._capacity})
        if self._slabs is not None:
            live_entries = len(self._entries)
            if live_entries:
                self._c.evictions.inc(live_entries)
            for leaf in self._slabs.values():
                delete = getattr(leaf, 'delete', None)
                if callable(delete):
                    try:
                        delete()
                    except RuntimeError:
                        # Already freed — the slab was donated into a
                        # later admission write; nothing left to release.
                        pass
        self._slabs = None
        self._entries.clear()
        self._free = []
        self._bump = 0
        self._slot_of_row[:] = -1
        self._slot_map_dev = None
        self._dropped = True
        self._update_gauges()
