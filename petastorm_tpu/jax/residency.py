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

from petastorm_tpu.jax.transfer import _supported, wire_dtype_for
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
)


def killed():
    """True when the ``PETASTORM_TPU_NO_RESIDENCY`` kill switch is set."""
    return bool(os.environ.get(KILL_SWITCH))


def donation_supported():
    """Whether buffer donation actually recycles memory on this backend.

    ``jax.jit(..., donate_argnums=...)`` is a no-op (a copy) on CPU; the
    tier still runs there — tests and the CPU-emulated bench leg exercise
    the exact same code path — but the in-place recycling story only
    holds on accelerators.
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


def ensure_counters(metrics):
    """Create the full residency counter shape (all zeros when plane off)."""
    return ResidencyCounters(metrics)


# ---------------------------------------------------------------------------
# The residency LRU tier
# ---------------------------------------------------------------------------

class ResidencyTier(object):
    """Budget-bounded device-resident slab of wire-dtype rows with batch LRU.

    Rows live in per-field slabs of shape ``(capacity,) + row_shape`` in
    the wire dtype.  Each admitted batch occupies a contiguous slot range
    tracked as one LRU entry; ``slot_of_row`` maps dataset row id →
    slab slot (-1 when not resident).  Admission writes through a jitted
    ``dynamic_update_slice_in_dim`` with the slab donated off-CPU, so an
    "eviction" is just the LRU entry releasing its slot range — the bytes
    are overwritten in place by the next donated admission.

    Warm serving is one jitted gather: slice ``batch_size`` row ids out
    of the epoch permutation, map them through the device copy of
    ``slot_of_row``, ``take`` from each slab, and widen — no host work at
    all.
    """

    def __init__(self, plan, n_rows, batch_size, budget_bytes, counters,
                 device=None):
        self._plan = plan
        self._n = int(n_rows)
        self._bs = int(batch_size)
        self._device = device
        row_bytes = max(1, plan.wire_row_nbytes)
        if budget_bytes is None:
            self._capacity = self._n
        else:
            self._capacity = min(self._n, max(0, int(budget_bytes) // row_bytes))
        self._c = counters
        counters.budget.set(int(budget_bytes) if budget_bytes is not None
                            else self._capacity * row_bytes)
        self._slabs = None
        self._entries = OrderedDict()   # seq -> (slot, rows)
        self._seq = 0
        self._free = []                 # list of (slot, rows) released ranges
        self._bump = 0
        self._slot_of_row = np.full(self._n, -1, dtype=np.int32)
        self._slot_map_dev = None
        self._write_fns = {}
        self._gather_fn = None
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
            return {name: jnp.zeros((self._capacity,) + f.row_shape,
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

    def _write(self, slot, rows, wire_dev):
        fn = self._write_fns.get(rows)
        if fn is None:
            @jax.named_scope('pt/residency_update')
            def pt_residency_update(slabs, batch, start):
                return {name: jax.lax.dynamic_update_slice_in_dim(
                            slabs[name], batch[name], start, axis=0)
                        for name in slabs}
            donate = (0,) if self._donate else ()
            fn = jax.jit(pt_residency_update, donate_argnums=donate)
            self._write_fns[rows] = fn
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

    def gather(self, order_dev, start):
        """One warm full batch: jitted slice→map→take→widen, zero host work."""
        if self._gather_fn is None:
            bs = self._bs
            outs = {name: jnp.dtype(f.out)
                    for name, f in self._plan.fields.items()}

            # Named for the device trace: the program is
            # ``jit_pt_residency_gather`` in ``XLA Modules`` and its
            # operations carry the two scopes, where an anonymous ``copy``
            # stood before.
            def pt_residency_gather(slabs, slot_map, order, start):
                with jax.named_scope('pt/residency_gather'):
                    idx = jax.lax.dynamic_slice_in_dim(order, start, bs)
                    slots = jnp.take(slot_map, idx)
                    rows = {name: jnp.take(slabs[name], slots, axis=0)
                            for name in slabs}
                with jax.named_scope('pt/residency_widen'):
                    return {name: rows[name].astype(outs[name])
                            for name in rows}

            self._gather_fn = jax.jit(pt_residency_gather)
        self._c.hits.inc()
        return self._gather_fn(self._slabs, self._slot_map(), order_dev, start)

    def gather_tail(self, order_dev, start):
        """Ragged final batch (``drop_last=False``): unjitted, once per epoch."""
        idx = order_dev[start:]
        slots = jnp.take(self._slot_map(), idx)
        self._c.hits.inc()
        return {name: jnp.take(self._slabs[name], slots,
                               axis=0).astype(jnp.dtype(f.out))
                for name, f in self._plan.fields.items()}

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
