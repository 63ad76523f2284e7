"""Pallas TPU flash attention (forward + backward), MXU-tiled.

Block-wise online-softmax attention: the [seq, seq] score matrix is never
materialised — each loop turn holds one ``block_q × block_k`` tile in VMEM,
folding it into running (max, denominator, output) accumulators.  The
backward pass is the standard flash recomputation split into a dQ kernel
(grid over Q blocks) and a dK/dV kernel (grid over K blocks), using the saved
log-sum-exp instead of stored probabilities.

**Precision follows the inputs.**  Every product feeds the MXU operands of
the dtype q, k, v (and dO) arrive in and accumulates in float32
(``preferred_element_type``): bfloat16 inputs multiply in bfloat16, float32
inputs in float32 (at the compiler's default precision, which on the v5e
rounds float32 operands to bfloat16 for one pass of the MXU: measured, the
parent's float32 products and these bfloat16 ones agree to the last bit
there).  Scores, the running max and denominator, ``lse``,
``delta``, the exponentials and the accumulators of o, dq, dk and dv are
float32 whatever the inputs are; the probabilities ``p`` and ``ds`` are
rounded to the operand dtype only at the product that consumes them.

**Tiles visited.**  Without ``segment_ids`` a kernel visits every tile (the
causal triangle when ``causal``).  With them, a (q block, k block) tile can
hold a pair the model needs only if the two blocks' ranges of nonzero ids
intersect, so each call computes once, in XLA, every block's smallest and
largest nonzero id and from them the run of k blocks worth visiting for each
q block and the run of q blocks for each k block (``_tile_bounds``), hands
the runs to the kernels as prefetched scalars, and the kernels loop over
``[lo, hi)`` only.  The test is conservative for ANY ids: disjoint ranges
cannot hold an equal pair, and a tile inside a run that holds no pair (ids
not laid end to end) is masked to nothing, as every visited tile is masked
by segment, position and padding.  ``tile_visits`` counts, with the same
function on NumPy arrays, what a call visits.

**Tile shape and masks.**  A tile is ``block_default(seq_len)`` square
unless the caller names its blocks (512 where the length allows: measured, see
there).  Of a tile's three masks (positions past the sequence's end, keys
after the query, other documents' keys) a call builds only those its static
shape can need: none for padding when the length is a whole number of blocks,
none at all for a dense call that is neither.  Choosing per TILE (a
``lax.cond`` to a mask-free body where a tile lies under the diagonal inside
one document) was measured and lost 13 % on the chip: the branch costs more
than the masks.

Used standalone and as the ``attn_fn`` inside
``petastorm_tpu.parallel.ulysses_attention`` (each device's local full-
sequence attention after the all-to-all) — the composition that makes long
context cheap: Ulysses moves the data, this kernel keeps HBM traffic at
O(seq · head_dim).

**Two head sizes.**  q and k share one head size ``d`` and v, o, dO and dV
another, ``d_v`` (a latent-attention layer has q/k of 128 + 64 = 192 and v of
128): each array is laid in HBM and VMEM at its own size, the score products
contract over ``d``, the value products over the tile, and the accumulators
of o and dv are ``[block, d_v]``, those of dq and dk ``[block, d]``.  A call
with ``d == d_v`` traces to what it traced to before there were two.  The
default scale is ``d ** -0.5``, the q/k head size's.

K and V are CHUNKED: each kernel call holds one ``kv_chunk`` (default sized
from VMEM bytes, ``kv_chunk_default``) of K/V in VMEM, and chunks are folded at the XLA level with the same
normalized-(output, lse) merge the ring fold uses — so a single device
streams arbitrary ``seq_len`` (the old ~8k VMEM cliff is gone; beyond one
device's FLOPs, shard with ring/Ulysses).  The backward pass streams the
same way: dQ accumulates over K/V chunks, dK/dV over Q chunks, all against
the global lse/delta.  A chunked call takes the same runs of tiles, clipped
to its chunk.

**Kept across recomputation.**  The forward's ``out`` and ``lse`` (after the
fold, on the chunked path) carry the checkpoint names of ``KEPT``.  A
``jax.checkpoint`` whose policy saves those names (``save_only_these_names``,
as ``models.transformer.REMAT_POLICY`` does for every recomputed block) keeps
them from the forward to the backward pass, so its recomputed forward calls
no forward kernel: a layer keeps ``batch x seq x heads x d_v`` in the
compute dtype and ``batch x heads x seq`` float32 for it.  Under Ulysses,
whose local attention is this kernel, each device keeps its local share of
them the same way.  Outside such a checkpoint the names change nothing.

No reference equivalent (the reference has no compute kernels at all,
SURVEY.md §2.6).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Shared with ring attention so masked-softmax semantics never diverge.
from petastorm_tpu.parallel.ring_attention import NEG_INF

#: The checkpoint names of a forward call's ``out`` and ``lse``: what a
#: recomputed block keeps so that it calls no forward kernel again.
KEPT = ('pt_flash_out', 'pt_flash_lse')


def _auto_interpret():
    return jax.default_backend() != 'tpu'


# ---------------------------------------------------------------------------
# which tiles a packed call visits
# ---------------------------------------------------------------------------

def _reaches_diagonal(n_q, n_k, block_q, block_k, xp):
    """``[n_q, n_k]``: the tile's last query is not before its first key."""
    return ((xp.arange(n_q)[:, None] + 1) * block_q
            > xp.arange(n_k)[None, :] * block_k)


def _inside_window(n_q, n_k, block_q, block_k, window, xp):
    """``[n_q, n_k]``: the tile holds a pair whose query lies fewer than
    ``window`` positions after its key (its first query against its last
    key)."""
    return (xp.arange(n_q)[:, None] * block_q
            - (xp.arange(n_k)[None, :] + 1) * block_k < window - 1)


def _tile_bounds(seg, block_q, block_k, causal, xp=jnp, window=None):
    """The runs of tiles worth visiting for ``seg`` (``[batch, seq_pad]``
    int, 0 = padding, ``seq_pad`` a multiple of both blocks).

    A tile (q block i, k block j) is worth visiting when the two blocks'
    ranges of nonzero ids intersect and, if ``causal``, the tile reaches the
    diagonal, and, with a ``window``, holds a pair whose query lies fewer
    than ``window`` positions after its key.  Returns ``(kv_lo, kv_hi)``,
    ``[batch, seq_pad // block_q]``: the k blocks ``[lo, hi)`` from the first
    to the last worth visiting for each q block, and ``(q_lo, q_hi)``,
    ``[batch, seq_pad // block_k]``: the same for each k block; ``lo == hi`` where none is.  ``xp`` is ``jnp`` (in
    the call, traced) or ``numpy`` (``tile_visits``)."""
    info = np.iinfo(np.int32)

    def id_range(block):
        ids = seg.reshape(seg.shape[0], -1, block)
        live = ids != 0
        return (xp.where(live, ids, info.max).min(-1),
                xp.where(live, ids, info.min).max(-1))

    q_min, q_max = id_range(block_q)
    k_min, k_max = id_range(block_k)
    visit = ((q_min[:, :, None] <= k_max[:, None, :])
             & (k_min[:, None, :] <= q_max[:, :, None]))
    n_q, n_k = visit.shape[1:]
    if causal:
        visit &= _reaches_diagonal(n_q, n_k, block_q, block_k, xp)
    if window is not None:
        visit &= _inside_window(n_q, n_k, block_q, block_k, window, xp)

    def run(axis, n):
        at = xp.arange(n).reshape((n, 1) if axis == 1 else (1, n))
        hi = xp.where(visit, at + 1, 0).max(axis)
        lo = xp.minimum(xp.where(visit, at, n).min(axis), hi)
        return lo.astype(xp.int32), hi.astype(xp.int32)

    return run(2, n_k), run(1, n_q)


def _tile_maps(segment_ids, block_q, block_k, causal, window=None):
    """``(visited, triangle, holding)`` as boolean maps of tiles, ``[batch, q
    blocks, k blocks]`` (``triangle``: ``[q blocks, k blocks]``); see
    ``tile_visits``."""
    seg = np.asarray(segment_ids, np.int32)
    seg = np.pad(seg, ((0, 0), (0, -seg.shape[1] % math.lcm(block_q, block_k))))
    (lo, hi), _ = _tile_bounds(seg, block_q, block_k, causal, xp=np, window=window)
    n_q, n_k = seg.shape[1] // block_q, seg.shape[1] // block_k
    at = np.arange(n_k)
    visited = (lo[:, :, None] <= at) & (at < hi[:, :, None])
    triangle = (_reaches_diagonal(n_q, n_k, block_q, block_k, np) if causal
                else np.ones((n_q, n_k), bool))
    if window is not None:
        triangle = triangle & _inside_window(n_q, n_k, block_q, block_k, window, np)
    holding = np.zeros(visited.shape, bool)
    pos = np.arange(seg.shape[1])
    for row, held in zip(seg, holding):
        for i in range(n_q):
            q = slice(i * block_q, (i + 1) * block_q)
            pair = (row[q, None] == row[None, :]) & (row[q, None] != 0)
            if causal:
                pair &= pos[q, None] >= pos[None, :]
            if window is not None:
                pair &= pos[q, None] - pos[None, :] < window
            held[i] = pair.reshape(block_q, n_k, block_k).any(axis=(0, 2))
    return visited, triangle, holding


def tile_visits(segment_ids, block_q=128, block_k=128, causal=True, window=None):
    """What a packed call visits, counted on the host: ``(visited, triangle,
    holding)`` tiles over all rows of ``segment_ids`` (``[batch, seq]``).

    ``visited``: the tiles the forward and dQ kernels loop over (the runs of
    ``_tile_bounds``, the function the call itself uses; for ids laid end to
    end the dK/dV kernel visits the same tiles).  ``triangle``: the tiles a
    call without ``segment_ids`` visits (those that reach the diagonal when
    ``causal``, else all; with a ``window``, only those inside it).
    ``holding``: the tiles that hold at least one pair the model needs (same
    nonzero id, key not after query when ``causal``, fewer than ``window``
    positions apart with one), counted pair by pair: every one of them is
    visited."""
    visited, triangle, holding = _tile_maps(segment_ids, block_q, block_k,
                                            causal, window)
    return (int(visited.sum()), len(visited) * int(triangle.sum()),
            int(holding.sum()))


def _pallas(kernel, name, grid, in_specs, out_specs, out_shape, bounds, args,
            interpret):
    """One kernel call; ``bounds`` (the runs of a packed call, else none) ride
    as prefetched scalars ahead of ``args``."""
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(bounds), grid=grid, in_specs=in_specs,
            out_specs=out_specs),
        out_shape=out_shape, interpret=interpret, name=name,
    )(*bounds, *args)


def _mask(*parts):
    """The conjunction of a tile's masks; a part that the call's static
    shape rules out (``False``: no padding, not causal, not packed) costs
    nothing.  ``None`` when every pair of the tile counts."""
    parts = [part for part in parts if part is not False]
    return functools.reduce(jnp.logical_and, parts) if parts else None


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, scale, causal, seq_len, padded, block_q, block_k,
                packed, heads, k_start, kv_blocks, window):
    if packed:
        lo_ref, hi_ref, q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref, lse_ref = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
    qi = pl.program_id(1)
    q = q_ref[0]  # [block_q, d], fed to the MXU in the dtype it arrives in
    d_v = v_ref.shape[-1]    # v and o have a head size of their own

    # ``k_ref`` holds one K/V CHUNK starting at absolute position
    # ``k_start`` (k_start=0, kv_blocks=whole-sequence for the unchunked
    # call); all masks work in absolute positions so chunked calls fold
    # into exactly the unchunked result.
    first_kv = 0
    num_kv = jnp.minimum(kv_blocks,
                         jnp.maximum(0, pl.cdiv(seq_len - k_start, block_k)))
    if causal:
        # Blocks strictly above the diagonal contribute nothing.
        num_kv = jnp.minimum(num_kv, jnp.maximum(
            0, pl.cdiv((qi + 1) * block_q - k_start, block_k)))
    if packed:
        # The run of k blocks whose ids can meet this q block's, in blocks
        # of this chunk.
        at = (pl.program_id(0) // heads) * pl.num_programs(1) + qi
        first_kv = jnp.maximum(lo_ref[at] - k_start // block_k, 0)
        num_kv = jnp.minimum(num_kv, hi_ref[at] - k_start // block_k)
        # Packed rows: queries only see keys of their own NONZERO segment
        # (0 marks padding in both roles).
        sq = sq_ref[0, 0][:, None]                              # [block_q, 1]
        sq_live = sq != 0
    if window is not None:
        # Blocks wholly before the window of the block's first query.
        first_kv = jnp.maximum(first_kv, jnp.maximum(
            qi * block_q - (window - 1) - k_start, 0) // block_k)

    # Positions relative to the block's first key: a tile's masks compare
    # them with one scalar each.
    k_col = jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    q_ahead = qi * block_q - k_start + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) - k_col    # q_pos - k_pos + kb * block_k

    def body(kb, carry):
        o, l, m = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        mask = _mask(
            # padded keys never attend
            padded and k_col < seq_len - k_start - kb * block_k,
            causal and q_ahead >= kb * block_k,
            window is not None and q_ahead < window + kb * block_k,
            packed and (sq == sk_ref[0, 0, pl.ds(kb * block_k, block_k)]
                        [None, :]) & sq_live)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.where(m == NEG_INF, 0.0, jnp.exp(m - m_new))
        # A row that has met no key yet holds NEG_INF throughout: against a
        # max of 0 its exponentials are exactly 0.
        p = jnp.exp(s - jnp.where(m_new == NEG_INF, 0.0, m_new)[:, None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        o_new = o * alpha[:, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return o_new, l_new, m_new

    o0 = jnp.zeros((block_q, d_v), jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    o, l, m = jax.lax.fori_loop(first_kv, num_kv, body, (o0, l0, m0))

    l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
    o_ref[0] = (o / l_safe[:, None]).astype(o_ref.dtype)
    lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(l_safe))
    # lse rides as [bh, 1, seq]: a (1, 1, block_q) block keeps the last-two
    # block dims Mosaic-legal (second-to-last equals the full array dim).
    lse_ref[0, 0] = lse.astype(jnp.float32)


def _fwd(q3, k3, v3, seg3, seg3_k, kv_run, scale, causal, seq_len, block_q,
         block_k, packed, heads, interpret, k_start=0, window=None):
    """One forward kernel call: full Q against the K/V chunk ``k3``/``v3``
    (absolute start ``k_start``).  ``seg3`` is the q-side segment array
    (full length), ``seg3_k`` the k-side chunk slice, ``kv_run`` the flat
    ``(lo, hi)`` of ``_tile_bounds`` for each (batch row, q block)."""
    bh, seq_pad, d = q3.shape
    kv_pad, d_v = k3.shape[1], v3.shape[2]
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda i, j, *_: (i, j, 0)),
        pl.BlockSpec((1, kv_pad, d), lambda i, j, *_: (i, 0, 0)),
        pl.BlockSpec((1, kv_pad, d_v), lambda i, j, *_: (i, 0, 0)),
    ]
    args = [q3, k3, v3]
    if packed:
        # seg3 is [batch, 1, seq_pad]; every head of a batch row shares it,
        # so the index map folds the (batch*heads) grid axis back down.
        in_specs += [
            pl.BlockSpec((1, 1, block_q), lambda i, j, *_: (i // heads, 0, j)),
            pl.BlockSpec((1, 1, kv_pad), lambda i, j, *_: (i // heads, 0, 0)),
        ]
        args += [seg3, seg3_k]
    return _pallas(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          seq_len=seq_len, padded=seq_len != seq_pad,
                          block_q=block_q, block_k=block_k,
                          packed=packed, heads=heads, k_start=k_start,
                          kv_blocks=kv_pad // block_k, window=window),
        'pt_flash_fwd', (bh, seq_pad // block_q), in_specs,
        [pl.BlockSpec((1, block_q, d_v), lambda i, j, *_: (i, j, 0)),
         pl.BlockSpec((1, 1, block_q), lambda i, j, *_: (i, 0, j))],
        [jax.ShapeDtypeStruct((bh, seq_pad, d_v), q3.dtype),
         jax.ShapeDtypeStruct((bh, 1, seq_pad), jnp.float32)],
        kv_run, args, interpret)


def _fold_normalized(o1, lse1, o2, lse2):
    """Merge two normalized partial attentions (softmax weight exp(lse)).

    The chunk-level analog of the ring hop fold: o = Σ o_i·exp(lse_i) /
    Σ exp(lse_i), with fully-masked (lse == NEG_INF) parts contributing
    exactly zero.  ``o*`` are [bh, seq, d] fp32, ``lse*`` [bh, 1, seq]."""
    m = jnp.maximum(lse1, lse2)
    m_safe = jnp.where(m == NEG_INF, 0.0, m)
    w1 = jnp.where(lse1 == NEG_INF, 0.0, jnp.exp(lse1 - m_safe))
    w2 = jnp.where(lse2 == NEG_INF, 0.0, jnp.exp(lse2 - m_safe))
    denom = w1 + w2
    safe = jnp.where(denom == 0.0, 1.0, denom)
    wa = jnp.swapaxes(w1 / safe, 1, 2)          # [bh, seq, 1]
    wb = jnp.swapaxes(w2 / safe, 1, 2)
    o = o1 * wa + o2 * wb
    lse = jnp.where(denom == 0.0, NEG_INF, m_safe + jnp.log(safe))
    return o, lse


def _runs(seg3, block_q, block_k, causal, window=None):
    """``_tile_bounds`` of a packed call, flat for the kernels' scalar
    memory: ``((kv_lo, kv_hi), (q_lo, q_hi))``; empty without segment ids."""
    if seg3 is None:
        return (), ()
    kv_run, q_run = _tile_bounds(seg3[:, 0], block_q, block_k, causal,
                                 window=window)
    return (tuple(x.reshape(-1) for x in kv_run),
            tuple(x.reshape(-1) for x in q_run))


def _fwd_chunked(q3, k3, v3, seg3, kv_run, scale, causal, seq_len, block_q,
                 block_k, packed, heads, interpret, kv_chunk, window=None):
    """Stream K/V through the forward kernel in ``kv_chunk`` slices.

    VMEM per call is one chunk instead of the whole sequence — the piece
    that removes the single-device seq-length cliff.  Accumulation stays
    fp32 across folds; the final cast matches the unchunked kernel."""
    bh, seq_pad, d = q3.shape
    o = None
    lse = None
    for c0 in range(0, seq_pad, kv_chunk):
        c1 = min(c0 + kv_chunk, seq_pad)
        k_c = jax.lax.slice_in_dim(k3, c0, c1, axis=1)
        v_c = jax.lax.slice_in_dim(v3, c0, c1, axis=1)
        seg_k = (jax.lax.slice_in_dim(seg3, c0, c1, axis=2)
                 if packed else None)
        o_c, lse_c = _fwd(q3, k_c, v_c, seg3, seg_k, kv_run, scale, causal,
                          seq_len, block_q, block_k, packed, heads, interpret,
                          k_start=c0, window=window)
        o_c = o_c.astype(jnp.float32)
        if o is None:
            o, lse = o_c, lse_c
        else:
            o, lse = _fold_normalized(o, lse, o_c, lse_c)
    return o.astype(q3.dtype), lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(*refs, scale, causal, seq_len, padded, block_q, block_k,
                   packed, heads, k_start, kv_blocks, window):
    if packed:
        (lo_ref, hi_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         sq_ref, sk_ref, dq_ref) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref = refs
    qi = pl.program_id(1)
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, 0]       # [block_q]
    delta = delta_ref[0, 0]   # [block_q]
    d = q.shape[-1]

    # Chunk-relative K/V (absolute start ``k_start``): dq contributions
    # against the GLOBAL lse/delta are additive across chunks.
    first_kv = 0
    num_kv = jnp.minimum(kv_blocks,
                         jnp.maximum(0, pl.cdiv(seq_len - k_start, block_k)))
    if causal:
        num_kv = jnp.minimum(num_kv, jnp.maximum(
            0, pl.cdiv((qi + 1) * block_q - k_start, block_k)))
    if packed:
        at = (pl.program_id(0) // heads) * pl.num_programs(1) + qi
        first_kv = jnp.maximum(lo_ref[at] - k_start // block_k, 0)
        num_kv = jnp.minimum(num_kv, hi_ref[at] - k_start // block_k)
        sq = sq_ref[0, 0][:, None]
        sq_live = sq != 0
    if window is not None:
        first_kv = jnp.maximum(first_kv, jnp.maximum(
            qi * block_q - (window - 1) - k_start, 0) // block_k)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(kb, dq):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :]
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :]
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = k_start + kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        # Padded query rows carry lse == NEG_INF; without the q_pos guard
        # exp(s - NEG_INF) overflows to inf and poisons ds with NaNs.
        mask = _mask(padded and (k_pos < seq_len) & (q_pos < seq_len),
                     causal and q_pos >= k_pos,
                     window is not None and q_pos - k_pos < window,
                     packed and (sq == sk_ref[0, 0, pl.ds(kb * block_k, block_k)]
                                 [None, :]) & sq_live)
        # exp(s - lse) == softmax row (lse = m + log l); masked/empty rows
        # have lse == NEG_INF and p underflows to 0.
        p = jnp.exp(s - lse[:, None])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])      # x scale: once, on the sum
        return dq + jax.lax.dot_general(
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(first_kv, num_kv, body,
                           jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(*refs, scale, causal, seq_len, padded, block_q, block_k,
                    packed, heads, q_start, k_start, q_blocks, k_blocks_total,
                    window):
    if packed:
        (lo_ref, hi_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         sq_ref, sk_ref, dk_ref, dv_ref) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref = refs
    ki = pl.program_id(1)
    k = k_ref[0]  # [block_k, d]
    v = v_ref[0]  # [block_k, d_v]

    # ``q_ref``/``do_ref``/``lse_ref``/``delta_ref`` hold one Q chunk
    # (absolute start ``q_start``); k blocks are chunk-relative with
    # absolute start ``k_start``.  dk/dv contributions against the global
    # lse/delta are additive across Q chunks.
    num_q = jnp.minimum(q_blocks,
                        jnp.maximum(0, pl.cdiv(seq_len - q_start, block_q)))
    if causal:
        q_begin = jnp.clip((k_start + ki * block_k - q_start) // block_q,
                           0, num_q)
    else:
        q_begin = 0
    if window is not None:
        # Q blocks wholly past the window of the block's last key.
        num_q = jnp.minimum(num_q, jnp.maximum(0, pl.cdiv(
            k_start + (ki + 1) * block_k + window - 1 - q_start, block_q)))
    if packed:
        # The run of q blocks whose ids can meet this k block's, in blocks
        # of this Q chunk.
        at = ((pl.program_id(0) // heads) * k_blocks_total
              + k_start // block_k + ki)
        q_begin = jnp.maximum(q_begin, lo_ref[at] - q_start // block_q)
        num_q = jnp.minimum(num_q, hi_ref[at] - q_start // block_q)
        sk = sk_ref[0, 0][None, :]                              # [1, block_k]
        sk_live = sk != 0
    k_pos = k_start + ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    def body(qb, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(qb * block_q, block_q), :]
        do_blk = do_ref[0, pl.ds(qb * block_q, block_q), :]
        lse_blk = lse_ref[0, 0, pl.ds(qb * block_q, block_q)]
        delta_blk = delta_ref[0, 0, pl.ds(qb * block_q, block_q)]
        s = jax.lax.dot_general(q_blk, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = q_start + qb * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        mask = _mask(padded and (k_pos < seq_len) & (q_pos < seq_len),
                     causal and q_pos >= k_pos,
                     window is not None and q_pos - k_pos < window,
                     packed and (sq_ref[0, 0, pl.ds(qb * block_q, block_q)]
                                 [:, None] == sk) & sk_live)
        p = jnp.exp(s - lse_blk[:, None])
        if mask is not None:
            p = jnp.where(mask, p, 0.0)
        dv = dv + jax.lax.dot_general(
            p.astype(do_blk.dtype), do_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do_blk, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_blk[:, None])  # x scale: once, on the sum
        dk = dk + jax.lax.dot_general(
            ds.astype(q_blk.dtype), q_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    dk, dv = jax.lax.fori_loop(q_begin, num_q, body, (
        jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32)))
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_dq_call(q3, k_c, v_c, seg3, seg_k, kv_run, do3, lse, delta, scale,
                 causal, seq_len, block_q, block_k, packed, heads, interpret,
                 k_start, window=None):
    """dQ contribution of one K/V chunk (full Q streamed block-by-block)."""
    bh, seq_pad, d = q3.shape
    kv_pad, d_v = k_c.shape[1], v_c.shape[2]
    dq_specs = [
        pl.BlockSpec((1, block_q, d), lambda i, j, *_: (i, j, 0)),
        pl.BlockSpec((1, kv_pad, d), lambda i, j, *_: (i, 0, 0)),
        pl.BlockSpec((1, kv_pad, d_v), lambda i, j, *_: (i, 0, 0)),
        pl.BlockSpec((1, block_q, d_v), lambda i, j, *_: (i, j, 0)),
        pl.BlockSpec((1, 1, block_q), lambda i, j, *_: (i, 0, j)),
        pl.BlockSpec((1, 1, block_q), lambda i, j, *_: (i, 0, j)),
    ]
    dq_args = [q3, k_c, v_c, do3, lse, delta]
    if packed:
        dq_specs += [
            pl.BlockSpec((1, 1, block_q), lambda i, j, *_: (i // heads, 0, j)),
            pl.BlockSpec((1, 1, kv_pad), lambda i, j, *_: (i // heads, 0, 0)),
        ]
        dq_args += [seg3, seg_k]
    return _pallas(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          seq_len=seq_len, padded=seq_len != seq_pad,
                          block_q=block_q, block_k=block_k,
                          packed=packed, heads=heads, k_start=k_start,
                          kv_blocks=kv_pad // block_k, window=window),
        'pt_flash_bwd_dq', (bh, seq_pad // block_q), dq_specs,
        pl.BlockSpec((1, block_q, d), lambda i, j, *_: (i, j, 0)),
        jax.ShapeDtypeStruct((bh, seq_pad, d), q3.dtype),
        kv_run, dq_args, interpret)


def _bwd_dkv_call(q_c, k_c, v_c, seg_q, seg_k, q_run, do_c, lse_c, delta_c,
                  scale, causal, seq_len, block_q, block_k, packed, heads,
                  interpret, q_start, k_start, seq_pad, window=None):
    """dK/dV contribution of one Q chunk against one K/V chunk."""
    bh, q_pad, d = q_c.shape
    kv_pad, d_v = k_c.shape[1], v_c.shape[2]
    dkv_specs = [
        pl.BlockSpec((1, q_pad, d), lambda i, j, *_: (i, 0, 0)),
        pl.BlockSpec((1, block_k, d), lambda i, j, *_: (i, j, 0)),
        pl.BlockSpec((1, block_k, d_v), lambda i, j, *_: (i, j, 0)),
        pl.BlockSpec((1, q_pad, d_v), lambda i, j, *_: (i, 0, 0)),
        pl.BlockSpec((1, 1, q_pad), lambda i, j, *_: (i, 0, 0)),
        pl.BlockSpec((1, 1, q_pad), lambda i, j, *_: (i, 0, 0)),
    ]
    dkv_args = [q_c, k_c, v_c, do_c, lse_c, delta_c]
    if packed:
        dkv_specs += [
            pl.BlockSpec((1, 1, q_pad), lambda i, j, *_: (i // heads, 0, 0)),
            pl.BlockSpec((1, 1, block_k), lambda i, j, *_: (i // heads, 0, j)),
        ]
        dkv_args += [seg_q, seg_k]
    return _pallas(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          seq_len=seq_len, padded=seq_len != seq_pad,
                          block_q=block_q, block_k=block_k,
                          packed=packed, heads=heads, q_start=q_start,
                          k_start=k_start, q_blocks=q_pad // block_q,
                          k_blocks_total=seq_pad // block_k, window=window),
        'pt_flash_bwd_dkv', (bh, kv_pad // block_k), dkv_specs,
        [pl.BlockSpec((1, block_k, d), lambda i, j, *_: (i, j, 0)),
         pl.BlockSpec((1, block_k, d_v), lambda i, j, *_: (i, j, 0))],
        [jax.ShapeDtypeStruct((bh, kv_pad, d), k_c.dtype),
         jax.ShapeDtypeStruct((bh, kv_pad, d_v), v_c.dtype)],
        q_run, dkv_args, interpret)


def _bwd(q3, k3, v3, seg3, o3, lse, do3, scale, causal, seq_len, block_q,
         block_k, packed, heads, interpret, kv_chunk=None, window=None):
    """Backward pass, K/V (and Q, for dK/dV) streamed in chunks.

    Per-chunk contributions computed against the GLOBAL lse/delta are
    plain sums — no softmax refold needed in the backward direction."""
    bh, seq_pad, d = q3.shape
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)[:, None, :]  # [bh, 1, seq] like lse
    chunk = kv_chunk if kv_chunk is not None else seq_pad
    chunk = min(chunk, seq_pad)
    kv_run, q_run = _runs(seg3, block_q, block_k, causal, window)

    def sl(x, lo, hi, axis=1):
        return jax.lax.slice_in_dim(x, lo, hi, axis=axis)

    dq = None
    dk_parts, dv_parts = [], []
    for c0 in range(0, seq_pad, chunk):
        c1 = min(c0 + chunk, seq_pad)
        k_c, v_c = sl(k3, c0, c1), sl(v3, c0, c1)
        seg_k = sl(seg3, c0, c1, axis=2) if packed else None
        dq_c = _bwd_dq_call(q3, k_c, v_c, seg3, seg_k, kv_run, do3, lse,
                            delta, scale, causal, seq_len, block_q, block_k,
                            packed, heads, interpret, k_start=c0,
                            window=window)
        # Partials accumulate in fp32 at the XLA level (the single-call
        # path accumulates in fp32 inside the kernel; chunking must not
        # lose that).
        dq_c = dq_c.astype(jnp.float32)
        dq = dq_c if dq is None else dq + dq_c
        dk_c = None
        dv_c = None
        for r0 in range(0, seq_pad, chunk):
            r1 = min(r0 + chunk, seq_pad)
            if causal and r1 <= c0:
                continue  # whole Q chunk above the diagonal: contributes 0
            dkc, dvc = _bwd_dkv_call(
                sl(q3, r0, r1), k_c, v_c,
                sl(seg3, r0, r1, axis=2) if packed else None, seg_k, q_run,
                sl(do3, r0, r1), sl(lse, r0, r1, axis=2),
                sl(delta, r0, r1, axis=2), scale, causal, seq_len, block_q,
                block_k, packed, heads, interpret, q_start=r0, k_start=c0,
                seq_pad=seq_pad, window=window)
            dkc = dkc.astype(jnp.float32)
            dvc = dvc.astype(jnp.float32)
            dk_c = dkc if dk_c is None else dk_c + dkc
            dv_c = dvc if dv_c is None else dv_c + dvc
        if dk_c is None:  # every Q chunk skipped (can't happen, but safe)
            dk_c = jnp.zeros(k_c.shape, jnp.float32)
            dv_c = jnp.zeros(v_c.shape, jnp.float32)
        dk_parts.append(dk_c)
        dv_parts.append(dv_c)
    dk = dk_parts[0] if len(dk_parts) == 1 else jnp.concatenate(dk_parts, axis=1)
    dv = dv_parts[0] if len(dv_parts) == 1 else jnp.concatenate(dv_parts, axis=1)
    return dq.astype(q3.dtype), dk.astype(k3.dtype), dv.astype(v3.dtype)


# ---------------------------------------------------------------------------
# public op
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12))
def _flash(q3, k3, v3, seg3, scale, causal, seq_len, block_q, block_k, packed,
           heads, kv_chunk, window):
    out, _ = _flash_fwd(q3, k3, v3, seg3, scale, causal, seq_len, block_q,
                        block_k, packed, heads, kv_chunk, window)
    return out


def _flash_fwd(q3, k3, v3, seg3, scale, causal, seq_len, block_q, block_k,
               packed, heads, kv_chunk, window):
    seq_pad = q3.shape[1]
    kv_run, _ = _runs(seg3, block_q, block_k, causal, window)
    if kv_chunk is None or kv_chunk >= seq_pad:
        out, lse = _fwd(q3, k3, v3, seg3, seg3, kv_run, scale, causal,
                        seq_len, block_q, block_k, packed, heads,
                        interpret=_auto_interpret(), window=window)
    else:
        out, lse = _fwd_chunked(q3, k3, v3, seg3, kv_run, scale, causal,
                                seq_len, block_q, block_k, packed, heads,
                                interpret=_auto_interpret(),
                                kv_chunk=kv_chunk, window=window)
    out, lse = checkpoint_name(out, KEPT[0]), checkpoint_name(lse, KEPT[1])
    return out, (q3, k3, v3, seg3, out, lse)


def _flash_bwd(scale, causal, seq_len, block_q, block_k, packed, heads,
               kv_chunk, window, res, g):
    q3, k3, v3, seg3, out, lse = res
    dq, dk, dv = _bwd(q3, k3, v3, seg3, out, lse, g, scale, causal, seq_len,
                      block_q, block_k, packed, heads,
                      interpret=_auto_interpret(), kv_chunk=kv_chunk,
                      window=window)
    # Integer operands take a float0 cotangent (segment ids are labels);
    # the non-packed path carries seg3=None (empty pytree, no cotangent).
    dseg = (None if seg3 is None
            else np.zeros(seg3.shape, dtype=jax.dtypes.float0))
    return dq, dk, dv, dseg


_flash.defvjp(_flash_fwd, _flash_bwd)


#: VMEM one kernel call may spend on its resident K/V chunk (the Q/dO chunk
#: in the dK/dV kernel), counted the way the Pallas pipeline allocates it:
#: two operands, each double-buffered.  Half of the 16 MiB scoped-VMEM limit
#: Mosaic gives a kernel on v5e; the other half is left to the streamed
#: blocks, the lse/delta/segment rows and the fp32 temporaries.
KV_CHUNK_VMEM_BYTES = 8 << 20


def kv_chunk_default(head_dim, dtype, v_head_dim=None):
    """Rows of K/V resident per kernel call when ``kv_chunk`` is not given:
    the most that keeps double-buffered K and V inside
    ``KV_CHUNK_VMEM_BYTES`` (bf16 d=128: 8192 rows; f32 d=128: 4096).
    Sequences padded beyond this stream K/V in chunks of this many rows.
    A row occupies whole 128-lane tiles in VMEM whatever ``head_dim`` is, and
    K and V each their own where ``v_head_dim`` differs: q/k of 192 take 256
    lanes and v of 128 takes 128, so bf16 at 192/128 holds 5461 rows (one
    chunk for a row of 4096 tokens; 4096 rows if v were padded to 192).  The
    dK/dV kernel's resident Q and dO chunk has the same two widths."""
    def lanes(d):
        return -(-d // 128) * 128
    both = lanes(head_dim) + lanes(v_head_dim or head_dim)
    return KV_CHUNK_VMEM_BYTES // (2 * both * jnp.dtype(dtype).itemsize)


def block_default(seq_len):
    """Rows and columns of a tile when the caller names none: the largest of
    512, 256 and 128 that pads ``seq_len`` by at most an eighth (a sequence
    that fits one tile takes one).

    Measured on one TPU v5e, device ms of the three kernels for one forward
    and backward call (PR 29; ``PERF.md`` section 6): a turn of the tile loop
    costs the VPU and the loop about as much at 128 x 128 as the products of
    a tile four times the size, so larger tiles win until VMEM runs out, and
    they win although a packed call skips fewer of them.

    ==============================================  =====  =====  =====
    call (causal)                                     128    256    512
    ==============================================  =====  =====  =====
    bf16 ``[4, 8192, 32, 64]``, packed documents    150.8   78.8   49.8
    bf16 ``[4, 2048, 16, 128]``                      10.2    5.4    3.2
    f32 ``[4, 2048, 16, 128]``                       10.3    5.5    3.4
    bf16 ``[1, 16384, 8, 128]`` (two K/V chunks)     74.8           18.6
    bf16 ``[8, 2048, 16, 64]``, packed documents     17.4    9.8    6.7
    ==============================================  =====  =====  =====

    Rectangles at the first shape: 256 x 512 54.4, 512 x 256 71.3, 512 x 1024
    52.5, 1024 x 512 55.3; 1024 x 1024 does not fit VMEM.  Neither
    ``head_dim`` nor the dtype nor packing moved the winner, so the rule
    reads the length alone.

    Read again at two head sizes (PR 34; bf16 q/k ``[2, 8192, 32, 192]``, v
    ``[2, 8192, 32, 128]``, packed documents, K/V in two chunks of 5,120 rows;
    wall ms of a jitted forward and backward call, the copies around the
    kernels included): 512 x 512 35.0, 256 x 512 34.4, 512 x 256 38.2, 256 x
    256 37.1; 512 x 1024 and 1024 x 512 do not fit VMEM.  The winner is where
    it was to 2 %."""
    for block in (512, 256, 128):
        if seq_len <= block or -seq_len % block * 8 <= seq_len:
            break
    return block



def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, segment_ids=None, kv_chunk=None, window=None):
    """Flash attention over ``[batch, seq, heads, head_dim]`` inputs.

    ``v`` may have a head size of its own (``[batch, seq, heads, d_v]``; q
    and k share theirs): the output then has ``d_v``, and nothing is padded
    to the other's size in HBM.

    Drop-in for ``petastorm_tpu.parallel.full_attention`` (same signature and
    semantics, O(seq) memory).  Differentiable via the flash backward
    kernels.  Sequences are padded to the block size internally; padded keys
    are masked out, padded query rows are sliced off.

    ``segment_ids`` (``[batch, seq]`` int, 0 = padding) restricts attention
    to same-nonzero-segment pairs — the O(seq)-memory path for
    ``petastorm_tpu.jax.packing`` packed rows (same semantics as
    ``packing.packed_attention``, which is the dense oracle).

    ``block_q`` x ``block_k`` is the tile one turn of a kernel's loop holds;
    a caller that names none gets ``block_default(seq_len)`` for both, and
    one that names them is taken at its word (on the TPU rounded up to whole
    128-lane tiles).

    ``kv_chunk`` streams K/V through VMEM in chunks of that many rows
    (auto-enabled above ``kv_chunk_default(head_dim, dtype)`` padded rows;
    ``0`` forces whole-K/V residency), so a single device handles arbitrary
    sequence lengths instead of capping where whole-K/V VMEM residency runs
    out.  The backward pass streams the same way (dQ over K/V chunks, dK/dV
    over Q chunks).

    ``window`` (with ``causal``): a query sees only the ``window`` keys that
    end at it (``0 <= q - k < window``); every kernel skips the tiles that lie
    wholly outside, as it skips those above the diagonal.  ``None``: no
    window, and the call traces to what it traced to before there was one.

    Compiles to Mosaic on TPU; on CPU/GPU backends it runs the same kernels
    through the Pallas interpreter (tests, dry runs).
    """
    if q.ndim != 4:
        raise ValueError('expected [batch, seq, heads, head_dim], got %r' % (q.shape,))
    b, seq_len, h, d = q.shape
    kv_len, d_v = k.shape[1], v.shape[3]
    if kv_len != seq_len:
        raise ValueError('flash_attention requires seq_q == seq_kv (got %d vs %d)'
                         % (seq_len, kv_len))
    if k.shape != q.shape or v.shape[:3] != q.shape[:3]:
        raise ValueError('q and k must have one shape and v their batch, seq '
                         'and heads; got q %r, k %r, v %r'
                         % (q.shape, k.shape, v.shape))
    packed = segment_ids is not None
    if packed and tuple(segment_ids.shape) != (b, seq_len):
        raise ValueError('segment_ids must be [batch, seq] = %r, got %r'
                         % ((b, seq_len), tuple(segment_ids.shape)))
    scale = scale if scale is not None else d ** -0.5
    if window is not None and (not causal or window < 1):
        raise ValueError('a window needs causal attention and window >= 1, '
                         'got causal=%r, window=%r' % (causal, window))

    block_q = min(block_q or block_default(seq_len), max(seq_len, 16))
    block_k = min(block_k or block_default(seq_len), max(seq_len, 16))
    if not _auto_interpret():
        # Mosaic on real TPU rejects non-tile-aligned layouts: block_q/block_k
        # appear as the minor (lane) dim of the lse/delta blocks, so round UP
        # to a 128-lane multiple.  The Pallas interpreter (CI) accepts any
        # block shape — keep the requested sizes there so small-block tests
        # still exercise multi-block grids and the lcm tail-block logic.
        block_q = -(-block_q // 128) * 128
        block_k = -(-block_k // 128) * 128
    # Pad to the lcm so BOTH grids (seq_pad // block_q, seq_pad // block_k)
    # cover the sequence exactly — padding to max() alone drops tail blocks
    # whenever the smaller block doesn't divide the larger.
    lcm = math.lcm(block_q, block_k)
    seq_pad = -(-seq_len // lcm) * lcm

    def to3(x):
        x = jnp.moveaxis(x, 2, 1).reshape(b * h, seq_len, x.shape[3])
        if seq_pad != seq_len:
            x = jnp.pad(x, ((0, 0), (0, seq_pad - seq_len), (0, 0)))
        return x

    if packed:
        seg = jnp.asarray(segment_ids, jnp.int32)
        if seq_pad != seq_len:   # pad with 0 = "padding segment"
            seg = jnp.pad(seg, ((0, 0), (0, seq_pad - seq_len)))
        seg3 = seg[:, None, :]   # [b, 1, seq_pad]; heads share via index map
    else:
        seg3 = None

    if kv_chunk is None:
        kv_chunk = kv_chunk_default(d, q.dtype, d_v)
    if kv_chunk == 0:
        kv_chunk = None      # explicit 0: whole-K/V residency, no streaming
    else:
        # chunk boundaries must land on both block grids
        kv_chunk = max(lcm, (int(kv_chunk) // lcm) * lcm)
        if kv_chunk >= seq_pad:
            kv_chunk = None

    out = _flash(to3(q), to3(k), to3(v), seg3, scale, causal, seq_len,
                 block_q, block_k, packed, h, kv_chunk, window)
    out = out[:, :seq_len].reshape(b, h, seq_len, d_v)
    return jnp.moveaxis(out, 1, 2)
