"""Pallas TPU flash attention (forward + backward), MXU-tiled.

Block-wise online-softmax attention: the [seq, seq] score matrix is never
materialised — each grid step holds one ``block_q × block_k`` tile in VMEM,
folding it into running (max, denominator, output) accumulators in fp32
while the matmuls feed the MXU in the input dtype.  The backward pass is
the standard flash recomputation split into a dQ kernel (grid over Q
blocks) and a dK/dV kernel (grid over K blocks), using the saved
log-sum-exp instead of stored probabilities.

Used standalone and as the ``attn_fn`` inside
``petastorm_tpu.parallel.ulysses_attention`` (each device's local full-
sequence attention after the all-to-all) — the composition that makes long
context cheap: Ulysses moves the data, this kernel keeps HBM traffic at
O(seq · head_dim).

K and V are CHUNKED: each kernel call holds one ``kv_chunk`` (default sized
from VMEM bytes, ``kv_chunk_default``) of K/V in VMEM, and chunks are folded at the XLA level with the same
normalized-(output, lse) merge the ring fold uses — so a single device
streams arbitrary ``seq_len`` (the old ~8k VMEM cliff is gone; beyond one
device's FLOPs, shard with ring/Ulysses).  The backward pass streams the
same way: dQ accumulates over K/V chunks, dK/dV over Q chunks, all against
the global lse/delta.

No reference equivalent (the reference has no compute kernels at all,
SURVEY.md §2.6).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Shared with ring attention so masked-softmax semantics never diverge.
from petastorm_tpu.parallel.ring_attention import NEG_INF


def _auto_interpret():
    return jax.default_backend() != 'tpu'


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, *refs, scale, causal,
                seq_len, block_q, block_k, packed, k_start, kv_blocks):
    if packed:
        sq_ref, sk_ref, o_ref, lse_ref = refs
    else:
        o_ref, lse_ref = refs
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)  # [block_q, d]
    d = q.shape[-1]

    # ``k_ref`` holds one K/V CHUNK starting at absolute position
    # ``k_start`` (k_start=0, kv_blocks=whole-sequence for the unchunked
    # call); all masks work in absolute positions so chunked calls fold
    # into exactly the unchunked result.
    num_kv = jnp.minimum(kv_blocks,
                         jnp.maximum(0, pl.cdiv(seq_len - k_start, block_k)))
    if causal:
        # Blocks strictly above the diagonal contribute nothing.
        num_kv = jnp.minimum(num_kv, jnp.maximum(
            0, pl.cdiv((qi + 1) * block_q - k_start, block_k)))

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(kb, carry):
        o, l, m = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = k_start + kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = k_pos < seq_len  # padded keys never attend
        if causal:
            mask &= q_pos >= k_pos
        if packed:
            # Packed rows: queries only see keys of their own NONZERO
            # segment (0 marks padding in both roles).
            sq = sq_ref[0, 0]                                   # [block_q]
            sk = sk_ref[0, 0, pl.ds(kb * block_k, block_k)]     # [block_k]
            mask &= (sq[:, None] == sk[None, :]) & (sq[:, None] != 0)
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.where(m == NEG_INF, 0.0, jnp.exp(m - m_new))
        p = jnp.where(m_new[:, None] == NEG_INF, 0.0, jnp.exp(s - m_new[:, None]))
        l_new = l * alpha + jnp.sum(p, axis=-1)
        o_new = o * alpha[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return o_new, l_new, m_new

    o0 = jnp.zeros((block_q, d), jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    o, l, m = jax.lax.fori_loop(0, num_kv, body, (o0, l0, m0))

    l_safe = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
    o_ref[0] = (o / l_safe[:, None]).astype(o_ref.dtype)
    lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(l_safe))
    # lse rides as [bh, 1, seq]: a (1, 1, block_q) block keeps the last-two
    # block dims Mosaic-legal (second-to-last equals the full array dim).
    lse_ref[0, 0] = lse.astype(jnp.float32)


def _fwd(q3, k3, v3, seg3, seg3_k, scale, causal, seq_len, block_q, block_k,
         packed, heads, interpret, k_start=0):
    """One forward kernel call: full Q against the K/V chunk ``k3``/``v3``
    (absolute start ``k_start``).  ``seg3`` is the q-side segment array
    (full length), ``seg3_k`` the k-side chunk slice."""
    bh, seq_pad, d = q3.shape
    kv_pad = k3.shape[1]
    grid = (bh, seq_pad // block_q)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, kv_pad, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((1, kv_pad, d), lambda i, j: (i, 0, 0)),
    ]
    args = [q3, k3, v3]
    if packed:
        # seg3 is [batch, 1, seq_pad]; every head of a batch row shares it,
        # so the index map folds the (batch*heads) grid axis back down.
        in_specs += [
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i // heads, 0, j)),
            pl.BlockSpec((1, 1, kv_pad), lambda i, j: (i // heads, 0, 0)),
        ]
        args += [seg3, seg3_k]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          seq_len=seq_len, block_q=block_q, block_k=block_k,
                          packed=packed, k_start=k_start,
                          kv_blocks=kv_pad // block_k),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_pad, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, 1, seq_pad), jnp.float32),
        ],
        interpret=interpret,
        name='pt_flash_fwd',
    )(*args)


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


def _fwd_chunked(q3, k3, v3, seg3, scale, causal, seq_len, block_q, block_k,
                 packed, heads, interpret, kv_chunk):
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
        o_c, lse_c = _fwd(q3, k_c, v_c, seg3, seg_k, scale, causal, seq_len,
                          block_q, block_k, packed, heads, interpret,
                          k_start=c0)
        o_c = o_c.astype(jnp.float32)
        if o is None:
            o, lse = o_c, lse_c
        else:
            o, lse = _fold_normalized(o, lse, o_c, lse_c)
    return o.astype(q3.dtype), lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                   scale, causal, seq_len, block_q, block_k, packed,
                   k_start, kv_blocks):
    if packed:
        sq_ref, sk_ref, dq_ref = refs
    else:
        (dq_ref,) = refs
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0]       # [block_q]
    delta = delta_ref[0, 0]   # [block_q]
    d = q.shape[-1]

    # Chunk-relative K/V (absolute start ``k_start``): dq contributions
    # against the GLOBAL lse/delta are additive across chunks.
    num_kv = jnp.minimum(kv_blocks,
                         jnp.maximum(0, pl.cdiv(seq_len - k_start, block_k)))
    if causal:
        num_kv = jnp.minimum(num_kv, jnp.maximum(
            0, pl.cdiv((qi + 1) * block_q - k_start, block_k)))
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)

    def body(kb, dq):
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k_blk, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        k_pos = k_start + kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        # Padded query rows carry lse == NEG_INF; without the q_pos guard
        # exp(s - NEG_INF) overflows to inf and poisons ds with NaNs.
        mask = (k_pos < seq_len) & (q_pos < seq_len)
        if causal:
            mask &= q_pos >= k_pos
        if packed:
            sq = sq_ref[0, 0]
            sk = sk_ref[0, 0, pl.ds(kb * block_k, block_k)]
            mask &= (sq[:, None] == sk[None, :]) & (sq[:, None] != 0)
        # exp(s - lse) == softmax row (lse = m + log l); masked/empty rows
        # have lse == NEG_INF and p underflows to 0.
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
        dp = jax.lax.dot_general(do, v_blk, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        return dq + jax.lax.dot_general(ds, k_blk, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, num_kv, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                    scale, causal, seq_len, block_q, block_k, packed,
                    q_start, k_start, q_blocks):
    if packed:
        sq_ref, sk_ref, dk_ref, dv_ref = refs
    else:
        dk_ref, dv_ref = refs
    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)  # [block_k, d]
    v = v_ref[0].astype(jnp.float32)
    d = k.shape[-1]

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
    k_pos = k_start + ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)

    def body(qb, carry):
        dk, dv = carry
        q_blk = q_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        do_blk = do_ref[0, pl.ds(qb * block_q, block_q), :].astype(jnp.float32)
        lse_blk = lse_ref[0, 0, pl.ds(qb * block_q, block_q)]
        delta_blk = delta_ref[0, 0, pl.ds(qb * block_q, block_q)]
        s = jax.lax.dot_general(q_blk, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        q_pos = q_start + qb * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        mask = (k_pos < seq_len) & (q_pos < seq_len)
        if causal:
            mask &= q_pos >= k_pos
        if packed:
            sq_blk = sq_ref[0, 0, pl.ds(qb * block_q, block_q)]
            sk = sk_ref[0, 0]
            mask &= (sq_blk[:, None] == sk[None, :]) & (sq_blk[:, None] != 0)
        p = jnp.where(mask, jnp.exp(s - lse_blk[:, None]), 0.0)
        dv = dv + jax.lax.dot_general(p, do_blk, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do_blk, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_blk[:, None]) * scale
        dk = dk + jax.lax.dot_general(ds, q_blk, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    zeros = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(q_begin, num_q, body, (zeros, zeros))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_dq_call(q3, k_c, v_c, seg3, seg_k, do3, lse, delta, scale, causal,
                 seq_len, block_q, block_k, packed, heads, interpret,
                 k_start):
    """dQ contribution of one K/V chunk (full Q streamed block-by-block)."""
    bh, seq_pad, d = q3.shape
    kv_pad = k_c.shape[1]
    dq_specs = [
        pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, kv_pad, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((1, kv_pad, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
        pl.BlockSpec((1, 1, block_q), lambda i, j: (i, 0, j)),
    ]
    dq_args = [q3, k_c, v_c, do3, lse, delta]
    if packed:
        dq_specs += [
            pl.BlockSpec((1, 1, block_q), lambda i, j: (i // heads, 0, j)),
            pl.BlockSpec((1, 1, kv_pad), lambda i, j: (i // heads, 0, 0)),
        ]
        dq_args += [seg3, seg_k]
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          seq_len=seq_len, block_q=block_q, block_k=block_k,
                          packed=packed, k_start=k_start,
                          kv_blocks=kv_pad // block_k),
        grid=(bh, seq_pad // block_q),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, seq_pad, d), q3.dtype),
        interpret=interpret,
        name='pt_flash_bwd_dq',
    )(*dq_args)


def _bwd_dkv_call(q_c, k_c, v_c, seg_q, seg_k, do_c, lse_c, delta_c, scale,
                  causal, seq_len, block_q, block_k, packed, heads, interpret,
                  q_start, k_start):
    """dK/dV contribution of one Q chunk against one K/V chunk."""
    bh, q_pad, d = q_c.shape
    kv_pad = k_c.shape[1]
    dkv_specs = [
        pl.BlockSpec((1, q_pad, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
        pl.BlockSpec((1, q_pad, d), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((1, 1, q_pad), lambda i, j: (i, 0, 0)),
        pl.BlockSpec((1, 1, q_pad), lambda i, j: (i, 0, 0)),
    ]
    dkv_args = [q_c, k_c, v_c, do_c, lse_c, delta_c]
    if packed:
        dkv_specs += [
            pl.BlockSpec((1, 1, q_pad), lambda i, j: (i // heads, 0, 0)),
            pl.BlockSpec((1, 1, block_k), lambda i, j: (i // heads, 0, j)),
        ]
        dkv_args += [seg_q, seg_k]
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          seq_len=seq_len, block_q=block_q, block_k=block_k,
                          packed=packed, q_start=q_start, k_start=k_start,
                          q_blocks=q_pad // block_q),
        grid=(bh, kv_pad // block_k),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, kv_pad, d), k_c.dtype),
            jax.ShapeDtypeStruct((bh, kv_pad, d), v_c.dtype),
        ],
        interpret=interpret,
        name='pt_flash_bwd_dkv',
    )(*dkv_args)


def _bwd(q3, k3, v3, seg3, o3, lse, do3, scale, causal, seq_len, block_q,
         block_k, packed, heads, interpret, kv_chunk=None):
    """Backward pass, K/V (and Q, for dK/dV) streamed in chunks.

    Per-chunk contributions computed against the GLOBAL lse/delta are
    plain sums — no softmax refold needed in the backward direction."""
    bh, seq_pad, d = q3.shape
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)[:, None, :]  # [bh, 1, seq] like lse
    chunk = kv_chunk if kv_chunk is not None else seq_pad
    chunk = min(chunk, seq_pad)

    def sl(x, lo, hi, axis=1):
        return jax.lax.slice_in_dim(x, lo, hi, axis=axis)

    dq = None
    dk_parts, dv_parts = [], []
    for c0 in range(0, seq_pad, chunk):
        c1 = min(c0 + chunk, seq_pad)
        k_c, v_c = sl(k3, c0, c1), sl(v3, c0, c1)
        seg_k = sl(seg3, c0, c1, axis=2) if packed else None
        dq_c = _bwd_dq_call(q3, k_c, v_c, seg3, seg_k, do3, lse, delta,
                            scale, causal, seq_len, block_q, block_k, packed,
                            heads, interpret, k_start=c0)
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
                sl(seg3, r0, r1, axis=2) if packed else None, seg_k,
                sl(do3, r0, r1), sl(lse, r0, r1, axis=2),
                sl(delta, r0, r1, axis=2), scale, causal, seq_len, block_q,
                block_k, packed, heads, interpret, q_start=r0, k_start=c0)
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

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11))
def _flash(q3, k3, v3, seg3, scale, causal, seq_len, block_q, block_k, packed,
           heads, kv_chunk):
    out, _ = _flash_fwd(q3, k3, v3, seg3, scale, causal, seq_len, block_q,
                        block_k, packed, heads, kv_chunk)
    return out


def _flash_fwd(q3, k3, v3, seg3, scale, causal, seq_len, block_q, block_k,
               packed, heads, kv_chunk):
    seq_pad = q3.shape[1]
    if kv_chunk is None or kv_chunk >= seq_pad:
        out, lse = _fwd(q3, k3, v3, seg3, seg3, scale, causal, seq_len,
                        block_q, block_k, packed, heads,
                        interpret=_auto_interpret())
    else:
        out, lse = _fwd_chunked(q3, k3, v3, seg3, scale, causal, seq_len,
                                block_q, block_k, packed, heads,
                                interpret=_auto_interpret(),
                                kv_chunk=kv_chunk)
    return out, (q3, k3, v3, seg3, out, lse)


def _flash_bwd(scale, causal, seq_len, block_q, block_k, packed, heads,
               kv_chunk, res, g):
    import numpy as _np
    q3, k3, v3, seg3, out, lse = res
    dq, dk, dv = _bwd(q3, k3, v3, seg3, out, lse, g, scale, causal, seq_len,
                      block_q, block_k, packed, heads,
                      interpret=_auto_interpret(), kv_chunk=kv_chunk)
    # Integer operands take a float0 cotangent (segment ids are labels);
    # the non-packed path carries seg3=None (empty pytree, no cotangent).
    dseg = (None if seg3 is None
            else _np.zeros(seg3.shape, dtype=jax.dtypes.float0))
    return dq, dk, dv, dseg


_flash.defvjp(_flash_fwd, _flash_bwd)


#: VMEM one kernel call may spend on its resident K/V chunk (the Q/dO chunk
#: in the dK/dV kernel), counted the way the Pallas pipeline allocates it:
#: two operands, each double-buffered.  Half of the 16 MiB scoped-VMEM limit
#: Mosaic gives a kernel on v5e; the other half is left to the streamed
#: blocks, the lse/delta/segment rows and the fp32 temporaries.
KV_CHUNK_VMEM_BYTES = 8 << 20


def kv_chunk_default(head_dim, dtype):
    """Rows of K/V resident per kernel call when ``kv_chunk`` is not given:
    the most that keeps double-buffered K and V inside
    ``KV_CHUNK_VMEM_BYTES`` (bf16 d=128: 8192 rows; f32 d=128: 4096).
    Sequences padded beyond this stream K/V in chunks of this many rows.
    A row occupies whole 128-lane tiles in VMEM whatever ``head_dim`` is."""
    lanes = -(-head_dim // 128) * 128
    return KV_CHUNK_VMEM_BYTES // (2 * 2 * lanes * jnp.dtype(dtype).itemsize)


def flash_attention(q, k, v, causal=False, scale=None, block_q=128, block_k=128,
                    segment_ids=None, kv_chunk=None):
    """Flash attention over ``[batch, seq, heads, head_dim]`` inputs.

    Drop-in for ``petastorm_tpu.parallel.full_attention`` (same signature and
    semantics, O(seq) memory).  Differentiable via the flash backward
    kernels.  Sequences are padded to the block size internally; padded keys
    are masked out, padded query rows are sliced off.

    ``segment_ids`` (``[batch, seq]`` int, 0 = padding) restricts attention
    to same-nonzero-segment pairs — the O(seq)-memory path for
    ``petastorm_tpu.jax.packing`` packed rows (same semantics as
    ``packing.packed_attention``, which is the dense oracle).

    ``kv_chunk`` streams K/V through VMEM in chunks of that many rows
    (auto-enabled above ``kv_chunk_default(head_dim, dtype)`` padded rows;
    ``0`` forces whole-K/V residency), so a single device handles arbitrary
    sequence lengths instead of capping where whole-K/V VMEM residency runs
    out.  The backward pass streams the same way (dQ over K/V chunks, dK/dV
    over Q chunks).

    Compiles to Mosaic on TPU; on CPU/GPU backends it runs the same kernels
    through the Pallas interpreter (tests, dry runs).
    """
    if q.ndim != 4:
        raise ValueError('expected [batch, seq, heads, head_dim], got %r' % (q.shape,))
    b, seq_len, h, d = q.shape
    kv_len = k.shape[1]
    if kv_len != seq_len:
        raise ValueError('flash_attention requires seq_q == seq_kv (got %d vs %d)'
                         % (seq_len, kv_len))
    packed = segment_ids is not None
    if packed and tuple(segment_ids.shape) != (b, seq_len):
        raise ValueError('segment_ids must be [batch, seq] = %r, got %r'
                         % ((b, seq_len), tuple(segment_ids.shape)))
    scale = scale if scale is not None else d ** -0.5

    import math
    block_q = min(block_q, max(seq_len, 16))
    block_k = min(block_k, max(seq_len, 16))
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
        x = jnp.moveaxis(x, 2, 1).reshape(b * h, seq_len, d)
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
        kv_chunk = kv_chunk_default(d, q.dtype)
    if kv_chunk == 0:
        kv_chunk = None      # explicit 0: whole-K/V residency, no streaming
    else:
        # chunk boundaries must land on both block grids
        kv_chunk = max(lcm, (int(kv_chunk) // lcm) * lcm)
        if kv_chunk >= seq_pad:
            kv_chunk = None

    out = _flash(to3(q), to3(k), to3(v), seg3, scale, causal, seq_len,
                 block_q, block_k, packed, h, kv_chunk)
    out = out[:, :seq_len].reshape(b, h, seq_len, d)
    return jnp.moveaxis(out, 1, 2)
