"""The gated delta rule with a decay for every key channel (Kimi Delta
Attention, KDA), over packed rows: token by token, and chunked.

A head keeps a state ``S`` of ``[d_k, d_v]`` (key x value) and, at token
``t`` with key ``k_t``, value ``v_t``, query ``q_t``, log-decay ``g_t <= 0``
(one for each of the ``d_k`` key channels: what makes this KDA and not the
plain gated delta rule, whose decay is one scalar a head) and write strength
``beta_t``::

    S'  = diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

In a packed row (``segment_ids``, 0 = padding) ``S`` restarts at 0 on the
first token of every document, and padding neither writes ``S`` nor reads it
(its output is 0).

**The chunked form.**  :func:`kda_recurrent` is the recurrence as written,
one ``lax.scan`` step a token, float32: what the tests hold the rest to.
:func:`kda_chunked` is what the model runs.  The row is cut into chunks of
``CHUNK`` = 64 tokens.  Inside a chunk, with ``c_r`` the cumulative log-decay
from the chunk's start (or from the start of the token's document, where
that lies inside the chunk), ``A_rj = beta_r sum_d k_rd k_jd exp(c_rd -
c_jd)`` for ``j < r`` and ``T = (I + A)^-1`` (the WY form), ``W = T diag(beta)
(K * exp(c))``, ``U_v = T diag(beta) V``; with the state ``S`` handed in, the
new values of the whole chunk are ``U = U_v - W S``, the outputs ``(Q *
exp(c)) S + tril(A^qk) U`` and the state handed on ``diag(exp(c_C)) S + (K *
exp(c_C - c))^T U``: matrix products of 64 x 64 and 64 x 128 in place of 64
rank-one updates.  There is ONE statement of this, :func:`_chunk_parts`
(what does not depend on ``S``) and :func:`_chunk_apply`, written on 2-D
values alone so that Mosaic lowers it as it stands; the backward pass through
both is :func:`_chunk_backward`.

**What the chunked form must survive.**  A head's cumulative log-decay over 64
tokens reaches about -100 under the reference initialisation, past float32's
``exp``: the naive product of ``k * exp(c)`` against ``k * exp(-c)`` overflows.
Every exponent formed here is a DIFFERENCE ``c_r - c_j`` with ``r >= j``,
which is <= 0.  As the published kernels do, a chunk is cut into sub-chunks
of ``SUB_CHUNK`` = 16: on the diagonal 16 x 16 blocks the pairwise difference
is formed before the exponent (one column of all four blocks at a time, a
``[64, d_k]`` term reduced at once); an off-diagonal block of sub-chunk ``I``
against earlier tokens goes through the decay at the sub-chunk's first row,
``exp(c_r - c_I0) x exp(c_I0 - c_j)``, both factors <= 1, so it stays one
matrix product.  ``T`` comes from block forward substitution that doubles the
block (:func:`_unit_lower_inverse`).  Decays, ``c``, ``A``, ``T``, the state
and every accumulation are float32; the 64 x 128 products multiply in the
compute dtype.

**Documents inside a chunk.**  A pair ``(r, j)`` counts where both tokens lie
in the same document; ``S`` reaches the tokens of the document that the
chunk's first token continues, and the state handed on holds what the
chunk's last document wrote.  That cuts ``A`` and restarts ``c`` and ``S``
at a document's start without a pass per document.  ``c`` is a sum under the
documents' mask (a 64 x 64 product), not a running sum over the chunk, so
that no document's decays enter another's result by as much as a rounding:
with one document's inputs changed, the others' outputs and gradients are
bit-equal (``tests/test_kimi_linear.py``).

**Which form runs where** is read from the call, not from a knob.  On a TPU
with ``d_k`` and ``d_v`` multiples of 128: the Pallas kernels ``pt_kda_fwd``
and ``pt_kda_bwd`` under one ``jax.custom_vjp``.  Off a TPU: the same
kernels through the Pallas interpreter at any head size (tier-1 and the
``tiny`` configurations run the kernels' own code).  On a TPU at any other
head size: the plain path, the chunk function under ``vmap`` (rows, heads)
and ``lax.scan`` (chunks) with ``jax.grad``'s own backward pass, which is
also the tests' second reference.

**The kernels.**  Grid (row, block of ``HEADS_PER_STEP`` = 2 heads, step), the
steps of a row in order: a step is ``chunks_per_step`` chunks, read from q, k,
v, g where the model has them (``[B, T, H * d]``: a block is the step's
tokens by its heads' lanes, no copy in another layout), the heads' states
``[d_v, d_k]`` float32 in VMEM scratch.  A chunk's whole working set stays in
VMEM; HBM sees q, k, v, g, beta, ``o`` and the kept states.  The heads of a
block share the chunk's masks, and two heads' inverses share each product
(side by side they fill the MXU's 128 x 128).  **Kept for the backward
pass:** q, k, v, g, beta as given and the state at the start of every step
(``chunks_per_step`` chunks between two kept states: ``T / (64 *
chunks_per_step)`` states of ``[B, H, d_v, d_k]`` float32, 268 MB for 16,384
tokens of 32 heads of 128 x 128 at 2 chunks a step, against 537 MB for a
state a chunk); ``o`` is not.  The forward call of a pass that will not be
differentiated writes no states.  The backward kernel walks a row's steps
from its last, the state's cotangent in scratch: from the kept state it makes
the step's forward quantities again in VMEM, then walks the step's chunks
backwards (the pairwise decays' exponents are reused, not formed again).

**Measured** on one TPU v5e (PR 35; bf16 q, k, v ``[2, 8192, 32, 128]``,
packed documents, 2 chunks a step; wall ms of a jitted call, which pays
layout copies of its operands that a model's step does not): forward 15.3,
forward + backward 43.6 at two heads a grid step (13.1 and 38.6 at four); the
plain ``jax.numpy`` scan these replaced took 33.0-33.7 and 114.8.  Inside
``kimilinear.packed``'s step: ``pt_kda_fwd`` 12.7 ms a call, ``pt_kda_bwd`` 26.9
(a layer 52.3, its three loops 153.6 before).  The forward
call at four heads, 13.1 ms, by its parts, each left out in turn: the inverse 3.1 ms (11
before two heads shared its products), the sum ``c`` 0.9, the pairwise
decays 2.5, the 64 x 128 products with the loads and stores 4.9; everything
at one bf16 pass would still take 9.7: the kernels are bound by the chains of
small dependent products, not by their passes.  On the chip the kernels agree
with the plain path to 4e-7 of the largest entry in float32 (7e-6 with the
recurrence) and to 0.6 % in bfloat16, outputs and the five gradients, and one
document's change leaves the others' bit-equal.

On the device every operation of the recurrence, both ways, runs inside a
``pt_kda_*`` kernel, and no kernel call lies inside a loop:
``benchmarks/metrics/kda_scan_ms.py`` sums the step's ``pt_kda_*`` events
(and ``while*`` events, of which a step has none since PR 35; the plain
path's scan is one).  The masks' inputs (a row's documents numbered from 1,
the number at each chunk's last token: a few int32 ``[B, T]`` operations)
are made outside.
"""

import functools
import importlib
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# ``_auto_interpret`` is read through the module (which the package's
# ``flash_attention`` function hides), so that whoever steers the flash kernels
# off the interpreter (``tests/test_tpu_compile.py``) steers these with them.
_flash = importlib.import_module('petastorm_tpu.ops.flash_attention')

CHUNK = 64
SUB_CHUNK = 16
#: Heads a grid step of the kernels works through: their chains of small
#: products do not depend on each other, so the scheduler interleaves them, and
#: two heads' inverses lie side by side.  One TPU v5e, ms a forward call at
#: ``[2, 8192, 32, 128]``: 15.3 at 2, 13.1 at 4, 12.8 at 8; but every head
#: lengthens the unrolled body that is traced and lowered before a compile
#: cache is even asked: at 4, ``kimilinear.packed``'s set-up took 9 s more than
#: its bound allows, for 24 ms of a 940 ms step.
HEADS_PER_STEP = 2
#: VMEM a kernel call may take (Mosaic's own limit is 16 MiB of a v5e's 128):
#: the backward kernel keeps its heads' forward quantities of a step alive.
VMEM_BYTES = 64 << 20
HIGHEST = jax.lax.Precision.HIGHEST
F32 = jnp.float32
#: (lhs axis, rhs axis) a product contracts: plain, rhs transposed, lhs transposed
NN, NT, TN = (1, 0), (1, 1), (0, 0)


def _runs(segment_ids, shape):
    """(run [B, T] int32: which document of its row a token lies in, counted
    from 1 in the order they lie, so that two documents never share a number;
    valid [B, T]: not padding)."""
    if segment_ids is None:
        return jnp.ones(shape, jnp.int32), jnp.ones(shape, bool)
    seg = jnp.asarray(segment_ids, jnp.int32)
    before = jnp.pad(seg, ((0, 0), (1, 0)))[:, :-1]
    starts = (seg != before) & (seg != 0)
    return jnp.cumsum(starts.astype(jnp.int32), axis=1), seg != 0


def kda_recurrent(q, k, v, g, beta, segment_ids=None):
    """The recurrence token by token, float32.  ``q``, ``k``, ``g``: ``[B, T,
    H, d_k]``; ``v``: ``[B, T, H, d_v]``; ``beta``: ``[B, T, H]``;
    ``segment_ids``: ``[B, T]`` or None (one document a row).  Returns ``o``
    ``[B, T, H, d_v]`` float32."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    run, valid = _runs(segment_ids, q.shape[:2])
    first = (run != jnp.pad(run, ((0, 0), (1, 0)))[:, :-1]) | ~valid

    def step(state, xs):
        q_t, k_t, v_t, g_t, beta_t, first_t, valid_t = xs
        state = jnp.where(first_t[:, None, None, None], 0.0, state)
        decayed = jnp.exp(g_t)[..., None] * state                 # [B, H, K, V]
        seen = jnp.einsum('bhkv,bhk->bhv', decayed, k_t, precision=HIGHEST)
        write = jnp.where(valid_t[:, None], beta_t, 0.0)
        state = decayed + (write[..., None] * k_t)[..., None] \
            * (v_t - seen)[:, :, None, :]
        out = jnp.einsum('bhkv,bhk->bhv', state, q_t, precision=HIGHEST)
        return state, jnp.where(valid_t[:, None, None], out, 0.0)

    b, _, h, d_k = q.shape
    state = jnp.zeros((b, h, d_k, v.shape[-1]), f32)
    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta, first, valid))
    _, out = jax.lax.scan(step, state, xs)
    return jnp.moveaxis(out, 0, 1)


def _dot(a, b, contract, dtype):
    """``a`` and ``b`` (2-D) contracted over the axes ``contract``, multiplied
    in ``dtype`` and accumulated in float32 (float32: at the highest
    precision, so that a TPU does not round its operands)."""
    dims = (((contract[0],), (contract[1],)), ((), ()))
    if jnp.dtype(dtype) == jnp.float32:
        return lax.dot_general(a, b, dims, precision=HIGHEST,
                               preferred_element_type=F32)
    return lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                           preferred_element_type=F32)


# ---------------------------------------------------------------------------
# a chunk's mathematics: 2-D values alone, so that Mosaic lowers it as it
# stands and ``vmap`` + ``lax.scan`` run the same statement
# ---------------------------------------------------------------------------

def _from_row(x, offset, sub):
    """``x`` ``[C, d]`` with row ``offset`` of every sub-chunk laid over that
    sub-chunk's rows."""
    return jnp.concatenate(
        [jnp.broadcast_to(x[lo + offset:lo + offset + 1], (sub, x.shape[1]))
         for lo in range(0, x.shape[0], sub)], axis=0)


def _decayed_products(lefts, k, c, allowed, sub):
    """``sum_d x_rd k_jd exp(c_rd - c_jd)`` for every pair ``r >= j`` of a
    chunk that ``allowed`` (``[C, C]``) keeps, and every ``x`` of ``lefts``;
    the other entries are 0.  ``k``, ``c`` and each ``x``: ``[C, d]`` float32,
    ``c`` the cumulative log-decay from the start of a token's document (or
    of the chunk), so non-increasing inside a document.  No exponent formed
    is positive (see the module's text): the clamps only touch pairs that
    ``allowed`` drops.  Returns (the products, what
    :func:`_decayed_products_back` reuses: the pairwise decays of the
    sub-chunks' own blocks and each later sub-chunk's two factors through
    its reference row)."""
    size, d = k.shape
    row = lax.broadcasted_iota(jnp.int32, (size, size), 0)
    col = lax.broadcasted_iota(jnp.int32, (size, size), 1)
    # the blocks on the diagonal, all at once a column of each: the pairwise
    # difference, before the exponent
    own, pair = [jnp.zeros((size, size), F32) for _ in lefts], []
    for offset in range(sub):
        pair.append(jnp.exp(jnp.minimum(c - _from_row(c, offset, sub), 0.0)))
        decayed = pair[-1] * _from_row(k, offset, sub)
        own = [jnp.where(col % sub == offset, jnp.sum(x * decayed, axis=1, keepdims=True), a)
               for x, a in zip(lefts, own)]
    # a sub-chunk against earlier tokens: through the decay at its first row.
    # A pair allowed there spans that row, so both factors' exponents are <= 0
    earlier, through = [[jnp.zeros((sub, size), F32)] for _ in lefts], []
    for lo in range(sub, size, sub):
        ref = c[lo:lo + 1]
        from_ref = jnp.exp(jnp.minimum(c[lo:lo + sub] - ref, 0.0))   # [sub, d]
        to_ref = jnp.concatenate(
            [jnp.exp(jnp.minimum(ref - c[:lo], 0.0)), jnp.zeros((size - lo, d), F32)],
            axis=0)                                               # [C, d]
        through.append((from_ref, to_ref))
        for rows, x in zip(earlier, lefts):
            rows.append(_dot(x[lo:lo + sub] * from_ref, k * to_ref, NT, F32))
    return [jnp.where(allowed, jnp.where(row // sub == col // sub, a,
                                         jnp.concatenate(rows, axis=0)), 0.0)
            for a, rows in zip(own, earlier)], (pair, through)


def _decayed_products_back(d_outs, lefts, k, c, kept, sub):
    """The cotangents ``d_outs`` (0 outside the allowed pairs) of
    :func:`_decayed_products` handed back: (to each ``x`` of ``lefts``, to
    ``k`` as the right factor, to ``c``), under the same sub-chunk rule.
    With ``dx_rd = sum_j dP_rj k_jd e^(c_rd - c_jd)`` and ``dy_jd = sum_r
    dP_rj x_rd e^(c_rd - c_jd)``, ``dc = x * dx - k * dy``; the reference
    row's two exponents cancel, so nothing is handed to it."""
    size, d = k.shape
    pair, through = kept
    blocks = range(0, size, sub)
    at = lax.broadcasted_iota(jnp.int32, (size, 1), 0) % sub
    dx, dy = [jnp.zeros((size, d), F32) for _ in lefts], jnp.zeros((size, d), F32)
    for offset in range(sub):
        # each row's cotangent against its own sub-chunk's column ``offset``
        weights = [jnp.concatenate([p[lo:lo + sub, lo + offset:lo + offset + 1]
                                    for lo in blocks], axis=0) for p in d_outs]
        decayed = pair[offset] * _from_row(k, offset, sub)
        dx = [a + w * decayed for a, w in zip(dx, weights)]
        seen = sum(w * x for w, x in zip(weights, lefts)) * pair[offset]
        dy = jnp.where(at == offset, jnp.concatenate(
            [jnp.broadcast_to(jnp.sum(seen[lo:lo + sub], axis=0, keepdims=True), (sub, d))
             for lo in blocks], axis=0), dy)
    dx_through = [[jnp.zeros((sub, d), F32)] for _ in lefts]
    for (from_ref, to_ref), lo in zip(through, blocks[1:]):
        for rows, p in zip(dx_through, d_outs):
            rows.append(_dot(p[lo:lo + sub], k * to_ref, NN, F32) * from_ref)
        dy += to_ref * _dot(
            jnp.concatenate([p[lo:lo + sub] for p in d_outs], axis=0),
            jnp.concatenate([x[lo:lo + sub] * from_ref for x in lefts], axis=0), TN, F32)
    dx = [a + jnp.concatenate(rows, axis=0) for a, rows in zip(dx, dx_through)]
    return dx, dy, sum(x * a for x, a in zip(lefts, dx)) - k * dy


def _unit_lower_inverse(lower, count=1):
    """``(I + L)^-1`` for ``count`` strictly lower-triangular ``L`` ``[C,
    C]`` that lie side by side in ``lower`` ``[C, count * C]``, by block
    forward substitution that doubles the block: with ``X`` and ``Y`` the
    inverses of two neighbouring diagonal blocks and ``B`` what lies between
    them, the pair's inverse holds ``-Y B X`` there.  Blocks of 2 need no
    product; ``log2(C) - 1`` doublings of two products each, float32 at the
    highest precision.  Side by side, ``count`` matrices share each
    product (the right factor laid block-diagonally, ``[count * C, count *
    C]``): two of 64 fill the MXU's 128 x 128 and a vector register's 128
    lanes, where one alone leaves half of each empty.  (The product form
    ``(I - A)(I + A^2)(I + A^4)...`` takes as many products and loses digits
    where ``A``'s powers grow before they vanish.)"""
    size = lower.shape[0]
    r = lax.broadcasted_iota(jnp.int32, lower.shape, 0)
    lane = lax.broadcasted_iota(jnp.int32, lower.shape, 1)
    apart = r ^ (lane % size)   # its highest bit: the smallest block holding both

    def diagonally(x):
        if count == 1:
            return x
        return jnp.concatenate([jnp.where(lane // size == n, x, 0.0)
                                for n in range(count)], axis=0)
    inv = jnp.where(apart == 0, 1.0, 0.0) - jnp.where(apart == 1, lower, 0.0)
    half = 2
    while half < size:
        between = jnp.where((apart >= half) & (apart < 2 * half), lower, 0.0)
        inv = inv - _dot(_dot(inv, diagonally(between), NN, F32), diagonally(inv),
                         NN, F32)
        half *= 2
    return inv


def _chunk_masks(tag_col, tag_row, tag_before, tag_last):
    """What the documents allow inside one chunk.  ``tag`` numbers a row's
    documents from 1 in the order they lie (0: padding), as a column ``[C,
    1]`` and as a row ``[1, C]``; ``tag_before``: of the token before the
    chunk (-1 before a row's first), ``tag_last``: of the chunk's last."""
    size = tag_col.shape[0]
    r = lax.broadcasted_iota(jnp.int32, (size, size), 0)
    j = lax.broadcasted_iota(jnp.int32, (size, size), 1)
    valid = tag_col > 0
    same = (tag_col == tag_row) & valid
    return types.SimpleNamespace(
        valid=valid, allowed=same & (r >= j), strict=same & (r > j),
        # the document that the chunk's first token continues meets the
        # state handed in; what the chunk's last document writes is handed on
        carried=(tag_col == tag_before) & valid,
        to_last=(tag_col == tag_last) & valid,
        last=lax.broadcasted_iota(jnp.int32, (size, 1), 0) == size - 1)


def _chunk_parts(heads, masks, dtype):
    """All of a chunk that does not depend on the state handed in, for each
    of ``heads``: (q, k, v, g, beta) of the heads that share the chunk's
    ``masks``; ``q``, ``k``, ``g``: ``[C, d_k]``; ``v``: ``[C, d_v]``;
    ``beta``: ``[C, 1]``."""
    m = masks
    size = m.valid.shape[0]
    found = []
    for q, k, v, g, beta in heads:
        g = jnp.where(m.valid, g.astype(F32), 0.0)
        beta = jnp.where(m.valid, beta.astype(F32), 0.0)
        q, k, v = q.astype(F32), k.astype(F32), v.astype(F32)
        # the cumulative log-decay from the start of a token's document, or of
        # the chunk: a sum under the documents' mask, so that no document's
        # decays enter another's by as much as a rounding
        c = _dot(jnp.where(m.allowed, 1.0, 0.0), g, NN, F32)
        (a_kk, a_qk), kept = _decayed_products((k, q), k, c, m.allowed, SUB_CHUNK)
        found.append(types.SimpleNamespace(
            masks=m, q=q, k=k, v=v, beta=beta, c=c, kept=kept, a_kk=a_kk, a_qk=a_qk))
    for n in range(0, len(found), 2):              # two heads' inverses side by side
        some = found[n:n + 2]
        lower = [jnp.where(m.strict, p.a_kk * p.beta, 0.0) for p in some]
        solve = _unit_lower_inverse(jnp.concatenate(lower, axis=1), len(some))
        for i, p in enumerate(some):
            p.solve = solve[:, i * size:(i + 1) * size]
    for p in found:
        # what meets the state handed in, and what is handed on: the documents
        # that reach over the chunk's ends (elsewhere exp(-inf) = 0)
        p.decay_in = jnp.exp(jnp.where(m.carried, p.c, -jnp.inf))
        p.decay_out = jnp.exp(jnp.where(m.to_last, p.c[-1:] - p.c, -jnp.inf))
        p.k_in, p.q_in = p.k * p.decay_in, p.q * p.decay_in
        p.k_out, p.keep = p.k * p.decay_out, p.decay_in[-1:]
        p.w = _dot(p.solve, p.beta * p.k_in, NN, dtype)
        p.u_v = _dot(p.solve, p.beta * p.v, NN, dtype)
    return found


def _chunk_apply(state_t, p, dtype):
    """A chunk's outputs and the state it hands on.  ``state_t`` is the
    state transposed, ``[d_v, d_k]`` float32: so the decay that scales it is
    a row, and every product below is one the MXU takes as it lies."""
    u = p.u_v - _dot(p.w, state_t, NT, dtype)                    # [C, d_v]
    out = _dot(p.q_in, state_t, NT, dtype) + _dot(p.a_qk, u, NN, dtype)
    state_t = p.keep * state_t + _dot(u, p.k_out, TN, dtype)
    return state_t, jnp.where(p.masks.valid, out, 0.0).astype(dtype)


def _chunk_backward(state_t, p, d_out, d_state_t, dtype):
    """The cotangents ``d_out`` ``[C, d_v]`` and ``d_state_t`` (of the state
    handed on) through :func:`_chunk_apply` and :func:`_chunk_parts`: (dq,
    dk, dv, dg, dbeta ``[C, 1]``, the cotangent of the state handed in).
    Products that the forward pass multiplies in ``dtype`` are transposed in
    it; the pairwise decays' and the inverse's stay float32."""
    m = p.masks
    d_out = jnp.where(m.valid, d_out.astype(F32), 0.0)
    u = p.u_v - _dot(p.w, state_t, NT, dtype)
    d_u = _dot(p.a_qk, d_out, TN, dtype) + _dot(p.k_out, d_state_t, NT, dtype)
    d_a_qk = jnp.where(m.allowed, _dot(d_out, u, NT, dtype), 0.0)
    d_q_in = _dot(d_out, state_t, NN, dtype)
    d_k_out = _dot(u, d_state_t, NN, dtype)
    d_keep = jnp.sum(d_state_t * state_t, axis=0, keepdims=True)  # [1, d_k]
    d_w = -_dot(d_u, state_t, NN, dtype)
    d_state_in = p.keep * d_state_t + _dot(d_out, p.q_in, TN, dtype) \
        - _dot(d_u, p.w, TN, dtype)
    # through W = T (beta k_in), U = T (beta v) and T = (I + A)^-1
    d_bk, d_bv = _dot(p.solve, d_w, TN, dtype), _dot(p.solve, d_u, TN, dtype)
    d_a = -jnp.where(m.strict, _dot(d_bk, p.w, NT, F32) + _dot(d_bv, p.u_v, NT, F32),
                     0.0)
    d_beta = jnp.sum(d_a * p.a_kk, axis=1, keepdims=True) \
        + jnp.sum(d_bk * p.k_in, axis=1, keepdims=True) \
        + jnp.sum(d_bv * p.v, axis=1, keepdims=True)
    d_k_in = p.beta * d_bk
    (d_k_left, d_q), d_k_right, d_c = _decayed_products_back(
        (d_a * p.beta, d_a_qk), (p.k, p.q), p.k, p.c, p.kept, SUB_CHUNK)
    # the decays at the chunk's two ends
    d_decay_in = d_k_in * p.k + d_q_in * p.q + jnp.where(m.last, d_keep, 0.0)
    d_exponent_out = d_k_out * p.k * p.decay_out
    d_c = d_c + d_decay_in * p.decay_in - d_exponent_out \
        + jnp.where(m.last, jnp.sum(d_exponent_out, axis=0, keepdims=True), 0.0)
    d_q = d_q + d_q_in * p.decay_in
    d_k = d_k_left + d_k_right + d_k_in * p.decay_in + d_k_out * p.decay_out
    d_g = _dot(jnp.where(m.allowed, 1.0, 0.0), d_c, TN, F32)
    return (d_q, d_k, p.beta * d_bv, jnp.where(m.valid, d_g, 0.0),
            jnp.where(m.valid, d_beta, 0.0), d_state_in)


# ---------------------------------------------------------------------------
# the plain path: the chunk function under ``vmap`` and ``lax.scan``
# ---------------------------------------------------------------------------

def _plain(q, k, v, g, beta, tag, edges, chunks_per_step, dtype):
    """``vmap`` over rows and heads of one ``lax.scan`` whose step holds
    ``chunks_per_step`` chunks and is a ``jax.checkpoint``: the backward pass
    is ``jax.grad``'s, from the states at the scan's steps."""
    b, length, h, _ = q.shape
    steps = length // (CHUNK * chunks_per_step)

    def lay(x):        # [B, T, H, d] -> [B, H, steps, n, C, d]
        return jnp.moveaxis(x.reshape(b, steps, chunks_per_step, CHUNK, h, -1), 4, 1)
    tag = tag.reshape(b, steps, chunks_per_step, CHUNK)
    before, last = (e.reshape(b, steps, chunks_per_step)
                    for e in (edges[:, :-1], edges[:, 1:]))

    @jax.checkpoint
    def step(state_t, xs):
        q, k, v, g, beta, tag, before, last = xs
        outs = []
        for i in range(chunks_per_step):          # the chunks, in order
            masks = _chunk_masks(tag[i][:, None], tag[i][None, :], before[i], last[i])
            parts, = _chunk_parts([(q[i], k[i], v[i], g[i], beta[i])], masks, dtype)
            state_t, out = _chunk_apply(state_t, parts, dtype)
            outs.append(out)
        return state_t, jnp.stack(outs)

    def head(q, k, v, g, beta, tag, before, last):
        state_t = jnp.zeros((v.shape[-1], q.shape[-1]), F32)
        return lax.scan(step, state_t, (q, k, v, g, beta, tag, before, last))[1]
    heads = jax.vmap(head, in_axes=(0,) * 5 + (None,) * 3)
    out = jax.vmap(heads)(lay(q), lay(k), lay(v), lay(g), lay(beta[..., None]),
                          tag, before, last)       # [B, H, steps, n, C, d_v]
    return jnp.moveaxis(out.reshape(b, h, length, -1), 1, 2)


# ---------------------------------------------------------------------------
# the kernels: a chunk's work in VMEM, the state in scratch
# ---------------------------------------------------------------------------

def _head_beta(beta_ref, head):
    """One head's column ``[step, 1]`` of the write strengths' block ``[1,
    step, H]`` (a head is a lane there, and which one is known only on the
    chip: a masked sum takes it out)."""
    block = beta_ref[0]
    lane = lax.broadcasted_iota(jnp.int32, block.shape, 1)
    return jnp.sum(jnp.where(lane == head, block, 0.0), axis=1, keepdims=True)


def _step_masks(edges_ref, tag_col_ref, tag_row_ref, row, step, chunks):
    """The masks of a grid step's chunks (all heads share them)."""
    return [_chunk_masks(tag_col_ref[0, i * CHUNK:(i + 1) * CHUNK], tag_row_ref[0, i],
                         edges_ref[row, step * chunks + i],
                         edges_ref[row, step * chunks + i + 1])
            for i in range(chunks)]


def _step_parts(refs, beta_ref, masks, i, heads, widths, dtype):
    """Chunk ``i`` of a grid step: the parts of each head of its block."""
    q_ref, k_ref, v_ref, g_ref = refs
    d_k, d_v = widths
    rows = slice(i * CHUNK, (i + 1) * CHUNK)
    return _chunk_parts(
        [(q_ref[0, rows, h * d_k:(h + 1) * d_k], k_ref[0, rows, h * d_k:(h + 1) * d_k],
          v_ref[0, rows, h * d_v:(h + 1) * d_v], g_ref[0, rows, h * d_k:(h + 1) * d_k],
          _head_beta(beta_ref, pl.program_id(1) * heads + h)[rows])
         for h in range(heads)], masks[i], dtype)


def _fwd_kernel(edges_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, tag_col_ref,
                tag_row_ref, out_ref, *rest, chunks, heads, widths, dtype):
    """Grid (row, block of heads, step), the steps in order: ``chunks``
    chunks a step, the states of the block's heads in scratch, zeroed at a
    row's first step and, where the backward pass will want them, written
    out as each step finds them."""
    kept_ref, state_ref = rest if len(rest) == 2 else (None,) + rest
    row, step = pl.program_id(0), pl.program_id(2)

    @pl.when(step == 0)
    def _():
        state_ref[...] = jnp.zeros(state_ref.shape, F32)
    masks = _step_masks(edges_ref, tag_col_ref, tag_row_ref, row, step, chunks)
    states = [state_ref[h] for h in range(heads)]
    if kept_ref is not None:
        for h in range(heads):
            kept_ref[0, h, 0] = states[h]
    for i in range(chunks):
        parts = _step_parts((q_ref, k_ref, v_ref, g_ref), beta_ref, masks, i, heads,
                            widths, dtype)
        for h in range(heads):
            states[h], out = _chunk_apply(states[h], parts[h], dtype)
            out_ref[0, i * CHUNK:(i + 1) * CHUNK,
                    h * widths[1]:(h + 1) * widths[1]] = out
    for h in range(heads):
        state_ref[h] = states[h]


def _bwd_kernel(edges_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, tag_col_ref,
                tag_row_ref, kept_ref, d_out_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                dbeta_ref, d_state_ref, *, chunks, heads, widths, dtype):
    """The same grid with the steps in reverse (the index maps turn them):
    from the state kept at the step's start its chunks' forward quantities
    are made again in VMEM, then the chunks are walked backwards, the
    state's cotangent in scratch."""
    row = pl.program_id(0)
    step = pl.num_programs(2) - 1 - pl.program_id(2)
    d_k, d_v = widths

    @pl.when(pl.program_id(2) == 0)
    def _():
        d_state_ref[...] = jnp.zeros(d_state_ref.shape, F32)
    masks = _step_masks(edges_ref, tag_col_ref, tag_row_ref, row, step, chunks)
    r = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 0)
    j = lax.broadcasted_iota(jnp.int32, (CHUNK, CHUNK), 1)
    states, parts = [[kept_ref[0, h, 0] for h in range(heads)]], []
    for i in range(chunks):
        parts.append(_step_parts((q_ref, k_ref, v_ref, g_ref), beta_ref, masks, i,
                                 heads, widths, dtype))
        if i + 1 < chunks:
            states.append([_chunk_apply(states[-1][h], parts[-1][h], dtype)[0]
                           for h in range(heads)])
    for h in range(heads):
        d_state_t = d_state_ref[h]
        for i in reversed(range(chunks)):
            rows = slice(i * CHUNK, (i + 1) * CHUNK)
            dq, dk, dv, dg, dbeta, d_state_t = _chunk_backward(
                states[i][h], parts[i][h], d_out_ref[0, rows, h * d_v:(h + 1) * d_v],
                d_state_t, dtype)
            keys = slice(h * d_k, (h + 1) * d_k)
            dq_ref[0, rows, keys] = dq.astype(dq_ref.dtype)
            dk_ref[0, rows, keys] = dk.astype(dk_ref.dtype)
            dv_ref[0, rows, h * d_v:(h + 1) * d_v] = dv.astype(dv_ref.dtype)
            dg_ref[0, rows, keys] = dg.astype(dg_ref.dtype)
            # a head's write strengths leave as a row: the column, turned
            dbeta_ref[0, h, i] = jnp.sum(jnp.where(r == j, dbeta, 0.0), axis=0,
                                         keepdims=True).astype(dbeta_ref.dtype)
        d_state_ref[h] = d_state_t


@functools.lru_cache(maxsize=None)
def _kernel(kernel, name, sizes, extra, outputs, chunks_per_step, heads, dtype,
            interpret, reverse):
    """The jitted call of one kernel at one set of sizes ``(B, T, H, d_k,
    d_v)``, made once: a model's layers of one shape then share ONE trace and
    ONE lowering of its unrolled body (traced at every call site, the twelve
    calls of ``kimilinear.packed``'s step cost a run 25 s of set-up before its
    compile cache was even asked).  The jit holds the kernel call alone, its
    operands as the kernel reads them, so the program around it is the one
    XLA would make without it.  ``extra``: the kinds of the operands after q,
    k, v, g, beta and the tags; ``outputs``: (kind, dtype) pairs; a kind is
    ``'keys'`` / ``'values'`` (``[B, T, H * d_k]`` / ``[.., H * d_v]``: a block
    is a grid step's chunks of its heads' lanes), ``'states'`` (``[B, H,
    steps, d_v, d_k]``, the one at each step's start) or ``'strengths'``
    (``[B, H, chunks, 1, C]``).  ``reverse`` walks a row's steps from its
    last."""
    b, length, h, d_k, d_v = sizes
    step = CHUNK * chunks_per_step
    steps = length // step

    def at(s):
        return steps - 1 - s if reverse else s

    def lanes(width):
        return ((b, length, h * width),
                pl.BlockSpec((1, step, heads * width), lambda r, hb, s, e: (r, at(s), hb)))
    kinds = {
        'keys': lanes(d_k), 'values': lanes(d_v),
        'states': ((b, h, steps, d_v, d_k),
                   pl.BlockSpec((1, heads, 1, d_v, d_k),
                                lambda r, hb, s, e: (r, hb, at(s), 0, 0))),
        'strengths': ((b, h, steps * chunks_per_step, 1, CHUNK),
                      pl.BlockSpec((1, heads, chunks_per_step, 1, CHUNK),
                                   lambda r, hb, s, e: (r, hb, at(s), 0, 0)))}
    in_specs = [kinds['keys'][1], kinds['keys'][1], kinds['values'][1], kinds['keys'][1],
                pl.BlockSpec((1, step, h), lambda r, hb, s, e: (r, at(s), 0)),
                pl.BlockSpec((1, step, 1), lambda r, hb, s, e: (r, at(s), 0)),
                pl.BlockSpec((1, chunks_per_step, 1, CHUNK),
                             lambda r, hb, s, e: (r, at(s), 0, 0))]
    return kinds, jax.jit(pl.pallas_call(
        functools.partial(kernel, chunks=chunks_per_step, heads=heads,
                          widths=(d_k, d_v), dtype=dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, h // heads, steps),
            in_specs=in_specs + [kinds[kind][1] for kind in extra],
            out_specs=[kinds[kind][1] for kind, _ in outputs],
            scratch_shapes=[pltpu.VMEM((heads, d_v, d_k), F32)]),
        out_shape=[jax.ShapeDtypeStruct(kinds[kind][0], dt) for kind, dt in outputs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel', 'parallel', 'arbitrary'),
            vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret, name=name))


def _kernel_call(kernel, name, inputs, extra, outputs, tag, edges,
                 chunks_per_step, dtype, reverse=False):
    """One kernel call: q, k, v, g (``inputs``) are read where the model has
    them, as ``[B, T, H * d]``.  ``extra``: (array, kind) pairs,
    ``outputs``: (kind, dtype) pairs (:func:`_kernel`)."""
    q, k, v, g, beta = inputs
    b, length, h, d_k = q.shape
    interpret = _flash._auto_interpret()
    heads = max(n for n in range(1, HEADS_PER_STEP + 1) if h % n == 0)
    kinds, call = _kernel(kernel, name, (b, length, h, d_k, v.shape[-1]),
                          tuple(kind for _, kind in extra), tuple(outputs),
                          chunks_per_step, heads, dtype, interpret, reverse)
    return call(edges, *(x.reshape(b, length, -1) for x in (q, k, v, g)), beta,
                tag[..., None], tag.reshape(b, -1, 1, CHUNK),
                *(x.reshape(kinds[kind][0]) for x, kind in extra))


def _forward(q, k, v, g, beta, tag, edges, chunks_per_step, dtype, keep):
    """(o ``[B, T, H, d_v]``, the states kept for the backward pass or
    None)."""
    out, *kept = _kernel_call(
        _fwd_kernel, 'pt_kda_fwd', (q, k, v, g, beta), [],
        [('values', dtype)] + [('states', jnp.dtype(F32))] * keep, tag, edges,
        chunks_per_step, dtype)
    return out.reshape(v.shape), (kept[0] if keep else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _kernels(q, k, v, g, beta, tag, edges, chunks_per_step, dtype):
    return _forward(q, k, v, g, beta, tag, edges, chunks_per_step, dtype, False)[0]


def _kernels_fwd(q, k, v, g, beta, tag, edges, chunks_per_step, dtype):
    out, kept = _forward(q, k, v, g, beta, tag, edges, chunks_per_step, dtype, True)
    return out, (q, k, v, g, beta, tag, edges, kept)


def _kernels_bwd(chunks_per_step, dtype, residuals, d_out):
    q, k, v, g, beta, tag, edges, kept = residuals
    dq, dk, dv, dg, dbeta = _kernel_call(
        _bwd_kernel, 'pt_kda_bwd', (q, k, v, g, beta),
        [(kept, 'states'), (d_out, 'values')],
        [('keys', q.dtype), ('keys', k.dtype), ('values', v.dtype),
         ('keys', g.dtype), ('strengths', beta.dtype)], tag, edges,
        chunks_per_step, dtype, reverse=True)
    dbeta = jnp.moveaxis(dbeta.reshape(beta.shape[0], beta.shape[2], -1), 1, 2)
    # the tags are labels: integer operands take a float0 cotangent
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape), dbeta) \
        + tuple(np.zeros(x.shape, jax.dtypes.float0) for x in (tag, edges))


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def kda_chunked(q, k, v, g, beta, segment_ids=None, chunks_per_step=2,
                dtype=None):
    """The same function as :func:`kda_recurrent` in chunks of ``CHUNK``
    tokens.  ``chunks_per_step`` chunks lie between two states kept for the
    backward pass (and are a grid step of the kernels, a step of the plain
    path's scan).  ``dtype`` is what the 64 x 128 products multiply in
    (default: ``v``'s; accumulation, decays, ``A``, its inverse and the state
    are float32 whatever it is).  Which form runs is read from the shapes
    (the module's text).  Returns ``o`` ``[B, T, H, d_v]`` in ``dtype``."""
    b, length, _, d_k = q.shape
    dtype = jnp.dtype(dtype or v.dtype)
    step = CHUNK * chunks_per_step
    pad = -length % step
    run, valid = _runs(segment_ids, (b, length))
    # a row's documents numbered from 1, padding 0; ``edges``: the number at
    # each chunk's last token, and -1 before a row's first chunk
    tag = jnp.pad(jnp.where(valid, run, 0), ((0, 0), (0, pad)))
    edges = jnp.pad(tag[:, CHUNK - 1::CHUNK], ((0, 0), (1, 0)), constant_values=-1)
    q, k, v, g, beta = (jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                        for x in (q, k, v, g, beta))
    on_chip = not _flash._auto_interpret()
    form = _plain if on_chip and (d_k % 128 or v.shape[-1] % 128) else _kernels
    return form(q, k, v, g, beta, tag, edges, chunks_per_step, dtype)[:, :length]
