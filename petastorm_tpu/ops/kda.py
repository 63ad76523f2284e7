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

Two forms of the same function:

* :func:`kda_recurrent` is the recurrence as written, one ``lax.scan`` step a
  token, float32: what the tests hold the chunked form to.
* :func:`kda_chunked` is what the model runs.  The row is cut into chunks of
  ``chunk`` = 64 tokens.  Inside a chunk, with ``G_r`` the cumulative
  log-decay from the chunk's start (or from the start of the token's document,
  where that lies inside the chunk), ``A_rj = beta_r sum_d k_rd k_jd exp(G_rd
  - G_jd)`` for ``j < r`` and ``T = (I + A)^-1`` (the WY form), the new values
  of the whole chunk are ``U = T diag(beta) (V - (K * exp(G)) S_0)``, the
  outputs ``(Q * exp(G)) S_0 + tril(A^qk) U`` and the state handed on
  ``diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U``: matrix products of 64 x 64
  and 64 x 128 in place of 64 rank-one updates.  Between chunks a
  ``lax.scan`` carries the ``[d_k, d_v]`` states.

**What the chunked form must survive.**  A head's cumulative log-decay over 64
tokens reaches about -100 under the reference initialisation, past float32's
``exp``: the naive product of ``k * exp(G)`` against ``k * exp(-G)`` overflows.
Every exponent formed here is a DIFFERENCE ``G_r - G_j`` with ``r >= j``,
which is <= 0.  As the published kernels do, a chunk is cut into sub-chunks
of 16: on the diagonal 16 x 16 blocks the pairwise difference is formed
before the exponent (``exp(G_r - G_j)``, a ``[16, 16, d_k]`` term reduced at
once); an off-diagonal block of sub-chunk ``I`` against earlier tokens goes
through the decay at the sub-chunk's first row, ``exp(G_r - G_I0) x exp(G_I0
- G_j)``, both factors <= 1, so it stays one matrix product.  ``T`` comes
from forward substitution on the 16 x 16 diagonal blocks (16 row steps,
unrolled) and three block rows of merging products; nothing here is a nested
loop.

**Documents inside a chunk.**  A pair ``(r, j)`` counts where both tokens lie
in the same document; ``S_0`` reaches the tokens of the document that the
chunk's first token continues, and the state handed on holds what the
chunk's last document wrote.  That cuts ``A`` and restarts ``G`` and ``S``
at a document's start without a pass per document.  ``G`` is a sum under the
documents' mask (a 64 x 64 product), not a running sum over the chunk, so
that no document's decays enter another's result by as much as a rounding:
with one document's inputs changed, the others' outputs and gradients are
bit-equal (``tests/test_kimi_linear.py``).

**Backward.**  ``jax.grad`` through the scan, whose body is a
``jax.checkpoint``: kept for the backward pass are the states at the scan's
steps alone (``chunks_per_step`` chunks a step: ``T / (chunk *
chunks_per_step)`` states of ``[batch, heads, d_k, d_v]`` float32, 268 MB for
16,384 tokens of 32 heads of 128 x 128 at 2 chunks a step, against 537 MB
for a state a chunk); everything inside a step is recomputed there.

``chunks_per_step`` on one TPU v5e (PR 34; bf16 q, k, v ``[2, 8192, 32,
128]``, packed documents; wall ms of a jitted call, and the seconds its
first call took to compile): forward 33.0-33.7 at 8, 4, 2 and 1 (the passes
over ``[.., 64, 128]`` float32 arrays bound it, not the loop); forward +
backward 138.2 (41 s) at 8, 131.2 (26 s) at 4, **114.8 (21 s) at 2**, 105.7
(21 s) at 1, which keeps 537 MB of states a layer.

All of a chunk's work is in the scan's body, so on the device every
operation of the recurrence runs inside one ``while`` of the forward pass
and one of the backward pass: ``benchmarks/metrics/kda_scan_ms.py`` reads
them by that name (XLA's fusions carry numbers, not scopes).
"""

import functools

import jax
import jax.numpy as jnp

CHUNK = 64
SUB_CHUNK = 16
HIGHEST = jax.lax.Precision.HIGHEST


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


def _mm(spec, a, b, dtype):
    """A product in the compute dtype, accumulated in float32 (float32: at the
    highest precision, so that a TPU does not round its operands)."""
    if jnp.dtype(dtype) == jnp.float32:
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def _diagonal_blocks(x, sub):
    """The ``sub`` x ``sub`` blocks on the diagonal of ``x`` ``[..., C, C]``,
    as ``[..., C / sub, sub, sub]``."""
    blocks = x.shape[-1] // sub
    cut = x.reshape(x.shape[:-2] + (blocks, sub, blocks, sub))
    return jnp.stack([cut[..., i, :, i, :] for i in range(blocks)], axis=-3)


def _block_diagonal(blocks):
    """``blocks`` ``[..., I, sub, n]`` laid on the diagonal of zeros: block
    ``i`` in rows ``i * sub`` on and columns ``i * n`` on; ``[..., I * sub, I *
    n]``."""
    count, sub, n = blocks.shape[-3:]
    eye = jnp.eye(count, dtype=blocks.dtype)[:, None, :, None]
    return (blocks[..., :, :, None, :] * eye).reshape(
        blocks.shape[:-3] + (count * sub, count * n))


def _decayed_products(lefts, k, c, same, sub):
    """``sum_d x_rd k_jd exp(c_rd - c_jd)`` for every ``r >= j`` of a chunk
    that ``same`` (``[..., C, C]``: both tokens in one document) allows, and
    every ``x`` of ``lefts``; the other entries are 0.  ``k``, ``c`` and each
    ``x``: ``[..., C, d]`` float32, ``c`` the cumulative log-decay from the
    start of a token's document (or of the chunk), so non-increasing inside
    a document.  No exponent formed is positive (see the module's text)."""
    size, d = k.shape[-2:]
    blocks = size // sub
    lead = k.shape[:-2]

    def cut(x):                                   # [..., I, sub, d]
        return x.reshape(lead + (blocks, sub, d))
    c_b, k_b = cut(c), cut(k)
    at = jnp.arange(size)
    allowed = same & (at[:, None] >= at[None, :])                # [..., C r, C j]
    on_diagonal = _diagonal_blocks(allowed, sub)  # [..., I, sub r, sub j]
    # diagonal blocks: the pairwise difference, before the exponent
    pair = jnp.exp(jnp.where(on_diagonal[..., None],
                             c_b[..., :, None, :] - c_b[..., None, :, :],
                             -jnp.inf))           # [..., I, sub r, sub j, d]
    # off-diagonal blocks: through the decay at the sub-chunk's first row.  A
    # pair allowed there spans that row, so both factors' exponents are <= 0;
    # the clamp only touches pairs that ``allowed`` drops
    ref = c_b[..., :1, :]                          # [..., I, 1, d]
    earlier = at[None, :] < (jnp.arange(blocks) * sub)[:, None]  # [I, C j]
    to_ref = jnp.where(earlier[:, :, None],
                       jnp.minimum(ref - c[..., None, :, :], 0.0), -jnp.inf)
    k_to_ref = k[..., None, :, :] * jnp.exp(to_ref)              # [..., I, C j, d]
    from_ref = jnp.exp(jnp.minimum(c_b - ref, 0.0))
    out = []
    for x in lefts:
        x_b = cut(x)
        diag = jnp.sum((x_b[..., :, None, :] * k_b[..., None, :, :]) * pair,
                       axis=-1)                   # [..., I, sub r, sub j]
        off = jnp.einsum('...Ird,...Ijd->...Irj', x_b * from_ref, k_to_ref,
                         precision=HIGHEST)       # [..., I, sub r, C j]
        whole = off.reshape(lead + (size, size)) + _block_diagonal(diag)
        out.append(jnp.where(allowed, whole, 0.0))
    return out


def _unit_lower_inverse(lower, sub):
    """``(I + lower)^-1`` for strictly lower-triangular ``lower`` ``[..., C,
    C]``: forward substitution inside the ``sub`` x ``sub`` diagonal blocks,
    row by row (unrolled), then one block row of merging products at a time.
    Float32 at the highest precision."""
    diag = _diagonal_blocks(lower, sub)           # [..., I, sub, sub]
    inv = jnp.broadcast_to(jnp.eye(sub, dtype=lower.dtype), diag.shape)
    for i in range(1, sub):
        row = jnp.sum(diag[..., i, :, None] * inv, axis=-2)
        inv = inv.at[..., i, :].add(-row)
    whole = _block_diagonal(inv)
    for i in range(1, lower.shape[-1] // sub):
        lo = i * sub
        reach = jnp.matmul(lower[..., lo:lo + sub, :lo], whole[..., :lo, :lo],
                           precision=HIGHEST)
        whole = whole.at[..., lo:lo + sub, :lo].set(
            -jnp.matmul(inv[..., i, :, :], reach, precision=HIGHEST))
    return whole


def _chunks(state, xs, dtype, sub):
    """One step of the scan: ``n`` chunks of ``C`` tokens.  ``state`` ``[B, H,
    d_k, d_v]`` float32; ``xs``: q, k, g ``[B, H, n, C, d_k]``, v ``[B, H, n,
    C, d_v]``, beta ``[B, H, n, C]``, run and valid ``[B, n, C]``, the run of
    the token before each chunk ``[B, n]``.  Returns (state, o ``[B, H, n,
    C, d_v]``)."""
    q, k, v, g, beta, run, valid, run_before = xs
    f32 = jnp.float32
    valid_h = valid[:, None]                                     # [B, 1, n, C]
    g = jnp.where(valid_h[..., None], g.astype(f32), 0.0)
    beta = jnp.where(valid_h, beta.astype(f32), 0.0)
    q, k = q.astype(f32), k.astype(f32)
    at = jnp.arange(q.shape[-2])
    same = (run[..., :, None] == run[..., None, :]) & valid[..., :, None]
    # the cumulative log-decay from the start of a token's document, or of the
    # chunk: a sum under the documents' mask, so that no document's decays
    # enter another's by as much as a rounding
    since = (same & (at[:, None] >= at[None, :])).astype(f32)    # [B, n, C, C]
    c = jnp.einsum('bnrj,bhnjd->bhnrd', since, g, precision=HIGHEST)
    same = same[:, None]                                         # [B, 1, n, C, C]
    carried = ((run == run_before[..., None]) & valid)[:, None, ..., None]
    # what is handed on: the writes of the chunk's last document (of none
    # where the chunk ends in padding)
    to_last = ((run == run[..., -1:]) & valid & valid[..., -1:])[:, None, ..., None]

    a_kk, a_qk = _decayed_products((k, q), k, c, same, sub)
    a_kk = jnp.where(at[:, None] > at[None, :], a_kk * beta[..., None], 0.0)
    solve = _unit_lower_inverse(a_kk, sub)                       # [B, H, n, C, C]

    # what meets the state handed in, and what is handed on: the documents
    # that reach over the chunk's ends (elsewhere exp(-inf) = 0)
    decay_in = jnp.exp(jnp.where(carried, c, -jnp.inf))
    k_in, q_in = k * decay_in, q * decay_in
    k_out = k * jnp.exp(jnp.where(to_last, c[..., -1:, :] - c, -jnp.inf))
    w = _mm('bhnrj,bhnjd->bhnrd', solve, k_in * beta[..., None], dtype)
    u_v = _mm('bhnrj,bhnjd->bhnrd', solve, v.astype(f32) * beta[..., None], dtype)
    keep = decay_in[..., -1, :]                                  # [B, H, n, d_k]

    outs = []
    for i in range(q.shape[2]):                    # the chunks, in order
        u = u_v[:, :, i] - _mm('bhrk,bhkv->bhrv', w[:, :, i], state, dtype)
        outs.append(_mm('bhrk,bhkv->bhrv', q_in[:, :, i], state, dtype)
                    + _mm('bhrj,bhjv->bhrv', a_qk[:, :, i], u, dtype))
        state = keep[:, :, i, :, None] * state \
            + _mm('bhjk,bhjv->bhkv', k_out[:, :, i], u, dtype)
    out = jnp.stack(outs, axis=2)
    return state, jnp.where(valid_h[..., None], out, 0.0).astype(dtype)


def kda_chunked(q, k, v, g, beta, segment_ids=None, chunks_per_step=2,
                dtype=None):
    """The same function as :func:`kda_recurrent` in chunks of ``CHUNK``
    tokens, ``chunks_per_step`` of them to a step of the scan (their work
    inside a chunk runs side by side, their states in order).  ``dtype`` is
    what the 64 x 128 products multiply in (default: ``v``'s; accumulation,
    decays, ``A``, its inverse and the state are float32 whatever it is).
    Returns ``o`` ``[B, T, H, d_v]`` in ``dtype``."""
    b, length, h, d_k = q.shape
    dtype = dtype or v.dtype
    run, valid = _runs(segment_ids, (b, length))
    step = CHUNK * chunks_per_step
    steps = -(-length // step)
    pad = steps * step - length

    def lay(x):
        """``[B, T, ...]`` -> ``[steps, B, (H,) n, C, ...]``: the scan's xs."""
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((b, steps, chunks_per_step, CHUNK) + x.shape[2:])
        if x.ndim >= 5:                            # heads before the chunks
            x = jnp.moveaxis(x, 4, 1)              # [B, H, steps, n, C, ...]
            return jnp.moveaxis(x, 2, 0)
        return jnp.moveaxis(x, 1, 0)
    run_p = jnp.pad(run, ((0, 0), (0, pad)))
    # the run of the token before each chunk; before a row's first, none
    run_before = jnp.pad(run_p, ((0, 0), (1, 0)), constant_values=-1)[:, :-1]
    run_before = run_before.reshape(b, steps, chunks_per_step, CHUNK)[..., 0]
    xs = (lay(q), lay(k), lay(v), lay(g), lay(beta), lay(run), lay(valid),
          jnp.moveaxis(run_before, 1, 0))
    body = jax.checkpoint(functools.partial(_chunks, dtype=dtype, sub=SUB_CHUNK))
    state = jnp.zeros((b, h, d_k, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(body, state, xs)         # [steps, B, H, n, C, d_v]
    out = jnp.moveaxis(out, 0, 2).reshape(b, h, steps * step, v.shape[-1])
    return jnp.moveaxis(out, 1, 2)[:, :length]
