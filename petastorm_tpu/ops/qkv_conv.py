"""The short convolutions of a delta-rule layer (Kimi Delta Attention) as one
op: q, k and v from their three projections ``u`` ``[B, T, H * d]``::

    pre_t = sum_j taps[j] * u_{t-j}      (depthwise, causal, ``conv_kernel`` taps)
    y     = silu(pre)
    q     = y / sqrt(sum_head y^2 + L2_EPS) * d ** -0.5
    k     = y / sqrt(sum_head y^2 + L2_EPS)
    v     = y

In a packed row a tap that would reach into another document or into padding
(a different ``segment_id``, or 0) contributes 0: the segment rule of
``models.transformer.causal_taps``.  Everything is float32 from the load of
``u`` to the store of the result, which leaves in ``u``'s dtype: the layout
the delta rule's kernels read (``ops/kda.py``).

**Why one op.**  As separate XLA operations (taps, SiLU, the norm over a
head) the work split into fusions that wrote float32 ``[B, T, H * d]``
intermediates beside the bf16 results, read them back for the norm, and
passed them through layout copies: on ``kimilinear.packed``'s step 63 GB a
step, recomputed twice over, where the op needs its three inputs and three
outputs, and in the backward pass three cotangents and three gradients more.

**The kernels** ``pt_taps_fwd`` and ``pt_taps_bwd`` under one
``jax.custom_vjp``.  Grid (row, tile of ``ROWS`` tokens, tile of whole heads
of at most ``LANES`` channels).  A token tile reads, beside its own rows, the
``HALO`` rows before it (a bf16 sublane tile: the ``conv_kernel - 1`` earlier
rows are its last) as a block of its own at the neighbouring index; the
taps shift the tile by ``pltpu.roll`` over the two.  Which taps a token may
use is one int32 a token (bit ``j`` set where tap ``j`` stays in the token's
document), made outside from the segment ids.  A head's norm is a lane
reduction inside the tile.  **Kept for the backward pass:** the op's inputs
alone (projections, taps, the bits).  The backward kernel makes the
pre-activations again in VMEM over its tile and the ``HALO`` rows after it
(the inputs' as another block; the cotangents', 0 after a row's last tile,
as a small array of their own, so that the cotangents become the gradients
for the projections in place), so that a row's gradient takes what its
later rows' taps hand back; it writes d(projection) and, for each tile, the
partial sums of d(taps) ``[B, tiles, conv_kernel, H * d]`` float32, which
are summed outside.

**Measured** on one TPU v5e (three bf16 projections ``[2, 8192, 4096]``,
packed documents, heads of 128; median wall ms of a jitted call): forward
2.65, backward 6.7, against 11.3 and 26.9 for the XLA composition of
``causal_taps``, SiLU and the norm under ``jax.checkpoint``; 512 x 512 tiles,
within 0.1 ms of 256 to 1,024 either way.  Whole tiles, each operation a
pass over VMEM, beat a walk of 16-row strips through every head with its
intermediates in registers (3.8 forward, 5.9 backward), and loads at a row
offset in place of ``pltpu.roll`` changed nothing: the kernels are bound by
their vector work (float32 taps, SiLU, the norm and its backward), not by
HBM (805 MB a forward call, 1.2 GB a backward one: 1.0 and 1.5 ms at 819
GB/s).  Against a float32 reference both ways are within bf16's rounding:
values to 0.3 % of the largest entry (the composition 0.6 %), gradients to
0.2 % (0.7 %).

**Which form runs where** is read from the call, as in ``ops/kda.py``: on a
TPU where the head size is a multiple of 128, the kernels; off a TPU, the
same kernels through the Pallas interpreter at any head size; on a TPU at any
other head size, :func:`_plain`, the same function in ``jax.numpy`` under
``jax.checkpoint``.
"""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# read through the module, as ``ops/kda.py`` does: whoever steers the flash
# kernels off the interpreter (``tests/test_tpu_compile.py``) steers these
_flash = importlib.import_module('petastorm_tpu.ops.flash_attention')

F32 = jnp.float32
#: added to the sum of squares under the root of q's and k's normalisation
#: (the reference implementation's)
L2_EPS = 1e-6
#: tokens a grid step
ROWS = 512
#: rows read before (and, backward, after) a token tile: one bf16 sublane tile
HALO = 16
#: channels a grid step at most, in whole heads
LANES = 512
#: VMEM a kernel call may take: the backward call keeps a dozen float32
#: arrays of its tile alive
VMEM_BYTES = 64 << 20


def reach_bits(segment_ids, shape, conv_kernel):
    """``[B, T]`` int32: bit ``j`` (``1 <= j < conv_kernel``) set where tap
    ``j`` of the token reads a token of its own document (``segment_ids``
    None: of its row)."""
    seg = (jnp.ones(shape, jnp.int32) if segment_ids is None
           else jnp.asarray(segment_ids, jnp.int32))
    bits = jnp.zeros(shape, jnp.int32)
    for j in range(1, conv_kernel):
        before = jnp.pad(seg, ((0, 0), (j, 0)))[:, :shape[1]]
        bits = bits | jnp.where((seg == before) & (seg != 0), 1 << j, 0)
    return bits


def _taps_on(bits, j):
    return (bits & (1 << j)) != 0


def _unit(y, head_dim, scale):
    """``y`` ``[n, w]`` float32 over heads of ``head_dim`` lanes, each
    divided by its L2 norm and multiplied by ``scale``."""
    parts = []
    for lo in range(0, y.shape[1], head_dim):
        part = y[:, lo:lo + head_dim]
        norm = lax.rsqrt(jnp.sum(part * part, axis=1, keepdims=True) + L2_EPS)
        parts.append(part * (norm * scale))
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]


def _unit_back(y, d_out, head_dim):
    """The cotangent ``d_out`` of ``y / |y|`` (over each head) handed back to
    ``y``: ``n dz - n^3 (dz . y) y``."""
    parts = []
    for lo in range(0, y.shape[1], head_dim):
        part, dz = y[:, lo:lo + head_dim], d_out[:, lo:lo + head_dim]
        norm = lax.rsqrt(jnp.sum(part * part, axis=1, keepdims=True) + L2_EPS)
        seen = jnp.sum(dz * part, axis=1, keepdims=True)
        parts.append(norm * dz - (norm * norm * norm * seen) * part)
    return jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]


def _scales(head_dim):
    """(the factor after the norm, or None for none) of q, k, v."""
    return (head_dim ** -0.5, 1.0, None)


# ---------------------------------------------------------------------------
# the kernels
# ---------------------------------------------------------------------------

def _pre(ext, taps, bits, n):
    """The pre-activations of rows ``HALO .. HALO + n`` of ``ext`` (float32,
    the ``HALO`` rows before them first) and each tap's shifted rows:
    ``(pre [n, w], [u_{t-j} for j >= 1])``.  ``taps`` ``[conv_kernel, w]``
    float32, ``bits`` ``[n, 1]``."""
    shifted = [pltpu.roll(ext, j, 0)[HALO:HALO + n] for j in range(1, taps.shape[0])]
    pre = ext[HALO:HALO + n] * taps[0:1]
    for j, rows in enumerate(shifted, 1):
        pre = pre + jnp.where(_taps_on(bits, j), rows * taps[j:j + 1], 0.0)
    return pre, shifted


def _fwd_kernel(bits_ref, *refs, head_dim):
    """Grid (row, token tile, lane tile): q, k, v of the tile."""
    inputs, before, taps, outs = (refs[i:i + 3] for i in range(0, 12, 3))
    bits = bits_ref[0]
    n = bits.shape[0]
    for x_ref, h_ref, t_ref, o_ref, scale in zip(inputs, before, taps, outs,
                                                  _scales(head_dim)):
        ext = jnp.concatenate([h_ref[0].astype(F32), x_ref[0].astype(F32)], axis=0)
        pre, _ = _pre(ext, t_ref[...], bits, n)
        y = pre * jax.nn.sigmoid(pre)
        if scale is not None:
            y = _unit(y, head_dim, scale)
        o_ref[0] = y.astype(o_ref.dtype)


def _bwd_kernel(bits_ref, bits_after_ref, *refs, head_dim):
    """The same grid: the tile's pre-activations and those of the ``HALO``
    rows after it made again, the cotangents handed back through SiLU and the
    norm, then through the taps (a row's gradient takes its later rows'),
    and the tile's own share of d(taps).  The cotangents of the rows after a
    row's last tile are 0, so what lies there hands nothing back."""
    inputs, before, after, taps, d_outs, d_after, d_inputs, d_taps = (
        refs[i:i + 3] for i in range(0, 24, 3))
    rows = bits_ref.shape[1]
    n = rows + HALO
    bits = jnp.concatenate([bits_ref[0], bits_after_ref[0]], axis=0)   # [n, 1]
    for i, scale in enumerate(_scales(head_dim)):
        ext = jnp.concatenate([before[i][0].astype(F32), inputs[i][0].astype(F32),
                               after[i][0].astype(F32)], axis=0)
        t = taps[i][...]
        pre, shifted = _pre(ext, t, bits, n)
        gate = jax.nn.sigmoid(pre)
        d_y = jnp.concatenate([d_outs[i][0].astype(F32),
                               d_after[i][0, 0].astype(F32)], axis=0)
        if scale is not None:
            d_y = _unit_back(pre * gate, d_y * scale, head_dim)
        d_pre = d_y * gate * (1.0 + pre * (1.0 - gate))                  # [n, w]
        d_u = d_pre[:rows] * t[0:1]
        for j in range(1, t.shape[0]):
            reached = jnp.where(_taps_on(bits, j), d_pre, 0.0)
            d_u = d_u + pltpu.roll(reached, n - j, 0)[:rows] * t[j:j + 1]
        d_inputs[i][0] = d_u.astype(d_inputs[i].dtype)
        own = d_pre[:rows]
        sums = [jnp.sum(ext[HALO:HALO + rows] * own, axis=0, keepdims=True)]
        sums += [jnp.sum(jnp.where(_taps_on(bits[:rows], j), u[:rows] * own, 0.0),
                         axis=0, keepdims=True) for j, u in enumerate(shifted, 1)]
        d_taps[i][0, 0] = jnp.concatenate(sums, axis=0)


@functools.lru_cache(maxsize=None)
def _kernel(backward, sizes, dtype, interpret):
    """The jitted call of one kernel at one set of sizes ``(B, T, H * d, d,
    conv_kernel, token tile, lane tile)``, made once: a model's layers of one
    shape share one trace and one lowering."""
    b, length, width, head_dim, conv_kernel, rows, lanes = sizes
    per, halos = rows // HALO, length // HALO
    grid = (b, length // rows, width // lanes)
    tile = pl.BlockSpec((1, rows, lanes), lambda r, t, c: (r, t, c))
    before = pl.BlockSpec((1, HALO, lanes),
                          lambda r, t, c: (r, jnp.maximum(t * per - 1, 0), c))
    after = pl.BlockSpec((1, HALO, lanes),
                         lambda r, t, c: (r, jnp.minimum((t + 1) * per, halos - 1), c))
    taps = pl.BlockSpec((conv_kernel, lanes), lambda r, t, c: (0, c))
    bits = pl.BlockSpec((1, rows, 1), lambda r, t, c: (r, t, 0))
    bits_after = pl.BlockSpec(
        (1, HALO, 1), lambda r, t, c: (r, jnp.minimum((t + 1) * per, halos - 1), 0))
    values = jax.ShapeDtypeStruct((b, length, width), dtype)
    if backward:
        name, kernel = 'pt_taps_bwd', _bwd_kernel
        # the cotangents' rows after each tile come as an array of their own
        # ``[B, tiles, HALO, H * d]``, so that the cotangents themselves
        # become the gradients for the projections in place
        in_specs = [bits, bits_after] + [tile] * 3 + [before] * 3 + [after] * 3 \
            + [taps] * 3 + [tile] * 3 + [pl.BlockSpec(
                (1, 1, HALO, lanes), lambda r, t, c: (r, t, 0, c))] * 3
        aliases = {14 + i: i for i in range(3)}
        d_taps = jax.ShapeDtypeStruct((b, grid[1], conv_kernel, width), F32)
        out_specs = [tile] * 3 + [pl.BlockSpec((1, 1, conv_kernel, lanes),
                                               lambda r, t, c: (r, t, 0, c))] * 3
        out_shape = [values] * 3 + [d_taps] * 3
    else:
        name, kernel = 'pt_taps_fwd', _fwd_kernel
        in_specs = [bits] + [tile] * 3 + [before] * 3 + [taps] * 3
        out_specs, out_shape, aliases = [tile] * 3, [values] * 3, {}
    return jax.jit(pl.pallas_call(
        functools.partial(kernel, head_dim=head_dim), grid=grid,
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        input_output_aliases=aliases, compiler_params=pltpu.CompilerParams(
            dimension_semantics=('parallel',) * 3, vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret, name=name))


def _sizes(projected, taps, head_dim):
    b, length, width = projected[0].shape
    lanes = head_dim * max(1, min(LANES, width) // head_dim)
    while width % lanes:
        lanes -= head_dim
    return (b, length, width, head_dim, taps[0].shape[0], min(ROWS, length), lanes)


def _call(backward, projected, taps, head_dim, *operands):
    sizes = _sizes(projected, taps, head_dim)
    return _kernel(backward, sizes, jnp.dtype(projected[0].dtype),
                   _flash._auto_interpret())(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _kernels(projected, taps, bits, head_dim):
    taps = tuple(t.astype(F32) for t in taps)
    return tuple(_call(False, projected, taps, head_dim, bits[..., None],
                       *projected, *projected, *taps))


def _kernels_fwd(projected, taps, bits, head_dim):
    return _kernels(projected, taps, bits, head_dim), (projected, taps, bits)


def _kernels_bwd(head_dim, residuals, d_outs):
    projected, taps, bits = residuals
    bits = bits[..., None]
    d_outs = tuple(d.astype(projected[0].dtype) for d in d_outs)
    b, length, width = projected[0].shape
    rows = _sizes(projected, taps, head_dim)[5]

    def after_tiles(d):       # each tile's next HALO rows, 0 after the last
        d = d.reshape(b, length // rows, rows, width)[:, 1:, :HALO]
        return jnp.pad(d, ((0, 0), (0, 1), (0, 0), (0, 0)))
    found = _call(True, projected, taps, head_dim, bits, bits, *projected,
                  *projected, *projected, *(t.astype(F32) for t in taps),
                  *d_outs, *(after_tiles(d) for d in d_outs))
    d_taps = tuple(d.sum((0, 1)).astype(t.dtype) for d, t in zip(found[3:], taps))
    # the bits are labels: an integer operand takes a float0 cotangent
    return (tuple(found[:3]), d_taps,
            np.zeros(residuals[2].shape, jax.dtypes.float0))


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _plain(projected, taps, bits, head_dim):
    """The same function in ``jax.numpy`` (``jax.grad``'s own backward pass,
    recomputed there from the inputs alone)."""
    out = []
    for u, t, scale in zip(projected, taps, _scales(head_dim)):
        x, t = u.astype(F32), t.astype(F32)
        pre = x * t[0]
        for j in range(1, t.shape[0]):
            shifted = jnp.pad(x, ((0, 0), (j, 0), (0, 0)))[:, :x.shape[1]]
            pre = pre + jnp.where(_taps_on(bits, j)[..., None], shifted * t[j], 0.0)
        y = jax.nn.silu(pre)
        if scale is not None:
            y = _unit(y.reshape(-1, y.shape[-1]), head_dim, scale).reshape(y.shape)
        out.append(y.astype(u.dtype))
    return tuple(out)


def qkv_conv(projected, taps, segment_ids=None, head_dim=128):
    """q, k, v ``[B, T, H * head_dim]`` in the projections' dtype (the
    module's text) from ``projected``, the three projections ``[B, T, H *
    head_dim]``, and ``taps``, their three ``[conv_kernel, H * head_dim]``
    filters (multiplied in float32)."""
    b, length, width = projected[0].shape
    if width % head_dim:
        raise ValueError('%d channels are not whole heads of %d' % (width, head_dim))
    bits = reach_bits(segment_ids, (b, length), taps[0].shape[0])
    on_chip = not _flash._auto_interpret()
    if on_chip and head_dim % 128:
        return _plain(tuple(projected), tuple(taps), bits, head_dim)
    # a row cut into whole tiles of whole sublane tiles: the rows added are
    # padding, which no tap of a real token reaches
    step = min(ROWS, -(-length // HALO) * HALO)
    pad = -length % step
    if pad:
        projected = [jnp.pad(u, ((0, 0), (0, pad), (0, 0))) for u in projected]
        bits = jnp.pad(bits, ((0, 0), (0, pad)))
    out = _kernels(tuple(projected), tuple(taps), bits, head_dim)
    return tuple(o[:, :length] for o in out)
