"""Pallas flash attention vs the dense oracle (interpret mode on CPU)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from petastorm_tpu.ops import flash_attention
from petastorm_tpu.parallel import full_attention


def _qkv(rng, b=2, s=64, h=2, d=16, dtype=np.float32):
    shape = (b, s, h, d)
    return tuple(jnp.asarray(rng.standard_normal(shape).astype(dtype))
                 for _ in range(3))


@pytest.mark.parametrize('causal', [False, True])
def test_matches_dense_oracle(rng, causal):
    q, k, v = _qkv(rng)
    got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    want = full_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize('seq', [24, 100])
def test_padded_sequences(rng, seq):
    """Sequence lengths that don't divide the block size are padded+masked."""
    q, k, v = _qkv(rng, s=seq)
    for causal in (False, True):
        got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
        want = full_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


def test_bfloat16(rng):
    q, k, v = _qkv(rng, dtype=np.float32)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    got = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    want = full_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), causal=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(np.float32), want, atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize('causal', [False, True])
def test_gradients_match_oracle(rng, causal):
    q, k, v = _qkv(rng, b=1, s=48, h=2, d=8)

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
        return jnp.sum(out * jnp.cos(out))  # non-trivial cotangent

    def loss_dense(q, k, v):
        out = full_attention(q, k, v, causal=causal)
        return jnp.sum(out * jnp.cos(out))

    got = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, 'qkv'):
        np.testing.assert_allclose(g, w, atol=1e-4, rtol=1e-4,
                                   err_msg='d%s mismatch' % name)


def test_gradients_with_padding(rng):
    q, k, v = _qkv(rng, b=1, s=40, h=1, d=8)  # 40 % 16 != 0

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    got = jax.grad(lambda *a: loss(
        lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=16, block_k=16),
        *a), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: loss(
        lambda q, k, v: full_attention(q, k, v, causal=True), *a),
        argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-4, rtol=1e-4)


def test_as_ulysses_attn_fn(rng):
    """flash_attention slots into Ulysses as the per-device local attention."""
    from jax.sharding import Mesh
    from petastorm_tpu.parallel import make_ulysses_attention

    devices = jax.devices()[:4]
    mesh = Mesh(np.array(devices).reshape(4), ('seq',))
    q, k, v = _qkv(rng, b=1, s=64, h=4, d=8)
    fn, sharding = make_ulysses_attention(
        mesh, seq_axis='seq', batch_axis='data', causal=True,
        attn_fn=lambda *a, **kw: flash_attention(*a, block_q=16, block_k=16, **kw))
    got = jax.jit(fn)(jax.device_put(q, sharding), jax.device_put(k, sharding),
                      jax.device_put(v, sharding))
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=2e-5)


def test_mismatched_block_sizes(rng):
    """block_q != block_k with neither dividing the other: lcm padding must
    keep every tail block covered (regression: max()-padding dropped rows)."""
    q, k, v = _qkv(rng, b=1, s=48, h=1, d=8)
    got = flash_attention(q, k, v, causal=True, block_q=32, block_k=48)
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    got = flash_attention(q, k, v, causal=False, block_q=48, block_k=32)
    np.testing.assert_allclose(got, full_attention(q, k, v), atol=2e-5, rtol=2e-5)


def test_no_nans_in_raw_dq_with_padding(rng):
    """Padded query rows must not produce NaN/inf in the dq kernel output
    (jax_debug_nans aborts on them even if later sliced off)."""
    q, k, v = _qkv(rng, b=1, s=40, h=1, d=8)
    with jax.debug_nans(True):
        g = jax.grad(lambda q: jnp.sum(
            flash_attention(q, k, v, causal=True, block_q=16, block_k=16) ** 2))(q)
    assert np.isfinite(np.asarray(g)).all()


def test_jit_and_vmap_compose(rng):
    q, k, v = _qkv(rng, b=2, s=32, h=2, d=8)
    jitted = jax.jit(lambda q, k, v: flash_attention(q, k, v, block_q=16, block_k=16))
    np.testing.assert_allclose(jitted(q, k, v),
                               full_attention(q, k, v), atol=2e-5, rtol=2e-5)
    # vmap over an extra leading axis: each inner call sees [b, s, h, d].
    q5, k5, v5 = (jnp.stack([x, x * 0.5]) for x in (q, k, v))
    batched = jax.vmap(lambda q, k, v: flash_attention(q, k, v, block_q=16, block_k=16))
    got = batched(q5, k5, v5)
    np.testing.assert_allclose(got[0], full_attention(q, k, v), atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(got[1], full_attention(q * 0.5, k * 0.5, v * 0.5),
                               atol=2e-5, rtol=2e-5)


# -- packed (segment-restricted) flash ---------------------------------------

def _segments(rng, b, s, max_segs=4):
    """Random contiguous nonzero segments with a zero-padded tail."""
    out = np.zeros((b, s), np.int32)
    for r in range(b):
        off = 0
        for seg in range(1, max_segs + 1):
            L = int(rng.integers(1, max(2, s // max_segs)))
            if off + L > s - 2:
                break
            out[r, off:off + L] = seg
            off += L
    return jnp.asarray(out)


@pytest.mark.parametrize('causal', [False, True])
@pytest.mark.parametrize('seq', [64, 52])
def test_packed_matches_packed_dense_oracle(rng, causal, seq):
    from petastorm_tpu.jax.packing import packed_attention

    q, k, v = _qkv(rng, s=seq)
    seg = _segments(rng, 2, seq)
    got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          segment_ids=seg)
    want = packed_attention(q, k, v, seg, causal=causal)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize('causal', [False, True])
def test_packed_gradients_match_oracle(rng, causal):
    from petastorm_tpu.jax.packing import packed_attention

    q, k, v = _qkv(rng, s=48)
    seg = _segments(rng, 2, 48)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=16,
                               block_k=16, segment_ids=seg).sum()

    def loss_dense(q, k, v):
        return packed_attention(q, k, v, seg, causal=causal).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gd, 'qkv'):
        np.testing.assert_allclose(a, b_, atol=3e-5, rtol=3e-5,
                                   err_msg='d%s causal=%s' % (name, causal))


def test_packed_no_cross_segment_leakage(rng):
    """Perturbing segment 2's keys must not change segment 1's outputs."""
    q, k, v = _qkv(rng, b=1, s=32)
    seg = jnp.asarray(np.array([[1] * 10 + [2] * 12 + [0] * 10], np.int32))
    base = flash_attention(q, k, v, block_q=16, block_k=16, segment_ids=seg)
    k2 = k.at[:, 10:22].add(7.0)
    v2 = v.at[:, 10:22].add(-3.0)
    pert = flash_attention(q, k2, v2, block_q=16, block_k=16, segment_ids=seg)
    np.testing.assert_allclose(base[:, :10], pert[:, :10], atol=1e-6)
    assert not np.allclose(base[:, 10:22], pert[:, 10:22])
    # padding rows output exactly zero
    assert np.abs(np.asarray(base[:, 22:])).max() == 0.0


def test_packed_rejects_bad_segment_shape(rng):
    q, k, v = _qkv(rng, s=32)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, segment_ids=jnp.zeros((2, 16), jnp.int32))


def test_packed_in_jit(rng):
    q, k, v = _qkv(rng, s=32)
    seg = _segments(rng, 2, 32)

    @jax.jit
    def f(q, k, v, seg):
        return flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                               segment_ids=seg)

    out = f(q, k, v, seg)
    assert np.isfinite(np.asarray(out)).all()


# -- packed: which tiles are visited, and in what precision -------------------

def _layout(name, s=96):
    """Two rows of segment ids at block 16, each a way the tile runs can go
    wrong."""
    seg = np.zeros((2, s), np.int32)
    if name == 'end_to_end':        # one document over many blocks, several in one
        seg[0, :50], seg[0, 50:54], seg[0, 54:57], seg[0, 57:62] = 1, 2, 3, 4
        seg[0, 62:] = 5
        seg[1, :3], seg[1, 3:9], seg[1, 9:16], seg[1, 16:81], seg[1, 81:] = 1, 2, 3, 4, 5
    elif name == 'one_document':
        seg[:] = 7
    elif name == 'returning_id':    # NOT laid end to end: an id comes back
        seg[0, :20], seg[0, 20:40], seg[0, 40:70], seg[0, 70:] = 1, 2, 1, 3
        seg[1, :10], seg[1, 10:30], seg[1, 30:35], seg[1, 35:90] = 5, 0, 2, 5
    elif name == 'padded_tail':     # a padded tail, and a row of padding only
        seg[0, :30], seg[0, 30:41] = 1, 2
    return jnp.asarray(seg)


LAYOUTS = ['end_to_end', 'one_document', 'returning_id', 'padded_tail']


@pytest.mark.parametrize('kv_chunk', [0, 32], ids=['whole_kv', 'chunked'])
@pytest.mark.parametrize('causal', [False, True])
@pytest.mark.parametrize('layout', LAYOUTS)
def test_packed_layouts_match_oracle_forward_and_gradients(rng, layout, causal,
                                                           kv_chunk):
    from petastorm_tpu.jax.packing import packed_attention

    q, k, v = _qkv(rng, s=96, d=8)
    seg = _layout(layout)
    dout = jnp.asarray(rng.standard_normal(q.shape).astype(np.float32))

    def both(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + vjp(dout)

    got = both(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, segment_ids=seg, block_q=16, block_k=16,
        kv_chunk=kv_chunk))
    want = both(lambda q, k, v: packed_attention(q, k, v, seg, causal=causal))
    for g, w, name in zip(got, want, ['out', 'dq', 'dk', 'dv']):
        np.testing.assert_allclose(g, w, atol=3e-5, rtol=3e-5, err_msg=name)


@pytest.mark.parametrize('layout', ['end_to_end', 'returning_id'])
def test_packed_bfloat16_within_its_tolerance_of_the_float32_oracle(rng, layout):
    from petastorm_tpu.jax.packing import packed_attention

    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(rng, s=96, d=16))
    seg = _layout(layout)
    dout = jnp.asarray(rng.standard_normal(q.shape).astype(np.float32))

    def both(fn, *args):
        out, vjp = jax.vjp(fn, *args)
        return (out,) + vjp(dout.astype(out.dtype))

    got = both(lambda q, k, v: flash_attention(
        q, k, v, causal=True, segment_ids=seg, block_q=16, block_k=32), q, k, v)
    want = both(lambda q, k, v: packed_attention(q, k, v, seg, causal=True),
                *(x.astype(jnp.float32) for x in (q, k, v)))
    for g, w, name in zip(got, want, ['out', 'dq', 'dk', 'dv']):
        assert g.dtype == jnp.bfloat16
        np.testing.assert_allclose(g.astype(np.float32), w, atol=4e-2,
                                   rtol=4e-2, err_msg=name)


def _kernels(closed_jaxpr):
    """Every ``pallas_call`` equation of a traced program, at any depth."""
    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == 'pallas_call':
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from walk(sub)
    return list(walk(closed_jaxpr.jaxpr))


def _products(jaxpr):
    """The operand dtypes of every ``dot_general`` of a kernel's body."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == 'dot_general':
            yield tuple(str(x.aval.dtype) for x in eqn.invars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _products(sub)


@pytest.mark.parametrize('packed', [False, True])
@pytest.mark.parametrize('dtype', ['bfloat16', 'float32'])
def test_kernels_multiply_in_the_dtype_of_their_inputs(rng, dtype, packed):
    q, k, v = (x.astype(dtype) for x in _qkv(rng, s=64, d=16))
    seg = _segments(rng, 2, 64) if packed else None
    program = jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, segment_ids=seg, block_q=32, block_k=32)
        .astype(jnp.float32).sum(), argnums=(0, 1, 2))
    products = [(kernel.params['name'], operands)
                for kernel in _kernels(jax.make_jaxpr(program)(q, k, v))
                for operands in _products(kernel.params['jaxpr'])]
    # two products forward, three in the dQ kernel, four in the dK/dV kernel
    assert sorted(name for name, _ in products) == (
        ['pt_flash_bwd_dkv'] * 4 + ['pt_flash_bwd_dq'] * 3 + ['pt_flash_fwd'] * 2)
    assert {d for _, operands in products for d in operands} == {dtype}


@pytest.mark.parametrize('seq_len,block', [(64, 512), (197, 512), (512, 512),
                                           (513, 128), (700, 256), (2000, 512),
                                           (8192, 512), (32768, 512)])
def test_block_default_is_the_largest_tile_that_pads_at_most_an_eighth(
        seq_len, block):
    from petastorm_tpu.ops.flash_attention import block_default
    assert block_default(seq_len) == block
    padded = -(-seq_len // block) * block
    # 128, the smallest tile the chip takes, is what is left when none fits
    assert seq_len <= block or block == 128 or padded - seq_len <= seq_len / 8


def test_blocks_default_to_the_rule_and_named_ones_are_honoured(rng):
    """The grid of each kernel says which tile a call took."""
    q, k, v = _qkv(rng, b=1, s=1024, h=1, d=8)

    def grids(**blocks):
        return {kernel.params['name']: kernel.params['grid_mapping'].grid
                for kernel in _kernels(jax.make_jaxpr(jax.grad(
                    lambda q: flash_attention(q, k, v, causal=True,
                                              **blocks).sum()))(q))}

    assert grids() == {'pt_flash_fwd': (1, 2), 'pt_flash_bwd_dq': (1, 2),
                       'pt_flash_bwd_dkv': (1, 2)}
    assert grids(block_q=128, block_k=256) == {
        'pt_flash_fwd': (1, 8), 'pt_flash_bwd_dq': (1, 8),
        'pt_flash_bwd_dkv': (1, 4)}


def test_tile_visits_of_one_document_a_row_is_the_causal_triangle():
    from petastorm_tpu.ops.flash_attention import tile_visits
    seg = np.full((3, 1024), 5, np.int32)
    assert tile_visits(seg, 128, 128, True) == (3 * 36, 3 * 36, 3 * 36)
    assert tile_visits(seg, 128, 128, False) == (3 * 64, 3 * 64, 3 * 64)
    # blocks that do not divide the length: padded like the call pads
    assert tile_visits(seg[:, :1000], 128, 256, True) == (3 * 20, 3 * 20, 3 * 20)


@pytest.mark.parametrize('causal', [False, True])
def test_tile_visits_of_documents_of_one_block_each_is_one_tile_each(causal):
    from petastorm_tpu.ops.flash_attention import tile_visits
    seg = np.repeat(np.arange(1, 9, dtype=np.int32), 128)[None]
    visited, triangle, holding = tile_visits(seg, 128, 128, causal)
    assert (visited, holding) == (8, 8)
    assert triangle == (36 if causal else 64)
    # a padded tail and a row of padding only are not visited at all
    seg = np.concatenate([seg, np.zeros_like(seg)])
    seg[0, 640:] = 0
    assert tile_visits(seg, 128, 128, causal)[::2] == (5, 5)


def test_tile_visits_on_the_configurations_length_law():
    """50 rows packed from lfm2-24b-a2b's law of document lengths: no tile
    that holds a needed pair is left out, and about half of the causal
    triangle is visited at 128 x 128."""
    import json
    import os
    from petastorm_tpu.jax.packing import StreamPacker
    from petastorm_tpu.ops.flash_attention import _tile_maps, tile_visits
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, 'benchmarks', 'configs',
                           'lfm2-24b-a2b.json')) as f:
        spec = json.load(f)
    law, max_len = spec['dataset'], spec['max_len']
    rng = np.random.default_rng(29)
    lengths = np.clip(np.rint(rng.lognormal(
        np.log(law['length_median']), law['length_sigma'], 4000)),
        law['length_min'], max_len).astype(np.int64)
    packer, rows = StreamPacker(max_len, 1), []
    for n in lengths:
        rows += [b['segment_ids'] for b in packer.add(np.ones(n, np.int32))]
        if len(rows) >= 50:
            break
    seg = np.concatenate(rows[:50])
    assert seg.shape == (50, max_len)
    visited, triangle, holding = _tile_maps(seg, 128, 128, True)
    assert not (holding & ~visited).any()
    assert not (visited & ~triangle).any()
    counts = tile_visits(seg, 128, 128, True)
    assert counts == (visited.sum(), 50 * triangle.sum(), holding.sum())
    assert 0.45 < counts[0] / counts[1] < 0.70
    # larger tiles skip less, and still leave nothing out
    visited, _, holding = _tile_maps(seg[:8], 512, 256, True)
    assert not (holding & ~visited).any()


def test_tile_visits_leaves_no_pair_out_for_ids_in_any_order(rng):
    """The range test is conservative for ids that are not laid end to end."""
    from petastorm_tpu.ops.flash_attention import _tile_maps
    seg = rng.integers(0, 6, (4, 256)).astype(np.int32)
    seg[1] = np.sort(seg[1])[::-1]
    seg[2, 40:200] = 0
    for causal in (False, True):
        for block_q, block_k in ((16, 16), (32, 16), (16, 64)):
            visited, _, holding = _tile_maps(seg, block_q, block_k, causal)
            assert not (holding & ~visited).any()


# -- K/V chunking (streaming long sequences through VMEM-sized chunks) -------

@pytest.mark.parametrize('causal', [False, True])
def test_chunked_matches_oracle(rng, causal):
    """kv_chunk folding must reproduce the dense oracle exactly (fwd)."""
    q, k, v = _qkv(rng, s=96)
    want = full_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32,
                          kv_chunk=32)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize('causal', [False, True])
def test_chunked_backward_matches_oracle(rng, causal):
    q, k, v = _qkv(rng, s=96)
    dout = jnp.asarray(np.random.default_rng(5).standard_normal(q.shape),
                       jnp.float32)

    def loss(fn, extra):
        return lambda t: (fn(*t, causal=causal, **extra) * dout).sum()

    want = jax.grad(loss(full_attention, {}))((q, k, v))
    got = jax.grad(loss(flash_attention,
                        dict(block_q=32, block_k=32, kv_chunk=32)))((q, k, v))
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=3e-5, rtol=3e-5)


def test_chunked_packed_matches_oracle(rng):
    q, k, v = _qkv(rng, s=96)
    seg = np.zeros((2, 96), np.int32)
    seg[:, :40] = 1
    seg[:, 40:80] = 2          # tail [80:] stays 0 = padding
    seg = jnp.asarray(seg)
    dout = jnp.asarray(np.random.default_rng(7).standard_normal(q.shape),
                       jnp.float32)
    want = full_attention(q, k, v, causal=True, segment_ids=seg)
    got = flash_attention(q, k, v, causal=True, segment_ids=seg,
                          block_q=32, block_k=32, kv_chunk=32)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    def loss(fn, extra):
        return lambda t: (fn(*t, causal=True, segment_ids=seg,
                             **extra) * dout).sum()

    gw = jax.grad(loss(full_attention, {}))((q, k, v))
    gg = jax.grad(loss(flash_attention,
                       dict(block_q=32, block_k=32, kv_chunk=32)))((q, k, v))
    for g, w in zip(gg, gw):
        np.testing.assert_allclose(g, w, atol=3e-5, rtol=3e-5)


def test_chunk_boundaries_respect_block_lcm(rng):
    """A kv_chunk that isn't a block multiple is rounded, not crashed."""
    q, k, v = _qkv(rng, s=128)
    got = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                          kv_chunk=50)   # rounds down to 32
    want = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.slow
def test_32k_tokens_stream_through_chunks(rng):
    """The old cliff: >8k rows required whole-K/V VMEM residency.  32k rows
    must now run chunked, and agree with the (interpreter-resident)
    unchunked kernel."""
    b, s, h, d = 1, 32768, 1, 32
    qkv = [jnp.asarray(rng.standard_normal((b, s, h, d)).astype(np.float32))
           for _ in range(3)]
    kw = dict(causal=True, block_q=512, block_k=512)
    got = flash_attention(*qkv, kv_chunk=4096, **kw)
    want = flash_attention(*qkv, kv_chunk=0, **kw)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize('kv_chunk', [None, 32], ids=['whole', 'chunked'])
def test_two_head_sizes_match_a_dense_softmax(rng, kv_chunk):
    """q and k at one head size (24, as a latent-attention layer's 128 + 64)
    and v, the output and their cotangents at another (16): forward and every
    gradient against a dense masked softmax, packed and causal, K/V whole and
    streamed in chunks.  Nothing is padded to the other's size."""
    b, s, h, d, d_v = 2, 96, 2, 24, 16
    q, k = (jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.standard_normal((b, s, h, d_v)), jnp.float32)
    seg = np.zeros((b, s), np.int32)
    seg[:, :40], seg[:, 40:43], seg[:, 43:80] = 1, 2, 3     # tail stays padding
    seg = jnp.asarray(seg)
    dout = jnp.asarray(rng.standard_normal((b, s, h, d_v)), jnp.float32)

    def dense(q, k, v):
        at = jnp.arange(s)
        mask = (seg[:, :, None] == seg[:, None, :]) & (seg[:, :, None] != 0) \
            & (at[None, :, None] >= at[None, None, :])
        scores = jnp.einsum('bqhd,bkhd->bhqk', q, k, precision='highest') * d ** -0.5
        scores = jnp.where(mask[:, None], scores, -jnp.inf)
        scores = jnp.where(mask.any(-1)[:, None, :, None], scores, 0.0)
        weights = jnp.where(mask[:, None], jax.nn.softmax(scores, -1), 0.0)
        return jnp.einsum('bhqk,bkhd->bqhd', weights, v, precision='highest')

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, segment_ids=seg, block_q=32,
                               block_k=32, kv_chunk=kv_chunk)
    got = flash(q, k, v)
    assert got.shape == (b, s, h, d_v)
    np.testing.assert_allclose(got, dense(q, k, v), atol=2e-5, rtol=2e-5)
    gg = jax.grad(lambda *t: (flash(*t) * dout).sum(), argnums=(0, 1, 2))(q, k, v)
    gw = jax.grad(lambda *t: (dense(*t) * dout).sum(), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(gg, gw):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=3e-5, rtol=3e-5)


def test_q_and_k_share_a_shape_and_v_their_rows(rng):
    """One head size for q, k and v sizes the resident K/V chunk as it did
    before there were two; a k of another head size than q's, or a v of
    another length, is refused."""
    from petastorm_tpu.ops.flash_attention import kv_chunk_default
    q, k, v = _qkv(rng, s=64)
    assert kv_chunk_default(128, jnp.bfloat16) == kv_chunk_default(128, jnp.bfloat16, 128)
    with pytest.raises(ValueError, match='one shape'):
        flash_attention(q, k[..., :8], v)
    with pytest.raises(ValueError, match='one shape'):
        flash_attention(q, k, v[:, :32])
