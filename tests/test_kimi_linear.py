"""Kimi-Linear's layers (``ops/kda.py``: the gated delta rule with per-channel
decays, token by token and chunked, the chunked form as its Pallas kernels
through the interpreter and as the plain ``vmap`` + ``lax.scan`` path;
``models/transformer.py``: the mixers
``'kda'`` and ``'mla'``, the shared expert, the untied head) against the plain
float32 reference that the benchmark keeps in
``benchmarks/configs/kimi-linear-48b-a3b.py``, at small sizes on the CPU with
seeded random weights.

Everything here computes in float32 on both sides, so the tolerances are those
of float32 sums taken in another order (the chunked form multiplies matrices
where the reference walks token by token; flash attention folds blocks; the
program sorts tokens by expert): ``3e-5`` absolute on logits of order 5
through five layers, ``5e-5`` relative to the largest entry on gradients.
"""

import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, 'benchmarks')
ATOL = 3e-5      # logits of order 5, through five layers


@pytest.fixture(scope='module')
def kimi():
    """(the configuration's module, its spec) as the harness loads them."""
    sys.path[:0] = [p for p in (BENCH,) if p not in sys.path]
    import catalog
    base = os.path.join(BENCH, 'configs', 'kimi-linear-48b-a3b')
    with open(base + '.json') as f:
        return catalog._module(base + '.py'), json.load(f)


def config_of(kimi, layers_here=None, **sizes):
    """The tiny configuration, cut to the published layers ``layers_here``."""
    module, spec = kimi
    if layers_here is not None:
        spec = dict(spec, layers_here=layers_here, num_hidden_layers=len(layers_here))
    return module.Config(spec, tiny=True, **sizes)


def packed_rows(module, config, seed, lengths):
    """Documents of ``lengths`` packed the yardstick's way."""
    rng = np.random.default_rng(seed)
    docs = [rng.integers(0, config.vocab, n).astype(np.int32) for n in lengths]
    return docs, module.packed.pack_in_order(docs, list(range(len(docs))),
                                             config.max_len)


def close(got, want, rtol=5e-5):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= rtol * max(np.max(np.abs(want)), 1e-3), \
        np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-3)


def all_on(config):
    """(every held expert and the shared one on, no planted fault)."""
    return np.ones(len(config.experts_held) + 1, np.float32), np.zeros(3, bool)


#: published layers: 1 = KDA + dense feed-forward, 2 = KDA + experts, 4 = MLA
#: + experts; [1..5] is the cell's own cut
CUTS = {'kda_dense': [1], 'kda_experts': [2], 'mla_experts': [4],
        'whole': [1, 2, 3, 4, 5]}
#: documents that start inside a chunk of 64, three shorter than the
#: convolution's four taps, and padding at the second row's end
LENGTHS = [40, 2, 61, 1, 23, 90, 3, 33]


@pytest.mark.parametrize('cut', sorted(CUTS))
def test_a_cut_of_the_model_agrees_with_the_reference(kimi, cut):
    """Each mixer with each feed-forward, and the whole model: logits of every
    packed row, the loss, the losses of each document's first tokens, the norm
    of every gradient leaf."""
    module, _ = kimi
    config = config_of(kimi, CUTS[cut])
    key = jax.random.PRNGKey(3)
    _, batch = packed_rows(module, config, 11, LENGTHS)
    assert len(batch['tokens']) == 2 and (batch['segment_ids'][1] == 0).any()
    params, buffers = config.init_params(key), config.init_buffers(key)
    logits = config.model().apply(
        {'params': params, 'buffers': buffers}, batch['tokens'], batch['positions'],
        batch['segment_ids'], mutable=['diagnostics'])[0]
    reference_logits = jax.jit(config.reference_parts().logits)
    on, sound = all_on(config)
    for r in range(len(batch['tokens'])):
        want = reference_logits(params, buffers, batch['tokens'][r],
                                batch['segment_ids'][r], on, sound)
        real = batch['segment_ids'][r] != 0       # padding's logits are no one's
        assert np.max(np.abs(np.asarray(logits[r]) - np.asarray(want))[real]) < ATOL
    state, out = jax.jit(config.train_step())(config.init_state(key), batch)
    reference = config.reference(key, [batch])
    assert float(out['loss']) == pytest.approx(reference['losses'][0], rel=1e-6)
    heads = len(reference['sample_losses'][0])
    assert heads == len(LENGTHS) * config.head_tokens and config.head_tokens == 3
    close(out['sample_loss'][:heads], reference['sample_losses'][0])
    assert not np.asarray(out['sample_loss'][heads:]).any()
    norm = jax.tree_util.tree_map(lambda g: float(jnp.sqrt(jnp.sum(jnp.square(g)))),
                                  config.first_gradient(state, key))
    flat_got = jax.tree_util.tree_flatten_with_path(norm)[0]
    flat_want = jax.tree_util.tree_flatten_with_path(reference['grad_norms'])[0]
    assert [p for p, _ in flat_got] == [p for p, _ in flat_want]
    for (path, got), (_, want) in zip(flat_got, flat_want):
        assert got == pytest.approx(float(want), rel=1e-4, abs=1e-9), path


def test_every_gradient_leaf_agrees_element_by_element(kimi):
    """The norms above could hide a rotated gradient: here every element of
    every leaf, for the whole model."""
    module, _ = kimi
    config = config_of(kimi)
    key = jax.random.PRNGKey(5)
    _, batch = packed_rows(module, config, 2, LENGTHS)
    params, buffers = config.init_params(key), config.init_buffers(key)
    model = config.model()
    from petastorm_tpu.jax import packing
    targets, weights = packing.next_token_targets(batch['tokens'], batch['segment_ids'])

    def program_loss(p):
        logits = model.apply({'params': p, 'buffers': buffers}, batch['tokens'],
                             batch['positions'], batch['segment_ids'],
                             mutable=['diagnostics'])[0]
        picked = jnp.take_along_axis(jax.nn.log_softmax(logits), targets[..., None], -1)
        return -jnp.sum(picked[..., 0] * weights) / weights.sum()

    row = jax.jit(config.reference_row())
    grads = jax.tree_util.tree_map(jnp.zeros_like, params)
    on, sound = all_on(config)
    for r in range(len(batch['tokens'])):
        grads, _ = row(params, buffers, grads, batch['tokens'][r],
                       batch['segment_ids'][r], np.float32(1.0 / weights.sum()),
                       on, sound)
    got = jax.jit(jax.grad(program_loss))(params)
    for (path, g), (_, want) in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                                    jax.tree_util.tree_flatten_with_path(grads)[0]):
        close(g, want)


def mixer_and_reference(config, kind, params):
    """``(program, reference)``: each ``(params of the mixer, h [rows, L, d],
    segment ids) -> [rows, L, d]``."""
    from petastorm_tpu.models.transformer import DeltaAttention, LatentAttention
    model, parts = config.model(), config.reference_parts()
    if kind == 'kda':
        module = DeltaAttention(dtype=jnp.float32, norm_eps=config.norm_eps, **model.kda)

        def reference(p, h, seg):
            return jnp.stack([parts.kda_mixer(p, h[r], seg[r], np.zeros(3, bool))
                              for r in range(len(h))])
    else:
        module = LatentAttention(dtype=jnp.float32, norm_eps=config.norm_eps, **model.mla)

        def reference(p, h, seg):
            return jnp.stack([parts.mla_mixer(p, h[r], seg[r]) for r in range(len(h))])
    return (lambda p, h, seg: module.apply({'params': p}, h, seg)), reference


@pytest.mark.parametrize('kind', ['kda', 'mla'])
def test_each_mixer_alone_agrees_with_the_reference(kimi, kind):
    """The mixer's module on random hidden states against the reference's
    mixer: values, and the gradient for the input and for every parameter."""
    module, _ = kimi
    config = config_of(kimi, [1 if kind == 'kda' else 4])
    params = config.init_params(jax.random.PRNGKey(1))['block_0'][
        'kda' if kind == 'kda' else 'attn']
    # an output gate's bias that is not its initial 0, so that it is held to
    if kind == 'kda':
        params['g_b']['bias'] = 0.3 * jax.random.normal(
            jax.random.PRNGKey(2), params['g_b']['bias'].shape)
    _, batch = packed_rows(module, config, 7, LENGTHS)
    seg = batch['segment_ids']
    h = jax.random.normal(jax.random.PRNGKey(3), seg.shape + (config.hidden,))
    probe = jax.random.normal(jax.random.PRNGKey(4), h.shape) * (seg != 0)[..., None]
    program, reference = mixer_and_reference(config, kind, params)
    close(jax.jit(program)(params, h, seg) * (seg != 0)[..., None],
          jax.jit(reference)(params, h, seg) * (seg != 0)[..., None], rtol=2e-5)
    got = jax.jit(jax.grad(lambda p, h: jnp.sum(program(p, h, seg) * probe),
                           (0, 1)))(params, h)
    want = jax.jit(jax.grad(lambda p, h: jnp.sum(reference(p, h, seg) * probe),
                            (0, 1)))(params, h)
    close(got[1], want[1])
    for (path, g), (_, w) in zip(jax.tree_util.tree_flatten_with_path(got[0])[0],
                                 jax.tree_util.tree_flatten_with_path(want[0])[0]):
        close(g, w)


def delta_rule_inputs(seed, rows, length, heads, d_k, d_v, a=16.0, shift=-2.0):
    """Unit keys, scaled unit queries, values, log-decays ``-a * softplus(n +
    shift)`` a channel (one head a thousand times weaker) and write strengths."""
    rng = np.random.default_rng(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(rows, length, heads, d_k))) * d_k ** -0.5
    k = unit(rng.normal(size=(rows, length, heads, d_k)))
    v = rng.normal(size=(rows, length, heads, d_v))
    g = -a * np.log1p(np.exp(rng.normal(size=(rows, length, heads, d_k)) + shift))
    g[:, :, 0] *= 0.001
    beta = 1.0 / (1.0 + np.exp(-rng.normal(size=(rows, length, heads))))
    return tuple(x.astype(np.float32) for x in (q, k, v, g, beta))


def segments(length, *rows):
    """Segment ids of ``rows``, each a list of document lengths; what is left
    of a row is padding."""
    seg = np.zeros((len(rows), length), np.int32)
    for r, lengths in enumerate(rows):
        at = 0
        for s, n in enumerate(lengths):
            seg[r, at:at + n] = s + 1
            at += n
    return seg


#: name: (segment ids or None, length).  Chunks are 64 tokens long.
LAYOUTS = {
    'one_document_a_row': (None, 200),
    'documents_that_start_inside_a_chunk': (
        segments(200, [3, 67, 60, 70], [100, 100]), 200),
    'documents_shorter_than_the_convolution': (
        segments(130, [1, 2, 3, 1, 60, 2, 61], [64, 1, 1, 64]), 130),
    'padding_at_a_rows_end': (segments(150, [70, 40], [10]), 150),
    'a_length_that_is_no_multiple_of_the_chunk': (segments(77, [30, 47], [77]), 77),
}


@pytest.mark.parametrize('layout', sorted(LAYOUTS))
def test_the_chunked_delta_rule_is_the_token_by_token_one(layout):
    """The kernels ``pt_kda_fwd`` / ``pt_kda_bwd`` (off a TPU ``kda_chunked``
    runs them through the Pallas interpreter): values and every gradient (q,
    k, v, the decays, the write strengths) under the assumed
    initialisation's strongest decays: ``A`` = 16 and steps whose cumulative
    log-decay over a chunk passes -200, where ``exp`` of it is 0 and of its
    negative is not a float32."""
    from petastorm_tpu.ops.kda import kda_chunked, kda_recurrent
    seg, length = LAYOUTS[layout]
    # one chunk between two kept states, or two: the states cross both kinds
    # of edge
    chunks_per_step = 1 + sorted(LAYOUTS).index(layout) % 2
    inputs = delta_rule_inputs(0, 2, length, 3, 32, 16)
    assert np.cumsum(inputs[3], axis=1)[:, 63].min() < -200
    probe = np.cos(np.arange(2 * length * 3 * 16)).reshape(2, length, 3, 16)

    def chunked(*xs):
        return kda_chunked(*xs, seg, chunks_per_step=chunks_per_step)

    def by_token(*xs):
        return kda_recurrent(*xs, seg)
    got, want = jax.jit(chunked)(*inputs), jax.jit(by_token)(*inputs)
    assert np.isfinite(np.asarray(got)).all()
    close(got, want, rtol=1e-5)
    if seg is not None:
        assert not np.asarray(got)[seg == 0].any()
    grads = [jax.jit(jax.grad(lambda *xs: jnp.sum(f(*xs) * probe),
                              argnums=(0, 1, 2, 3, 4)))(*inputs)
             for f in (chunked, by_token)]
    for g, w in zip(*grads):
        assert np.isfinite(np.asarray(g)).all()
        close(g, w)


def test_the_chunked_delta_rule_stays_finite_where_the_naive_product_does_not():
    """``k * exp(G)`` against ``k * exp(-G)``, the chunked form without the
    sub-chunks, overflows at these decays; the form here does not, forward or
    through the backward kernel (every gradient)."""
    from petastorm_tpu.ops.kda import kda_chunked
    inputs = delta_rule_inputs(1, 1, 128, 2, 32, 16, shift=1.0)
    cumulative = np.cumsum(inputs[3][0, :64], axis=0)
    assert cumulative.min() < -100
    with np.errstate(over='ignore'):
        assert np.isinf(np.exp(-cumulative)).any()
    out, grads = jax.jit(jax.value_and_grad(
        lambda *xs: jnp.sum(kda_chunked(*xs)), argnums=(0, 1, 2, 3, 4)))(*inputs)
    assert np.isfinite(float(out))
    assert all(np.isfinite(np.asarray(g)).all() and np.asarray(g).any() for g in grads)


@pytest.fixture
def plain_path(monkeypatch):
    """``kda_chunked`` on the plain path (``vmap`` + ``lax.scan`` over the
    same chunk function), which a TPU takes where the head size is no multiple
    of 128: the tests' second reference."""
    from petastorm_tpu.ops import kda

    def chunked(*args, **kwargs):
        with monkeypatch.context() as patch:
            patch.setattr(kda, '_kernels', kda._plain)
            return kda.kda_chunked(*args, **kwargs)
    return chunked


def values_and_gradients(form, inputs, probe):
    def loss(*xs):
        out = form(*xs)
        return jnp.sum((out * probe).astype(jnp.float32)), out
    grads, out = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*inputs)
    return (out,) + tuple(grads)


def test_the_plain_path_is_the_token_by_token_one(plain_path):
    """The chunk function under ``vmap`` + ``lax.scan`` with ``jax.grad``'s
    own backward pass: values and every gradient, two chunks a step."""
    from petastorm_tpu.ops.kda import kda_recurrent
    seg, length = LAYOUTS['documents_that_start_inside_a_chunk']
    inputs = delta_rule_inputs(2, 2, length, 2, 32, 16)
    probe = np.cos(np.arange(2 * length * 2 * 16)).reshape(2, length, 2, 16)
    got = values_and_gradients(lambda *xs: plain_path(*xs, seg), inputs, probe)
    want = values_and_gradients(lambda *xs: kda_recurrent(*xs, seg), inputs, probe)
    for g, w in zip(got, want):
        close(g, w, rtol=1e-5)


def test_the_kernels_are_the_plain_path_at_the_cells_head_size(plain_path):
    """Head size 128 in bfloat16, as ``kimilinear.packed`` runs it: a document
    that ends inside a sub-chunk of 16, one that ends at a chunk's edge, a
    single token, and a row that ends in padding.  Both forms round their
    products to bfloat16 at the same places and add them in another order."""
    from petastorm_tpu.ops.kda import kda_chunked
    seg = segments(192, [37, 27, 1, 70, 57], [100, 50])
    assert seg[0, 63] != seg[0, 64] and not seg[1, -1]
    bf16 = jnp.bfloat16
    q, k, v, g, beta = delta_rule_inputs(3, 2, 192, 2, 128, 128, a=1.0)
    inputs = (q.astype(bf16), k.astype(bf16), v.astype(bf16), g, beta)
    probe = jnp.asarray(np.cos(np.arange(2 * 192 * 2 * 128)).reshape(2, 192, 2, 128), bf16)
    got = values_and_gradients(lambda *xs: kda_chunked(*xs, seg), inputs, probe)
    want = values_and_gradients(lambda *xs: plain_path(*xs, seg), inputs, probe)
    assert got[0].dtype == bf16 and not np.asarray(got[0], np.float32)[seg == 0].any()
    for name, g_, w_ in zip(('o', 'dq', 'dk', 'dv', 'dg', 'dbeta'), got, want):
        assert g_.dtype == w_.dtype, name
        # bfloat16 keeps 8 bits and each form rounds to it at the end: an
        # entry near the largest may differ by two of its last places
        close(np.asarray(g_, np.float32), np.asarray(w_, np.float32), rtol=2 ** -6)


def test_the_states_kept_do_not_change_the_gradients():
    """``chunks_per_step`` says how many chunks lie between two states kept
    for the backward pass, not what is computed: 1 and 2 agree."""
    from petastorm_tpu.ops.kda import kda_chunked
    seg, length = LAYOUTS['padding_at_a_rows_end']
    inputs = delta_rule_inputs(4, 2, length, 2, 32, 16)
    probe = np.cos(np.arange(2 * length * 2 * 16)).reshape(2, length, 2, 16)
    one, two = (values_and_gradients(
        lambda *xs: kda_chunked(*xs, seg, chunks_per_step=n), inputs, probe)
        for n in (1, 2))
    for a, b in zip(one, two):
        close(a, b, rtol=1e-6)


def test_two_inverses_side_by_side_are_each_ones_own():
    """``(I + L)^-1`` of two strictly lower-triangular matrices laid side by
    side, as the kernels hand two heads' over: random ones, and the hardest
    for a series in ``L``'s powers (every entry 0.9: they reach 1e17 before
    they vanish, the inverse's entries stay under 1)."""
    from petastorm_tpu.ops.kda import CHUNK, _unit_lower_inverse
    rng = np.random.default_rng(0)
    lower = np.tril(rng.normal(size=(2, CHUNK, CHUNK)), -1).astype(np.float32)
    lower[1] = np.tril(np.full((CHUNK, CHUNK), 0.9, np.float32), -1)
    got = np.asarray(_unit_lower_inverse(jnp.concatenate(list(lower), axis=1), 2))
    for n in range(2):
        want = np.linalg.inv(np.eye(CHUNK) + lower[n].astype(np.float64))
        close(got[:, n * CHUNK:(n + 1) * CHUNK], want, rtol=2e-5)
    alone = np.asarray(_unit_lower_inverse(jnp.asarray(lower[0])))
    assert np.array_equal(alone, got[:, :CHUNK])


def test_decayed_products_and_what_they_hand_back():
    """The pairwise decayed products of a chunk under the sub-chunk rule, and
    their cotangents, against the sum as written (decays mild enough for it:
    no ``exp`` overflows), two documents in the chunk."""
    from petastorm_tpu.ops.kda import CHUNK, SUB_CHUNK, _decayed_products, \
        _decayed_products_back
    rng = np.random.default_rng(1)
    d = 32
    q, k, dp_kk, dp_qk = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                          for shape in [(CHUNK, d)] * 2 + [(CHUNK, CHUNK)] * 2)
    tag = np.where(np.arange(CHUNK) < 21, 1, 2)
    at = np.arange(CHUNK)
    allowed = jnp.asarray((tag[:, None] == tag[None, :]) & (at[:, None] >= at[None, :]))
    g = -0.3 * rng.random(size=(CHUNK, d)).astype(np.float32)

    def cumulative(g):                      # from a document's start
        return jnp.where(allowed, 1.0, 0.0) @ g

    def as_written(q, k, g):
        c = cumulative(g)
        pair = jnp.exp(jnp.where(allowed[..., None], c[:, None] - c[None, :], -jnp.inf))
        return tuple(jnp.where(allowed, jnp.sum(x[:, None] * k[None, :] * pair, -1), 0.0)
                     for x in (k, q))
    want, pull = jax.vjp(as_written, q, k, g)
    c = cumulative(g)
    got, kept = _decayed_products((k, q), k, c, allowed, SUB_CHUNK)
    for a, b in zip(got, want):
        close(a, b, rtol=1e-5)
    cotangents = tuple(jnp.where(allowed, x, 0.0) for x in (dp_kk, dp_qk))
    (dk_left, dq), dk_right, dc = _decayed_products_back(cotangents, (k, q), k, c,
                                                         kept, SUB_CHUNK)
    want_dq, want_dk, want_dg = pull(cotangents)
    close(dq, want_dq, rtol=1e-5)
    close(dk_left + dk_right, want_dk, rtol=1e-5)
    close(jnp.where(allowed, 1.0, 0.0).T @ dc, want_dg, rtol=1e-5)


def pallas_calls(jaxpr, in_loop=False):
    """(name, inside a ``scan`` / ``while``) of every kernel call of a jaxpr."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == 'pallas_call':
            yield eqn.params['name'], in_loop
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from pallas_calls(inner, in_loop or eqn.primitive.name in ('scan', 'while'))


def test_the_delta_rules_kernels_lie_in_no_loop(kimi, monkeypatch):
    """``kda_scan_ms`` sums a step's ``while*`` events and its ``pt_kda_*``
    events: a kernel inside a loop would be counted twice.  The tiny
    configuration's step calls each of the four KDA layers' kernels (forward,
    the layer's recomputed forward, backward) outside any loop; and at head
    size 128, lowered for a TPU, the layer is those kernels and no loop."""
    from petastorm_tpu.ops import kda
    config = config_of(kimi)
    assert [kind for kind, _ in config.layers].count('kda') == 4
    batch = {name: jax.ShapeDtypeStruct((config.batch, config.max_len), jnp.int32)
             for name in ('tokens', 'positions', 'segment_ids')}
    step = jax.make_jaxpr(config.train_step())(
        jax.eval_shape(config.init_state, jax.random.PRNGKey(0)), batch)
    calls = [c for c in pallas_calls(step.jaxpr) if c[0].startswith('pt_kda_')]
    assert sorted(calls) == [('pt_kda_bwd', False)] * 4 + [('pt_kda_fwd', False)] * 8

    monkeypatch.setattr(kda._flash, '_auto_interpret', lambda: False)   # as on a TPU
    shape = (1, 256, 4, 128)
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    g = jax.ShapeDtypeStruct(shape, jnp.float32)
    beta, seg = (jax.ShapeDtypeStruct(shape[:n], dtype)
                 for n, dtype in ((3, jnp.float32), (2, jnp.int32)))

    def layer(q, k, v, g, beta, seg):
        return jax.grad(lambda *xs: jnp.sum(kda.kda_chunked(*xs, seg).astype(jnp.float32)),
                        argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    text = jax.jit(layer).trace(q, q, q, g, beta, seg).lower(
        lowering_platforms=('tpu',)).as_text()
    assert 'kernel_name = "pt_kda_fwd"' in text and 'kernel_name = "pt_kda_bwd"' in text
    assert 'stablehlo.while' not in text
    # any other head size takes the plain path there: its scan, no kernel
    small = jax.ShapeDtypeStruct((1, 256, 4, 64), jnp.bfloat16)
    text = jax.jit(kda.kda_chunked).trace(
        small, small, small, jax.ShapeDtypeStruct(small.shape, jnp.float32), beta,
        seg).lower(lowering_platforms=('tpu',)).as_text()
    assert 'stablehlo.while' in text and 'pt_kda_' not in text


@pytest.mark.parametrize('kind', ['kda', 'conv', 'mla'])
def test_no_document_reaches_another(kimi, kind):
    """No leak: with one document's inputs changed, every other document's
    outputs, and the gradients for its inputs, are bit-equal, for the delta
    rule's mixer, its convolution alone and latent attention."""
    from petastorm_tpu.models.transformer import causal_taps
    module, _ = kimi
    config = config_of(kimi, [4 if kind == 'mla' else 1])
    params = config.init_params(jax.random.PRNGKey(1))['block_0'][
        'attn' if kind == 'mla' else 'kda']
    seg = segments(128, [30, 3, 50, 40], [64, 1, 63])
    h = jax.random.normal(jax.random.PRNGKey(3), seg.shape + (config.hidden,))
    probe = jax.random.normal(jax.random.PRNGKey(4), h.shape)
    if kind == 'conv':
        taps = jax.random.normal(jax.random.PRNGKey(5), (4, config.hidden))

        def mixer(h):
            return causal_taps(h, taps, jnp.asarray(seg))
    else:
        program, _ = mixer_and_reference(config, kind, params)

        def mixer(h):
            return program(params, h, jnp.asarray(seg))
    run = jax.jit(jax.value_and_grad(lambda h: jnp.sum(mixer(h) * probe), has_aux=False))
    outputs = jax.jit(mixer)
    changed = (seg == 3) & (np.arange(2)[:, None] == 0)       # row 0's third document
    h2 = jnp.where(changed[..., None], h + 1.0, h)
    (out1, out2), (g1, g2) = zip(*[(np.asarray(outputs(x)), np.asarray(run(x)[1]))
                                   for x in (h, h2)])
    others = (seg != 0) & ~changed
    assert np.array_equal(out1[others], out2[others])
    assert np.array_equal(g1[others], g2[others])
    assert not np.array_equal(out1[changed], out2[changed])


def test_the_32_shares_and_the_shared_expert_add_up_to_the_uncut_layer(kimi):
    """32 experts of a small width over 32 shares of one, top-8: the results
    of the 32 shares, with the shared expert that every share computes alike
    counted once, are the uncut reference layer's; so are the gradients for
    the input."""
    from petastorm_tpu.models.transformer import MoEShare
    config = config_of(kimi, published_num_experts=32, num_experts_per_token=8,
                       experts_held=list(range(32)))
    parts = config.reference_parts()
    d, f, tokens = config.hidden, config.d_expert, 96
    whole = config.init_params(jax.random.PRNGKey(0))
    whole = dict(whole['block_1']['moe'])
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (32,))
    x = jax.random.normal(jax.random.PRNGKey(2), (1, tokens, d))
    probe = jax.random.normal(jax.random.PRNGKey(3), (1, tokens, d))

    def uncut(x):
        return (parts.experts(whole, bias, x[0], jnp.ones((32,)),
                              held=tuple(range(32)))
                + parts.shared_expert(whole, x[0]))[None]

    def share(x, first):
        held = (first,)
        mine = dict(whole, **{k: whole[k][first:first + 1] for k in ('w1', 'w3', 'w2')})
        layer = MoEShare(num_experts=32, top_k=8, d_expert=f, experts_held=held,
                         scale=config.route_scale, eps=config.route_eps,
                         d_shared=config.d_shared, dtype=jnp.float32)
        return layer.apply({'params': mine, 'buffers': {'expert_bias': bias}}, x,
                           mutable=['diagnostics'])[0]

    def summed(x):
        shared = parts.shared_expert(whole, x[0])[None]
        return sum(share(x, first) for first in range(32)) - 31 * shared
    close(jax.jit(summed)(x), jax.jit(uncut)(x), rtol=2e-5)
    close(jax.jit(jax.grad(lambda x: jnp.sum(summed(x) * probe)))(x),
          jax.jit(jax.grad(lambda x: jnp.sum(uncut(x) * probe)))(x))


@pytest.mark.parametrize('side', ['over_the_budget', 'within_the_budget'])
def test_the_gate_at_its_scale_on_either_side_of_the_budget(kimi, side):
    """The share at ``routed_scaling_factor`` 2.446 and the normaliser's eps
    1e-20 against the reference's experts, forward and every gradient, where
    every token picks all held experts (the fallback) and where the router
    picks as it likes (the budgeted path)."""
    from petastorm_tpu.models import moe
    config = config_of(kimi)
    held, k, tokens = config.experts_held, config.top_k, 256
    assert config.route_scale == 2.446 and config.route_eps == 1e-20
    params = moe.moe_share_init(jax.random.PRNGKey(0), config.hidden,
                                config.d_expert, config.num_experts, held)
    bias = jnp.zeros((config.num_experts,))
    if side == 'over_the_budget':
        bias = bias.at[jnp.asarray(held)].set(10.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (tokens, config.hidden))
    probe = jax.random.normal(jax.random.PRNGKey(2), x.shape)

    def program(p, x):
        y, stats = moe.moe_share_apply(p, x, held, k, expert_bias=bias,
                                       scale=config.route_scale, eps=config.route_eps)
        return jnp.sum(y * probe), (y, stats)

    def reference(p, x):
        y = config.reference_parts().experts(p, bias, x, jnp.ones((len(held),)))
        return jnp.sum(y * probe), y
    (_, (y, stats)), got = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True))(params, x)
    (_, want_y), want = jax.value_and_grad(reference, argnums=(0, 1), has_aux=True)(
        params, x)
    assert int(stats['over_budget']) == int(side == 'over_the_budget')
    close(y, want_y)
    close(got[1], want[1])
    for name in ('router', 'w1', 'w3', 'w2'):
        close(got[0][name], want[0][name])


@pytest.mark.parametrize('kind', ['kda', 'mla'])
def test_decoding_is_not_built(kimi, kind):
    """A recurrent state or a latent cache kept between calls is serving: the
    new mixers say so and build nothing."""
    import dataclasses
    config = config_of(kimi, [1 if kind == 'kda' else 4])
    model = dataclasses.replace(config.model(), decode=True, remat=False)
    tokens = jnp.zeros((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match='serving'):
        model.init(jax.random.PRNGKey(0), tokens, None, tokens + 1)


def test_the_sharding_rules_name_every_new_leaf(kimi):
    """Every leaf of the model is sharded by a rule of ``_spec_for`` or
    replicated by name (``REPLICATED_PARENTS``, the expert layer's own
    stacks, the dense SwiGLU as before) or leaf (``REPLICATED_LEAVES``), none by
    silence; and on a mesh of
    two the sharded ones divide."""
    from jax.sharding import Mesh, PartitionSpec as P
    from petastorm_tpu.models import transformer
    config = config_of(kimi)
    params = jax.eval_shape(config.init_params, jax.random.PRNGKey(0))
    as_before = {'router', 'w1', 'w3', 'w2'}      # experts: divided by experts_held
    silent = []
    for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]:
        names = [p.key for p in path]
        spec = transformer._spec_for(path, 'model')
        if spec == P() and names[-2] not in transformer.REPLICATED_PARENTS \
                and names[-1] not in transformer.REPLICATED_LEAVES \
                and not as_before & set(names[-2:]):
            silent.append('/'.join(names))
    assert not silent, silent
    mesh = Mesh(np.array(jax.devices()[:2]), ('model',))
    shardings = transformer.param_shardings(params, mesh)
    sharded = {'/'.join(p.key for p in path)
               for path, s in jax.tree_util.tree_flatten_with_path(shardings)[0]
               if s.spec != P()}
    for leaf in ('block_0/kda/q_proj/kernel', 'block_0/kda/o_proj/kernel',
                 'block_0/kda/A_log', 'block_0/kda/dt_bias', 'block_0/kda/k_conv',
                 'block_0/kda/g_b/bias', 'block_3/attn/kv_b/kernel',
                 'block_3/attn/q/kernel', 'block_1/moe/shared_w2/kernel',
                 'lm_head/kernel', 'embed/embedding'):
        assert leaf in sharded, leaf
    assert 'block_3/attn/kv_a/kernel' not in sharded


def test_the_weights_have_the_shapes_the_model_asks_for(kimi):
    config = config_of(kimi)
    mine = jax.eval_shape(lambda k: {'params': config.init_params(k),
                                     'buffers': config.init_buffers(k)},
                          jax.random.PRNGKey(0))
    tokens = jnp.zeros((2, config.max_len), jnp.int32)
    theirs = jax.eval_shape(lambda: config.model().init(
        jax.random.PRNGKey(0), tokens, tokens, tokens + 1))
    theirs = {k: theirs[k] for k in ('params', 'buffers')}
    assert jax.tree_util.tree_structure(mine) == jax.tree_util.tree_structure(theirs)
    assert jax.tree_util.tree_map(lambda a: a.shape, mine) \
        == jax.tree_util.tree_map(lambda a: a.shape, theirs)


def test_the_assumed_initialisation_of_the_decays(kimi):
    """``A_log`` is the log of a number in [1, 16] and ``softplus(dt_bias)`` a
    step in [0.001, 0.1], as the configuration's file assumes them."""
    config = config_of(kimi, [1])
    kda = config.init_params(jax.random.PRNGKey(0))['block_0']['kda']
    a, step = np.exp(kda['A_log']), np.log1p(np.exp(np.asarray(kda['dt_bias'], np.float64)))
    assert (a >= 1).all() and (a <= 16).all()
    assert step.min() >= 0.001 * (1 - 1e-4) and step.max() <= 0.1 * (1 + 1e-4)
    assert not np.asarray(kda['g_b']['bias']).any()
