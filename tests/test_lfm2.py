"""LFM2's layers (``models/transformer.py``: the layer pattern, the gated short
convolution, RMS-normalised q and k, SwiGLU; ``models/moe.py``: one chip's share
of a top-k expert layer) against the plain float32 reference that the
benchmark keeps in ``benchmarks/configs/lfm2-24b-a2b.py``, at small sizes on
the CPU with seeded random weights.

Everything here computes in float32 on both sides, so the tolerances are those
of float32 sums taken in another order (the program sorts tokens by expert and
multiplies grouped; flash attention folds blocks): ``1e-5`` absolute on values
of order 1, ``2e-5`` relative to the largest entry on gradients.
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
ATOL = 1e-5


@pytest.fixture(scope='module')
def lfm2():
    """(the configuration's module, its spec) as the harness loads them."""
    sys.path[:0] = [p for p in (BENCH,) if p not in sys.path]
    import catalog
    base = os.path.join(BENCH, 'configs', 'lfm2-24b-a2b')
    with open(base + '.json') as f:
        return catalog._module(base + '.py'), json.load(f)


def config_of(lfm2, layers_here=None, **sizes):
    """The tiny configuration, cut to the published layers ``layers_here``."""
    module, spec = lfm2
    if layers_here is not None:
        spec = dict(spec, layers_here=layers_here, num_hidden_layers=len(layers_here))
    return module.Config(spec, tiny=True, **sizes)


def packed_rows(module, config, seed, lengths):
    """Documents of ``lengths`` packed the yardstick's way."""
    rng = np.random.default_rng(seed)
    docs = [rng.integers(0, config.vocab, n).astype(np.int32) for n in lengths]
    return docs, module.pack_in_order(docs, list(range(len(docs))), config.max_len)


def close(got, want, rtol=2e-5):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= rtol * max(np.max(np.abs(want)), 1e-3), \
        np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-3)


#: published layers: 1 = conv + dense feed-forward, 2 = attention + experts,
#: 3 = conv + experts; [1..5] is the cell's own cut
CUTS = {'conv_dense': [1], 'attention_experts': [2], 'conv_experts': [3],
        'whole': [1, 2, 3, 4, 5]}
LENGTHS = [40, 7, 61, 128, 23, 90, 5, 33, 70]


@pytest.mark.parametrize('cut', sorted(CUTS))
def test_a_cut_of_the_model_agrees_with_the_reference(lfm2, cut):
    """Each mixer with each feed-forward, and the whole model: logits of every
    packed row, the loss, the losses of each document's first tokens, every
    gradient leaf."""
    module, _ = lfm2
    config = config_of(lfm2, CUTS[cut])
    key = jax.random.PRNGKey(3)
    _, batch = packed_rows(module, config, 11, LENGTHS)
    params, buffers = config.init_params(key), config.init_buffers(key)
    model = config.model()
    logits = model.apply({'params': params, 'buffers': buffers}, batch['tokens'],
                         batch['positions'], batch['segment_ids'],
                         mutable=['diagnostics'])[0]
    parts = config.reference_parts()
    on = np.ones(len(config.experts_held), np.float32)
    for r in range(len(batch['tokens'])):
        want = parts.logits(params, buffers, batch['tokens'][r],
                            batch['segment_ids'][r], batch['positions'][r], on, False)
        real = batch['segment_ids'][r] != 0       # padding's logits are no one's
        assert np.max(np.abs(np.asarray(logits[r]) - np.asarray(want))[real]) < ATOL
    # one step: loss, per-document losses, and the gradient the optimizer got
    state, out = jax.jit(config.train_step())(config.init_state(key), batch)
    reference = config.reference(key, [batch])
    assert float(out['loss']) == pytest.approx(reference['losses'][0], rel=1e-6)
    heads = len(reference['sample_losses'][0])
    assert heads == len(LENGTHS) * config.head_tokens
    close(out['sample_loss'][:heads], reference['sample_losses'][0])
    assert not np.asarray(out['sample_loss'][heads:]).any()
    norm = jax.tree_util.tree_map(lambda g: float(jnp.sqrt(jnp.sum(jnp.square(g)))),
                                  config.first_gradient(state, key))
    flat_got = jax.tree_util.tree_flatten_with_path(norm)[0]
    flat_want = jax.tree_util.tree_flatten_with_path(reference['grad_norms'])[0]
    assert [p for p, _ in flat_got] == [p for p, _ in flat_want]
    for (path, got), (_, want) in zip(flat_got, flat_want):
        assert got == pytest.approx(float(want), rel=1e-4, abs=1e-9), path


def test_every_gradient_leaf_agrees_element_by_element(lfm2):
    """The norms above could hide a rotated gradient: here every element of
    every leaf, for the whole model."""
    module, _ = lfm2
    config = config_of(lfm2)
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

    row = config.reference_row()
    grads = jax.tree_util.tree_map(jnp.zeros_like, params)
    on = np.ones(len(config.experts_held), np.float32)
    for r in range(len(batch['tokens'])):
        grads, _ = row(params, buffers, grads, batch['tokens'][r],
                       batch['segment_ids'][r], batch['positions'][r],
                       np.float32(1.0 / weights.sum()), on, False)
    got = jax.grad(program_loss)(params)
    for (path, g), (_, want) in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                                    jax.tree_util.tree_flatten_with_path(grads)[0]):
        close(g, want, rtol=5e-5)


def test_the_eight_shares_add_up_to_the_uncut_layer(lfm2):
    """64 experts of a small width, top-4: the results of the eight shares
    (experts 0-7, 8-15, ...), summed, are the uncut reference layer's; so are
    their gradients for the input and the router, and each share's gradient
    for its own matrices is the uncut one's slice."""
    from petastorm_tpu.models import moe
    config = config_of(lfm2, published_num_experts=64,
                       experts_held=list(range(64)))
    experts = config.reference_parts().experts
    d, f, tokens = config.hidden, config.d_expert, 96
    whole = moe.moe_share_init(jax.random.PRNGKey(0), d, f, 64, tuple(range(64)))
    bias = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (64,))
    x = jax.random.normal(jax.random.PRNGKey(2), (tokens, d))
    probe = jax.random.normal(jax.random.PRNGKey(3), (tokens, d))
    on = jnp.ones((64,))

    def uncut(p, x):
        return experts(p, bias, x, on)

    def share(p, x, held):
        mine = dict(p, **{k: p[k][held[0]:held[-1] + 1] for k in ('w1', 'w3', 'w2')})
        return moe.moe_share_apply(mine, x, held, config.top_k, expert_bias=bias)

    def summed(p, x):
        return sum(share(p, x, tuple(range(s, s + 8)))[0] for s in range(0, 64, 8))

    close(summed(whole, x), uncut(whole, x))
    routed = [share(whole, x, tuple(range(s, s + 8)))[1] for s in range(0, 64, 8)]
    assert sum(int(r['tokens_per_expert'].sum()) for r in routed) == tokens * 4
    assert sum(float(r['held_share']) for r in routed) == pytest.approx(1.0)
    got = jax.grad(lambda p, x: jnp.sum(summed(p, x) * probe), argnums=(0, 1))(whole, x)
    want = jax.grad(lambda p, x: jnp.sum(uncut(p, x) * probe), argnums=(0, 1))(whole, x)
    close(got[1], want[1])
    for name in ('router', 'w1', 'w3', 'w2'):
        close(got[0][name], want[0][name])


def test_no_token_is_dropped_under_skewed_routing(lfm2):
    """The bias sends every token to held expert 5 and none to held expert 6;
    there is no capacity, so expert 5 takes all of them and the result is
    still the reference's."""
    from petastorm_tpu.models import moe
    config = config_of(lfm2)                        # 16 experts, 4..7 held
    held, tokens = config.experts_held, 200
    params = moe.moe_share_init(jax.random.PRNGKey(0), config.hidden,
                                config.d_expert, config.num_experts, held)
    bias = jnp.zeros((config.num_experts,)).at[5].set(10.0).at[6].set(-10.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (tokens, config.hidden))
    y, stats = jax.jit(lambda p, x: moe.moe_share_apply(
        p, x, held, config.top_k, expert_bias=bias))(params, x)
    counts = dict(zip(held, np.asarray(stats['tokens_per_expert'])))
    assert counts[5] == tokens and counts[6] == 0
    want = config.reference_parts().experts(
        params, bias, x, jnp.ones((len(held),)))
    close(y, want)
    # and a share that holds none of the chosen experts adds exactly nothing
    none, stats = moe.moe_share_apply(
        params, x, held, config.top_k,
        expert_bias=jnp.zeros((config.num_experts,)).at[jnp.arange(4, 8)].set(-10.0))
    assert not np.asarray(none).any() and int(stats['tokens_per_expert'].sum()) == 0


def selection_bias(scores, held, by_all, by_none, some=None):
    """A selection bias under which every token picks the experts ``by_all``
    and no token any of ``by_none``; with ``some = (expert, n)`` exactly ``n``
    tokens, those keenest on it, pick that held expert beside them
    (``scores``: the router's, [T, E]; top-4)."""
    bias = np.zeros(scores.shape[1], np.float32)
    bias[list(by_all)], bias[list(by_none)] = 10.0, -10.0
    if some:
        expert, pickers = some
        absent = [e for e in range(scores.shape[1]) if e not in held]
        # it competes with the absent ones for the places ``by_all`` leave
        rival = np.sort(np.asarray(scores)[:, absent], axis=1)[:, len(by_all) - 4]
        need = np.sort(rival - np.asarray(scores)[:, expert])
        bias[expert] = (need[pickers - 1] + need[pickers]) / 2
    return jnp.asarray(bias)


#: 512 tokens x top-4 = 2048 assignments, 16 experts.  Holding experts 4-7 the
#: buffer has 2 x 2048 x 4 / 16 = 1024 rows; holding all there is no buffer.
#: name: (held, picked by every token, picked by none, (expert, its pickers)
#: or None, held assignments that makes)
TOKENS = 512
ROUTINGS = {
    'over_the_budget': ((4, 5, 6, 7), (4, 5, 6, 7), (), None, 2048),
    'one_over_the_budget': ((4, 5, 6, 7), (4, 5), (7,), (6, 1), 1025),
    'exactly_at_the_budget': ((4, 5, 6, 7), (4, 5), (6, 7), None, 1024),
    'one_under_the_budget': ((4, 5, 6, 7), (4,), (6, 7), (5, TOKENS - 1), 1023),
    'chosen_by_no_token': ((4, 5, 6, 7), (), (4, 5, 6, 7), None, 0),
    'as_the_router_likes': ((4, 5, 6, 7), (), (), None, None),
    'all_experts_held': (tuple(range(16)), (), (), None, 2048),
}


@pytest.mark.parametrize('routing', sorted(ROUTINGS))
def test_the_share_is_the_references_on_either_side_of_the_budget(lfm2, routing):
    """Forward and gradients (input, router, ``w1``, ``w3``, ``w2``) against
    the reference's experts, with the held assignments over, at, one off and
    far under the buffer's rows.  Which path a call takes follows from the
    routing alone (the selection bias), never from an argument."""
    from petastorm_tpu.models import moe
    held, by_all, by_none, some, count = ROUTINGS[routing]
    config = config_of(lfm2, experts_held=list(held))
    k, d, num_experts = config.top_k, config.hidden, config.num_experts
    budget = moe.share_budget(TOKENS, k, len(held), num_experts)
    assert (k, num_experts) == (4, 16)
    assert budget == (TOKENS * k if len(held) == num_experts else 1024)
    params = moe.moe_share_init(jax.random.PRNGKey(0), d, config.d_expert,
                                num_experts, held)
    x = jax.random.normal(jax.random.PRNGKey(1), (TOKENS, d))
    scores = jax.nn.sigmoid(jnp.dot(x, params['router'], precision='highest'))
    bias = selection_bias(scores, held, by_all, by_none, some)
    probe = jax.random.normal(jax.random.PRNGKey(2), (TOKENS, d))

    def program(p, x):
        y, stats = moe.moe_share_apply(p, x, held, k, expert_bias=bias)
        return jnp.sum(y * probe), (y, stats)

    def reference(p, x):
        y = config.reference_parts().experts(p, bias, x, jnp.ones((len(held),)),
                                             held=held)
        return jnp.sum(y * probe), y

    (_, (y, stats)), got = jax.jit(jax.value_and_grad(
        program, argnums=(0, 1), has_aux=True))(params, x)
    (_, want_y), want = jax.value_and_grad(
        reference, argnums=(0, 1), has_aux=True)(params, x)
    routed = int(stats['tokens_per_expert'].sum())
    assert count is None or routed == count
    assert int(stats['over_budget']) == int(routed > budget)
    assert float(stats['held_share']) == pytest.approx(routed / (TOKENS * k))
    close(y, want_y)
    close(got[1], want[1])
    for name in ('router', 'w1', 'w3', 'w2'):
        close(got[0][name], want[0][name])
    assert routed or not np.asarray(y).any()


def rows_of_all_assignments(jaxpr, rows, widths, found=None, in_fallback=False):
    """The arrays anywhere in ``jaxpr`` that have ``rows`` rows (all leading
    axes together) of one of ``widths`` columns, as ``{'fallback': n,
    'elsewhere': n, 'branches': n}``; the fallback is the branch a ``cond``
    takes where its predicate holds."""
    found = {'fallback': 0, 'elsewhere': 0, 'branches': 0} if found is None else found
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            shape = getattr(var.aval, 'shape', ())
            if len(shape) >= 2 and shape[-1] in widths \
                    and int(np.prod(shape[:-1])) == rows:
                found['fallback' if in_fallback else 'elsewhere'] += 1
        if eqn.primitive.name == 'cond':
            found['branches'] += 1
            within, beyond = eqn.params['branches']
            rows_of_all_assignments(within.jaxpr, rows, widths, found, in_fallback)
            rows_of_all_assignments(beyond.jaxpr, rows, widths, found, True)
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            rows_of_all_assignments(sub, rows, widths, found, in_fallback)
    return found


@pytest.mark.parametrize('held', [(4, 5, 6, 7), tuple(range(16))],
                         ids=['a_quarter_held', 'all_held'])
def test_only_the_fallback_holds_rows_for_all_assignments(lfm2, held):
    """A count the CPU may state: in the share's program, forward and
    backward, at the tiny configuration's shapes (512 tokens x top-4), no
    array outside the fallback branch has ``T * top_k`` rows of the model's or
    an expert's width.  Where all experts are held there is no branch, and
    the one path's buffer is all assignments."""
    from petastorm_tpu.models import moe
    config = config_of(lfm2, experts_held=list(held))
    tokens, k = config.batch * config.max_len, config.top_k
    d, f = config.hidden, config.d_expert
    shapes = jax.eval_shape(lambda key: moe.moe_share_init(
        key, d, f, config.num_experts, held), jax.random.PRNGKey(0))

    def loss(p, x):
        return jnp.sum(moe.moe_share_apply(p, x, held, k)[0])
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
        shapes, jax.ShapeDtypeStruct((tokens, d), jnp.float32))
    found = rows_of_all_assignments(jaxpr.jaxpr, tokens * k, (d, f))
    if len(held) == config.num_experts:
        assert moe.share_budget(tokens, k, len(held), config.num_experts) == tokens * k
        assert found['branches'] == 0 and found['elsewhere'] > 0
    else:
        assert moe.share_budget(tokens, k, len(held), config.num_experts) == 1024
        assert found['branches'] >= 2, found       # forward's and backward's
        assert found['elsewhere'] == 0 and found['fallback'] > 0, found


@pytest.mark.parametrize('cut', ['conv_experts', 'attention_experts'])
def test_a_packed_row_equals_its_documents_one_by_one(lfm2, cut):
    """No leak: the convolution's taps and attention stop at document
    boundaries, so a document's logits do not depend on what it is packed
    with."""
    module, _ = lfm2
    config = config_of(lfm2, CUTS[cut])
    key = jax.random.PRNGKey(9)
    docs, batch = packed_rows(module, config, 4, [30, 3, 50, 40, 1, 64])
    variables = {'params': config.init_params(key),
                 'buffers': config.init_buffers(key)}
    model = config.model()

    def logits_of(batch):
        return np.asarray(model.apply(
            variables, batch['tokens'], batch['positions'], batch['segment_ids'],
            mutable=['diagnostics'])[0])
    packed = logits_of(batch)
    starts = module.starts_of(batch['segment_ids'])
    places = list(zip(*np.nonzero(starts)))
    assert len(places) == len(docs)
    for doc, (r, at) in zip(docs, places):
        alone = logits_of(module.pack_in_order([doc], [0], config.max_len))
        assert np.max(np.abs(packed[r, at:at + len(doc)] - alone[0, :len(doc)])) < ATOL


def test_sample_loss_is_the_loss_of_each_documents_first_tokens(lfm2):
    """``sample_loss`` of the step and of the reference: ``head_tokens`` losses
    a document, those of its first tokens (0 where it has no such token, or
    that token no target), against a loop over the documents; documents of one
    and two tokens among them."""
    module, _ = lfm2
    config = config_of(lfm2)
    assert config.head_tokens == 2
    key = jax.random.PRNGKey(13)
    lengths = [1, 2, 3, 40, 2, 1, 77, 128, 9, 1]
    docs, batch = packed_rows(module, config, 6, lengths)
    logits = config.model().apply(
        {'params': config.init_params(key), 'buffers': config.init_buffers(key)},
        batch['tokens'], batch['positions'], batch['segment_ids'],
        mutable=['diagnostics'])[0]
    log_p = np.asarray(jax.nn.log_softmax(logits), np.float64)
    want = []
    for doc, (r, at) in zip(docs, zip(*np.nonzero(module.starts_of(batch['segment_ids'])))):
        want += [-log_p[r, at + j, doc[j + 1]] if j + 1 < len(doc) else 0.0
                 for j in range(config.head_tokens)]
    _, out = jax.jit(config.train_step())(config.init_state(key), batch)
    close(out['sample_loss'][:len(want)], want)
    assert not np.asarray(out['sample_loss'][len(want):]).any()
    close(config.reference(key, [batch])['sample_losses'][0], want)


def test_a_leaking_tap_shows_in_the_documents_first_tokens(lfm2):
    """The planted fault ``leaking_tap`` (a convolution that was never handed
    the segment ids) against the sound reference: the gradient's norms barely
    move, the loss of the documents' first tokens does, and a document with
    nothing before it in its row is untouched."""
    module, _ = lfm2
    sys.path[:0] = [p for p in (BENCH,) if p not in sys.path]
    import oracle
    config = config_of(lfm2)
    key = jax.random.PRNGKey(17)
    _, batch = packed_rows(module, config, 8, LENGTHS)
    sound = config.reference(key, [batch])
    leaking = config.reference(key, [batch], fault='leaking_tap')
    gaps = oracle.training_gaps(leaking, sound)
    assert gaps['first_sample_loss_gap'] > 0.3, gaps
    assert gaps['grad_gap_median'] < 0.1 * gaps['first_sample_loss_gap'], gaps
    rows, at = np.nonzero(module.starts_of(batch['segment_ids']))
    moved = (np.abs(leaking['sample_losses'][0] - sound['sample_losses'][0])
             .reshape(len(at), config.head_tokens) > 1e-4)
    assert not moved[at == 0].any() and moved[at != 0].all(), (at, moved)


def test_the_weights_have_the_shapes_the_model_asks_for(lfm2):
    config = config_of(lfm2)
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


def test_the_old_blocks_parameter_tree_is_as_it_was():
    """``TransformerLM`` at its defaults still builds attention + GELU blocks
    with biases, named as before."""
    from petastorm_tpu.models.transformer import TransformerLM
    model = TransformerLM(vocab_size=32, d_model=16, num_heads=2, num_layers=1,
                          d_ff=24, max_seq_len=8, attn_fn=lambda q, k, v, causal: v)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))['params']
    assert sorted(shapes['block_0']) == ['attn', 'ffw_in', 'ffw_out', 'ln1', 'ln2']
    assert sorted(shapes['block_0']['attn']) == ['out', 'qkv']
    assert sorted(shapes['block_0']['ffw_in']) == ['bias', 'kernel']
    assert sorted(shapes) == ['block_0', 'embed', 'ln_f', 'pos_embed']
