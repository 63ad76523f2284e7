"""Long-context Transformer LM: forward, attention-strategy equivalence,
tensor-parallel param shardings, and a short training sanity loop."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from petastorm_tpu.models.transformer import (TransformerLM, make_attn_fn,
                                              param_shardings)
from petastorm_tpu.parallel import make_mesh

VOCAB, D_MODEL, HEADS, LAYERS, D_FF, SEQ = 64, 32, 4, 2, 64, 32


def _model(attn_fn, **kw):
    return TransformerLM(vocab_size=VOCAB, d_model=D_MODEL, num_heads=HEADS,
                         num_layers=LAYERS, d_ff=D_FF, max_seq_len=SEQ,
                         dtype=jnp.float32, attn_fn=attn_fn, **kw)


@pytest.fixture(scope='module')
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (4, SEQ), 0, VOCAB, jnp.int32)


@pytest.fixture(scope='module')
def dense_params(tokens):
    model = _model(make_attn_fn(strategy='dense'))
    return model.init(jax.random.PRNGKey(0), tokens)['params']


def test_forward_shapes_and_finite(tokens, dense_params):
    logits = _model(make_attn_fn(strategy='dense')).apply(
        {'params': dense_params}, tokens)
    assert logits.shape == (4, SEQ, VOCAB)
    assert logits.dtype == jnp.float32
    assert np.isfinite(np.asarray(logits)).all()


def test_flash_matches_dense(tokens, dense_params):
    dense = _model(make_attn_fn(strategy='dense')).apply({'params': dense_params}, tokens)
    flash = _model(make_attn_fn(strategy='flash')).apply({'params': dense_params}, tokens)
    np.testing.assert_allclose(np.asarray(flash), np.asarray(dense),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize('strategy', ['ring', 'ulysses'])
def test_sequence_parallel_matches_dense(tokens, dense_params, strategy):
    """Same params, sequence sharded over 4 devices: identical logits."""
    mesh = make_mesh({'data': 1, 'seq': 4}, devices=jax.devices()[:4])
    model = _model(make_attn_fn(mesh, strategy, head_axis=None))
    sharded_tokens = jax.device_put(tokens, NamedSharding(mesh, P(None, 'seq')))
    got = jax.jit(lambda p, t: model.apply({'params': p}, t))(dense_params,
                                                              sharded_tokens)
    want = _model(make_attn_fn(strategy='dense')).apply({'params': dense_params}, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_tensor_parallel_matches_dense(tokens, dense_params):
    """Megatron-sharded params over a model axis: identical logits."""
    mesh = make_mesh({'data': 2, 'model': 2}, devices=jax.devices()[:4])
    shardings = param_shardings(dense_params, mesh)
    sharded = jax.device_put(dense_params, shardings)
    model = _model(make_attn_fn(strategy='flash'))
    got = jax.jit(lambda p, t: model.apply({'params': p}, t))(
        sharded, jax.device_put(tokens, NamedSharding(mesh, P('data', None))))
    want = model.apply({'params': dense_params}, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


def test_param_shardings_cover_tree(dense_params):
    mesh = make_mesh({'data': 2, 'model': 2}, devices=jax.devices()[:4])
    shardings = param_shardings(dense_params, mesh)
    flat = jax.tree_util.tree_leaves_with_path(shardings)
    assert len(flat) == len(jax.tree_util.tree_leaves(dense_params))
    by_name = {jax.tree_util.keystr(path): s.spec for path, s in flat}
    assert by_name["['embed']['embedding']"] == P('model', None)
    qkv = [s for n, s in by_name.items() if 'qkv' in n and 'kernel' in n]
    assert qkv and all(s == P(None, None, 'model', None) for s in qkv)
    ffw_in = [s for n, s in by_name.items() if 'ffw_in' in n and 'kernel' in n]
    assert ffw_in and all(s == P(None, 'model') for s in ffw_in)
    norms = [s for n, s in by_name.items() if 'ln' in n]
    assert norms and all(s == P() for s in norms)


def test_remat_matches_and_trains(tokens):
    import optax
    model = _model(make_attn_fn(strategy='flash'), remat=True)
    params = model.init(jax.random.PRNGKey(0), tokens)['params']
    tx = optax.adam(1e-2)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state):
        def loss_fn(p):
            logits = model.apply({'params': p}, tokens)
            labels = jnp.roll(tokens, -1, axis=1)
            return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state2 = tx.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state2, loss

    losses = []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0], 'loss did not decrease: %s' % losses


def test_make_attn_fn_packed_strategies():
    """segment_ids reach every strategy through make_attn_fn (packed rows)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from petastorm_tpu.parallel import full_attention, make_mesh

    rng = np.random.default_rng(3)
    B, S, H, D = 2, 32, 8, 8
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
               for _ in range(3))
    seg = np.zeros((B, S), np.int32)
    seg[:, :12] = 1
    seg[:, 12:26] = 2
    seg = jnp.asarray(seg)
    want = full_attention(q, k, v, causal=True, segment_ids=seg)

    mesh = make_mesh({'seq': 8})
    seg_sh = jax.device_put(seg, NamedSharding(mesh, P(None, 'seq')))
    for strategy, ids in (('dense', seg), ('flash', seg),
                          ('ring', seg_sh), ('ulysses', seg_sh)):
        fn = make_attn_fn(mesh=mesh, strategy=strategy, head_axis=None,
                          segment_ids=ids)
        got = fn(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5, err_msg=strategy)


def test_gqa_forward_and_train():
    """Grouped-query attention: fewer KV heads, same interface; trains."""
    model = TransformerLM(vocab_size=50, d_model=32, num_heads=4,
                          num_layers=1, d_ff=64, max_seq_len=16,
                          num_kv_heads=2, dtype=jnp.float32)
    tokens = jnp.asarray(np.arange(16, dtype=np.int32)[None, :] % 50)
    params = model.init(jax.random.PRNGKey(0), tokens)
    # separate q/kv projections replace the fused qkv
    attn_params = params['params']['block_0']['attn']
    assert 'q' in attn_params and 'kv' in attn_params and 'qkv' not in attn_params
    assert attn_params['kv']['kernel'].shape == (32, 2, 2, 8)
    logits = model.apply(params, tokens)
    assert logits.shape == (1, 16, 50)
    grads = jax.grad(lambda p: model.apply(p, tokens).sum())(params)
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree_util.tree_leaves(grads))


def test_gqa_rejects_indivisible():
    model = TransformerLM(vocab_size=50, d_model=32, num_heads=4,
                          num_layers=1, d_ff=64, max_seq_len=16,
                          num_kv_heads=3)
    with pytest.raises(ValueError, match='num_kv_heads'):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


def test_gqa_tp_sharding():
    from petastorm_tpu.models.transformer import param_shardings
    from petastorm_tpu.parallel import make_mesh
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh({'data': 4, 'model': 2})
    model = TransformerLM(vocab_size=64, d_model=32, num_heads=4,
                          num_layers=1, d_ff=64, max_seq_len=16,
                          num_kv_heads=2, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))['params']
    shardings = param_shardings(params, mesh)
    attn = shardings['block_0']['attn']
    assert attn['q']['kernel'].spec == P(None, 'model', None)
    assert attn['kv']['kernel'].spec == P(None, None, 'model', None)
    sharded = jax.device_put(params, shardings)
    out = jax.jit(lambda p, t: model.apply({'params': p}, t))(
        sharded, jnp.zeros((4, 8), jnp.int32))
    assert np.isfinite(np.asarray(out)).all()


def test_mqa_sharding_falls_back_to_replication():
    """MQA (kv_heads=1) under 2-way TP: the kv leaf replicates instead of
    producing an invalid sharding."""
    from petastorm_tpu.models.transformer import param_shardings
    from petastorm_tpu.parallel import make_mesh
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh({'data': 4, 'model': 2})
    model = TransformerLM(vocab_size=64, d_model=32, num_heads=4,
                          num_layers=1, d_ff=64, max_seq_len=16,
                          num_kv_heads=1, dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))['params']
    shardings = param_shardings(params, mesh)
    attn = shardings['block_0']['attn']
    assert attn['kv']['kernel'].spec == P()         # replicated fallback
    assert attn['q']['kernel'].spec == P(None, 'model', None)
    jax.device_put(params, shardings)               # must not raise


def test_rope_translation_invariance():
    """RoPE attends by RELATIVE position: shifting all positions by a
    constant must not change the logits (no learned absolute table)."""
    model = TransformerLM(vocab_size=50, d_model=32, num_heads=2,
                          num_layers=2, d_ff=64, max_seq_len=64,
                          pos_embed='rope', dtype=jnp.float32)
    tokens = jnp.asarray(np.arange(12, dtype=np.int32)[None, :] % 50)
    params = model.init(jax.random.PRNGKey(0), tokens)
    base = model.apply(params, tokens,
                       positions=jnp.arange(12)[None, :])
    shifted = model.apply(params, tokens,
                          positions=jnp.arange(12)[None, :] + 7)
    np.testing.assert_allclose(np.asarray(base), np.asarray(shifted),
                               rtol=2e-4, atol=2e-4)
    # ...while a learned table does change (sanity that the test can fail)
    learned = TransformerLM(vocab_size=50, d_model=32, num_heads=2,
                            num_layers=2, d_ff=64, max_seq_len=64,
                            dtype=jnp.float32)
    lp = learned.init(jax.random.PRNGKey(0), tokens)
    a = learned.apply(lp, tokens, positions=jnp.arange(12)[None, :])
    c = learned.apply(lp, tokens, positions=jnp.arange(12)[None, :] + 7)
    assert not np.allclose(np.asarray(a), np.asarray(c))


def test_rope_packed_equals_solo_documents():
    """Packed row + per-segment positions + RoPE: each document's logits
    must equal running it alone (the packing correctness contract)."""
    import functools
    from petastorm_tpu.jax import packing

    model_kw = dict(vocab_size=50, d_model=32, num_heads=2, num_layers=2,
                    d_ff=64, max_seq_len=32, pos_embed='rope',
                    dtype=jnp.float32)
    rng = np.random.default_rng(3)
    docs = [rng.integers(0, 50, L).astype(np.int32) for L in (9, 7, 5)]
    batch = packing.pack_sequences(docs, max_len=24)
    tokens = jnp.asarray(batch['tokens'])
    seg = jnp.asarray(batch['segment_ids'])
    pos = jnp.asarray(batch['positions'])

    packed_model = TransformerLM(
        attn_fn=functools.partial(packing.packed_attention, segment_ids=seg),
        **model_kw)
    params = packed_model.init(jax.random.PRNGKey(1), tokens)
    packed_logits = np.asarray(packed_model.apply(params, tokens,
                                                  positions=pos))

    solo_model = TransformerLM(**model_kw)
    seg_np, tok_np = np.asarray(seg), np.asarray(tokens)
    for row in range(tok_np.shape[0]):
        for s in range(1, seg_np[row].max() + 1):
            m = seg_np[row] == s
            doc = tok_np[row][m]
            solo = np.asarray(solo_model.apply(
                params, jnp.asarray(doc[None, :])))
            np.testing.assert_allclose(packed_logits[row][m], solo[0],
                                       rtol=3e-4, atol=3e-4,
                                       err_msg='row %d seg %d' % (row, s))


def test_rope_rejects_bad_mode_and_odd_head_dim():
    with pytest.raises(ValueError, match='pos_embed'):
        TransformerLM(vocab_size=10, d_model=8, num_heads=2, num_layers=1,
                      d_ff=16, max_seq_len=8, pos_embed='alibi').init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    # odd head_dim (d_model=6, heads=2 -> hd=3) is rejected by rope()
    with pytest.raises(ValueError, match='even head_dim'):
        TransformerLM(vocab_size=10, d_model=6, num_heads=2, num_layers=1,
                      d_ff=16, max_seq_len=8, pos_embed='rope').init(
            jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))


def _pallas_names(jaxpr, out=None):
    """The names of the Pallas calls in ``jaxpr`` and every jaxpr inside it,
    in order."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == 'pallas_call':
            out.append(eqn.params['name'])
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                if isinstance(sub, (ClosedJaxpr, Jaxpr)):
                    _pallas_names(getattr(sub, 'jaxpr', sub), out)
    return out


#: Tiny recomputed models of the packed cells' attention layers, each with the
#: forward kernel calls its step makes once: ``lfm2.packed``'s grouped-query
#: layer (RoPE, RMS-normalised q and k) beside a convolution; latent attention
#: at q/k 12 and v 8 whose K/V stream in two chunks (``kimilinear.packed``);
#: differential attention in a window, in full with its K and V handed on, and
#: across over them (``phi4flash.packed``).  The flash kernels run through the
#: interpreter at 8 x 8 tiles.
_KEPT_CASES = {
    'gqa': (dict(layer_types=('conv', 'full_attention'), num_kv_heads=2,
                 pos_embed='rope', qk_norm=True, use_bias=False, ffn='swiglu'),
            {}, 1),
    'mla_chunked': (dict(layer_types=('mla',), pos_embed='none',
                         mla=dict(num_heads=4, kv_rank=16, qk_nope_dim=8,
                                  qk_rope_dim=4, v_dim=8)),
                    dict(kv_chunk=16), 2),
    'differential': (dict(layer_types=('sliding_attention', 'full_attention',
                                       'cross_attention'),
                          num_kv_heads=2, diff_attn=True, window=12,
                          pos_embed='none', norm='layer', ffn='swiglu'),
                     {}, 3),
}


def _kept_model(kw, flash_kw):
    import functools
    from petastorm_tpu.ops import flash_attention
    return TransformerLM(
        vocab_size=VOCAB, d_model=D_MODEL, num_heads=HEADS,
        num_layers=len(kw['layer_types']), d_ff=D_FF, max_seq_len=SEQ,
        dtype=jnp.float32, remat=True,
        attn_fn=functools.partial(flash_attention, block_q=8, block_k=8,
                                  **flash_kw), **kw)


def _packed_batch():
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, SEQ), 0, VOCAB, jnp.int32)
    seg = np.zeros((2, SEQ), np.int32)
    seg[0, :20], seg[0, 20:30] = 1, 2
    seg[1, :7], seg[1, 7:] = 1, 2
    return tokens, jnp.asarray(seg)


@pytest.mark.parametrize('case', sorted(_KEPT_CASES))
def test_recomputed_block_keeps_the_flash_forward(monkeypatch, case):
    """``remat=True`` keeps a flash forward call's ``out`` and ``lse`` across
    the block's recomputation: the gradient's program holds one forward
    kernel call for each attention layer and K/V chunk, where recomputing
    everything (the policy ``None``) held two, and loss and gradients are
    those of recomputing everything, to the bit."""
    import optax
    from petastorm_tpu.models import transformer
    kw, flash_kw, calls = _KEPT_CASES[case]
    model = _kept_model(kw, flash_kw)
    tokens, seg = _packed_batch()
    params = model.init(jax.random.PRNGKey(0), tokens, segment_ids=seg)['params']

    def loss(p):
        logits = model.apply({'params': p}, tokens, segment_ids=seg)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.roll(tokens, -1, axis=1)).mean()

    def program():
        names = _pallas_names(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
        return names.count('pt_flash_fwd'), names.count('pt_flash_bwd_dq')
    kept, got = program(), jax.jit(jax.value_and_grad(loss))(params)
    monkeypatch.setattr(transformer, 'REMAT_POLICY', None)
    assert program() == (2 * calls, calls)
    assert kept == (calls, calls)
    want = jax.jit(jax.value_and_grad(loss))(params)
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_recomputed_block_without_flash_is_unchanged(monkeypatch):
    """A model of convolutions and delta-rule layers names nothing that
    ``REMAT_POLICY`` keeps: its gradient lowers to the program that
    recomputing everything gives."""
    from petastorm_tpu.models import transformer
    model = TransformerLM(
        vocab_size=VOCAB, d_model=D_MODEL, num_heads=HEADS, num_layers=2,
        d_ff=D_FF, max_seq_len=SEQ, dtype=jnp.float32, remat=True,
        layer_types=('conv', 'kda'), pos_embed='none',
        kda=dict(num_heads=HEADS, head_dim=8, gate_rank=8))
    tokens, seg = _packed_batch()
    params = model.init(jax.random.PRNGKey(0), tokens, segment_ids=seg)

    def lowered():
        return jax.jit(jax.grad(lambda p: model.apply(
            p, tokens, segment_ids=seg).sum())).lower(params).as_text()
    kept = lowered()
    monkeypatch.setattr(transformer, 'REMAT_POLICY', None)
    assert kept == lowered()
