"""``telemetry.device_scopes``: the ``pt/*`` scopes of a compiled training
step's optimised HLO, and device time summed by scope."""

import jax
import jax.numpy as jnp
import optax
import pytest

from petastorm_tpu.models.transformer import TransformerLM
from petastorm_tpu.telemetry import device_scopes

#: every scope of the tiny model below
MODEL_SCOPES = {'pt/embed', 'pt/norm', 'pt/attention', 'pt/lfm2_conv', 'pt/ffn',
                'pt/moe_route', 'pt/moe_experts', 'pt/moe_combine',
                'pt/moe_shared', 'pt/lm_head_loss'}


def _attention(q, k, v, causal=True, **_):
    scores = jnp.einsum('bqhd,bkhd->bhqk', q, k)
    return jnp.einsum('bhqk,bkhd->bqhd', jax.nn.softmax(scores, -1), v)


@pytest.fixture(scope='module')
def compiled_step():
    """A two-layer ``TransformerLM`` under ``nn.remat``: attention with a
    SwiGLU feed-forward, then a short convolution with an expert layer and a
    shared expert; its loss under ``value_and_grad``, lowered and compiled
    on the CPU."""
    model = TransformerLM(
        vocab_size=64, d_model=32, num_heads=2, num_layers=2, d_ff=48,
        max_seq_len=16, dtype=jnp.float32, attn_fn=_attention, remat=True,
        pos_embed='rope', layer_types=('full_attention', 'conv'), ffn='moe',
        num_dense_layers=1, use_bias=False,
        moe={'num_experts': 4, 'top_k': 2, 'd_expert': 16, 'd_shared': 24})
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    variables = model.init(jax.random.PRNGKey(0), tokens)

    def train_step(params, tokens):
        def loss(p):
            logits = model.apply(dict(variables, params=p), tokens,
                                 mutable=['diagnostics'])[0]
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, tokens).mean()
        return jax.value_and_grad(loss)(params)
    lowered = jax.jit(train_step).lower(variables['params'], tokens)
    return lowered, lowered.compile().as_text()


def test_every_scope_of_the_model_is_in_the_table(compiled_step):
    lowered, text = compiled_step
    table = device_scopes.scope_table(text)
    named = {scope for scope, _, _ in table.values()} - {None}
    assert named == MODEL_SCOPES
    lowered_table = device_scopes.scope_table(lowered.as_text('hlo', debug_info=True))
    assert {scope for scope, _, _ in lowered_table.values()} - {None} == named


@pytest.mark.parametrize('scope', ['pt/ffn', 'pt/attention', 'pt/moe_experts'])
def test_a_recomputed_block_shows_all_three_passes(compiled_step, scope):
    table = device_scopes.scope_table(compiled_step[1])
    passes = {p for s, p, _ in table.values() if s == scope}
    assert passes == set(device_scopes.PASSES)


def test_the_step_name_is_no_scope(compiled_step):
    table = device_scopes.scope_table(compiled_step[1])
    assert any(s is None for s, _, _ in table.values())   # the optimizer's glue
    assert device_scopes.scope_of('jit(pt_bench_train_step)/jvp(M)/mul') is None
    assert device_scopes.scope_of(
        'jit(pt_bench_train_step)/jvp(M)/b0/pt/attention/pt/diff_attention/mul') \
        == 'pt/diff_attention'


HAND_MADE = '''HloModule jit_pt_bench_train_step, entry_computation_layout={(f32[8]{0}, pred[])->f32[8]{0}}

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  ROOT %multiply.1 = f32[8]{0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(pt_bench_train_step)/transpose(jvp(M))/jvp(M)/checkpoint/b0/pt/ffn/Dense_0/mul" stack_frame_id=3}
}

%fused_computation.2 (param_0.2: f32[8]) -> (f32[8], f32[8]) {
  %param_0.2 = f32[8]{0} parameter(0)
  %exponential.2 = f32[8]{0} exponential(%param_0.2), metadata={op_name="jit(pt_bench_train_step)/transpose(jvp(M))/jvp(M)/checkpoint/rematted_computation/b1/moe/pt/moe_route/exp"}
  ROOT %tuple.2 = (f32[8]{0}, f32[8]{0}) tuple(%exponential.2, %param_0.2)
}

%branch_0.3 (p.3: f32[8]) -> f32[8] {
  %p.3 = f32[8]{0} parameter(0)
  ROOT %fusion.3 = f32[8]{0} fusion(%p.3), kind=kLoop, calls=%fused_computation.1
}

%branch_1.4 (p.4: f32[8]) -> f32[8] {
  %p.4 = f32[8]{0} parameter(0)
  %fusion.4 = (f32[8]{0}, f32[8]{0}) fusion(%p.4), kind=kLoop, calls=%fused_computation.2
  ROOT %get-tuple-element.4 = f32[8]{0} get-tuple-element(%fusion.4), index=0
}

ENTRY %main.9 (x.1: f32[8], p.1: pred[]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0)
  %p.1 = pred[] parameter(1)
  %copy-start.2 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]) copy-start(%x.1)
  %copy-done.2 = f32[8]{0:S(1)} copy-done(%copy-start.2)
  %pt_flash_fwd.1 = (f32[8]{0:T(256)}, f32[8]{0}) custom-call(%copy-done.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(pt_bench_train_step)/jvp(M)/b0/pt/attention/pt_flash_fwd"}
  %get-tuple-element.5 = f32[8]{0} get-tuple-element(%pt_flash_fwd.1), index=0
  %ragged-dot-none.8 = f32[8]{0} custom-call(%x.1, /*index=1*/%get-tuple-element.5), custom_call_target="ragged_dot", metadata={op_name="ragged-dot-none"}
  %conditional.6 = f32[8]{0} conditional(%p.1, %get-tuple-element.5, %ragged-dot-none.8), branch_computations={%branch_0.3, %branch_1.4}, metadata={op_name="jit(pt_bench_train_step)/transpose(jvp(M))/b1/moe/pt/moe_experts/cond"}
  ROOT %add.7 = f32[8]{0} add(%conditional.6, %x.1), metadata={op_name="jit(pt_bench_train_step)/add"}
}
'''


def test_a_hand_made_module_reads_as_documented():
    table = device_scopes.scope_table(HAND_MADE)
    # a fusion without metadata takes its fused ROOT's, in a branch as well
    assert table['fusion.3'] == ('pt/ffn', 'backward', 'fusion')
    # ... and where the ROOT (a tuple) has none, the last before it that has
    assert table['fusion.4'] == ('pt/moe_route', 'recompute', 'fusion')
    # a kernel is its custom-call, with the scope it was called under
    assert table['pt_flash_fwd.1'] == ('pt/attention', 'forward', 'custom-call')
    assert table['conditional.6'] == ('pt/moe_experts', 'backward', 'conditional')
    assert table['add.7'] == (None, 'forward', 'add')
    assert table['multiply.1'][0] == 'pt/ffn'     # inside a fused computation
    # an operation the compiler made and named, and one without metadata,
    # lie under no scope
    assert table['ragged-dot-none.8'] == (None, 'forward', 'custom-call')
    assert table['get-tuple-element.5'][0] is None
    assert table['copy-done.2'] == (None, 'forward', 'copy-done')


def test_containers_are_not_counted_again():
    table = device_scopes.scope_table(HAND_MADE)
    out = device_scopes.by_scope(
        [['conditional.6', 3.0], ['fusion.3', 1.0], ['fusion.4', 2.0]], table, 1)
    assert out == {'pt/ffn': {'seconds': 1.0, 'forward': 0.0, 'recompute': 0.0,
                              'backward': 1.0},
                   'pt/moe_route': {'seconds': 2.0, 'forward': 0.0,
                                    'recompute': 2.0, 'backward': 0.0}}


def test_by_scope_sums_exactly_and_keeps_what_it_cannot_place():
    table = {'fusion.1': ('pt/ffn', 'forward', 'fusion'),
             'fusion.2': ('pt/ffn', 'backward', 'fusion'),
             'fusion.3': ('pt/ffn', 'recompute', 'fusion'),
             'copy.4': (None, 'forward', 'copy'),
             'while.5': ('pt/kda_scan', 'forward', 'while')}
    ops = [['fusion.1', 0.5], ['fusion.2', 1.25], ['fusion.3', 0.25],
           ['fusion.1', 0.5], ['copy.4', 0.75], ['while.5', 8.0],
           ['fusion.99', 0.125]]
    out = device_scopes.by_scope(ops, table, steps=2)
    assert out['pt/ffn'] == {'seconds': 1.25, 'forward': 0.5, 'recompute': 0.125,
                             'backward': 0.625}
    assert out[None] == {'seconds': 0.375, 'forward': 0.375, 'recompute': 0.0,
                         'backward': 0.0}
    assert out[device_scopes.UNMATCHED]['seconds'] == 0.0625
    assert 'pt/kda_scan' not in out
    total = sum(s for name, s in ops if not name.startswith('while')) / 2
    assert sum(e['seconds'] for e in out.values()) == total
    assert device_scopes.largest(ops, table, 2, scope='pt/ffn', n=2) == \
        [['fusion.2', 0.625], ['fusion.1', 0.5]]
    assert device_scopes.largest(ops, table, 2) == [['copy.4', 0.375]]
