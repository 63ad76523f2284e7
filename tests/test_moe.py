"""Expert-parallel MoE vs the single-device oracle on the 8-device CPU mesh."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from petastorm_tpu.models.moe import (make_expert_parallel_moe, moe_apply,
                                      moe_init)
from petastorm_tpu.parallel import make_mesh

D, F, E = 16, 32, 8


@pytest.fixture(scope='module')
def params():
    return moe_init(jax.random.PRNGKey(0), D, F, E)


@pytest.fixture(scope='module')
def tokens():
    rng = np.random.default_rng(5)
    return jnp.asarray(rng.standard_normal((64, D)), jnp.float32)


def _place(fn_shardings, params, tokens, token_sharding):
    placed_params = jax.tree_util.tree_map(
        jax.device_put, params, fn_shardings(params))
    placed_tokens = jax.device_put(tokens, token_sharding)
    return placed_params, placed_tokens


@pytest.mark.parametrize('mesh_axes', [
    {'data': 2, 'expert': 4},
    {'data': 1, 'expert': 8},
    {'data': 8},               # no expert axis: pure DP degenerates cleanly
])
def test_matches_oracle(params, tokens, mesh_axes):
    mesh = make_mesh(mesh_axes)
    # Ample capacity: no token drops, so sharded == dense oracle exactly.
    fn, shardings, token_sharding = make_expert_parallel_moe(
        mesh, E, capacity_factor=float(E))
    p, x = _place(shardings, params, tokens, token_sharding)
    got = jax.jit(fn)(p, x)
    want = moe_apply(params, tokens, capacity_factor=float(E))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_gradients_match_oracle(params, tokens):
    mesh = make_mesh({'data': 2, 'expert': 4})
    fn, shardings, token_sharding = make_expert_parallel_moe(
        mesh, E, capacity_factor=float(E))
    p, x = _place(shardings, params, tokens, token_sharding)

    def loss_sharded(p, x):
        return jnp.sum(fn(p, x) ** 2)

    def loss_dense(p, x):
        return jnp.sum(moe_apply(p, x, capacity_factor=float(E)) ** 2)

    got = jax.jit(jax.grad(loss_sharded))(p, x)
    want = jax.grad(loss_dense)(params, tokens)
    for key in ('router', 'w1', 'w2'):
        np.testing.assert_allclose(np.asarray(got[key]), np.asarray(want[key]),
                                   rtol=5e-4, atol=5e-4)


def test_capacity_drops_tokens():
    """Tiny capacity: overflow tokens contribute zero (outputs differ from
    the ample-capacity result but stay finite and bounded)."""
    params = moe_init(jax.random.PRNGKey(1), D, F, 2)
    # All tokens route wherever they like; capacity_factor=0.25 keeps only
    # ~an eighth of slots per expert.
    x = jnp.asarray(np.random.default_rng(0).standard_normal((32, D)),
                    jnp.float32)
    tight = moe_apply(params, x, capacity_factor=0.25)
    ample = moe_apply(params, x, capacity_factor=4.0)
    assert np.isfinite(np.asarray(tight)).all()
    dropped_rows = np.asarray(jnp.all(tight == 0, axis=-1)).sum()
    assert dropped_rows > 0  # something actually overflowed
    assert not np.allclose(np.asarray(tight), np.asarray(ample))


def test_indivisible_experts_rejected():
    mesh = make_mesh({'expert': 8})
    with pytest.raises(ValueError, match='divisible'):
        make_expert_parallel_moe(mesh, num_experts=6)


def test_the_share_budgets_factor_defaults_to_twice_the_fair_share():
    """``budget_factor`` at its default leaves the share's program what it
    was (the lowered text of a call that names no factor and of one that names
    2 are the same), and a larger factor only widens the buffer."""
    from petastorm_tpu.models import moe
    assert moe.share_budget(32768, 4, 8, 64) == moe.share_budget(32768, 4, 8, 64, 2) \
        == 32768
    assert moe.share_budget(16384, 8, 8, 256) == 8192
    assert moe.share_budget(16384, 8, 8, 256, 3) == 12288
    assert moe.share_budget(64, 2, 8, 8, 3) == 128          # never over all of them
    held = (2, 3)
    shapes = jax.eval_shape(lambda key: moe.moe_share_init(key, D, F, E, held),
                            jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1024, D), jnp.float32)

    def lowered(**kw):
        return jax.jit(lambda p, x: moe.moe_share_apply(p, x, held, 2, **kw)) \
            .lower(shapes, x).as_text()
    assert lowered() == lowered(budget_factor=2)
    assert lowered() != lowered(budget_factor=3)
