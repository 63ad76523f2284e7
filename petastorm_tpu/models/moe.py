"""Mixture-of-Experts FFN with all-to-all expert parallelism.

No reference equivalent (the reference is a data library — SURVEY.md §2.6);
this is the transformer-side EP obligation, the GShard/Switch pattern done
the XLA way:

* **Routing** — Switch top-1: a replicated router picks one expert per
  token; the gate probability scales the expert output (so router gradients
  flow through the gate).
* **Capacity** — each expert accepts ``capacity`` token slots per device
  per step (``capacity_factor`` × fair share); overflow tokens are dropped
  (contribute zero), the standard fixed-shape trick that keeps everything
  static for XLA.
* **Dispatch** — one-hot dispatch/combine tensors turn routing into
  einsums (MXU work, no gathers), and two ``lax.all_to_all``s move token
  slots to the devices that own the experts and back — ICI traffic only,
  inside ``jax.shard_map``.

``moe_apply`` is the single-device oracle (all experts everywhere);
``make_expert_parallel_moe`` returns the sharded twin + param shardings.
Tested equal to the oracle (forward and gradients) on the CPU mesh.

The second half of the module is another layer: **one chip's share of a
top-k mixture with no capacity** (``moe_share_apply``, LFM2's expert layer;
``models/transformer.py::MoEShare`` is its module).  The router scores all
experts and picks ``top_k`` a token; the chip is told which experts it holds
and returns what those add, by grouped products over rows sorted by expert
(``jax.lax.ragged_dot``), so no token is dropped however skewed the routing.

* **Budget** -- only assignments that fall on held experts are moved.  They
  are laid in a buffer of a static size ``B`` (``share_budget``: twice the
  fair share ``T * top_k * held / num_experts``, in whole row tiles): one
  gather of ``[B, d]`` from the tokens, three grouped products over
  ``[B, .]``, one add of ``[B, d]`` into ``[T, d]`` by token.
* **Fallback** -- a step that routes more than ``B`` assignments to the held
  experts takes the path over all ``T * top_k`` sorted rows (``lax.cond`` on
  the count): the same sum, slower, nothing dropped.  ``stats['over_budget']``
  counts it.  Where half of the experts or more are held, ``B`` is
  ``T * top_k`` and there is no branch.
"""

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import NamedSharding, PartitionSpec as P


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _a2a(x, axis_name, split_axis, concat_axis):
    """``lax.all_to_all`` with a hand-written transpose.

    The stock transpose rule in this jax version returns the cotangent with
    the split/concat dims swapped (verified: a [El, ep, ...] cotangent comes
    back [ep, El, ...] and lowering fails); an all_to_all's transpose is
    simply the reverse all_to_all, written out here.
    """
    return jax.lax.all_to_all(x, axis_name, split_axis, concat_axis)


def _a2a_fwd(x, axis_name, split_axis, concat_axis):
    return _a2a(x, axis_name, split_axis, concat_axis), None


def _a2a_bwd(axis_name, split_axis, concat_axis, _, g):
    return (_a2a(g, axis_name, concat_axis, split_axis),)


_a2a.defvjp(_a2a_fwd, _a2a_bwd)


def fan_in_normal(rng, shape, dtype=jnp.float32):
    """Normal(0, 1 / fan_in) for a ``[..., fan_in, fan_out]`` stack of
    matrices: the initialiser of every router and expert matrix here, the
    Switch layer's and the top-k share layer's alike."""
    return (jax.random.normal(rng, shape) / np.sqrt(shape[-2])).astype(dtype)


def moe_init(rng, d_model, d_ff, num_experts, dtype=jnp.float32):
    """{'router': [d, E], 'w1': [E, d, f], 'w2': [E, f, d]}."""
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        'router': fan_in_normal(k1, (d_model, num_experts), dtype),
        'w1': fan_in_normal(k2, (num_experts, d_model, d_ff), dtype),
        'w2': fan_in_normal(k3, (num_experts, d_ff, d_model), dtype),
    }


def _route(params, x, capacity):
    """Switch top-1 dispatch/combine tensors for local tokens ``x [T, d]``.

    Returns (dispatch [T, E, C] one-hot slots, combine = dispatch * gate).
    Tokens beyond an expert's capacity get all-zero rows (dropped).
    """
    logits = x @ params['router']                     # [T, E]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)               # [T]
    gate = jnp.take_along_axis(probs, expert[:, None], axis=-1)[:, 0]  # [T]
    onehot = jax.nn.one_hot(expert, params['router'].shape[1],
                            dtype=jnp.float32)        # [T, E]
    # Slot index of each token within its expert (arrival order).
    slot = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot      # [T, E]
    kept = onehot * (slot < capacity)
    dispatch = kept[:, :, None] * jax.nn.one_hot(
        slot.astype(jnp.int32), capacity, dtype=jnp.float32)  # [T, E, C]
    combine = dispatch * gate[:, None, None].astype(jnp.float32)
    return dispatch, combine


def _expert_ffn(w1, w2, xs):
    """Per-expert FFN over slot buffers ``xs [E?, C?, d]`` (vmapped over E)."""
    return jax.vmap(lambda a, b, x: jax.nn.relu(x @ a) @ b)(w1, w2, xs)


def moe_apply(params, x, capacity_factor=2.0):
    """Single-device oracle: dense dispatch to every expert, no collectives.

    ``x``: [T, d] tokens; returns [T, d].
    """
    num_experts = params['router'].shape[1]
    capacity = _capacity(x.shape[0], num_experts, capacity_factor)
    dispatch, combine = _route(params, x, capacity)
    xs = jnp.einsum('tec,td->ecd', dispatch, x.astype(jnp.float32))
    ys = _expert_ffn(params['w1'].astype(jnp.float32),
                     params['w2'].astype(jnp.float32), xs)
    return jnp.einsum('tec,ecd->td', combine, ys).astype(x.dtype)


def _capacity(tokens, num_experts, capacity_factor):
    return max(1, int(np.ceil(tokens * capacity_factor / num_experts)))


def make_expert_parallel_moe(mesh, num_experts, expert_axis='expert',
                             batch_axis='data', capacity_factor=2.0):
    """shard_map-wrapped MoE over ``mesh``: experts sharded over
    ``expert_axis`` (leading E axis of w1/w2), tokens over ``batch_axis``.

    Tokens shard over BOTH axes (the expert axis does double duty as extra
    data parallelism — the standard GShard layout, so no device routes a
    token twice); experts shard over ``expert_axis`` alone, the router is
    replicated.

    Returns ``(fn, param_shardings_fn, token_sharding)``: ``fn(params, x)``
    on global ``x [T, d]`` placed with ``token_sharding``;
    ``param_shardings_fn(params)`` places the params.  ``num_experts`` must
    be divisible by the expert-axis size.
    """
    ep = mesh.shape[expert_axis] if expert_axis in mesh.axis_names else 1
    if num_experts % max(ep, 1):
        raise ValueError('num_experts=%d not divisible by %r axis size %d'
                         % (num_experts, expert_axis, ep))
    experts_local = num_experts // ep

    def inner(params, x):
        # x: [T_local, d]; every device routes its own tokens.
        capacity = _capacity(x.shape[0], num_experts, capacity_factor)
        dispatch, combine = _route(params, x, capacity)
        xs = jnp.einsum('tec,td->ecd', dispatch,
                        x.astype(jnp.float32))        # [E, C, d]
        d = xs.shape[-1]
        if ep > 1:
            # Send each expert block to its owner; receive my experts' slot
            # buffers from every peer: [E, C, d] -> [El, ep, C, d] (dim 1
            # indexes the source peer) -> [El, ep*C, d].
            xs = _a2a(xs.reshape(ep, experts_local, capacity, d),
                      expert_axis, 0, 1)
            xs = xs.reshape(experts_local, ep * capacity, d)
        ys = _expert_ffn(params['w1'].astype(jnp.float32),
                         params['w2'].astype(jnp.float32), xs)
        if ep > 1:
            # Route results back to the tokens' home devices:
            # [El, ep*C, d] -> [ep, El, C, d] -> [E, C, d], the same
            # expert-major order the forward reshape used.
            ys = _a2a(ys.reshape(experts_local, ep, capacity, d),
                      expert_axis, 1, 0)
            ys = ys.reshape(num_experts, capacity, d)
        return jnp.einsum('tec,ecd->td', combine, ys).astype(x.dtype)

    expert_spec = expert_axis if expert_axis in mesh.axis_names else None
    token_axes = tuple(ax for ax in (batch_axis, expert_axis)
                       if ax in mesh.axis_names)
    token_spec = P(token_axes) if token_axes else P()
    fn = jax.shard_map(
        inner, mesh=mesh,
        in_specs=({'router': P(), 'w1': P(expert_spec), 'w2': P(expert_spec)},
                  token_spec),
        out_specs=token_spec)

    def param_shardings(params):
        return {
            'router': NamedSharding(mesh, P()),
            'w1': NamedSharding(mesh, P(expert_spec)),
            'w2': NamedSharding(mesh, P(expert_spec)),
        }

    return fn, param_shardings, NamedSharding(mesh, token_spec)


# ---------------------------------------------------------------------------
# top-k experts, no capacity: one chip's share of an expert-parallel layer
# ---------------------------------------------------------------------------

def moe_share_shapes(d_model, d_ff, num_experts, experts_held):
    """{name: shape} of the parameters of :func:`moe_share_apply`: the router
    over ALL experts ``[d, E]`` and the SwiGLU matrices of the experts held
    here, ``w1`` and ``w3`` ``[H, d, f]``, ``w2`` ``[H, f, d]``; each is
    initialised by :func:`fan_in_normal`."""
    held = len(experts_held)
    return {'router': (d_model, num_experts),
            'w1': (held, d_model, d_ff),
            'w3': (held, d_model, d_ff),
            'w2': (held, d_ff, d_model)}


def moe_share_init(rng, d_model, d_ff, num_experts, experts_held,
                   dtype=jnp.float32):
    """Parameters of :func:`moe_share_apply` (:func:`moe_share_shapes`)."""
    shapes = moe_share_shapes(d_model, d_ff, num_experts, experts_held)
    return {name: fan_in_normal(key, shape, dtype) for (name, shape), key
            in zip(shapes.items(), jax.random.split(rng, len(shapes)))}


def sigmoid_top_k(logits, bias, top_k, scale=1.0, eps=1e-6):
    """Sigmoid scores, the ``top_k`` largest of ``score + bias`` selected
    (``bias`` steers the choice only and gets no gradient), weights
    ``score_i / (sum of the selected scores + eps) * scale``.  Returns
    ``(experts [T, k] int32, weights [T, k] float32)``."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, experts = jax.lax.top_k(
        jax.lax.stop_gradient(scores + bias.astype(jnp.float32)), top_k)
    picked = jnp.take_along_axis(scores, experts, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + eps) * scale
    return experts.astype(jnp.int32), weights


@jax.custom_vjp
def _permute(x, perm, inverse):
    """``x[perm]`` for a permutation: its transpose is the gather by the
    inverse permutation, not the scatter XLA would derive."""
    return x[perm]


def _permute_fwd(x, perm, inverse):
    return x[perm], (perm, inverse)


def _permute_bwd(res, g):
    perm, inverse = res
    return g[inverse], None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


#: The buffer of the budgeted path holds this many times the assignments a
#: share would get if the router spread them evenly over all experts.  Twice:
#: the steps of ``lfm2.packed`` brought 0.92-1.18 of the fair share (PERF.md
#: section 6, PR 31), a router in training drifts further than one twenty
#: steps old, and a step that brings more loses nothing but the saving (it
#: takes the path over all assignments, at more than twice this one's time).
#: The default of ``budget_factor``; a configuration whose held share swings
#: further states its own factor with the reading that set it.
_BUDGET_FACTOR = 2
#: Rows of the buffer come in multiples of this: the grouped product's row
#: tile on the TPU (its tile list has 64 + 7 entries for 32,768 rows in 8
#: groups: my chip run, PR 31).
_ROW_TILE = 512


def share_budget(tokens, top_k, held, num_experts, factor=_BUDGET_FACTOR):
    """Rows of the buffer that :func:`moe_share_apply` lays the held experts'
    assignments in: ``factor`` times the fair share
    ``tokens * top_k * held / num_experts``, in whole row tiles, and never
    more than all ``tokens * top_k`` assignments (then there is no buffer:
    one path, no branch)."""
    fair = -(-factor * tokens * top_k * held // num_experts)
    return min(tokens * top_k, -(-fair // _ROW_TILE) * _ROW_TILE)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rows_of(x, token, tokens):
    """``x[token]`` for ``x`` ``[tokens, d]``.  Its transpose adds the rows
    back by token, accumulated in float32 whatever ``x`` is (a token may be
    taken ``top_k`` times)."""
    return x[token]


def _rows_of_fwd(x, token, tokens):
    return x[token], token


def _rows_of_bwd(tokens, token, g):
    added = _add_by_token(g.astype(jnp.float32), token, tokens)
    return added.astype(g.dtype), None


_rows_of.defvjp(_rows_of_fwd, _rows_of_bwd)


def _add_by_token(rows, token, tokens):
    """``out[t]`` = the sum of the ``rows`` whose ``token`` is ``t``: a
    scatter-add of ``[B, d]`` into ``[tokens, d]`` (its transpose, which JAX
    derives, is the gather ``g[token]``).  PERF.md section 6 (PR 31) has what
    the other ways to add cost on the chip."""
    return jnp.zeros((tokens, rows.shape[1]), rows.dtype).at[token].add(rows)


#: What the budgeted path keeps for its backward pass: the gathered rows and
#: the three grouped products' results.  Everything else (masks, SiLU, the
#: weighted rows) is elementwise and recomputed there, so it never crosses
#: the ``cond``'s boundary, where XLA would have to write each out in full.
_KEPT = 'pt_moe_kept'
_kept = functools.partial(checkpoint_name, name=_KEPT)


def _swiglu_groups(xs, w1, w3, w2, group_sizes):
    """SwiGLU of the held experts over rows sorted by expert, ``group_sizes``
    of them to each; what it returns in the rows past the last group is 0."""
    in_a_group = (jnp.arange(xs.shape[0]) < jnp.sum(group_sizes))[:, None]

    def grouped(rows, matrices):
        # rows past the last group belong to no held expert: the grouped
        # product visits none of them, and what it leaves there, in the
        # result as in the cotangent, is not data (on the TPU not even
        # finite), so both are blanked
        rows = jnp.where(in_a_group, rows, 0)
        out = jax.lax.ragged_dot(rows, matrices, group_sizes)
        return jnp.where(in_a_group, out, 0)
    gate, up = _kept(grouped(xs, w1)), _kept(grouped(xs, w3))
    return _kept(grouped(jax.nn.silu(gate) * up, w2))


def _share_of_all(x, w1, w3, w2, weights, local, order, group_sizes):
    """All ``T * top_k`` assignments, sorted, through the grouped products
    and back to their tokens: the path of a step whose held assignments do
    not fit the budget."""
    tokens, top_k = weights.shape
    held = group_sizes.shape[0]
    with jax.named_scope('pt/moe_route'):
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        xs = _permute(jnp.repeat(x, top_k, axis=0), order, inverse)
    with jax.named_scope('pt/moe_experts'):
        ys = _swiglu_groups(xs, w1, w3, w2, group_sizes)
    with jax.named_scope('pt/moe_combine'):
        here = (local < held).reshape(tokens, top_k)
        ya = _permute(ys, inverse, order).reshape(tokens, top_k, x.shape[1])
        return jnp.sum(ya.astype(jnp.float32)
                       * jnp.where(here, weights, 0.0)[:, :, None], axis=1)


def _share_in_budget(budget, x, w1, w3, w2, weights, local, order,
                     group_sizes):
    """The same sum over the first ``budget`` places of ``order`` alone,
    which hold every held assignment when ``sum(group_sizes) <= budget``:
    no array here has ``T * top_k`` rows of a token's or an expert's width.
    The places past the last group hold absent experts' assignments: their
    rows are gathered and added too, as zeros."""
    tokens, top_k = weights.shape
    with jax.named_scope('pt/moe_route'):
        first = order[:budget]
        token = first // top_k
        xs = _kept(_rows_of(x, token, tokens))
    with jax.named_scope('pt/moe_experts'):
        ys = _swiglu_groups(xs, w1, w3, w2, group_sizes)
    with jax.named_scope('pt/moe_combine'):
        gate = weights.reshape(-1)[first]
        return _add_by_token(ys.astype(jnp.float32) * gate[:, None], token,
                             tokens)


def moe_share_apply(params, x, experts_held, top_k, expert_bias=None,
                    scale=1.0, eps=1e-6, dtype=None,
                    budget_factor=_BUDGET_FACTOR):
    """What the experts held here add to a top-k mixture's result.

    ``x``: [T, d] tokens.  The router scores every token over ALL experts
    (``params['router']`` is ``[d, E]``) and selects ``top_k`` of them
    (:func:`sigmoid_top_k`); this chip holds the experts ``experts_held``
    (global ids, in the order of the leading axis of ``w1``/``w3``/``w2``)
    and returns ``sum over the selected experts held here of w_i * E_i(x)``,
    ``E_i`` SwiGLU.  What the absent experts would add is left out: in an
    expert-parallel job the shares of all chips add up to the whole layer
    (``tests/test_lfm2.py`` holds that), and one chip alone runs without
    the exchange.  There is no capacity and no token is dropped.

    The ``T * top_k`` assignments are sorted by expert as int32 keys, those
    of held experts first; only rows of held experts are moved.  The first
    ``B`` = :func:`share_budget` (``budget_factor`` times the fair share)
    places of that order name the tokens whose
    rows are gathered into a ``[B, d]`` buffer (one gather from ``[T, d]``),
    the held experts' matrices are applied as grouped products over the
    ragged groups (``jax.lax.ragged_dot``: on the TPU a grouped matmul that
    visits only the tiles of rows that belong to a group), and the weighted
    float32 rows are added into ``[T, d]`` by token.  A step that routes
    more than ``B`` assignments to the held experts takes the path over all
    ``T * top_k`` rows instead (``lax.cond`` on the count; the same sum, so
    nothing is dropped and nothing approximated; it recomputes its forward
    in the backward pass, so the step taken within budget saves no residual
    of that size).  Where ``B`` would be ``T * top_k`` (half of the experts
    or more are held) that path is the only one and there is no branch.

    Returns ``(y [T, d], stats)``; ``stats['tokens_per_expert']`` ``[H]`` is
    how many tokens went to each held expert, ``stats['held_share']`` the
    share of all assignments that fell on held experts and
    ``stats['over_budget']`` (int32) 1 where this call took the path over
    all assignments because they did not fit ``B``, else 0.
    """
    tokens, d_model = x.shape
    held = len(experts_held)
    num_experts = params['router'].shape[1]
    dtype = dtype or x.dtype
    if expert_bias is None:
        expert_bias = jnp.zeros((num_experts,), jnp.float32)
    with jax.named_scope('pt/moe_route'):
        # float32 at full precision whatever ``dtype`` is: the choice of
        # top_k experts is discrete, and a bfloat16 product picks other
        # experts on near ties
        logits = jnp.dot(x.astype(jnp.float32),
                         params['router'].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        experts, weights = sigmoid_top_k(logits, expert_bias, top_k, scale, eps)
        # global expert id -> place among the held ones; ``held`` = not here
        place = np.full((num_experts,), held, np.int32)
        place[np.asarray(experts_held)] = np.arange(held)
        local = jnp.asarray(place)[experts].reshape(-1)          # [T * k]
        order = jnp.argsort(local, stable=True)
        # a compare and a sum: ``bincount``'s scatter of T * k ones takes
        # the chip longer than the sort above
        group_sizes = jnp.sum(local[:, None] == jnp.arange(held), axis=0,
                              dtype=jnp.int32)
    operands = (x.astype(dtype),
                *(params[k].astype(dtype) for k in ('w1', 'w3', 'w2')),
                weights, local, order, group_sizes)
    budget = share_budget(tokens, top_k, held, num_experts, budget_factor)
    in_budget = jax.checkpoint(
        functools.partial(_share_in_budget, budget),
        policy=jax.checkpoint_policies.save_only_these_names(_KEPT))
    over_budget = jnp.sum(group_sizes) > budget
    if budget == tokens * top_k:            # never over: one path, no branch
        y = in_budget(*operands)
    else:
        y = jax.lax.cond(over_budget, jax.checkpoint(_share_of_all), in_budget,
                         *operands)
    stats = {'tokens_per_expert': group_sizes,
             'held_share': jnp.sum(group_sizes) / (tokens * top_k),
             'over_budget': over_budget.astype(jnp.int32)}
    return y.astype(x.dtype), stats
