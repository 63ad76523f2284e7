"""The traced window's device time a step by the program's ``pt/*`` scope, for
the per-layer metrics that read it (``dense_ffn_ms``, ``expert_layer_ms``,
``lm_head_loss_ms``, ``kda_conv_ms``, ``unscoped_ms``).

``petastorm_tpu.telemetry.device_scopes`` joins traced operations to the
scopes in the ``op_name`` metadata of the optimised HLO.  The HLO here is the
step's: once the window is over, the step that ``TimedPath`` jitted is
lowered again on the shapes of its state and a batch (the state itself was
donated and freed) and compiled, which hands back from JAX's caches the
executable that the window ran.  JAX leaves metadata out of the persistent
cache's key, so an executable read back from it may carry the scopes of the
program that wrote it; where it lacks a scope that the lowered step names,
the step is compiled once more with every cache off.  Only a traced run gets
here, after its window.

The operations are the reduced trace's ``device_ops`` over its ``step_count``:
every program's operations in the window on the first device, by name.  The
step's are nearly all of them; an operation of the small transfer program
that shares a name with one of the step's counts with it, the others are
``<unmatched>``.  A program without ``device_scopes`` reads ``None``.
"""

import time

import oracle

#: the keys of ``device_scopes.by_scope`` that no ``pt/`` scope claims
UNSCOPED = (None, '<unmatched>')


def scopes(c):
    """``c['scopes']``, joined on the first call of a run: ``{'by_scope':
    device_scopes.by_scope(...), 'unscoped_ops': the ten largest operations
    under no scope, 'hlo': how the step's HLO was had}``; ``None`` without a
    trace or without ``device_scopes``."""
    if 'scopes' not in c:
        c['scopes'] = join(c)
    return c['scopes']


def ms(c, names):
    """Device ms a step of the scopes ``names``; ``None`` where none is there."""
    joined = scopes(c)
    if joined is None:
        return None
    found = [joined['by_scope'][name]['seconds'] for name in names
             if name in joined['by_scope']]
    return 1e3 * sum(found) if found else None


def join(c):
    trace = c.get('trace')
    if not trace or not trace['step_count']:
        return None
    try:
        from petastorm_tpu.telemetry import device_scopes
    except ImportError:      # a program from before the join
        return None
    t0 = time.monotonic()
    found = step_hlo(c['config'], device_scopes)
    if found is None:
        return None
    text, source = found
    table = device_scopes.scope_table(text)
    ops, steps = trace['device_ops'], trace['step_count']
    return {'by_scope': device_scopes.by_scope(ops, table, steps),
            'unscoped_ops': device_scopes.largest(ops, table, steps),
            'hlo': {'source': source, 'fetch_s': time.monotonic() - t0,
                    'instructions': len(table)}}


def abstract_args(config, step):
    """The shapes of the step's state as the window's steps see it (the
    output of a step) and of a batch."""
    import jax
    key = jax.eval_shape(lambda: oracle.key_of(0))
    state, batch = {name: shapes for name, _, shapes, _
                    in config.rehearsal_programs(key)}['step']
    return jax.eval_shape(step, state, batch)[0], batch


def named_scopes(table):
    return {scope for scope, _, _ in table.values()} - {None}


def step_hlo(config, device_scopes):
    """``(optimised HLO text of the window's step, 'kept' | 'compiled')``."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    made = getattr(config, '_timed_path_programs', None)
    if made is None:
        return None
    step = made[1]
    args = abstract_args(config, step)
    lowered = step.lower(*args)
    named = named_scopes(device_scopes.scope_table(
        lowered.as_text('hlo', debug_info=True)))
    text = lowered.compile().as_text()
    if named <= named_scopes(device_scopes.scope_table(text)):
        return text, 'kept'
    was = jax.config.jax_enable_compilation_cache
    jax.clear_caches()
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    try:
        return step.lower(*args).compile().as_text(), 'compiled'
    finally:
        jax.config.update('jax_enable_compilation_cache', was)
        compilation_cache.reset_cache()
