"""Device time a step that no ``pt/`` scope claims: the step's operations
under none (the optimizer's update, layout copies XLA adds, parts of a mixer
that no scope wraps, and operations a compiler pass makes and names itself,
such as the TPU's ``ragged-dot`` custom-calls that ``expert_ffn_ms`` reads)
and the names the step's HLO does not hold (another program's operations in
the window), joined to the trace by ``step_scopes``.  Its ``explain`` prints
the whole scope table of the run on an earlier line: every scope's ms a step
with its forward / recompute / backward split, and the ten largest
operations under no scope.  ``None`` without a trace or without the join."""

import step_scopes


def explain(c):
    joined = step_scopes.scopes(c)
    table = {'(none)' if name is None else name:
             {k: round(1e3 * v, 4) for k, v in entry.items()}
             for name, entry in sorted(joined['by_scope'].items(),
                                       key=lambda kv: -kv[1]['seconds'])}
    return {'scopes_ms': table,
            'total_ms': 1e3 * sum(e['seconds'] for e in joined['by_scope'].values()),
            'unscoped_ops_ms': [[name, 1e3 * s] for name, s in joined['unscoped_ops']],
            'hlo': joined['hlo']}


def read(c):
    joined = step_scopes.scopes(c)
    if joined is None:
        return None
    return 1e3 * sum(joined['by_scope'][name]['seconds']
                     for name in step_scopes.UNSCOPED if name in joined['by_scope'])
