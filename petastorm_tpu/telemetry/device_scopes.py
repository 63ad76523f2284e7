"""Device time by named scope: the step's traced operations joined to the
``pt/*`` scopes of its optimised HLO.

The model code names the parts of a step with ``jax.named_scope('pt/...')``
(``pt/attention``, ``pt/ffn``, ``pt/moe_experts``, ...; the catalogue is in
``docs/observability.md``, "Profiler spans").  A profiler trace does not hold
those names: an operation's event is named by its HLO instruction
(``fusion.576``, ``pt_flash_fwd.1``) and its stats hold no scope.  The scope
is in the ``op_name`` metadata of that instruction in the optimised module,
which ``compiled.as_text()`` prints.  This module joins the two; it has no
timer of its own.

Rules of :func:`scope_table`:

* **scope**: the innermost ``pt/<name>`` component of the instruction's
  ``op_name`` (``.../b0/pt/ffn/Dense_1/dot_general`` -> ``pt/ffn``), or
  ``None`` where it has none.  ``jit(pt_bench_train_step)`` is no scope.
* **pass**: ``'recompute'`` where the name stack holds
  ``rematted_computation`` (a block recomputed under ``jax.checkpoint``), else
  ``'backward'`` where it holds ``transpose(``, else ``'forward'``.
* A fusion that carries no metadata takes that of its fused computation's
  ROOT (or, where the ROOT has none either, of the last instruction before
  it that has some).  Any other instruction without a name stack has no
  scope: the layout copies XLA adds, and the operations a compiler pass makes
  and names itself (the TPU compiler's ``ragged-dot-none`` custom-calls, the
  grouped expert products, carry that name alone).
* Every computation counts: the entry, branches, loop bodies and wrappers.
  A Pallas kernel is the custom-call it was lowered to (``pt_flash_fwd.1``)
  and has the scope it was called under.

:func:`by_scope` sums ``[name, seconds]`` pairs of traced operations by
scope.  Operations whose opcode is ``conditional``, ``while`` or ``call`` are
left out: their events span their bodies' operations, which the trace lists
under their own names.

On an operator's own profile::

    compiled = jax.jit(train_step).lower(state, batch).compile()
    table = device_scopes.scope_table(compiled.as_text())
    ms = device_scopes.by_scope(op_seconds, table, steps)

where ``op_seconds`` are ``[name, seconds]`` of the step's operations in the
trace (the ``XLA Ops`` line of ``/device:TPU:0``, each name cut at its
`` = ``; ``benchmarks/trace_reduce.py`` does this) over ``steps`` steps.
"""

import re

#: the two extra keys of :func:`by_scope`: operations under no ``pt/`` scope
#: (``None``) and names the module does not hold
UNMATCHED = '<unmatched>'
PASSES = ('forward', 'recompute', 'backward')
#: opcodes whose events enclose their bodies' operations
CONTAINERS = frozenset(('conditional', 'while', 'call'))

_SCOPE = re.compile(r'(?:^|/)pt/([A-Za-z0-9_]+)')
_COMPUTATION = re.compile(r'^(?:ENTRY\s+)?%?([^\s(]+).*\{\s*$')
_INSTRUCTION = re.compile(r'^\s+(ROOT\s+)?%?([^\s=]+)\s+=\s+(.*)$')
_OPCODE = re.compile(r'\s*([a-z][a-z0-9\-]*)\(')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r'\bcalls=%?([^\s,}]+)')


def scope_of(op_name):
    """The innermost ``pt/<name>`` of a name stack, or ``None``."""
    found = _SCOPE.findall(op_name or '')
    return 'pt/' + found[-1] if found else None


def pass_of(op_name):
    if 'rematted_computation' in op_name:
        return 'recompute'
    return 'backward' if 'transpose(' in op_name else 'forward'


def _opcode(rest):
    """The opcode after an instruction's result type, which is a tuple in
    balanced parentheses or a word without spaces."""
    if rest.startswith('('):
        depth = 0
        for i, ch in enumerate(rest):
            depth += {'(': 1, ')': -1}.get(ch, 0)
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest[rest.find(' '):]
    found = _OPCODE.match(rest)
    return found.group(1) if found else None


def scope_table(hlo_text):
    """``{instruction name: (scope, pass, opcode)}`` for every instruction of
    every computation of an HLO module's text (optimised, or as lowered:
    ``lowered.as_text('hlo', debug_info=True)``)."""
    parsed, roots, in_order = {}, {}, {}
    computation = None
    for line in hlo_text.splitlines():
        if not line.startswith(' '):
            head = _COMPUTATION.match(line)
            computation = head.group(1) if head else None
            continue
        found = _INSTRUCTION.match(line) if computation else None
        if not found:
            continue
        is_root, name, rest = found.groups()
        op_name = _OP_NAME.search(rest)
        calls = _CALLS.search(rest)
        parsed[name] = (op_name.group(1) if op_name else None, _opcode(rest),
                        calls.group(1) if calls else None)
        in_order.setdefault(computation, []).append(name)
        if is_root:
            roots[computation] = name
    table = {}
    for name, (op_name, opcode, calls) in parsed.items():
        if op_name is None and opcode == 'fusion' and calls in roots:
            op_name = _fused_op_name(parsed, in_order[calls], roots[calls])
        table[name] = (scope_of(op_name), pass_of(op_name or ''), opcode)
    return table


def _fused_op_name(parsed, names, root):
    """The ROOT's ``op_name``, else that of the last instruction before the
    ROOT that has one."""
    for name in reversed(names[:names.index(root) + 1]):
        if parsed[name][0] is not None:
            return parsed[name][0]
    return None


def by_scope(op_seconds, table, steps):
    """Device seconds a step by scope: ``{scope: {'seconds': s, 'forward':
    s, 'recompute': s, 'backward': s}}``, with the key ``None`` for
    operations under no ``pt/`` scope and :data:`UNMATCHED` for names the
    table does not hold (which have no split).  Containers are left out."""
    out = {}
    for name, seconds in op_seconds:
        found = table.get(name)
        if found is None:
            key, pass_ = UNMATCHED, None
        elif found[2] in CONTAINERS:
            continue
        else:
            key, pass_ = found[0], found[1]
        entry = out.setdefault(key, dict.fromkeys(('seconds',) + PASSES, 0.0))
        entry['seconds'] += seconds / steps
        if pass_ is not None:
            entry[pass_] += seconds / steps
    return out


def largest(op_seconds, table, steps, scope=None, n=10):
    """The ``n`` operations of ``scope`` (``None``: under no scope) that took
    most device time, as ``[name, seconds a step]``."""
    found = {}
    for name, seconds in op_seconds:
        entry = table.get(name)
        if entry is not None and entry[0] == scope and entry[2] not in CONTAINERS:
            found[name] = found.get(name, 0.0) + seconds / steps
    return sorted(([name, s] for name, s in found.items()),
                  key=lambda kv: -kv[1])[:n]
