"""Device time a step of the expert layers but their grouped products: the
operations under the scopes ``pt/moe_route`` (scores, top-k, the sort by
expert, the gathers of the assignments' rows), ``pt/moe_experts`` (the masks
and activations around the products), ``pt/moe_combine`` (the weighted
scatter-add back to the tokens) and ``pt/moe_shared`` (the shared expert),
joined to the trace by ``step_scopes``.  The ``conditional`` events that
enclose a layer's operations are not counted again.  The grouped products
are not here: the TPU compiler names its ``ragged-dot`` custom-calls itself,
with no name stack, so they lie under no scope (``unscoped_ms``) and
``expert_ffn_ms`` reads them by that name; the two together are the layers.
``None`` without a trace or where the program names no such scope."""

import step_scopes

SCOPES = ('pt/moe_route', 'pt/moe_experts', 'pt/moe_combine', 'pt/moe_shared')


def read(c):
    return step_scopes.ms(c, SCOPES)
