"""Device time a step of the output head and the loss: the operations under
the scope ``pt/lm_head_loss`` (the head's product, the float32 logits, the
cross-entropy and their backward), joined to the trace by ``step_scopes``.
``None`` without a trace or where the program names no such scope."""

import step_scopes

SCOPES = ('pt/lm_head_loss',)


def read(c):
    return step_scopes.ms(c, SCOPES)
