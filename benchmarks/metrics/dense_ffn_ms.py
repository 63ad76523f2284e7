"""Device time a step of the dense feed-forward layers: the operations under
the scope ``pt/ffn`` (``Block``'s ``gelu`` and ``swiglu`` feed-forward, not
the expert layers, which have scopes of their own), forward, recomputed and
backward, joined to the trace by ``step_scopes``.  Parts of AdamW's update
that XLA fuses into their operations count here.  ``None`` without a trace or
where the program names no such scope."""

import step_scopes

SCOPES = ('pt/ffn',)


def read(c):
    return step_scopes.ms(c, SCOPES)
