"""Device time a step of the delta-rule layers' three short convolutions
(q, k and v, four causal taps each, their SiLU and q's and k's L2
normalisation): the operations under the scope ``pt/kda_conv``, forward,
recomputed and backward, joined to the trace by ``step_scopes``.  ``None``
without a trace or where the program names no such scope."""

import step_scopes

SCOPES = ('pt/kda_conv',)


def read(c):
    return step_scopes.ms(c, SCOPES)
