"""Self time per round of the round program's LM head and loss: the ops under
the ``client`` scope with a ``head`` segment (``models/model.py``: the
vocabulary tiles' logits and their reductions, the loss, and the custom
backward's recompute, weight gradient and ``dh``), forward and backward
(``scope_ms.py``)."""

import scope_ms

UNIT = "ms"


def read(ctx):
    return scope_ms.sum_ms(ctx, every=("head",))
