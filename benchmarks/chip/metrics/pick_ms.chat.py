"""Host time to pick a step's tokens, ms a step: the program's
``serve.pick`` span (argmax of each slot's logits and the slots'
bookkeeping), averaged over the traced steps (``spans.reduce``).
Moves ``itl_p99_ms``."""


def read(run):
    return run.spans.get("pick_ms")
