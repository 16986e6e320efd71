"""Time to pull a step's logits to the host after the device is done,
ms a step: from the end of the step's decode program on the latest chip
to the end of the program's ``serve.pull`` span, averaged over the
traced steps (``spans.reduce``).  Moves ``itl_p99_ms``."""


def read(run):
    return run.spans.get("pull_ms")
