"""Device time of the decode program outside every named scope, ms a
step (such as whole-cache copies), mean over the chips and the traced
steps (``spans.reduce``).  Moves ``itl_p99_ms``."""


def read(run):
    return run.spans.get("unscoped_ms")
