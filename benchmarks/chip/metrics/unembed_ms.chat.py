"""The unembedding's device time in the decode program, ms a step: self
time of the ops under the named scope ``unembed``, mean over the chips
and the traced steps (``spans.reduce``).  Moves ``itl_p99_ms``."""


def read(run):
    return run.spans.get("unembed_ms")
