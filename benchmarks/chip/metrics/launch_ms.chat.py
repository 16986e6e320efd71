"""Host time to launch a step, ms a step: from the start of the
program's ``serve.step`` span to the end of its ``serve.launch`` (slots
admitted, tokens and positions to the device, the decode program
dispatched), averaged over the traced steps (``spans.reduce``).
Moves ``itl_p99_ms``."""


def read(run):
    return run.spans.get("launch_ms")
