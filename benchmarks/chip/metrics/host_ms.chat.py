"""Host time per step: the wall time of ``ServeLoop.run(max_steps=1)``
less the device time of the decode program inside it, in ms, summed
over the traced steps and divided by their number.  Both come from
the profiler trace (the harness's ``bench.step`` spans and the device's
program executions).  Moves ``itl_p99_ms``."""


def read(run):
    tr = run.trace
    if not tr or not tr["steps"]:
        return None
    return 1e3 * tr["host_per_step_s"]
