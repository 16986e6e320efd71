"""The decode program's share of its roofline, %: for each traced step
the least time its needed work takes on the chips (the larger of
``step_flops`` over peak FLOP/s and ``step_bytes`` over HBM bandwidth,
of the architecture's ``Shapes``; for ``dense_gqa`` bytes are the
weights read once, the valid KV entries of admitted slots and the
entries written), summed, over the device time of the decode program
in the trace (mean over chips).  Moves ``itl_p99_ms``."""
from chipbench import work


def read(run):
    tr = run.trace
    if not tr or tr.get("program_s", 0) <= 0:
        return None
    first, last = run.traced_steps
    least = 0.0
    for s in run.steps[first:last]:
        t, _ = work.least_seconds(
            run.shapes.step_flops(s), run.shapes.step_bytes(s), run.chips,
            run.peak)
        least += t
    return 100.0 * least / tr["program_s"]
