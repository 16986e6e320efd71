"""The whole step's share of the chips' peak, %: the operations every
step of the window and the drain needed (``step_flops`` of the
architecture's ``Shapes``; for ``dense_gqa``, matmuls for the token of
each admitted slot, attention over its valid context, the unembedding
where a token was produced), over the steps' wall time on the host
clock times chips times peak bf16 FLOP/s.  Moves ``itl_p99_ms``."""


def read(run):
    steps = run.steps
    wall = sum(s.end - s.start for s in steps)
    if not steps or wall <= 0:
        return None
    flops = sum(run.shapes.step_flops(s) for s in steps)
    return 100.0 * flops / (wall * run.chips * run.peak["bf16_flops_per_s"])
