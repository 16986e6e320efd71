"""Share of the traced window in which no operation ran on the device,
%: one minus the union of the device's op intervals over the window,
averaged over the chips.  Moves ``itl_p99_ms``."""


def read(run):
    tr = run.trace
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * tr["idle_share"]
