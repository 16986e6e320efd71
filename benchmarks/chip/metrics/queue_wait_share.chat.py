"""Share of all time to first token that requests spent waiting for a
slot: from each request's due time to the start of the step that
admitted it, over all requests of the window, from the harness's
host-clock stamps.  Moves ``ttft_p90_s``."""
from chipbench import records


def read(run):
    if not run.records:
        return None
    return records.queue_wait_share(run.records, run.end)
