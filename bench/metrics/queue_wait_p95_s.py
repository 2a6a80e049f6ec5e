"""queue_wait_p95_s: 95th percentile, over requests admitted inside the
window, of the time from the request's due time to the engine's
``admit`` event (the benchmark's front queue and the engine's queue
together: everything before prefill starts)."""

import numpy as np


def read(run):
    waits = [e["ts"] - run.due[e["uid"]] for e in run.events
             if e["ev"] == "admit" and e["uid"] in run.due
             and run.in_window(e["ts"])]
    return float(np.percentile(waits, 95)) if waits else None
