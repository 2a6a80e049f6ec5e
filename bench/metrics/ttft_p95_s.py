"""ttft_p95_s: 95th percentile, over every request due inside the
window, of the time from its due time to its first token; a request with
no token by the close counts with the wait it has had."""

import numpy as np


def read(run):
    waits = []
    for uid, due in run.due.items():
        if not run.in_window(due):
            continue
        ts = run.tokens.get(uid)
        first = ts[0] if ts else run.t_end
        waits.append(min(first, run.t_end) - due)
    return float(np.percentile(waits, 95)) if waits else None
