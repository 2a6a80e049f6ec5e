"""itl_p95_ms: 95th percentile of every gap between consecutive output
tokens of a request, both inside the window, in milliseconds."""

import numpy as np


def read(run):
    gaps = []
    for ts in run.tokens.values():
        w = [t for t in ts if run.in_window(t)]
        gaps += [b - a for a, b in zip(w, w[1:])]
    return 1e3 * float(np.percentile(gaps, 95)) if gaps else None
