"""decode_rows_mean: output tokens per decode dispatch inside the window
(the engine's ``tick`` events: rows that decoded in each)."""

import numpy as np


def read(run):
    rows = [e["n_active"] for e in run.events
            if e["ev"] == "tick" and run.in_window(e["ts"])]
    return float(np.mean(rows)) if rows else None
