"""prefill_ms_per_ktok: over the engine's ``prefill`` spans that start
inside the window (whole prompts, suffixes and chunks; the whole window,
not the traced slice), their summed duration in milliseconds over the
thousands of real prompt tokens they wrote.  A span runs from before its
host arrays are built until its logits exist."""


def read(run):
    spans = [e for e in run.events
             if e["ev"] == "prefill" and "t0" in e and run.in_window(e["t0"])]
    tokens = sum(e["n_tokens"] for e in spans)
    if not tokens:
        return None
    return 1e3 * sum(e["ts"] - e["t0"] for e in spans) / (tokens / 1e3)
