"""prefill_useful_share: over the engine's ``prefill`` spans that start
inside the window, the real prompt tokens over the tokens dispatched
(rows padded to a power of two times the bucket- or chunk-padded
length), in percent."""


def read(run):
    spans = [e for e in run.events if e["ev"] == "prefill"
             and "padded_tokens" in e and run.in_window(e["t0"])]
    padded = sum(e["padded_tokens"] for e in spans)
    if not padded:
        return None
    return 100.0 * sum(e["n_tokens"] for e in spans) / padded
