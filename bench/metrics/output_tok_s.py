"""output_tok_s: every output token emitted inside the window, over the
window's seconds (first tokens from prefill included)."""


def read(run):
    n = sum(1 for ts in run.tokens.values() for t in ts if run.in_window(t))
    return n / run.seconds
