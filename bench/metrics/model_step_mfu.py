"""model_step_mfu: the operations the model needs for every token served
in the traced steps (prompt tokens prefilled and tokens decoded), each
precision over its own peak (int8 for the quantized linears, bf16 for
attention, the SSM scan and the output head), over the traced window, in
percent."""

from bench.work import model_seconds


def read(run):
    if not run.trace or not run.dispatches:
        return None
    lo, hi = run.trace.window()
    return 100.0 * model_seconds(run.m, run.dispatches, run.peaks) / (hi - lo)
