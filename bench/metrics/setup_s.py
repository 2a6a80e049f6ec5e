"""setup_s: seconds from process start to the window's opening (weights,
calibration, fold, engine, warming every shape the traffic uses)."""


def read(run):
    return run.setup_s
