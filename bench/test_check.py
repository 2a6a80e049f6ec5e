"""The comparison that decides ``correct``, driven through a whole run
at a size the CPU holds: the harness's look for a chip is skipped, the
rest of a run is the real one (weights, calibration, fold, warm-up,
window, snapshot, reference).  A sound run passes; the control (the
reference one precision step below, in the program's place) fails; and
so does a decode step that returns its cache unchanged, planted under
the harness.  Served tokens altered where they are sampled, or half of
the batch served the other half's tokens, raise ``gap_worst_p50``, which
has no limit until it is read on the chip (PERF.md).  An exchange
between chips does not exist in these one-chip serving cells.
"""

import copy
import os
import re

import pytest

from bench import run as bench_run
from bench import spec


def tiny(config: str):
    """The configuration on ``decode-long`` at its family's CPU test
    sizes (``TINY``: two layers and a narrow model, with engine knobs to
    match), with its limits, and traffic scaled to match."""
    c = spec.cell_of(config, "decode-long",
                     end_to_end=[{"name": "output_tok_s", "unit": "tokens/s"}])
    conf = copy.deepcopy(c.config)
    small = spec.family(conf["family"]).TINY
    conf.update(small["model"])
    conf["engine"].update(small["engine"])
    c.config = conf
    c.traffic = copy.deepcopy(c.traffic)
    c.traffic["prompt"].update(median=20, min=8, max=60)
    c.traffic["output"].update(median=12, min=4, max=40)
    return c


def _run(name, fault=None, control=False, seed=2**31 + 11):
    return bench_run.run_cell(tiny(name), seed, 2.0, False, control=control,
                              fault=fault, peaks=spec.peaks("TPU v5 lite"))


# every configuration file, so that a new family's whole run comes with it
CONFIGS = sorted(os.path.splitext(f)[0] for f in
                 os.listdir(os.path.join(spec.BENCH, "configs"))
                 if f.endswith(".json"))


@pytest.mark.parametrize("name", CONFIGS)
def test_sound_run_passes_and_control_fails(name, capsys):
    out = _run(name, control=True)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    err = capsys.readouterr().err
    assert "control correct: False" in err


@pytest.mark.parametrize("name", CONFIGS)
def test_unchanged_state_fails(name):
    out = _run(name, fault="state_unchanged")
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("fault", ["token_altered", "half_batch",
                                   "layer_skipped"])
def test_served_token_faults_raise_the_gap(fault, capsys):
    """``gap_worst_p50`` has no chip readings yet and so no limit: at the
    test size, a request served wrong tokens, or the output of a model
    with its middle layer skipped, reads more than three times the gap of
    a sound run."""
    def gap(**kw):
        _run("stablelm-3b-w4a4", **kw)
        err = capsys.readouterr().err
        return float(re.search(r"^reading gap_worst_p50: (\S+)$", err,
                               re.M).group(1))
    assert gap(fault=fault) > 3 * gap()
