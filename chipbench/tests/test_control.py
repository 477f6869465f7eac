"""The control, the plain reference computed in the next precision below
the configuration's and put in the program's place, fails the check of
each cell; the program passes it.  Small sizes on the CPU; the readings
that set the limits come from ``chipbench/control.py`` on the chip."""

import gc
import importlib

import pytest

from chipbench import run as R
from chipbench.spans import Spans
from chipbench.tests.faults import SMALL


@pytest.mark.parametrize("workload", ["sim.fig3_sc1.ccp_static",
                                      "cmm.phi4_ffn.lost_shard"])
def test_control_fails_and_program_passes(workload):
    spec = R.resolve(R.load_benchmark(), workload)
    small = SMALL[workload.split(".")[0]]
    config = dict(spec["config"], **small["config"])
    traffic = dict(spec["traffic"], **small["traffic"])
    generator = importlib.import_module(
        f"chipbench.generators.{config['generator']}")
    wl = generator.Workload(config, traffic, spec["limits"], 2 ** 31 + 5, 1,
                         Spans())
    wl.warm()
    wl.window(0.5)
    wl.release()
    gc.collect()
    program, control = wl.check(), wl.control()
    assert program.correct, program.as_dict()
    assert not control.correct, control.as_dict()
    # The control fails by a margin, not by rounding at the limit.
    assert any(v > 3 * lim and lim > 0 for _, v, lim in control.items)
