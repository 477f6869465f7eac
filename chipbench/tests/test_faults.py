"""A run whose timed path is broken underneath comes out not correct, and
one that is sound comes out correct: each cell, at a small size on the CPU,
past the harness's look for a chip."""

import json
import os
import pathlib
import subprocess
import sys

import pytest

from chipbench.tests.faults import run_small

ROOT = pathlib.Path(__file__).resolve().parents[2]

ONE_CHIP = [
    ("sim.fig3_sc1.ccp_static", None),
    ("sim.fig3_sc1.ccp_static", "sim_state_unchanged"),
    ("sim.fig3_sc1.ccp_static", "sim_half_batch"),
    ("sim.fig3_sc1.ccp_static", "sim_answer_altered"),
    ("cmm.phi4_ffn.lost_shard", None),
    ("cmm.phi4_ffn.lost_shard", "cmm_state_unchanged"),
    ("cmm.phi4_ffn.lost_shard", "cmm_half_batch"),
    ("cmm.phi4_ffn.lost_shard", "cmm_answer_altered"),
]


@pytest.mark.parametrize("workload,fault", ONE_CHIP)
def test_one_chip_cell(workload, fault):
    out = run_small(workload, fault)
    assert out["correct"] is (fault is None), out["checks"]
    assert out["attempted"] > 0


@pytest.mark.parametrize("fault", [None, "cmm_state_unchanged",
                                   "cmm_half_batch", "cmm_no_exchange",
                                   "cmm_answer_altered"])
def test_four_chip_cell(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    p = subprocess.run(
        [sys.executable, "-m", "chipbench.tests.faults",
         "cmm.phi4_ffn.lost_chip4", fault or "none"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is (fault is None), out["checks"]
