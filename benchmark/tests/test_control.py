"""The control of the correctness check, at a size a test run can hold:
the plain reference computed in fp8 (the precision step below the
configurations' bf16), put in the program's place, comes out as NOT
correct under the limits that sound runs of the program pass.  On the chip
the same readings are taken at the cells' own sizes by calibrate.py
(PERF.md has them); this keeps the mechanism under test on the CPU."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import calibrate  # noqa: E402
from benchmark import harness  # noqa: E402

SEEDS = [2200000000 + 7919 * i for i in range(3)]


def context(workload):
    parts = harness.load_cell(harness.load_spec(), workload, rehearse=True)
    jax, devices = harness.start_jax(parts["cell"]["chips"], rehearse=True)
    return {"jax": jax, "devices": devices, "config": parts["config"],
            "mix": parts["mix"], "seconds": 2.0, "trace": False,
            "workload": workload, "control_precisions": ["fp8"]}


def test_fp8_reference_fails_the_training_check():
    ctx = context("train_350m_seq2048")
    limits = ctx["config"]["check"]["limits"]
    pairs = (("loss_gap", "loss_rel_gap"), ("grad_gap", "grad_norm_gap"),
             ("delta_gap", "delta_norm_gap"))
    for row in calibrate.calibrate_train(ctx, SEEDS, len(SEEDS)):
        for reading, limit in pairs:
            assert row["program"][reading] <= limits[limit], row
        failed = [r for r, l in pairs if row["control_fp8"][r] > limits[l]]
        assert "grad_gap" in failed, row


def test_fp8_reference_fails_the_serving_check():
    ctx = context("serve_1.3b_closed")
    limit = ctx["config"]["check"]["limits"]["logit_deficit"]
    for row in calibrate.calibrate_serve(ctx, SEEDS, len(SEEDS)):
        assert row["failed"] == 0 and row["tokens"] > 50, row
        assert row["program_deficit"] <= limit < row["control_fp8"], row
