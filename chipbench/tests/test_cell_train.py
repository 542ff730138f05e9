"""`qwen3_4b_train` driven end to end at a tiny size on the CPU, the chip
gate skipped: the program"s checked steps agree with the plain reference,
a run with the step or its feed broken underneath is not correct, and the
control, the program on its own bfloat16 weights, departs from the float32
reference."""
import pytest

from chipbench import faults
from chipbench.tests import tiny

WORKLOAD = "qwen3_4b_train"


def test_sound_run_is_correct():
    r = tiny.run(WORKLOAD)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["compiles"]["in_window"] == 0
    c = r["checks"]
    assert c["batch_rows_wrong"]["value"] == 0
    # at this size the bf16 step and the f32 reference agree closely
    assert c["loss_gap"]["value"] < 1e-3
    assert c["grad_norm_gap"]["value"] < 1e-2
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("plant", ["step_unchanged", "half_batch_left_out",
                                   "token_altered"])
def test_broken_step_is_not_correct(plant):
    r = tiny.run(WORKLOAD, plant=faults.named(plant))
    assert not r["correct"]
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


def test_step_unchanged_reads_one():
    r = tiny.run(WORKLOAD, plant=faults.named("step_unchanged"))
    # the weights are made again from the seed for the comparison, which
    # reproduces them to float round-off, not bit for bit
    assert r["checks"]["update_norm_gap"]["value"] == pytest.approx(
        1.0, rel=1e-3)


def test_bf16_weights_control_is_not_correct():
    sound = tiny.run(WORKLOAD)["checks"]
    ctl = tiny.run(WORKLOAD, overrides={"config": {"training": {
        "param_dtype": "bfloat16"}}})
    assert not ctl["correct"]
    # the updates are lost to bfloat16 rounding of the weights
    assert ctl["checks"]["update_norm_gap"]["value"] > \
        3 * sound["update_norm_gap"]["value"]


def test_traced_run_reports_per_layer_metrics():
    r = tiny.run(WORKLOAD, trace=True)
    assert r["correct"], r["checks"]
    assert "data_ms_per_step" in r["metrics"]
    assert not any(k in r["metrics"] for k in ("mfu", "device_idle.train"))
    assert r["breakdown"]["idle_gaps"]
