"""`mellum2_moe_train` driven end to end at a tiny size on the CPU, the chip
gate skipped: the program's checked steps agree with the plain reference,
and a run with the step broken underneath (an expert held but left out,
the step unchanged, half of the batch left out, the program on its own
bfloat16 weights) is not correct. At these widths (heads of 16) bfloat16
compute alone moves the gradient's leaves by more than the chip's limits
allow, so the tiny runs compute in float32, where the program agrees with
the reference to round-off, and the bfloat16 path is run beside them. A
traced run reports the per-layer metrics the CPU can give."""
import dataclasses

import pytest

from chipbench import faults, harness

WORKLOAD = "mellum2_moe_train"

TINY = {"config": {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "moe_intermediate_size": 32, "num_experts": 4,
    "num_experts_published": 16, "num_experts_per_tok": 4,
    "expert_offset": 4, "vocab_size": 512, "sliding_window": 8,
    "training": {"seq_length": 32, "global_batch": 2,
                 "compute_dtype": "float32"}},
    "traffic": {"corpus_seqs": 256}}


def run(seconds=0.3, trace=False, plant=None, overrides=None):
    over = harness.merge(TINY, overrides)
    return harness.run_cell(WORKLOAD, 2**31 + 13, seconds, trace,
                            gate=False, overrides=over, plant=plant)


def gaps(r):
    """Every gap the run read, compared or not."""
    return {**r["notes"]["not_compared"],
            **{k: c["value"] for k, c in r["checks"].items()}}


def _one_expert_left_out(orig):
    # the last held expert's pairs are treated as held elsewhere
    def held_expert_ffn(cfg, p, x, rc):
        n = cfg.experts_held - 1
        cut = {k: (v[:n] if k in ("wg", "wu", "wd") else v)
               for k, v in p.items()}
        return orig(dataclasses.replace(cfg, experts_held=n), cut, x, rc)
    return held_expert_ffn


ONE_EXPERT_LEFT_OUT = {
    "repro.models.moe:held_expert_ffn": _one_expert_left_out}


@pytest.fixture(scope="module")
def sound():
    return run()


def test_sound_run_is_correct(sound):
    r = sound
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["compiles"]["in_window"] == 0
    g = gaps(r)
    assert g["batch_rows_wrong"] == 0
    assert g["loss_gap"] < 1e-6
    assert g["grad_norm_gap"] < 1e-4


def test_bf16_compute_runs_near_the_reference():
    r = run(overrides={"config": {"training": {
        "compute_dtype": "bfloat16"}}})
    assert r["failed"] == 0
    g = gaps(r)
    assert g["loss_gap"] < 1e-3
    assert g["grad_norm_gap"] < 3e-2


@pytest.mark.parametrize("plant", [
    ONE_EXPERT_LEFT_OUT, faults.named("step_unchanged"),
    faults.named("half_batch_left_out")],
    ids=["one_expert_left_out", "step_unchanged", "half_batch_left_out"])
def test_broken_step_is_not_correct(plant):
    r = run(plant=plant)
    assert not r["correct"]
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


def test_bf16_weights_control_is_not_correct(sound):
    ctl = run(overrides={"config": {"training": {
        "param_dtype": "bfloat16"}}})
    assert not ctl["correct"]
    assert ctl["checks"]["update_norm_gap"]["value"] > \
        3 * sound["checks"]["update_norm_gap"]["value"]


def test_traced_run_reports_per_layer_metrics():
    r = run(trace=True)
    assert r["correct"], r["checks"]
    assert "batch_wait_ms_per_step" in r["metrics"]
    # device shares and kernel times only from a TPU's trace
    assert not any(k in r["metrics"] for k in (
        "mfu", "device_idle.train", "moe_gmm_ms_per_step", "gmm_roofline"))
