"""`qwen3_4b_train_fsdp4` driven end to end at a tiny size on four virtual
CPU devices, the chip gate skipped: the trainer's mesh spans the cell's
four chips, the checked steps agree with the reference computed in blocks
of one chip's batch, and a run with the step or its feed broken
underneath, or with the program's own bfloat16 weights (the control), is
not correct.

The runs share one child process, since the device count is fixed when
JAX starts: `python3 -m chipbench.tests.test_cell_train_fsdp4` prints one
JSON line a run."""
import json
import os
import subprocess
import sys

import pytest

from chipbench import registry

WORKLOAD = "qwen3_4b_train_fsdp4"
PLANTS = ["step_unchanged", "half_batch_left_out", "exchange_left_out",
          "token_altered"]
CONTROL = {"config": {"training": {"param_dtype": "bfloat16"}}}


def main():
    from chipbench import faults
    from chipbench.tests import tiny
    runs = [("sound", {})] + [(p, {"plant": faults.named(p)})
                              for p in PLANTS] + \
        [("bf16_weights", {"overrides": CONTROL})]
    for name, kw in runs:
        r = tiny.run(WORKLOAD, **kw)
        print(json.dumps({"run": name, "correct": r["correct"],
                          "failed": r["failed"], "notes": r["notes"],
                          "compiles": r["compiles"], "checks": r["checks"]}),
              flush=True)


@pytest.fixture(scope="module")
def runs():
    root = registry.ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(root / "src"), str(root)]),
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") +
                          " --xla_force_host_platform_device_count=4"))
    p = subprocess.run([sys.executable, "-m", __name__], cwd=root, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return {r["run"]: r for r in map(json.loads, p.stdout.splitlines())}


def test_sound_run_is_correct_on_four_devices(runs):
    r = runs["sound"]
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["compiles"]["in_window"] == 0
    # a (data = 4, model = 1) mesh, 4 sequences a chip
    assert r["notes"]["mesh"] == {"data": 4, "model": 1}
    assert r["notes"]["sequences_per_step"] == 16
    # the mix gives the loss gap no limit: read, not compared
    assert "loss_gap" not in r["checks"]
    assert r["notes"]["not_compared"]["loss_gap"] < 1e-3


@pytest.mark.parametrize("plant", PLANTS)
def test_broken_step_is_not_correct_on_four_devices(runs, plant):
    r = runs[plant]
    assert not r["correct"]
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


def test_bf16_weights_control_is_not_correct_on_four_devices(runs):
    ctl, sound = runs["bf16_weights"]["checks"], runs["sound"]["checks"]
    assert not runs["bf16_weights"]["correct"]
    assert ctl["update_norm_gap"]["value"] > \
        3 * sound["update_norm_gap"]["value"]


if __name__ == "__main__":
    main()
