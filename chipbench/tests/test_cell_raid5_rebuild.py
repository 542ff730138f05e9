"""`raid5_rebuild_under_load` driven end to end at a tiny size on the CPU,
the chip gate skipped: sound passes verify, each rebuilding every file
once while the ranks write, and a rebuilt object left stale (its writer
kept the layout from before the swap) or rebuilt wrong fails `correct`."""
import pytest

from chipbench import faults, harness

WORKLOAD = "raid5_rebuild_under_load"
# 32 transfers a file, as the chip's 64 MiB in 2 MiB, so that a pass
# rebuilds every file once; a transfer is half a round, as there
TINY = {"config": {"file_bytes_per_rank": 1 << 19,
                   "layout": {"stripe_size": 1 << 13}},
        "traffic": {"transfer_bytes": 1 << 14}}


def run(seconds=0.3, trace=False, plant=None):
    return harness.run_cell(WORKLOAD, 2**31 + 17, seconds, trace,
                            gate=False, overrides=TINY, plant=plant)


def _layout_kept(orig):
    # the handle keeps the layout it had before the swap
    def revalidate_layout(self, fh):
        fh.layout_stale = False
    return revalidate_layout


def test_sound_passes_are_correct():
    r = run(seconds=1.0)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert all(c["value"] == 0 for c in r["checks"].values())
    # at least two whole passes: the second rebuilds onto the OST the
    # first left dead, brought back
    per_pass = r["notes"]["rebuilt_per_pass"]
    assert len(per_pass) >= 2 and set(per_pass) == {8}
    assert "write_MiBps" in r["metrics"]


@pytest.mark.parametrize("plant", [
    {"repro.fsio.client:LustreClient.revalidate_layout": _layout_kept},
    faults.named("raid5_no_reconstruct")],
    ids=["stale_rebuilt_unit", "no_reconstruct"])
def test_stale_or_wrong_rebuilt_object_is_not_correct(plant):
    r = run(plant=plant)
    assert not r["correct"]
    assert r["checks"]["rebuilt_bytes_wrong"]["value"] > 0


def test_traced_run_reports_per_layer_metrics():
    r = run(trace=True)
    assert r["correct"], r["checks"]
    assert any(k.endswith(".write") for k in r["metrics"])
