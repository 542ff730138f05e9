"""`raid5_ior_easy_write` driven end to end at a tiny size on the CPU, the
chip gate skipped: a sound run is correct, and a run with the timed path
broken underneath is not, for each fault the cell can have and for its
control. A traced run reports the host layers' metrics and the idle
breakdown."""
import pytest

from chipbench import faults
from chipbench.tests import tiny

WORKLOAD = "raid5_ior_easy_write"


def test_sound_run_is_correct():
    r = tiny.run(WORKLOAD)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert r["compiles"]["in_window"] == 0
    assert all(c["value"] == 0 for c in r["checks"].values())
    assert "setup_s" in r["metrics"]
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("plant", [
    "write_unchanged",
    "half_writes_left_out",
    "parity_altered",
    "raid5_parity_of_touched_units"])
def test_broken_path_is_not_correct(plant):
    r = tiny.run(WORKLOAD, plant=faults.named(plant))
    assert not r["correct"]
    assert any(c["value"] > c["limit"] for c in r["checks"].values()) \
        or r["failed"]


def test_traced_run_reports_per_layer_metrics():
    r = tiny.run(WORKLOAD, trace=True)
    assert r["correct"], r["checks"]
    # host layers are read on any backend; device shares only on a TPU
    assert any(k.startswith("client_ms_per_MiB") for k in r["metrics"])
    assert not any(k.startswith(("device_idle", "parity_roofline"))
                   for k in r["metrics"])
    assert r["device"]["window_s"] > 0
    assert r["breakdown"]["idle_gaps"]


def test_no_pass_puts_back_what_an_earlier_one_wrote():
    # a write left out must show, however many passes the window runs
    from chipbench import harness, registry
    from chipbench.drivers import ior
    bench = registry.load_benchmark()
    wl = registry.workload(bench, WORKLOAD)
    cell = ior.Cell(harness.merge(registry.config(bench, wl["config"]),
                                  tiny.RAID5["config"]),
                    harness.merge(registry.traffic(wl["traffic"]),
                                  tiny.RAID5["traffic"]), 1)
    for r in range(cell.ranks):
        for s in range(cell.nslots):
            picks = [cell._pick(r, s, p) for p in range(64)]
            assert picks[0] not in picks[1:]
            assert all(a != b for a, b in zip(picks[1:], picks[2:]))
