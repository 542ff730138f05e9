"""The reader of the LOV's degraded size checks: the tail-unit bytes the
`fs.io` spans counted per byte read, nothing where no span counted them,
and, in a tiny degraded read run, about one unit per file and pass."""
import pytest

from chipbench import harness, progspans, registry
from chipbench.tests import tiny

MS = 1_000_000
T0 = 1000 * MS
NAME = "size_rebuild_per_byte.read"


def _run():
    part = harness.Part(T0 / 1e9, (T0 + 100 * MS) / 1e9,
                        [(1.0, 1.05, {"read_bytes": 2 << 20}),
                         (1.05, 1.09, {"read_bytes": 2 << 20})])
    return harness.Run("w", {}, {}, None, 1.0, {"trace": part})


@pytest.fixture
def recorded(monkeypatch):
    def use(events, dropped=0):
        monkeypatch.setattr(progspans, "_recorded", lambda: (events, dropped))
    return use


def test_counts_the_size_checks_per_byte_read(recorded):
    recorded([("fs.io", T0 + 5 * MS, T0 + 40 * MS, -1,
               {"size_rebuilt_bytes": 1 << 20}),
              ("fs.io", T0 + 50 * MS, T0 + 90 * MS, -1,
               {"size_rebuilt_bytes": 0}),
              # outside the part: not counted
              ("fs.io", T0 - 50 * MS, T0 - 10 * MS, -1,
               {"size_rebuilt_bytes": 1 << 20})])
    assert registry.reader(NAME)(_run()) == pytest.approx(0.25)


@pytest.mark.parametrize("events", [
    [("fs.io", T0 + 5 * MS, T0 + 40 * MS, -1, {"bytes": 2 << 20})],
    [("osc.io", T0 + 5 * MS, T0 + 40 * MS, -1, {"copied": 1})],
    []])
def test_nothing_where_no_span_counted_it(recorded, events):
    recorded(events)
    assert registry.reader(NAME)(_run()) is None


def test_a_tiny_degraded_read_rebuilds_about_a_unit_per_file_and_pass():
    from repro.core import metrics
    metrics.HOST_SPANS.clear()
    r = tiny.run("raid5_ior_easy_degraded_read", trace=True)
    assert r["correct"], r["checks"]
    got = r["metrics"][NAME]["value"]
    unit = tiny.RAID5["config"]["layout"]["stripe_size"]
    per_file = unit / tiny.RAID5["config"]["file_bytes_per_rank"]
    every_read = unit / tiny.RAID5["traffic"]["transfer_bytes"]
    # a fresh mount's first check per file rebuilds, the rest reuse it;
    # the first pass meets the writers' PW locks until its reads revoke
    # them (4 units a file here); a rebuild on every read gives 0.5
    assert per_file <= got <= 4 * per_file < every_read
