"""The registry finds every configuration, traffic mix, driver and metric
reader by the names BENCHMARK.json gives, and refuses unknown names."""
import json

import pytest

from chipbench import registry

BENCH = registry.load_benchmark()


def test_every_cell_resolves():
    for wl in BENCH["workloads"]:
        cfg = registry.config(BENCH, wl["config"])
        assert cfg["name"] == wl["config"]
        tr = registry.traffic(wl["traffic"])
        assert hasattr(registry.driver(tr["kind"]), "Cell")


def test_every_model_configuration_has_its_architecture():
    for c in BENCH["configs"]:
        cfg = registry.config(BENCH, c["name"])
        if "architectures" in cfg:
            arch = registry.arch(cfg)
            assert callable(arch.model_config)
            assert callable(arch.train_flops_per_token)
            assert callable(arch.loss_and_grads)


def test_missing_architecture_file_is_named():
    with pytest.raises(registry.UnknownName,
                       match="chipbench/archs/NoSuchForCausalLM.py"):
        registry.arch({"architectures": ["NoSuchForCausalLM"]})
    with pytest.raises(registry.UnknownName):
        registry.arch({"architectures": ["../model"]})


def test_every_metric_has_a_reader():
    for kind in ("end_to_end", "per_layer"):
        for m in BENCH[kind]:
            assert callable(registry.reader(m["name"]))


@pytest.mark.parametrize("lookup", [
    lambda: registry.workload(BENCH, "no_such_cell"),
    lambda: registry.config(BENCH, "no-such-config"),
    lambda: registry.traffic("no_such_mix"),
    lambda: registry.driver("no_such_kind"),
    lambda: registry.reader("no_such_metric"),
    lambda: registry.traffic("../BENCHMARK"),
    lambda: registry.reader("a/b"),
])
def test_unknown_names_are_refused(lookup):
    with pytest.raises(registry.UnknownName):
        lookup()


def test_per_layer_metrics_move_a_metric_their_cells_report():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        moved = e2e[m["moves"]]
        for wl in m["workloads"]:
            assert wl in cells
            assert wl in moved.get("workloads", cells)


def test_every_cell_reports_setup_another_e2e_and_a_layer_metric():
    for wl in BENCH["workloads"]:
        e2e = [m["name"] for m in registry.metrics_for(
            BENCH, wl["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert registry.metrics_for(BENCH, wl["name"], "per_layer")


def test_config_files_state_their_cuts():
    for c in BENCH["configs"]:
        cfg = registry.config(BENCH, c["name"])
        for key in c["reduced"]:
            assert key in cfg["reduced"], key
        assert cfg["source"]


def test_metrics_for_filters_by_workload():
    bench = {"end_to_end": [{"name": "a"}, {"name": "b", "workloads": ["x"]}]}
    assert [m["name"] for m in registry.metrics_for(
        bench, "y", "end_to_end")] == ["a"]
    assert [m["name"] for m in registry.metrics_for(
        bench, "x", "end_to_end")] == ["a", "b"]


def test_benchmark_json_is_plain_json():
    with open(registry.BENCHMARK) as f:
        assert json.load(f) == BENCH
