"""A new configuration, traffic mix and metric are found by name, from
files of their own, without an edit to any file that is already there."""
import json
import textwrap

from chipbench import spec


def test_new_cell_parts_are_found_by_name(tmp_path):
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics", "checks"):
        (bench / sub).mkdir(parents=True)
    (bench / "configs" / "dummy-cfg.json").write_text(json.dumps(
        {"name": "dummy-cfg", "settings": {"dim": 4}, "check": "dummy"}))
    (bench / "traffic" / "trickle.json").write_text(json.dumps(
        {"kind": "ingest", "prefill": 0}))
    (bench / "metrics" / "dummy_rate.x.py").write_text(textwrap.dedent("""
        def read(ctx):
            return 2.0 * ctx
    """))
    (bench / "checks" / "dummy.py").write_text("LIMIT = 3\n")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": "dummy-cfg",
                     "file": "bench/configs/dummy-cfg.json"}],
        "workloads": [{"name": "dummy.cell", "config": "dummy-cfg",
                       "traffic": "trickle", "chips": 1}],
        "end_to_end": [{"name": "dummy_rate.x", "unit": "x",
                        "workloads": ["dummy.cell"]},
                       {"name": "elsewhere", "unit": "x",
                        "workloads": ["other.cell"]}],
        "per_layer": [{"name": "everywhere", "unit": "x"}]}))
    cell = spec.load_cell("dummy.cell", tmp_path, bench)
    assert cell.config["settings"] == {"dim": 4}
    assert cell.traffic["kind"] == "ingest"
    assert [m["name"] for m in cell.end_to_end] == ["dummy_rate.x"]
    assert [m["name"] for m in cell.per_layer] == ["everywhere"]
    assert cell.reader("dummy_rate.x").read(21.0) == 42.0
    assert cell.check_module().LIMIT == 3


def test_every_metric_of_the_benchmark_has_a_reader():
    root = spec.HERE.parent
    bench = spec.load_json(root / "BENCHMARK.json")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.load_module(
            spec.HERE / "metrics" / f"{m['name']}.py").read)
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], root)
        assert cell.check_module().compare
