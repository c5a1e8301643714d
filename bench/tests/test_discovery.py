"""A configuration, traffic mix, limit file or per-layer metric dropped
into the benchmark's directories is found by its name alone."""
import json
import os

from bench import run as bench_run


def test_new_files_are_found_by_name(tmp_path):
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "metrics", "limits"):
        (bench / d).mkdir(parents=True)
    (bench / "configs" / "toy-7b.json").write_text(json.dumps(
        {"name": "toy-7b", "hidden_size": 8}))
    (bench / "configs" / "toy-7b.py").write_text(
        "def make_task(c):\n    return ('toy', c['hidden_size'])\n")
    (bench / "traffic" / "toy-mix.json").write_text(json.dumps(
        {"kind": "train", "seq_len": 16}))
    (bench / "limits" / "toy-7b.toy-mix.json").write_text(json.dumps(
        {"loss_gap": 0.5}))
    (bench / "metrics" / "toy.share.py").write_text(
        "def read(ctx):\n    return ctx.get('x')\n")
    spec = {"configs": [{"name": "toy-7b",
                         "file": "bench/configs/toy-7b.json"}],
            "workloads": [{"name": "toy-7b.toy-mix", "config": "toy-7b",
                           "traffic": "toy-mix", "chips": 1}],
            "end_to_end": [{"name": "setup_s", "unit": "s"}],
            "per_layer": [{"name": "toy.share", "unit": "%",
                           "workloads": ["toy-7b.toy-mix"]}]}
    cell = bench_run.Cell(spec, "toy-7b.toy-mix", bench_dir=str(bench))
    assert cell.config_module.make_task(cell.config) == ("toy", 8)
    assert cell.traffic["seq_len"] == 16
    assert cell.limits == {"loss_gap": 0.5}
    assert cell.driver.__name__ == "bench.drive_train"
    assert [m["name"] for m in cell.per_layer] == ["toy.share"]
    reader = cell.metric_reader("toy.share")
    assert reader.read({"x": 42.0}) == 42.0
    assert reader.read({}) is None


def test_every_declared_piece_exists():
    spec = bench_run.read_json(os.path.join(bench_run.ROOT, "BENCHMARK.json"))
    for w in spec["workloads"]:
        cell = bench_run.Cell(spec, w["name"])
        for m in cell.per_layer:
            assert hasattr(cell.metric_reader(m["name"]), "read")
        assert cell.end_to_end, w["name"]
