"""A cell, a configuration, a traffic mix and a metric are found by name:
adding them takes files and entries, no edit of the harness's code."""

import json

from conftest import TINY, add_cell, run_cell

from bench import cells


def test_committed_cells_resolve():
    bench = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = cells.load(w["name"])
        assert len(cell.fields) == cell.traffic["fields"]
        assert [m["name"] for m in cell.end_to_end] == [
            m["name"] for m in bench["end_to_end"]]
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]))


def test_new_files_make_a_new_cell(tiny_root, capsys):
    root = tiny_root
    (root / "bench/traffic/dump2.eb1e-3.json").write_text(json.dumps(
        {"policy": {"mode": "fixed_accuracy", "eb_rel": 1e-3, "r_sp": 0.05}, "fields": 2}))
    add_cell(root, "small3d", (16, 24, 32), 2, "dump2.eb1e-3")
    (root / "bench/metrics/decompress.spans.py").write_text(
        "from bench import tracing\n\n\n"
        "def read(trace, records):\n"
        "    return float(len(tracing.spans(trace, 'decompress')))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({"name": "decompress.spans", "unit": "count", "better": "higher",
                               "source": "program_span", "layer": "host decoders",
                               "moves": "decompress_GBps", "workloads": ["small3d.eb1e-3"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = cells.load("small3d.eb1e-3", root)
    assert cell.shape == (16, 24, 32) and [f.name for f in cell.fields] == ["ATM_00", "ATM_03"]
    assert "decompress.spans" not in [m["name"] for m in cells.load(TINY, root).per_layer]

    rc, res = run_cell(root, capsys, "--workload", "small3d.eb1e-3", "--seed", "5",
                       "--seconds", "0.5", "--trace", "1")
    assert rc == 0 and res["correct"], res
    assert res["metrics"]["decompress.spans"]["value"] == res["attempted"]
