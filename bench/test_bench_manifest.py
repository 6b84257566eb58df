"""BENCHMARK.json against the benchmark's rules, and every file a cell
needs found by its name: configuration, traffic, loop and metric readers.
A metric or a cell added as new files plus new entries resolves without
any edit to an existing file."""
import json
import re
import shutil

import pytest

from bench import common

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank)$|width|hidden|intermediate|latent|state|"
                   r"proj|head|expan|per_tok")
MAN = common.manifest()
E2E = {m["name"]: m for m in MAN["end_to_end"]}


def reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_top_level_keys_and_command():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "bench/run.py"]
    assert MAN["paths"] == ["bench"]
    assert 1 <= MAN["run_seconds"] <= 51
    assert len(json.dumps(MAN)) <= 64 * 1024


def test_run_seconds_fits_a_full_check_of_24_cells():
    runs = 2 + 14 * 24
    assert runs * (MAN["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_text_fields():
    items = MAN["configs"] + MAN["workloads"] + MAN["end_to_end"] + \
        MAN["per_layer"]
    texts = [it[k] for it in MAN["workloads"] + MAN["configs"] +
             MAN["per_layer"] for k in ("why", "layer") if k in it]
    texts += [c["source"] for c in MAN["configs"]] + MAN["command"]
    for text in texts:
        assert 1 <= len(text) <= 200 and "\n" not in text and \
            "\t" not in text, text
    for it in items:
        assert NAME.match(it["name"]), it["name"]
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for cfg in MAN["configs"]:
        for k in cfg["reduced"]:
            assert NAME.match(k) and not WIDTH.search(k), k
    names = [it["name"] for it in items]
    assert len(names) == len(set(names))


def test_entries_have_only_their_keys():
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in MAN["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
    for m in MAN["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_bounds():
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25, m
    assert E2E["setup_s"]["bound"] <= 0.25


def test_every_cell_reports_setup_another_e2e_and_a_layer():
    for w in MAN["workloads"]:
        e2e = [m["name"] for m in MAN["end_to_end"] if reports(m, w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert any(reports(m, w["name"]) for m in MAN["per_layer"])


def test_each_layer_metric_moves_an_e2e_metric_its_cells_report():
    cells = {w["name"] for w in MAN["workloads"]}
    for m in MAN["per_layer"]:
        assert m["moves"] in E2E, m
        for cell in m.get("workloads", cells):
            assert cell in cells, (m["name"], cell)
            assert reports(E2E[m["moves"]], cell), (m["name"], cell)
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_layers_are_named_alike():
    by_layer = {}
    for m in MAN["per_layer"]:
        by_layer.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_layer.values())


def test_configs_used_and_files_under_paths():
    used = {w["config"] for w in MAN["workloads"]}
    assert used == {c["name"] for c in MAN["configs"]}
    files = [c["file"] for c in MAN["configs"]]
    assert len(set(files)) == len(files)
    for c in MAN["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        body = common.load_json(common.ROOT / c["file"])
        assert body["name"] == c["name"]
        assert body["reduced"] == c["reduced"]


@pytest.mark.parametrize("cell", [w["name"] for w in MAN["workloads"]])
def test_cell_files_found_by_name(cell):
    from bench import run
    _, w, cfg, traffic, loop, e2e, per_layer = run.resolve(cell)
    assert callable(loop.run)
    assert {m["name"] for m in e2e} >= {"setup_s"}
    for m in per_layer:
        assert callable(run.load_reader(m["name"]))
    from repro.configs.ddpm_unet import UNetConfig
    from bench import program
    assert isinstance(program.program_unet_config(cfg), UNetConfig)


def test_a_new_metric_and_cell_are_found_from_new_files_only(tmp_path,
                                                             monkeypatch):
    from bench import run
    bench = tmp_path / "bench"
    shutil.copytree(common.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    man = json.loads(common.MANIFEST.read_text())
    (bench / "metrics" / "serve.waves.new.py").write_text(
        "def read(rec):\n    return float(rec['counters']['waves'])\n")
    traffic = common.load_json(common.traffic_file("unique"))
    traffic["backlog_waves"] = 3
    (bench / "traffic" / "unique_deep.json").write_text(json.dumps(traffic))
    man["workloads"].append({"name": "unet32.serve.deep",
                             "config": "collafuse-unet32",
                             "traffic": "unique_deep", "chips": 1,
                             "why": "a deeper backlog"})
    man["per_layer"].append({"name": "serve.waves.new", "unit": "waves",
                             "better": "higher", "source": "program_counter",
                             "layer": "serve scheduler",
                             "moves": "setup_s",
                             "workloads": ["unet32.serve.deep"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))
    monkeypatch.setattr(common, "BENCH_DIR", bench)
    monkeypatch.setattr(common, "MANIFEST", tmp_path / "BENCHMARK.json")
    _, _, _, tr, loop, _, per_layer = run.resolve("unet32.serve.deep")
    assert tr["backlog_waves"] == 3 and loop.__name__ == "bench.loops.serve"
    assert [m["name"] for m in per_layer] == ["setup.trace_lower_s",
                                              "serve.waves.new"]
    assert run.load_reader("serve.waves.new")({"counters": {"waves": 7}}) \
        == 7.0
