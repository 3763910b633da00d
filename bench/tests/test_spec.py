"""BENCHMARK.json keeps the benchmark's contract, and every name in it
finds its file: configurations, traffic mixes, metric readers."""
import json
import os
import re
import shutil

import pytest

from bench import spec

ROOT = spec.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_benchmark_json_shape(bench):
    assert set(bench) == KEYS
    assert 1 <= bench["run_seconds"] <= 51
    assert all(_line(w) for w in bench["command"])
    for p in bench["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
    names = [c["name"] for c in bench["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        assert len(c["reduced"]) <= 16
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and _line(m["layer"])
        assert set(m.get("workloads", cells)) <= cells
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    assert len(json.dumps(bench)) <= 64 * 1024


@pytest.mark.parametrize("workload", ["autompg-dnn.prove",
                                      "autompg-dnn.prove_t2"])
def test_every_cell_finds_its_files(workload):
    cell = spec.load_cell(workload)
    assert cell.chips == 1 and cell.config["family"] == "fcnn"
    assert {m.name for m in cell.end_to_end} == {"prove_s_per_step",
                                                 "setup_s"}
    assert {m.name for m in cell.per_layer} == {
        "witness_s_per_step", "keygen_s"}
    assert all(callable(m.read) for m in cell.end_to_end + cell.per_layer)


def test_new_files_are_found_by_name(tmp_path):
    """A configuration, a mix and a metric added as files, with entries
    in BENCHMARK.json, need no edit of the harness."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfg = json.loads((tmp_path / "bench/configs/autompg-dnn.json")
                     .read_text())
    cfg["name"] = "other-net"
    cfg["proof_layout_by_steps_per_proof"]["4"] = {"steps": 4}
    (tmp_path / "bench/configs/other-net.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/burst.json").write_text(
        json.dumps({"steps_per_proof": 4, "x_range": [-1, 1],
                    "y_range": [-1, 1]}))
    (tmp_path / "bench/metrics/windows_done.py").write_text(
        "def read(run):\n    return run.windows or None\n")
    bench["configs"].append({"name": "other-net", "source": "x",
                             "file": "bench/configs/other-net.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "other-net.burst",
                               "config": "other-net", "traffic": "burst",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "windows_done", "unit": "n",
                               "better": "higher", "source": "host_clock",
                               "layer": "x", "moves": "prove_s_per_step",
                               "workloads": ["other-net.burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("other-net.burst", root=str(tmp_path))
    assert cell.config["name"] == "other-net"
    assert cell.traffic["steps_per_proof"] == 4
    assert [m.name for m in cell.per_layer] == ["windows_done"]
    assert cell.per_layer[0].read(type("R", (), {"windows": 3})) == 3
    with pytest.raises(spec.SpecError):
        spec.load_cell("missing.cell", root=str(tmp_path))


@pytest.mark.parametrize("mix", [
    {"steps_per_proof": 1, "streams": 4},
    {"steps_per_proof": 1, "loop": "open"},
    {"steps_per_proof": 3}])
def test_mix_the_generator_cannot_run_is_refused(tmp_path, mix):
    """A key the one generator does not read, or a proof window the
    configuration states no proof layout for, is refused before a run."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (tmp_path / "bench/traffic/odd.json").write_text(json.dumps(
        dict(mix, x_range=[-1, 1], y_range=[-1, 1])))
    bench["workloads"].append({"name": "autompg-dnn.odd",
                               "config": "autompg-dnn", "traffic": "odd",
                               "chips": 1, "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(spec.SpecError):
        spec.load_cell("autompg-dnn.odd", root=str(tmp_path))


@pytest.mark.parametrize("traffic", ["prove", "prove_t2"])
def test_stated_merged_length(traffic):
    """The configuration states the merged opening's length that the
    protocol's layout gives it; a change to the layout shows here."""
    from repro.core.pipeline import PipelineConfig
    from repro.core.pipeline.graph import proof_graph_for_family

    cell = spec.load_cell(f"autompg-dnn.{traffic}")
    c, t = cell.config, cell.traffic["steps_per_proof"]
    graph = proof_graph_for_family(c["family"], widths=tuple(c["widths"]),
                                   batch=c["batch"])
    cfg = PipelineConfig.from_graph(graph, q_bits=c["q_bits"],
                                    r_bits=c["r_bits"], n_steps=t)
    assert cfg.merged_len == c["merged_len_by_steps_per_proof"][str(t)]
