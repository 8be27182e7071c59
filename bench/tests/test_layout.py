"""BENCHMARK.json against the files the harness finds by name.

Every configuration (with its reference model and tiny stand-in), traffic
mix, cell and per-layer metric is a file of its own; this test lists the
directories and holds them to BENCHMARK.json and to the benchmark's rules,
so that adding a cell or an architecture is adding files.
"""
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

from bench import flops, generator, harness, reference
from bench.tests import tiny

ROOT = harness.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTHS = re.compile(r"^(?!vocab_size$).*(_dim|_rank|_size)$|"
                    r"^(d_inner|d_model|d_ff|expand|num_experts_per_tok)$|"
                    r"proj|latent")


def _stems(sub, suffix):
    return sorted(p.name[: -len(suffix)]
                  for p in (ROOT / "bench" / sub).glob(f"*{suffix}"))


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]


def test_configs_are_files_found_by_name():
    names = [c["name"] for c in BENCH["configs"]]
    assert sorted(names) == _stems("configs", ".json")
    for c in BENCH["configs"]:
        assert c["file"] == f"bench/configs/{c['name']}.json"
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert sorted(c["reduced"]) == sorted(conf["reduced"])
        assert not any(WIDTHS.search(k) for k in c["reduced"]), c["reduced"]
        assert set(conf["fields"]) <= set(conf["sizes"])


def test_cells_are_files_found_by_name():
    assert sorted(w["name"] for w in BENCH["workloads"]) == \
        _stems("workloads", ".json")
    for w in BENCH["workloads"]:
        f = json.loads((ROOT / "bench" / "workloads"
                        / f"{w['name']}.json").read_text())
        for k in ("config", "traffic", "chips", "why"):
            assert f[k] == w[k], (w["name"], k)
        assert f["limits"], w["name"]
    assert {w["config"] for w in BENCH["workloads"]} == \
        {c["name"] for c in BENCH["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_traffic_mixes_are_data_for_the_one_generator():
    """Each mix is a data file naming a pattern module, found by name, with
    its class and the faults its cells can have."""
    used = {w["traffic"] for w in BENCH["workloads"]}
    assert used == set(_stems("traffic", ".json"))
    patterns = set()
    for mix in used:
        t = json.loads((ROOT / "bench" / "traffic" / f"{mix}.json")
                       .read_text())
        mod = generator.load_pattern(t["pattern"])
        assert callable(mod.Pattern) and mod.FAULTS, t["pattern"]
        assert generator.CONTROL not in mod.FAULTS
        patterns.add(t["pattern"])
    assert patterns == set(_stems("patterns", ".py"))


def test_per_layer_metrics_are_readers_found_by_name():
    assert sorted(m["name"] for m in BENCH["per_layer"]) == \
        _stems("metrics", ".py")
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        mod = harness.load_metric(m["name"])
        assert callable(mod.read)
        moved = e2e[m["moves"]]
        for cell in m["workloads"]:
            assert cell in moved.get("workloads", [cell]), (m["name"], cell)


def test_every_cell_reports_what_it_must():
    for w in BENCH["workloads"]:
        cell = harness.load_cell(w["name"])
        names = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in names and len(names) >= 2, w["name"]
        assert cell.per_layer, w["name"]


def test_names_units_bounds():
    items = (BENCH["configs"] + BENCH["workloads"] + BENCH["end_to_end"]
             + BENCH["per_layer"])
    for x in items:
        assert NAME.match(x["name"]), x["name"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_run_seconds_fit_a_full_check_of_24_cells():
    rs = BENCH["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_adding_a_cell_is_adding_files(tmp_path, monkeypatch):
    """A copy of the benchmark with one more cell, made of a workload file
    and a BENCHMARK.json entry only, loads through the same harness."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    new = dict(bench["workloads"][0], name="train.extra-cell")
    bench["workloads"].append(new)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    wl = json.loads((ROOT / "bench" / "workloads" /
                     f"{bench['workloads'][0]['name']}.json").read_text())
    (tmp_path / "bench" / "workloads" / "train.extra-cell.json").write_text(
        json.dumps(wl))
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    monkeypatch.setattr(harness, "BENCH", tmp_path / "bench")
    cell = harness.load_cell("train.extra-cell")
    assert generator.load_pattern(cell.traffic["pattern"]).Pattern
    assert cell.config["name"] == new["config"]


def test_adding_a_mix_is_adding_files(tmp_path, monkeypatch):
    """A further mix for an existing pattern (here the short fan-out that
    PERF.md lists: 256-token chains only, 64 in flight) and its cell are a
    traffic file, a workload file and BENCHMARK.json entries only."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    mix = dict(json.loads((ROOT / "bench" / "traffic" / "fanout.json")
                          .read_text()), in_flight=64, cycle=[256])
    (tmp_path / "bench" / "traffic" / "fanout-short.json").write_text(
        json.dumps(mix))
    old = next(w for w in bench["workloads"] if w["traffic"] == "fanout")
    new = dict(old, name="fanout-short.smollm-360m", traffic="fanout-short")
    bench["workloads"].append(new)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    wl = json.loads((ROOT / "bench" / "workloads" / f"{old['name']}.json")
                    .read_text())
    (tmp_path / "bench" / "workloads" / f"{new['name']}.json").write_text(
        json.dumps(dict(wl, traffic="fanout-short")))
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    monkeypatch.setattr(harness, "BENCH", tmp_path / "bench")
    cell = harness.load_cell(new["name"])
    assert cell.traffic["in_flight"] == 64
    assert generator.load_pattern(cell.traffic["pattern"]).Pattern


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_every_configuration_resolves(name):
    """Its reference model and FLOPs count are found by the name its file
    gives, and it has a tiny stand-in for the CPU tests."""
    conf = json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())
    model = reference.model(conf["reference"])
    assert callable(model.token_nll) and callable(model.flops_per_token)
    assert flops.per_token(conf, 1024, training=False) > 0
    assert (ROOT / "bench" / "tests" / "tiny_data" / "configs"
            / f"{name}.json").is_file()
    assert tiny.config(name)["name"] == name


@pytest.mark.parametrize("name", ["no_such_model", "numerics", "train",
                                  "adamw", "corpus", "__init__"])
def test_unknown_reference_is_refused(name):
    """A name that is no reference model, a shared helper among them, is
    refused with the known models listed."""
    with pytest.raises(SystemExit, match="transformer"):
        reference.model(name)


TINY_ARCH = '''"""A new architecture's float32 reference (here: the dense decoder's)."""
from .transformer import flops_per_token, token_nll  # noqa: F401
'''


def test_adding_a_configuration_is_adding_files(tmp_path, monkeypatch):
    """A configuration of a new architecture is its reference module, its
    configuration file, its tiny stand-in, a workload file (and its tiny
    limits) and BENCHMARK.json entries, with no edit of a file that was
    there; its tiny cell runs correct through the harness."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    old = next(w for w in bench["workloads"] if w["traffic"] == "fanout"
               and w["config"] == "smollm-360m")
    conf = json.loads((ROOT / "bench" / "configs" / "smollm-360m.json")
                      .read_text())
    new = {
        "reference/tiny_arch.py": TINY_ARCH,
        "configs/tiny-arch.json": json.dumps(dict(
            conf, name="tiny-arch", reference="tiny_arch")),
        "workloads/fanout.tiny-arch.json": json.dumps(dict(
            json.loads((ROOT / "bench" / "workloads" / f"{old['name']}.json")
                       .read_text()), config="tiny-arch")),
        "tests/tiny_data/configs/tiny-arch.json": (
            ROOT / "bench/tests/tiny_data/configs/smollm-360m.json"
        ).read_text(),
        "tests/tiny_data/workloads/fanout.tiny-arch.json": (
            ROOT / "bench/tests/tiny_data/workloads/fanout.smollm-360m.json"
        ).read_text()}
    for rel, text in new.items():
        path = tmp_path / "bench" / rel
        assert not path.exists(), rel
        path.write_text(text)
    bench["configs"].append(dict(bench["configs"][0], name="tiny-arch",
                                 file="bench/configs/tiny-arch.json"))
    bench["workloads"].append(dict(old, name="fanout.tiny-arch",
                                   config="tiny-arch"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    monkeypatch.setattr(harness, "BENCH", tmp_path / "bench")
    try:
        cell = tiny.cell("fanout.tiny-arch")
        model = reference.model(cell.config["reference"])
        assert model.__file__ == str(tmp_path / "bench" / "reference"
                                     / "tiny_arch.py")
        base = json.loads((ROOT / "bench" / "configs" / "smollm-360m.json")
                          .read_text())
        assert flops.per_token(cell.config, 1024, training=True) == \
            flops.per_token(dict(base, sizes=cell.config["sizes"]), 1024,
                            training=True)
        res = harness.run(["--workload", "fanout.tiny-arch", "--seed", "31",
                           "--seconds", "2"], allow_cpu=True, cell=cell)
    finally:
        sys.modules.pop("bench.reference.tiny_arch", None)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    for path, data in before.items():
        assert path.read_bytes() == data, path
