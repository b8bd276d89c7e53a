"""BENCHMARK.json: every cell resolves by name to its files, every name and
unit keeps to the allowed characters, and a cell added as new files is
found without editing any file that is there."""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spec as SPEC  # noqa: E402

BENCH = SPEC.load()
CELLS = [w["name"] for w in BENCH["workloads"]]
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_paths():
    assert set(BENCH) == TOP_KEYS
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert BENCH["command"] == ["python3", "bench/run.py"]


def test_names_units_and_entries():
    names = [c["name"] for c in BENCH["configs"]] + CELLS + [
        m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in BENCH["workloads"]]:
        assert SPEC.NAME_RE.fullmatch(n), n
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["why"]) and _line(c["source"])
        assert all(SPEC.NAME_RE.fullmatch(k) for k in c["reduced"])
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _line(w["why"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert SPEC.UNIT_RE.fullmatch(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and _line(m["layer"])
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = SPEC.resolve(cell)
    assert c.model["d_model"] > 0 and c.traffic["seq_len"] > 0
    assert {"grad_gap", "update_gap"} <= set(c.limits)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    for m in c.per_layer:
        assert callable(c.reader(m["name"]))


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_config_matches_the_programs_except_what_is_reduced(name):
    """The configuration file holds the program's config of the same
    model, with only the keys in ``reduced`` changed."""
    import dataclasses
    import repro.configs as C
    entry = next(c for c in BENCH["configs"] if c["name"] == name)
    model = json.loads((ROOT / entry["file"]).read_text())["model"]
    arch = {"qwen1.5-0.5b": "qwen1.5-0.5b"}
    prog = dataclasses.asdict(C.get_config(arch[name]))
    differ = {k for k, v in model.items() if k in prog and prog[k] != v}
    assert differ == set(entry["reduced"])


def test_a_cell_added_as_files_is_found(tmp_path):
    """A later PR adds a cell, its traffic, its limits and a metric as new
    files plus entries in BENCHMARK.json; nothing else is edited."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    (root / "bench" / "traffic" / "b4-s1024.json").write_text(json.dumps(
        dict(SPEC.resolve(CELLS[0]).traffic, seq_len=1024, global_batch=4)))
    new = "qwen1.5-0.5b.new-4x1024"
    (root / "bench" / "workloads" / f"{new}.json").write_text(json.dumps(
        {"limits": {"loss_gap": 1, "grad_gap": 1, "update_gap": 1}}))
    (root / "bench" / "metrics" / "new.metric.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    bench["workloads"].append({"name": new, "config": "qwen1.5-0.5b",
                               "traffic": "b4-s1024", "chips": 1,
                               "why": "a new cell"})
    bench["per_layer"].append({"name": "new.metric", "unit": "ms",
                               "better": "lower", "source": "host_clock",
                               "layer": "runtime", "moves": "tokens_per_s",
                               "workloads": [new]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = SPEC.resolve(new, root)
    assert cell.traffic["global_batch"] == 4
    assert "new.metric" in [m["name"] for m in cell.per_layer]
    assert cell.reader("new.metric")(None) == 1.0
    assert "new.metric" not in [m["name"] for m in
                                SPEC.resolve(CELLS[0], root).per_layer]
    assert all(p.read_bytes() == b for p, b in before.items())


@pytest.mark.parametrize("n, rank", [(100, 95), (41, 39), (1, 1)])
def test_iteration_p95_is_the_nearest_rank(n, rank):
    from types import SimpleNamespace
    reader = SPEC.resolve(CELLS[0]).reader("iteration.p95_s")
    times = [float(i) for i in range(n, 0, -1)]
    assert reader(SimpleNamespace(window={"times": times})) == float(rank)
