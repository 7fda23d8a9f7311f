"""The benchmark is driven by data: every entry of BENCHMARK.json is
found by name in files of its own, and a later change adds a
configuration, a mix or a per-layer metric by adding files and entries
alone."""
import json
import re
import shutil

import numpy as np
import pytest

from bench import common, traffic

REG = common.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
WORKLOADS = [w["name"] for w in REG["workloads"]]


def test_top_level_keys_and_paths():
    assert set(REG) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert REG["paths"] == ["bench"]
    assert all(PATH.match(p) and ".." not in p for p in REG["paths"])
    assert len(REG["command"]) <= 32
    assert isinstance(REG["run_seconds"], int) and \
        1 <= REG["run_seconds"] <= 51


def test_names_units_and_one_line_texts():
    names = [c["name"] for c in REG["configs"]] + WORKLOADS + \
        [m["name"] for m in REG["end_to_end"] + REG["per_layer"]] + \
        [w["traffic"] for w in REG["workloads"]]
    for n in names:
        assert NAME.match(n), n
    for m in REG["end_to_end"] + REG["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for e in REG["configs"] + REG["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
        assert "\t" not in e["why"]
    for m in REG["per_layer"]:
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]


@pytest.mark.parametrize("name", WORKLOADS)
def test_every_cell_is_found_by_name(name):
    cell = common.cell(name)
    e2e = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert m["moves"] in e2e, m
    assert cell.config["runner"] in ("serve", "compiler")


@pytest.mark.parametrize("metric", [m["name"] for m in REG["per_layer"]])
def test_every_per_layer_metric_has_its_reader(metric):
    assert callable(common.metric_reader(metric))


def test_every_config_file_lies_under_paths():
    files = [c["file"] for c in REG["configs"]]
    assert len(set(files)) == len(files)
    for f in files:
        assert f.startswith("bench/")
        assert isinstance(common.load_json(common.ROOT / f), dict)


def _mix(name):
    return common.load_json(common.ROOT / "bench" / "traffic" /
                            f"{name}.json")


# every serving mix kept under bench/traffic, registered or not
SERVING_MIXES = sorted(
    p.stem for p in (common.ROOT / "bench" / "traffic").glob("*.json")
    if "arrivals" in _mix(p.stem))


@pytest.mark.parametrize("mix", SERVING_MIXES)
def test_same_seed_same_requests(mix):
    m = _mix(mix)
    make = lambda s: traffic.open_loop(m, s, 3.0, 1000)  # noqa: E731
    a, b, c = make(2**31 + 7), make(2**31 + 7), make(5)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # another seed replays the same schedule of sizes and arrivals with
    # other prompts
    sched = lambda rs: [(r.arrival, r.prompt_len, r.gen_len)  # noqa: E731
                        for r in rs]
    assert sched(a) == sched(b) == sched(c)
    assert not any(np.array_equal(x.prompt, y.prompt)
                   for x, y in zip(a, c))


@pytest.mark.parametrize("mix", SERVING_MIXES)
def test_the_schedule_is_the_mix_own(mix):
    """The order of the trace comes from the mix's ``sizes_seed``."""
    m = _mix(mix)

    def make(mx):
        return traffic.open_loop(mx, 1, 20.0, 1000)
    other = dict(m, sizes_seed=m.get("sizes_seed", 0) + 1)
    assert [(r.arrival, r.prompt_len) for r in make(m)] != \
        [(r.arrival, r.prompt_len) for r in make(other)]


def test_lengths_follow_the_mix():
    lens = traffic.lengths({"kind": "lognormal", "median": 384,
                            "sigma": 1.0, "min": 16, "max": 2048,
                            "buckets": [16, 64, 384, 2048]}, 1001)
    assert set(lens) <= {16, 64, 384, 2048}
    assert lens[500] == 384 and lens[501] == 2048    # rounded up to a bucket
    assert traffic.lengths({"kind": "choice", "values": [3, 1, 2]},
                           6).tolist() == [1, 1, 2, 2, 3, 3]


def test_a_later_change_adds_cells_by_files_alone(tmp_path):
    """Copy the benchmark, add a mix, a cell and a per-layer metric as
    new files and entries, and find them by name; no file that was there
    changes."""
    shutil.copytree(common.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("data", "__pycache__"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    reg = json.loads(json.dumps(REG))
    serving = next(w for w in reg["workloads"]
                   if common.cell(w["name"]).config["runner"] == "serve")
    (tmp_path / "bench" / "traffic" / "chat-bursty.json").write_text(
        json.dumps(dict(common.cell(serving["name"]).traffic,
                        arrivals={"kind": "poisson", "rate_per_s": 3.0})))
    (tmp_path / "bench" / "metrics" / "requests.bursty.py").write_text(
        "def read(run):\n    return float(len(run.window.requests))\n")
    name = serving["config"] + ".chat-bursty"
    reg["workloads"].append({"name": name, "config": serving["config"],
                             "traffic": "chat-bursty", "chips": 1,
                             "why": "bursty"})
    for m in reg["end_to_end"]:
        if "workloads" in m and serving["name"] in m["workloads"]:
            m["workloads"].append(name)
    reg["per_layer"].append({"name": "requests.bursty", "unit": "count",
                             "better": "higher",
                             "source": "program_counter",
                             "layer": "scheduler",
                             "moves": "tbt_p95_ms", "workloads": [name]})
    # a compiler cell with an input program of its own
    compiled = next(w for w in reg["workloads"]
                    if common.cell(w["name"]).config["runner"] == "compiler")
    (tmp_path / "bench" / "programs" / "axpy.py").write_text(AXPY)
    (tmp_path / "bench" / "traffic" / "axpy-1m.json").write_text(
        json.dumps({"program": "axpy", "n": 1 << 20,
                    "check": {"rel_err": 1e-6}}))
    name2 = compiled["config"] + ".axpy-1m"
    reg["workloads"].append({"name": name2, "config": compiled["config"],
                             "traffic": "axpy-1m", "chips": 1,
                             "why": "axpy"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(reg))
    cell = common.cell(name, root=tmp_path)
    assert cell.traffic["arrivals"]["rate_per_s"] == 3.0
    assert [m["name"] for m in cell.per_layer] == ["requests.bursty"]
    read = common.metric_reader("requests.bursty", root=tmp_path)
    run = type("R", (), {"window": type("W", (), {"requests": [1, 2]})})
    assert read(run) == 2.0
    cell2 = common.cell(name2, root=tmp_path)
    prog = common.program_maker(cell2.traffic["program"], root=tmp_path)(
        cell2.config, cell2.traffic, 2**31 + 1)
    assert prog.reference(*prog.args).shape == (1 << 20,)
    assert prog.flops == 2 * (1 << 20)
    assert all(p.read_bytes() == b for p, b in before.items())


AXPY = """
import numpy as np
from bench.compiler import Program


def make(cfg, mix, seed):
    rng = np.random.default_rng(seed)
    x, y = rng.standard_normal((2, mix["n"]), dtype=np.float32)
    return Program(args=(x, y), fn=lambda a, b: 2.0 * a + b,
                   reference=lambda a, b: 2.0 * a.astype(float) + b,
                   control=lambda a, b: 2.0 * a + b,
                   flops=2.0 * mix["n"], bytes=12.0 * mix["n"])
"""


@pytest.mark.parametrize("name", sorted(
    p.stem for p in (common.ROOT / "bench" / "programs").glob("*.py")))
def test_every_program_file_has_make(name):
    assert callable(common.program_maker(name))


@pytest.mark.parametrize("name", WORKLOADS)
def test_every_compiler_cell_names_a_program_file(name):
    cell = common.cell(name)
    if cell.config["runner"] == "compiler":
        assert (common.ROOT / "bench" / "programs" /
                f"{cell.traffic['program']}.py").is_file()
