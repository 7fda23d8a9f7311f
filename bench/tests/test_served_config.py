"""A served configuration names its own plain reference, and the serving
runner reaches the architecture only through it: a second served model
comes as a configuration file, a reference file and entries alone."""
import dataclasses
import json
import shutil
import time

import pytest

from bench import common, run, serve
from bench.reference import qwen2

DATA = common.ROOT / "bench" / "tests" / "data"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}

# a second architecture's reference, as a later change would add it: here
# qwen2's equations with a padding of its own, recording each use
OTHER_REF = '''
from bench.reference import qwen2 as base

CALLS = []
PAD = 512


def shapes(cfg, padded_vocab):
    CALLS.append("shapes")
    return base.shapes(cfg, padded_vocab)


def cfg_items(cfg):
    CALLS.append("cfg_items")
    return base.cfg_items(cfg)


def served_gaps(w, tokens, served, items):
    CALLS.append(("served_gaps", len(tokens)))
    return base.served_gaps(w, tokens, served, items)


def control_gaps(w, tokens, items, quant="fp8"):
    CALLS.append("control_gaps")
    return base.control_gaps(w, tokens, items, quant)


def check_program(cfg, program_cfg):
    CALLS.append("check_program")
    base.check_program(cfg, program_cfg)
'''

REFUSING_REF = '''
from bench.reference.qwen2 import PAD, shapes, cfg_items  # noqa: F401


def check_program(cfg, program_cfg):
    raise ValueError("tiny-other serves no program config of this family")
'''


@pytest.fixture(scope="module")
def program():
    common.import_program()


def _add_served_config(root, reference_source):
    """Copy the benchmark to ``root`` and add a served configuration, its
    reference, a mix and a cell as new files and entries.  Returns the
    cell's name and the bytes of every file that was there."""
    shutil.copytree(common.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("data", "__pycache__"))
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    (root / "bench" / "reference" / "tiny_other.py").write_text(
        reference_source)
    cfg = dict(common.load_json(DATA / "tiny-qwen2.json"),
               reference="bench/reference/tiny_other.py")
    (root / "bench" / "configs" / "tiny-other.json").write_text(
        json.dumps(cfg))
    (root / "bench" / "traffic" / "tiny-chat.json").write_text(
        (DATA / "tiny-chat.json").read_text())
    reg = json.loads(json.dumps(common.benchmark()))
    name = "tiny-other.tiny-chat"
    reg["configs"].append({"name": "tiny-other", "source": "a test",
                           "file": "bench/configs/tiny-other.json",
                           "reduced": [], "why": "a second served model"})
    reg["workloads"].append({"name": name, "config": "tiny-other",
                             "traffic": "tiny-chat", "chips": 1,
                             "why": "tiny"})
    for m in reg["end_to_end"]:
        if m["name"] in ("output_tok_s", "tbt_p95_ms"):
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(reg))
    return name, before


def test_a_second_served_config_runs_through_its_own_reference(
        program, tmp_path):
    name, before = _add_served_config(tmp_path, OTHER_REF)
    cell = common.cell(name, root=tmp_path)
    res = run.measure(cell, 2**31 + 11, 0.5, False, CPU, time.monotonic())
    assert res["correct"], res["checks"]
    assert res["attempted"] == 20 and res["failed"] == 0
    assert set(res["metrics"]) == {"output_tok_s", "tbt_p95_ms", "setup_s"}
    calls = common.reference(cell.config, tmp_path).CALLS
    assert {"check_program", "shapes", "cfg_items"} <= set(calls)
    # the tiny mix's sequences fit one padding of the new reference's own
    assert ("served_gaps", 512) in calls
    assert all(p.read_bytes() == b for p, b in before.items())


def test_the_reference_check_refuses_at_set_up(program, tmp_path):
    name, _ = _add_served_config(tmp_path, REFUSING_REF)
    cell = common.cell(name, root=tmp_path)
    with pytest.raises(ValueError, match="tiny-other serves no program"):
        serve.set_up(cell, 1, 0.5)


@pytest.mark.parametrize("path", [
    "src/repro/models/model.py",        # the program's own code
    "bench/../src/repro/models/model.py",
    "/etc/hostname",
    "bench/configs/qwen2-1.5b.json",    # not a Python file
])
def test_a_reference_outside_the_benchmark_is_refused(path):
    with pytest.raises(ValueError, match="benchmark's paths"):
        common.reference({"reference": path})


def test_a_served_config_without_a_reference_is_refused():
    cfg = common.load_json(DATA / "tiny-qwen2.json")
    del cfg["reference"]
    with pytest.raises(KeyError, match="names no"):
        common.reference(cfg)


def test_every_served_config_names_its_reference():
    for c in common.benchmark()["configs"]:
        cfg = common.load_json(common.ROOT / c["file"])
        if cfg["runner"] == "serve":
            assert callable(common.reference(cfg).check_program), c["name"]


@pytest.mark.parametrize("change", [
    {}, {"d_model": 96}, {"vocab_size": 1024}, {"qkv_bias": False},
    {"norm": "layernorm"}, {"act": "gelu"}])
def test_qwen2_check_program(program, change):
    """The qwen2 reference serves the program config that matches the
    file, and refuses one that differs in a size or the layer's parts."""
    from repro.configs import get_config
    cfg = common.load_json(DATA / "tiny-qwen2.json")
    pc = dataclasses.replace(get_config("qwen2-1.5b", reduced=True),
                             n_layers=cfg["num_hidden_layers"])
    if not change:
        qwen2.check_program(cfg, pc)
        return
    with pytest.raises(ValueError, match="not the configuration's file"):
        qwen2.check_program(cfg, dataclasses.replace(pc, **change))
