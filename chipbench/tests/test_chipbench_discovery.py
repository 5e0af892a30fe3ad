"""The harness finds every file of a cell by name, and a cell, a
configuration or a per-layer metric is added by adding files alone
(CPU only; nothing here touches a TPU)."""
import json
import os
import re
import shutil

import jax
import pytest

from chipbench.tests import small
from chipbench import run

BENCH = run.load_json(os.path.join(small.ROOT, "BENCHMARK.json"))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
SEED = 2 ** 31 + 3


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_is_found_by_name(workload):
    found = run.find_cell(BENCH, workload)
    assert os.path.isfile(found["engine_path"])
    engine = run.load_module(found["engine_path"])
    # a cell holds the numbers that separate its program from its control
    assert found["check"]["limits"]
    assert set(found["check"]["limits"]) <= set(engine.NUMBERS)
    assert found["check"]["calls"] >= 1
    assert found["metrics"], "every cell reports a per-layer metric"
    for m in found["metrics"]:
        mod = run.load_module(os.path.join(found["metrics_dir"],
                                           m["name"] + ".py"))
        assert callable(mod.read)


def test_benchmark_file_keeps_the_format():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][0] == "python3" and len(BENCH["command"]) <= 32
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
    assert all(w.startswith(tuple(BENCH["paths"]))
               for w in BENCH["command"][1:])
    # a full check of 24 cells fits its 43200 seconds
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.fullmatch(c["name"]) and _line(c["why"])
        assert _line(c["source"])
        conf = run.load_json(os.path.join(small.ROOT, c["file"]))
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        assert all(NAME.fullmatch(k) and k in conf for k in c["reduced"])
    cells = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(cells)) == len(cells)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.fullmatch(w["name"]) and NAME.fullmatch(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert _line(w["why"])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in metrics:
        assert NAME.fullmatch(m["name"]) and UNIT.fullmatch(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and _line(m["layer"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", [])) <= {
            w["name"] for w in BENCH["workloads"]}
    assert len(json.dumps(BENCH)) < 64 * 1024


def _real_dir(path):
    """Turn the linked directory ``path`` of a small checkout into a real
    one that links the same files; returns ``path``."""
    real = os.path.realpath(path)
    os.unlink(path)
    os.mkdir(path)
    for name in os.listdir(real):
        os.symlink(os.path.join(real, name), os.path.join(path, name))
    return path


def _write_json(path, data):
    with open(path, "w") as f:
        json.dump(data, f)


def _drop_in(root):
    """Add to the full-size files of a checkout, by adding files and
    entries alone: a configuration of an engine no cell uses (a copy of
    the social driver under a new name), with its sizes file and its
    faults file; a traffic mix with its sizes file; a four-chip cell; and a
    per-layer metric."""
    bench_dir = os.path.join(root, "chipbench")
    conf = run.load_json(os.path.join(bench_dir, "configs",
                                      "hier-social-1m.json"))
    conf.update(engine="social_copy", drop_prob=0.1)
    _write_json(os.path.join(bench_dir, "configs", "extra.json"), conf)
    _write_json(os.path.join(bench_dir, "traffic", "extra-1x8.json"),
                {"scenarios_per_call": 1, "rounds_per_call": 16})
    _write_json(os.path.join(bench_dir, "workloads", "extra-cell.json"),
                {"calls": 1, "limits": {"belief_gap": 1e-5,
                                        "z_over_m_gap": 1e-5,
                                        "curve_gap": 1e-4}})
    engines = _real_dir(os.path.join(bench_dir, "engines"))
    shutil.copy(os.path.join(run.HERE, "engines", "social.py"),
                os.path.join(engines, "social_copy.py"))
    tests = os.path.join(bench_dir, "tests")
    sizes = _real_dir(os.path.join(tests, "sizes"))
    _write_json(os.path.join(sizes, "extra.json"), {"networks": 32})
    _write_json(os.path.join(sizes, "extra-1x8.json"),
                {"rounds_per_call": 8})
    faults = _real_dir(os.path.join(tests, "faults"))
    shutil.copy(os.path.join(small.HERE, "faults", "social.py"),
                os.path.join(faults, "social_copy.py"))
    metrics = _real_dir(os.path.join(bench_dir, "metrics"))
    with open(os.path.join(metrics, "calls_traced.py"), "w") as f:
        f.write("def read(ctx):\n    return float(ctx.calls)\n")
    bench = run.load_json(os.path.join(root, "BENCHMARK.json"))
    bench["configs"].append({"name": "extra", "source": "s",
                             "file": "chipbench/configs/extra.json",
                             "reduced": [], "why": "w"})
    bench["workloads"].append({"name": "extra-cell", "config": "extra",
                               "traffic": "extra-1x8", "chips": 4,
                               "why": "w"})
    bench["per_layer"].append({"name": "calls_traced", "unit": "calls",
                               "better": "higher", "source": "host_clock",
                               "layer": "run_* entry points",
                               "moves": "agent_rounds_per_s",
                               "workloads": ["extra-cell"]})
    _write_json(os.path.join(root, "BENCHMARK.json"), bench)
    return bench


def test_dropped_in_files_are_found_without_a_code_edit(tmp_path):
    root = small.layout(str(tmp_path))
    _drop_in(root)
    small.shrink(root)
    bench = run.load_json(os.path.join(root, "BENCHMARK.json"))
    found = run.find_cell(bench, "extra-cell", root)
    assert found["config"]["networks"] == 32
    assert found["traffic"]["rounds_per_call"] == 8
    assert found["engine_path"].endswith("social_copy.py")
    assert [m["name"] for m in found["metrics"]] == ["calls_traced"]
    reader = run.load_module(os.path.join(found["metrics_dir"],
                                          "calls_traced.py"))

    class Ctx:
        calls = 3
    assert reader.read(Ctx) == 3.0
    # the new cell asks for more chips than this process has: it runs end
    # to end in a child on four CPU devices, and is correct
    assert small.chips(root, "extra-cell") == 4 > len(jax.devices())
    res = small.on_devices(4, small.run_small, root, "extra-cell", SEED)
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4
    assert res["metrics"]["agent_rounds_per_s"]["value"] > 0
    # its own faults, found by its engine's name, each come out not correct
    faults = small.faults(root, "extra-cell")
    assert [f.__name__ for f in faults] == [
        f.__name__ for f in small.faults(small.ROOT, "social-1m-drops")]
    for fault in faults:
        res = small.on_devices(4, small.run_small, root, "extra-cell", SEED,
                               small.fault_ref(fault))
        assert res["correct"] is False and res["failed"] >= 1, \
            (fault.__name__, res["checks"])


def test_a_cell_file_without_a_sizes_file_is_an_error(tmp_path):
    root = small.layout(str(tmp_path))
    _drop_in(root)
    os.unlink(os.path.join(root, "chipbench", "tests", "sizes",
                           "extra.json"))
    with pytest.raises(FileNotFoundError, match="extra.json"):
        small.shrink(root)


def test_every_cell_file_has_sizes_and_every_engine_faults():
    for rel in small.cell_files(small.ROOT):
        sizes = small.sizes_file(small.ROOT, rel)
        assert os.path.isfile(sizes), rel
        assert isinstance(run.load_json(sizes), dict)
    engines = [n[:-3] for n in os.listdir(os.path.join(run.HERE, "engines"))
               if n.endswith(".py") and n != "__init__.py"]
    assert engines
    for engine in engines:
        faults = run.load_module(os.path.join(
            small.HERE, "faults", engine + ".py")).FAULTS
        assert len(faults) >= 2, engine
        assert all(callable(f) and f.__doc__ for f in faults)
    for w in BENCH["workloads"]:
        assert len(small.faults(small.ROOT, w["name"])) >= 2


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_each_cell_builds_its_inputs_at_a_small_size(tmp_path, workload):
    root = small.checkout(str(tmp_path))
    found = run.find_cell(run.load_json(os.path.join(root,
                                                     "BENCHMARK.json")),
                          workload, root)
    cell = small.on_devices(small.chips(root, workload), small.small_cell,
                            root, workload, 2 ** 31 + 11)
    assert cell["agent_rounds_per_call"] > 0
    assert cell["rounds_per_call"] == found["traffic"]["rounds_per_call"]
    assert cell["bytes_per_round"]
    assert all(b > 0 for b in cell["bytes_per_round"].values())


def test_without_a_tpu_the_run_stops_before_a_result(capsys):
    # the tests run JAX on the CPU, which is exactly "no accelerator"
    assert run.main(["--workload", BENCH["workloads"][0]["name"],
                     "--seed", "1", "--seconds", "1"]) != 0
    assert capsys.readouterr().out == ""
