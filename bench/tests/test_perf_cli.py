"""The benchmark command run without a chip, and the shape of
``BENCHMARK.json`` against the files it names."""
import json
import os
import re
import shutil
import subprocess
import sys

from bench.tests.tiny import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "corpus-zipf16k.splits", "--seed", "3000000000", "--seconds", "1",
         "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def no_result(out: str) -> bool:
    return not any(line.startswith("{") for line in out.splitlines())


def test_without_a_tpu_the_run_exits_2_and_prints_no_result():
    p = cli(ROOT)
    assert p.returncode == 2, p.stderr[-2000:]
    assert no_result(p.stdout) and "no TPU" in p.stderr


def test_with_only_the_benchmark_files_the_run_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    p = cli(tmp_path)
    assert p.returncode != 0 and no_result(p.stdout)


def test_benchmark_json_names_files_that_exist():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["bench"]
    assert spec["command"] == ["python3", "bench/run.py"]
    configs = {c["name"]: c for c in spec["configs"]}
    for c in configs.values():
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["reduced"] == c["reduced"]
        assert (BENCH / "entries" / f"{cfg['entry']}.py").is_file()
        assert set(cfg["limits"]) == {"set_diff", "score_gap",
                                      "side_semantics_off"}
        assert cfg["semantics"] in ("extra", "variant_exact")
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    cells = {w["name"] for w in spec["workloads"]}
    for w in spec["workloads"]:
        assert w["config"] in configs and w["chips"] == 1
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        reported = [m for m in e2e.values()
                    if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in {m["name"] for m in reported}
        assert len(reported) >= 2
    for m in spec["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        assert m["moves"] in e2e and set(m["workloads"]) <= cells
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", [cell])
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
