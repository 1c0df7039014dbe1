"""BENCHMARK.json and every file it names, loaded by name."""
import os
import re
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
               os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__)))), "src")]

import pytest

import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH = re.compile(r"^(hidden|intermediate|latent|state|proj\w*|head)_size$"
                   r"|_dim$|_rank$|expan|experts_per_tok")
B = spec.benchmark()


def _text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["paths"] == ["bench"]
    assert B["command"][1].startswith("bench/") and len(B["command"]) <= 32
    rs = B["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_texts():
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in B[group]]
        assert len(names) == len(set(names)), group
        for e in B[group]:
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
            for k in ("why", "layer", "source"):
                if k in e and group != "end_to_end" and \
                        group != "per_layer" or k == "layer" and k in e:
                    assert _text(e[k]), (e["name"], k)
    for w in B["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])


def test_configs_match_the_program_and_list_every_cut():
    from repro.configs.base import get_config
    for c in B["configs"]:
        f = spec.load_config(c["name"])
        assert os.path.normpath(c["file"]) == os.path.join(
            "bench", "configs", f"{c['name']}.json")
        assert f["source"] == c["source"] and f["name"] == c["name"]
        assert sorted(f["reduced"]) == sorted(c["reduced"])
        assert sorted(f["published"]) == sorted(c["reduced"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
            assert f["published"][key] != f[key]
        prog = spec.program_config(f)
        full = get_config(f["model"])
        for field, key in spec.arch(f).PROGRAM_KEYS.items():
            want = f["published"].get(key, f[key])
            assert getattr(full, field) == want, (c["name"], key)
            assert getattr(prog, field) == f[key]


def test_cells_load_by_name():
    for w in B["workloads"]:
        assert w["chips"] == 1
        assert _text(w["why"])
        cell = spec.load_cell(w["name"], B)
        e2e = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell["per_layer"], w["name"]
        if cell["mix"]["loop"] == "open":
            assert cell["params"]["rate_per_s"] > 0
        lim = cell["params"]["limits"]
        assert lim["max_logit_gap"] > 0 and lim["min_served_tokens"] > 0
        assert cell["engine"]["slots"] > 0


def test_per_layer_metrics_move_what_their_cells_report():
    e2e = {m["name"]: m for m in B["end_to_end"]}
    cells = {w["name"] for w in B["workloads"]}
    layers = {}
    for m in B["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert callable(spec.reader(m["name"]))
        for w in m["workloads"]:
            assert w in cells
            reported = e2e[m["moves"]].get("workloads", cells)
            assert w in reported, (m["name"], w)
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_end_to_end_bounds_and_sources():
    for m in B["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["name"] for m in B["end_to_end"]} >= {"setup_s"}


def test_unknown_chip_is_an_error():
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        spec.peaks("cpu")


def test_no_chip_no_result(tmp_path):
    """Without a TPU the command exits non-zero and prints no result, also
    from a directory that holds only BENCHMARK.json and ``bench/``."""
    import shutil
    import subprocess
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    for cwd in (spec.ROOT, tmp_path):
        p = subprocess.run(
            [sys.executable] + B["command"][1:] + [
                "--workload", B["workloads"][0]["name"], "--seed", "3",
                "--seconds", "1", "--trace", "0"],
            cwd=cwd, capture_output=True, text=True, timeout=300,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert p.returncode != 0 and p.stdout.strip() == "", p.stdout
