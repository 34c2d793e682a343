"""BENCHMARK.json and the files it names: every name resolves to a file,
every reader declares the unit the entry states, and each configuration
file agrees with its published source."""

import importlib
import importlib.util
import json
import re
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from harness.reference import STATS  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

#: the configuration file's `model` keys against the source's config.json
FROM_SOURCE = {"n_layers": "num_hidden_layers", "d_model": "hidden_size",
               "n_heads": "num_attention_heads",
               "n_kv_heads": "num_key_value_heads",
               "d_ff": "intermediate_size", "vocab": "vocab_size",
               "tie_embeddings": "tie_word_embeddings",
               "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps"}


def _module(path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_top_level_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"][0] == "python3"
    assert all((ROOT / p).is_dir() for p in SPEC["paths"])
    assert 1 <= SPEC["run_seconds"] <= 51
    assert (ROOT / SPEC["command"][1]).is_file()


def test_names_and_cells():
    configs = {c["name"] for c in SPEC["configs"]}
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[g]]
    assert all(NAME.match(n) for n in names)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        group_names = [x["name"] for x in SPEC[group]]
        assert len(group_names) == len(set(group_names))
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in SPEC["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert 1 <= len(w["why"]) <= 200
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == configs


def test_metrics_cover_every_cell():
    cells = {w["name"] for w in SPEC["workloads"]}
    e2e = {e["name"]: set(e.get("workloads", cells))
           for e in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"] == cells
    for e in SPEC["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25 and UNIT.match(e["unit"])
    for cell in cells:
        assert sum(cell in ws for ws in e2e.values()) >= 2
        assert any(cell in p["workloads"] for p in SPEC["per_layer"])
    for p in SPEC["per_layer"]:
        assert p["source"] in SOURCES and UNIT.match(p["unit"])
        assert p["better"] in ("lower", "higher")
        assert set(p["workloads"]) <= e2e[p["moves"]]
        reader = _module(BENCH / "metrics" / f"{p['name']}.py")
        assert reader.UNIT == p["unit"], p["name"]
        assert callable(reader.read)
        if p["name"].split(".")[0].endswith("_roofline") \
                or "mfu" in p["name"]:
            assert p["unit"] == "%"


def test_configuration_files_match_their_sources():
    for c in SPEC["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        src = conf["source_config"]
        for key, hf in FROM_SOURCE.items():
            if hf in c["reduced"]:
                continue
            assert conf["model"][key] == src[hf], (c["name"], key)
        hd = src.get("head_dim", src["hidden_size"]
                     // src["num_attention_heads"])
        assert conf["model"]["head_dim"] == hd
        assert set(c["reduced"]) <= set(src)
        assert conf["check"] and all(0 < v for v in conf["check"].values())
        assert set(conf["check"]) <= set(STATS)


def test_peaks_and_work_tables():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    assert peaks["source"]
    row = peaks["devices"]["TPU v5 lite"]
    assert row["bf16_flops"] == 197e12 and row["hbm_bytes_s"] == 819e9
    for name in ("w4a16_matmul", "paged_decode_attention", "flash_prefill"):
        mod = importlib.import_module(f"work.{name}")
        assert mod.TRACE_NAMES and callable(mod.calls)
