"""A cell, as the files name it.

``BENCHMARK.json`` at the repository root lists configurations and cells.
A cell names a configuration (``bench/configs/<config>.json``, found by the
``file`` entry of that configuration) and a traffic mix
(``bench/traffic/<traffic>.json``).  Nothing else says what a cell is: a
later cell is a new entry and new data files, never an edit here.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path
from typing import Dict, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent

#: model keys of a configuration file's ``model`` section, as the program's
#: ArchConfig names them.  The harness checks each against the registry
#: entry it builds, so a registry that drifts from the published sizes
#: stops the run instead of measuring another model under this name.
MODEL_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab", "qk_norm", "qkv_bias", "tie_embeddings",
              "rope_theta", "norm_eps")


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict          # the configuration file's contents
    traffic: Dict         # the traffic file's contents

    @property
    def serving(self) -> Dict:
        return self.traffic["serving"]


def load_cell(name: str, bench_file: Optional[Path] = None) -> Cell:
    """The cell `name` of ``BENCHMARK.json`` with its two data files."""
    bench_file = bench_file or ROOT / "BENCHMARK.json"
    bench = load_json(bench_file)
    root = bench_file.parent
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic)


def arch_config(config: Dict):
    """The program's ArchConfig for a configuration file: the registry
    entry, with the file's ``changed`` keys applied, checked against the
    file's ``model`` sizes."""
    from repro.configs import get_config

    cfg = dataclasses.replace(get_config(config["registry"]),
                              **config.get("changed", {}))
    want = config["model"]
    got = {k: getattr(cfg, k) for k in MODEL_KEYS if k in want}
    if cfg.head_dim == 0 and "head_dim" in want:
        got["head_dim"] = cfg.hd
    bad = {k: (got[k], want[k]) for k in got if got[k] != want[k]}
    if bad:
        raise SystemExit(f"registry entry {config['registry']!r} differs "
                         f"from the configuration file: {bad}")
    return cfg


def serving_config(cell: Cell):
    """The cell's ServingConfig: paged layout, bucketed step, prefix cache
    on (the engine's defaults), sized by the traffic file."""
    from repro.configs import ServingConfig

    s = cell.serving
    ps = s["page_size"]
    pool_tokens = s.get("kv_pool_tokens", s["max_batch"] * s["max_ctx"])
    return ServingConfig(layout="paged", step="bucketed", prefix_cache=True,
                         max_batch=s["max_batch"], page_size=ps,
                         num_pages=pool_tokens // ps, max_ctx=s["max_ctx"])


def runtime(cell: Cell):
    """The runtime ``launch/serve.py`` serves with, under the
    configuration's quantization."""
    from repro.launch.serve import serving_runtime

    return serving_runtime(cell.serving["max_ctx"],
                           quant_backend=cell.config["quant"])
