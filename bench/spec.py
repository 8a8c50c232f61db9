"""What one cell of the benchmark is, read from files by name.

``BENCHMARK.json`` at the checkout root names each cell's configuration,
traffic and chips. A configuration is ``bench/configs/<config>.json`` (the
model, the graph, the name of its plain reference in ``bench/reference/``
and of its operation count in ``bench/work/``); a traffic mix is
``bench/traffic/<traffic>.json`` (the federated job); a cell's comparison
limits are ``bench/cells/<cell>.json``; the chips' peaks are
``bench/peaks.json``. Adding a cell, a configuration or a per-layer
metric adds files and entries; nothing here names one.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def _load(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, Any]
    run_seconds: int
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def job(self) -> Dict[str, Any]:
        """The traffic's job with its privacy settings, as the reference
        reads it."""
        return dict(self.traffic["job"], privacy=self.traffic.get("privacy", {}))

    def reports(self, metric: Dict[str, Any]) -> bool:
        """Whether this cell reports ``metric`` (an entry of BENCHMARK.json)."""
        cells = metric.get("workloads")
        return cells is None or self.name in cells


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _load(root / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(entries)}")
    w = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load(root / configs[w["config"]]["file"])
    traffic = _load(BENCH / "traffic" / f"{w['traffic']}.json")
    limits = _load(BENCH / "cells" / f"{name}.json")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        limits=limits, run_seconds=int(bench["run_seconds"]),
        end_to_end=bench["end_to_end"], per_layer=bench["per_layer"],
    )


def load_peaks(device_kind: str) -> Dict[str, float]:
    """The peak table's row for ``device_kind``; a kind not in the table is
    an error, never a default."""
    table = _load(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in bench/peaks.json "
            f"(have {sorted(table)})"
        )
    return table[device_kind]


def federated_config(config: Dict[str, Any], traffic: Dict[str, Any], *,
                     seed: int, rounds: int):
    """The program's ``FederatedConfig`` for this configuration and job."""
    from repro.core import FedGATConfig
    from repro.federated import FederatedConfig
    from repro.privacy import PrivacyConfig

    program = dict(config["program"])
    model = program.pop("model", None)
    if model is not None:
        model = dict(model)
        model["domain"] = tuple(model["domain"])
        program["model"] = FedGATConfig(**model)
    return FederatedConfig(
        **program, **traffic["job"],
        privacy=PrivacyConfig(**traffic.get("privacy", {})),
        seed=seed, rounds=rounds,
    )
