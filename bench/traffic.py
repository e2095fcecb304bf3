"""The general query generator: a configuration file and a traffic file in,
a fixed catalog of DSE queries and a seeded order over it out.

A configuration (``bench/configs/<name>.json``) is a deployment of the DSE
system: the design space, the workloads' buffer requirements (``tasks``),
and the selection and compose policy. A traffic mix
(``bench/traffic/<workload>.json``) picks the tasks, the hierarchy depth,
policy overrides, the search engine and the replay re-rank. Each catalog
entry is one public ``compose`` call; the seed only orders the catalog, in
rounds that each hold every entry once, so every seed does the same work.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterator, List

import numpy as np

from bench import reference as R

# the compose policy a configuration may set, with the program's defaults
POLICY_DEFAULTS = {
    "preference": ["os-si", "si-si", "sram"],
    "objective": "preference",
    "candidate_mode": "per_family_best",
    "max_candidates_per_bucket": 64,
    "max_compositions": 200_000,
    "search": "auto",
    "search_threshold": 200_000,
    "top_k": 8,
    "vdd_sweep": [],
    "refresh_margin_sweep": [],
}
SIM_DEFAULTS = {"phases": ["prefill", "decode"], "duration_s": 1e-3,
                "n_bins": 32, "refresh": True, "refresh_margin": 0.8,
                "rewrite_overhead": 2.0, "objective": "energy",
                "adaptive_refresh": False, "temp_drift_k": 0.0}


@dataclass(frozen=True)
class Case:
    """One catalog entry: the task's name and the query."""
    name: str
    query: R.Query


def design_space(space: Dict[str, List]) -> List[Dict[str, object]]:
    """The configurations of a design space, in enumeration order (SRAM has
    no level shifter)."""
    out = []
    for mt in space["mem_types"]:
        for wz in space["word_sizes"]:
            for nw in space["num_words"]:
                for banks in space.get("banks", [1]):
                    for ls in (space["ls_options"] if mt != "sram6t"
                               else [False]):
                        out.append(dict(mem_type=mt, word_size=wz,
                                        num_words=nw, banks=banks,
                                        level_shift=ls,
                                        sa_current_mode=False, mux=0))
    return out


def load_json(path: Path) -> Dict[str, object]:
    return json.loads(path.read_text())


def catalog(config: Dict[str, object], traffic: Dict[str, object]
            ) -> List[Case]:
    configs = tuple(design_space(config["design_space"]))
    policy = {**POLICY_DEFAULTS, **config.get("policy", {}),
              **traffic.get("policy", {})}
    unknown = set(policy) - set(POLICY_DEFAULTS)
    if unknown:
        raise KeyError(f"unknown policy keys {sorted(unknown)}")
    refine = traffic.get("refine")
    sim = {**SIM_DEFAULTS, **traffic.get("sim", {})} if refine else None
    wanted = traffic.get("tasks")
    depth = traffic.get("levels")
    cases = []
    for task in config["tasks"]:
        if wanted is not None and task["id"] not in wanted:
            continue
        t = {"id": task["id"], "name": task["name"],
             "levels": task["levels"][:depth] if depth else task["levels"]}
        cases.append(Case(task["name"], R.Query(
            configs=configs, task=t, policy=policy, refine=refine, sim=sim)))
    if not cases:
        raise ValueError("the traffic mix selects no task")
    return cases


def schedule(n_cases: int, seed: int) -> Iterator[int]:
    """Catalog indices for the window: rounds of a seeded permutation, each
    round holding every entry once."""
    rng = np.random.default_rng(seed)
    while True:
        yield from (int(i) for i in rng.permutation(n_cases))
