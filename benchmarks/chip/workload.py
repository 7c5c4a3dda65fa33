"""Find a cell's files by name and build what one DAG-FL episode needs.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix. The
configuration is ``BENCHMARK.json``'s ``configs[].file``; its ``task`` names
``tasks/<task>.py`` (inputs, plain reference, FLOPs). The traffic mix is
``traffic/<traffic>.json``. Nothing here knows a cell by name, so a new cell
is new files plus a ``workloads`` entry.

Seeds: ``--seed`` may be any non-negative integer. The population's data
and the overlay's link draw come from it once per run; episode ``e`` runs
under a small episode seed derived from ``(seed, e)`` (the program folds
its seed into 32-bit PRNG keys), and each node draws its batches from
``(episode seed, node)``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    task: Any                      # the tasks/<task>.py module
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @property
    def iterations(self) -> int:
        return int(self.config["iterations"])

    @property
    def eval_every(self) -> int:
        return int(self.traffic["eval_every"])


def load_cell(root: str, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files loaded."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", w["traffic"] + ".json")) as f:
        traffic = json.load(f)
    task = load_module(os.path.join(HERE, "tasks", config["task"] + ".py"),
                       "bench_task_" + config["task"])

    def applies(m):
        return name in m.get("workloads", [name])

    return Cell(name, int(w["chips"]), w["config"], config, w["traffic"], traffic,
                task, [m for m in bench["end_to_end"] if applies(m)],
                [m for m in bench["per_layer"] if applies(m)])


def _seed_words(seed: int) -> List[int]:
    seed = int(seed)
    if seed < 0:
        raise ValueError("--seed must be a non-negative integer")
    words = []
    while True:
        words.append(seed & 0xFFFFFFFF)
        seed >>= 32
        if not seed:
            return words


def run_seeds(seed: int) -> np.random.SeedSequence:
    return np.random.SeedSequence(_seed_words(seed))


def episode_seed(seed: int, episode: int) -> int:
    """Episode ``episode``'s seed, in [0, 2**20): fixed by (seed, episode)."""
    ss = np.random.SeedSequence(_seed_words(seed) + [0x5EED, episode])
    return int(ss.generate_state(1)[0] % (1 << 20))


class BenchNode:
    """One device of the population: its data and its batch stream.

    Duck-types what the program's driver reads from a node (``node_id``,
    ``behavior``, ``epoch``, ``val_batch``); draws are a function of
    ``(episode seed, node_id)`` and the call order alone.
    """

    behavior = "normal"

    def __init__(self, node_id: int, train: dict, test: dict, ep_seed: int):
        self.node_id, self.train, self.test = node_id, train, test
        self.rng = np.random.default_rng([ep_seed, node_id])

    def epoch(self, steps: int, size: int) -> dict:
        n = len(next(iter(self.train.values())))
        idx = self.rng.integers(0, n, (steps, size))
        return {k: v[idx] for k, v in self.train.items()}

    def val_batch(self, size: int) -> dict:
        n = len(next(iter(self.test.values())))
        idx = self.rng.integers(0, n, size)
        return {k: v[idx] for k, v in self.test.items()}


def link_bandwidth(traffic: dict, n: int, ss: np.random.SeedSequence,
                   classes_table: Dict[str, float]) -> np.ndarray:
    """(n, n) f32 bits/s of a full overlay, symmetric, zero diagonal.

    Every undirected link takes one of the traffic's classes. The classes
    are dealt in equal shares (as near as the link count allows) and
    shuffled by the seed, so every seed prices the same mix of links.
    """
    bws = [classes_table[c] for c in traffic["link_classes"]]
    iu = np.triu_indices(n, 1)
    deal = np.resize(np.asarray(bws, np.float64), len(iu[0]))
    np.random.default_rng(ss).shuffle(deal)
    bw = np.zeros((n, n), np.float64)
    bw[iu] = deal
    bw = bw + bw.T
    return bw.astype(np.float32)


class Episodes:
    """The cell's deployment, built once per run from ``--seed``: the
    population's data, the global validation set and the overlay.
    ``nodes(ep_seed)`` returns fresh node objects for one episode."""

    def __init__(self, cell: Cell, seed: int):
        self.cell = cell
        data_ss, link_ss = run_seeds(seed).spawn(2)
        self.node_data, self.gval = cell.task.make_data(cell.config, data_ss)
        self.link_ss = link_ss

    def overlay(self, topo_mod):
        """The program's ``Topology`` for this traffic (full overlay)."""
        tr = self.cell.traffic
        n = self.cell.config["dagfl"]["num_nodes"]
        if tr["overlay"] != "full":
            raise ValueError(f"unknown overlay {tr['overlay']!r}")
        top = topo_mod.full(n, link_latency=float(tr["link_latency_s"]))
        bw = link_bandwidth(tr, n, self.link_ss, topo_mod.TABLE1_LINK_CLASSES)
        return top._replace(bandwidth=np.where(top.adjacency, bw, 0.0)
                            .astype(np.float32))

    def nodes(self, ep_seed: int) -> List[BenchNode]:
        return [BenchNode(i, tr, te, ep_seed)
                for i, (tr, te) in enumerate(self.node_data)]


def dagfl_config(base_mod, cfg: dict):
    d = dict(cfg["dagfl"])
    d["cpu_freq_range"] = tuple(d["cpu_freq_range"])
    return base_mod.DagFLConfig(**d)
