"""Cells small enough for the CPU, defined here and nowhere else:
deployments, traffic mixes and a query kind (a module) that
``BENCHMARK.json`` does not know, found by the harness by name as a later
PR's files would be."""

import copy
import json
from pathlib import Path

from bench import harness

CONFIGS = {
    "tiny_rw": {"store": "random_walk", "serve": "nn_search",
                "n_series": 256, "length": 32, "w": 6, "v": 4, "k": 1,
                "sketch_segments": 8, "verify_chunk": 8,
                "candidate_chunk": 128, "data_seed": 5,
                "precision": "float32", "chips": 1},
    "tiny_rw_k3": {"store": "random_walk", "serve": "nn_search",
                   "n_series": 256, "length": 32, "w": 6, "v": 4, "k": 3,
                   "sketch_segments": 8, "verify_chunk": 8,
                   "candidate_chunk": 128, "data_seed": 6,
                   "precision": "float32", "chips": 1},
    "tiny_ucr": {"store": "ucr_synthetic", "serve": "nn_search",
                 "n_series": 140, "n_test": 30,
                 "n_classes": 7, "length": 24, "w": 3, "v": 4, "k": 1,
                 "sketch_segments": 8, "verify_chunk": 8,
                 "candidate_chunk": 128, "warp": 0.5, "noise": 0.15,
                 "data_seed": 3, "precision": "float32", "chips": 1},
}
MIXES = {
    "tiny_walks": {"loop": "closed", "batch": 4, "queries": "random_walk",
                   "pool": 3, "warmup": 1, "check": 3},
    "tiny_dups": {"loop": "closed", "batch": 4, "queries": "store_noise",
                  "noise": 0.01, "pool": 3, "warmup": 1, "check": 3},
    "tiny_online": {"loop": "closed", "batch": 1, "queries": "test_split",
                    "warmup": 1, "check": "all"},
    "tiny_rows": {"loop": "closed", "batch": 4, "queries": "tiny_rows",
                  "warmup": 1, "check": 4},
}
# a query kind of its own: store rows in turn, reversed in time
QUERY_MODULES = {
    "tiny_rows": '''
import numpy as np


class Rows:
    def __init__(self, store, batch):
        self.store, self.b = store, batch

    def batch(self, i):
        n = self.store.shape[0]
        return self.store[(i * self.b + np.arange(self.b)) % n, ::-1]


def make(mix, cfg, data, seed):
    return Rows(data.store, mix["batch"])
''',
}
# cell -> (config, mix, the benchmark cell whose metrics it reports)
CELLS = {
    "tiny_rw.tiny_walks": ("tiny_rw", "tiny_walks", "paper_chip.rw_q32"),
    "tiny_rw.tiny_dups": ("tiny_rw", "tiny_dups", "paper_chip.neardup_q32"),
    "tiny_rw_k3.tiny_walks": ("tiny_rw_k3", "tiny_walks",
                              "paper_chip.rw_q32"),
    "tiny_rw.tiny_rows": ("tiny_rw", "tiny_rows", "paper_chip.rw_q32"),
    "tiny_ucr.tiny_online": ("tiny_ucr", "tiny_online",
                             "ucr_elec.online_1q"),
}
LIMITS = {"answer_gap": 1e-4}


def make(root: Path):
    """Write the tiny files under ``root``; return ``(spec, catalog)``.

    The spec is ``BENCHMARK.json`` with the tiny cells in place of the
    real ones, each reporting the metrics of the cell it stands for."""
    for kind, table in (("configs", CONFIGS), ("traffic", MIXES)):
        (root / kind).mkdir(parents=True, exist_ok=True)
        for name, body in table.items():
            (root / kind / f"{name}.json").write_text(json.dumps(body))
    (root / "queries").mkdir(exist_ok=True)
    for name, body in QUERY_MODULES.items():
        (root / "queries" / f"{name}.py").write_text(body)
    (root / "limits").mkdir(exist_ok=True)
    for cell in CELLS:
        (root / "limits" / f"{cell}.json").write_text(json.dumps(LIMITS))
    spec = copy.deepcopy(harness.load_spec())
    spec["workloads"] = [
        {"name": c, "config": cf, "traffic": mx, "chips": 1, "why": "test"}
        for c, (cf, mx, _) in CELLS.items()]
    for group in ("end_to_end", "per_layer"):
        for m in spec[group]:
            if "workloads" in m:
                m["workloads"] = [c for c, (_, _, real) in CELLS.items()
                                  if real in m["workloads"]]
    return spec, harness.Catalog(root, harness.BENCH)
