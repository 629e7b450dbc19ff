"""Datasets of the benchmark's traffic, as plain numpy records.

One general entry, ``dataset(traffic)``: the traffic file names its generator
(``generators/<name>.py``, found by name, with a ``generate(**params)``) and
carries every parameter and the ``data_seed``, so the dataset, and with it
every batch shape, is a function of the traffic file alone. ``--seed`` never
reaches this module. The pieces generators share are here: the radius graph
(``hydragnn_tpu``'s KD-tree route with its nearest-k cap, copied so that a
change to the program cannot change the traffic) and Lennard-Jones targets.

A record is a dict of numpy arrays: x, pos, senders, receivers, energy [1],
forces [n, 3], z. Built datasets are kept in one ``.npz`` under the
benchmark's cache directory, keyed by the generator's parameters.
"""

from __future__ import annotations

import gc
import hashlib
import importlib
import json
import os
from typing import Dict, List

import numpy as np

Record = Dict[str, np.ndarray]


def radius_graph(pos: np.ndarray, radius: float, max_neighbours: int):
    """Directed edges j -> i within ``radius``, nearest ``max_neighbours``
    incoming edges per receiver (distance ties broken by sender index)."""
    from scipy.spatial import cKDTree

    pos = np.asarray(pos, np.float64)
    pairs = cKDTree(pos).query_pairs(r=radius, output_type="ndarray")
    senders = np.concatenate([pairs[:, 0], pairs[:, 1]]).astype(np.int32)
    receivers = np.concatenate([pairs[:, 1], pairs[:, 0]]).astype(np.int32)
    if senders.size == 0:
        return senders, receivers
    d = np.linalg.norm(pos[senders] - pos[receivers], axis=1)
    order = np.lexsort((senders, d, receivers))
    recv_sorted = receivers[order]
    starts = np.r_[0, np.flatnonzero(np.diff(recv_sorted)) + 1]
    group = np.repeat(starts, np.diff(np.r_[starts, order.size]))
    rank = np.arange(order.size) - group
    keep = np.zeros(order.size, bool)
    keep[order[rank < max_neighbours]] = True
    return senders[keep], receivers[keep]


def symmetrize_edges(senders: np.ndarray, receivers: np.ndarray, n: int):
    """Every pair in both directions, sorted by (sender, receiver)."""
    key = np.concatenate(
        [senders.astype(np.int64) * n + receivers, receivers.astype(np.int64) * n + senders]
    )
    key = np.unique(key)
    return (key // n).astype(np.int32), (key % n).astype(np.int32)


def lj_targets(pos, senders, receivers, epsilon: float, sigma: float):
    """Lennard-Jones energy (half a pair energy per directed edge) and its
    exact forces over the edge list."""
    diff = pos[receivers] - pos[senders]
    r = np.linalg.norm(diff, axis=1)
    s6 = (sigma / r) ** 6
    s12 = s6**2
    energy = float(np.sum(0.5 * 4.0 * epsilon * (s12 - s6)))
    coef = 0.5 * 24.0 * epsilon * (2.0 * s12 - s6) / r**2
    forces = np.zeros_like(pos)
    np.add.at(forces, receivers, coef[:, None] * diff)
    np.add.at(forces, senders, -coef[:, None] * diff)
    return energy, forces


_FIELDS = ("x", "pos", "senders", "receivers", "energy", "forces", "z")


def _pack(records: List[Record]) -> Dict[str, np.ndarray]:
    flat = {f: np.concatenate([r[f] for r in records], axis=0) for f in _FIELDS}
    flat["n_nodes"] = np.asarray([r["x"].shape[0] for r in records], np.int64)
    flat["n_edges"] = np.asarray([r["senders"].shape[0] for r in records], np.int64)
    return flat


def _unpack(flat) -> List[Record]:
    n_off = np.r_[0, np.cumsum(flat["n_nodes"])]
    e_off = np.r_[0, np.cumsum(flat["n_edges"])]
    per_node = ("x", "pos", "forces", "z")
    out = []
    for i in range(len(flat["n_nodes"])):
        rec = {f: flat[f][n_off[i]:n_off[i + 1]] for f in per_node}
        rec["senders"] = flat["senders"][e_off[i]:e_off[i + 1]]
        rec["receivers"] = flat["receivers"][e_off[i]:e_off[i + 1]]
        rec["energy"] = flat["energy"][i:i + 1]
        out.append(rec)
    return out


def dataset(traffic: dict, cache_dir: str, scale: float = 1.0) -> List[Record]:
    """The traffic file's dataset, from the cache when it is there.
    ``scale`` < 1 shrinks the graph count (CPU rehearsals only)."""
    params = dict(traffic["generator_params"])
    if scale != 1.0:
        params["number_configurations"] = max(int(params["number_configurations"] * scale), 8)
    params["seed"] = int(traffic["data_seed"])
    key = json.dumps({"generator": traffic["generator"], **params}, sort_keys=True)
    path = os.path.join(
        cache_dir, f"{traffic['generator']}-{hashlib.sha256(key.encode()).hexdigest()[:16]}.npz"
    )
    if os.path.exists(path):
        with np.load(path) as z:
            return _unpack({k: z[k] for k in z.files})
    generator = importlib.import_module(f"generators.{traffic['generator']}")
    flat = _pack(generator.generate(**params))
    os.makedirs(cache_dir, exist_ok=True)
    tmp = path + ".tmp.npz"
    np.savez(tmp, **flat)
    os.replace(tmp, path)
    # the run that builds the dataset feeds the loader from the same memory
    # layout as the runs that find it cached (slices of flat arrays; the
    # generator's per-graph arrays and garbage are dropped first): a cell's
    # first run in a checkout read 1.6% low without this
    gc.collect()
    return _unpack(flat)
