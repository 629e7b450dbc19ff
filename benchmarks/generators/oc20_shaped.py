"""OC20-S2EF-shaped slabs (a copy of ``hydragnn_tpu/data/synthetic.py``
``oc20_shaped_dataset``: the same edges, energies and forces)."""

from __future__ import annotations

from typing import List

import numpy as np

import datagen


def generate(number_configurations, mean_atoms, min_atoms, max_atoms, radius,
             max_neighbours, lattice_constant, jitter, seed) -> List[datagen.Record]:
    """Lognormal sizes clipped to [min, max], FCC
    packing at a metallic lattice constant, capped ~20-degree radius graphs,
    LJ energy per atom (graph) and forces (node); x = [Z, x, y, z]."""
    rng = np.random.default_rng(seed)
    mu = np.log(mean_atoms) - 0.35**2 / 2.0
    zs = np.array([1, 6, 8, 13, 26, 29, 46, 78])
    a = lattice_constant
    basis = np.array([[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]], np.float64)
    sigma = (a / np.sqrt(2.0)) / 2.0 ** (1.0 / 6.0)
    out: List[datagen.Record] = []
    for _ in range(int(number_configurations)):
        n = int(np.clip(rng.lognormal(mu, 0.35), min_atoms, max_atoms))
        side = int(np.ceil((n / 4.0) ** (1.0 / 3.0))) + 1
        ax = np.arange(side, dtype=np.float64)
        zz, yy, xx = np.meshgrid(ax, ax, ax, indexing="ij")
        cells = np.stack([xx.ravel(), yy.ravel(), zz.ravel()], axis=1)
        pos = (cells[:, None, :] + basis[None, :, :]).reshape(-1, 3) * a
        pos = pos[:n] + rng.uniform(-jitter, jitter, (n, 3))
        s, r = datagen.radius_graph(pos, radius, max_neighbours)
        s, r = datagen.symmetrize_edges(s, r, n)
        energy, forces = datagen.lj_targets(pos, s, r, 1.0, sigma)
        z = rng.choice(zs, size=n).astype(np.int32)
        out.append({
            "x": np.concatenate([z[:, None].astype(np.float32), pos.astype(np.float32)], axis=1),
            "pos": pos.astype(np.float32), "senders": s, "receivers": r,
            "energy": np.asarray([energy / n], np.float32),
            "forces": forces.astype(np.float32), "z": z,
        })
    return out
