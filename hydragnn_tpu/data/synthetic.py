"""Deterministic synthetic graph dataset for CI-grade accuracy tests.

Behavioral equivalent of the reference's test fixture generator
(tests/deterministic_graph_data.py:20-66 and create_configuration :68-220):
BCC-lattice configurations with random per-node types and closed-form targets

    out1 = knn_smooth(type)        (k-nearest-neighbour average, simulating MP)
    out2 = out1**2 + type
    out3 = out1**3
    graph_target = sum(out1) + sum(out2) + sum(out3)

The node feature *table* exposed per node is ``[type, out2, out3]`` matching
the reference CI configs' column selection (tests/inputs/ci.json node_features
column_index [0, 6, 7]); the single graph feature is the total sum.
``linear_only=True`` mirrors the reference flag: out1 = type, graph target =
sum(out1).
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np

from .graph import Graph
from .neighbors import radius_graph, radius_graph_pbc


def knn_average(pos: np.ndarray, values: np.ndarray, k: int) -> np.ndarray:
    """Average of the k nearest samples (incl. self), like KNeighborsRegressor."""
    from scipy.spatial import cKDTree

    tree = cKDTree(pos)
    _, idx = tree.query(pos, k=k)
    if k == 1:
        idx = idx[:, None]
    return values[idx].mean(axis=1)


def deterministic_graph_dataset(
    number_configurations: int = 500,
    unit_cell_x_range: Sequence[int] = (1, 3),
    unit_cell_y_range: Sequence[int] = (1, 3),
    unit_cell_z_range: Sequence[int] = (1, 2),
    number_types: int = 3,
    types: Optional[Sequence[int]] = None,
    number_neighbors: int = 2,
    linear_only: bool = False,
    radius: float = 2.0,
    max_neighbours: int = 100,
    seed: int = 97,
) -> List[Graph]:
    """Generate BCC configurations with closed-form targets as ``Graph`` list.

    Unlike the reference (which writes LSMS-style text files and re-reads them
    through the raw loader, tests/test_graphs.py:91-126) this builds the graphs
    in memory; the text round-trip is exercised separately by the raw-loader
    tests.
    """
    if types is None:
        types = list(range(number_types))
    rng = np.random.default_rng(seed)
    graphs: List[Graph] = []
    for _ in range(number_configurations):
        uc = (
            rng.integers(unit_cell_x_range[0], unit_cell_x_range[1]),
            rng.integers(unit_cell_y_range[0], unit_cell_y_range[1]),
            rng.integers(unit_cell_z_range[0], unit_cell_z_range[1]),
        )
        graphs.append(
            _configuration(rng, uc, types, number_neighbors, linear_only, radius, max_neighbours)
        )
    return graphs


def bcc_positions(uc_x: int, uc_y: int, uc_z: int) -> np.ndarray:
    """Body-centered-cubic positions: corner + center atom per unit cell."""
    corners = np.array(
        [(x, y, z) for x in range(uc_x) for y in range(uc_y) for z in range(uc_z)],
        np.float64,
    )
    pos = np.empty((2 * corners.shape[0], 3), np.float64)
    pos[0::2] = corners
    pos[1::2] = corners + 0.5
    return pos


def _configuration(rng, uc, types, number_neighbors, linear_only, radius, max_neighbours):
    pos = bcc_positions(*uc)
    n = pos.shape[0]
    node_type = rng.integers(min(types), max(types) + 1, (n, 1)).astype(np.float64)

    if linear_only:
        out1 = node_type.copy()
    else:
        out1 = knn_average(pos, node_type, number_neighbors)
    out2 = out1**2 + node_type
    out3 = out1**3

    if linear_only:
        total = out1.sum(keepdims=False)
        x_table = node_type.astype(np.float32)
    else:
        total = out1.sum() + out2.sum() + out3.sum()
        # columns as selected by ci.json: [type, out2, out3]
        x_table = np.concatenate([node_type, out2, out3], axis=1).astype(np.float32)

    senders, receivers = radius_graph(pos, radius, max_neighbours)
    return Graph(
        x=x_table,
        pos=pos.astype(np.float32),
        senders=senders,
        receivers=receivers,
        graph_y=np.asarray([float(total)], np.float32),
        z=node_type[:, 0].astype(np.int32),
    )


def grow_molecule(rng, n: int, lo: float = 1.0, hi: float = 1.9,
                  step: float = 1.5, max_tries: int = 8000) -> np.ndarray:
    """Bonded-molecule geometry by rejection sampling at covalent distances:
    each new atom anchors off a random placed atom and must land within
    [lo, hi] of its nearest neighbor. Shared by the molecular generators
    (qm9 here; ani1x/qm7x/transition1x/omol25/uv in data/shaped.py)."""
    pos = np.zeros((n, 3))
    placed, tries = 1, 0
    while placed < n and tries < max_tries:
        tries += 1
        anchor = pos[int(rng.integers(placed))]
        cand = anchor + rng.normal(0.0, 1.0, 3) * step
        d = np.linalg.norm(pos[:placed] - cand, axis=1)
        if d.min() > lo and d.min() < hi:
            pos[placed] = cand
            placed += 1
    return pos[:placed]


def supercell_frac(basis: np.ndarray, reps: int) -> np.ndarray:
    """Fractional coordinates of a ``reps^3`` supercell of ``basis`` (one
    row per atom, x-major cell order) — shared by the periodic generators
    (mptrj/alexandria/omat24/eam)."""
    cells = np.array(
        [(x, y, z) for x in range(reps) for y in range(reps)
         for z in range(reps)],
        np.float64,
    )
    return (cells[:, None, :] + basis[None, :, :]).reshape(-1, 3) / reps


def _symmetrize_edges(senders: np.ndarray, receivers: np.ndarray):
    """Every pair must appear in both directions or the 0.5-per-edge energy
    sum and the receiver-side force accumulation break Newton's third law."""
    pairs = set(zip(senders.tolist(), receivers.tolist()))
    pairs |= {(i, j) for (j, i) in pairs}
    s, r = zip(*sorted(pairs))
    return np.asarray(s, np.int32), np.asarray(r, np.int32)


def _lj_targets(pos, senders, receivers, epsilon: float, sigma: float,
                shifts=None):
    """Closed-form Lennard-Jones total energy and per-atom forces over the
    edge list. Each pair of a symmetric list appears twice, so half the
    pair energy is charged per edge; forces are the exact gradient of that
    edge-restricted energy (half accumulated on each endpoint), so
    F = -dE/dpos holds for ANY edge list — including ones where a neighbor
    cap dropped one direction of a pair. ``shifts`` makes the displacements
    PBC-aware (minimum-image convention of the graph)."""
    diff = pos[receivers] - pos[senders]  # r_i - r_j for edge j->i
    if shifts is not None:
        diff = diff - shifts
    r = np.linalg.norm(diff, axis=1)
    s6 = (sigma / r) ** 6
    s12 = s6**2
    energy = float(np.sum(0.5 * 4.0 * epsilon * (s12 - s6)))
    # dE/dpos of the per-edge half energies: each edge pushes both endpoints
    coef = 0.5 * 24.0 * epsilon * (2.0 * s12 - s6) / r**2
    forces = np.zeros_like(pos)
    np.add.at(forces, receivers, coef[:, None] * diff)
    np.add.at(forces, senders, -coef[:, None] * diff)
    return energy, forces


def oc20_shaped_dataset(
    number_configurations: int = 64,
    mean_atoms: float = 73.0,
    min_atoms: int = 20,
    max_atoms: int = 225,
    radius: float = 5.0,
    max_neighbours: int = 20,
    lattice_constant: float = 3.8,
    jitter: float = 0.12,
    seed: int = 42,
) -> List[Graph]:
    """OC20-S2EF-*shaped* workload: catalyst-slab-like configurations whose
    node-count and degree distributions match the real benchmark target
    (PERF.md; the dataset itself cannot be downloaded in this
    image). Sizes are lognormal with mean ~73 atoms clipped to [20, 225]
    (the OC20 slab range); positions are FCC-packed at a metallic lattice
    constant so ``radius``/``max_neighbours`` produce the capped ~20-degree
    graphs of the SC25 production config
    (reference: examples/multibranch/multibranch_GFM260_SC25.json).
    Targets are physically-consistent LJ energies (graph) and forces (node);
    the node feature table is [Z, x, y, z] (input_dim 4, matching the SC25
    Variables_of_interest).
    """
    rng = np.random.default_rng(seed)
    mu = np.log(mean_atoms) - 0.35**2 / 2.0
    zs = np.array([1, 6, 8, 13, 26, 29, 46, 78])  # adsorbate + catalyst metals
    a = lattice_constant
    # FCC basis
    basis = np.array(
        [[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]], np.float64
    )
    d_nn = a / np.sqrt(2.0)
    sigma = d_nn / 2.0 ** (1.0 / 6.0)  # LJ minimum at the nn distance
    graphs: List[Graph] = []
    for _ in range(number_configurations):
        n = int(np.clip(rng.lognormal(mu, 0.35), min_atoms, max_atoms))
        side = int(np.ceil((n / 4.0) ** (1.0 / 3.0))) + 1
        cells = np.array(
            [(x, y, z) for z in range(side) for y in range(side) for x in range(side)],
            np.float64,
        )
        pos = (cells[:, None, :] + basis[None, :, :]).reshape(-1, 3) * a
        pos = pos[:n] + rng.uniform(-jitter, jitter, (n, 3))
        senders, receivers = radius_graph(pos, radius, max_neighbours)
        senders, receivers = _symmetrize_edges(senders, receivers)
        energy, forces = _lj_targets(pos, senders, receivers, 1.0, sigma)
        z = rng.choice(zs, size=n).astype(np.int32)
        x = np.concatenate([z[:, None].astype(np.float32), pos.astype(np.float32)], axis=1)
        graphs.append(
            Graph(
                x=x,
                pos=pos.astype(np.float32),
                senders=senders,
                receivers=receivers,
                graph_targets={"energy": np.asarray([energy / n], np.float32)},
                node_targets={"forces": forces.astype(np.float32)},
                z=z,
            )
        )
    return graphs


def md17_shaped_dataset(
    number_configurations: int = 256,
    jitter: float = 0.12,
    radius: float = 5.0,
    max_neighbours: int = 32,
    seed: int = 7,
) -> List[Graph]:
    """MD17-(aspirin)-*shaped* workload: one fixed 21-atom molecule (the
    aspirin C9H8O4 composition) whose configurations are thermal perturbations
    of a common template — the structure of the real MD17 benchmark
    (PERF.md; reference: examples/md17). Targets are LJ energies/forces
    evaluated on each perturbed geometry, so force MAE measured on this task
    exercises exactly the energy+force training path at MD17's scale.
    """
    rng = np.random.default_rng(seed)
    z = np.array([6] * 9 + [1] * 8 + [8] * 4, np.int32)  # C9 H8 O4
    n = z.shape[0]
    # fixed template: min-distance rejection sampling inside a molecule-size ball
    template = np.zeros((n, 3))
    placed = 1
    while placed < n:
        cand = rng.uniform(-3.2, 3.2, 3)
        if np.linalg.norm(cand) > 3.4:
            continue
        if np.min(np.linalg.norm(template[:placed] - cand, axis=1)) > 1.25:
            template[placed] = cand
            placed += 1
    graphs: List[Graph] = []
    # Boltzmann-style acceptance (round 5): thermal sampling never visits
    # the LJ repulsive wall, but isotropic jitter does — measured on the
    # unfiltered generator, 17% of draws contained a near-contact pair with
    # per-atom |F| > 10 (up to ~250, vs a 0.59 mean |component|). Those
    # samples dominate any force objective: across a recipe sweep NO model
    # family learned forces (corr ~0.02). Rejecting draws whose max
    # per-atom |force| exceeds ``force_cap`` keeps ~3/4 of draws and
    # restores the near-equilibrium force distribution real MD17
    # trajectories have (a Boltzmann ensemble suppresses the wall
    # exponentially). Deterministic: same rng stream, draws until accepted.
    force_cap = 5.0
    attempts = 0
    max_attempts = 100 * number_configurations
    while len(graphs) < number_configurations:
        attempts += 1
        if attempts > max_attempts:
            # a jitter large enough to put ~every draw inside the LJ wall
            # must fail loudly, not spin forever
            raise ValueError(
                f"md17_shaped_dataset: acceptance rate "
                f"{len(graphs)}/{attempts} too low for jitter={jitter} "
                f"(force cap {force_cap}); reduce jitter"
            )
        pos = template + rng.normal(0.0, jitter, (n, 3))
        senders, receivers = radius_graph(pos, radius, max_neighbours)
        senders, receivers = _symmetrize_edges(senders, receivers)
        energy, forces = _lj_targets(pos, senders, receivers, 0.2, 1.1)
        if float(np.abs(forces).max()) > force_cap:
            continue
        graphs.append(
            Graph(
                x=z[:, None].astype(np.float32),
                pos=pos.astype(np.float32),
                senders=senders,
                receivers=receivers,
                graph_targets={"energy": np.asarray([energy], np.float32)},
                node_targets={"forces": forces.astype(np.float32)},
                z=z.copy(),
            )
        )
    # reference-energy centering (forces invariant)
    e_mean = float(np.mean([g.graph_targets["energy"][0] for g in graphs]))
    for g in graphs:
        g.graph_targets["energy"] = (g.graph_targets["energy"] - e_mean).astype(
            np.float32
        )
    return graphs


def qm9_shaped_dataset(
    number_configurations: int = 1000,
    radius: float = 7.0,
    max_neighbours: int = 5,
    seed: int = 0,
) -> List[Graph]:
    """QM9-*shaped* workload: small organic molecules with the size and
    composition statistics of the real QM9 benchmark (3-29 atoms, elements
    H/C/N/O/F, ~18 atoms on average), which cannot be downloaded in this
    image. Mirrors the reference example's data contract
    (examples/qm9/qm9.py:20-34): node feature table = [Z], graph feature
    table = [free_energy per atom] — a physically-consistent closed-form
    LJ energy so the target is learnable from geometry.
    """
    rng = np.random.default_rng(seed)
    graphs: List[Graph] = []
    heavy_choices = np.array([6, 7, 8, 9])  # C N O F
    heavy_probs = np.array([0.72, 0.12, 0.13, 0.03])
    for _ in range(number_configurations):
        n_heavy = int(rng.integers(1, 10))  # QM9: up to 9 heavy atoms
        # QM9's smallest molecules have 3 atoms (e.g. water): keep >= 2
        # hydrogens on a lone heavy atom so every graph has edges
        n_h = int(np.clip(rng.poisson(1.3 * n_heavy), 2 if n_heavy < 2 else 0, 20))
        z = np.concatenate(
            [
                rng.choice(heavy_choices, size=n_heavy, p=heavy_probs),
                np.ones(n_h, np.int64),
            ]
        ).astype(np.int32)
        n = z.shape[0]
        pos = grow_molecule(rng, n)
        z = z[: pos.shape[0]]
        n = pos.shape[0]
        senders, receivers = radius_graph(pos, radius, max_neighbours)
        senders, receivers = _symmetrize_edges(senders, receivers)
        energy, _ = _lj_targets(pos, senders, receivers, 0.15, 1.2)
        graphs.append(
            Graph(
                x=z[:, None].astype(np.float32),
                pos=pos.astype(np.float32),
                senders=senders,
                receivers=receivers,
                graph_y=np.asarray([energy / n], np.float32),
                z=z.copy(),
            )
        )
    return graphs


def mptrj_shaped_dataset(
    number_configurations: int = 128,
    radius: float = 5.0,
    max_neighbours: int = 20,
    seed: int = 23,
) -> List[Graph]:
    """MPTrj-*shaped* workload: perturbed periodic crystals with varied
    lattices, compositions, and cell sizes — the structure of the
    Materials-Project-trajectory benchmark the reference trains MACE/GFM
    models on (reference: examples/mptrj; the real download is unavailable
    in this image). Each sample is a BCC/FCC/SC supercell with a random
    binary composition, thermal rattling, PBC radius-graph edges with shift
    vectors, and physically-consistent LJ energy (graph, per atom) and
    force (node) targets evaluated on the periodic displacements.
    """
    rng = np.random.default_rng(seed)
    bases = {
        "sc": np.zeros((1, 3)),
        "bcc": np.array([[0, 0, 0], [0.5, 0.5, 0.5]], np.float64),
        "fcc": np.array(
            [[0, 0, 0], [0.5, 0.5, 0], [0.5, 0, 0.5], [0, 0.5, 0.5]], np.float64
        ),
    }
    element_pool = np.array([3, 8, 13, 14, 22, 26, 28, 29])  # Li O Al Si Ti Fe Ni Cu
    graphs: List[Graph] = []
    for _ in range(number_configurations):
        kind = ("sc", "bcc", "fcc")[int(rng.integers(3))]
        basis = bases[kind]
        a = float(rng.uniform(3.4, 4.4))
        reps = int(rng.integers(2, 4))
        frac = supercell_frac(basis, reps)
        cell = np.diag([a * reps] * 3)
        pos = frac @ cell + rng.normal(0.0, 0.08, (frac.shape[0], 3))
        n = pos.shape[0]
        zs = rng.choice(element_pool, size=2, replace=False)
        z = np.where(rng.random(n) < rng.uniform(0.2, 0.8), zs[0], zs[1]).astype(
            np.int32
        )
        senders, receivers, shifts = radius_graph_pbc(
            pos, cell, radius, max_neighbours
        )
        # LJ on the shift-corrected periodic displacements, via the shared
        # helper whose halving/receiver-only accumulation keeps F = -dE/dpos
        # exact on symmetric edge lists
        sigma = a / np.sqrt(2.0) / 2.0 ** (1.0 / 6.0)
        energy, forces = _lj_targets(
            pos, senders, receivers, 0.5, sigma, shifts=shifts
        )
        graphs.append(
            Graph(
                x=z[:, None].astype(np.float32),
                pos=pos.astype(np.float32),
                senders=senders,
                receivers=receivers,
                edge_shifts=shifts.astype(np.float32),
                cell=cell.astype(np.float32),
                graph_targets={"energy": np.asarray([energy / n], np.float32)},
                node_targets={"forces": forces.astype(np.float32)},
                z=z.copy(),
            )
        )
    return graphs


def lennard_jones_dataset(
    number_configurations: int = 200,
    supercell: Sequence[int] = (2, 2, 2),
    spacing: float = 1.2,
    jitter: float = 0.08,
    radius: float = 2.5,
    max_neighbours: int = 32,
    epsilon: float = 1.0,
    sigma: float = 1.0,
    seed: int = 17,
    center_energies: bool = True,
) -> List[Graph]:
    """Perturbed-lattice configurations with exact Lennard-Jones energies and
    analytic forces, for energy+force (``compute_grad_energy``) training.

    Behavioral analog of the reference's ``examples/LennardJones`` dataset
    (examples/LennardJones/LJ_data.py): graph target ``energy`` (total LJ
    energy within the cutoff) and node target ``forces`` (−∇E, closed form).

    ``center_energies`` subtracts the dataset-mean per-atom energy (the
    standard atomic-reference-energy shift; forces are invariant to it).
    """
    rng = np.random.default_rng(seed)
    graphs: List[Graph] = []
    for _ in range(number_configurations):
        base = np.array(
            [
                (x, y, z)
                for x in range(supercell[0])
                for y in range(supercell[1])
                for z in range(supercell[2])
            ],
            np.float64,
        )
        pos = base * spacing + rng.uniform(-jitter, jitter, base.shape)
        senders, receivers = radius_graph(pos, radius, max_neighbours)
        senders, receivers = _symmetrize_edges(senders, receivers)
        energy, forces = _lj_targets(pos, senders, receivers, epsilon, sigma)
        graphs.append(
            Graph(
                x=np.ones((pos.shape[0], 1), np.float32),
                pos=pos.astype(np.float32),
                senders=senders,
                receivers=receivers,
                graph_targets={"energy": np.asarray([energy], np.float32)},
                node_targets={"forces": forces.astype(np.float32)},
                z=np.ones((pos.shape[0],), np.int32),
            )
        )
    if center_energies:
        e_per_atom = float(
            np.mean(
                [g.graph_targets["energy"][0] / g.num_nodes for g in graphs]
            )
        )
        for g in graphs:
            g.graph_targets["energy"] = (
                g.graph_targets["energy"] - e_per_atom * g.num_nodes
            ).astype(np.float32)
    return graphs


def packed_documents_dataset(
    number_configurations: int,
    median_tokens: float,
    sigma: float,
    min_tokens: int,
    max_tokens: int,
    vocab_size: int,
    zipf_exponent: float = 1.1,
    seed: int = 0,
) -> List[Graph]:
    """Documents as graphs for the decoder stack (models/zaya.py): lognormal
    lengths clipped to ``[min_tokens, max_tokens]``, ids Zipf over
    ``vocab_size``, p(rank) ~ rank ** -exponent (repeated ids route alike:
    uneven expert load). Node id in
    ``z`` (int32) and ``x`` (float32 column); ``pos = [index in document,
    document number, 0]``; chain edges ``t-1 -> t``. The targets are carried
    for the loader's sake and read by no head."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, vocab_size + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(zipf_exponent))
    cdf /= cdf[-1]
    graphs = []
    for doc in range(int(number_configurations)):
        n = int(np.clip(rng.lognormal(np.log(median_tokens), sigma), min_tokens, max_tokens))
        ids = np.searchsorted(cdf, rng.random(n)).astype(np.int32)
        idx = np.arange(n, dtype=np.float32)
        graphs.append(Graph(
            x=ids[:, None].astype(np.float32),
            pos=np.stack([idx, np.full(n, doc, np.float32), np.zeros(n, np.float32)], axis=1),
            senders=np.arange(0, n - 1, dtype=np.int32),
            receivers=np.arange(1, n, dtype=np.int32),
            z=ids,
            graph_targets={"energy": np.zeros((1,), np.float32)},
            node_targets={"forces": np.zeros((n, 3), np.float32)},
        ))
    return graphs
