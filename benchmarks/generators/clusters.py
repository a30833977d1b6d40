"""The one general traffic generator: seeded synthetic atomistic structures.

A traffic file names this generator and gives it parameters; QM9-like
molecules and OC20-like clusters are two parameter sets, not two programs.
``make(seed, **params)`` returns three lists of plain records (dicts of
numpy arrays), one per split. Nothing of the program is imported: the
neighbour search is a dense numpy one, so the edges the program is fed and
the edges the plain reference sees come from the yardstick.

What the seed changes and what it does not. The geometry (atom counts,
positions, hence edges) comes from the traffic file's ``structure_seed``
alone, so every ``--seed`` gives the program the same multiset of (atoms,
edges) per split: the same work, the same padded shapes, the same compiled
programs. Species, labels and the order of the structures inside each
split come from ``--seed`` (and so do the program's weights and its
shuffling, which the driver seeds).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = 4
SPLITS = ("train", "val", "test")


def atom_counts(n_graphs: int, atoms: dict) -> np.ndarray:
    """``n_graphs`` atom counts from a histogram, without randomness.

    ``atoms`` is ``{"edges": [e0, e1, ...], "weights": [w0, ...]}``: bin i
    holds the counts e_i .. e_{i+1}-1 with total weight w_i, uniform inside.
    The counts are the histogram's quantiles at (i + 0.5) / n_graphs.
    """
    edges = np.asarray(atoms["edges"], dtype=np.int64)
    weights = np.asarray(atoms["weights"], dtype=np.float64)
    if len(edges) != len(weights) + 1 or np.any(np.diff(edges) <= 0):
        raise ValueError("atoms: need len(edges) == len(weights) + 1, rising")
    values = np.concatenate(
        [np.arange(lo, hi) for lo, hi in zip(edges[:-1], edges[1:])]
    )
    mass = np.concatenate(
        [
            np.full(hi - lo, w / (hi - lo))
            for lo, hi, w in zip(edges[:-1], edges[1:], weights)
        ]
    )
    cdf = np.cumsum(mass) / mass.sum()
    q = (np.arange(n_graphs) + 0.5) / n_graphs
    return values[np.searchsorted(cdf, q, side="left").clip(0, len(values) - 1)]


def split_sizes(n_graphs: int, split: list) -> list:
    """Graphs per split; the last split takes the remainder."""
    sizes = [int(round(n_graphs * f)) for f in split[:-1]]
    return sizes + [n_graphs - sum(sizes)]


def _deal(counts: np.ndarray, sizes: list) -> list:
    """Deal the sorted counts to the splits so each gets the same shape of
    histogram: a fixed pseudo-random order (seed 0), then cut."""
    order = np.random.default_rng(0).permutation(len(counts))
    dealt = counts[order]
    cuts = np.cumsum(sizes)[:-1]
    return np.split(dealt, cuts)


def _neighbours(pos: np.ndarray, cutoff: float, max_neighbours: int):
    """Directed edges (sender, receiver) of ``m`` structures of ``n`` atoms.

    pos is [m, n, 3]. A receiver keeps its ``max_neighbours`` nearest
    senders inside the cutoff. Returns (graph, sender, receiver, length)
    sorted by graph, then receiver, then sender.
    """
    m, n, _ = pos.shape
    diff = pos[:, :, None, :] - pos[:, None, :, :]
    d2 = np.einsum("mijk,mijk->mij", diff, diff)
    idx = np.arange(n)
    d2[:, idx, idx] = np.inf
    within = d2 < cutoff * cutoff
    if n - 1 > max_neighbours:
        kth = np.partition(d2, max_neighbours - 1, axis=-1)[
            ..., max_neighbours - 1
        ]
        within &= d2 <= kth[..., None]
    g, rcv, snd = np.nonzero(within)  # row = receiver, column = sender
    return g, snd, rcv, np.sqrt(d2[g, rcv, snd])


def _structures(geo, rng, counts, params, species_energy, want_forces):
    """All structures of one split with their raw labels, grouped by atom
    count for the dense neighbour search, returned in the order of
    ``counts``.

    Labels are smooth functions of species and geometry: E = mean_i e(z_i)
    + 0.3 / n * sum_edges exp(-d), and, per atom, the pull of its
    neighbours under that pair term, sum_j exp(-d_ij) * u_ij.
    """
    cutoff = float(params["cutoff"])
    max_nb = int(params["max_neighbours"])
    vol = float(params["volume_per_atom"])
    n_species = int(params["species"])
    records = [None] * len(counts)

    def chunk_records(n, where, pos, z):
        """One chunk of structures of ``n`` atoms: edges and labels."""
        k = len(where)
        g, snd, rcv, length = _neighbours(pos, cutoff, max_nb)
        w = np.exp(-length)
        energy = (
            species_energy[z].mean(axis=1)
            + 0.3 * np.bincount(g, w, minlength=k) / n
        )
        if want_forces:
            unit = (pos[g, snd] - pos[g, rcv]) / np.maximum(length, 1e-9)[:, None]
            flat = g * n + rcv
            force = np.stack(
                [
                    np.bincount(flat, w * unit[:, c], minlength=k * n)
                    for c in range(3)
                ],
                axis=1,
            ).reshape(k, n, 3)
        snd, rcv = snd.astype(np.int32), rcv.astype(np.int32)
        starts = np.searchsorted(g, np.arange(k + 1))
        for j in range(k):
            a, b = starts[j], starts[j + 1]
            rec = {
                "z": z[j],
                "pos": pos[j],
                "senders": snd[a:b],
                "receivers": rcv[a:b],
                "energy": energy[j],
            }
            if want_forces:
                rec["forces"] = force[j]
            records[where[j]] = rec

    # every draw is made here, in one fixed order; the chunks' arithmetic,
    # which draws nothing, then runs on a few threads (numpy drops the GIL)
    chunks = []
    for n in np.unique(counts):
        where = np.nonzero(counts == n)[0]
        m = len(where)
        side = (n * vol) ** (1.0 / 3.0)
        pos = geo.uniform(0.0, side, size=(m, n, 3))
        z = rng.integers(0, n_species, size=(m, n))
        # the dense search holds m * n * n * 3 doubles: go in chunks
        chunk = max(1, int(4e6 // (n * n)))
        for lo in range(0, m, chunk):
            hi = min(m, lo + chunk)
            chunks.append((n, where[lo:hi], pos[lo:hi], z[lo:hi]))
    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        list(pool.map(lambda c: chunk_records(*c), chunks))
    return records


def make(
    seed: int,
    *,
    n_graphs: int,
    atoms: dict,
    volume_per_atom: float,
    species: int,
    cutoff: float,
    max_neighbours: int,
    structure_seed: int,
    forces: bool = False,
    split=(0.8, 0.1, 0.1),
) -> dict:
    """Three splits of records from ``seed``.

    Each record: ``z`` [n] int, ``pos`` [n, 3], ``senders`` / ``receivers``
    [e] int32, ``energy`` [1] and, with ``forces``, ``forces`` [n, 3]; all
    floats float32, labels standardized over the whole set.
    """
    params = dict(
        volume_per_atom=volume_per_atom, species=species, cutoff=cutoff,
        max_neighbours=max_neighbours,
    )
    geo = np.random.default_rng(int(structure_seed))
    rng = np.random.default_rng(int(seed))
    species_energy = rng.normal(size=species)
    counts = _deal(atom_counts(n_graphs, atoms), split_sizes(n_graphs, list(split)))
    splits = [
        _structures(geo, rng, c, params, species_energy, forces)
        for c in counts
    ]
    splits = [[s[i] for i in rng.permutation(len(s))] for s in splits]
    everything = [r for s in splits for r in s]
    energy = np.array([r["energy"] for r in everything])
    e_mean, e_std = energy.mean(), energy.std()
    f_std = (
        np.concatenate([r["forces"] for r in everything]).std()
        if forces else 1.0
    )
    for r in everything:
        r["energy"] = np.array([(r["energy"] - e_mean) / e_std], np.float32)
        r["pos"] = r["pos"].astype(np.float32)
        if forces:
            r["forces"] = (r["forces"] / f_std).astype(np.float32)
    return dict(zip(SPLITS, splits))


def describe(splits: dict) -> dict:
    """Mean sizes, for the run's log."""
    out = {}
    for name, recs in splits.items():
        nodes = np.array([len(r["z"]) for r in recs])
        edges = np.array([len(r["senders"]) for r in recs])
        out[name] = {
            "graphs": len(recs),
            "mean_atoms": float(nodes.mean()),
            "mean_edges": float(edges.mean()),
            "max_atoms": int(nodes.max()),
            "max_edges": int(edges.max()),
        }
    return out
