"""Deterministic shared PadSpec schedules for multi-device stacking.

Under the dp / multibranch schemes every device sub-batch of one
optimizer step is stacked into a ``[D, ...]`` array, so all sub-batches
of that step must share one padded shape. A fixed worst-case spec
satisfies that trivially but pays worst-case padding on every step;
these schedules instead give each STEP the smallest bucketed spec
covering all of its sub-batches — computed purely from per-sample size
metadata, identically on every host process. The cross-process
determinism is load-bearing: under GSPMD a batch is ONE global array
(``jax.make_array_from_process_local_data`` requires every process to
pass the same global shape), so a step's spec can never be derived from
one process's local batches alone.

Reference parity: ``HYDRAGNN_USE_VARIABLE_GRAPH_SIZE`` applies under
DDP in the reference (hydragnn/utils/input_config_parsing/
config_utils.py:29); there each rank pads independently because NCCL
only moves gradients. Here the schedule plays that role for the
global-array layout.

Compile-count bounding mirrors the single-scheme loader: distinct
bucketed specs are counted as the schedule is consumed, and once the
count exceeds twice the bucket budget every later step takes the
worst-case spec — one final compile, bounded forever after, and the
clamp point is itself deterministic across processes.
"""

from __future__ import annotations

from typing import Callable, Iterator, List, Optional, Sequence

import numpy as np

from hydragnn_tpu.data.graph import PackSpec, PadSpec, bucket_size


def epoch_batch_indices(
    n: int,
    batch_size: int,
    *,
    shuffle: bool,
    seed: int,
    epoch: int,
    num_samples: Optional[int] = None,
    drop_last: bool = False,
) -> Iterator[np.ndarray]:
    """Index arrays of each batch for one epoch — the single source of
    batch order shared by ``GraphLoader`` and the spec schedules (a
    schedule that disagreed with the loader's actual order would emit
    specs too small for the real batches). Seed-sequence keyed by
    (seed, epoch): deterministic per epoch."""
    rng = np.random.default_rng((seed, epoch))
    if num_samples is not None:
        order = rng.choice(n, size=num_samples, replace=num_samples > n)
    else:
        order = np.arange(n)
        if shuffle:
            rng.shuffle(order)
    for start in range(0, len(order), batch_size):
        idx = order[start : start + batch_size]
        if drop_last and len(idx) < batch_size:
            return
        yield idx


def batch_size_rows(
    node_sizes: np.ndarray, edge_sizes: np.ndarray, index_batches
) -> np.ndarray:
    """[n_batches, 3] int array of (nodes incl. one pad slot, edges,
    graphs incl. one pad slot) per batch — THE row contract every
    schedule and loader shares (collate guarantees at least one padding
    node and one padding graph slot, graph.PadSpec.for_samples)."""
    rows = [
        (int(node_sizes[idx].sum()) + 1, int(edge_sizes[idx].sum()), len(idx) + 1)
        for idx in index_batches
    ]
    return np.asarray(rows, np.int64).reshape(-1, 3)


def dataset_size_arrays(dataset) -> tuple:
    """Per-sample (node, edge) counts as int64 arrays. Containers with a
    header index (BinDataset) answer without payload reads; otherwise
    one scan, cached on the dataset object."""
    sizes = getattr(dataset, "sample_sizes", None)
    if callable(sizes):
        n, e = sizes()
        return (
            np.asarray(n, dtype=np.int64),
            np.asarray(e, dtype=np.int64),
        )
    cached = getattr(dataset, "_cached_sample_sizes", None)
    if cached is not None:
        return cached
    n = np.array([s.num_nodes for s in dataset], dtype=np.int64)
    e = np.array([s.num_edges for s in dataset], dtype=np.int64)
    try:
        dataset._cached_sample_sizes = (n, e)
    except (AttributeError, TypeError):
        pass
    return n, e


def ladder_spec(tot_nodes: int, tot_edges: int, n_graphs: int) -> PadSpec:
    """Bucketed per-batch PadSpec from size TOTALS alone — the
    dataset-free "plan shapes" arithmetic (same bucket ladder and +1
    pad slots as ``PadSpec.for_samples``), shared by
    ``GraphLoader.epoch_plan`` (epoch mode over cached size arrays) and
    queue-fed consumers that see sizes without a dataset (the serving
    batcher's unpacked fallback, the ROADMAP streaming item)."""
    return PadSpec(
        num_nodes=bucket_size(int(tot_nodes) + 1),
        num_edges=bucket_size(max(int(tot_edges), 1)),
        num_graphs=int(n_graphs) + 1,
        num_triplets=None,
    )


def worst_case_spec_from_sizes(
    node_sizes: np.ndarray, edge_sizes: np.ndarray, batch_size: int
) -> PadSpec:
    """Worst-case bucketed spec over any batch of ``batch_size`` samples.
    Nodes and edges bound independently: the worst batch for nodes is
    not necessarily the worst for edges (small dense graphs)."""
    node_top = sorted((int(c) for c in node_sizes), reverse=True)
    edge_top = sorted((int(c) for c in edge_sizes), reverse=True)
    n = sum(node_top[:batch_size])
    e = sum(edge_top[:batch_size])
    return PadSpec(
        num_nodes=bucket_size(n + 1),
        num_edges=bucket_size(max(e, 1)),
        num_graphs=batch_size + 1,
        num_triplets=None,
    )


class PadSpecSchedule:
    """Per-(epoch, batch-index) shared PadSpecs with a deterministic
    compile-count clamp.

    ``rows_fn(epoch)`` returns an int array ``[n_batches, 3]`` of
    (nodes_incl_pad_slot, edges, graphs_incl_pad_slot) targets — already
    maxed over whatever set of sub-batches must share the step's shape.
    The schedule buckets node/edge targets up the ladder, counts the
    distinct resulting keys, and clamps to ``worst_spec`` once the count
    exceeds ``2 * bucket_limit`` — replayed in epoch order, so every
    process clamps at the same (epoch, batch).
    """

    def __init__(
        self,
        rows_fn: Callable[[int], np.ndarray],
        worst_spec: PadSpec,
        bucket_limit: int,
    ):
        self._rows_fn = rows_fn
        self.worst_spec = worst_spec
        self._limit = int(bucket_limit)
        self._epochs: List[List[PadSpec]] = []
        self._seen: set = set()
        self._clamped = False

    @staticmethod
    def _key(row) -> tuple:
        n, e, g = (int(v) for v in row)
        return (bucket_size(n), bucket_size(max(e, 1)), g)

    def _extend_through(self, epoch: int) -> None:
        while len(self._epochs) <= epoch:
            specs: List[PadSpec] = []
            for row in self._rows_fn(len(self._epochs)):
                if not self._clamped:
                    key = self._key(row)
                    self._seen.add(key)
                    if len(self._seen) > 2 * self._limit:
                        self._clamped = True
                if self._clamped:
                    specs.append(self.worst_spec)
                else:
                    specs.append(
                        PadSpec(
                            num_nodes=key[0],
                            num_edges=key[1],
                            num_graphs=key[2],
                            num_triplets=None,
                        )
                    )
            self._epochs.append(specs)

    def spec(self, epoch: int, batch_index: int) -> PadSpec:
        self._extend_through(epoch)
        specs = self._epochs[epoch]
        if batch_index >= len(specs):
            # Reachable only when a loader iterates past the shared step
            # count (multibranch slots stop at the min; a bare loader
            # doesn't) — the worst spec is always safe.
            return self.worst_spec
        return specs[batch_index]

    def distinct_keys(self, epochs: int = 4) -> set:
        """Distinct bucketed spec keys the first ``epochs`` epochs would
        produce — pure simulation, no clamp-state mutation (one key ≈
        one XLA compilation of the step)."""
        keys = set()
        for e in range(epochs):
            for row in self._rows_fn(e):
                keys.add(self._key(row))
        return keys

    def ladder_is_small(self, epochs: int = 4) -> bool:
        return len(self.distinct_keys(epochs)) <= self._limit

    def fingerprint(self, epochs: int = 2) -> List[int]:
        """Small integer summary for cross-process agreement asserts."""
        keys = self.distinct_keys(epochs)
        return [len(keys), sum(k[0] + k[1] + k[2] for k in keys)]


def dp_spec_schedule(
    node_sizes: np.ndarray,
    edge_sizes: np.ndarray,
    *,
    batch_size: int,
    n_procs: int,
    steps_group: int,
    seed: int,
    shuffle: bool,
    num_samples: Optional[int] = None,
    drop_last: bool = False,
    bucket_limit: Optional[int] = None,
) -> PadSpecSchedule:
    """Schedule for the dp scheme, built from the FULL (pre-shard)
    dataset sizes so every process computes the identical schedule.

    Reproduces the runtime's data layout exactly: contiguous equal-size
    process shards (parallel/runtime.shard_dataset_for_process), each
    process's per-epoch batch order (same seed on every process), and
    ``steps_group`` consecutive local batches stacked per step
    (parallel/dp.DPLoader). Step t's spec covers batches
    [t*steps_group, (t+1)*steps_group) of EVERY process.
    """
    from hydragnn_tpu.data.diststore import shard_for_process

    node_sizes = np.asarray(node_sizes, dtype=np.int64)
    edge_sizes = np.asarray(edge_sizes, dtype=np.int64)
    n_total = len(node_sizes)
    if n_procs > 1:
        equal = n_total // n_procs
        shards = []
        for p in range(n_procs):
            idx = np.fromiter(
                shard_for_process(n_total, p, n_procs), dtype=np.int64
            )[:equal]
            shards.append((node_sizes[idx], edge_sizes[idx]))
    else:
        shards = [(node_sizes, edge_sizes)]

    def rows_fn(epoch: int) -> np.ndarray:
        per_proc = []
        for ns, es in shards:
            per_proc.append(
                batch_size_rows(
                    ns,
                    es,
                    epoch_batch_indices(
                        len(ns),
                        batch_size,
                        shuffle=shuffle,
                        seed=seed,
                        epoch=epoch,
                        num_samples=num_samples,
                        drop_last=drop_last,
                    ),
                )
            )
        # Equal shard lengths => equal batch counts on every process.
        gmax = np.stack(per_proc).max(axis=0)
        for t0 in range(0, len(gmax), steps_group):
            gmax[t0 : t0 + steps_group] = gmax[
                t0 : t0 + steps_group
            ].max(axis=0)
        return gmax

    if bucket_limit is None:
        bucket_limit = _default_bucket_limit()
    worst = worst_case_spec_from_sizes(node_sizes, edge_sizes, batch_size)
    return PadSpecSchedule(rows_fn, worst, bucket_limit)


def slot_spec_schedule(
    loaders: Sequence, bucket_limit: Optional[int] = None
) -> PadSpecSchedule:
    """Schedule for the multibranch scheme: one batch per device slot per
    step, so step t's spec is the max over every slot's t-th batch.
    Every process constructs ALL slot loaders deterministically
    (parallel/multibranch.MultiBranchLoader), so building the schedule
    from them is process-consistent by construction."""

    def rows_fn(epoch: int) -> np.ndarray:
        per_slot = [ld.epoch_size_rows(epoch) for ld in loaders]
        n_steps = min(len(r) for r in per_slot)
        return np.stack([r[:n_steps] for r in per_slot]).max(axis=0)

    worsts = [
        worst_case_spec_from_sizes(
            *dataset_size_arrays(ld.dataset), ld.batch_size
        )
        for ld in loaders
    ]
    worst = PadSpec(
        num_nodes=max(w.num_nodes for w in worsts),
        num_edges=max(w.num_edges for w in worsts),
        num_graphs=max(w.num_graphs for w in worsts),
        num_triplets=None,
    )
    if bucket_limit is None:
        bucket_limit = _default_bucket_limit()
    return PadSpecSchedule(rows_fn, worst, bucket_limit)


def _default_bucket_limit() -> int:
    import os

    return int(os.environ.get("HYDRAGNN_TPU_MAX_PAD_BUCKETS", "6"))


# ----------------------------------------------------------------------
# Bin-packed batch forming: fit a small set of (nodes, edges, graphs)
# budgets from the size histogram, then first-fit-decreasing pack each
# epoch's graphs into them. Device-free size arithmetic throughout, like
# the spec schedules above — the packing residual replaces the ladder's
# growth-factor padding waste (the ladder's buckets grow by a factor,
# so a batch can pad up to that factor; packing pads by its slack).
# ----------------------------------------------------------------------


def _round8(v: float) -> int:
    return int(int(np.ceil(float(v) / 8.0)) * 8)


def _fit_sample(
    node_sizes: np.ndarray, edge_sizes: np.ndarray, seed: int
) -> tuple:
    """Deterministic bounded subsample of the size histogram for the
    fitting/auto simulations (budget capacities are ratios of means, so
    a bounded sample yields the same budgets; simulating FFD over 1M+
    graphs at startup would stall training for minutes)."""
    import os

    cap = int(
        os.environ.get("HYDRAGNN_TPU_PACKING_FIT_SAMPLE", "50000")
    )
    n = len(node_sizes)
    if cap <= 0 or n <= cap:
        return node_sizes, edge_sizes
    rng = np.random.default_rng((int(seed), n))
    pick = rng.choice(n, size=cap, replace=False)
    return node_sizes[pick], edge_sizes[pick]


def _budget_from_caps(
    cap_n: int, cap_e: int, cap_g: int, max_n: int, max_e: int
) -> PackSpec:
    """PackSpec with lane-friendly padded sizes; capacities never fall
    below the largest single graph (a budget every graph fits is the
    packer's termination guarantee)."""
    cap_n = max(int(cap_n), int(max_n))
    cap_e = max(int(cap_e), int(max_e), 1)
    return PackSpec(
        num_nodes=_round8(cap_n + 1),
        num_edges=_round8(cap_e),
        num_graphs=max(int(cap_g), 1) + 1,
    )


class OpenBin:
    """One bin a ``PackPlanner`` is filling: remaining capacities under
    the largest budget, the placed member tags (epoch positions for the
    offline packer, request objects for the serving batcher — the
    planner never looks inside them), running real-size totals, and a
    caller-owned ``meta`` dict (the serving batcher anchors each bin's
    dispatch deadline there; the epoch packer never touches it)."""

    __slots__ = (
        "node_room",
        "edge_room",
        "graph_room",
        "tags",
        "tot_nodes",
        "tot_edges",
        "meta",
    )

    def __init__(self, node_room: int, edge_room: int, graph_room: int):
        self.node_room = int(node_room)
        self.edge_room = int(edge_room)
        self.graph_room = int(graph_room)
        self.tags: List = []
        self.tot_nodes = 0
        self.tot_edges = 0
        self.meta: dict = {}


class PackPlanner:
    """Incremental first-fit packer over a nested ``PackSpec`` budget
    set — the dataset-free core of bin-packed batch forming. This is
    the "plan shapes" half of what used to live inline in the epoch
    packer, split out so a QUEUE can feed it just as well as an epoch
    order: ``pack_epoch_ffd`` drives it with the FFD-sorted epoch
    order, and the online serving batcher (serve/batcher.py) drives it
    with requests as they arrive — the same split the ROADMAP
    streaming item needs.

    Placement, freeze and downshift arithmetic are EXACTLY the epoch
    packer's former internals, so the offline plan is bit-identical
    through this refactor (tests/test_serving.py pins it against an
    inlined reference): items go to the FIRST open bin with room in
    both the node and edge dimension under the LARGEST budget; once
    more than ``open_window`` bins are open the fullest (least node
    room, first on ties) is FROZEN out of the first-fit scan —
    surfaced through ``take_frozen`` (the serving batcher's
    capacity-pressure dispatch signal) and still part of ``drain``'s
    output; ``assign_budget`` downshifts a finished bin to the
    smallest fitted budget that holds it, so the compiled-shape set is
    always exactly the budget set."""

    def __init__(self, budgets: Sequence[PackSpec], open_window: int = 256):
        self.budgets = sorted(
            budgets, key=lambda b: (b.num_nodes, b.num_edges), reverse=True
        )
        if not self.budgets:
            raise ValueError("PackPlanner needs at least one budget")
        self.big = self.budgets[0]
        # Bins are opened under the LARGEST budget and downshifted
        # after — sound only when budgets nest (fitted sets do by
        # construction). A non-nested user set (e.g. a narrow-but-
        # edge-heavy sibling) would silently never use its extra
        # capacity, so reject it loudly.
        for b in self.budgets[1:]:
            if (
                b.num_edges > self.big.num_edges
                or b.num_graphs > self.big.num_graphs
                or b.num_nodes > self.big.num_nodes
            ):
                raise ValueError(
                    f"pack budgets must be nested under the largest; "
                    f"{b} exceeds {self.big} in some dimension"
                )
        self.open_window = max(int(open_window), 1)
        self._open: List[OpenBin] = []
        self._frozen: List[OpenBin] = []

    def fits(self, n_nodes: int, n_edges: int) -> bool:
        """Whether a single item can ever be packed (the largest budget
        holds it)."""
        return self.big.fits(int(n_nodes), int(n_edges), 1)

    @property
    def open_bins(self) -> List[OpenBin]:
        """The live first-fit scan list (read-only view; mutate only
        through ``add``/``pop``/``drain``)."""
        return self._open

    def add(self, tag, n_nodes: int, n_edges: int) -> OpenBin:
        """Place one item first-fit; returns the bin it landed in (a
        NEW bin when nothing open had room). Raises ``ValueError`` when
        the item exceeds the largest budget — callers wanting a
        friendlier message test ``fits`` first."""
        n, e = int(n_nodes), int(n_edges)
        placed = None
        for b in self._open:
            if b.node_room >= n and b.edge_room >= e and b.graph_room >= 1:
                placed = b
                break
        if placed is None:
            if not self.fits(n, e):
                raise ValueError(
                    f"item ({n} nodes, {e} edges) exceeds the largest "
                    f"pack budget {self.big}"
                )
            placed = OpenBin(
                self.big.capacity_nodes,
                self.big.capacity_edges,
                self.big.capacity_graphs,
            )
            self._open.append(placed)
        placed.node_room -= n
        placed.edge_room -= e
        placed.graph_room -= 1
        placed.tot_nodes += n
        placed.tot_edges += e
        placed.tags.append(tag)
        # Freeze check AFTER the placement decrement: the just-opened
        # bin's node room already reflects its first member, so the
        # "fullest" pick is identical to the former inline packer's.
        if len(self._open) > self.open_window:
            full = min(
                range(len(self._open)),
                key=lambda k: self._open[k].node_room,
            )
            self._frozen.append(self._open.pop(full))
        return placed

    def pop(self, b: OpenBin) -> None:
        """Remove one bin from the scan (a deadline-expired or full bin
        the caller is dispatching). No-op if already frozen out."""
        try:
            self._open.remove(b)
        except ValueError:
            try:
                self._frozen.remove(b)
            except ValueError:
                pass

    def take_frozen(self) -> List[OpenBin]:
        """Bins frozen out of the scan since the last call — capacity
        pressure says they will not fill further; the serving batcher
        dispatches them."""
        out, self._frozen = self._frozen, []
        return out

    def drain(self) -> List[OpenBin]:
        """Every remaining bin (frozen first, then open, each in
        creation order), clearing the planner — the epoch packer's
        end-of-order flush and the batcher's shutdown flush."""
        out = self._frozen + self._open
        self._open, self._frozen = [], []
        return out

    def assign_budget(
        self, tot_nodes: int, tot_edges: int, n_graphs: int
    ) -> PackSpec:
        """Smallest fitted budget holding the totals (descending scan,
        last fitting wins) — tail bins downshift to a cheaper compiled
        shape instead of padding to the full budget."""
        spec = self.big
        for cand in self.budgets:  # descending: last fitting = smallest
            if cand.fits(int(tot_nodes), int(tot_edges), int(n_graphs)):
                spec = cand
        return spec


def pack_epoch_ffd(
    order: np.ndarray,
    node_sizes: np.ndarray,
    edge_sizes: np.ndarray,
    budgets: Sequence[PackSpec],
    open_window: int = 256,
) -> List[tuple]:
    """First-fit-decreasing pack one epoch's sample order into budget
    bins. Returns ``[(idx, PackSpec), ...]`` — one entry per packed
    batch, deterministic for a given (order, sizes, budgets).

    Graphs are placed largest-nodes-first (classic FFD; ties broken by
    their position in the shuffled epoch order) into a ``PackPlanner``
    (the queue-feedable first-fit core — placement, freeze and
    downshift semantics live there); each finished bin is assigned the
    smallest fitted budget that holds it, so tail bins (the packing
    residual) downshift to a cheaper shape instead of padding to the
    full budget. Bin order and within-bin sample order follow the
    shuffled epoch order, keeping step composition stochastic across
    epochs.

    ``open_window`` bounds the first-fit scan: once more than that many
    bins are open, the fullest (least node room) is frozen, so the pack
    costs O(n x window) instead of O(n x bins) on epoch-scale inputs —
    identical results whenever an epoch packs into <= window bins (every
    dataset in the test/bench envelope), still deterministic beyond.
    """
    planner = PackPlanner(budgets, open_window=open_window)
    order = np.asarray(order, dtype=np.int64)
    n_of = node_sizes[order]
    # Stable sort on negated sizes: equal-size graphs keep epoch order.
    by_size = np.argsort(-n_of, kind="stable")
    for pos in by_size:
        i = int(order[pos])
        n, e = int(node_sizes[i]), int(edge_sizes[i])
        if not planner.fits(n, e):
            raise ValueError(
                f"graph {i} ({n} nodes, {e} edges) exceeds the "
                f"largest pack budget {planner.big}"
            )
        planner.add(int(pos), n, e)
    # Emit in epoch order: bins sorted by their earliest member's
    # position in the shuffled order, members likewise.
    out = []
    for b in sorted(planner.drain(), key=lambda b: min(b.tags)):
        members = sorted(b.tags)
        idx = order[members]
        tot_n = int(node_sizes[idx].sum())
        tot_e = int(edge_sizes[idx].sum())
        out.append(
            (idx, planner.assign_budget(tot_n, tot_e, len(idx)))
        )
    return out


def fit_pack_budgets(
    node_sizes: np.ndarray,
    edge_sizes: np.ndarray,
    batch_size: int,
    *,
    max_budgets: int = 2,
    slack: Optional[float] = None,
    max_graphs: Optional[int] = None,
    sim_epochs: int = 2,
    seed: int = 0,
    with_meta: bool = False,
) -> "List[PackSpec] | tuple":
    """Fit the budget set the packer fills — device-free arithmetic over
    the per-sample size histogram (same spirit as ``dp_spec_schedule``).

    The primary budget targets ``len(dataset) / batch_size`` bins per
    epoch (graphs-per-step parity with unpacked batching) with a small
    capacity ``slack`` so first-fit-decreasing closes bins nearly full;
    when ``slack`` is None a handful of candidates are simulated on
    shuffled epoch orders and the one minimizing executed/real size is
    kept. ``max_budgets - 1`` geometrically smaller sub-budgets absorb
    the epoch-tail residual (each budget is one compiled shape).
    ``max_graphs`` caps a bin's real graph count. Graph-LINEAR compute
    (GPS dense-attention scores, per-graph heads, ``[G, S, F]`` dense
    layouts) is priced by the padded graph dimension, which the
    node/edge waste metric cannot see — so the default bound is a
    tight 2x the unpacked batch size: FFD bins average ~1x, and a
    tiny-graph dataset that would otherwise inflate the graph dim
    instead closes bins on graph capacity, surfaces the waste in the
    node/edge simulation, and keeps the ladder under ``"auto"``.

    ``with_meta`` returns ``(budgets, {"slack", "waste"})`` — the
    chosen slack and its simulated executed/real (nodes+edges) ratio —
    so callers comparing against the ladder (``packing_beats_ladder``)
    or fitting sibling splits (the runner forwards the tuned slack to
    eval loaders) don't re-run the FFD simulation.

    Fitting cost is bounded on epoch-scale datasets: the slack
    simulation runs over a deterministic size subsample
    (``_fit_sample``, default 50k, env
    HYDRAGNN_TPU_PACKING_FIT_SAMPLE) — capacities are ratios of means,
    so a bounded sample fits the same budgets at O(1) cost; only the
    single-largest-graph floor always uses the full arrays.
    """
    node_sizes = np.asarray(node_sizes, dtype=np.int64)
    edge_sizes = np.asarray(edge_sizes, dtype=np.int64)
    if len(node_sizes) == 0:
        raise ValueError("cannot fit pack budgets over an empty dataset")
    # The largest graph must fit whatever the sample missed.
    max_n = int(node_sizes.max())
    max_e = int(edge_sizes.max())
    node_sizes, edge_sizes = _fit_sample(node_sizes, edge_sizes, seed)
    n = len(node_sizes)
    total_n = int(node_sizes.sum())
    total_e = int(edge_sizes.sum())
    min_n = max(int(node_sizes.min()), 1)
    k = max(1, int(round(n / float(batch_size))))

    def _budget_set(s: float) -> List[PackSpec]:
        cap_n = int(np.ceil(total_n / k * s))
        cap_e = int(np.ceil(total_e / k * s))
        cap_g = (
            int(max_graphs)
            if max_graphs is not None
            else min(cap_n // min_n, 2 * int(batch_size))
        )
        cap_g = max(cap_g, 1)
        out = [_budget_from_caps(cap_n, cap_e, cap_g, max_n, max_e)]
        for _ in range(max(int(max_budgets), 1) - 1):
            cap_n //= 2
            cap_e //= 2
            cap_g = max(cap_g // 2, 1)
            cand = _budget_from_caps(cap_n, cap_e, cap_g, max_n, max_e)
            if cand != out[-1]:
                out.append(cand)
        return out

    def _waste(budgets: List[PackSpec]) -> float:
        executed = real = 0.0
        for ep in range(max(int(sim_epochs), 1)):
            order = np.concatenate(
                [
                    idx
                    for idx in epoch_batch_indices(
                        n, batch_size, shuffle=True, seed=seed, epoch=ep
                    )
                ]
            )
            for idx, spec in pack_epoch_ffd(
                order, node_sizes, edge_sizes, budgets
            ):
                executed += spec.num_nodes + spec.num_edges
                real += float(
                    node_sizes[idx].sum() + edge_sizes[idx].sum()
                )
        return executed / max(real, 1.0)

    if slack is not None:
        cand = _budget_set(float(slack))
        if with_meta:
            return cand, {"slack": float(slack), "waste": _waste(cand)}
        return cand
    best = None
    best_w = float("inf")
    best_s = None
    for s in (1.01, 1.02, 1.04, 1.06, 1.1):
        cand = _budget_set(s)
        w = _waste(cand)
        if w < best_w:
            best, best_w, best_s = cand, w, s
    if with_meta:
        return best, {"slack": best_s, "waste": best_w}
    return best


def pack_epoch_ffd_dp(
    order: np.ndarray,
    node_sizes: np.ndarray,
    edge_sizes: np.ndarray,
    budgets: Sequence[PackSpec],
    n_shards: int,
    open_window: int = 256,
) -> List[tuple]:
    """Device-coordinated FFD pack for the dp scheme: one epoch's sample
    order packed into budget bins and arranged so every consecutive
    ``n_shards`` bins (one optimizer step — one bin per device on the
    ``data`` axis) share a single budget spec and the plan length is an
    exact multiple of ``n_shards``. Every device therefore steps the
    same number of times with the same compiled shapes, and no sample
    is dropped or duplicated — the coordination invariant a stacked
    ``[D, ...]`` global batch requires.

    Built on ``pack_epoch_ffd``'s bins:

    - bins are grouped by their assigned budget (budget identity IS the
      compiled shape);
    - a group whose bin count is not a multiple of ``n_shards`` has
      tail bins BALANCED up to the next multiple by splitting the
      largest-membership bin in two (a subset of a fitting bin always
      fits, so splits are capacity-safe by construction);
    - a group with fewer graphs than ``n_shards`` (it could not feed
      every device a real sub-batch) — or one whose graphs cannot
      supply enough splits — is merged into the LARGEST budget's group
      (every bin fits under it, ``pack_epoch_ffd`` validates nesting)
      and balanced there;
    - steps are emitted spec-major (largest budget first), each spec
      block keeping the shuffled epoch order, so same-shape step runs
      are maximal for the dp superstep executor.

    Raises ``ValueError`` when the epoch holds fewer graphs than
    ``n_shards``, or in the degenerate near-all-singleton-bins corner
    where no split can reach a multiple of ``n_shards`` (graphs close
    to budget capacity) — callers resolving packing for a dp run
    simulate an epoch first and fall back to the spec-schedule former.
    """
    n_shards = int(n_shards)
    if n_shards <= 1:
        return pack_epoch_ffd(
            order, node_sizes, edge_sizes, budgets, open_window
        )
    order = np.asarray(order, dtype=np.int64)
    if len(order) < n_shards:
        raise ValueError(
            f"cannot coordinate packed bins across {n_shards} devices: "
            f"the epoch holds only {len(order)} graphs"
        )
    # Pack on POSITIONS in the epoch order (an oversampling epoch may
    # repeat a dataset index; positions are unique), mapping back to
    # dataset indices only at emission — exactly the base packer's own
    # internal bookkeeping. The positions are handed to the packer in
    # CANONICAL (-nodes, -edges, position) order: pack_epoch_ffd's
    # stable size sort then processes an (n, e) sequence that depends
    # only on the size MULTISET, never on the shuffle — so the bin
    # size-structure (loads, budget assignment, per-group bin counts)
    # and therefore the balance pass's FEASIBILITY are identical every
    # epoch, and the runner's epoch-0 probe proves the whole run.
    # (Epoch-order tie-breaking — the base packer's default — would
    # let equal-node graphs with different edge counts reshape bins
    # per shuffle, reaching the infeasible corner hours into a run.)
    # Step COMPOSITION still reshuffles: which graph occupies each
    # size slot, and the emission order below, follow the epoch order.
    n_of = np.asarray(node_sizes, dtype=np.int64)[order]
    e_of = np.asarray(edge_sizes, dtype=np.int64)[order]
    canon = np.lexsort(
        (np.arange(len(order)), -e_of, -n_of)
    ).astype(np.int64)
    bins = pack_epoch_ffd(canon, n_of, e_of, budgets, open_window)
    big = sorted(
        budgets, key=lambda b: (b.num_nodes, b.num_edges), reverse=True
    )[0]
    groups: dict = {}
    for idx, spec in bins:
        key = (spec.num_nodes, spec.num_edges, spec.num_graphs)
        g = groups.setdefault(key, {"spec": spec, "bins": []})
        g["bins"].append(list(idx))
    big_key = (big.num_nodes, big.num_edges, big.num_graphs)

    def _graphs(g) -> int:
        return sum(len(b) for b in g["bins"])

    def _target(g) -> int:
        return -(-len(g["bins"]) // n_shards) * n_shards

    # Merge pass: any non-largest group that cannot fill (or split to)
    # a whole number of steps folds into the largest budget's group.
    for key in sorted(k for k in groups if k != big_key):
        g = groups[key]
        if _graphs(g) < max(_target(g), n_shards):
            bg = groups.setdefault(
                big_key, {"spec": big, "bins": []}
            )
            bg["bins"].extend(g["bins"])
            del groups[key]
    bg = groups.get(big_key)
    if bg is not None and _graphs(bg) < max(_target(bg), n_shards):
        # The largest group itself cannot fill its steps: pull every
        # other group in (all bins fit the largest budget), largest
        # remaining first, until it can.
        for key in sorted(
            (k for k in groups if k != big_key), reverse=True
        ):
            bg["bins"].extend(groups[key]["bins"])
            del groups[key]
            if _graphs(bg) >= max(_target(bg), n_shards):
                break

    # Balance pass: split bins until every group's count is a multiple
    # of n_shards. Splitting the largest-membership bin keeps the two
    # halves near-even; alternating the size-sorted members balances
    # node totals. Deterministic throughout.
    def _split(members: List[int]) -> tuple:
        by_size = sorted(members, key=lambda p: (-int(n_of[p]), p))
        return by_size[0::2], by_size[1::2]

    for key in sorted(groups):
        g = groups[key]
        while len(g["bins"]) % n_shards:
            splittable = [
                j for j, b in enumerate(g["bins"]) if len(b) >= 2
            ]
            if not splittable:
                raise ValueError(
                    f"cannot balance packed bins across {n_shards} "
                    "devices: every remaining bin holds a single graph "
                    "(graphs near budget capacity) — use the "
                    "spec-schedule former for this dataset"
                )
            j = max(splittable, key=lambda j: len(g["bins"][j]))
            a, b = _split(g["bins"].pop(j))
            g["bins"].extend([a, b])

    # Emission: spec-major (largest budget first), bins within a group
    # by their earliest member's position in the shuffled epoch order.
    out: List[tuple] = []
    for key in sorted(groups, reverse=True):
        g = groups[key]
        for members in sorted(g["bins"], key=min):
            out.append((order[sorted(members)], g["spec"]))
    return out


def dp_step_plan(plan, n_shards: int) -> tuple:
    """Fold a flat epoch plan into STEP-level entries for a
    ``n_shards``-device data axis: step t covers plan entries
    ``[t*D, (t+1)*D)`` (the run ``DPLoader`` stacks into one
    ``[D, ...]`` batch). Returns ``(steps, tail)``:

    - ``steps``: one ``(t, spec)`` entry per FULL step — ``spec`` when
      all D entries share one spec key (the step is stackable at a
      known shape, hence groupable by ``superstep_groups``), ``None``
      otherwise;
    - ``tail``: the trailing ``len(plan) % D`` flat entries, delivered
      through ``DPLoader``'s masked-pad remainder path.
    """
    def _key(s):  # PadSpec or PackSpec (budgets carry no triplet dim)
        if s is None:
            return None
        return (
            s.num_nodes,
            s.num_edges,
            s.num_graphs,
            getattr(s, "num_triplets", None),
        )

    plan = list(plan)
    d = max(int(n_shards), 1)
    n_full = len(plan) // d
    steps: List[tuple] = []
    for t in range(n_full):
        specs = [s for _, s in plan[t * d : (t + 1) * d]]
        key = _key(specs[0])
        same = key is not None and all(_key(s) == key for s in specs)
        steps.append((t, specs[0] if same else None))
    return steps, plan[n_full * d :]


# ----------------------------------------------------------------------
# Superstep grouping: fold one epoch's (idx, spec) plan into runs of K
# consecutive SAME-SPEC batches so the train loop can stack each run
# into one [K, ...] macro-batch and drive K optimizer steps from a
# single Python dispatch (train/loop.make_superstep_fn's lax.scan).
# Pure functions of the existing epoch_plan — serial and pipeline
# delivery group identically by construction, preserving the PR-1
# bit-identity contract.
# ----------------------------------------------------------------------


def _spec_key(spec) -> tuple:
    return (
        spec.num_nodes,
        spec.num_edges,
        spec.num_graphs,
        spec.num_triplets,
    )


def superstep_groups(plan, k: int) -> List[list]:
    """Group one epoch's ``[(idx, spec), ...]`` plan into superstep
    groups: each group is a list of consecutive same-spec plan entries
    of length exactly ``k`` (one stacked macro-batch = one dispatch of
    K scanned steps) or length 1 (a plain single-step batch).

    Maximal same-spec runs are cut into full ``k``-chunks as they
    accumulate; a run's remainder (< k entries) is emitted as
    singletons, so the compiled-shape set stays bounded at {K-stacked
    per spec} plus {single per spec} — the single-step executable is
    needed for K=1 runs anyway. Entries with ``spec=None`` (the
    triplet ladder derives specs at collate time, so equality is
    unknowable here) are never grouped. ``k <= 1`` returns every entry
    as a singleton: the plan's batch order and content are ALWAYS
    preserved, only the grouping boundaries change.
    """
    k = int(k)
    groups: List[list] = []
    run: List[tuple] = []
    run_key = None

    def _flush():
        # remainder of a broken run: singletons (see docstring)
        groups.extend([e] for e in run)
        run.clear()

    for entry in plan:
        spec = entry[1]
        key = None if spec is None else _spec_key(spec)
        if key is None:
            _flush()
            run_key = None
            groups.append([entry])
            continue
        if key != run_key:
            _flush()
            run_key = key
        if k <= 1:
            groups.append([entry])
            continue
        run.append(entry)
        if len(run) == k:
            groups.append(list(run))
            run.clear()
    _flush()
    return groups


def estimate_spec_bytes(
    spec,
    *,
    node_cols: float = 16.0,
    edge_cols: float = 8.0,
    graph_cols: float = 12.0,
    triplet_cols: float = 4.0,
) -> int:
    """Coarse host-RAM bound of one collated batch at ``spec`` —
    float32-equivalent column counts per node/edge/graph/triplet row
    chosen to upper-bound every GraphBatch field combination in the
    test/bench envelope (x + pos + pe + masks + indices per node;
    endpoints + attrs + shifts per edge; targets + cell rows per graph;
    t_kj/t_ji/triplet_mask per triplet — padded triplet counts dwarf E
    on DimeNet-class batches, so omitting them would let auto-K blow
    the host cap on exactly the densest workloads). Used only to cap
    auto-picked K against ``max_host_bytes``; an order-of-magnitude
    bound is all the cap needs."""
    triplets = spec.num_triplets or 0
    return int(
        4
        * (
            spec.num_nodes * node_cols
            + spec.num_edges * edge_cols
            + spec.num_graphs * graph_cols
            + triplets * triplet_cols
        )
    )


def auto_superstep_k(
    plan,
    *,
    max_host_bytes: int = 256 << 20,
    candidates: Sequence[int] = (32, 16, 8),
    min_grouped_frac: float = 0.5,
    min_steps: int = 64,
) -> int:
    """The ``superstep: {steps: "auto"}`` decision — a pure function of
    one epoch's plan: the largest candidate K whose full K-groups cover
    at least ``min_grouped_frac`` of the epoch's steps (spec runs must
    actually be long enough — grouping a fragmented ladder would leave
    most steps on the single-step path while paying the scan compiles)
    and whose stacked macro-batch stays under ``max_host_bytes``
    (estimate_spec_bytes x K, workers hold ~2 in flight).

    Plans shorter than ``min_steps`` always return 1: amortizing
    Python dispatch is a long-epoch optimization, and short runs (unit
    tests, tiny examples) should keep today's exact execution shape
    rather than pay extra scan compiles.
    """
    plan = list(plan)
    if len(plan) < max(int(min_steps), 2):
        return 1
    specs = [s for _, s in plan if s is not None]
    if not specs:
        return 1
    biggest = max(estimate_spec_bytes(s) for s in specs)
    for k in sorted({int(c) for c in candidates}, reverse=True):
        if k <= 1:
            continue
        if biggest * k > int(max_host_bytes):
            continue
        grouped = sum(
            len(g) for g in superstep_groups(plan, k) if len(g) > 1
        )
        if grouped >= min_grouped_frac * len(plan):
            return k
    return 1


def packing_beats_ladder(
    node_sizes: np.ndarray,
    edge_sizes: np.ndarray,
    batch_size: int,
    *,
    margin: float = 0.97,
    epochs: int = 2,
    seed: int = 0,
    baseline: str = "auto",
    **fit_kw,
) -> Optional[tuple]:
    """The ``packing: "auto"`` decision — device-free size arithmetic:
    fit budgets and return ``(budgets, slack)`` when the packed
    executed/real (nodes + edges) ratio beats the bucket ladder's by
    at least the margin (default: a >=3% padding-waste win); None
    otherwise. A near-tie keeps the ladder — no reason to change batch
    composition for noise-level gains. The packed side reuses the
    fitting pass's own FFD simulation (``with_meta``); the baseline is
    what the run would ACTUALLY do without packing — ``baseline``
    mirrors the resolved fixed-pad mode: ``"ladder"`` (forced
    per-batch buckets), ``"worst"`` (forced single worst-case spec),
    or ``"auto"``: the bucket ladder while its distinct-shape count
    stays within HYDRAGNN_TPU_MAX_PAD_BUCKETS, else the worst-case
    clamp — exactly the high-variance regime where packing wins
    most."""
    node_sizes = np.asarray(node_sizes, dtype=np.int64)
    edge_sizes = np.asarray(edge_sizes, dtype=np.int64)
    if len(node_sizes) == 0:
        return None
    budgets, meta = fit_pack_budgets(
        node_sizes,
        edge_sizes,
        batch_size,
        seed=seed,
        sim_epochs=epochs,
        with_meta=True,
        **fit_kw,
    )
    # The baseline loops run over the FULL arrays (cheap numpy index
    # sums, unlike the FFD simulation the fit subsamples): the ladder's
    # distinct-key count — and hence whether the real run would clamp
    # to the worst case — scales with the true batches-per-epoch, which
    # a subsample would understate on exactly the large datasets where
    # the clamp (and packing's win) kicks in.
    n = len(node_sizes)
    if baseline == "ladder":
        ladder_ok = True
    elif baseline == "worst":
        ladder_ok = False
    else:
        keys = set()
        for ep in range(4):  # the loader's own _ladder_is_small horizon
            for idx in epoch_batch_indices(
                n, batch_size, shuffle=True, seed=seed, epoch=ep
            ):
                keys.add(
                    (
                        bucket_size(int(node_sizes[idx].sum()) + 1),
                        bucket_size(max(int(edge_sizes[idx].sum()), 1)),
                        len(idx) + 1,
                    )
                )
        ladder_ok = len(keys) <= _default_bucket_limit()
    worst = worst_case_spec_from_sizes(node_sizes, edge_sizes, batch_size)
    baseline_exe = real = 0.0
    for ep in range(max(int(epochs), 1)):
        for idx in epoch_batch_indices(
            n, batch_size, shuffle=True, seed=seed, epoch=ep
        ):
            if ladder_ok:
                baseline_exe += bucket_size(
                    int(node_sizes[idx].sum()) + 1
                ) + bucket_size(max(int(edge_sizes[idx].sum()), 1))
            else:
                baseline_exe += worst.num_nodes + worst.num_edges
            real += float(
                node_sizes[idx].sum() + edge_sizes[idx].sum()
            )
    if meta["waste"] <= (baseline_exe / max(real, 1.0)) * float(margin):
        return budgets, meta["slack"]
    return None


def dp_packing_beats_schedule(
    node_sizes: np.ndarray,
    edge_sizes: np.ndarray,
    batch_size: int,
    n_shards: int,
    *,
    margin: float = 0.97,
    epochs: int = 2,
    seed: int = 0,
    baseline: str = "auto",
    **fit_kw,
) -> Optional[tuple]:
    """The ``packing: "auto"`` decision for the dp scheme — the
    device-coordinated sibling of ``packing_beats_ladder``: fit budgets
    and return ``(budgets, slack)`` when the COORDINATED packed plan
    (``pack_epoch_ffd_dp``, including its tail-balancing splits) beats
    the dp run's no-packing baseline by at least the margin; None when
    it doesn't, or when the coordination is infeasible for this size
    distribution (the packer raises — e.g. near-all-singleton bins).

    The baseline is what a dp run actually executes without packing:
    every batch of a step pads to the STEP's shared spec
    (``dp_spec_schedule`` semantics — the max over ``n_shards``
    consecutive batches, bucketed), the short remainder step pads to a
    full device group with masked copies, and the whole schedule clamps
    to the worst-case spec when its distinct-shape count exceeds
    HYDRAGNN_TPU_MAX_PAD_BUCKETS (``baseline="auto"``; ``"ladder"`` /
    ``"worst"`` force either side, mirroring the resolved
    HYDRAGNN_TPU_USE_VARIABLE_GRAPH_SIZE mode).

    The waste simulation runs over the bounded ``_fit_sample``
    subsample like the budget fit itself (capacities and waste are
    ratios of means); the ladder-vs-worst CLAMP decision runs over the
    full arrays (its key count scales with true batches-per-epoch —
    see the baseline comment in ``packing_beats_ladder``); the packed
    side replays the REAL dp plan construction, so balancing overhead
    and spec-major emission are priced in.
    """
    node_sizes = np.asarray(node_sizes, dtype=np.int64)
    edge_sizes = np.asarray(edge_sizes, dtype=np.int64)
    n_shards = max(int(n_shards), 1)
    if len(node_sizes) < n_shards:
        return None
    budgets, meta = fit_pack_budgets(
        node_sizes,
        edge_sizes,
        batch_size,
        seed=seed,
        sim_epochs=epochs,
        with_meta=True,
        **fit_kw,
    )
    ns, es = _fit_sample(node_sizes, edge_sizes, seed)
    n = len(ns)
    if n < n_shards:
        return None

    def _rows(ep, nodes, edges):
        rows = batch_size_rows(
            nodes,
            edges,
            epoch_batch_indices(
                len(nodes), batch_size, shuffle=True, seed=seed, epoch=ep
            ),
        )
        for t0 in range(0, len(rows), n_shards):
            rows[t0 : t0 + n_shards] = rows[
                t0 : t0 + n_shards
            ].max(axis=0)
        return rows

    if baseline == "ladder":
        ladder_ok = True
    elif baseline == "worst":
        ladder_ok = False
    else:
        # The clamp decision runs over the FULL arrays (cheap numpy
        # index sums), like packing_beats_ladder's baseline: the
        # schedule's distinct-key count scales with the true
        # batches-per-epoch, which a subsample would understate on
        # exactly the large high-variance datasets where the clamp
        # (and packing's win) kicks in. Threshold is the SCHEDULE's
        # own criterion — PadSpecSchedule clamps only past 2x the
        # bucket limit (there is no up-front 1x ladder decision under
        # dp, unlike the single-scheme loader) — so the simulated
        # baseline prices what the run would actually execute.
        keys = set()
        for ep in range(4):
            for row in _rows(ep, node_sizes, edge_sizes):
                keys.add(PadSpecSchedule._key(row))
        ladder_ok = len(keys) <= 2 * _default_bucket_limit()
    worst = worst_case_spec_from_sizes(ns, es, batch_size)
    # Same samples on both sides => the real-size denominator cancels:
    # compare executed totals directly.
    base_exe = pack_exe = 0.0
    for ep in range(max(int(epochs), 1)):
        rows = _rows(ep, ns, es)
        for gn, ge, _ in rows:
            if ladder_ok:
                base_exe += bucket_size(int(gn)) + bucket_size(
                    max(int(ge), 1)
                )
            else:
                base_exe += worst.num_nodes + worst.num_edges
        rem = (-len(rows)) % n_shards
        if rem:  # masked-pad device-group completion executes too
            gn, ge, _ = rows[-1]
            if ladder_ok:
                base_exe += rem * (
                    bucket_size(int(gn)) + bucket_size(max(int(ge), 1))
                )
            else:
                base_exe += rem * (worst.num_nodes + worst.num_edges)
        order = np.concatenate(
            [
                idx
                for idx in epoch_batch_indices(
                    n, batch_size, shuffle=True, seed=seed, epoch=ep
                )
            ]
        )
        try:
            dp_plan = pack_epoch_ffd_dp(order, ns, es, budgets, n_shards)
        except ValueError:
            return None  # coordination infeasible: keep the schedule
        for _, spec in dp_plan:
            pack_exe += spec.num_nodes + spec.num_edges
    if pack_exe <= base_exe * float(margin):
        return budgets, meta["slack"]
    return None
