"""Host-side batching: shuffling, bucketed padding, device feed.

The TPU-native replacement for torch DataLoader + DistributedSampler
(reference: hydragnn/preprocess/load_data.py:226-334). Batches are padded
to bucketed static shapes so jitted steps compile once per bucket; per-rank
lockstep is static by construction (every rank sees the same number of
batches for a given dataset split — no allreduce(MIN) needed, compare
reference train_validate_test.py:671-672).
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, List, Optional, Sequence

import numpy as np

from hydragnn_tpu.data.graph import (
    GraphBatch,
    GraphSample,
    PadSpec,
    collate,
    optional_field_widths,
)
from hydragnn_tpu.data.padschedule import (
    PadSpecSchedule,
    dataset_size_arrays,
    epoch_batch_indices,
    fit_pack_budgets,
    pack_epoch_ffd,
    worst_case_spec_from_sizes,
)


class GraphLoader:
    """Iterates GraphBatches over a list of GraphSamples.

    A fixed ``PadSpec`` for all batches (computed from the worst-case
    batch) keeps a single compiled executable; ``fixed_pad=False``
    instead pads each batch up a geometric bucket ladder (fewer wasted
    FLOPs, a bounded handful of compilations). ``fixed_pad="auto"``
    simulates the first epochs' bucket specs (pure size arithmetic, no
    collation) and picks the ladder when it stays within
    ``HYDRAGNN_TPU_MAX_PAD_BUCKETS`` (default 6) distinct shapes —
    padding waste drops to the ladder's growth factor without an
    open-ended compile count.
    """

    def __init__(
        self,
        dataset: Sequence[GraphSample],
        batch_size: int,
        *,
        shuffle: bool = False,
        seed: int = 0,
        fixed_pad: "bool | str" = True,
        drop_last: bool = False,
        with_triplets: bool = False,
        with_segment_plan: "bool | str" = False,
        num_samples: Optional[int] = None,
        ensure_fields: Optional[dict] = None,
        cache_batches: bool = False,
        spec_schedule: Optional[PadSpecSchedule] = None,
        packing: bool = False,
        pack_budgets: Optional[List] = None,
        pack_max_budgets: int = 2,
        pack_slack: Optional[float] = None,
        pack_max_graphs: Optional[int] = None,
        pack_dp_shards: int = 0,
        sort_receivers: bool = False,
    ):
        """``num_samples`` resamples each epoch to a fixed size — the
        reference's oversampling RandomSampler (load_data.py:240-250),
        used to equalize epoch lengths across datasets of different
        sizes; draws with replacement when num_samples > len(dataset).
        Random by construction, so it requires shuffle=True (a
        fixed-order eval loader would otherwise silently drop samples).

        ``cache_batches`` keeps the collated batches of the first full
        iteration and replays them on later epochs — fixed-order
        loaders (val/test, run every epoch) produce identical batches
        each time, so re-collating them is pure host overhead. Only
        honored when the epoch order is deterministic (no shuffle, no
        resampling). Batches are cached as HOST numpy copies (a
        device-resident cache would pin the whole padded val/test set
        in HBM for the entire run); the per-epoch host->device transfer
        is overlapped by the prefetch wrapper. Costs one padded copy of
        the dataset in host RAM — leave it off for lazy containers
        bigger than memory.

        ``spec_schedule`` (data/padschedule.py) overrides the pad-spec
        logic entirely: batch j of epoch e is padded to
        ``spec_schedule.spec(e, j)`` — the dp/multibranch schemes use it
        to give every device sub-batch of one step the same bucketed
        shape, consistently across host processes. The schedule MUST be
        built from this loader's exact batch order (same sizes, seed,
        batch_size); undersized specs are rejected at collate time.

        ``packing`` replaces per-epoch fixed-size batches with
        bin-packed batches: a small set of (nodes, edges, graphs)
        budgets is fitted from the size histogram
        (padschedule.fit_pack_budgets, or passed via ``pack_budgets``)
        and each epoch's shuffled sample order is first-fit-decreasing
        packed into them, so padding waste drops to the packing residual
        while the compiled-shape count stays at the budget count. With
        packing OFF every epoch_plan sequence is bit-identical to the
        ladder/fixed behavior — nothing in the unpacked path consults
        the packing code. Incompatible with ``spec_schedule`` (dp steps
        need cross-process shapes) and ``with_triplets`` (budgets do not
        cover triplet counts).

        ``pack_dp_shards > 1`` switches the packer to the
        device-coordinated dp form (padschedule.pack_epoch_ffd_dp):
        each epoch's plan length is an exact multiple of the shard
        count and every consecutive shard-count run of bins shares one
        budget spec, so a ``DPLoader`` stacking the delivered batches
        sees identical shapes across the ``data`` axis and the same
        step count on every device.

        ``with_segment_plan`` may be ``"auto"``: the sorted-segment
        block plan (Pallas aggregation) is attached only for padded
        shapes where the kernel beats the XLA scatter per the
        ROOFLINE-seeded crossover table
        (ops/pallas_segment.planned_profitable).

        ``sort_receivers`` puts ``sorted_receivers`` on every spec of
        the epoch plan: collation then keeps each batch's edges in
        receiver order (a check for monotone receivers first, a stable
        sort only where it fails) and the batch carries the promise
        (``GraphBatch.receivers_sorted``), which the receiver
        aggregation hands to XLA's scatter (ops/segment.py). It changes
        no shape.
        """
        # Dataset OBJECTS (BinDataset, SimplePickleDataset, ...) pass
        # through unmaterialized — __iter__ indexes them per batch, so a
        # mmap-backed container stays a partial-read container instead
        # of being pulled wholesale into RAM (the reference's ADIOS
        # "direct" mode, adiosdataset.py:899-1018). Plain lists/tuples
        # are defensively copied, and anything without len+indexing
        # (a generator, a one-shot iterable) is materialized — only
        # true containers stay lazy.
        if isinstance(dataset, (list, tuple)) or not (
            hasattr(dataset, "__getitem__") and hasattr(dataset, "__len__")
        ):
            dataset = list(dataset)
        self.dataset = dataset
        self.batch_size = int(batch_size)
        self.shuffle = shuffle
        self.num_samples = None if num_samples is None else int(num_samples)
        if self.num_samples is not None and not shuffle:
            raise ValueError(
                "num_samples (oversampling) draws a random sample each "
                "epoch; pass shuffle=True"
            )
        self.drop_last = drop_last
        self.with_triplets = with_triplets
        self.with_segment_plan = with_segment_plan
        self.sort_receivers = bool(sort_receivers)
        self._seed = int(seed)
        self._epoch = 0
        self._skip_next = 0
        self._auto_selected = False
        self._seen_specs: set = set()
        self._triplet_size_cache: Optional[np.ndarray] = None
        self.spec_schedule = spec_schedule
        if spec_schedule is not None:
            if with_triplets:
                raise ValueError(
                    "spec_schedule does not cover triplet counts; use "
                    "fixed padding for triplet-bearing models"
                )
            fixed_pad = False
        self.packing = bool(packing)
        self.pack_dp_shards = max(int(pack_dp_shards), 0)
        if self.pack_dp_shards > 1 and num_samples is not None:
            # Without resampling the size multiset — and therefore the
            # coordinated plan's feasibility — is epoch-invariant, so
            # the runner's epoch-0 probe proves every epoch. Per-epoch
            # resampling draws a NEW multiset each epoch and could hit
            # the infeasible corner (pack_epoch_ffd_dp raises) hours
            # into a run; reject the combination up front instead.
            raise ValueError(
                "device-coordinated packing (pack_dp_shards) is "
                "incompatible with num_samples resampling: a resampled "
                "epoch can become infeasible to coordinate mid-train"
            )
        self.pack_budgets: Optional[List] = None
        self._pack_plan_cache: Optional[tuple] = None
        if self.packing:
            if spec_schedule is not None:
                raise ValueError(
                    "packing is incompatible with a shared spec_schedule"
                    " (a packed dp run coordinates shapes through the"
                    " device-coordinated plan itself — pass"
                    " pack_dp_shards, not a schedule)"
                )
            if with_triplets:
                raise ValueError(
                    "packing budgets do not cover triplet counts; use "
                    "fixed padding for triplet-bearing models"
                )
            fixed_pad = False
            if pack_budgets is not None:
                self.pack_budgets = list(pack_budgets)
            elif len(self.dataset):
                nodes, edges = self._size_arrays()
                self.pack_budgets = fit_pack_budgets(
                    nodes,
                    edges,
                    self.batch_size,
                    max_budgets=pack_max_budgets,
                    slack=pack_slack,
                    max_graphs=pack_max_graphs,
                    seed=self._seed,
                )
        if fixed_pad == "auto":
            # Triplet counts need the edge topology (a full decode on
            # lazy datasets) — keep the single worst-case shape there.
            fixed_pad = (
                True
                if (with_triplets or not len(self.dataset))
                else not self._ladder_is_small()
            )
            self._auto_selected = not fixed_pad
        self.fixed_pad = fixed_pad
        self.cache_batches = (
            cache_batches and not shuffle and num_samples is None
        )
        self._batch_cache: Optional[List[GraphBatch]] = None
        self.pad_spec: Optional[PadSpec] = None
        # One pytree structure across all batches: a mixed dataset
        # (some samples periodic, some not) must materialize the same
        # optional fields in every batch. Callers coordinating several
        # loaders (MultiBranchLoader device slots) pass a shared union
        # map instead.
        self._ensure_fields = (
            ensure_fields
            if ensure_fields is not None
            else (
                optional_field_widths(self.dataset)
                if len(self.dataset)
                else {}
            )
        )
        if fixed_pad and len(self.dataset):
            self.pad_spec = self._worst_case_spec()

    def _size_arrays(self) -> tuple:
        """Per-sample (node, edge) counts as int64 arrays (metadata fast
        path / cached scan — data/padschedule.py)."""
        return dataset_size_arrays(self.dataset)

    def _packed_plan(self, epoch: int) -> List[tuple]:
        """One epoch's packed ``(idx, PackSpec)`` bins
        (padschedule.pack_epoch_ffd over the epoch's shuffled sample
        order), cached per epoch so ``__len__``, ``packing_stats`` and
        iteration share a single packing pass. Fixed-order loaders
        (no shuffle, no resampling) have an epoch-invariant plan, so
        every epoch shares the one cached pack."""
        if not (self.shuffle or self.num_samples is not None):
            epoch = 0  # deterministic order: plan identical every epoch
        if (
            self._pack_plan_cache is not None
            and self._pack_plan_cache[0] == epoch
        ):
            return self._pack_plan_cache[1]
        if not self.pack_budgets:  # empty dataset: nothing to pack
            return []
        nodes, edges = self._size_arrays()
        batches = list(self._epoch_batches(epoch))
        order = (
            np.concatenate(batches)
            if batches
            else np.zeros(0, np.int64)
        )
        if self.pack_dp_shards > 1:
            from hydragnn_tpu.data.padschedule import pack_epoch_ffd_dp

            bins = pack_epoch_ffd_dp(
                order, nodes, edges, self.pack_budgets,
                self.pack_dp_shards,
            )
        else:
            bins = pack_epoch_ffd(order, nodes, edges, self.pack_budgets)
        self._pack_plan_cache = (epoch, bins)
        return bins

    def packing_stats(self, epoch: Optional[int] = None) -> Optional[dict]:
        """Fill/waste arithmetic of one epoch's packed plan (None when
        packing is off): batch count, node/edge fill fractions, and the
        size-linear pad ratio executed/real (the loop's
        ``pack_pad_ratio`` sample reads it)."""
        if not self.packing or not self.pack_budgets:
            return None
        plan = self._packed_plan(self._epoch if epoch is None else epoch)
        if not plan:
            return None
        nodes, edges = self._size_arrays()
        real_n = real_e = exe_n = exe_e = 0
        for idx, spec in plan:
            real_n += int(nodes[idx].sum())
            real_e += int(edges[idx].sum())
            exe_n += spec.num_nodes
            exe_e += spec.num_edges
        return {
            "batches": len(plan),
            "budgets": len(self.pack_budgets),
            "node_fill": real_n / max(exe_n, 1),
            "edge_fill": real_e / max(exe_e, 1),
            "pad_ratio": (exe_n + exe_e) / max(real_n + real_e, 1),
        }

    def segment_plan_enabled(self, spec: Optional[PadSpec]) -> bool:
        """Resolve ``with_segment_plan`` for one batch spec: ``"auto"``
        consults the ROOFLINE-seeded crossover table so the host-side
        edge sort + block plan is only paid for padded shapes where the
        planned Pallas kernel would actually be dispatched
        (ops.segment.planned_path_wanted). An explicit ``True`` always
        attaches the plan — but the step-side dispatch STILL vetoes the
        kernel on table-losing shapes (the oc20-class 0.48-0.77x
        regression must never recur), so on those shapes an explicit
        attach pays the host sort for nothing; prefer ``"auto"``, or
        force consumption with HYDRAGNN_TPU_SEGMENT_IMPL=pallas."""
        if self.with_segment_plan != "auto":
            return bool(self.with_segment_plan)
        if spec is None:
            return False
        from hydragnn_tpu.ops.segment import planned_path_wanted

        return planned_path_wanted(spec.num_edges, spec.num_nodes)

    def epoch_size_rows(self, epoch: int) -> np.ndarray:
        """[n_batches, 3] per-batch size rows for one epoch — the
        loader's side of the spec-schedule contract
        (padschedule.batch_size_rows defines the row layout)."""
        from hydragnn_tpu.data.padschedule import batch_size_rows

        nodes, edges = self._size_arrays()
        if self.packing:
            return batch_size_rows(
                nodes,
                edges,
                (idx for idx, _ in self._packed_plan(epoch)),
            )
        return batch_size_rows(nodes, edges, self._epoch_batches(epoch))

    def _triplet_sizes(self) -> np.ndarray:
        """Per-sample angular triplet counts (``count_triplets``), one
        scan, cached: the worst-case spec and the step rows share it."""
        if self._triplet_size_cache is None:
            from hydragnn_tpu.data.graph import count_triplets

            self._triplet_size_cache = np.array(
                [count_triplets(s) for s in self.dataset], dtype=np.int64
            )
        return self._triplet_size_cache

    def epoch_triplet_rows(self, epoch: int) -> np.ndarray:
        """[n_batches] real triplets per batch for one epoch, in the
        order of ``epoch_size_rows`` (a triplet-bearing loader keeps
        fixed-size batches: packing budgets do not cover triplets)."""
        t = self._triplet_sizes()
        return np.asarray(
            [int(t[idx].sum()) for idx in self._epoch_batches(epoch)],
            np.int64,
        )

    def planned_spec_keys(self, epochs: int = 2) -> set:
        """Distinct bucketed-PadSpec keys (nodes, edges, graphs) the
        first ``epochs`` epochs would produce under ``fixed_pad=False``
        — pure size arithmetic over the epoch orders, no sample
        decoding. One key ≈ one XLA compilation of the train step."""
        from hydragnn_tpu.data.graph import bucket_size

        if self.packing:
            # Budgets ARE the shape set: one key per fitted budget.
            return {
                (b.num_nodes, b.num_edges, b.num_graphs)
                for b in (self.pack_budgets or [])
            }
        nodes, edges = self._size_arrays()
        keys = set()
        for ep in range(epochs):
            for idx in self._epoch_batches(ep):
                n = bucket_size(int(nodes[idx].sum()) + 1)
                e = bucket_size(max(int(edges[idx].sum()), 1))
                keys.add((n, e, len(idx) + 1))
        return keys

    @staticmethod
    def _bucket_limit() -> int:
        import os

        return int(os.environ.get("HYDRAGNN_TPU_MAX_PAD_BUCKETS", "6"))

    def _ladder_is_small(self) -> bool:
        # Simulate a few epochs' orders; later reshuffles can still
        # reach new bucket combinations, so __iter__ additionally clamps
        # to the worst-case spec once 2x this limit is observed live.
        return len(self.planned_spec_keys(epochs=4)) <= self._bucket_limit()

    def _worst_case_spec(self) -> PadSpec:
        node_counts, edge_counts = self._size_arrays()
        spec = worst_case_spec_from_sizes(
            node_counts, edge_counts, self.batch_size
        )
        if not self.with_triplets:
            return spec
        from hydragnn_tpu.data.graph import bucket_size

        t_sizes = np.sort(self._triplet_sizes())[::-1]
        t = bucket_size(max(int(t_sizes[: self.batch_size].sum()), 1))
        return PadSpec(
            num_nodes=spec.num_nodes,
            num_edges=spec.num_edges,
            num_graphs=spec.num_graphs,
            num_triplets=t,
        )

    def set_epoch(self, epoch: int) -> None:
        self._epoch = epoch
        # __iter__ is a generator: an armed cursor is only consumed at
        # the first next(). An epoch abandoned before that (e.g. the
        # HYDRAGNN_TPU_MAX_NUM_BATCH cap) must not leak its skip into
        # the next epoch — the loop re-arms after set_epoch on resume.
        self._skip_next = 0

    def skip_to(self, step: int) -> None:
        """One-shot fast-forward: the NEXT iteration starts at plan
        entry ``step`` of the current epoch, replaying the
        deterministic ``epoch_plan`` (spec arithmetic only) WITHOUT
        collating the consumed entries — the mid-epoch resume cursor
        (docs/DURABILITY.md). Consumed by the next ``__iter__`` (or
        dropped by the next ``set_epoch``); subsequent epochs iterate
        in full again."""
        self._skip_next = max(0, int(step))

    def __len__(self) -> int:
        if self.packing:
            # Bin counts vary slightly epoch to epoch (packing follows
            # the shuffled order); report the current epoch's plan.
            return len(self._packed_plan(self._epoch))
        n = (
            self.num_samples
            if self.num_samples is not None
            else len(self.dataset)
        )
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size

    def _epoch_batches(self, epoch: int) -> Iterator[np.ndarray]:
        """Index arrays of each batch for one epoch — the single source
        of batch order for __iter__, planned_spec_keys, AND the spec
        schedules (padschedule.epoch_batch_indices keeps the order
        reproducible outside the loader)."""
        return epoch_batch_indices(
            len(self.dataset),
            self.batch_size,
            shuffle=self.shuffle,
            seed=self._seed,
            epoch=epoch,
            num_samples=self.num_samples,
            drop_last=self.drop_last,
        )

    def __iter__(self) -> Iterator[GraphBatch]:
        skip = self._skip_next
        self._skip_next = 0
        if self._batch_cache is not None:
            yield from self._batch_cache[skip:]
            return
        # Never populate the replay cache from a fast-forwarded (and
        # therefore partial) epoch — a later full iteration would
        # silently replay the suffix as the whole epoch.
        cache: Optional[List[GraphBatch]] = (
            [] if self.cache_batches and not skip else None
        )
        for batch in self._iter_collate(skip):
            if cache is not None:
                # Host copies: never pin accelerator memory.
                import jax

                cache.append(
                    jax.tree_util.tree_map(np.asarray, batch)
                )
            yield batch
        if cache is not None:
            self._batch_cache = cache

    def _fixed_batch_spec(self) -> PadSpec:
        return PadSpec(
            num_nodes=self.pad_spec.num_nodes,
            num_edges=self.pad_spec.num_edges,
            num_graphs=self.batch_size + 1,
            num_triplets=self.pad_spec.num_triplets,
        )

    def epoch_plan(self, epoch: int) -> Iterator[tuple]:
        """Yield ``(idx, spec)`` for every batch of one epoch — the
        deterministic per-step plan shared by the serial collate path
        and the parallel input pipeline (data/pipeline.py), which farms
        the (idx, spec) tasks out to a worker pool. Specs are computed
        from size metadata only (no sample decoding), so the plan is
        cheap; a ``None`` spec means "derive the batch's own bucketed
        spec from the decoded samples" (only the triplet-bearing ladder
        needs full edge decodes — each batch's spec is then independent,
        so out-of-order workers stay deterministic).

        With ``packing`` on, the plan is the epoch's first-fit-
        decreasing bin assignment instead (one entry per packed batch,
        spec = the bin's budget shape); with packing OFF this method is
        bit-identical to the pre-packing behavior.
        """
        for idx, spec in self._planned_specs(epoch):
            if self.sort_receivers and spec is not None:
                spec = dataclasses.replace(spec, sorted_receivers=True)
            yield idx, spec

    def _planned_specs(self, epoch: int) -> Iterator[tuple]:
        """``epoch_plan`` before ``sort_receivers``."""
        if self.packing:
            for idx, budget in self._packed_plan(epoch):
                yield idx, budget.pad_spec()
            return
        if self.spec_schedule is not None:
            nodes, edges = self._size_arrays()
            for j, idx in enumerate(self._epoch_batches(epoch)):
                spec = self.spec_schedule.spec(epoch, j)
                need_n = int(nodes[idx].sum()) + 1
                need_e = int(edges[idx].sum())
                if (
                    need_n > spec.num_nodes
                    or need_e > spec.num_edges
                    or len(idx) + 1 > spec.num_graphs
                ):
                    raise ValueError(
                        f"spec schedule out of sync with loader: batch "
                        f"{j} of epoch {epoch} needs "
                        f"({need_n}, {need_e}, {len(idx) + 1}) but the "
                        f"schedule allows ({spec.num_nodes}, "
                        f"{spec.num_edges}, {spec.num_graphs}) — the "
                        "schedule must be built from this loader's "
                        "exact sizes/seed/batch_size"
                    )
                yield idx, spec
            return
        if self.pad_spec is None and self.with_triplets:
            # Ladder + triplets (explicit fixed_pad=False only — auto
            # always resolves to the fixed pad here): per-batch triplet
            # counts need the edge topology, so the spec is derived at
            # collate time from the decoded samples.
            for idx in self._epoch_batches(epoch):
                yield idx, None
            return
        nodes = edges = None
        from hydragnn_tpu.data.padschedule import ladder_spec

        for idx in self._epoch_batches(epoch):
            if self.pad_spec is not None:
                yield idx, self._fixed_batch_spec()
                continue
            if nodes is None:
                nodes, edges = self._size_arrays()
            # Same arithmetic as PadSpec.for_samples over this batch's
            # samples, from the cached size arrays (no decode) — the
            # dataset-free half lives in padschedule.ladder_spec.
            spec = ladder_spec(
                int(nodes[idx].sum()), int(edges[idx].sum()), len(idx)
            )
            if self._auto_selected:
                # Live guard on the auto decision: reshuffled later
                # epochs can reach bucket combinations the upfront
                # simulation didn't; once 2x the budget is observed,
                # clamp to the worst-case spec permanently (one
                # final compile, bounded forever after).
                self._seen_specs.add(
                    (spec.num_nodes, spec.num_edges, spec.num_graphs)
                )
                if len(self._seen_specs) > 2 * self._bucket_limit():
                    self.pad_spec = self._worst_case_spec()
                    self._auto_selected = False
                    spec = self._fixed_batch_spec()
            yield idx, spec

    def batch_spec(self, samples: Sequence[GraphSample]) -> PadSpec:
        """Spec for a planned batch whose ``epoch_plan`` entry was
        ``None`` (triplet ladder): each batch buckets independently."""
        return dataclasses.replace(
            PadSpec.for_samples(samples, with_triplets=self.with_triplets),
            sorted_receivers=self.sort_receivers,
        )

    def collate_entry(
        self, idx, spec, *, as_numpy: bool = False
    ) -> GraphBatch:
        """Collate ONE planned ``(idx, spec)`` entry with this loader's
        full policy (segment-plan resolution, ensure_fields) — the
        single collate call shared by serial iteration and the
        superstep wrapper (which stacks several entries host-side
        before one device commit, hence ``as_numpy``)."""
        samples = [self.dataset[i] for i in idx]
        if spec is None:
            spec = self.batch_spec(samples)
        return collate(
            samples,
            spec,
            with_segment_plan=self.segment_plan_enabled(spec),
            ensure_fields=self._ensure_fields,
            as_numpy=as_numpy,
        )

    def _iter_collate(self, skip: int = 0) -> Iterator[GraphBatch]:
        plan = self.epoch_plan(self._epoch)
        if skip:
            # islice still CONSUMES the generator for the skipped
            # entries — the spec arithmetic (and the ladder's live
            # clamp bookkeeping) runs exactly as in an uninterrupted
            # epoch; only the collation is saved.
            import itertools

            plan = itertools.islice(plan, skip, None)
        for idx, spec in plan:
            yield self.collate_entry(idx, spec)


class SuperstepLoader:
    """Serial superstep delivery over a GraphLoader: the epoch plan is
    folded into same-spec runs of ``k`` (padschedule.superstep_groups),
    each full run collated host-side, stacked into a ``[K, ...]``
    MacroBatch and committed with ONE ``jax.device_put``; run tails
    (< k entries) are delivered as plain per-step batches. Batch
    content and order are bit-identical to iterating the wrapped
    loader directly — only the grouping boundaries (and therefore the
    Python-dispatch count of the consuming train loop) change.

    ``k=1`` is rejected: callers (parallel/runtime.wrap_loader) keep
    the unwrapped loader there so K=1 reproduces today's feed path
    exactly. Fixed-order loaders with ``cache_batches`` replay a
    host-side cache of the grouped deliveries, stored ON THE WRAPPED
    LOADER as ``_superstep_cache = (k, items)`` — so several wrappers
    over one shared eval loader (the val/test pattern) collate and
    hold the epoch ONCE, like GraphLoader's own per-step
    ``_batch_cache`` (which stays untouched: its replay contract is
    per-step batches, never macros)."""

    def __init__(self, loader, k: int, *, to_device: bool = True):
        if int(k) <= 1:
            raise ValueError(
                "SuperstepLoader needs k >= 2; keep the unwrapped "
                "loader for K=1"
            )
        if not hasattr(loader, "epoch_plan"):
            raise TypeError(
                "SuperstepLoader wraps a GraphLoader (it groups "
                f"loader.epoch_plan); got {type(loader)}"
            )
        self.loader = loader
        self.k = int(k)
        self.to_device = bool(to_device)
        self._skip_next = 0

    def set_epoch(self, epoch: int) -> None:
        self.loader.set_epoch(epoch)
        self._skip_next = 0  # a cursor never outlives its epoch

    def skip_to(self, step: int) -> None:
        """One-shot mid-epoch resume cursor (steps, not deliveries):
        the next iteration drops the groups the cursor already covers.
        Groups are cut from the FULL epoch plan first, so the resumed
        macro-batches are exactly the uninterrupted run's delivery
        suffix (checkpoint cursors land on delivery boundaries — the
        epoch loop saves only between dispatches)."""
        self._skip_next = max(0, int(step))

    def __len__(self) -> int:
        """Delivered items (dispatches) this epoch — groups, not steps."""
        from hydragnn_tpu.data.padschedule import superstep_groups

        return len(
            superstep_groups(
                self.loader.epoch_plan(self.loader._epoch), self.k
            )
        )

    def _deliver(self, item):
        if not self.to_device:
            return item
        import jax

        return jax.device_put(item)

    def __iter__(self):
        from hydragnn_tpu.data.graph import stack_batches
        from hydragnn_tpu.data.padschedule import superstep_groups

        skip = self._skip_next
        self._skip_next = 0
        shared = superstep_cache_get(self.loader, self.k)
        if shared is not None:
            for item in skip_delivered_items(shared, skip):
                yield self._deliver(item)
            return
        want_cache = (
            bool(getattr(self.loader, "cache_batches", False))
            and not skip  # a partial epoch must never seed the cache
        )
        cache: Optional[list] = [] if want_cache else None
        plan = list(self.loader.epoch_plan(self.loader._epoch))
        for group in drop_consumed_groups(
            superstep_groups(plan, self.k), skip
        ):
            batches = [
                self.loader.collate_entry(idx, spec, as_numpy=True)
                for idx, spec in group
            ]
            item = (
                stack_batches(batches)
                if len(batches) > 1
                else batches[0]
            )
            if cache is not None:
                cache.append(item)  # numpy-backed already: owns memory
            yield self._deliver(item)
        if cache is not None:
            superstep_cache_put(self.loader, self.k, cache)


def drop_consumed_groups(groups: list, skip_steps: int) -> list:
    """Resume-cursor arithmetic shared by every superstep-grouping feed
    (serial SuperstepLoader, pipeline, DPLoader's group-length form):
    drop the leading groups a ``skip_steps`` cursor fully covers, so
    the remaining deliveries are EXACTLY the uninterrupted run's suffix
    (groups are cut from the full plan; the cursor lands on delivery
    boundaries by construction — the loop checkpoints only between
    dispatches). A cursor INSIDE a group can only mean the grouping
    changed between save and resume (K drift the config fingerprint
    did not cover); the group's unconsumed remainder is then delivered
    as per-step singles, loudly — deterministic, never replaying or
    dropping a step."""
    if skip_steps <= 0:
        return list(groups)
    out = []
    remaining = skip_steps
    for g in groups:
        if remaining >= len(g):
            remaining -= len(g)
            continue
        if remaining > 0:
            print(
                "[resume] step cursor lands inside a superstep group "
                f"(group of {len(g)}, {remaining} consumed) — "
                "delivering the remainder as per-step batches",
                flush=True,
            )
            out.extend([e] for e in g[remaining:])
            remaining = 0
        else:
            out.append(g)
    return out


def skip_delivered_items(items: list, skip_steps: int):
    """Cursor skip over already-collated delivery items (the superstep
    replay caches): each item covers ``k`` steps (MacroBatch) or 1.
    Only fixed-order eval loaders cache, and eval never resumes
    mid-pass, so a mid-item cursor is config drift; the whole item is
    skipped (under-running by < K steps) rather than replaying steps —
    a replayed optimizer step would corrupt the trajectory, a short
    eval epoch only perturbs one metric reading. Loud either way."""
    from hydragnn_tpu.data.graph import MacroBatch

    remaining = skip_steps
    for item in items:
        k = item.k if isinstance(item, MacroBatch) else 1
        if remaining >= k:
            remaining -= k
            continue
        if remaining > 0:
            print(
                "[resume] step cursor lands inside a cached superstep "
                f"delivery (k={k}, {remaining} consumed) — skipping "
                "the whole item",
                flush=True,
            )
            remaining = 0
            continue
        yield item


def superstep_cache_get(loader, k: int) -> Optional[list]:
    """The grouped-delivery cache shared by every superstep wrapper
    over one base loader — keyed by K so a K-mismatched wrapper
    re-collates rather than replaying wrong group boundaries."""
    cached = getattr(loader, "_superstep_cache", None)
    if cached is not None and cached[0] == int(k):
        return cached[1]
    return None


def superstep_cache_put(loader, k: int, items: list) -> None:
    try:
        loader._superstep_cache = (int(k), items)
    except (AttributeError, TypeError):
        pass  # exotic containers without attribute storage: no cache


def iter_loader_chain(loader, max_depth: int = 8):
    """Walk a feed-wrapper chain (PrefetchLoader / DPLoader / pipeline
    in any nesting, each exposing the wrapped loader as ``.loader``) —
    THE one traversal shared by every find-in-chain helper
    (``loader_packing_stats`` here, ``pipeline_stats`` in
    data/pipeline.py)."""
    seen = 0
    while loader is not None and seen < max_depth:
        yield loader
        loader = getattr(loader, "loader", None)
        seen += 1


def loader_packing_stats(loader) -> Optional[dict]:
    """Find the packing GraphLoader inside a wrapper chain and return
    its current-epoch ``packing_stats``, or None when the chain doesn't
    pack."""
    for ld in iter_loader_chain(loader):
        fn = getattr(ld, "packing_stats", None)
        if callable(fn):
            return fn()
    return None


def split_dataset(
    dataset: Sequence[GraphSample],
    perc_train: float,
    *,
    stratified: bool = False,
    seed: int = 0,
) -> tuple[List[GraphSample], List[GraphSample], List[GraphSample]]:
    """train/val/test split; val and test each get (1-perc_train)/2
    (reference: hydragnn/preprocess/load_data.py:337-385 split_dataset,
    compositional stratified variant
    hydragnn/utils/datasets/compositional_data_splitting.py:118-156)."""
    rng = np.random.default_rng(seed)
    if stratified:
        # Group samples by element composition (sorted unique node
        # feature signature) and split each category proportionally so
        # every split sees every composition; singleton categories are
        # duplicated across splits like the reference does.
        keys: dict = {}
        for i, s in enumerate(dataset):
            key = tuple(np.unique(np.round(s.x[:, 0], 6)))
            keys.setdefault(key, []).append(i)
        tr_idx: List[int] = []
        va_idx: List[int] = []
        te_idx: List[int] = []
        for _, idxs in sorted(keys.items()):
            idxs = list(idxs)
            rng.shuffle(idxs)
            if len(idxs) == 1:
                tr_idx += idxs
                va_idx += idxs
                te_idx += idxs
                continue
            k = len(idxs)
            n_tr = max(int(round(k * perc_train)), 1)
            n_va = max(int(round(k * (1.0 - perc_train) / 2.0)), 1)
            n_tr = min(n_tr, k - 1)
            tr_idx += idxs[:n_tr]
            va_idx += idxs[n_tr : n_tr + n_va]
            te_idx += idxs[n_tr + n_va :] or idxs[n_tr : n_tr + 1]
        for part in (tr_idx, va_idx, te_idx):
            rng.shuffle(part)
        return (
            [dataset[i] for i in tr_idx],
            [dataset[i] for i in va_idx],
            [dataset[i] for i in te_idx],
        )

    order = np.arange(len(dataset))
    rng.shuffle(order)
    n = len(order)
    n_train = int(n * perc_train)
    n_val = int(n * (1.0 - perc_train) / 2.0)
    train = [dataset[i] for i in order[:n_train]]
    val = [dataset[i] for i in order[n_train : n_train + n_val]]
    test = [dataset[i] for i in order[n_train + n_val :]]
    return train, val, test
