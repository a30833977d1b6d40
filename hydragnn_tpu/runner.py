"""End-to-end orchestration: run_training / run_prediction.

The TPU counterpart of the reference entry points
(hydragnn/run_training.py:59-211 and hydragnn/run_prediction.py:34-114):
config loading, dataset ingestion + splitting, ``update_config``
derivation, model + optimizer construction, the train loop, and final
model save. Distributed setup maps to jax.distributed + mesh creation
instead of DDP process groups.
"""

from __future__ import annotations

import os
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from hydragnn_tpu.config import load_config, save_config, update_config
from hydragnn_tpu.data.graph import GraphSample, select_input_features
from hydragnn_tpu.data.loader import GraphLoader, split_dataset
from hydragnn_tpu.data.raw import process_raw_samples, read_lsms_directory
from hydragnn_tpu.models.create import (
    create_model_config,
    init_params,
    needs_triplets,
)
from hydragnn_tpu.train.loop import test as run_test
from hydragnn_tpu.train.loop import train_validate_test
from hydragnn_tpu.train.optimizer import select_optimizer
from hydragnn_tpu.train.state import create_train_state, resolve_precision
from hydragnn_tpu.utils.checkpoint import (
    CheckpointWriter,
    checkpoint_settings,
    config_fingerprint,
    find_continue_log_name,
    load_checkpoint,
    load_checkpoint_sharded,
    load_resume_checkpoint,
    load_resume_checkpoint_sharded,
)
from hydragnn_tpu.utils.print_utils import (
    get_log_name_config,
    print_distributed,
    setup_log,
)


def _ingest_datasets(
    config: dict,
) -> Tuple[List[GraphSample], List[GraphSample], List[GraphSample]]:
    """Load train/val/test GraphSample lists per the Dataset section.

    Formats: ``unit_test`` / ``LSMS`` read raw text dirs (reference raw
    path, hydragnn/preprocess/lsms_raw_dataset_loader.py); ``pickle``
    reads serialized splits. ``Dataset.path`` may be a single ``total``
    dir (then split by perc_train) or per-split dirs.
    """
    ds = config.get("Dataset", {})
    fmt = ds.get("format", "unit_test")
    paths = ds.get("path", {})
    training = config["NeuralNetwork"]["Training"]
    perc_train = float(training.get("perc_train", 0.7))
    stratified = bool(ds.get("compositional_stratified_splitting", False))

    def _ingest_raw(reader):
        """Shared total-vs-per-split raw ingestion: normalization
        statistics always come from the union so splits share one scale."""
        if not isinstance(paths, dict):
            raise ValueError(
                f"Dataset.path must be a dict, got {type(paths)}"
            )
        if "total" in paths:
            samples = process_raw_samples(reader(paths["total"]), config)
            return split_dataset(samples, perc_train, stratified=stratified)
        raws = {
            split: reader(paths[split])
            for split in ("train", "validate", "test")
        }
        all_samples = process_raw_samples(
            raws["train"] + raws["validate"] + raws["test"], config
        )
        n_tr, n_va = len(raws["train"]), len(raws["validate"])
        return (
            all_samples[:n_tr],
            all_samples[n_tr : n_tr + n_va],
            all_samples[n_tr + n_va :],
        )

    if fmt in ("unit_test", "LSMS"):
        return _ingest_raw(lambda p: read_lsms_directory(p, ds))
    if fmt in ("CFG", "XYZ"):
        from hydragnn_tpu.data.formats import (
            read_cfg_directory,
            read_xyz_directory,
        )
        from hydragnn_tpu.data.raw import RawSample

        reader = read_cfg_directory if fmt == "CFG" else read_xyz_directory
        node_cols = ds.get("node_features", {}).get("column_index")
        graph_cols = ds.get("graph_features", {}).get("column_index")
        wants_graph_target = "graph" in config["NeuralNetwork"][
            "Variables_of_interest"
        ].get("type", [])

        def _to_raw(p):
            out = []
            for s in reader(p):
                if s.y_graph is None and wants_graph_target:
                    raise ValueError(
                        f"{fmt} sample in {p} has no graph target "
                        "sidecar (_energy.txt / .bulk) but the config "
                        "asks for a graph output"
                    )
                x = np.asarray(s.x, np.float64)
                if node_cols is not None:
                    x = x[:, node_cols]
                y = (
                    np.asarray(s.y_graph, np.float64)
                    if s.y_graph is not None
                    else np.zeros(1)
                )
                if graph_cols is not None and s.y_graph is not None:
                    y = y[graph_cols]
                out.append(
                    RawSample(
                        node_features=x,
                        positions=np.asarray(s.pos, np.float64),
                        graph_features=y,
                        cell=s.cell,
                    )
                )
            return out

        return _ingest_raw(_to_raw)
    if fmt == "pickle":
        from hydragnn_tpu.data.pickledataset import SimplePickleDataset

        # serialized samples carry original-width x: apply the
        # input_node_features selection (raw formats select during
        # processing; pickled/binary data is stored unselected)
        in_cols = _input_cols(config)
        out = []
        for split in ("train", "validate", "test"):
            out.append(
                select_input_features(
                    SimplePickleDataset(paths[split]), in_cols
                )
            )
        return tuple(out)
    if fmt in ("binary", "hgb", "adios"):
        from hydragnn_tpu.data.binformat import BinDataset

        if not isinstance(paths, dict) or not all(
            k in paths for k in ("train", "validate", "test")
        ):
            raise ValueError(
                "binary format needs Dataset.path with train/validate/"
                "test container files (write splits separately with "
                f"write_bin_dataset); got {paths!r}"
            )
        preload = bool(ds.get("preload", False))
        in_cols = _input_cols(config)
        out = []
        for split in ("train", "validate", "test"):
            out.append(
                select_input_features(
                    BinDataset(paths[split], preload=preload), in_cols
                )
            )
        return tuple(out)
    raise ValueError(f"Unknown Dataset.format: {fmt}")


def restore_checkpoint_state(config, training, model, example, tx=None):
    """Rebuild a TrainState and load the run's checkpoint (the shared
    restore core of run_prediction and the export CLI — one place to
    grow when checkpoint formats or state fields change). ``tx`` must
    match the optimizer the checkpoint was trained with (the multibranch
    scheme passes its dual optimizer so the opt_state trees line up)."""
    params, batch_stats = init_params(model, example)
    if tx is None:
        tx = select_optimizer(training)
    state = create_train_state(params, tx, batch_stats)
    # A config that round-tripped through run_training carries the
    # actual run dir; a fresh config derives it — and when the derived
    # dir is empty (num_epoch extended since training, so the name
    # drifted — docs/DURABILITY.md) the load would only raise, so
    # resolve to the sibling run dir that has the artifacts, loudly.
    log_name = config.get("_log_name") or find_continue_log_name(
        get_log_name_config(config),
        fingerprint=config_fingerprint(config),
    )
    if str(training.get("checkpoint_format", "msgpack")) == "orbax":
        return load_checkpoint_sharded(log_name, state)
    return load_checkpoint(log_name, state)


def _input_cols(config: dict):
    """Variables_of_interest.input_node_features, or None."""
    return (
        config.get("NeuralNetwork", {})
        .get("Variables_of_interest", {})
        .get("input_node_features")
    )


def _check_num_nodes_bound(config: dict, *datasets) -> None:
    """Fail fast when graphs exceed the static per-graph node bound used
    by GPS dense attention / mlp_per_node heads (silently-degraded
    outputs otherwise — the dense scatter drops out-of-bound nodes)."""
    arch = config["NeuralNetwork"]["Architecture"]
    heads = arch.get("output_heads", {})
    needs_bound = bool(arch.get("global_attn_engine")) or (
        isinstance(heads.get("node"), dict)
        and heads["node"].get("type") == "mlp_per_node"
    )
    bound = arch.get("num_nodes")
    if not needs_bound or bound is None:
        return
    def _max_nodes(ds):
        sizes = getattr(ds, "sample_sizes", None)
        if callable(sizes):
            n, _ = sizes()
            return int(max(n)) if len(n) else 0
        return max((s.num_nodes for s in ds), default=0)

    max_n = max((_max_nodes(ds) for ds in datasets if len(ds)), default=0)
    if max_n > int(bound):
        raise ValueError(
            f"Graph with {max_n} nodes exceeds Architecture.num_nodes="
            f"{bound}; raise num_nodes (it must bound every split)"
        )


def _resolve_fixed_pad(scheme: str, verbosity: int = 0):
    """Variable-graph-size mode (reference
    HYDRAGNN_USE_VARIABLE_GRAPH_SIZE, config_utils.py:29): pad each
    batch up a bucket ladder instead of one worst-case shape — fewer
    padded FLOPs, a bounded handful of compiles. On the single scheme
    the loader buckets each batch independently; dp/multibranch use a
    shared per-step spec schedule instead (data/padschedule.py), since
    stacked device sub-batches must share one padded shape.

    Default (env unset or "auto") is AUTO: the ladder is taken when the
    simulated spec count stays within HYDRAGNN_TPU_MAX_PAD_BUCKETS
    distinct shapes — padding waste drops to the ladder growth factor
    by default, without an open-ended compile count. "1"/"true" forces
    the ladder, "0"/"false" forces the single worst-case shape.
    """
    raw = (
        os.environ.get("HYDRAGNN_TPU_USE_VARIABLE_GRAPH_SIZE", "auto")
        .strip()
        .lower()
    )
    if raw in ("0", "false"):
        return True
    if raw in ("1", "true"):
        return False
    return "auto"


def _dp_pad_schedules(
    plan, mode, batch_size, seed, trips, datasets, verbosity=0
):
    """Resolve dp-scheme padding into per-split spec schedules, or
    (None, None, None) for the fixed worst-case spec.

    The schedules are built from the FULL (pre-shard) datasets so every
    host process computes the identical per-step spec — a stacked dp
    batch is one global array, so its padded shape must agree across
    processes (padschedule.dp_spec_schedule)."""
    from hydragnn_tpu.data.padschedule import (
        dataset_size_arrays,
        dp_spec_schedule,
    )

    fixed = (None, None, None)
    if mode is True:
        return fixed
    if trips:
        if mode is False:
            print_distributed(
                verbosity,
                0,
                "HYDRAGNN_TPU_USE_VARIABLE_GRAPH_SIZE ignored: triplet "
                "counts need full edge decodes, so triplet-bearing "
                "models keep the fixed worst-case pad",
            )
        return fixed
    n_local = max(plan.data_parallel_size // jax.process_count(), 1)

    def _sched(ds, shuffle, sched_seed):
        ns, es = dataset_size_arrays(ds)
        return dp_spec_schedule(
            ns,
            es,
            batch_size=batch_size,
            n_procs=jax.process_count(),
            steps_group=n_local,
            seed=sched_seed,
            shuffle=shuffle,
        )

    trainset, valset, testset = datasets
    cand = _sched(trainset, True, seed)
    if mode == "auto" and not cand.ladder_is_small():
        return fixed
    return (cand, _sched(valset, False, 0), _sched(testset, False, 0))


def _resolve_packing(
    plan,
    trips,
    batch_size,
    trainset,
    verbosity=0,
    *,
    fixed_pad="auto",
    seed=0,
):
    """Resolve the plan's bin-packed batch forming for this run.

    Returns ``(packing_on, train_budgets, fitted_slack)`` — the slack
    the train-histogram fit chose, forwarded to eval loaders so their
    per-split budget fits skip the candidate simulation. Packing
    applies on the single scheme (per-batch bins) and on
    SINGLE-PROCESS dp meshes (device-coordinated bins,
    padschedule.pack_epoch_ffd_dp: every device-group of bins shares a
    budget and every device steps the same number of times) — never on
    multibranch, multi-host dp (process shards would pack divergent
    plans; they keep the cross-process spec schedules), or
    triplet-bearing models (budgets do not cover triplet counts).
    Explicit requests outside that envelope warn and fall back.
    ``"auto"`` (the default) packs when the fitted budgets beat the
    run's ACTUAL no-packing baseline — ``fixed_pad`` (the resolved
    HYDRAGNN_TPU_USE_VARIABLE_GRAPH_SIZE mode) picks ladder vs
    worst-case — by the simulated padding-waste margin
    (padschedule.packing_beats_ladder / dp_packing_beats_schedule,
    device-free size arithmetic over the run's own ``seed`` epoch
    orders; the dp form also proves the coordination feasible)."""
    mode = plan.packing
    if not mode:
        return False, None, None
    n_shards = 0
    blocked = None
    if plan.scheme == "dp":
        if jax.process_count() > 1:
            blocked = (
                "multi-host dp shards would pack divergent per-process "
                "plans; the cross-process spec schedules coordinate "
                "shapes there"
            )
        else:
            n_shards = plan.data_parallel_size
    elif plan.scheme != "single":
        blocked = (
            f"the {plan.scheme} scheme needs cross-process coordinated "
            "shapes"
        )
    if blocked is None:
        if trips:
            blocked = "packing budgets do not cover triplet counts"
        elif not len(trainset):
            blocked = "empty training set"
        elif n_shards > 1 and len(trainset) < n_shards:
            blocked = (
                f"{len(trainset)} training graphs cannot feed "
                f"{n_shards} devices a coordinated packed plan"
            )
    if blocked:
        if mode != "auto":  # explicitly requested: tell the user
            print_distributed(
                verbosity,
                0,
                f"Training.Parallelism.packing ignored: {blocked}",
            )
        return False, None, None
    from hydragnn_tpu.data.padschedule import (
        dataset_size_arrays,
        dp_packing_beats_schedule,
        fit_pack_budgets,
        packing_beats_ladder,
    )

    ns, es = dataset_size_arrays(trainset)
    kw = dict(
        max_budgets=plan.packing_max_budgets,
        slack=plan.packing_slack,
        max_graphs=plan.packing_max_graphs,
        seed=int(seed),
    )
    if mode == "auto":
        # fixed_pad True = forced worst-case spec, False = forced
        # ladder, "auto" = the loader's/schedule's own clamp simulation.
        baseline = (
            "worst"
            if fixed_pad is True
            else ("ladder" if fixed_pad is False else "auto")
        )
        if n_shards > 1:
            won = dp_packing_beats_schedule(
                ns, es, batch_size, n_shards, baseline=baseline, **kw
            )
        else:
            won = packing_beats_ladder(
                ns, es, batch_size, baseline=baseline, **kw
            )
        if won is None:
            return False, None, None
        print_distributed(
            verbosity,
            2,
            "packing: auto-enabled (fitted budgets beat the run's "
            "no-packing baseline padding waste)",
        )
        return True, won[0], won[1]
    if plan.packing_slack is not None:
        # Slack pinned by config: no candidate simulation to run, and
        # the with_meta waste number would be computed only to be
        # discarded.
        return (
            True,
            fit_pack_budgets(ns, es, batch_size, **kw),
            plan.packing_slack,
        )
    budgets, meta = fit_pack_budgets(
        ns, es, batch_size, with_meta=True, **kw
    )
    # Explicitly-requested dp packing is NOT probed for coordination
    # feasibility here: run_training forces each split's epoch-0
    # coordinated pack right after loader construction (the result is
    # cached on the loader, so the work is paid once) and falls back
    # loudly there.
    return True, budgets, meta["slack"]


def _pin_full_worst_specs(loaders_and_datasets, batch_size, trips):
    """Multi-host fixed-pad consistency: every process pads to the
    worst case of the FULL dataset, not of its local shard — shards are
    heterogeneous, and a stacked dp batch's global shape must be
    identical on every process."""
    from hydragnn_tpu.data.graph import PadSpec, bucket_size, count_triplets
    from hydragnn_tpu.data.padschedule import (
        dataset_size_arrays,
        worst_case_spec_from_sizes,
    )

    for loader, full in loaders_and_datasets:
        ns, es = dataset_size_arrays(full)
        spec = worst_case_spec_from_sizes(ns, es, batch_size)
        if trips:
            t_sizes = sorted(
                (count_triplets(s) for s in full), reverse=True
            )
            spec = PadSpec(
                num_nodes=spec.num_nodes,
                num_edges=spec.num_edges,
                num_graphs=spec.num_graphs,
                num_triplets=bucket_size(
                    max(sum(t_sizes[:batch_size]), 1)
                ),
            )
        loader.pad_spec = spec


def run_training(
    config_source,
    datasets: Optional[
        Tuple[Sequence[GraphSample], Sequence[GraphSample], Sequence[GraphSample]]
    ] = None,
    *,
    seed: int = 0,
):
    """Train end-to-end from a JSON config (path or dict).

    Parallelism is automatic (reference auto-wraps DDP,
    run_training.py:105): with >1 visible device the run is
    data-parallel over a ``data`` mesh axis; ``Training.Parallelism``
    (or ``HYDRAGNN_TPU_MESH``) configures mesh axes / FSDP / scheme —
    see hydragnn_tpu/parallel/runtime.py. For the multibranch scheme
    pass ``datasets`` as a list of per-branch (train, val, test)
    triples. Under a multi-process launcher every process calls this
    same function (SPMD).

    Returns (state, model, cfg, history, config).
    """
    from hydragnn_tpu.utils import telemetry

    # Where the time before the first steady epoch goes, phase by phase:
    # ``setup`` rows in the telemetry stream (docs/OBSERVABILITY.md).
    setup = telemetry.SetupClock()
    try:
        return _run_training(config_source, datasets, seed, setup)
    finally:
        setup.close()


def _run_training(config_source, datasets, seed, setup):
    from hydragnn_tpu.parallel import runtime
    from hydragnn_tpu.utils.runtime import maybe_enable_compilation_cache

    setup.phase("config")
    runtime.maybe_initialize_distributed()
    maybe_enable_compilation_cache()
    config = load_config(config_source)
    verbosity = int(config.get("Verbosity", {}).get("level", 0))
    plan = runtime.plan_from_config(config)

    multibranch = plan.scheme == "multibranch"
    branch_sets: Optional[List[Tuple]] = None
    if multibranch:
        if datasets is None or not all(
            isinstance(d, (tuple, list)) and len(d) == 3 for d in datasets
        ):
            raise ValueError(
                "multibranch scheme needs datasets=[(train,val,test), "
                "...] per branch"
            )
        in_cols = _input_cols(config)
        branch_sets = [
            tuple(select_input_features(list(s), in_cols) for s in d)
            for d in datasets
        ]
        trainset = [s for d in branch_sets for s in d[0]]
        valset = [s for d in branch_sets for s in d[1]]
        testset = [s for d in branch_sets for s in d[2]]
    elif datasets is None:
        # raw ingestion applies input_node_features itself (data/raw.py)
        trainset, valset, testset = _ingest_datasets(config)
    else:
        in_cols = _input_cols(config)
        # No list() wrapper: select_input_features passes lazy dataset
        # objects through untouched when the selection is a no-op.
        trainset, valset, testset = (
            select_input_features(d, in_cols) for d in datasets
        )

    config = update_config(config, trainset, valset, testset)
    _check_num_nodes_bound(config, trainset, valset, testset)
    log_name = get_log_name_config(config)
    if config["NeuralNetwork"]["Training"].get("continue"):
        # The derived name encodes num_epoch; a continue that extends
        # the run must still find the checkpoints it is continuing
        # (docs/DURABILITY.md "extending a run keeps the cursor").
        log_name = find_continue_log_name(
            log_name,
            preferred=config.get("_log_name"),
            fingerprint=config_fingerprint(config),
        )
    if verbosity > 0:
        setup_log(log_name)
    save_config(config, log_name)
    config["_log_name"] = log_name

    # HYDRAGNN_TPU_TRACE_LEVEL > 0: install the region timer so the
    # loop's tr.region sites actually record (reference wires
    # tr.initialize in its drivers; here the runner owns it).
    trace_env = os.environ.get("HYDRAGNN_TPU_TRACE_LEVEL", "")
    if trace_env.strip().isdigit() and int(trace_env) > 0:
        from hydragnn_tpu.utils import tracer as tr

        if not tr.has("RegionTimer"):
            tr.initialize(["RegionTimer"])

    training = config["NeuralNetwork"]["Training"]
    _, compute_dtype = resolve_precision(training.get("precision", "fp32"))

    # Training.segment_impl: config-surface twin of
    # HYDRAGNN_TPU_SEGMENT_IMPL (the env var wins), so runs can pin
    # the aggregation kernel flavor (xla | pallas | pallas_fused)
    # without shell plumbing. Set on EVERY run — absent/empty CLEARS
    # the override back to crossover-table dispatch
    # (ops/segment.planned_path_wanted), so consecutive run_training
    # calls in one process can't inherit each other's flavor.
    seg_impl = training.get("segment_impl", "")
    if seg_impl and seg_impl not in ("xla", "pallas", "pallas_fused"):
        raise ValueError(
            f"Training.segment_impl {seg_impl!r} not in "
            "('xla', 'pallas', 'pallas_fused')"
        )
    from hydragnn_tpu.ops.segment import set_segment_impl_override

    set_segment_impl_override(seg_impl)

    setup.phase("loaders")  # the packing fit and the pad plans included
    batch_size = int(training.get("batch_size", 32))
    trips = needs_triplets(
        config["NeuralNetwork"]["Architecture"].get("mpnn_type", "SchNet")
    )
    model, cfg = create_model_config(config)
    recal_loader = None

    if multibranch:
        from hydragnn_tpu.data.prefetch import PrefetchLoader
        from hydragnn_tpu.parallel.multibranch import (
            MultiBranchLoader,
            dual_optimizer,
            proportional_branch_split,
        )

        # Multi-host multibranch: every process must pass the SAME full
        # per-branch datasets (MultiBranchLoader builds all slot loaders
        # deterministically and iterates only its local slice).
        if training.get("use_segment_plan"):
            print_distributed(
                verbosity,
                0,
                "Training.use_segment_plan ignored: supported on the "
                "single scheme only",
            )
        # Proportional split by dataset size (default) or uniform
        # (reference HYDRAGNN_TASK_PARALLEL_PROPORTIONAL_SPLIT,
        # USER_MANUAL.md FSDP/task-parallel notes).
        if os.environ.get(
            "HYDRAGNN_TPU_TASK_PARALLEL_PROPORTIONAL_SPLIT", "1"
        ) in ("0", "false"):
            k = len(branch_sets)
            if plan.data_parallel_size < k:
                raise ValueError(
                    f"{plan.data_parallel_size} devices < {k} branches"
                )
            base, rem = divmod(plan.data_parallel_size, k)
            dpb = [base + (1 if i < rem else 0) for i in range(k)]
        else:
            dpb = proportional_branch_split(
                [len(d[0]) for d in branch_sets], plan.data_parallel_size
            )
        import dataclasses as _dc

        plan = _dc.replace(
            plan, scheme="multibranch", devices_per_branch=tuple(dpb)
        )
        if plan.pipeline_workers > 0:
            # The parallel input pipeline drives GraphLoader pad plans;
            # MultiBranchLoader owns its per-slot loaders internally, so
            # the multibranch scheme keeps the single-thread prefetch
            # feed (the ``workers: 0`` fallback path).
            print_distributed(
                verbosity,
                2,
                "input pipeline: multibranch scheme uses the "
                "single-thread prefetch feed (pipeline.workers ignored)",
            )
        mode = _resolve_fixed_pad(plan.scheme, verbosity)
        var_pad = False if mode is True else ("auto" if mode == "auto" else True)
        if trips and var_pad:
            if mode is False:  # explicitly forced, tell the user
                print_distributed(
                    verbosity,
                    0,
                    "HYDRAGNN_TPU_USE_VARIABLE_GRAPH_SIZE ignored: "
                    "triplet counts need full edge decodes, so "
                    "triplet-bearing models keep the fixed worst-case "
                    "pad",
                )
            var_pad = False
        train_loader = MultiBranchLoader(
            [d[0] for d in branch_sets], dpb, batch_size, plan.mesh,
            shuffle=True, seed=seed, with_triplets=trips,
            variable_pad=var_pad,
        )
        val_loader = MultiBranchLoader(
            [d[1] for d in branch_sets], dpb, batch_size, plan.mesh,
            shuffle=False, seed=seed, with_triplets=trips,
            variable_pad=var_pad,
        )
        test_loader = MultiBranchLoader(
            [d[2] for d in branch_sets], dpb, batch_size, plan.mesh,
            shuffle=False, seed=seed, with_triplets=trips,
            variable_pad=var_pad,
        )
        init_loader = train_loader.loaders[0]
        if plan.prefetch > 0:
            # Same overlap as the dp path: collation + stack + sharded
            # device_put run in a worker thread one step ahead.
            train_loader = PrefetchLoader(
                train_loader, depth=plan.prefetch, to_device=False
            )
            val_loader = PrefetchLoader(
                val_loader, depth=plan.prefetch, to_device=False
            )
            test_loader = PrefetchLoader(
                test_loader, depth=plan.prefetch, to_device=False
            )
        tx = dual_optimizer(training)
    else:
        # Each host process trains on its own equal-size dataset shard
        # (reference DistributedSampler semantics).
        trainset_p = runtime.shard_dataset_for_process(trainset)
        valset_p = runtime.shard_dataset_for_process(valset)
        testset_p = runtime.shard_dataset_for_process(testset)
        fixed_pad = _resolve_fixed_pad(plan.scheme, verbosity)
        pad_mode = fixed_pad  # pre-dp-pin mode: the packing baseline
        # Sorted-segment block plans for the Pallas aggregation kernel
        # (ops/pallas_segment.py). Single scheme only: the planned
        # pallas_call is not exercised under the dp step's vmap.
        # Default "auto": pipeline workers attach the plan (edge sort +
        # block windows, host-side) only for padded shapes on the
        # kernel's winning side of the ROOFLINE crossover table, and
        # aggregate_receivers dispatches from the same table — so the
        # MXU path is fed wherever it wins with zero per-step host
        # planning, and oc20-class shapes keep the XLA scatter.
        seg_plan = training.get("use_segment_plan", "auto")
        if seg_plan == "auto":
            seg_plan = "auto" if plan.scheme == "single" else False
        else:
            seg_plan = bool(seg_plan)
            if seg_plan and plan.scheme != "single":
                print_distributed(
                    verbosity,
                    0,
                    "Training.use_segment_plan ignored: supported on "
                    "the single scheme only",
                )
                seg_plan = False
        # One optional-field map over the FULL (pre-shard) datasets:
        # per-shard maps can diverge across processes (a rare field in
        # one process's shard only) and stall collectives with
        # mismatched global-array structures. The multi-dataset merge
        # keeps lazy containers lazy (metadata fast path per split).
        from hydragnn_tpu.data.graph import optional_field_widths_multi

        ensure = optional_field_widths_multi(
            [trainset, valset, testset]
        )
        # Bin-packed batch forming (the tentpole default former on the
        # single scheme, device-coordinated on single-process dp):
        # pack_budgets are fitted from the TRAIN size histogram; eval
        # loaders fit their own over their split.
        packing_on, pack_budgets, pack_slack = _resolve_packing(
            plan, trips, batch_size, trainset_p, verbosity,
            fixed_pad=pad_mode, seed=seed,
        )

        # The cross-process spec schedules apply only to unpacked dp
        # splits — built lazily, so a fully-packed dp run (and the
        # single scheme) never pays for them.
        _scheds_cache: List = []

        def _scheds():
            if not _scheds_cache:
                _scheds_cache.append(
                    _dp_pad_schedules(
                        plan, pad_mode, batch_size, seed, trips,
                        (trainset, valset, testset), verbosity,
                    )
                    if plan.scheme == "dp"
                    else (None, None, None)
                )
            return _scheds_cache[0]

        def _build_loader(which, dataset, packed):
            sched = None
            fp = fixed_pad
            if plan.scheme == "dp":
                if not packed:
                    sched = _scheds()[which]
                # Loaders under dp never bucket independently: the
                # packed plan, the shared schedule, or the fixed worst
                # case drives the spec.
                fp = True
            # Eval loaders fit budgets over their own split but reuse
            # the train-tuned slack — one budget construction, no
            # re-simulation.
            pack_kw = dict(
                packing=packed,
                pack_max_budgets=plan.packing_max_budgets,
                pack_slack=(
                    plan.packing_slack
                    if plan.packing_slack is not None
                    else pack_slack
                ),
                pack_max_graphs=plan.packing_max_graphs,
                pack_dp_shards=(
                    plan.data_parallel_size
                    if packed and plan.scheme == "dp"
                    else 0
                ),
            )
            # Receiver-sorted edges with the batch saying so, for XLA's
            # sorted scatter in the aggregation (ops/segment.py). Single
            # scheme only, like the block plans: the other schemes'
            # collators promise nothing, and their batches keep the
            # plain scatter.
            sort_receivers = plan.scheme == "single"
            if which == 0:
                return GraphLoader(
                    dataset, batch_size, shuffle=True, seed=seed,
                    with_triplets=trips, fixed_pad=fp,
                    with_segment_plan=seg_plan, ensure_fields=ensure,
                    spec_schedule=sched, sort_receivers=sort_receivers,
                    pack_budgets=pack_budgets if packed else None,
                    **pack_kw,
                )
            # Fixed-order eval loaders produce identical batches every
            # epoch — cache the collated batches (in-memory datasets
            # only; lazy containers keep their memory profile).
            return GraphLoader(
                dataset, batch_size, with_triplets=trips,
                fixed_pad=fp, with_segment_plan=seg_plan,
                ensure_fields=ensure,
                cache_batches=isinstance(dataset, list),
                spec_schedule=sched, sort_receivers=sort_receivers,
                **pack_kw,
            )

        split_sets = (trainset_p, valset_p, testset_p)
        split_names = ("train", "val", "test")
        loaders = [
            _build_loader(i, ds, packing_on)
            for i, ds in enumerate(split_sets)
        ]
        if packing_on and plan.scheme == "dp":
            # Force each split's epoch-0 coordinated pack NOW (the
            # result stays cached on the loader): the canonical packing
            # order makes feasibility epoch-invariant, so a split that
            # passes here can never raise mid-train. A split too small
            # (or too singleton-binned) to feed every device falls back
            # to the spec-schedule former PER SPLIT — a 5-graph test
            # set must not cost the train loader its packed fast path.
            for i, ds in enumerate(split_sets):
                try:
                    len(loaders[i])
                except ValueError as e:
                    print_distributed(
                        verbosity,
                        0,
                        f"Training.Parallelism.packing disabled for "
                        f"the {split_names[i]} split: {e}",
                    )
                    loaders[i] = _build_loader(i, ds, False)
        base_train, base_val, base_test = loaders
        scheds = _scheds_cache[0] if _scheds_cache else (None, None, None)
        if (
            plan.scheme == "dp"
            and scheds[0] is None
            and jax.process_count() > 1
        ):
            _pin_full_worst_specs(
                [
                    (base_train, trainset),
                    (base_val, valset),
                    (base_test, testset),
                ],
                batch_size,
                trips,
            )
        init_loader = base_train
        train_loader = runtime.wrap_loader(plan, base_train, train=True)
        val_loader = runtime.wrap_loader(plan, base_val)
        test_loader = runtime.wrap_loader(plan, base_test)
        from hydragnn_tpu.train.loop import _bn_recalibration_epochs

        if (
            _bn_recalibration_epochs(training) > 0
            and plan.scheme == "single"
        ):
            # BN recalibration reads this eval-shaped feed: plain
            # unpacked bucketed batches of the train split, matching
            # the compositions eval/run_prediction batches with. The
            # packed train loader is the wrong feed for stat pooling —
            # train-mode BN makes deep-layer features composition-
            # dependent and FFD bins are size-correlated (see
            # train/loop.recalibrate_batch_stats).
            recal_loader = GraphLoader(
                trainset_p, batch_size, with_triplets=trips,
                ensure_fields=ensure,
            )
        if plan.pipeline_workers > 0:
            print_distributed(
                verbosity,
                2,
                f"input pipeline: workers={plan.pipeline_workers} "
                f"depth={plan.pipeline_depth} "
                f"packed={plan.pipeline_packed} "
                f"chunk={plan.pipeline_chunk}",
            )
        tx = select_optimizer(training)

    setup.phase("model_init")
    example = next(iter(init_loader))
    params, batch_stats = init_params(model, example, seed=seed)
    n_params = sum(
        int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params)
    )
    print_distributed(verbosity, 1, f"Model parameters: {n_params}")
    if verbosity >= 2:
        # Reference print_peak_memory after model creation
        # (run_training.py:100-113, distributed.py:566-581).
        from hydragnn_tpu.utils.runtime import print_peak_memory

        print_peak_memory(lambda m: print_distributed(verbosity, 2, m))

    setup.phase("state")  # optimizer state, restore, mesh placement
    state = create_train_state(params, tx, batch_stats)

    # "orbax" writes every process's shards directly (no host gather;
    # scales past single-host state sizes); default msgpack gathers to
    # process 0. Orbax restores onto the prepared (mesh-placed) state's
    # exact sharding layout, so it loads AFTER prepare_state.
    ckpt_format = str(training.get("checkpoint_format", "msgpack"))
    resume = bool(training.get("continue", 0))
    fingerprint = config_fingerprint(config)
    resume_manifest = None
    if resume and ckpt_format != "orbax":
        state, resume_manifest = load_resume_checkpoint(log_name, state)
    state = runtime.prepare_state(plan, state)
    if resume and ckpt_format == "orbax":
        state, resume_manifest = load_resume_checkpoint_sharded(
            log_name, state
        )
    if resume_manifest is not None:
        # The cursor is only valid under the SAME deterministic batch
        # plan (config + seed); anything else falls back to the legacy
        # epoch-0 continue from the restored weights, loudly.
        mf = resume_manifest.get("config_fingerprint")
        ms = resume_manifest.get("plan_seed")
        if (mf is not None and mf != fingerprint) or (
            ms is not None and int(ms) != int(seed)
        ):
            print_distributed(
                verbosity,
                0,
                "resume manifest ignored: config fingerprint or plan "
                f"seed changed since the checkpoint (saved {mf}/{ms}, "
                f"now {fingerprint}/{seed}) — the (epoch, step) cursor "
                "no longer addresses the same batch sequence; "
                "restarting from epoch 0 with the restored weights",
            )
            resume_manifest = None
        elif multibranch:
            # Multibranch mid-epoch cursors are live since the scheme
            # gained plan-domain skip_to (MultiBranchLoader.skip_to,
            # docs/DURABILITY.md): every branch's feed fast-forwards
            # its own epoch_plan replay. The manifest's per-branch
            # cursors must still agree with the global one — the loop
            # consumes every branch in LOCKSTEP, so a drifted
            # container (foreign writer, future per-branch pacing)
            # cannot be honored and degrades to the epoch-0 warm
            # restart instead of replaying one branch's consumed
            # steps.
            bs = resume_manifest.get("branch_steps")
            step = int(resume_manifest.get("step", 0))
            if bs is not None and any(int(b) != step for b in bs):
                print_distributed(
                    verbosity,
                    0,
                    "resume manifest ignored: per-branch cursors "
                    f"{bs} disagree with the global step {step} — the "
                    "multibranch feed consumes branches in lockstep "
                    "and cannot honor a drifted container; restarting "
                    "from epoch 0 with the restored weights",
                )
                resume_manifest = None

    # Run telemetry (docs/OBSERVABILITY.md): the structured JSONL step
    # stream + compile/retrace observer, config-gated via
    # Training.Telemetry / HYDRAGNN_TPU_TELEMETRY*. EVERY process
    # streams its own shard (configure resolves shard_path: process 0
    # keeps the legacy path, process i writes telemetry.proc<i>.jsonl
    # next to it — graftboard fleet merges them; docs/OBSERVABILITY.md
    # "Fleet observability"). Configured HERE, immediately before the
    # try/finally that owns its teardown: a setup failure (bad arch,
    # missing continue checkpoint, loader envelope error) must not
    # leak the worker thread or the installed observer into the next
    # in-process trial (the HPO-driver leak class writer.close() below
    # guards against).
    from hydragnn_tpu.utils import telemetry

    setup.phase("stream")  # the telemetry stream, the checkpoint writer
    tel_stream = telemetry.configure(
        training,
        log_name=log_name,
        meta={"log_name": log_name, "scheme": plan.scheme},
    )
    setup.stream_ready()
    if telemetry.active():
        # Run context for the step clock: the model config keys the
        # live MFU rows (utils/flops.model_flops_per_graph), the
        # scheme labels the step-time breakdown.
        telemetry.set_context(model_cfg=cfg, scheme=plan.scheme, epoch=0)
        # Baseline memory row before the first step: every later
        # epoch/compile row reads as a delta against this.
        telemetry.emit_memory("run_start")

    ckpt_keep = int(training.get("checkpoint_keep", 5))
    ckpt_set = checkpoint_settings(training)
    writer = CheckpointWriter(
        log_name,
        fmt=ckpt_format,
        mesh=plan.mesh,
        keep=ckpt_keep,
        retries=ckpt_set.retries,
        backoff_s=ckpt_set.backoff_s,
        async_enabled=ckpt_set.async_enabled,
        plan_seed=int(seed),
        fingerprint=fingerprint,
        validate_finite=ckpt_set.validate_finite,
    )

    try:
        setup.end_phase()
        state, hist = train_validate_test(
            model,
            cfg,
            state,
            tx,
            train_loader,
            val_loader,
            test_loader,
            config,
            compute_dtype=compute_dtype,
            verbosity=verbosity,
            plan=plan,
            writer=writer,
            resume=resume_manifest,
            recal_loader=recal_loader,
        )
        # Success path, still inside the try: the loop performed the
        # end-of-run save (kind="final" with the loop state aboard) —
        # drain the async writer (close() never raises on a write
        # failure, it surfaces on writer.last_error; the second
        # close() in the finally below is an idempotent no-op), THEN
        # the cross-process final barrier: no process returns before
        # the end-of-run checkpoint is durable on the shared
        # filesystem (process 0 writes it; without this barrier
        # another process can exit/reload first — the reference
        # brackets rank-0 saves with dist.barrier the same way).
        # Rides the coordination service, not an XLA collective: it
        # must work on backends whose XLA has no multi-process
        # computations and must never queue device work behind a dead
        # process. Runs BEFORE the stream teardown in the finally so
        # its barrier row lands in the shard (fleet attribution of
        # end-of-run stragglers). An errored process skips the
        # barrier — it must not park 600s on a rendezvous it cannot
        # honor; its peers' waits time out loudly.
        writer.close()
        if jax.process_count() > 1:
            from hydragnn_tpu.utils.checkpoint import _process_barrier

            # graftlint: disable-next-line=barrier-discipline -- the sanctioned end-of-run fallback site: reached exactly once per process per run, so the call-site counter cannot desync (docs/DURABILITY.md "Barrier identity")
            _process_barrier("final_checkpoint")
    finally:
        # On the error path too: repeated in-process trials (the HPO
        # drivers) must not accumulate worker threads each holding a
        # full host-state snapshot.
        writer.close()
        # Tear down only the stream THIS call configured (an
        # externally installed stream — tests driving several runs —
        # stays live): observer summary + close row land first, then
        # the worker drains. Post-run compiles (run_test collection,
        # Visualizer) therefore never read as retrace leaks.
        telemetry.close_run(tel_stream)

    # End-of-run plots (reference train_validate_test.py:441-491 driven
    # by the Visualization config section). Per-sample collection runs
    # single-process only.
    if (
        config.get("Visualization", {}).get("create_plots", False)
        and jax.process_count() == 1
        and jax.process_index() == 0
    ):
        from hydragnn_tpu.postprocess import Visualizer

        viz_loader = GraphLoader(testset, batch_size, with_triplets=trips)
        _, _, trues, preds = run_test(
            model,
            cfg,
            state,
            viz_loader,
            compute_dtype=compute_dtype,
            compute_grad_energy=cfg.enable_interatomic_potential,
        )
        if cfg.enable_interatomic_potential:
            names = ["energy", "forces"]  # run_test's MLIP collections
        else:
            names = [h.name for h in cfg.heads]
        viz = Visualizer(log_name, num_heads=len(names))
        viz.create_scatter_plots(trues, preds, output_names=names)
        viz.plot_history(hist.train_loss, hist.val_loss, hist.test_loss)
        viz.num_nodes_plot(
            [trainset, valset, testset], ["train", "val", "test"]
        )
        vcfg = config.get("Visualization", {})
        if vcfg.get("error_histograms", True):
            viz.create_error_histograms(trues, preds, output_names=names)
        if vcfg.get("global_analysis", True):
            viz.create_plot_global(trues, preds, output_names=names)
        if vcfg.get("task_history", True):
            viz.plot_task_history(hist.train_tasks, task_names=names)
        if cfg.enable_interatomic_potential and trues[1].ndim == 2:
            viz.create_parity_plot_vector(trues[1], preds[1], name="forces")

    # Flush tracer regions — the reference dumps GPTL/region CSVs at
    # the end of its drivers.
    from hydragnn_tpu.utils import tracer as tr

    if tr.has("RegionTimer"):
        tr.save(log_name)
    return state, model, cfg, hist, config


def _multibranch_prediction(config, datasets, *, state=None, model=None, cfg=None):
    """Prediction under the multibranch scheme (the reference runs
    prediction through the same wrapper it trained with,
    hydragnn/run_prediction.py:62-71): every branch's test split runs
    through the trained multibranch state, with each sample's
    ``dataset_id`` routing it to its branch's decoder heads exactly as
    in training. Per-sample collections are keyed by branch: returns
    (error, per_task_error, trues, preds) where trues/preds are lists
    over branches of per-head arrays."""
    import dataclasses

    if datasets is None or not all(
        isinstance(d, (tuple, list)) and len(d) == 3 for d in datasets
    ):
        raise ValueError(
            "multibranch prediction needs datasets=[(train,val,test), "
            "...] per branch (the same structure run_training takes)"
        )
    in_cols = _input_cols(config)
    branch_sets = [
        tuple(select_input_features(list(s), in_cols) for s in d)
        for d in datasets
    ]
    trainset = [s for d in branch_sets for s in d[0]]
    valset = [s for d in branch_sets for s in d[1]]
    testset = [s for d in branch_sets for s in d[2]]
    config = update_config(config, trainset, valset, testset)
    _check_num_nodes_bound(config, trainset, valset, testset)
    training = config["NeuralNetwork"]["Training"]
    _, compute_dtype = resolve_precision(training.get("precision", "fp32"))
    batch_size = int(training.get("batch_size", 32))
    trips = needs_triplets(
        config["NeuralNetwork"]["Architecture"].get("mpnn_type", "SchNet")
    )
    if model is None or cfg is None:
        model, cfg = create_model_config(config)

    # dataset_id routing + one shared optional-field map across branches
    # (batches must keep the train-time pytree structure).
    from hydragnn_tpu.data.graph import optional_field_widths

    branch_tests = [
        [dataclasses.replace(s, dataset_id=bi) for s in d[2]]
        for bi, d in enumerate(branch_sets)
    ]
    shared_fields = optional_field_widths(
        [s for bt in branch_tests for s in bt]
    )
    loaders = [
        GraphLoader(
            bt, batch_size, with_triplets=trips,
            ensure_fields=shared_fields,
        )
        for bt in branch_tests
    ]
    if state is None:
        from hydragnn_tpu.parallel.multibranch import dual_optimizer

        example = next(iter(loaders[0]))
        state = restore_checkpoint_state(
            config, training, model, example, tx=dual_optimizer(training)
        )
    total = 0.0
    n_graphs = 0
    tasks_total = None
    trues_b: List = []
    preds_b: List = []
    for loader in loaders:
        err, tasks, trues, preds = run_test(
            model,
            cfg,
            state,
            loader,
            compute_dtype=compute_dtype,
            compute_grad_energy=cfg.enable_interatomic_potential,
        )
        ng = len(loader.dataset)
        total += float(err) * ng
        n_graphs += ng
        t = np.asarray(tasks)
        tasks_total = t * ng if tasks_total is None else tasks_total + t * ng
        trues_b.append(trues)
        preds_b.append(preds)
    denom = max(n_graphs, 1)
    return total / denom, tasks_total / denom, trues_b, preds_b


def run_prediction(
    config_source,
    datasets: Optional[Tuple] = None,
    *,
    state=None,
    model=None,
    cfg=None,
):
    """Load data + model + checkpoint and run a test pass (reference
    hydragnn/run_prediction.py:34-114). Returns
    (error, per-task error, true values, predicted values). Under the
    multibranch scheme pass ``datasets`` as per-branch (train,val,test)
    triples; trues/preds are then keyed by branch."""
    config = load_config(config_source)
    pscheme = (
        config.get("NeuralNetwork", {})
        .get("Training", {})
        .get("Parallelism", {})
        .get("scheme")
    )
    if pscheme == "multibranch":
        return _multibranch_prediction(
            config, datasets, state=state, model=model, cfg=cfg
        )
    if datasets is None:
        trainset, valset, testset = _ingest_datasets(config)
    else:
        # No list() wrapper: lazy dataset objects pass through untouched
        # (same as run_training).
        trainset, valset, testset = (
            select_input_features(d, _input_cols(config))
            for d in datasets
        )
    config = update_config(config, trainset, valset, testset)
    _check_num_nodes_bound(config, trainset, valset, testset)
    training = config["NeuralNetwork"]["Training"]
    _, compute_dtype = resolve_precision(training.get("precision", "fp32"))
    batch_size = int(training.get("batch_size", 32))
    trips = needs_triplets(
        config["NeuralNetwork"]["Architecture"].get("mpnn_type", "SchNet")
    )
    plan = None
    if jax.process_count() > 1:
        # Multi-host: same plan machinery as run_training — the test set
        # is process-sharded and batches are global [D, ...]-stacked
        # arrays, so test()'s process_allgather collects the FULL
        # per-sample set on every process (reference run_prediction
        # under DDP + gather_tensor_ranks).
        from hydragnn_tpu.parallel import runtime

        plan = runtime.plan_from_config(config)
        from hydragnn_tpu.data.graph import optional_field_widths

        testset_p = runtime.shard_dataset_for_process(testset)
        mode = _resolve_fixed_pad(plan.scheme)
        sched = None
        if plan.scheme == "dp":
            _, _, sched = _dp_pad_schedules(
                plan, mode, batch_size, 0, trips,
                (testset, testset, testset),
            )
            mode = True
        base_test = GraphLoader(
            testset_p, batch_size, with_triplets=trips,
            fixed_pad=mode, spec_schedule=sched,
            # full-set map: per-shard maps can diverge across processes
            ensure_fields=optional_field_widths(testset),
        )
        if plan.scheme == "dp" and sched is None and jax.process_count() > 1:
            _pin_full_worst_specs(
                [(base_test, testset)], batch_size, trips
            )
        # superstep=False: this loader feeds run_test's per-sample
        # collection and the checkpoint-restore example — consumers
        # that iterate per batch, with no MacroBatch dispatch path.
        test_loader = runtime.wrap_loader(plan, base_test, superstep=False)
    else:
        test_loader = GraphLoader(testset, batch_size, with_triplets=trips)

    if model is None or cfg is None:
        model, cfg = create_model_config(config)
    if state is None:
        example = next(iter(test_loader))
        state = restore_checkpoint_state(config, training, model, example)

    result = run_test(
        model,
        cfg,
        state,
        test_loader,
        compute_dtype=compute_dtype,
        compute_grad_energy=cfg.enable_interatomic_potential,
        plan=plan,
    )
    if plan is not None:
        # Equal-shard truncation drops len(testset) % process_count
        # samples from the lockstep dp pass; evaluate the leftovers
        # identically on every process (replicated params, no gather)
        # and merge, so prediction covers EVERY test sample.
        p = jax.process_count()
        equal = len(testset) // p
        # Materialize by index: lazy datasets (BinDataset,
        # SimplePickleDataset) accept only int indexing, not slices.
        leftover = [testset[i] for i in range(equal * p, len(testset))]
        if leftover:
            from jax.sharding import NamedSharding, PartitionSpec

            rep = NamedSharding(plan.mesh, PartitionSpec())
            rep_state = jax.jit(lambda s: s, out_shardings=rep)(state)
            left_loader = GraphLoader(
                leftover, batch_size, with_triplets=trips,
                # Same optional-field map as the main dp pass so leftover
                # batches keep the train-time input structure.
                ensure_fields=optional_field_widths(testset),
            )
            err_l, tasks_l, trues_l, preds_l = run_test(
                model,
                cfg,
                rep_state,
                left_loader,
                compute_dtype=compute_dtype,
                compute_grad_energy=cfg.enable_interatomic_potential,
                gather=False,
            )
            err_m, tasks_m, trues_m, preds_m = result
            n_m, n_l = equal * p, len(leftover)
            tot = n_m + n_l
            result = (
                (err_m * n_m + err_l * n_l) / tot,
                (np.asarray(tasks_m) * n_m + np.asarray(tasks_l) * n_l)
                / tot,
                [
                    np.concatenate([a, b], axis=0)
                    for a, b in zip(trues_m, trues_l)
                ],
                [
                    np.concatenate([a, b], axis=0)
                    for a, b in zip(preds_m, preds_l)
                ],
            )
    return result
