"""End-to-end train-to-threshold tests.

The TPU analog of the reference's central E2E tests
(tests/test_graphs.py:25-201): generate the deterministic synthetic BCC
dataset, run full run_training + run_prediction for each model type, and
assert head RMSE / sample MAE below per-model thresholds (threshold table
reference tests/test_graphs.py:144-158).
"""

import json
import os

import numpy as np
import pytest

import hydragnn_tpu
from hydragnn_tpu.config import load_config
from hydragnn_tpu.data.synthetic import deterministic_graph_data

# Reference threshold table (head RMSE, sample MAE) — see
# /root/reference/tests/test_graphs.py:144-158 and BASELINE.md.
THRESHOLDS = {
    "SchNet": (0.20, 0.20),
    "GIN": (0.25, 0.20),
    "SAGE": (0.20, 0.20),
    "MFC": (0.20, 0.30),
    "GAT": (0.60, 0.70),
    "CGCNN": (0.50, 0.40),
    "PNA": (0.20, 0.20),
    "PNAPlus": (0.20, 0.20),
    "DimeNet": (0.50, 0.50),
    "EGNN": (0.20, 0.20),
    "PAINN": (0.60, 0.60),
    "PNAEq": (0.60, 0.60),
    "MACE": (0.60, 0.70),
}


def _make_dataset(tmp_path, n_configs=300):
    path = os.path.join(tmp_path, "dataset", "unit_test")
    deterministic_graph_data(path, number_configurations=n_configs, seed=7)
    return path


def _base_config(data_path):
    here = os.path.dirname(__file__)
    config = load_config(os.path.join(here, "inputs", "ci.json"))
    config["Dataset"]["path"] = {"total": data_path}
    # Model-quality thresholds are calibrated for single-device
    # stepping; on the 8-device test mesh the auto plan would otherwise
    # train data-parallel with an 8x effective batch (fewer optimizer
    # steps). The parallel path has its own E2E suite
    # (tests/test_parallel_runtime.py).
    config["NeuralNetwork"]["Training"]["Parallelism"] = {"scheme": "single"}
    return config


def run_e2e(config, mpnn_type, overrides=None):
    arch = config["NeuralNetwork"]["Architecture"]
    arch["mpnn_type"] = mpnn_type
    if overrides:
        arch.update(overrides)
    state, model, cfg, hist, full_config = hydragnn_tpu.run_training(config)
    error, tasks, trues, preds = hydragnn_tpu.run_prediction(
        full_config,
        datasets=None,
        state=state,
        model=model,
        cfg=cfg,
    )
    return error, tasks, trues, preds


def check_thresholds(mpnn_type, tasks, trues, preds):
    thr_rmse, thr_mae = THRESHOLDS[mpnn_type]
    for hi, (t, p) in enumerate(zip(trues, preds)):
        rmse = float(np.sqrt(np.mean((t - p) ** 2)))
        mae = float(np.mean(np.abs(t - p)))
        assert rmse < thr_rmse, f"head {hi} RMSE {rmse} >= {thr_rmse}"
        assert mae < thr_mae, f"head {hi} MAE {mae} >= {thr_mae}"


@pytest.fixture(scope="module")
def dataset_path(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("e2e")
    return _make_dataset(str(tmp))


@pytest.mark.parametrize(
    "mpnn_type",
    [
        "SchNet",
        "GIN",
        "SAGE",
        # MFC trains with BN recalibration enabled (see the test body):
        # with ~7 train batches/epoch the BN EMA (momentum 0.9) lags
        # ~1.5 epochs behind MFC's drifting per-degree feature tables,
        # so the stats the model carries out of training are stale.
        # The end-of-training recalibration pass
        # (train/loop.recalibrate_batch_stats: frozen-param forward
        # passes pooling exact masked moments into the running stats,
        # fed by the runner's eval-shaped unpacked loader) is the
        # ROADMAP's measured fix (RMSE 0.39 -> 0.16); PyG's
        # max_degree=10 cap and batch_axis init both measured WORSE
        # (0.54) — do not retry. Per-epoch recalibration also measured
        # worse (0.30): it feeds the plateau scheduler a meaningful
        # val curve, keeps the LR hot, and the 210-sample run overfits
        # — the annealed raw trajectory + refreshed final stats is the
        # fix.
        "MFC",
        "CGCNN",
        "GAT",
        "PNA",
        "PNAPlus",
        "DimeNet",
        "EGNN",
        "PAINN",
        "PNAEq",
    ],
)
def test_train_singlehead_graph(dataset_path, mpnn_type):
    config = _base_config(dataset_path)
    if mpnn_type == "MFC":
        # End-of-training BatchNorm recalibration — required on
        # 7-batch CI epochs where the BN EMA lags the drifting
        # per-degree feature scales (see the parametrize comment).
        # One pass is exact: the stats are pooled moments, not
        # another EMA (RMSE 0.164 here vs 0.386 raw).
        config["NeuralNetwork"]["Training"]["bn_recalibration"] = {
            "enabled": True
        }
    # Re-ingest via the raw path (reference flow: text files -> raw loader
    # -> serialized samples -> loaders).
    error, tasks, trues, preds = run_e2e(config, mpnn_type)
    check_thresholds(mpnn_type, tasks, trues, preds)
