"""End-to-end train-to-threshold tests, second half: multi-head,
per-node head, MACE and global-attention variants. Split from
tests/test_graphs.py, whose dataset, config and threshold helpers it
shares: ``--dist loadfile`` gives a file to one worker, and the two
halves together ran past five minutes there.
"""

import os

import numpy as np
import pytest

from hydragnn_tpu.data.synthetic import deterministic_graph_data
from tests.test_graphs import (  # noqa: F401  (dataset_path: fixture)
    _base_config,
    check_thresholds,
    dataset_path,
    run_e2e,
)


def _multihead_config(data_path):
    """Graph head + two node heads (reference multihead CI config shape,
    tests/inputs/ci_multihead.json)."""
    config = _base_config(data_path)
    nn_cfg = config["NeuralNetwork"]
    nn_cfg["Variables_of_interest"] = {
        "input_node_features": [0],
        "output_names": ["sum_x_x2_x3", "x2", "x3"],
        "output_index": [0, 1, 2],
        "type": ["graph", "node", "node"],
        "denormalize_output": False,
    }
    nn_cfg["Architecture"]["task_weights"] = [1.0, 1.0, 1.0]
    nn_cfg["Architecture"]["output_heads"]["node"] = {
        "num_headlayers": 2,
        "dim_headlayers": [16, 16],
        "type": "mlp",
    }
    return config


@pytest.mark.parametrize("mpnn_type", ["SchNet", "PNA", "GAT"])
def test_train_multihead(dataset_path, mpnn_type):
    config = _multihead_config(dataset_path)
    error, tasks, trues, preds = run_e2e(config, mpnn_type)
    assert len(trues) == 3
    check_thresholds(mpnn_type, tasks, trues, preds)


def test_train_per_node_head(dataset_path):
    """mlp_per_node heads need fixed-size graphs; restrict to 1x1x1 BCC
    cells (2 nodes each) like the reference's fixed-graph tests."""
    path = os.path.join(os.path.dirname(dataset_path), "fixed_size")
    deterministic_graph_data(
        path,
        number_configurations=100,
        unit_cell_x_range=(1, 2),
        unit_cell_y_range=(1, 2),
        unit_cell_z_range=(1, 2),
        seed=11,
    )
    config = _multihead_config(path)
    arch = config["NeuralNetwork"]["Architecture"]
    arch["output_heads"]["node"]["type"] = "mlp_per_node"
    arch["num_nodes"] = 2
    config["NeuralNetwork"]["Training"]["num_epoch"] = 40
    error, tasks, trues, preds = run_e2e(config, "SchNet")
    assert np.isfinite(error)


def test_train_mace(dataset_path):
    """MACE trains to the reference threshold (reference
    tests/test_graphs.py:144-158: MACE 0.60/0.70). Atomic "numbers" are
    the synthetic 0..2 types, clamped into 1..118 exactly as the
    reference's process_node_attributes does (MACEStack.py:510-541)."""
    config = _base_config(dataset_path)
    error, tasks, trues, preds = run_e2e(
        config,
        "MACE",
        overrides={
            "max_ell": 2,
            "node_max_ell": 2,
            "correlation": 2,
            "hidden_dim": 8,
        },
    )
    check_thresholds("MACE", tasks, trues, preds)


@pytest.mark.parametrize("global_attn_type", ["multihead", "performer"])
def test_train_global_attention(dataset_path, global_attn_type):
    """GPS-wrapped SchNet trains to threshold (reference
    tests/test_graphs.py global-attention variants)."""
    config = _base_config(dataset_path)
    arch = config["NeuralNetwork"]["Architecture"]
    arch["global_attn_engine"] = "GPS"
    arch["global_attn_type"] = global_attn_type
    arch["global_attn_heads"] = 2
    arch["pe_dim"] = 6
    arch["hidden_dim"] = 16
    error, tasks, trues, preds = run_e2e(config, "SchNet")
    check_thresholds("SchNet", tasks, trues, preds)
