"""Interatomic-potential (MLIP) training: energy + grad-of-energy forces.

The TPU counterpart of the reference's ``EnhancedModelWrapper.energy_force_loss``
(hydragnn/models/create.py:626-738): the model predicts per-node or
per-graph energies; forces are the negative gradient of total energy with
respect to positions. Where the reference threads
``data.pos.requires_grad=True`` through a DDP/FSDP wrapper (with an FSDP2
reshard workaround, train_validate_test.py:150-169), here the force pass
is a nested ``jax.grad`` inside the jitted loss — second-order autodiff
through the sharded forward comes for free under XLA.

Loss terms (weights from ``Architecture.{energy,energy_peratom,force}_weight``,
reference create.py:89-91):
  1. graph energy loss
  2. energy-per-atom loss (energy / num real atoms)
  3. force loss on per-atom force vectors
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from hydragnn_tpu.data.graph import GraphBatch
from hydragnn_tpu.models.spec import ModelConfig
from hydragnn_tpu.ops import segment_sum
from hydragnn_tpu.train.losses import head_loss
from hydragnn_tpu.utils import tracer as tr


def predict_graph_energy(model, variables, batch: GraphBatch, cfg: ModelConfig, *, train: bool = False):
    """Forward pass returning ([G] graph energies, mutated batch_stats).

    Node-head models sum node energies per graph (reference
    create.py:650-660: ``scatter_add``); graph-head models require sum
    pooling so dE/dpos decomposes into per-atom forces (create.py:661-672).
    """
    if len(cfg.heads) != 1:
        raise ValueError("Force predictions require exactly one head.")
    outputs, mutated = model.apply(
        variables, batch, train=train, mutable=["batch_stats"]
    )
    head = cfg.heads[0]
    pred = outputs[0][:, : head.dim]
    if head.type == "node":
        node_e = pred[:, 0] * batch.node_mask.astype(pred.dtype)
        graph_e = segment_sum(
            node_e[:, None], batch.node_graph_idx, batch.num_graphs
        )[:, 0]
    elif head.type == "graph":
        if cfg.graph_pooling != "add":
            raise ValueError(
                "Graph head force loss requires sum pooling "
                "(graph_pooling='add')."
            )
        graph_e = pred[:, 0]
    else:
        raise ValueError(
            "Force predictions are only supported for node or graph "
            "energy heads."
        )
    graph_e = graph_e * batch.graph_mask.astype(graph_e.dtype)
    return graph_e, mutated.get("batch_stats", {})


def energy_and_forces(
    model, variables, batch: GraphBatch, cfg: ModelConfig, *, train: bool = False
) -> Tuple[jax.Array, jax.Array, dict]:
    """(graph_energy [G], forces [N, 3], new_batch_stats).

    forces = -d(sum_g E_g)/d pos; each atom contributes only to its own
    graph's energy, so the gradient of the masked sum is exactly the
    per-atom force field (reference create.py:718-728).
    """

    def esum(pos):
        ge, new_bn = predict_graph_energy(
            model, variables, batch.replace(pos=pos), cfg, train=train
        )
        return jnp.sum(ge), (ge, new_bn)

    # the inner grad of MLIP training: forward and position-gradient
    # of the energy read as one block in a trace
    with tr.scope("forces"):
        grad_pos, (graph_e, new_bn) = jax.grad(esum, has_aux=True)(
            batch.pos
        )
        forces = -grad_pos * batch.node_mask.astype(grad_pos.dtype)[:, None]
    return graph_e, forces, new_bn


@tr.scoped("loss")
def energy_force_loss_terms(
    graph_e: jax.Array, forces: jax.Array, batch: GraphBatch, cfg: ModelConfig
) -> Tuple[jax.Array, jax.Array]:
    """Weighted loss terms from precomputed (graph_e, forces).

    Returns (total, per-task [energy, energy_peratom, force]). All three
    task losses are always reported; only positively-weighted terms
    contribute to the total (reference create.py:675-738).
    """
    kind = cfg.loss_function_type
    if kind == "GaussianNLLLoss":
        raise ValueError(
            "GaussianNLLLoss is not supported for interatomic potential "
            "training; use mse/mae/smooth_l1/rmse."
        )
    gmask = batch.graph_mask
    e_true = batch.energy * gmask.astype(graph_e.dtype)

    e_loss = head_loss(kind, graph_e, e_true, gmask)

    natoms = jnp.maximum(batch.nodes_per_graph.astype(graph_e.dtype), 1.0)
    epa_loss = head_loss(kind, graph_e / natoms, e_true / natoms, gmask)

    f_true = batch.forces * batch.node_mask.astype(forces.dtype)[:, None]
    f_loss = head_loss(kind, forces, f_true, batch.node_mask)

    tot = (
        cfg.energy_weight * e_loss
        + cfg.energy_peratom_weight * epa_loss
        + cfg.force_weight * f_loss
    )
    return tot, jnp.stack([e_loss, epa_loss, f_loss])


def energy_force_loss(
    model, variables, batch: GraphBatch, cfg: ModelConfig, *, train: bool = False
) -> Tuple[jax.Array, jax.Array, dict]:
    """Weighted MLIP loss (reference create.py:675-738).

    Returns (total, per-task [energy, energy_peratom, force], new_bn).
    """
    if (
        cfg.energy_weight <= 0
        and cfg.energy_peratom_weight <= 0
        and cfg.force_weight <= 0
    ):
        raise ValueError(
            "All interatomic potential loss weights are zero; set at "
            "least one of energy_weight, energy_peratom_weight, or "
            "force_weight to a positive value."
        )
    if batch.pos is None or batch.energy is None or batch.forces is None:
        raise ValueError(
            "batch.pos, batch.energy, batch.forces must be provided for "
            "energy-force loss."
        )
    graph_e, forces, new_bn = energy_and_forces(
        model, variables, batch, cfg, train=train
    )
    tot, tasks = energy_force_loss_terms(graph_e, forces, batch, cfg)
    return tot, tasks, new_bn
