"""Plain reference: SchNet with HydraGNN's multi-head decoder, its loss, its
gradient and AdamW, in straightforward ``jax.numpy`` float32.

Follows Schuett et al. (J. Chem. Phys. 148, 241722) as PyTorch-Geometric's
``SchNet``/``CFConv`` and HydraGNN's ``SCFStack``/``Base`` wrap it:

    d_ij   = |r_j - r_i|,  rbf_k = exp(-(d - mu_k)^2 / (2 delta^2))
    W_ij   = (ssp(rbf W0 + b0) W1 + b1) * 0.5 (cos(pi d / r_c) + 1)
    x_i'   = act( (sum_j (x_j lin1) * W_ij) lin2 + b2 )
    graph head: mean-pool nodes -> shared MLP (act after every layer)
                -> head MLP;   node head: MLP on each node
    loss   = sum_h w_h * mean((pred_h - y_h)^2),  w normalised to sum 1

Departures from the paper, as HydraGNN has them: no residual connection and
no atom-embedding table (the input feature is the species number itself),
the activation between interactions is the configuration's (relu), and the
interaction's output projection is a single linear layer.

Nothing of the program is imported. Parameters arrive as a nested dict of
arrays laid out as the program lays them out (``stack/conv_i/...``,
``decoder/...``); that layout is the one thing shared. Every matmul runs at
``highest`` precision: on a TPU the default would round float32 operands to
bfloat16.

A batch is a dict of arrays, padded to fixed shapes so that one compiled
step serves every step: ``z`` [N], ``pos`` [N, 3], ``snd``/``rcv`` [E],
``node_graph`` [N], ``y_graph`` [G, Dg], ``y_node`` [N, Dn] and the weights
``node_w`` [N], ``edge_w`` [E], ``graph_w`` [G] (1 real, 0 padding).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def _dense(p, x):
    y = x @ p["kernel"].astype(x.dtype)
    return y + p["bias"].astype(x.dtype) if "bias" in p else y


def _ssp(x):
    return jax.nn.softplus(x) - math.log(2.0)


def _mlp(p, x, n_layers, act_last):
    for i in range(n_layers):
        x = _dense(p[f"dense_{i}"], x)
        if i < n_layers - 1 or act_last:
            x = jax.nn.relu(x)
    return x


def forward(params, batch, arch, heads, dtype=jnp.float32):
    """Head outputs, one array per head: [G, dim] or [N, dim]."""
    cutoff = float(arch["radius"])
    n_gauss = int(arch["num_gaussians"])
    n_nodes = batch["z"].shape[0]
    n_graphs = batch["graph_w"].shape[0]
    x = batch["z"].astype(dtype)[:, None]
    pos = batch["pos"].astype(dtype)
    snd, rcv = batch["snd"], batch["rcv"]
    vec = pos[snd] - pos[rcv]
    d = jnp.sqrt(jnp.sum(vec * vec, axis=-1) + 1e-9)
    centres = jnp.linspace(0.0, cutoff, n_gauss, dtype=dtype)
    coeff = -0.5 / (centres[1] - centres[0]) ** 2
    rbf = jnp.exp(coeff * (d[:, None] - centres) ** 2)
    envelope = jnp.where(
        d < cutoff, 0.5 * (jnp.cos(jnp.pi * d / cutoff) + 1.0), 0.0
    ) * batch["edge_w"].astype(dtype)
    for i in range(int(arch["num_conv_layers"])):
        p = params["stack"][f"conv_{i}"]
        f = p["filter_mlp"]
        filt = _dense(f["dense_1"], _ssp(_dense(f["dense_0"], rbf)))
        filt = filt * envelope[:, None]
        h = _dense(p["lin1"], x)
        agg = jax.ops.segment_sum(h[snd] * filt, rcv, num_segments=n_nodes)
        x = jax.nn.relu(_dense(p["lin2"], agg))
    node_w = batch["node_w"].astype(dtype)
    count = jax.ops.segment_sum(node_w, batch["node_graph"], n_graphs)
    pooled = jax.ops.segment_sum(
        x * node_w[:, None], batch["node_graph"], n_graphs
    ) / jnp.maximum(count, 1.0)[:, None]
    dec = params["decoder"]
    out_cfg = arch["output_heads"]
    outputs = []
    shared = None
    for hi, head in enumerate(heads):
        if head["type"] == "graph":
            g = out_cfg["graph"]
            if shared is None:
                shared = _mlp(
                    dec["graph_shared_branch-0"], pooled,
                    int(g["num_sharedlayers"]), act_last=True,
                )
            outputs.append(
                _mlp(
                    dec[f"head{hi}_branch-0"], shared,
                    int(g["num_headlayers"]) + 1, act_last=False,
                )
            )
        else:
            nd = out_cfg["node"]
            outputs.append(
                _mlp(
                    dec[f"head{hi}_branch-0"], x,
                    int(nd["num_headlayers"]) + 1, act_last=False,
                )
            )
    return outputs


def task_weights(arch, heads):
    """HydraGNN's task weights: the configured ones over their absolute sum."""
    raw = [float(v) for v in arch.get("task_weights") or [1.0] * len(heads)]
    total = sum(abs(v) for v in raw)
    return [v / total for v in raw]


def loss_fn(params, batch, arch, heads, dtype=jnp.float32):
    """(total, per-task) mean-squared-error losses over the real rows."""
    outs = forward(params, batch, arch, heads, dtype)
    g_off = n_off = 0
    tasks = []
    for out, head in zip(outs, heads):
        dim = int(head["dim"])
        out = out.astype(jnp.float32)
        if head["type"] == "graph":
            y, w = batch["y_graph"][:, g_off:g_off + dim], batch["graph_w"]
            g_off += dim
        else:
            y, w = batch["y_node"][:, n_off:n_off + dim], batch["node_w"]
            n_off += dim
        err = jnp.sum((out - y) ** 2 * w[:, None])
        tasks.append(err / jnp.maximum(jnp.sum(w) * dim, 1.0))
    tasks = jnp.stack(tasks)
    return jnp.sum(jnp.asarray(task_weights(arch, heads)) * tasks), tasks


def adamw_step(params, mu, nu, grads, t, opt):
    """One AdamW update as optax.adamw applies it; ``t`` counts from 1."""
    b1, b2 = float(opt["b1"]), float(opt["b2"])
    lr = jnp.float32(opt["learning_rate"])
    tm = jax.tree_util.tree_map
    mu = tm(lambda m, g: b1 * m + (1.0 - b1) * g, mu, grads)
    nu = tm(lambda v, g: b2 * v + (1.0 - b2) * g * g, nu, grads)
    c1 = 1.0 - b1 ** t
    c2 = 1.0 - b2 ** t
    params = tm(
        lambda p, m, v: p - lr * (
            (m / c1) / (jnp.sqrt(v / c2) + float(opt["eps"]))
            + float(opt["weight_decay"]) * p
        ),
        params, mu, nu,
    )
    return params, mu, nu


def follow(params, batches, arch, heads, opt, dtype=jnp.float32,
           mu=None, nu=None, t0=0, precision="highest"):
    """Train from ``params`` through ``batches`` (a list of batch dicts of
    one shape), as one dispatch of the program does. ``mu``, ``nu`` and
    ``t0`` are Adam's moments and step count before the dispatch (zeros
    and 0 at the start of training). ``precision`` is the matmul precision:
    ``highest`` for the reference, anything lower only for a control.

    Returns numpy trees and numbers: the parameters, Adam's two moments
    after the last step, the first step's gradient, each step's (total,
    per-task) loss and real-graph count, and per leaf the summed gradient
    norm (for the rule that leaves out leaves whose gradient is nought to
    rounding).
    """

    def step(params, mu, nu, t, batch):
        (tot, tasks), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, arch, heads, dtype), has_aux=True
        )(params)
        gnorm = jax.tree_util.tree_map(
            lambda g: jnp.sqrt(jnp.sum(g.astype(jnp.float32) ** 2)), grads
        )
        params, mu, nu = adamw_step(params, mu, nu, grads, t, opt)
        return params, mu, nu, tot, tasks, gnorm, grads

    with jax.default_matmul_precision(precision):
        jstep = jax.jit(step)
        params = jax.tree_util.tree_map(jnp.asarray, params)
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        mu = zeros if mu is None else jax.tree_util.tree_map(jnp.asarray, mu)
        nu = zeros if nu is None else jax.tree_util.tree_map(jnp.asarray, nu)
        first_grad = None
        gsum = jax.tree_util.tree_map(lambda p: jnp.zeros((), jnp.float32), params)
        losses, task_losses, graphs = [], [], []
        for t, batch in enumerate(batches, start=int(t0) + 1):
            params, mu, nu, tot, tasks, gnorm, grads = jstep(
                params, mu, nu, jnp.float32(t), batch
            )
            if first_grad is None:
                first_grad = grads
            gsum = jax.tree_util.tree_map(jnp.add, gsum, gnorm)
            losses.append(tot)
            task_losses.append(tasks)
            graphs.append(jnp.sum(batch["graph_w"]))
        out = jax.device_get(
            (params, mu, nu, gsum, first_grad, losses, task_losses, graphs)
        )
    params, mu, nu, gsum, first_grad, losses, task_losses, graphs = out
    return {
        "params": params, "mu": mu, "nu": nu, "grad_norm_sum": gsum,
        "grad_first": first_grad,
        "loss": np.asarray(losses, np.float64),
        "tasks": np.asarray(task_losses, np.float64),
        "graphs": np.asarray(graphs, np.float64),
    }


def collate(records, shape, forces: bool):
    """Concatenate generator records into one batch dict padded to
    ``shape = (N, E, G)``; padding rows carry weight 0, padding nodes sit
    in the last graph slot and padding edges loop on the last node."""
    n_pad, e_pad, g_pad = shape
    n = sum(len(r["z"]) for r in records)
    e = sum(len(r["senders"]) for r in records)
    if n >= n_pad or e > e_pad or len(records) >= g_pad:
        raise ValueError(
            f"batch of {n} nodes, {e} edges, {len(records)} graphs does "
            f"not fit the reference's padded shape {shape}"
        )
    z = np.zeros(n_pad, np.float32)
    pos = np.zeros((n_pad, 3), np.float32)
    snd = np.full(e_pad, n_pad - 1, np.int32)
    rcv = np.full(e_pad, n_pad - 1, np.int32)
    node_graph = np.full(n_pad, g_pad - 1, np.int32)
    y_graph = np.zeros((g_pad, 1), np.float32)
    y_node = np.zeros((n_pad, 3 if forces else 1), np.float32)
    node_w = np.zeros(n_pad, np.float32)
    edge_w = np.zeros(e_pad, np.float32)
    graph_w = np.zeros(g_pad, np.float32)
    no = eo = 0
    for gi, r in enumerate(records):
        k, m = len(r["z"]), len(r["senders"])
        z[no:no + k] = r["z"]
        pos[no:no + k] = r["pos"]
        snd[eo:eo + m] = r["senders"] + no
        rcv[eo:eo + m] = r["receivers"] + no
        node_graph[no:no + k] = gi
        y_graph[gi] = r["energy"]
        if forces:
            y_node[no:no + k] = r["forces"]
        no += k
        eo += m
    node_w[:n] = 1.0
    edge_w[:e] = 1.0
    graph_w[:len(records)] = 1.0
    return {
        "z": z, "pos": pos, "snd": snd, "rcv": rcv,
        "node_graph": node_graph, "y_graph": y_graph, "y_node": y_node,
        "node_w": node_w, "edge_w": edge_w, "graph_w": graph_w,
    }
