"""Plain reference: PaiNN with HydraGNN's multi-head decoder, its loss, its
gradient and AdamW, in straightforward ``jax.numpy`` float32.

Follows Schuett, Unke, Gastegger, "Equivariant message passing for the
prediction of tensorial properties and molecular spectra" (PaiNN, ICML
2021, arXiv:2102.03150), as HydraGNN's ``PAINNStack`` and ``Base`` wrap it.
With j the sender and i the receiver of an edge, r_ij = pos_j - pos_i,
d = |r_ij|, r^ = r_ij / d, c the cutoff and f_c(d) = (cos(pi d / c) + 1) / 2
for d < c (0 beyond):

    embedding  s_i = W_e onehot(Z_i),  v_i = 0 in R^{3 x F}
    for every block t = 1..L:
      message  W_ij  = (W_f sinc(d) + b_f) f_c(d) in R^{3F},
                       sinc_n(d) = sin(n pi d / c) / d,  n = 1..R
               phi_j = W_2 silu(W_1 s_j + b_1) + b_2 in R^{3F}
               (g_v, g_e, m) = split(W_ij * phi_j)
               s_i  += sum_j m
               v_i  += sum_j (v_j * g_v + g_e (x) r^ / d)        [1]
      update   Uv, Vv = v U, v V  (bias-free, over the channel axis)
               a = W_4 silu(W_3 [|Vv|, s] + b_3) + b_4,
               |Vv| = sqrt(sum_k (Vv)_k^2 + 1e-12)
               s  += a_sv * <Uv, Vv> + a_ss
               v  += a_vv * Uv  (not in the last block, where a has 2F
                                 outputs and v is left as it is)
      resize   s = W_6 tanh(W_5 s + b_5) + b_6                  [2]
               v = v W_v  (bias-free, not after the last block)  [2]
               s = act(s)                                        [3]
    graph head: mean-pool nodes -> shared MLP (act after every layer)
                -> head MLP;   loss = mean((pred - y)^2)          [4]

Departures from the paper, as HydraGNN has them and the program keeps:
[1] the edge-direction term is g_e r^ / d, where the paper has g_e r^
(HydraGNN's PAINNStack.py:255-258 divides the normalised displacement by
the distance once more). [2] The tanh resize of s and the linear map of v
after every block are HydraGNN's, not the paper's. [3] HydraGNN's Base puts
the configuration's activation (relu) on s after every block. [4] The
readout is HydraGNN's pooled multi-head decoder; the paper sums an
atomwise MLP. The embedding takes the atomic number Z from the first input
column, rounded and clamped into 1..118 (so a species 0, as the benchmark's
generator draws it, shares the row of Z = 1).

Nothing of the program is imported. Parameters arrive as a nested dict of
arrays laid out as the program lays them out (``stack/message_i/...``,
``decoder/...``); that layout is the one thing shared. The vector message
is an ``[E, 3, F]`` array summed per receiver with ``jax.ops.segment_sum``;
padding edges are zeroed through f_c. The batch is the SchNet reference's
(``collate``). Matmuls run at the precision ``follow`` is given.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import spec

_plain = spec.load_module("references", "schnet")  # batch, AdamW
_dense, collate = _plain._dense, _plain.collate
task_weights, adamw_step = _plain.task_weights, _plain.adamw_step
_decode = spec.load_module("references", "dimenet")._decode  # graph heads
EPS = 1e-9  # inside the square root of every length
NUM_ELEMENTS = 118


def _silu_mlp(p, x):
    return _dense(p["dense_1"], jax.nn.silu(_dense(p["dense_0"], x)))


def forward(params, batch, arch, heads, dtype=jnp.float32):
    """Head outputs, one array per head: [G, dim]."""
    cutoff = float(arch["radius"])
    n_radial = int(arch["num_radial"])
    blocks = int(arch["num_conv_layers"])
    n_nodes = batch["z"].shape[0]
    n_graphs = batch["graph_w"].shape[0]
    snd, rcv = batch["snd"], batch["rcv"]
    pos = batch["pos"].astype(dtype)
    r = pos[snd] - pos[rcv]
    d = jnp.sqrt(jnp.sum(r * r, axis=-1) + EPS)
    direction = r / d[:, None] / d[:, None]  # r^ / d
    n = jnp.arange(1, n_radial + 1, dtype=dtype)
    sinc = jnp.sin(n * jnp.pi * d[:, None] / cutoff) / d[:, None]
    f_c = jnp.where(d < cutoff, 0.5 * (jnp.cos(jnp.pi * d / cutoff) + 1.0), 0.0)
    f_c = f_c * batch["edge_w"].astype(dtype)  # padding edges carry nothing

    stack = params["stack"]
    z = jnp.clip(jnp.round(batch["z"]), 1, NUM_ELEMENTS).astype(jnp.int32)
    onehot = jax.nn.one_hot(z - 1, NUM_ELEMENTS, dtype=dtype)
    onehot = onehot * batch["node_w"].astype(dtype)[:, None]
    s = onehot @ stack["species_embed"]["kernel"].astype(dtype)
    v = jnp.zeros((n_nodes, 3, s.shape[-1]), dtype)
    for t in range(blocks):
        last = t == blocks - 1
        p = stack[f"message_{t}"]
        w = _dense(p["filter_layer"], sinc) * f_c[:, None]
        phi = _silu_mlp(p["scalar_message_mlp"], s)
        g_v, g_e, m = jnp.split(w * phi[snd], 3, axis=-1)
        msg_v = v[snd] * g_v[:, None, :] + g_e[:, None, :] * direction[:, :, None]
        s = s + jax.ops.segment_sum(m, rcv, num_segments=n_nodes)
        v = v + jax.ops.segment_sum(msg_v, rcv, num_segments=n_nodes)

        u = stack[f"update_{t}"]
        uv = v @ u["update_U"]["kernel"].astype(dtype)
        vv = v @ u["update_V"]["kernel"].astype(dtype)
        norm = jnp.sqrt(jnp.sum(vv * vv, axis=1) + 1e-12)
        a = _silu_mlp(u["update_mlp"], jnp.concatenate([norm, s], axis=-1))
        inner = jnp.sum(uv * vv, axis=1)
        if last:
            a_sv, a_ss = jnp.split(a, 2, axis=-1)
        else:
            a_vv, a_sv, a_ss = jnp.split(a, 3, axis=-1)
            v = v + a_vv[:, None, :] * uv
        s = s + a_sv * inner + a_ss

        e = stack[f"node_embed_out_{t}"]
        s = _dense(e["dense_1"], jnp.tanh(_dense(e["dense_0"], s)))
        if not last:
            v = v @ stack[f"vec_embed_out_{t}"]["kernel"].astype(dtype)
        s = jax.nn.relu(s)
    return _decode(params["decoder"], s, batch, arch, heads, n_graphs, dtype)


def loss_fn(params, batch, arch, heads, dtype=jnp.float32):
    """(total, per-task) mean-squared-error losses over the real graphs."""
    outs = forward(params, batch, arch, heads, dtype)
    tasks, off = [], 0
    w = batch["graph_w"]
    for out, head in zip(outs, heads):
        dim = int(head["dim"])
        y = batch["y_graph"][:, off:off + dim]
        off += dim
        err = jnp.sum((out.astype(jnp.float32) - y) ** 2 * w[:, None])
        tasks.append(err / jnp.maximum(jnp.sum(w) * dim, 1.0))
    tasks = jnp.stack(tasks)
    return jnp.sum(jnp.asarray(task_weights(arch, heads)) * tasks), tasks


def follow(params, batches, arch, heads, opt, dtype=jnp.float32,
           mu=None, nu=None, t0=0, precision="highest"):
    """Train from ``params`` through ``batches`` as one dispatch of the
    program does; the same contract and result as the SchNet reference's
    ``follow`` (benchmarks/references/schnet.py)."""

    def step(params, mu, nu, t, batch):
        (tot, tasks), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch, arch, heads, dtype), has_aux=True
        )(params)
        gnorm = jax.tree_util.tree_map(
            lambda g: jnp.sqrt(jnp.sum(g.astype(jnp.float32) ** 2)), grads
        )
        params, mu, nu = adamw_step(params, mu, nu, grads, t, opt)
        return params, mu, nu, tot, tasks, gnorm, grads

    tm = jax.tree_util.tree_map
    with jax.default_matmul_precision(precision):
        jstep = jax.jit(step)
        params = tm(jnp.asarray, params)
        mu = tm(jnp.zeros_like, params) if mu is None else tm(jnp.asarray, mu)
        nu = tm(jnp.zeros_like, params) if nu is None else tm(jnp.asarray, nu)
        first_grad = None
        gsum = tm(lambda p: jnp.zeros((), jnp.float32), params)
        losses, task_losses, graphs = [], [], []
        for t, batch in enumerate(batches, start=int(t0) + 1):
            params, mu, nu, tot, tasks, gnorm, grads = jstep(
                params, mu, nu, jnp.float32(t), batch
            )
            if first_grad is None:
                first_grad = grads
            gsum = tm(jnp.add, gsum, gnorm)
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
