"""Plain reference: DimeNet++ with HydraGNN's multi-head decoder, its loss,
its gradient and AdamW, in straightforward ``jax.numpy`` float32.

Follows Gasteiger et al., "Fast and Uncertainty-Aware Directional Message
Passing for Non-Equilibrium Molecules" (DimeNet++, arXiv:2011.14115), as
PyTorch-Geometric's ``DimeNetPlusPlus`` blocks and HydraGNN's ``DIMEStack``
wrap them. With c the cutoff, env the envelope u(x) = 1/x + a x^(p-1) +
b x^p + c' x^(p+1) (p = exponent + 1, zero from x = 1 on), silu as s and
the configuration's activation (relu) as act:

    rbf_ji = env(d_ji/c) sqrt(2/c) sin(n pi d_ji/c),          n = 1..R
    sbf_t  = env(d_kj/c) N_ln j_l(z_ln d_kj/c) sqrt((2l+1)/4pi) P_l(cos a_t)
             z_ln the n-th root of j_l, N_ln = sqrt(2 / j_{l+1}(z_ln)^2)
    for every block b = 1..L, with x the node features:
      x      = W_b x + w_b                          (the stack's lin_b)
      m_ji   = s(W [x_i | x_j | s(W_r rbf_ji + w_r)] + w)      embedding
      x_kj   = s(W_down (s(W_kj m + w_kj) * W_r2 W_r1 rbf) + w_down)
      a_ji   = sum_{t = (k->j, j->i)} x_kj[t_kj] * (W_s2 W_s1 sbf)_t
      h      = s(W_ji m + w_ji) + s(W_up a + w_up)
      h      = h + s(W_2 s(W_1 h + w_1) + w_2)       (1 residual layer)
      m      = s(W h + w) + m
      m      = m + s(W_2 s(W_1 m + w_1) + w_2)       (2 residual layers)
      x_i    = act(W_out s(W_0 (W_up sum_j (W_rbf rbf_ji) * m_ji) + w_0))
    graph head: mean-pool nodes -> shared MLP (act after every layer)
                -> head MLP;   loss = mean((pred - y)^2)

Departures from the paper, as HydraGNN has them: the triplet angle a_t is
the angle at node i between the directions to j and to k (HydraGNN's and
PyG's ``_embedding``; the paper's is between the edges k->j and j->i, at
j), and this is the one followed here. Every block has an embedding of its
own (the paper embeds once), the input feature is the species number
through a linear layer (no embedding table), the output block has one
layer between ``lin_up`` and ``lin_out`` (the paper three), its output is
the hidden width and goes through the configuration's activation into the
next block, and the readout is HydraGNN's pooled multi-head decoder (the
paper sums the blocks' outputs). Triplets are every edge k->j paired with
every edge j->i, k != i.

Nothing of the program is imported. Parameters arrive as a nested dict of
arrays laid out as the program lays them out (``stack/inter_i/lin_kj``,
``decoder/...``); that layout is the one thing shared. The spherical
Bessel functions, their roots, the Legendre polynomials and the angles are
evaluated on the host in float64 from the generator's positions (roots by
bisection on a sign-change scan, j_l by its closed forms and upward
recurrence, or its power series where the recurrence loses digits) and
then cast to float32: the basis carries no parameter. The rest runs in
``jax.numpy`` at the matmul precision ``follow`` is given.

A batch is a dict of arrays: ``z`` [N], ``pos`` [N, 3], ``snd``/``rcv`` [E]
padded to the program's N and E, ``t_kj``/``t_ji`` [T] edge indices and
each triplet's ``d_kj`` and ``cos`` of its angle (``with_basis`` turns
them into ``sbf`` [T, S*R]), ``node_graph`` [N], ``y_graph`` [G, 1], ``y_node``, and
the weights ``node_w``, ``edge_w``, ``graph_w``, ``trip_w`` [T] (1 real, 0
padding). ``collate`` builds one step's triplets from the records alone;
``follow`` pads T to the largest count among the dispatch's steps (rounded
up to a multiple of 2^16), so that one compiled step serves them all.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import spec

_plain = spec.load_module("references", "schnet")  # decoder, loss, AdamW
_dense, _mlp = _plain._dense, _plain._mlp
task_weights, adamw_step = _plain.task_weights, _plain.adamw_step
EPS = 1e-9  # inside the square root of every length


def _envelope(x, exponent):
    """u(x) of DimeNet, zero from x = 1 on; numpy or jax.numpy."""
    xp = jnp if isinstance(x, jax.Array) else np
    p = exponent + 1
    a, b, c = -(p + 1) * (p + 2) / 2.0, p * (p + 2.0), -p * (p + 1) / 2.0
    x = xp.where(x < 1e-8, 1e-8, x)
    out = 1.0 / x + a * x ** (p - 1) + b * x**p + c * x ** (p + 1)
    return xp.where(x < 1.0, out, 0.0)


# -- the basis on the host, float64 ----------------------------------------


def spherical_jn(l: int, x: np.ndarray) -> np.ndarray:
    """j_l(x) for x > 0: the closed forms of j_0 and j_1 and the upward
    recurrence j_{m+1} = (2m+1)/x j_m - j_{m-1} where x > l; below, where
    the recurrence loses digits, the power series
    x^l/(2l+1)!! sum_k (-x^2/2)^k / (k! (2l+3)(2l+5)...(2l+2k+1))."""
    x = np.asarray(x, np.float64)
    out = np.empty_like(x)
    big = x > l
    xb = x[big]
    j0, j1 = np.sin(xb) / xb, np.sin(xb) / xb**2 - np.cos(xb) / xb
    if l == 0:
        out[big] = j0
    else:
        for m in range(1, l):
            j0, j1 = j1, (2 * m + 1) / xb * j1 - j0
        out[big] = j1
    xs = x[~big]
    term = xs**l / np.prod(np.arange(1, 2 * l + 2, 2, dtype=np.float64))
    total = term.copy()
    for k in range(1, 40):
        term = term * (-0.5 * xs * xs) / (k * (2 * l + 2 * k + 1))
        total += term
    out[~big] = total
    return out


@functools.lru_cache(maxsize=None)
def bessel_roots(num_spherical: int, num_radial: int) -> np.ndarray:
    """[S, R] first R positive roots of j_l: sign changes on a fine grid,
    then bisection to float64's end."""
    grid = np.linspace(0.5, 40.0, 40001)
    roots = np.zeros((num_spherical, num_radial))
    for l in range(num_spherical):
        v = spherical_jn(l, grid)
        found = np.nonzero(np.sign(v[:-1]) != np.sign(v[1:]))[0][:num_radial]
        for n, i in enumerate(found):
            lo, hi = grid[i], grid[i + 1]
            f_lo = spherical_jn(l, np.array([lo]))[0]
            for _ in range(100):
                mid = 0.5 * (lo + hi)
                f_mid = spherical_jn(l, np.array([mid]))[0]
                if (f_mid > 0) == (f_lo > 0):
                    lo, f_lo = mid, f_mid
                else:
                    hi = mid
            roots[l, n] = 0.5 * (lo + hi)
    return roots


def spherical_basis(d_kj, cos_angle, cutoff, num_spherical, num_radial,
                    exponent) -> np.ndarray:
    """[T, S*R] float64: sbf_t as the module text gives it, index l*R + n."""
    z = bessel_roots(num_spherical, num_radial)
    x = np.clip(np.asarray(d_kj, np.float64) / cutoff, 0.0, 1.0)
    x = np.where(x < 1e-8, 1e-8, x)
    env = _envelope(x, exponent)
    c = np.clip(cos_angle, -1.0, 1.0)
    legendre = [np.ones_like(c), c]
    for l in range(1, num_spherical - 1):
        legendre.append(((2 * l + 1) * c * legendre[l] - l * legendre[l - 1]) / (l + 1))
    out = np.empty((len(x), num_spherical, num_radial))
    for l in range(num_spherical):
        norm = np.sqrt(2.0 / spherical_jn(l + 1, z[l]) ** 2)
        y = math.sqrt((2 * l + 1) / (4 * math.pi)) * legendre[l]
        for n in range(num_radial):
            out[:, l, n] = env * norm[n] * spherical_jn(l, z[l, n] * x) * y
    return out.reshape(len(x), num_spherical * num_radial)


def triplets(snd: np.ndarray, rcv: np.ndarray) -> tuple:
    """(t_kj, t_ji): every pair of edges k->j, j->i with k != i, as edge
    indices, from the senders and receivers of one structure."""
    meets = (rcv[:, None] == snd[None, :]) & (snd[:, None] != rcv[None, :])
    kj, ji = np.nonzero(meets)
    return kj, ji


# -- the model -------------------------------------------------------------


def _sizes(arch):
    return (
        float(arch["radius"]), int(arch["num_spherical"]),
        int(arch["num_radial"]), int(arch["envelope_exponent"]),
    )


def forward(params, batch, arch, heads, dtype=jnp.float32):
    """Head outputs, one array per head: [G, dim]."""
    cutoff, _, n_radial, exponent = _sizes(arch)
    n_nodes = batch["z"].shape[0]
    n_graphs = batch["graph_w"].shape[0]
    snd, rcv = batch["snd"], batch["rcv"]
    pos = batch["pos"].astype(dtype)
    vec = pos[snd] - pos[rcv]
    x_c = jnp.sqrt(jnp.sum(vec * vec, axis=-1) + EPS) / cutoff
    x_c = jnp.where(x_c < 1e-8, 1e-8, x_c)
    freq = jnp.arange(1, n_radial + 1, dtype=dtype) * jnp.pi
    rbf = (_envelope(x_c, exponent) * math.sqrt(2.0 / cutoff))[:, None] * jnp.sin(
        freq * x_c[:, None]
    )
    if "sbf" not in batch:  # called on a collated batch, outside jit
        batch = with_basis(batch, arch)
    sbf = batch["sbf"].astype(dtype)
    t_kj, t_ji = batch["t_kj"], batch["t_ji"]
    trip_w, edge_w = batch["trip_w"] > 0, batch["edge_w"] > 0
    n_edges = snd.shape[0]
    x = batch["z"].astype(dtype)[:, None]
    stack = params["stack"]
    for i in range(int(arch["num_conv_layers"])):
        x = _dense(stack[f"lin_{i}"], x)
        emb = stack[f"emb_{i}"]
        rbf_h = jax.nn.silu(_dense(emb["lin_rbf"], rbf))
        m = jax.nn.silu(_dense(emb["lin"], jnp.concatenate([x[rcv], x[snd], rbf_h], -1)))
        p = stack[f"inter_{i}"]
        x_ji = jax.nn.silu(_dense(p["lin_ji"], m))
        x_kj = jax.nn.silu(_dense(p["lin_kj"], m))
        x_kj = x_kj * _dense(p["lin_rbf2"], _dense(p["lin_rbf1"], rbf))
        x_kj = jax.nn.silu(_dense(p["lin_down"], x_kj))
        basis = _dense(p["lin_sbf2"], _dense(p["lin_sbf1"], sbf))
        msg = jnp.where(trip_w[:, None], x_kj[t_kj] * basis, 0.0)
        agg = jax.ops.segment_sum(msg, t_ji, num_segments=n_edges)
        h = x_ji + jax.nn.silu(_dense(p["lin_up"], agg))
        h = _residual(p["before_skip_0"], h)
        m = jax.nn.silu(_dense(p["lin"], h)) + m
        for r in range(2):
            m = _residual(p[f"after_skip_{r}"], m)
        o = stack[f"out_{i}"]
        g = jnp.where(edge_w[:, None], _dense(o["lin_rbf"], rbf) * m, 0.0)
        node = jax.ops.segment_sum(g, rcv, num_segments=n_nodes)
        node = jax.nn.silu(_dense(o["lin_0"], _dense(o["lin_up"], node)))
        x = jax.nn.relu(_dense(o["lin_out"], node))
    return _decode(params["decoder"], x, batch, arch, heads, n_graphs, dtype)


def _residual(p, h):
    return h + jax.nn.silu(_dense(p["lin2"], jax.nn.silu(_dense(p["lin1"], h))))


def _decode(dec, x, batch, arch, heads, n_graphs, dtype):
    """HydraGNN's multi-head decoder over mean-pooled nodes (graph heads)."""
    node_w = batch["node_w"].astype(dtype)
    count = jax.ops.segment_sum(node_w, batch["node_graph"], n_graphs)
    pooled = jax.ops.segment_sum(
        x * node_w[:, None], batch["node_graph"], n_graphs
    ) / jnp.maximum(count, 1.0)[:, None]
    g = arch["output_heads"]["graph"]
    shared = _mlp(dec["graph_shared_branch-0"], pooled,
                  int(g["num_sharedlayers"]), act_last=True)
    outputs = []
    for hi, head in enumerate(heads):
        if head["type"] != "graph":
            raise ValueError("the DimeNet reference has graph heads only")
        outputs.append(_mlp(dec[f"head{hi}_branch-0"], shared,
                            int(g["num_headlayers"]) + 1, act_last=False))
    return outputs


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


def _pad_triplets(batch: dict, t: int) -> dict:
    """The batch with ``t`` triplet slots: padding slots point at edge 0,
    carry a zero basis and weight 0."""
    have = batch["t_kj"].shape[0]
    out = dict(batch)
    for key, fill in (("t_kj", 0), ("t_ji", 0), ("trip_w", 0.0)):
        out[key] = np.concatenate(
            [batch[key], np.full(t - have, fill, batch[key].dtype)]
        )
    out["sbf"] = np.concatenate(
        [batch["sbf"], np.zeros((t - have, batch["sbf"].shape[1]), np.float32)]
    )
    return out


def follow(params, batches, arch, heads, opt, dtype=jnp.float32,
           mu=None, nu=None, t0=0, precision="highest"):
    """Train from ``params`` through ``batches`` as one dispatch of the
    program does; the same contract and result as the SchNet reference's
    ``follow`` (benchmarks/references/schnet.py). The steps' triplets are
    padded to the largest count among them first."""
    # the largest count among the steps, rounded up to a multiple of 2^16
    # so that other dispatches and seeds find the step compiled
    t_max = -(-max(b["t_kj"].shape[0] for b in batches) // 65536) * 65536
    batches = [_pad_triplets(with_basis(b, arch), t_max) for b in batches]

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
    """The SchNet reference's batch (``shape = (N, E, G)``) with this
    step's triplets: ``t_kj``/``t_ji`` index the batch's edges, unpadded
    (``follow`` pads them), with each triplet's float64 ``d_kj`` and
    ``cos`` of its angle, from which ``with_basis`` makes ``sbf``."""
    if forces:
        raise ValueError("the DimeNet reference has graph heads only")
    batch = _plain.collate(records, shape, False)
    kjs, jis, dists, coss = [], [], [], []
    eo = 0
    for r in records:
        snd, rcv = r["senders"], r["receivers"]
        kj, ji = triplets(snd, rcv)
        pos = np.asarray(r["pos"], np.float32).astype(np.float64)
        v_ji = pos[snd[ji]] - pos[rcv[ji]]  # at i, towards j
        v_ki = pos[snd[kj]] - pos[rcv[ji]]  # at i, towards k
        cos = np.sum(v_ji * v_ki, -1) / np.sqrt(
            np.sum(v_ji**2, -1) * np.sum(v_ki**2, -1)
        )
        v_kj = pos[snd[kj]] - pos[rcv[kj]]
        dists.append(np.sqrt(np.sum(v_kj**2, -1) + EPS))
        coss.append(cos)
        kjs.append(kj + eo)
        jis.append(ji + eo)
        eo += len(snd)
    batch["t_kj"] = np.concatenate(kjs).astype(np.int32)
    batch["t_ji"] = np.concatenate(jis).astype(np.int32)
    batch["d_kj"] = np.concatenate(dists)
    batch["cos"] = np.concatenate(coss)
    batch["trip_w"] = np.ones(len(batch["t_kj"]), np.float32)
    return batch


def with_basis(batch: dict, arch) -> dict:
    """The batch with ``sbf`` [T, S*R] float32, evaluated on the host in
    float64 from its ``d_kj`` and ``cos``, which it then leaves out."""
    out = {k: v for k, v in batch.items() if k not in ("d_kj", "cos")}
    out["sbf"] = spherical_basis(
        batch["d_kj"], batch["cos"], *_sizes(arch)
    ).astype(np.float32)
    return out
