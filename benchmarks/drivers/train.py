"""Driver ``train``: one call of ``hydragnn_tpu.run_training`` per run.

Epoch 0 is the warm-up (it compiles every shape of the cell) and counts as
set-up. When the program's compile observer is told that epoch 1 starts,
the window opens and the driver sets the users' own walltime stop
(``HYDRAGNN_WALLCLOCK_DEADLINE``) ``--seconds`` ahead (in a traced run when
epoch 2 starts, after the traced epoch); the loop then ends at the first
epoch end after it, and ``end_of_training`` closes the window.
Every epoch end fetches its losses, so the close is after the device has
finished.

Two things are wrapped around the program, from here, without changing it:

* ``telemetry.CompileObserver`` is subclassed so that ``set_phase`` stamps
  the clock (the loop calls it at every epoch start and after the last);
* ``train.loop.build_steps`` / ``make_superstep_fn`` are wrapped so that
  two train dispatches of epoch 0 hand over what went in (a copy of the
  state, the batch) and what came out (state, loss): the last K-step scan
  before the first single-step dispatch (kind ``scan``; it is given a zero
  accumulator, and the epoch's running sums are added back on the way out),
  and that single step (kind ``step``), whose state after one step gives
  the gradient as the optimizer got it. The jitted objects are the
  program's own, the same the window then drives; once the window opens
  the wrapper only counts steps. Those dispatches are what ``correct``
  compares with the plain reference (benchmarks/checks/train.py).
"""

from __future__ import annotations

import gc
import os
import shutil
import time

import numpy as np

DEADLINE = "HYDRAGNN_WALLCLOCK_DEADLINE"
NUM_EPOCH = 100000  # open-ended: the walltime stop ends the run


class Window:
    """Clock stamps of the run's phases, driven by the observer."""

    def __init__(self, seconds: float, counter, trace: bool = False):
        self.seconds = float(seconds)
        # a traced run measures for --seconds after the traced epoch: epoch
        # 1 carries the profiler's start and stop (21-23 s against 16.7 s
        # untraced, my chip run 6, PR 26) and would close the window alone,
        # leaving no epoch at the real rate for train_mfu
        self.deadline_phase = 2 if trace else 1
        self.counter = counter  # the Capture: steps dispatched so far
        self.steps_at_open = self.steps_at_close = None
        self.t_open = self.t_close = None
        self.epoch_starts = []  # (epoch, perf_counter)
        self.observer = None
        self.compiles_at_open = None

    def on_phase(self, phase: int, observer) -> None:
        now = time.perf_counter()
        self.observer = observer
        if phase >= 0:
            self.epoch_starts.append((phase, now))
        if phase == 1 and self.t_open is None:
            self.counter.freeze()
            self.t_open = now = time.perf_counter()
            self.compiles_at_open = observer.compile_count
            self.steps_at_open = self.counter.steps
        if phase == self.deadline_phase:
            os.environ[DEADLINE] = repr(time.time() + self.seconds)
        if phase == -1 and self.t_open is not None and self.t_close is None:
            self.t_close = now
            self.steps_at_close = self.counter.steps

    @property
    def epochs(self) -> int:
        """Whole epochs inside the window: 1 .. the last one started."""
        return max((e for e, _ in self.epoch_starts), default=0)


class Abort(Exception):
    """Raised by the capture in readings mode, once it has what it needs."""


class Capture:
    """Wraps the program's train step functions. See the module text.

    Every K-step scan dispatch is held (copies on the device, nothing
    fetched) until the first single-step dispatch has been taken: the scan
    held then, the last before it, is the one compared. After that, and in
    any case once the window opens, the wrapper only counts steps."""

    def __init__(self, stop_after_capture=False, tamper=None, tamper_batch=None):
        self.stop_after_capture = stop_after_capture
        # test hooks (benchmarks/tests): a fault planted under the run
        self.tamper = tamper  # fn(state_in, state_out) -> state
        self.tamper_batch = tamper_batch  # fn(batch) -> batch
        self.scan = None  # the latest K-step dispatch, still on the device
        self.single = None  # the first single-step train dispatch
        self.frozen = False
        self.steps = 0

    @property
    def captures(self) -> dict:
        """{kind: capture}: ``scan`` for the K-step dispatch held, ``step``
        for the first single-step dispatch."""
        self._fetch()
        out = {}
        if self.scan is not None:
            out["scan"] = self.scan
        if self.single is not None:
            out["step"] = self.single
        return out

    def _fetch(self) -> None:
        """The scan held, from the device to the host."""
        if self.scan is not None and not isinstance(self.scan["count_in"], int):
            self.scan = _to_host(self.scan)

    def freeze(self) -> None:
        """The window opens: nothing is taken from here on, and nothing of
        the capture stays on the device."""
        self.frozen = True
        self._fetch()
        if self.stop_after_capture:
            raise Abort()

    def _take(self, state_in, batch, k, state_out, acc_out):
        """One dispatch, as device arrays and small host copies of the
        batch's identifying fields (the feed may recycle its buffers)."""
        mu_in, nu_in, count = _adam_state(state_in.opt_state)
        mu, _, _ = _adam_state(state_out.opt_state)
        copy = _device_copy  # the state goes on into a donating call
        taken = {
            "k": int(k),
            "params_in": state_in.params,
            "params_out": copy(state_out.params),
            "mu_in": mu_in,
            "nu_in": nu_in,
            "count_in": count,
            "mu": copy(mu),
            "acc": list(acc_out),
            "pos": np.array(batch.pos),
            "node_graph": np.array(batch.node_graph_idx),
            "node_mask": np.array(batch.node_mask),
            "graph_mask": np.array(batch.graph_mask),
            "padded": (
                int(batch.node_mask.shape[-1]),
                int(batch.edge_mask.shape[-1]),
                int(batch.graph_mask.shape[-1]),
            ),
        }
        if k == 1:  # a single step: give every array the leading K axis
            for key in ("pos", "node_graph", "node_mask", "graph_mask"):
                taken[key] = taken[key][None]
        return taken

    def wrap_step(self, step_fn):
        def step(state, batch):
            fed = batch  # what the feed delivered is what is identified
            if self.tamper_batch is not None:
                batch = self.tamper_batch(batch)
            self.steps += 1
            if self.frozen or self.single is not None:
                return step_fn(state, batch)
            import jax.numpy as jnp

            kept = _device_copy(state)  # it is donated
            out = step_fn(state, batch)
            new_state, loss, tasks = out[0], out[1], out[2]
            if self.tamper is not None:
                new_state = self.tamper(_device_copy(kept), new_state)
                out = (new_state,) + tuple(out[1:])
            g = jnp.sum(batch.graph_mask).astype(jnp.float32)
            self.single = _to_host(
                self._take(kept, fed, 1, new_state, (loss * g, tasks * g, g))
            )
            if self.stop_after_capture and self.scan is not None:
                raise Abort()
            return out

        return step

    def wrap_superstep(self, fn):
        def superstep(state, acc, batches):
            k = int(batches.graph_mask.shape[0])
            fed = batches
            if self.tamper_batch is not None:
                batches = self.tamper_batch(batches)
            self.steps += k
            if self.frozen or (
                self.scan is not None and self.single is not None
            ):
                return fn(state, acc, batches)
            import jax
            import jax.numpy as jnp

            kept = _device_copy(state)
            # the dispatch's own sums: it is given a zero accumulator, and
            # the epoch's running sums are added back on the way out
            zero = jax.tree_util.tree_map(jnp.zeros_like, acc)
            out = fn(state, zero, batches)
            if self.tamper is not None:
                out = (self.tamper(_device_copy(kept), out[0]),) + tuple(out[1:])
            self.scan = self._take(kept, fed, k, out[0], out[1])
            total = jax.tree_util.tree_map(jnp.add, acc, out[1])
            out = (out[0], total) + tuple(out[2:])
            if self.stop_after_capture and self.single is not None:
                raise Abort()
            return out

        return superstep


def _to_host(taken: dict) -> dict:
    import jax

    taken = jax.device_get(taken)
    taken["count_in"] = int(taken["count_in"])
    taken["acc"] = [np.asarray(a, np.float64) for a in taken["acc"]]
    return taken


def _device_copy(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(jnp.copy, tree)


def _adam_state(opt_state):
    """(mu, nu, count) of the Adam state inside an optax state, wherever
    it sits."""
    stack = [opt_state]
    while stack:
        node = stack.pop()
        if hasattr(node, "mu") and hasattr(node, "nu"):
            return node.mu, node.nu, node.count
        if hasattr(node, "inner_state"):
            stack.append(node.inner_state)
        elif isinstance(node, (tuple, list)):
            stack.extend(node)
    raise ValueError("no Adam moments (mu, nu) found in the optimizer state")


def to_samples(records):
    """Generator records -> the program's host-side samples."""
    from hydragnn_tpu.data.graph import GraphSample

    return [
        GraphSample(
            x=r["z"].astype(np.float32)[:, None],
            pos=r["pos"],
            edge_index=np.stack([r["senders"], r["receivers"]]),
            y_graph=r["energy"],
            y_node=r.get("forces"),
        )
        for r in records
    ]


def build_config(cell: dict, work: str, trace: bool) -> dict:
    """The configuration's HydraGNN block with the cell's batch size, an
    open-ended epoch count and, for a traced run, telemetry and profiler."""
    import copy

    config = copy.deepcopy(cell["config"]["hydragnn"])
    traffic = cell["traffic"]
    config.setdefault("Dataset", {})["name"] = cell["name"].replace(".", "_")
    training = config["NeuralNetwork"]["Training"]
    training["batch_size"] = int(traffic["batch_size"])
    training["num_epoch"] = NUM_EPOCH
    training["walltime_min_seconds_left"] = 0
    if trace:
        training["Telemetry"] = {
            "enabled": True,
            "stream_path": os.path.join(work, "telemetry.jsonl"),
            "sync_interval_steps": 0,
            "cost_analysis": False,
        }
        training["Profiling"] = {
            "enabled": True,
            "epoch": 1,
            "steps": 0,  # the whole epoch
            "trace_dir": os.path.join(work, "trace"),
        }
    return config


def run(
    cell: dict,
    *,
    seed: int,
    seconds: float,
    trace: bool,
    work: str,
    log=print,
    mark=lambda name: None,
    stop_after_capture: bool = False,
    tamper=None,
    tamper_batch=None,
    training_overrides: dict | None = None,
) -> dict:
    """One run. Returns the window's facts and the artefacts' paths."""
    import jax

    import hydragnn_tpu
    from hydragnn_tpu.train import loop
    from hydragnn_tpu.utils import telemetry

    from benchmarks import spec

    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    os.chdir(work)  # the program writes logs/<run>/ relative to the cwd
    os.environ.pop(DEADLINE, None)
    for key, value in cell["extras"].get("env", {}).items():
        os.environ[key] = str(value)

    traffic = cell["traffic"]
    t0 = time.perf_counter()
    generator = spec.load_module("generators", traffic["generator"])
    splits = generator.make(seed, **traffic["params"])
    log(f"data: {generator.describe(splits)} in "
        f"{time.perf_counter() - t0:.1f}s")
    mark("data")
    datasets = tuple(to_samples(splits[k]) for k in ("train", "val", "test"))
    mark("samples")

    config = build_config(cell, work, trace)
    if training_overrides:
        config["NeuralNetwork"]["Training"].update(training_overrides)

    capture = Capture(stop_after_capture, tamper, tamper_batch)
    window = Window(seconds, capture, trace)

    class Observer(telemetry.CompileObserver):
        def set_phase(self, phase):
            super().set_phase(phase)
            window.on_phase(int(phase), self)

    originals = (
        telemetry.CompileObserver, loop.build_steps, loop.make_superstep_fn,
        jax.profiler.start_trace,
    )

    def build_steps(*a, **kw):
        train_step, eval_step = originals[1](*a, **kw)
        return capture.wrap_step(train_step), eval_step

    def make_superstep_fn(*a, **kw):
        fn = originals[2](*a, **kw)
        return capture.wrap_superstep(fn) if kw.get("train", True) else fn

    def start_trace(log_dir, *a, **kw):
        # the program starts the profiler with jax's defaults, which trace
        # every Python call: on this host-heavy loop that stretched a 16.7 s
        # epoch to 27.5 s (my chip run 1, PR 26) and the idle share with it.
        # The benchmark's trace keeps the host's TraceMe spans (the loop's
        # step annotations among them) and leaves the Python tracer off.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        kw.setdefault("profiler_options", options)
        return originals[3](log_dir, *a, **kw)

    telemetry.CompileObserver = Observer
    loop.build_steps = build_steps
    loop.make_superstep_fn = make_superstep_fn
    jax.profiler.start_trace = start_trace
    observer = None if trace else telemetry.install_observer()
    aborted = False
    out = None
    try:
        out = hydragnn_tpu.run_training(
            config, datasets=datasets, seed=int(seed) % (2**31 - 1)
        )
    except Abort:
        aborted = True
    finally:
        if observer is not None:
            observer.close()
        (telemetry.CompileObserver, loop.build_steps,
         loop.make_superstep_fn, jax.profiler.start_trace) = originals
        os.environ.pop(DEADLINE, None)

    obs = window.observer if window.observer is not None else observer
    facts = {
        "captures": capture.captures,
        "splits": splits,
        "train_graphs": len(splits["train"]),
        "telemetry_path": os.path.join(work, "telemetry.jsonl") if trace else None,
        "trace_dir": os.path.join(work, "trace") if trace else None,
    }
    if aborted:
        return facts
    if window.t_open is None or window.t_close is None:
        raise RuntimeError(
            "the window never opened or never closed: the observer saw "
            f"phases {window.epoch_starts}"
        )
    hist = out[3]
    epochs = window.epochs
    in_window = [float(x) for x in hist.train_loss[1:epochs + 1]]
    starts = dict(window.epoch_starts)
    attempted = window.steps_at_close - window.steps_at_open
    compiles_in_window = obs.compile_count - window.compiles_at_open
    facts.update(
        t_open=window.t_open,
        window_s=window.t_close - window.t_open,
        epochs=epochs,
        attempted=attempted,
        epoch_starts=[t - window.t_open for e, t in sorted(starts.items()) if e >= 1],
    )
    marks = [("epoch_0_start", starts.get(0, window.t_open))]
    facts["marks"] = marks
    notes = [
        f"window {facts['window_s']:.3f}s holding {epochs} epochs (starts "
        f"at {[round(t, 2) for t in facts['epoch_starts']]}), {attempted} "
        f"train steps; compilations {obs.compile_count} "
        f"({obs.compile_ms / 1e3:.1f}s), persistent-cache hits "
        f"{obs.cache_hits} misses {obs.cache_misses}, in the window "
        f"{compiles_in_window}; captured dispatches (K, padded N, E, G, "
        f"Adam step before) "
        f"{ {k: (c['k'], *c['padded'], c['count_in']) for k, c in facts['captures'].items()} }",
        f"train loss by epoch {[float(x) for x in hist.train_loss]}",
    ]
    # a steady epoch replays executables: a compilation inside the window,
    # or a loss that is not finite, makes the window's steps count as failed
    failed = 0
    if compiles_in_window or obs.post_warmup:
        notes.append(f"COMPILED INSIDE THE WINDOW: {list(obs.post_warmup)}")
        failed = attempted
    if not (np.all(np.isfinite(in_window)) and len(in_window) == epochs):
        notes.append("a train loss of the window is not finite")
        failed = attempted
    facts.update(failed=failed, notes=notes)
    # free the program's state before the reference runs
    del out, hist
    gc.collect()
    return facts


def step_rows(facts: dict, region: str) -> list:
    """The program's StepClock rows of the window's epochs (a traced run
    streams them; an untraced run has none)."""
    import json

    path = facts.get("telemetry_path")
    if not path or not os.path.isfile(path):
        return []
    if "_rows" not in facts:
        with open(path) as fh:
            rows = [json.loads(line) for line in fh if line.strip()]
        facts["_rows"] = [r for r in rows if r.get("t") == "step"]
    return [
        r for r in facts["_rows"]
        if r["region"] == region and 1 <= r["epoch"] <= facts["epochs"]
    ]
