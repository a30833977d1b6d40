"""Multi-host (multi-process) training: 2 coordinated processes x 4
virtual CPU devices each run run_training over one global {data: 8}
mesh — rendezvous, process-sharded data, global-collective metric
reduction, and process-0 checkpointing (reference counterpart: the
2-rank MPI CI pytest, .github/workflows/CI.yml:62-67, and
distributed.py:113-275 setup_ddp).

Runs as subprocesses because each process needs its own JAX backend
(the in-process test session already pinned an 8-device single-process
platform).
"""

import json
import os
import socket
import subprocess
import sys
import time

import pytest


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.e2e
@pytest.mark.parametrize(
    "parallelism",
    [
        '{"scheme": "dp", "data": 8}',
        # fsdp axis spanning both processes: params sharded across
        # hosts, checkpoint all-gather crosses process boundaries.
        '{"scheme": "dp", "data": 4, "fsdp": 2}',
        # task parallelism across hosts: each process iterates only
        # its local device slots' branch loaders.
        '{"scheme": "multibranch"}',
    ],
    ids=["dp", "dp_fsdp", "multibranch"],
)
def test_two_process_training(tmp_path, parallelism):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    port = _free_port()
    procs, logs = [], []
    for pid in range(2):
        env = dict(os.environ)
        env.update(
            {
                "JAX_PLATFORMS": "cpu",
                "HYDRAGNN_TPU_COORDINATOR": f"127.0.0.1:{port}",
                "HYDRAGNN_TPU_NUM_PROCESSES": "2",
                "HYDRAGNN_TPU_PROCESS_ID": str(pid),
                "HYDRAGNN_TPU_LOCAL_DEVICES": "4",
                "HYDRAGNN_TEST_PARALLELISM": parallelism,
                "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
            }
        )
        # The pytest session's XLA_FLAGS pin 8 host devices; the workers
        # use jax_num_cpu_devices=4 instead. And the suite's other
        # workers already hold the host's cores: one compute thread a
        # device, not 2 processes x 4 devices x a pool as wide as the
        # host.
        env["XLA_FLAGS"] = " ".join(
            [
                f
                for f in env.get("XLA_FLAGS", "").split()
                if "xla_force_host_platform_device_count" not in f
            ]
            + ["--xla_cpu_multi_thread_eigen=false"]
        )
        env.update(
            OMP_NUM_THREADS="1", OPENBLAS_NUM_THREADS="1", MKL_NUM_THREADS="1"
        )
        logs.append(tmp_path / f"worker_{pid}.log")
        with open(logs[-1], "w") as log:
            procs.append(
                subprocess.Popen(
                    [
                        sys.executable,
                        os.path.join(repo, "tests", "multihost_worker.py"),
                        str(tmp_path),
                    ],
                    env=env,
                    cwd=repo,
                    stdout=log,
                    stderr=subprocess.STDOUT,
                )
            )
    # ~30 s uncontended, under three minutes on a host with twice as
    # many busy processes as cores: one limit for both workers, below
    # the per-test one of tests/conftest.py. A worker that dies leaves
    # its peer waiting at the rendezvous, so the first failure ends the
    # wait; either way both workers' output names what happened (under
    # load: gloo's own 30 s limit on the slower worker's arrival at the
    # first cross-process collective, "Gloo context initialization
    # failed: DEADLINE_EXCEEDED").
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        codes = [p.poll() for p in procs]
        if any(codes) or None not in codes:
            break
        time.sleep(0.2)
    for p in procs:
        p.kill()
    assert [p.wait() for p in procs] == [0, 0], "".join(
        f"--- worker {pid}: exit {p.returncode} ---\n"
        f"{log.read_text()[-4000:]}\n"
        for pid, (p, log) in enumerate(zip(procs, logs))
    )

    hists = []
    for pid in range(2):
        with open(tmp_path / f"hist_{pid}.json") as f:
            hists.append(json.load(f))
    # Metrics are global XLA collectives: every process must see the
    # exact same loss history.
    assert hists[0]["train"] == hists[1]["train"]
    assert hists[0]["val"] == hists[1]["val"]
    assert len(hists[0]["train"]) == 3
    assert all(x > 0 and x == x for x in hists[0]["train"])
    # Process 0 wrote the checkpoint; both saw it on the shared fs.
    assert hists[0]["ckpt_exists"] and hists[1]["ckpt_exists"]
    # Multi-host per-sample collection: run_prediction gathers the FULL
    # true/pred set on every process (reference gather_tensor_ranks,
    # train_validate_test.py:1082-1088). 128 samples, test split
    # (1-0.75)/2 -> 16, plus one deliberately-odd extra sample that the
    # equal-shard truncation cannot place: 17 total via leftover merge.
    if "pred_n_samples" in hists[0]:
        for h in hists:
            assert h["pred_n_samples"] == 17, h
            assert h["pred_n_pred"] == 17, h
            assert h["pred_error"] == hists[0]["pred_error"]
            # Lazy mmap-backed containers through the same path: the
            # leftover merge must index (not slice) the dataset and
            # produce the identical full collection.
            assert h["pred_lazy_n"] == 17, h
            # Lazy and eager round-trip the SAME samples through the
            # same state, so their errors must be equal — a merge path
            # consistently wrong on both processes can't hide.
            assert h["pred_lazy_error"] == h["pred_error"], h
