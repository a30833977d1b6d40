"""The epoch scalars' TensorBoard writer (hydragnn_tpu/utils/scalars.py):
an event file TensorBoard reads, written with neither torch nor
tensorflow in the process, and a run that goes on without a ``tb``
directory where ``tensorboard`` is not installed.

Every case is a fresh interpreter: what a run has imported is what is
asked, and pytest's own process has long imported everything. The file
is read back here by its framing (8-byte length, 4-byte crc, payload,
4-byte crc): TensorBoard's ``EventAccumulator`` would import tensorflow.
"""

import glob
import os
import struct
import subprocess
import sys

import pytest

import tests._cpu  # noqa: F401  (side effect: pin CPU platform)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NEITHER = (
    "import sys\n"
    "assert 'torch' not in sys.modules, 'torch was imported'\n"
    "assert 'tensorflow' not in sys.modules, 'tensorflow was imported'\n"
)


def _python(tmp_path, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("XLA_FLAGS", None)  # one CPU device: the single scheme
    r = subprocess.run(
        [sys.executable, "-c", script],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]


def _events(path):
    from tensorboard.compat.proto.event_pb2 import Event
    from tensorboard.compat.tensorflow_stub.pywrap_tensorflow import (
        masked_crc32c,
    )

    with open(path, "rb") as f:
        data = f.read()
    events, at = [], 0
    while at < len(data):
        (length,) = struct.unpack("<Q", data[at:at + 8])
        (length_crc,) = struct.unpack("<I", data[at + 8:at + 12])
        payload = data[at + 12:at + 12 + length]
        (payload_crc,) = struct.unpack(
            "<I", data[at + 12 + length:at + 16 + length]
        )
        assert length_crc == masked_crc32c(data[at:at + 8])
        assert len(payload) == length
        assert payload_crc == masked_crc32c(payload)
        events.append(Event.FromString(payload))
        at += 16 + length
    return events


def _scalars(events):
    """(tag, step, value) of every event after the version header."""
    assert events[0].file_version == "brain.Event:2"
    assert all(len(e.summary.value) == 1 for e in events[1:])
    return [
        (e.summary.value[0].tag, e.step, e.summary.value[0].simple_value)
        for e in events[1:]
    ]


def test_writer_frames_events_without_torch_or_tensorflow(tmp_path):
    pytest.importorskip("tensorboard")
    _python(
        tmp_path,
        "import glob, os\n"
        "from hydragnn_tpu.utils.scalars import ScalarsWriter\n"
        "w = ScalarsWriter('run/tb')\n"
        "(path,) = glob.glob('run/tb/events.out.tfevents.*')\n"
        "sizes = [os.path.getsize(path)]\n"
        "for step in range(4):\n"
        "    for i, tag in enumerate(['loss/train', 'lr', 'task0/train']):\n"
        "        w.add_scalar(tag, 0.5 * i + 0.125 * step, step)\n"
        "    w.flush()\n"
        "    sizes.append(os.path.getsize(path))\n"
        "w.close()\n"
        "# every flush hands the epoch's records to the file\n"
        "assert sizes[0] > 0 and sizes == sorted(set(sizes)), sizes\n"
        "assert os.path.getsize(path) == sizes[-1]\n" + NEITHER,
    )
    (path,) = glob.glob(str(tmp_path / "run/tb/events.out.tfevents.*"))
    events = _events(path)
    assert all(e.wall_time > 0 for e in events)
    assert _scalars(events) == [
        (tag, step, 0.5 * i + 0.125 * step)
        for step in range(4)
        for i, tag in enumerate(["loss/train", "lr", "task0/train"])
    ]


TINY_RUN = (
    "import json\n"
    "import hydragnn_tpu\n"
    "from hydragnn_tpu.data.synthetic import deterministic_graph_data\n"
    "deterministic_graph_data('dataset/demo', number_configurations=40,"
    " seed=1)\n"
    f"config = json.load(open({os.path.join(REPO, 'tests/inputs/ci.json')!r}))\n"
    "config['Dataset']['path'] = {'total': 'dataset/demo'}\n"
    "config['NeuralNetwork']['Training']['num_epoch'] = 3\n"
    "hist = hydragnn_tpu.run_training(config)[3]\n"
    "assert len(hist.train_loss) == 3\n"
)


def test_run_training_writes_its_scalars_without_torch_or_tensorflow(
    tmp_path,
):
    pytest.importorskip("tensorboard")
    _python(tmp_path, TINY_RUN + NEITHER)
    (path,) = glob.glob(str(tmp_path / "logs/*/tb/events.out.tfevents.*"))
    scalars = _scalars(_events(path))
    tags = ["loss/train", "loss/val", "loss/test", "lr", "task0/train"]
    assert [(t, s) for t, s, _ in scalars] == [
        (tag, epoch) for epoch in range(3) for tag in tags
    ]
    by_tag = {t: [v for tag, _, v in scalars if tag == t] for t in tags}
    # one task, weight 1: the task's loss is the loss
    assert by_tag["task0/train"] == by_tag["loss/train"]
    assert all(v == pytest.approx(0.01) for v in by_tag["lr"])
    assert all(v > 0 for t in tags[:3] for v in by_tag[t])


def test_run_training_without_tensorboard_writes_no_tb(tmp_path):
    # None in sys.modules: ``import tensorboard...`` raises ImportError
    _python(
        tmp_path,
        "import sys\nsys.modules['tensorboard'] = None\n" + TINY_RUN + NEITHER,
    )
    (run,) = glob.glob(str(tmp_path / "logs/*"))
    assert os.path.isfile(os.path.join(run, "config.json"))
    assert not os.path.exists(os.path.join(run, "tb"))
