"""TensorBoard scalars without torch or tensorflow.

The training loop writes five to eight floats an epoch (reference
SummaryWriter, train_validate_test.py:371-378). ``torch.utils.tensorboard``
imports torch and tensorflow for that, and ``tensorboard``'s own
``EventFileWriter`` imports tensorflow at its first write; its record
framing and its protos import neither. This module writes the event file
with those: ``Event`` protos framed as TFRecords in a plain local file, on
the caller's thread. Importing it raises ``ImportError`` where
``tensorboard`` is not installed.
"""

from __future__ import annotations

import itertools
import os
import socket
import time

from tensorboard.compat.proto.event_pb2 import Event
from tensorboard.compat.proto.summary_pb2 import Summary
from tensorboard.summary.writer.record_writer import RecordWriter

# two writers of one process on one directory in one second
_uid = itertools.count()


class ScalarsWriter:
    """``add_scalar`` / ``flush`` / ``close`` of a SummaryWriter on
    ``log_dir``."""

    def __init__(self, log_dir: str):
        os.makedirs(log_dir, exist_ok=True)
        now = time.time()
        name = (
            f"events.out.tfevents.{int(now):010d}.{socket.gethostname()}"
            f".{os.getpid()}.{next(_uid)}"
        )
        self._records = RecordWriter(open(os.path.join(log_dir, name), "wb"))
        header = Event(wall_time=now, file_version="brain.Event:2")
        self._records.write(header.SerializeToString())
        self.flush()

    def add_scalar(self, tag: str, value: float, step: int) -> None:
        summary = Summary(
            value=[Summary.Value(tag=tag, simple_value=float(value))]
        )
        event = Event(wall_time=time.time(), step=int(step), summary=summary)
        self._records.write(event.SerializeToString())

    def flush(self) -> None:
        """Hand what was added to the file: the loop calls it once an
        epoch, so a reader follows the run epoch by epoch and a killed
        run keeps every epoch it finished."""
        self._records.flush()

    def close(self) -> None:
        self._records.close()
