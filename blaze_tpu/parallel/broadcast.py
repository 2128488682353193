"""Broadcast exchange.

≙ reference NativeBroadcastExchangeBase (doExecuteBroadcastNative /
collectNative, NativeBroadcastExchangeBase.scala:138-230) +
IpcWriterExec (ipc_writer_exec.rs): the child's partitions are drained
into framed IPC bytes, the bytes are the broadcast payload, and
downstream BroadcastJoin partitions re-read them replicated.
"""

from __future__ import annotations

import struct
from typing import List, Optional

from ..batch import RecordBatch
from ..io.batch_serde import deserialize_batch, serialize_batch
from ..io.ipc_compression import (
    block_trailer, compress_frame, iter_blob_frames,
)
from ..ops.base import BatchStream, ExecNode
from ..runtime import dispatch, faults, integrity, trace
from ..runtime.context import RESOURCES, TaskContext
from ..schema import Schema


def _collect_blob(batches, site: str) -> bytes:
    """Drain a batch stream into ONE broadcast blob: checksummed IPC
    frames (conf ``spark.blaze.io.checksum``) closed by a block
    trailer, so a consumer detects both flipped bytes (per-frame
    checksum) and silently-missing whole frames (trailer count/XOR).
    The ``broadcast.write`` @corrupt probe fires per blob, flipping a
    committed payload byte the verified read must catch."""
    algo = integrity.frame_algo()
    frames: List[bytes] = []
    xor = 0
    for b in batches:
        # D2H + serialise of one batch; the child's compute, which the
        # loop pulls, stays outside the span
        with trace.span("exchange_write"):
            frame = compress_frame(serialize_batch(b), checksum_algo=algo)
        if algo is not None:
            xor ^= struct.unpack("<BI", frame[-5:])[1]
        frames.append(frame)
    if algo is not None:
        frames.append(block_trailer(len(frames), xor, algo))
    blob = b"".join(frames)
    dispatch.record("shuffle_bytes_written", len(blob))
    if faults.corrupt("broadcast.write", detail=site):
        blob = integrity.flip_byte(blob, 5 + max(0, (len(blob) - 16) // 2))
    return blob


class IpcWriterExec(ExecNode):
    """Drains the child into IPC frames registered under a resource id
    (the broadcast collect path)."""

    def __init__(self, child: ExecNode, resource_id: str):
        super().__init__([child])
        self.resource_id = resource_id

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def execute(self, partition: int, ctx: TaskContext) -> BatchStream:
        def stream():
            faults.hit("broadcast.write", attempt=ctx.task_attempt_id,
                       detail=f"{self.resource_id}.{partition}")
            blob = _collect_blob(
                self.children[0].execute(partition, ctx),
                f"{self.resource_id}.{partition}")
            if not ctx.is_task_running():
                # cancelled (a speculative loser): the child's drain
                # stopped early, so the frames are PARTIAL — publishing
                # them would overwrite the winner's complete blob
                return
            ctx.resources.put(f"{self.resource_id}.{partition}", blob)
            return
            yield  # pragma: no cover

        return stream()


class BroadcastExchangeExec(ExecNode):
    """Collects ALL child partitions once into IPC bytes; every output
    partition replays the full payload (replicated)."""

    def __init__(self, child: ExecNode):
        super().__init__([child])
        self._payload: Optional[List[bytes]] = None

    @property
    def schema(self) -> Schema:
        return self.children[0].schema

    def num_partitions(self) -> int:
        return 1

    def collect_ipc(self, ctx: Optional[TaskContext] = None) -> List[bytes]:
        """≙ collectNative: one IPC byte-blob per child partition
        (checksummed frames + block trailer, like the scheduler's
        IpcWriterExec path)."""
        if self._payload is None:
            child = self.children[0]
            out: List[bytes] = []
            for p in range(child.num_partitions()):
                c = ctx or TaskContext(p, child.num_partitions())
                out.append(_collect_blob(child.execute(p, c),
                                         f"broadcast.{p}"))
            self._payload = out
        return self._payload

    def execute(self, partition: int, ctx: TaskContext) -> BatchStream:
        def stream():
            for blob in self.collect_ipc(ctx):
                # the shared verified walker: checksummed frames verify,
                # the block trailer is checked and consumed — a corrupt
                # replicated blob raises typed BlockCorruptionError
                # (classified RETRY) instead of feeding wrong rows to
                # every consumer partition
                for payload in iter_blob_frames(blob, site="broadcast"):
                    b = deserialize_batch(payload, self.schema)
                    if b.num_rows:
                        self._record_batch(b)
                        yield b.to_device()

        return stream()
