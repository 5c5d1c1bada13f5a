"""Memmapped batch loader with per-host sharding and background prefetch.

Reimplements nanoGPT's get_batch contract (random-offset windows from
train.bin/val.bin memmaps; SURVEY.md §2.3 #28) with the two changes the TPU
architecture demands (SURVEY.md §7 hard part (b)):

  * **Per-host sharding** — under multi-host SPMD every process loads only
    its slice of the global batch. Offsets are drawn from a stream keyed by
    (seed, split, step, process_index) so hosts sample disjoint batches
    without communicating (the DDP analogue was implicit per-rank RNG).
  * **Background prefetch** — a worker thread stages the next batch while
    the current step runs on the chip, hiding host-side gather latency.
    The gather itself is native C++ (csrc/batchgen.cpp) when available.
"""

from __future__ import annotations

import os
import pickle
import queue
import threading

import numpy as np

from nanosandbox_tpu.obs import process_tracer
from nanosandbox_tpu.utils import native

# The prefetch thread's spans get a track of their own in an export.
_PREFETCH_TRACK = "loader_prefetch"


class BinDataset:
    """A prepared dataset directory: {train,val}.bin (+ meta.pkl)."""

    def __init__(self, data_dir: str, dataset: str):
        self.dir = os.path.join(data_dir, dataset)
        self.splits: dict[str, np.ndarray] = {}
        for split in ("train", "val"):
            path = os.path.join(self.dir, f"{split}.bin")
            if not os.path.exists(path):
                raise FileNotFoundError(
                    f"{path} not found — run `python -m nanosandbox_tpu.data.prepare "
                    f"{dataset} --data_dir={data_dir}` first")
            self.splits[split] = np.memmap(path, dtype=np.uint16, mode="r")
        meta_path = os.path.join(self.dir, "meta.pkl")
        self.meta: dict = {}
        if os.path.exists(meta_path):
            with open(meta_path, "rb") as f:
                self.meta = pickle.load(f)

    @property
    def vocab_size(self) -> int:
        # nanoGPT default: GPT-2 vocab padded to a multiple of 64 when no meta.
        return int(self.meta.get("vocab_size", 50304))

    def tokens(self, split: str) -> int:
        return int(self.splits[split].shape[0])

    def sample_batch(self, split: str, step: int, batch_size: int,
                     block_size: int, *, seed: int = 1337,
                     process_index: int = 0) -> tuple[np.ndarray, np.ndarray]:
        """Sample (x, y) int32 arrays of shape (batch_size, block_size)."""
        data = self.splits[split]
        width = block_size + 1
        split_tag = 0 if split == "train" else 1
        # Mix step/host/split into a single stream id for the native sampler.
        stream = (np.uint64(step) * np.uint64(0x10001)
                  + np.uint64(process_index) * np.uint64(2)
                  + np.uint64(split_tag))
        offsets = native.sample_offsets(seed, int(stream), data.shape[0],
                                        width, batch_size)
        windows = native.gather_windows(data, offsets, width)
        xy = windows.astype(np.int32)
        return xy[:, :-1], xy[:, 1:]


class BatchLoader:
    """Iterator over per-host batches with one-batch-ahead prefetch."""

    # Queue sentinel: the worker died on the exception stored in
    # self._worker_exc. An object(), not None, so a legitimate batch can
    # never be mistaken for it.
    _FAILED = object()

    def __init__(self, dataset: BinDataset, split: str, batch_size: int,
                 block_size: int, *, seed: int = 1337, process_index: int = 0,
                 num_processes: int = 1, start_step: int = 0,
                 prefetch: bool = True):
        if batch_size % num_processes:
            raise ValueError(
                f"global batch_size {batch_size} not divisible by "
                f"num_processes {num_processes}")
        self.dataset = dataset
        self.split = split
        self.global_batch_size = batch_size
        self.local_batch_size = batch_size // num_processes
        self.block_size = block_size
        self.seed = seed
        self.process_index = process_index
        self.step = start_step
        self.native = native.get_lib() is not None
        self._queue: queue.Queue | None = None
        self._worker_exc: BaseException | None = None
        self._tracer = process_tracer()
        if prefetch:
            self._queue = queue.Queue(maxsize=2)
            self._stop = threading.Event()
            self._thread = threading.Thread(target=self._worker, daemon=True)
            self._thread.start()

    def _load(self, step: int) -> tuple[np.ndarray, np.ndarray]:
        return self.dataset.sample_batch(
            self.split, step, self.local_batch_size, self.block_size,
            seed=self.seed, process_index=self.process_index)

    def _put(self, item) -> None:
        """Blocking put that still honors close() (bounded queue: a dead
        consumer must not wedge the worker forever). A put that finds
        the queue full is the producer's slack: span ``loader_full``."""
        try:
            self._queue.put_nowait(item)
            return
        except queue.Full:
            pass
        with self._tracer.span("loader_full", cat="loader",
                               track=_PREFETCH_TRACK):
            while not self._stop.is_set():
                try:
                    self._queue.put(item, timeout=0.1)
                    return
                except queue.Full:
                    continue

    def _worker(self) -> None:
        step = self.step
        try:
            while not self._stop.is_set():
                with self._tracer.span("loader_fill", cat="loader",
                                       step=step, track=_PREFETCH_TRACK):
                    batch = self._load(step)
                self._put((step, batch))
                step += 1
        except Exception as e:
            # A worker exception (truncated .bin mid-run, mmap I/O error)
            # used to kill the thread silently and leave __next__ blocked
            # forever on an empty queue. Park the exception and push the
            # sentinel through the queue so the consumer re-raises at its
            # next (and every later) __next__.
            self._worker_exc = e
            self._put(self._FAILED)

    def __iter__(self):
        return self

    def __next__(self) -> tuple[np.ndarray, np.ndarray]:
        if self._queue is not None:
            # depth: batches found waiting; 0 means the loop waits for the
            # worker (it is starved).
            with self._tracer.span("loader_wait", cat="loader",
                                   step=self.step,
                                   args={"depth": self._queue.qsize()}):
                item = self._queue.get()
            if item is self._FAILED:
                # Re-queue the sentinel: the worker is dead (nothing else
                # will ever be enqueued), so every subsequent __next__
                # must also raise instead of blocking forever.
                self._queue.put(item)
                raise RuntimeError(
                    f"BatchLoader prefetch worker failed on split "
                    f"{self.split!r}: {self._worker_exc!r}"
                ) from self._worker_exc
            step, batch = item
            self.step = step + 1
            return batch
        with self._tracer.span("loader_fill", cat="loader", step=self.step):
            batch = self._load(self.step)
        self.step += 1
        return batch

    def close(self) -> None:
        if self._queue is not None:
            self._stop.set()
            try:
                while True:
                    self._queue.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=2)
