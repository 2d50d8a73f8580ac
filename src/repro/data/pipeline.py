"""Prefetching host->device pipeline.

A background thread keeps ``depth`` batches materialized ahead of the
training loop (the host-side half of compute/transfer overlap; on real TPU
hosts this hides input latency behind the device step). Each fill (the
upstream ``next`` and the ``transform``) is recorded as an ``input_fill``
span in a profiler trace."""
from __future__ import annotations

import queue
import threading
from typing import Callable, Iterator

import jax
from jax.profiler import TraceAnnotation

_END = object()  # the upstream iterator is exhausted


class Prefetcher:
    def __init__(self, it: Iterator, depth: int = 2,
                 transform: Callable | None = None):
        self.it = it
        self.q: queue.Queue = queue.Queue(maxsize=depth)
        self.transform = transform or (lambda x: jax.tree.map(jax.numpy.asarray, x))
        self._err: BaseException | None = None
        self._stop = threading.Event()
        self.t = threading.Thread(target=self._work, daemon=True)
        self.t.start()

    def _work(self):
        try:
            it = iter(self.it)
            while True:
                with TraceAnnotation("input_fill"):
                    item = next(it, _END)
                    if item is _END or self._stop.is_set():
                        return
                    item = self.transform(item)
                self.q.put(item)
        except BaseException as e:  # noqa: BLE001
            self._err = e
            self.q.put(None)

    def __iter__(self):
        return self

    def __next__(self):
        item = self.q.get()
        if item is None:
            if self._err is not None:
                raise self._err
            raise StopIteration
        return item

    def close(self):
        self._stop.set()
        try:
            while True:
                self.q.get_nowait()
        except queue.Empty:
            pass
