"""Synthetic, deterministic, resumable token pipeline with host prefetch.

Production shape: each host materializes only its slice of the global batch
(``jax.make_array_from_process_local_data`` in multi-process deployments);
on a single process we device_put with the global NamedSharding.  The
stream is seeded and step-indexed, so checkpoint resume is exact: the
manifest records (seed, next_step).
"""
from __future__ import annotations

import queue
import threading
from dataclasses import dataclass

import jax
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding

from repro.sharding.specs import batch_pspec


@dataclass
class SyntheticLM:
    vocab_size: int
    global_batch: int
    seq_len: int
    seed: int = 0

    def batch_at(self, step: int) -> dict[str, np.ndarray]:
        rng = np.random.default_rng((self.seed, step))
        toks = rng.integers(
            0, self.vocab_size, (self.global_batch, self.seq_len + 1),
            dtype=np.int32,
        )
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


class Prefetcher:
    """Background-thread prefetch + device transfer (straggler hiding).

    Each batch is transferred to the device once.  An exception raised in
    the worker (a failed transfer, say) is raised again by ``next()``.
    Producing a batch (``batch_at`` and the transfer) is a ``data.produce``
    span in a profile.
    """

    def __init__(self, dataset: SyntheticLM, mesh: Mesh, start_step: int = 0,
                 depth: int = 2, extras: dict | None = None):
        self.dataset = dataset
        self.mesh = mesh
        self.extras = extras or {}
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._step = start_step
        self._error: BaseException | None = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def _shard(self, batch: dict) -> dict:
        out = {}
        for k, v in {**batch, **self.extras}.items():
            sh = NamedSharding(
                self.mesh, batch_pspec(v.shape, self.mesh, v.shape[0])
            )
            out[k] = jax.device_put(v, sh)
        return out

    def _put(self, item) -> bool:
        """Enqueue ``item`` unless closed first; True once it is queued."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.5)
                return True
            except queue.Full:
                continue
        return False

    def _worker(self):
        step = self._step
        try:
            while True:
                with TraceAnnotation("data.produce"):
                    item = (step, self._shard(self.dataset.batch_at(step)))
                if not self._put(item):
                    break
                step += 1
        except Exception as e:  # handed to the consumer, raised by next()
            self._put(e)

    def __iter__(self):
        return self

    def __next__(self):
        if self._error is None:
            item = self._q.get()
            if not isinstance(item, Exception):
                return item
            self._error = item
        raise self._error

    def close(self):
        self._stop.set()
        self._thread.join(timeout=10)
