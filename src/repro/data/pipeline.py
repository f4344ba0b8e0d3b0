"""Data pipeline: deterministic synthetic token streams, host-sharded loading
and background prefetch.

Real deployments swap ``SyntheticTokenSource`` for a tokenized corpus reader;
everything downstream (host sharding, slot-major batch layout, prefetch)
is production-shaped. Determinism contract: the tokens for (trial k, step t,
microbatch m, row r) depend only on (seed, k, t, m, r) — so a restarted or
re-sharded job sees identical data, which keeps Hydra's exact-replication
guarantee (paper D3) across failures and elastic re-meshes.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Iterator

import numpy as np

from repro.configs.base import ArchConfig
from repro.core.pipeline import EngineConfig
from repro.obs.tracer import resolve


def _philox(seed: int, *counters: int) -> np.random.Generator:
    return np.random.Generator(np.random.Philox(key=seed, counter=counters[0]))


@dataclasses.dataclass(frozen=True)
class SyntheticTokenSource:
    """Zipf-ish synthetic token stream (deterministic per coordinates)."""

    vocab_size: int
    seq_len: int
    seed: int = 0

    def sequence(self, trial: int, step: int, micro: int, row: int) -> np.ndarray:
        ctr = ((trial * 1_000_003 + step) * 1_000_033 + micro) * 1_000_037 + row
        rng = _philox(self.seed, ctr)
        # zipf-flavored ids clipped to vocab (more realistic than uniform)
        raw = rng.zipf(1.3, size=self.seq_len + 1)
        return (raw % self.vocab_size).astype(np.int32)


@dataclasses.dataclass(frozen=True)
class HostShard:
    """Which global batch rows this host materializes (multi-host loading)."""

    process_index: int
    process_count: int

    def rows(self, global_rows: int) -> range:
        per = global_rows // self.process_count
        lo = self.process_index * per
        hi = global_rows if self.process_index == self.process_count - 1 \
            else lo + per
        return range(lo, hi)


def _gen_tokens(vocab: int, seq: int, eng: EngineConfig, step: int,
                seed: int) -> np.ndarray:
    mb_global = eng.microbatch * (1 if eng.batch_replicated
                                  else eng.data_size * eng.pod_size)
    src = SyntheticTokenSource(vocab, seq, seed)
    out = np.empty((eng.n_trials, eng.n_microbatches, mb_global, seq + 1),
                   np.int32)
    for k in range(eng.n_trials):
        for m in range(eng.n_microbatches):
            for r in range(mb_global):
                out[k, m, r] = src.sequence(k, step, m, r)
    return out


class TrainBatches:
    """Iterator of slot-major train batches with background prefetch.

    ``tracer`` (``repro.obs.Tracer``) records a ``data.batch`` span around
    each ``batch_for_step`` call; the prefetch thread records none."""

    def __init__(self, cfg: ArchConfig, eng: EngineConfig, seq_len: int,
                 seed: int = 0, prefetch: int = 2,
                 frontend_fn=None, mrope_fn=None, tracer=None):
        self.cfg, self.eng, self.seq_len, self.seed = cfg, eng, seq_len, seed
        self.frontend_fn, self.mrope_fn = frontend_fn, mrope_fn
        self.trace = resolve(tracer)
        self._q: queue.Queue = queue.Queue(maxsize=prefetch)
        self._stop = threading.Event()
        self._step = 0
        self._thread = threading.Thread(target=self._producer, daemon=True)
        self._thread.start()

    def batch_for_step(self, step: int) -> dict:
        with self.trace.span("data.batch"):
            return self._batch(step)

    def _batch(self, step: int) -> dict:
        full = _gen_tokens(self.cfg.vocab_size, self.seq_len, self.eng, step,
                           self.seed)
        batch = {"tokens": full[..., :-1], "labels": full[..., 1:]}
        if self.cfg.frontend is not None:
            nf = self.cfg.n_frontend_tokens
            mbg = full.shape[2]
            rng = _philox(self.seed + 17, step)
            batch["frontend_embeds"] = rng.standard_normal(
                (self.eng.n_trials, self.eng.n_microbatches, mbg, nf,
                 self.cfg.d_model)).astype(np.float32)
        if self.cfg.rope == "mrope":
            mbg = full.shape[2]
            batch["mrope_pos"] = np.broadcast_to(
                np.arange(self.seq_len, dtype=np.int32),
                (self.eng.n_trials, self.eng.n_microbatches, 3, mbg,
                 self.seq_len)).copy()
        return batch

    def _producer(self):
        while not self._stop.is_set():
            b = self._batch(self._step)
            self._step += 1
            while not self._stop.is_set():
                try:
                    self._q.put(b, timeout=0.25)
                    break
                except queue.Full:
                    continue

    def __next__(self) -> dict:
        return self._q.get()

    def __iter__(self) -> Iterator[dict]:
        return self

    def close(self):
        self._stop.set()
