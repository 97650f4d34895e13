"""Deterministic, shardable, checkpointable data pipeline.

Two sources, both offline-synthesizable (this container has no datasets):

* :class:`SyntheticLM` — a deterministic "hash-LM" token stream with real
  learnable structure: tokens follow a hidden order-2 Markov chain, a
  seeded hash of the previous two tokens, so models actually reduce loss
  and compression/accuracy comparisons (Table 1 analogues) are meaningful.
* :class:`TeacherStudent` — classification batches from a frozen random
  teacher MLP (inputs ~ N(0,1), labels = argmax of the teacher). This is the
  LeNet-300-100/MNIST stand-in used by the paper-figure benchmarks: the task
  is exactly learnable, so "accuracy loss vs non-compressed" is measurable.

Both iterators are stateless functions of (seed, step, shard), so (a) any
host can produce its own shard without coordination — the multi-host layout
— and (b) checkpoint/restore only needs the integer ``step`` (see
``state()`` / ``restore()``), giving exactly-once data under preemption.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class SyntheticLM:
    vocab: int
    seq_len: int
    global_batch: int
    seed: int = 0
    shard_index: int = 0
    shard_count: int = 1
    step: int = 0

    def __post_init__(self):
        assert self.global_batch % self.shard_count == 0
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 17]))
        # hidden order-2 Markov structure (shared across shards)
        self._key = np.uint64(rng.integers(0, 2**63))
        self._noise_p = 0.1

    def transition(self, prev2, prev1) -> np.ndarray:
        """The hidden chain's next token after ``(prev2, prev1)``: a seeded
        hash of the pair (splitmix64's finaliser), so no ``vocab x vocab``
        table is held — at a 50k vocab that table alone is 20 GB."""
        v = np.uint64(self.vocab)
        with np.errstate(over="ignore"):
            x = (np.asarray(prev2, np.uint64) * v
                 + np.asarray(prev1, np.uint64)) ^ self._key
            x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
            x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
            x = x ^ (x >> np.uint64(31))
        return (x % v).astype(np.int32)

    @property
    def local_batch(self) -> int:
        return self.global_batch // self.shard_count

    def _rows(self, step: int) -> np.ndarray:
        b = self.local_batch
        row0 = step * self.global_batch + self.shard_index * b
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, 101, step, self.shard_index]))
        toks = np.empty((b, self.seq_len + 1), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, b)
        toks[:, 1] = rng.integers(0, self.vocab, b)
        for t in range(2, self.seq_len + 1):
            nxt = self.transition(toks[:, t - 2], toks[:, t - 1])
            noise = rng.random(b) < self._noise_p
            nxt = np.where(noise, rng.integers(0, self.vocab, b), nxt)
            toks[:, t] = nxt
        return toks

    def next(self) -> Dict[str, np.ndarray]:
        toks = self._rows(self.step)
        self.step += 1
        return {"inputs": toks[:, :-1], "labels": toks[:, 1:]}

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        while True:
            yield self.next()

    # --- checkpointable state -------------------------------------------
    def state(self) -> Dict[str, int]:
        return {"step": self.step, "seed": self.seed}

    def restore(self, st: Dict[str, int]) -> None:
        assert st["seed"] == self.seed, "restoring stream with different seed"
        self.step = int(st["step"])


@dataclasses.dataclass
class TeacherStudent:
    """Frozen-teacher classification data (MNIST stand-in).

    ``kind="clusters"`` (default): inputs are draws from ``n_classes``
    well-separated Gaussian clusters pushed through a fixed random nonlinear
    lift — high (~98-99%) accuracy is achievable, like MNIST, so the paper's
    "<1 point accuracy loss at 10x" claim has headroom to be tested.
    ``kind="argmax"``: harder argmax-of-random-MLP labels.

    d_in defaults to 800 (vs MNIST's 784) so the paper's compression factor
    c=10 divides every FC layer of LeNet-300-100 exactly.
    """

    d_in: int = 800
    n_classes: int = 10
    batch: int = 50
    seed: int = 0
    step: int = 0
    teacher_hidden: int = 64
    kind: str = "clusters"
    cluster_noise: float = 1.45

    def __post_init__(self):
        rng = np.random.default_rng(np.random.SeedSequence([self.seed, 31]))
        self._w1 = rng.normal(size=(self.d_in, self.teacher_hidden)).astype(np.float32)
        self._w1 /= np.sqrt(self.d_in)
        self._w2 = rng.normal(size=(self.teacher_hidden, self.n_classes)).astype(np.float32)
        self._w2 /= np.sqrt(self.teacher_hidden)
        # cluster centres in a low-dim latent, lifted by a fixed random map
        self._centers = rng.normal(size=(self.n_classes, 32)).astype(np.float32)
        self._lift = rng.normal(size=(32, self.d_in)).astype(np.float32) / np.sqrt(32)

    def _make(self, step: int, batch: int) -> Tuple[np.ndarray, np.ndarray]:
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, 57, step + 2**31]))
        if self.kind == "clusters":
            y = rng.integers(0, self.n_classes, batch).astype(np.int32)
            z = self._centers[y] + self.cluster_noise * rng.normal(
                size=(batch, 32)).astype(np.float32)
            x = np.tanh(z @ self._lift) + 0.20 * rng.normal(
                size=(batch, self.d_in)).astype(np.float32)
            return x.astype(np.float32), y
        x = rng.normal(size=(batch, self.d_in)).astype(np.float32)
        h = np.tanh(x @ self._w1)
        y = np.argmax(h @ self._w2, axis=-1).astype(np.int32)
        return x, y

    def next(self) -> Dict[str, np.ndarray]:
        x, y = self._make(self.step, self.batch)
        self.step += 1
        return {"inputs": x, "labels": y}

    def eval_set(self, n: int = 2048) -> Dict[str, np.ndarray]:
        x, y = self._make(-1, n)
        return {"inputs": x, "labels": y}

    def state(self) -> Dict[str, int]:
        return {"step": self.step, "seed": self.seed}

    def restore(self, st: Dict[str, int]) -> None:
        assert st["seed"] == self.seed
        self.step = int(st["step"])
