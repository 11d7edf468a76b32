"""Deterministic, shardable synthetic data pipeline for the LM architectures.

Production shape: an infinite stream of (tokens, targets, loss_weight)
batches, derived from a counter-based PRNG so that
  * any (step, dp_rank) pair regenerates its shard without coordination
    (restart/elasticity: the "data cursor" is just the step counter),
  * the gradient-coding subset structure is explicit: the global batch of a
    step is partitioned into M subsets; subset k is materialized on every DP
    rank that holds it (redundant computation, Sec. III of the paper).

The synthetic token distribution is a mixture of Zipfian unigrams with a
deterministic per-position Markov perturbation — enough structure that the
loss decreases during smoke training, with zero I/O.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Iterator, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.tracing import span

__all__ = ["SyntheticLMConfig", "synthetic_lm_batch", "subset_batch_for_rank",
           "coded_train_batch", "elastic_train_batch", "coded_batch_stream",
           "prefetch_to_device", "PrefetchStats", "host_stream"]


@dataclasses.dataclass(frozen=True)
class SyntheticLMConfig:
    vocab_size: int
    seq_len: int
    global_batch: int
    num_subsets: int = 0          # 0 => one subset per DP rank (plain DP)
    seed: int = 0

    def subsets(self, num_dp_ranks: int) -> int:
        return self.num_subsets or num_dp_ranks


def synthetic_lm_batch(key: jax.Array, step: int, batch: int, seq_len: int,
                       vocab: int) -> jnp.ndarray:
    """(batch, seq_len+1) int32 tokens, deterministic in (key, step)."""
    k = jax.random.fold_in(key, jnp.asarray(step, jnp.uint32))
    # Zipf-ish unigram sampling via inverse-CDF on exponential ranks
    u = jax.random.uniform(k, (batch, seq_len + 1), minval=1e-6, maxval=1.0)
    ranks = jnp.floor(jnp.exp(u * jnp.log(float(vocab)))) - 1.0
    toks = jnp.clip(ranks.astype(jnp.int32), 0, vocab - 1)
    # Markov perturbation: with prob .25 copy previous token (adds structure)
    k2 = jax.random.fold_in(k, 1)
    copy = jax.random.uniform(k2, toks.shape) < 0.25
    toks = jnp.where(copy, jnp.roll(toks, 1, axis=-1), toks)
    return toks


def subset_batch_for_rank(key: jax.Array, step, subset_ids: np.ndarray,
                          subset_weights: np.ndarray, per_subset: int,
                          seq_len: int, vocab: int
                          ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Materialize the union of a rank's subsets for one step.

    subset_ids: (n_local,) static subset indices held by this rank (from the
    allocation matrix S); subset_weights: 1/(d_k (1-p)) per local subset.
    Returns (tokens (B, L+1), targets implicit, per-example weight (B,)).
    The per-example weights implement the coded sum  sum_k w_k grad f_k  as a
    single weighted backward pass (DESIGN.md Sec. 2).
    """
    batches, weights = [], []
    for sid, w in zip(subset_ids.tolist(), subset_weights.tolist()):
        sk = jax.random.fold_in(key, np.uint32(sid))
        toks = synthetic_lm_batch(sk, step, per_subset, seq_len, vocab)
        batches.append(toks)
        weights.append(jnp.full((per_subset,), w, jnp.float32))
    return jnp.concatenate(batches, 0), jnp.concatenate(weights, 0)


def coded_train_batch(key: jax.Array, step, allocation, W, per_subset: int,
                      seq_len: int, vocab: int
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One GLOBAL coded batch for the mesh train step, straight from the
    synthetic pipeline: (tokens (N_code, b_loc, L+1) i32,
    weights (N_code, b_loc) f32).

    Rank i's rows are the union of its allocated subsets
    (`subset_batch_for_rank`); subset k's tokens are keyed by the subset id
    alone, so every rank holding k regenerates the IDENTICAL rows without
    coordination (the redundant computation of Sec. III), and the
    per-example weight folds the encode weight W[i, k] / per_subset so
    stage 1's weighted backward pass IS the coded sum of eq. 3.  Feed the
    SAME W the trainer aggregates with (rate-aware or mean-rate)."""
    Wn = np.asarray(W)
    toks, wts = [], []
    for i in range(allocation.num_devices):
        sids = allocation.subsets_of(i)
        t, w = subset_batch_for_rank(key, step, sids,
                                     Wn[i, sids] / per_subset,
                                     per_subset, seq_len, vocab)
        toks.append(t)
        wts.append(w)
    return jnp.stack(toks), jnp.stack(wts)


def elastic_train_batch(key: jax.Array, step, allocation, per_subset: int,
                        seq_len: int, vocab: int
                        ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """`coded_train_batch` with the encode weights left OUT of the batch:
    (tokens (N_code, b_loc, L+1) i32, weights (N_code, b_loc) f32 = 1,
    subset_ids (N_code, b_loc) i32).

    The dynamic coding plane folds W in-graph instead:
    `take_along_axis(W / per_subset, subset_ids, 1)`, with the division
    applied HOST-side by `launch.train.elastic_coding_state` — the
    identical IEEE f32 division the static path does here, so with the
    same W the two paths produce bit-for-bit equal per-example weights
    while W stays free to change every step without a retrace.  Tokens
    are generated subset-by-subset exactly as `coded_train_batch` does,
    so the examples themselves are bit-identical too.

    Requires a uniform per-rank subset count (the stacked shape must be
    rectangular AND stable across re-allocations):
    `rate_aware_allocation(..., exact_load=True)` or `cyclic_allocation`
    with N | d*M guarantee it.
    """
    counts = np.asarray(allocation.S).sum(axis=1)
    if np.any(counts != counts[0]):
        raise ValueError(
            f"elastic batches need a uniform per-rank subset count, got "
            f"loads {counts.tolist()} — use rate_aware_allocation("
            f"exact_load=True)")
    toks, sids_out = [], []
    for i in range(allocation.num_devices):
        sids = allocation.subsets_of(i)
        rows = []
        for sid in sids.tolist():
            sk = jax.random.fold_in(key, np.uint32(sid))
            rows.append(synthetic_lm_batch(sk, step, per_subset, seq_len,
                                           vocab))
        toks.append(jnp.concatenate(rows, 0))
        sids_out.append(np.repeat(sids.astype(np.int32), per_subset))
    b_loc = int(counts[0]) * per_subset
    weights = jnp.ones((allocation.num_devices, b_loc), jnp.float32)
    return jnp.stack(toks), weights, jnp.asarray(np.stack(sids_out))


def coded_batch_stream(key: jax.Array, allocation, W, per_subset: int,
                       seq_len: int, vocab: int, start_step: int = 0
                       ) -> Iterator[Tuple[jnp.ndarray, jnp.ndarray]]:
    """Infinite iterator of `coded_train_batch(key, t, ...)` for
    t = start_step, start_step+1, ... — the generator half of the
    prefetched train loop (`prefetch_to_device`).  Deterministic in
    (key, step), so prefetching cannot change what any step trains on."""
    step = start_step
    while True:
        yield coded_train_batch(key, step, allocation, W, per_subset,
                                seq_len, vocab)
        step += 1


@dataclasses.dataclass
class PrefetchStats:
    """Host-side counters for one `prefetch_to_device` stream.

    Single-writer per field (the worker owns producer-side counters, the
    consumer thread the rest), so reads are safe snapshots without a lock:

      put_count        batches staged (device_put done, parked in queue)
      get_count        batches the consumer pulled
      producer_wait_s  worker time blocked on a FULL queue (consumer is
                       the bottleneck — prefetch is doing its job)
      consumer_wait_s  consumer time blocked on an EMPTY queue (host batch
                       construction is on the critical path — the stall
                       prefetch exists to remove; ~0 once warmed up)
      device_put_s     worker time inside the host->device transfer
      max_depth        high-water queue occupancy (<= size)
      depth_sum        sum of occupancies seen at each get (mean depth =
                       depth_sum / get_count)
    """

    size: int = 0
    put_count: int = 0
    get_count: int = 0
    producer_wait_s: float = 0.0
    consumer_wait_s: float = 0.0
    device_put_s: float = 0.0
    max_depth: int = 0
    depth_sum: int = 0

    def snapshot(self) -> dict:
        """Plain-dict copy (the `prefetch` JSONL record's `stats` body)."""
        return dataclasses.asdict(self)


class _DevicePrefetch:
    """Iterator form of `prefetch_to_device` exposing `.stats`.

    Matches the previous generator's observable behavior exactly: same
    order/values as mapping device_put over the source, exceptions
    re-raised at the consumer's next pull, `.close()` (and exhaustion)
    stops + JOINS the worker."""

    def __init__(self, it: Iterator, size: int, shardings):
        if size < 1:
            raise ValueError("prefetch size must be >= 1")
        self.stats = PrefetchStats(size=size)
        self._q: "queue.Queue" = queue.Queue(maxsize=size)
        self._stop = threading.Event()
        self._sentinel = object()
        self._err: list = []
        self._done = False
        self._it = it
        self._shardings = shardings
        self._th = threading.Thread(target=self._worker, daemon=True,
                                    name="repro-prefetch")
        self._th.start()

    def _worker(self):
        q, stop, stats = self._q, self._stop, self.stats
        try:
            for item in self._it:
                t0 = time.perf_counter()
                with span("repro.feed.put"):
                    item = (jax.device_put(item, self._shardings)
                            if self._shardings is not None
                            else jax.device_put(item))
                stats.device_put_s += time.perf_counter() - t0
                t0 = time.perf_counter()
                while not stop.is_set():
                    try:
                        q.put(item, timeout=0.1)
                        stats.put_count += 1
                        break
                    except queue.Full:
                        continue
                stats.producer_wait_s += time.perf_counter() - t0
                if stop.is_set():
                    return
        except BaseException as exc:   # re-raised on the consumer side
            self._err.append(exc)
        finally:
            while not stop.is_set():
                try:
                    q.put(self._sentinel, timeout=0.1)
                    break
                except queue.Full:
                    continue

    def __iter__(self) -> "_DevicePrefetch":
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        stats = self.stats
        depth = self._q.qsize()
        stats.max_depth = max(stats.max_depth, depth)
        stats.depth_sum += depth
        t0 = time.perf_counter()
        item = self._q.get()
        stats.consumer_wait_s += time.perf_counter() - t0
        if item is self._sentinel:
            self._done = True
            self.close()
            if self._err:
                raise self._err[0]
            raise StopIteration
        stats.get_count += 1
        return item

    def close(self) -> None:
        """Stop + join the worker (idempotent).  Abandoning the stream
        mid-flight must not leak a blocked thread; a daemon still inside
        jax.device_put at interpreter exit aborts from XLA's C++
        teardown, hence the join."""
        self._done = True
        self._stop.set()
        # unblock a worker stuck on q.put, then wait for it to wind down
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._th.join(timeout=5.0)

    def __del__(self):
        try:
            if not self._done:
                self.close()
        except Exception:
            pass


def prefetch_to_device(it: Iterator, size: int = 2,
                       shardings=None) -> _DevicePrefetch:
    """Host -> device prefetcher: a background thread pulls from `it`,
    `jax.device_put`s each item (against `shardings` when given), and
    parks up to `size` device-resident items in a bounded queue.

    With size=2 (double buffer) the host is generating + transferring step
    t+1's coded batch while the mesh executes step t, hiding the
    host-side batch construction behind device compute — the step-ahead
    pipeline of ROADMAP open item 3.  Ordering is preserved exactly and
    items are never dropped, so consuming this iterator is
    indistinguishable from mapping device_put over `it`.

    The returned iterator exposes `.stats` (a `PrefetchStats`) counting
    queue depth and producer/consumer blocked time — `consumer_wait_s`
    rising above ~0 after warmup is the regression signature of the
    worker stall the PR 6 perf pass chased (host batch construction back
    on the step's critical path); `repro.obs.MetricsLogger.log_prefetch`
    takes `.stats.snapshot()` verbatim.

    The worker thread is a daemon and also honors a stop event set when
    the consumer abandons the iterator (`.close()`), so partial
    consumption cannot leak a blocked thread; closing the iterator also
    JOINS the worker (a daemon still inside jax.device_put at interpreter
    exit aborts from XLA's C++ teardown).  Exceptions raised by `it` or
    by the transfer re-raise at the consumer's next pull.

    CAVEAT (XLA:CPU fake devices): the worker issues jax client calls
    (device_put, and any jax ops inside `it`) concurrently with whatever
    the consumer thread executes.  On the CPU backend's in-process
    collectives this can race the all-participant rendezvous of a mesh
    step and stall it (observed as `collective_ops_utils` "may be stuck"
    spam), so the train loop keeps prefetch OPT-IN (TrainRun.prefetch=0)
    until an accelerator backend lands; single-device streams (no
    collectives) are unaffected."""
    return _DevicePrefetch(it, size, shardings)


def host_stream(cfg: SyntheticLMConfig, start_step: int = 0
                ) -> Iterator[jnp.ndarray]:
    """Host-side infinite stream of global batches (single-host testing)."""
    key = jax.random.PRNGKey(cfg.seed)
    step = start_step
    while True:
        yield synthetic_lm_batch(key, step, cfg.global_batch, cfg.seq_len,
                                 cfg.vocab_size)
        step += 1
