"""Input pipelines: synthetic + memory-mapped token streams.

The reference has no data layer (user scripts bring their own input_fn);
this module provides the minimum a training job needs in a zero-egress
environment: a deterministic synthetic LM stream (benchmarks, tests) and a
memory-mapped binary token file reader (real corpora), both yielding
pre-shifted (inputs, targets) pairs shaped for the mesh's batch sharding.

Per-process sharding follows the jax.distributed contract: each process
yields only its slice of the global batch
(process_index/process_count), and jax.make_array_from_process_local_data
assembles the global array.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

Batch = tuple[jax.Array, jax.Array]  # (inputs [B,S], targets [B,S])


@dataclass(frozen=True)
class DataConfig:
    global_batch: int = 8
    seq_len: int = 2048
    vocab_size: int = 32000
    seed: int = 0
    path: str = ""  # empty -> synthetic
    # token files route through the C++ prefetching loader (shuffled epochs,
    # IO off the GIL) when it can build; False pins the numpy mmap path
    # (deterministic sequential windows)
    native: bool = True
    # device-prefetch depth: batches N+1..N+prefetch are host-generated and
    # device-placed on a background thread while the device runs step N
    # (train/prefetch.py). 0 pins the legacy synchronous path. The stream
    # order is identical either way (FIFO, single producer).
    prefetch: int = 2


def _local_slice(global_batch: int) -> tuple[int, int]:
    n, i = jax.process_count(), jax.process_index()
    if global_batch % n:
        raise ValueError(f"global batch {global_batch} not divisible by {n} processes")
    per = global_batch // n
    return per, i * per


def synthetic_batches(
    cfg: DataConfig, sharding: NamedSharding | None = None, start_step: int = 0
) -> Iterator[Batch]:
    """Endless deterministic token stream (Zipf-ish marginals so the loss
    moves like text, not uniform noise). ``start_step`` keys the generator
    per batch, so a checkpoint-resumed job continues the stream instead of
    replaying it."""
    per, _ = _local_slice(cfg.global_batch)
    ranks = np.arange(1, cfg.vocab_size + 1, dtype=np.float64)
    probs = (1.0 / ranks) / np.sum(1.0 / ranks)
    # inverse-CDF sampling over a cumulative table built ONCE: rng.choice(p=)
    # rebuilds its alias/sampling setup every call, which at vocab 32k was
    # the dominant host cost per batch. searchsorted(cum, U) draws the same
    # Zipf marginals (token t iff cum[t-1] <= U < cum[t]); the tail is
    # pinned to 1.0 so float rounding can never index past vocab_size-1.
    cum = np.cumsum(probs)
    cum[-1] = 1.0
    step = start_step
    while True:
        rng = np.random.default_rng((cfg.seed + jax.process_index(), step))
        draws = rng.random((per, cfg.seq_len + 1))
        tokens = np.searchsorted(cum, draws, side="right").astype(np.int32)
        step += 1
        yield _to_global(tokens, sharding)


def mmap_batches(
    cfg: DataConfig, sharding: NamedSharding | None = None, start_step: int = 0
) -> Iterator[Batch]:
    """Sequential reader over a flat binary int32 token file (np.memmap).

    Each process strides disjoint windows; wraps around at EOF. ``start_step``
    resumes the stream at the position step N would have read (elastic
    restart: no token is replayed or skipped).
    """
    data = np.memmap(cfg.path, dtype=np.int32, mode="r")
    per, off = _local_slice(cfg.global_batch)
    window = cfg.seq_len + 1
    stride = cfg.global_batch * window
    n = len(data)
    if n < stride:
        raise ValueError(f"token file too small: {n} tokens < one global batch {stride}")
    steps_per_epoch = n // stride  # windows before wrap-around
    step = start_step
    while True:
        pos = (step % steps_per_epoch) * stride + off * window
        chunk = data[pos : pos + per * window].reshape(per, window)
        # One contiguous copy per array instead of two strided views into
        # the page cache: the sharding assembler can then zero-copy whole
        # row-contiguous shards. The pair must be freshly owned by its
        # batch — jax's CPU device_put aliases compatible host buffers, so
        # a reused/preallocated ring would let a later copy corrupt a batch
        # still queued on device (breaks prefetch>0 determinism).
        out = (
            np.empty((per, cfg.seq_len), np.int32),
            np.empty((per, cfg.seq_len), np.int32),
        )
        step += 1
        yield _to_global(chunk, sharding, out=out)


def _to_global(
    tokens: np.ndarray,
    sharding: NamedSharding | None,
    out: tuple[np.ndarray, np.ndarray] | None = None,
) -> Batch:
    """Shift ``tokens`` into (inputs, targets) and assemble device arrays.

    ``out`` is an optional preallocated (inputs, targets) buffer pair: the
    shifted slices are written there in one contiguous pass each, so the
    assembler receives C-contiguous in-memory arrays instead of strided
    views into an mmap.
    """
    if out is not None:
        inputs, targets = out
        np.copyto(inputs, tokens[:, :-1])
        np.copyto(targets, tokens[:, 1:])
    else:
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
    return _assemble(inputs, targets, sharding)


def _assemble(
    inputs: np.ndarray, targets: np.ndarray, sharding: NamedSharding | None
) -> Batch:
    if sharding is None:
        return jnp.asarray(inputs), jnp.asarray(targets)
    return (
        jax.make_array_from_process_local_data(sharding, inputs),
        jax.make_array_from_process_local_data(sharding, targets),
    )


def native_batches(
    cfg: DataConfig, sharding: NamedSharding | None = None, start_step: int = 0
) -> Iterator[Batch]:
    """Prefetched shuffled windows via the C++ loader (train/native_loader).

    Same contract as mmap_batches — per-process [per, seq_len+1] chunks,
    ``start_step`` resume-exact via seek() — but each epoch visits every
    window of this process's shard once in a seeded order, and the read +
    shuffle + copy happens on a native thread that overlaps the device step.
    """
    from tony_tpu.train.native_loader import NativeTokenLoader

    per, _ = _local_slice(cfg.global_batch)
    loader = NativeTokenLoader(
        cfg.path, cfg.seq_len, per,
        n_shards=jax.process_count(), shard_id=jax.process_index(),
        seed=cfg.seed,
    )
    try:
        loader.seek(start_step)
        while True:
            # fresh owned contiguous pair per batch (same aliasing rule as
            # mmap_batches), filled by the loader without an extra copy
            out = (
                np.empty((per, cfg.seq_len), np.int32),
                np.empty((per, cfg.seq_len), np.int32),
            )
            loader.next_into(*out)
            yield _assemble(out[0], out[1], sharding)
    finally:
        # generator close (incl. PrefetchIterator.close / GC) frees the
        # native handle + mmap deterministically
        loader.close()


def make_batches(
    cfg: DataConfig, sharding: NamedSharding | None = None, start_step: int = 0
) -> Iterator[Batch]:
    """Build the configured batch stream; with ``cfg.prefetch > 0`` it is
    wrapped in a :class:`~tony_tpu.train.prefetch.PrefetchIterator` (same
    element order, host+H2D work overlapped with the device step). Streams
    that own a thread expose ``close()``; ``fit()`` calls it on exit."""
    it = _make_batches_raw(cfg, sharding, start_step)
    if cfg.prefetch > 0:
        from tony_tpu.train.prefetch import PrefetchIterator

        return PrefetchIterator(it, depth=cfg.prefetch)
    return it


def _make_batches_raw(
    cfg: DataConfig, sharding: NamedSharding | None = None, start_step: int = 0
) -> Iterator[Batch]:
    import logging

    log = logging.getLogger(__name__)
    if cfg.path:
        if cfg.native:
            from tony_tpu.train import native_loader

            if native_loader.available():
                # in a gang, every process must take this same branch; a
                # process whose build fails raises below instead of silently
                # mixing shuffled and sequential sampling in one global batch
                log.info("data loader: native (tony_tpu/native/tonyloader.cpp)")
                return native_batches(cfg, sharding, start_step)
            if jax.process_count() > 1:
                raise RuntimeError(
                    "native token loader unavailable on this host but "
                    "data.native=True in a multi-process job — the gang "
                    "would mix sampling schemes. Install g++ everywhere or "
                    "set DataConfig(native=False)."
                )
            log.warning(
                "native loader unavailable; falling back to sequential "
                "mmap windows (different sampling + resume stream)"
            )
        log.info("data loader: numpy mmap windows")
        return mmap_batches(cfg, sharding, start_step)
    log.info("data loader: synthetic tokens")
    return synthetic_batches(cfg, sharding, start_step)


__all__ = [
    "Batch", "DataConfig", "make_batches", "mmap_batches", "native_batches",
    "synthetic_batches",
]
