"""The token loader: batched (seq+1)-token windows drawn from TONYTOK shards.

A batch is a pure function of ``(seed, global batch index, shard)``, read out
of memory-mapped shards when it is asked for. The loader starts no thread:
``train/input_pipeline.InputPipeline`` is the one prefetcher, and calls
:meth:`TokenLoader.next` from its producer thread.
"""

from __future__ import annotations

from pathlib import Path

import numpy as np

from tony_tpu.data.dataset import open_shard


def _splitmix(x: int) -> int:
    x = (x + 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
    return x ^ (x >> 31)


class TokenLoader:
    """Batched (seq+1)-token window sampler over TONYTOK shards.

    Windows are drawn with a splitmix hash of (seed, GLOBAL slot) and read
    from the memory-mapped shards at the call to :meth:`next`.

    GLOBAL-ORDER CONTRACT (the elastic-replay spec): the stream is ONE
    global sequence of samples, a pure function of (seed, global slot);
    shard ``k`` of ``K`` produces rows ``[k*batch, (k+1)*batch)`` of each
    global batch of ``G = batch * num_shards`` rows — i.e. local batch
    ``t``, row ``i`` is global slot ``t*G + k*batch + i``. Consequences:
    - concatenating the K shards' local batches (in shard order)
      reconstructs the K=1 stream with batch ``G`` exactly;
    - replay after a RESHARD (K -> K') is exact provided the global batch
      ``G`` is held constant (per-shard batch adapts to ``G / K'``) and the
      resumed loaders start at ``start_index`` = global batch index —
      no sample is repeated or skipped across the shape change.
    """

    def __init__(
        self,
        shard_paths: list[str | Path],
        batch: int,
        seq: int,
        *,
        shard_id: int = 0,
        num_shards: int = 1,
        seed: int = 0,
        start_index: int = 0,
    ):
        """``start_index``: first GLOBAL batch index to produce. The window
        draw is a pure function of (seed, global slot), so a resumed run
        that keeps its seed and global batch size and starts the loader at
        its step counter replays the exact uninterrupted stream — no
        repeated, no skipped samples — even across a shard-count change."""
        if not shard_paths:
            raise ValueError("no shard paths")
        if num_shards < 1 or not 0 <= shard_id < num_shards:
            raise ValueError(f"shard_id {shard_id} out of range for num_shards {num_shards}")
        if start_index < 0:
            raise ValueError(f"start_index must be >= 0, got {start_index}")
        self.batch, self.seq = batch, seq
        self.shard_id, self.num_shards, self.seed = shard_id, num_shards, seed
        self._shards = [open_shard(p) for p in shard_paths]  # mmapped, stored dtype
        self.total_tokens = int(sum(s.size for s in self._shards))
        self.num_windows = int(sum(s.size // (seq + 1) for s in self._shards))
        if self.num_windows < 1:
            raise ValueError("not enough data for a single (seq+1)-token window")
        self._index = start_index

    def _window(self, window: int) -> np.ndarray:
        stride = self.seq + 1
        for s in self._shards:
            here = s.size // stride
            if window < here:
                # per-window int32 conversion: only seq+1 tokens leave the mmap
                return np.asarray(s[window * stride:(window + 1) * stride], np.int32)
            window -= here
        raise IndexError(window)

    def _batch_at(self, index: int) -> np.ndarray:
        out = np.empty((self.batch, self.seq + 1), np.int32)
        gbatch = self.batch * self.num_shards
        nw = self.num_windows
        for i in range(self.batch):
            # global slot: this shard owns rows [k*batch, (k+1)*batch) of
            # global batch `index` — the elastic-replay contract above
            g = index * gbatch + self.shard_id * self.batch + i
            epoch, pos = divmod(g, nw)
            r = _splitmix(_splitmix(self.seed ^ _splitmix(epoch)) ^ pos)
            out[i] = self._window(r % nw)
        return out

    def next(self) -> np.ndarray:
        """Next [batch, seq+1] int32 batch (tokens + shifted targets)."""
        out = self._batch_at(self._index)
        self._index += 1
        return out

    def __iter__(self):
        while True:
            yield self.next()

    def close(self) -> None:
        """Drop the memory maps; each lives until its last reference goes."""
        self._shards = []

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
