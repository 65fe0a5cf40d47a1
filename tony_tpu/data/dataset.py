"""TONYTOK shard format: flat token streams for LM pretraining.

Layout (little-endian): 8-byte magic ``TONYTOK1``, u32 dtype (0=uint16,
1=int32), u64 token count, then the flat token payload. uint16 covers
vocabularies <= 65535 (2 bytes/token on disk); int32 covers the rest.
``data/loader.TokenLoader`` reads windows out of memory-mapped shards.

Elastic-replay primitives (docs/fault-tolerance.md "Elastic training"):
:func:`global_slots` is the single definition of which GLOBAL sample slots a
rank owns in a global batch, and :class:`ConsumptionCursor` persists how far
the stream has been consumed — together they make "no sample dropped or
double-consumed across a live resize of the data axis" a checkable property
instead of a hope.
"""

from __future__ import annotations

import json
import os
import struct
from dataclasses import asdict, dataclass
from pathlib import Path

import numpy as np

MAGIC = b"TONYTOK1"
HEADER_SIZE = 20  # 8-byte magic + u32 dtype + u64 count

_DTYPES = {0: np.dtype("<u2"), 1: np.dtype("<i4")}


def write_token_shard(path: str | Path, tokens: np.ndarray) -> Path:
    """Write one shard; dtype picked from the token range."""
    path = Path(path)
    tokens = np.asarray(tokens).ravel()
    if tokens.size and int(tokens.min()) < 0:
        raise ValueError("negative token ids")
    code = 0 if (tokens.size == 0 or int(tokens.max()) <= 0xFFFF) else 1
    payload = tokens.astype(_DTYPES[code])
    with open(path, "wb") as f:
        f.write(MAGIC)
        f.write(struct.pack("<IQ", code, payload.size))
        f.write(payload.tobytes())
    return path


class TokenShardWriter:
    """Streaming writer: append token arrays, roll shards at ``shard_tokens``."""

    def __init__(self, out_dir: str | Path, prefix: str = "shard", shard_tokens: int = 1 << 24):
        self.out_dir = Path(out_dir)
        self.out_dir.mkdir(parents=True, exist_ok=True)
        self.prefix = prefix
        self.shard_tokens = shard_tokens
        self._buf: list[np.ndarray] = []
        self._buffered = 0
        self._shards: list[Path] = []

    def append(self, tokens: np.ndarray) -> None:
        tokens = np.asarray(tokens).ravel()
        self._buf.append(tokens)
        self._buffered += tokens.size
        if self._buffered >= self.shard_tokens:
            self._flush()

    def _flush(self) -> None:
        if not self._buffered:
            return
        path = self.out_dir / f"{self.prefix}-{len(self._shards):05d}.tonytok"
        write_token_shard(path, np.concatenate(self._buf))
        self._shards.append(path)
        self._buf, self._buffered = [], 0

    def close(self) -> list[Path]:
        self._flush()
        return self._shards


def open_shard(path: str | Path) -> np.memmap:
    """Memory-map a shard's payload in its stored dtype (u16 or i32) —
    no copy; slices convert to int32 at use (TokenLoader does this per
    window so a large corpus never materializes in RAM)."""
    path = Path(path)
    with open(path, "rb") as f:
        head = f.read(HEADER_SIZE)
    if len(head) < HEADER_SIZE or head[:8] != MAGIC:
        raise ValueError(f"{path}: not a TONYTOK1 shard")
    code, count = struct.unpack_from("<IQ", head, 8)
    if code not in _DTYPES:
        raise ValueError(f"{path}: unknown dtype code {code}")
    return np.memmap(path, dtype=_DTYPES[code], mode="r", offset=HEADER_SIZE, shape=(count,))


def read_shard(path: str | Path) -> np.ndarray:
    """Read a whole shard as int32 (materializes; fine for tools/tests —
    streaming consumers should use open_shard / TokenLoader)."""
    return np.asarray(open_shard(path), dtype=np.int32)


def global_slots(batch_index: int, global_batch: int, shard_id: int, num_shards: int) -> range:
    """The GLOBAL sample slots rank ``shard_id`` of ``num_shards`` consumes
    in global batch ``batch_index`` — the deterministic repartition rule the
    elastic resize relies on (TokenLoader's global-order contract,
    data/loader.py): rank ``k`` owns the contiguous rows
    ``[t*G + k*b, t*G + (k+1)*b)`` where ``G = global_batch`` and
    ``b = G / num_shards``.

    Because the rule is a pure function of (batch index, world size), the
    union of every rank's slots over any world-size history that covers
    global batches ``[0, T)`` with a constant ``G`` is exactly
    ``range(0, T*G)`` — each slot once. Tests and the chaos determinism
    assertion recompute consumption with this function rather than
    instrumenting the hot loop."""
    if num_shards < 1 or not 0 <= shard_id < num_shards:
        raise ValueError(f"shard_id {shard_id} out of range for num_shards {num_shards}")
    if global_batch % num_shards:
        raise ValueError(
            f"global batch {global_batch} must divide by num_shards {num_shards}"
        )
    b = global_batch // num_shards
    start = batch_index * global_batch + shard_id * b
    return range(start, start + b)


@dataclass
class ConsumptionCursor:
    """Persisted data-consumption position, written next to each checkpoint.

    One global batch is consumed per training step, so ``global_batch_index``
    (the next global batch to draw) equals the checkpoint step it was saved
    with. The cursor pins the two knobs the exact-replay contract depends on
    — the draw ``seed`` and the GLOBAL batch size — so a resumed run at a
    DIFFERENT world size can prove it is continuing the same stream (and a
    run that silently changed either fails loudly instead of silently
    double-consuming or skipping samples). ``world_size`` records who wrote
    it, for forensics only — it is exactly the thing allowed to change.
    """

    global_batch_index: int
    global_batch_size: int
    seed: int
    world_size: int = 1

    def save(self, ckpt_dir: str | Path) -> Path:
        """Atomic write to ``<ckpt_dir>/cursor-<index>.json`` (one file per
        checkpointed step, so a quarantined/garbage-collected checkpoint
        never strands the stream position of a surviving one)."""
        path = Path(ckpt_dir) / f"cursor-{self.global_batch_index}.json"
        tmp = str(path) + ".tmp"
        with open(tmp, "w") as f:
            json.dump(asdict(self), f)
        os.replace(tmp, path)
        return path

    @classmethod
    def load(cls, ckpt_dir: str | Path, global_batch_index: int) -> "ConsumptionCursor | None":
        """The cursor saved with checkpoint step ``global_batch_index``, or
        None (pre-cursor checkpoint / no data loader in that run)."""
        path = Path(ckpt_dir) / f"cursor-{global_batch_index}.json"
        try:
            with open(path) as f:
                d = json.load(f)
            return cls(
                global_batch_index=int(d["global_batch_index"]),
                global_batch_size=int(d["global_batch_size"]),
                seed=int(d["seed"]),
                world_size=int(d.get("world_size", 1)),
            )
        except (OSError, ValueError, KeyError):
            return None

    def validate_resume(self, global_batch_size: int, seed: int, start_index: int) -> None:
        """The exactly-once gate for a (possibly resized) resume: the GLOBAL
        batch and seed must match what the stream was consumed under, and
        the loader must restart at the recorded position. A violation means
        samples would repeat or vanish — fail the resume loudly."""
        if global_batch_size != self.global_batch_size:
            raise ValueError(
                f"global batch changed across resume: checkpointed stream "
                f"consumed {self.global_batch_size} rows/step, resuming with "
                f"{global_batch_size} — the replay contract requires a "
                "constant GLOBAL batch (per-rank batch adapts instead)"
            )
        if seed != self.seed:
            raise ValueError(
                f"data seed changed across resume: {self.seed} → {seed} — "
                "the resumed draw would be a different stream"
            )
        if start_index != self.global_batch_index:
            raise ValueError(
                f"loader resume position {start_index} disagrees with the "
                f"checkpoint's consumption cursor {self.global_batch_index}"
            )


def pack_sequences(
    sequences: list[np.ndarray] | list[list[int]],
    seq_len: int,
    pad_id: int = 0,
) -> tuple[np.ndarray, np.ndarray]:
    """First-fit pack variable-length sequences into [N, seq_len] rows.

    Returns (tokens, segment_ids), both [N, seq_len] int32. Each row holds
    one or more whole sequences back to back; segment_ids number them 1, 2,
    ... within the row, with 0 marking trailing padding. Feed both to
    ``llama.loss_fn`` (as ``tokens``/``segment_ids``): attention and RoPE
    stay confined per segment and cross-boundary/pad targets are masked.
    Sequences longer than seq_len are split into seq_len-sized pieces.
    """
    rows: list[tuple[list[int], list[int]]] = []  # (tokens, segs), mutable fill
    for seq in sequences:
        seq = list(np.asarray(seq, dtype=np.int32))
        for off in range(0, len(seq), seq_len):
            piece = seq[off:off + seq_len]
            for toks, segs in rows:
                if len(toks) + len(piece) <= seq_len:
                    seg_id = segs[-1] + 1 if segs else 1
                    toks.extend(int(t) for t in piece)
                    segs.extend([seg_id] * len(piece))
                    break
            else:
                rows.append(([int(t) for t in piece], [1] * len(piece)))
    tokens = np.full((len(rows), seq_len), pad_id, dtype=np.int32)
    segment_ids = np.zeros((len(rows), seq_len), dtype=np.int32)
    for i, (toks, segs) in enumerate(rows):
        tokens[i, : len(toks)] = toks
        segment_ids[i, : len(segs)] = segs
    return tokens, segment_ids
