"""Data plane: tokenized shard datasets and the loader that batches them.

The reference left the input pipeline to the user's framework (tf.data /
torch DataLoader inside the user process — SURVEY.md §2.4); tony-tpu owns it:
- ``dataset``: the TONYTOK shard format (writer, reader, memory map),
- ``loader``: ``TokenLoader``, seeded window batches out of the mapped shards
  (``train/input_pipeline`` prefetches them ahead of the step).
"""

from tony_tpu.data.dataset import TokenShardWriter, read_shard, write_token_shard
from tony_tpu.data.loader import TokenLoader

__all__ = [
    "TokenShardWriter",
    "read_shard",
    "write_token_shard",
    "TokenLoader",
]
