"""The ``image_files`` records packed into format-v2 shards, read as mmap views.

The corpus is the same records as ``image_files`` (``corpus.record_pixels``
through the program's codec), encoded in memory and packed by the
program's own ``pack(..., format_version=2, fields=("image",))`` at its
default shard size, so the disk holds the shards and no per-record file.
The pipeline is the same image loader over ``ShardDataset(workdir)``,
projected to the ``image`` column; each read is a ``memoryview`` of the
shard's mapping, with no open, stat or close per record.  Record ``i`` of
the shards is record ``i`` of the files, so the reference and the check
are ``image_files``'s.
"""

from __future__ import annotations

from bench import corpus
from bench.feeds import image_files

fields = image_files.fields
reference_batches = image_files.reference_batches
check = image_files.check
COLUMN = "image"


class _Records:
    """Encoded records in memory, as ``pack`` reads a one-blob source."""

    def __init__(self, blobs: list[bytes]):
        self.blobs = blobs

    def __len__(self) -> int:
        return len(self.blobs)

    def read_bytes(self, i: int) -> bytes:
        return self.blobs[i]


def write(workdir, config: dict, traffic: dict, n: int, seed: int, threads: int) -> None:
    from repro.data.shards import pack

    blobs = corpus.encode_records(traffic, n, seed, threads=threads)
    pack(_Records(blobs), workdir, format_version=2, fields=(COLUMN,)).close()


def build(workdir, config: dict, traffic: dict, *, batch: int, seed: int, shardings, sampler):
    from repro.data.shards import ShardDataset

    dataset = ShardDataset(workdir)
    pipe = image_files.image_loader(dataset, config, batch=batch, seed=seed,
                                    shardings=shardings, sampler=sampler, fields=(COLUMN,))
    pipe.add_stop_callback(dataset.close)
    return pipe
