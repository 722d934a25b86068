"""High-level loaders: SPDL pipelines wired for the two workload families.

``build_image_loader``  — the paper's benchmark pipeline: sample indices →
slot assignment → read bytes (I/O) → decode+resize (GIL-releasing CPU,
written in place) → slab batch assembly → device transfer (concurrency=1).

``build_lm_loader``     — the LM-training pipeline used by the trainer:
index batches → read docs → decode+tokenize/pack into (seq_len,) slab rows
with segment ids → slab batch assembly → shard-aware device placement.

Every stage's concurrency is tunable (paper "Tunability"); stats from
``Pipeline.stats()`` expose the bottleneck stage (paper "Visibility") and,
for the slab path, memory pressure (``slabs_in_flight``/``bytes_allocated``).

Memory model (zero-copy slab path, default ``zero_copy=True``)
---------------------------------------------------------------
Batches are assembled in a ``SlabArena``: a ring of ``arena_slabs``
preallocated ``(batch, *item_shape)`` buffers that the pipeline recycles
instead of reallocating.  Ownership rules:

1. **Producers do not own their outputs.**  A ``concurrency=1`` binder stage
   pairs every sample with a ``(slab, slot)`` ticket *before* decode; decode
   workers write their result directly into the assigned slot (GIL-released,
   concurrent — distinct slots never alias).
2. **Acquisition is the backpressure.**  ``arena.acquire()`` blocks (in the
   worker pool, never on the event loop) while all slabs are in flight, so a
   stalled consumer bounds host memory at ``arena_slabs`` slabs — the arena
   can never exceed its ring size.
3. **A failed sample leaves a hole.**  The decode wrapper calls
   ``ref.mark_hole()`` and re-raises (so stage stats still count the
   failure); the ``aggregate_into`` stage compacts around holes by copying
   only displaced rows, keeping emitted batches dense.
4. **Release follows the device copy.**  An emitted slab travels to
   ``DeviceTransfer``, which double-buffers: slab *k* returns to the arena
   only after the transfer for slab *k+1* has been issued — or, on
   zero-copy backends where ``device_put`` aliases host memory (CPU), only
   after the whole consumer window (``sink_buffer`` + the batch in hand)
   has moved past it; the ring is sized automatically for either case.
   Consumers that retain batches beyond the current iteration must copy
   them.  Slabs fully drained by compaction (never emitted) are recycled
   by the arena itself.
5. **Teardown can't hang.**  ``Pipeline.stop()`` first runs
   ``arena.close()`` (registered as a stop callback), waking any worker
   blocked in ``acquire`` with ``ArenaClosed``.

``zero_copy=False`` restores the classic list-collate path (one fresh slab
allocation + one extra copy per sample per batch) — the fallback for ragged
shapes or third-party stages that retain references into batches.

Chunked + fused execution (``chunk=``, default 16)
--------------------------------------------------
With the storage path this fast, the engine's per-item event-loop cost
(queue hops, task creation, executor dispatch — ~4-5 round trips per stage
per sample) is the remaining ceiling, so both loaders run their per-sample
stages chunked and fused:

* the slot binder and the read/decode stages take ``chunk=N``: one executor
  call per N samples instead of per sample (``pipe(..., chunk=N)``);
* read → decode are **fused** into a single worker call per chunk
  (``builder.fuse("read", "decode")``), eliminating the queue + task layer
  between them — ``Pipeline.stats()`` still shows them as separate rows.

Ordering and memory rules under chunking:

* Order is preserved end to end: chunks are dispatched and emitted in FIFO
  order and items keep their order within a chunk, so the
  ``aggregate_into`` input-order contract holds unchanged.
* Slab slot assignment makes chunked decode-into safe: every item carries
  its own ``(slab, slot)`` ticket, so the N decodes of a chunk write to
  disjoint rows no matter how chunks interleave across worker threads.
* A failing sample inside a chunk leaves exactly ONE hole (its slot);
  chunk-mates are unaffected (per-item error holes, ``OnError.SKIP``).
* In-flight memory grows from ``concurrency`` samples to
  ``concurrency × chunk`` samples per chunked stage, plus inter-stage
  queues widened to ``chunk`` — still item *references*, not pixel data;
  pixels live in the fixed slab ring either way.
* The chunked binder binds slots inside the worker (``arena.slot_writer``),
  so arena backpressure blocks a pool thread rather than polling the loop;
  ``Pipeline.stop()`` still wakes it via the ``arena.close`` callback.

The hot path to the device (``transfer_chunk=``, default 2)
-----------------------------------------------------------
The batch → device leg is chunked too, on both ends of the sink:

* **Transfer stage**: with ``transfer_chunk > 1`` the transfer runs as a
  vectorized chunk stage (``DeviceTransfer.transfer_many``) — one executor
  call issues ``device_put`` (+ the fused on-chip decode, below) for a
  whole chunk of batches, in arrival order, amortizing the engine's
  per-batch hops over the largest items in the pipeline.
* **Sink drain**: consumers pull matching chunks with
  ``Pipeline.get_items(n)`` (or ``HealthMonitor.guard(chunk=n)``) — one
  cross-thread round trip drains up to *n* buffered batches.  Ordering is
  preserved end to end: ``get_items`` returns batches exactly in emission
  order, and mixing ``get_item``/``get_items`` calls on the same pipeline
  is safe (they share one stash; a timed-out call never loses the batch it
  was waiting on).
* **Memory**: every batch parked in the ``chunk``-widened batch→transfer
  queue pins a slab, and up to ``transfer_chunk - 1`` dispatched-but-unput
  batches sit in the transfer worker mid-chunk, so both the transfer's
  hold window (``consumer_window + 1 + transfer_chunk``) and the arena's
  deadlock floor (see ``_ring_size``) grow with ``transfer_chunk``.
  Slabs still recycle per batch, in order, chunked or not.
* **Failure is fatal**: the transfer stage runs ``on_error="fail"``, so
  a failed ``device_put`` or on-chip decode raises ``PipelineFailure``
  out of the consumer's next ``get_item``/``get_items`` instead of
  dropping the batch (a skipped transfer would turn a decode that cannot
  run on the device into an empty epoch, or an endless wait on a stream).

With ``device_decode=DeviceDecode(mean, std, ...)`` the loader ships
**uint8 wire bytes end to end**: slab rows stay uint8 through collate and
transfer, and the fused ``dequant_normalize_augment`` decode (uint8→bf16
dequant, per-channel normalize, flip/crop augment, one XLA fusion) runs
on-chip right after ``device_put`` — zero host-side float math on pixels.
See ``data/transfer.py`` and ``kernels/ops.py``.

**Checkpoint skip bound under chunking**: samples accumulate inside
in-flight chunks before they reach a delivered batch, so a sampler
checkpoint taken mid-stream can additionally skip the samples resident in
chunked stages — at most ``chunk`` per unit of stage concurrency plus the
``chunk``-widened queues.  On the default wiring that is
``(max(read_concurrency, decode_concurrency) + 3) × chunk`` samples (the
fused read+decode stage runs at the max of the two concurrencies) — on
top of the sink-buffered batches (sampler.py), the ``2 × transfer_chunk``
batches the chunked transfer leg can hold (its widened input queue plus
the dispatch chunk in flight), and, on the prefetcher path, the
``_PREFETCH_LOOKAHEAD`` window below.
Still bounded and epoch-local; set ``chunk=1`` to restore the narrow
per-item bound when checkpoint tightness matters more than throughput.

Sharded datasets (``repro.data.shards``)
----------------------------------------
Both loaders accept a ``ShardDataset`` unchanged: its ``read_bytes`` hands
back a ``memoryview`` of the shard's mmap and the zero-copy path
decompresses it straight into a slab slot (mmap → ``decode_into`` → arena,
no intermediate copies).  When the dataset carries a ``ShardPrefetcher``
(remote mode), the index source is wrapped so upcoming shards are fetched
in the background ``_PREFETCH_LOOKAHEAD`` samples ahead of the read stage,
and the prefetcher's cache counters surface on the read stage's row in
``Pipeline.stats()``.  Pair with the sampler's shard-aware shuffle
(``shard_sizes=dataset.shard_sizes``) so consecutive samples share shards
and the cache actually hits.  At multi-rank scale, construct the dataset
with ``peers=[...]`` (other ranks' ``PeerShardServer`` URLs): a cache miss
then tries the peers' warm caches before the origin, and the read stage's
dashboard row grows ``peer_hits``/``origin_bytes`` (see
``repro.data.shards.peer``).

Columnar (format v2) shards add **projection pushdown**:
``build_image_loader(ds, fields=("image",))`` reads only the named column
— the read stage's zero-copy view covers just that field's bytes, and on
the prefetcher path the field name rides the lookahead hints so sparse
fetches pull only that column's byte ranges off the wire (``bytes_skipped``
on the dashboard counts what projection saved).  A ``ShardDataset``
constructed with its own ``fields=`` projection gets the same hint wiring
automatically in both loaders.

Checkpoint caveat: the lookahead wrapper holds up to ``_PREFETCH_LOOKAHEAD``
already-drawn indices that the sampler has counted as handed out, so a
sampler checkpoint taken mid-stream on the prefetcher path skips at most
``_PREFETCH_LOOKAHEAD`` samples *in addition to* the sink-buffered batches
documented in ``sampler.py`` — still bounded and epoch-local, but wider
than the local-dataset path.

Failure semantics (what a bad sample / slow sample / dead backend does)
-----------------------------------------------------------------------
The loaders inherit the engine's failure contract (see the "Failure
semantics" section of ``core/engine.py``) and add the storage layer's:

* **Corrupt sample** (unreadable bytes, malformed codec blob): the read or
  decode stage raises, the item becomes a hole under ``OnError.SKIP`` —
  one missing sample, never a torn batch (on the zero-copy path the slot
  is ``mark_hole``-ed so its batch still completes).  Fail-fast stages
  raise ``PipelineFailure`` carrying the *phase* name (``read``/
  ``decode``), the fused stage name, and the item's stage-stream index.
* **Slow sample** (storage tail, contended decode): with
  ``straggler_after=`` the slow lane detaches it so chunk-mates emit on
  time; its result re-enters at its original position.  Batches stay
  in-order and complete — straggling costs latency on ONE batch instead
  of throughput on all of them.  ``Pipeline.stats()`` shows ``stragglers``
  / ``straggler_shed`` per stage.
* **Truncated transfer** (backend dies mid-body): ``HttpShardSource``
  validates ``Content-Length`` and surfaces a retryable
  ``SourceUnavailable`` — a short body is *never* installed into the
  shard cache (``RetryingSource`` covers the retry).
* **Dead peer** (multi-rank): the peer tier's circuit breaker benches it
  (half-open probe after ``cooldown_s``), fetches fall through to the
  origin; with ``hedge_after_s`` a merely *slow* peer is raced against
  the origin instead of waited out.
* **Stall** (no batch progressing at all): wrap consumption in
  ``core.HealthMonitor.guard()`` — degradation actions (disable eager
  verify, widen the sparse threshold, go origin-only) fire first, then a
  structured ``PipelineStalled`` names the suspect stage.
"""

from __future__ import annotations

from collections import deque
from typing import Any, Iterable, Iterator

import numpy as np

from ..core import Pipeline, PipelineBuilder
from .arena import SlabArena
from .codec import (
    decode_into,
    decode_sample,
    parse_header,
    resize_nearest,
    resize_nearest_into,
)
from .packing import SequencePacker, collate
from .sampler import CheckpointableSampler
from .transfer import DeviceDecode, DeviceTransfer


def _ring_size(
    arena_slabs: int | None, transfer: DeviceTransfer, transfer_chunk: int = 2
) -> int:
    """Slab-ring size for a loader: the ring must outsize the slabs pinned
    at once (transfer hold + inter-stage queues + the one being filled) or
    the binder deadlocks the pipeline.  The batch→transfer queue is widened
    to the transfer stage's chunk (so the chunked drain can actually fill
    its chunks), and every batch parked there pins a slab — the floor
    grows with ``transfer_chunk`` past the default 2.  An explicit request
    below the floor is an error, not a silent inflation — the caller set
    it as a memory cap and must raise it (or the sink buffer) knowingly."""
    in_flight = 2 + max(2, transfer_chunk)  # queue + assembling + mid-transfer
    floor = transfer.hold_slabs + in_flight
    if arena_slabs is None:
        return floor
    if arena_slabs < floor:
        raise ValueError(
            f"arena_slabs={arena_slabs} is below the deadlock floor "
            f"{floor} (= transfer hold {transfer.hold_slabs} + {in_flight} "
            "in-flight); raise arena_slabs or lower sink_buffer/transfer_chunk"
        )
    return arena_slabs


def _pipe_transfer(
    builder: PipelineBuilder, transfer: DeviceTransfer, transfer_chunk: int
) -> PipelineBuilder:
    """Wire the terminal transfer stage (§2.1: exactly one transfer task).

    ``transfer_chunk > 1`` dispatches a drained chunk of batches per engine
    hop (``transfer_many`` as a vectorized chunk stage) — one executor call
    issues the whole chunk's ``device_put`` (+ fused decode) dispatches in
    order.  The ``cache=transfer`` probe surfaces ``device_decode_ms`` /
    ``device_decode_batches`` on the transfer stage's stats row.  A failed
    transfer is fatal (``on_error="fail"``): it never drops a batch."""
    if transfer_chunk > 1:
        return builder.pipe(
            transfer.transfer_many, concurrency=1, name="transfer",
            chunk=transfer_chunk, vectorized=True, cache=transfer,
            on_error="fail",
        )
    return builder.pipe(
        transfer, concurrency=1, name="transfer", cache=transfer, on_error="fail"
    )


#: how many samples of headroom the shard-prefetch wrapper keeps between
#: scheduling a shard's fetch and handing its first index to the pipeline —
#: the slack that lets the download overlap the decode of earlier shards.
_PREFETCH_LOOKAHEAD = 64


def _with_shard_prefetch(
    indices: Iterable[int],
    dataset: Any,
    lookahead: int = _PREFETCH_LOOKAHEAD,
    fields: tuple[str, ...] | None = None,
) -> Iterator[int]:
    """Index-stream wrapper for prefetcher-backed shard datasets: peek
    ``lookahead`` samples ahead of what the pipeline has been handed and
    schedule background fetches for the shards they live in, so by the time
    the read stage asks for a sample its shard is (usually) already in the
    local cache.  Scheduling is advisory — a dropped request just means the
    read stage fetches on demand.

    Index-first sources (``prefetcher.index_first``): instead of scheduling
    a whole-shard fetch on first sight, the wrapper accumulates the *run*
    of consecutive same-shard indices the sampler emits (the shard-aware
    shuffle makes runs the common case) and schedules the shard with those
    shard-local indices as ``samples=`` hints — the prefetcher then pulls
    the shard's header + index and fetches only the hinted sample ranges
    when they cover a small fraction of the payload.  A run that grows past
    ``lookahead`` clearly wants most of the shard, so it is committed early
    as a whole-shard fetch.

    The buffered indices have already advanced the sampler's cursor, so a
    checkpoint taken mid-stream treats them as consumed: resume skips at
    most ``lookahead`` samples beyond the sink-buffered batches (see the
    module docstring's checkpoint caveat).

    ``fields`` (columnar v2 shards) rides every hint: a sparse fetch then
    coalesces ranges over the requested columns only, so projection
    pushdown reaches the wire from here."""
    pf = dataset.prefetcher
    want_hints = bool(getattr(pf, "index_first", False))
    buf: deque[int] = deque()
    run_shard = -1
    run_samples: list[int] | None = []  # None = run already committed full

    def schedule(shard: int, samples=None) -> None:
        if fields is not None:
            pf.schedule(dataset.shard_names[shard], samples=samples, fields=fields)
        else:
            pf.schedule(dataset.shard_names[shard], samples=samples)

    def commit_run() -> None:
        if run_shard >= 0 and run_samples:
            schedule(run_shard, run_samples)

    for i in indices:
        shard, local = dataset.shard_and_offset(i)
        if shard != run_shard:  # run boundary; pf.schedule also dedups
            commit_run()
            run_shard, run_samples = shard, []
            if not want_hints:
                # no ranged reads available: schedule the whole shard as
                # early as possible (maximum fetch/decode overlap)
                schedule(shard)
                run_samples = None
        if want_hints and run_samples is not None:
            run_samples.append(local)
            if len(run_samples) >= lookahead:
                # the window wants most of this shard: commit to a full
                # fetch now rather than waiting for the run to end
                schedule(shard)
                run_samples = None
        buf.append(i)
        if len(buf) > lookahead:
            yield buf.popleft()
    commit_run()
    yield from buf


def _maybe_prefetch(
    indices: Iterable[int], dataset: Any, fields: tuple[str, ...] | None = None
) -> tuple[Iterable[int], Any]:
    """(index stream, cache probe) — wired only for prefetcher datasets.
    ``fields=None`` falls back to the dataset's own projection, so a
    ``ShardDataset(fields=...)`` hints its columns without loader help."""
    prefetcher = getattr(dataset, "prefetcher", None)
    if prefetcher is None:
        return indices, None
    if fields is None:
        fields = getattr(dataset, "fields", None)
    return _with_shard_prefetch(indices, dataset, fields=fields), prefetcher


def build_image_loader(
    dataset,
    *,
    batch_size: int = 32,
    hw: tuple[int, int] = (224, 224),
    read_concurrency: int = 4,
    decode_concurrency: int = 4,
    num_threads: int = 8,
    sink_buffer: int = 3,
    shardings: Any | None = None,
    uint8_wire: bool = True,
    sampler: CheckpointableSampler | None = None,
    epochs: int | None = 1,  # None = stream forever (training);  N = bounded
    zero_copy: bool = True,
    arena_slabs: int | None = None,  # None = sized from the consumer window
    chunk: int = 16,  # items per executor dispatch; 1 = per-item path
    fuse_stages: bool = True,  # collapse read+decode into one worker call
    straggler_after: float | None = None,  # soft deadline on read/decode
    trace=None,  # core.trace.Tracer: flight-recorder spans for every layer
    fields: tuple[str, ...] | None = None,  # columnar projection, e.g. ("image",)
    device_decode: DeviceDecode | None = None,  # on-chip fused decode tail
    transfer_chunk: int = 2,  # batches per transfer dispatch; 1 = per-batch
) -> Pipeline:
    if chunk < 1:
        raise ValueError("chunk must be >= 1")
    if transfer_chunk < 1:
        raise ValueError("transfer_chunk must be >= 1")
    if straggler_after is not None and chunk <= 1:
        raise ValueError("straggler_after requires chunk > 1 (see pipe())")
    # Columnar projection: this pipeline decodes exactly one image blob per
    # sample, so the projection must name exactly one field.  The name is
    # pushed down every layer — the read stage pulls only that column, the
    # prefetch hints carry it to the wire, and multi-field shards stop
    # paying fetch+decode for the columns this loader never touches.
    if fields is not None:
        fields = tuple(fields)
        if len(fields) != 1:
            raise ValueError(
                f"the image pipeline decodes one field per sample; "
                f"fields={list(fields)} names {len(fields)}"
            )
        if getattr(dataset, "schema_fields", None) is None:
            raise TypeError(
                "fields= needs a columnar (format v2) ShardDataset — "
                "migrate with pack(..., format_version=2)"
            )
    # fusion widens both stages to max(read, decode) concurrency — a
    # concurrency-1 stage may be deliberate (serialization), so don't
    fuse_stages = fuse_stages and (
        min(read_concurrency, decode_concurrency) > 1
        or read_concurrency == decode_concurrency
    )
    sampler = sampler or CheckpointableSampler(len(dataset), batch_size=1, shuffle=False)

    def indices():
        limit = None if epochs is None else sampler.batches_per_epoch() * epochs
        for k, batch in enumerate(sampler):
            if limit is not None and k >= limit:
                return
            yield from batch

    transfer = DeviceTransfer(
        shardings, uint8_wire=uint8_wire, consumer_window=sink_buffer,
        dispatch_chunk=transfer_chunk, device_decode=device_decode,
        tracer=trace,
    )

    index_stream, cache_probe = _maybe_prefetch(indices(), dataset, fields=fields)

    if fields is not None:
        _field = fields[0]

        def read_blob(i: int) -> memoryview:
            # projected read: only this column's bytes (zero-copy view)
            return dataset.read_fields(i, fields)[_field]
    else:
        read_blob = dataset.read_bytes

    if zero_copy and len(dataset) > 0:
        # The slab spec hard-codes uint8 (H, W, 3) slots.  A dataset of
        # incompatible samples (grayscale, float, video clips) would hole
        # out EVERY item under OnError.SKIP — a silent empty epoch — so
        # sniff one sample and fall back to list-collate instead.  Shard
        # manifests record sample 0's layout (per field on columnar
        # manifests), which answers the question without reading data (a
        # remote dataset would otherwise download a whole shard for this
        # one header).
        meta = (
            dataset.field_meta(fields[0])
            if fields is not None and callable(getattr(dataset, "field_meta", None))
            else getattr(dataset, "sample_meta", None)
        )
        if meta is not None:
            dtype, shape = meta
            if len(shape) != 3 or shape[2] != 3 or dtype != np.uint8:
                zero_copy = False
        else:
            try:
                probe = decode_sample(read_blob(0))
            except Exception:
                pass  # unreadable first sample: the runtime path will skip it
            else:
                if probe.ndim != 3 or probe.shape[2] != 3 or probe.dtype != np.uint8:
                    zero_copy = False

    if not zero_copy:
        # Classic list-collate fallback: each decode allocates its own
        # output, the collate stage allocates a fresh slab per batch.
        def read(i: int) -> bytes:
            return read_blob(i)

        def decode(data: bytes) -> np.ndarray:
            img = decode_sample(data)
            return resize_nearest(img, hw)

        def make_batch(imgs: list[np.ndarray]) -> dict:
            out = np.empty((len(imgs), *imgs[0].shape), imgs[0].dtype)
            for j, im in enumerate(imgs):
                out[j] = im
            return {"images": out}

        builder = (
            PipelineBuilder()
            .add_source(index_stream, name="sampler")
            .pipe(read, concurrency=read_concurrency, name="read",
                  cache=cache_probe, chunk=chunk,
                  straggler_after=straggler_after)
            .pipe(decode, concurrency=decode_concurrency, name="decode",
                  chunk=chunk, straggler_after=straggler_after)
        )
        if fuse_stages:
            builder.fuse("read", "decode")
        builder = builder.aggregate(
            batch_size, drop_last=True, name="batch"
        ).pipe(make_batch, name="collate")
        return (
            _pipe_transfer(builder, transfer, transfer_chunk)
            .add_sink(buffer_size=sink_buffer)
            .build(num_threads=num_threads, trace=trace)
        )

    # Zero-copy slab path (see module docstring "Memory model").
    arena = SlabArena(
        {"images": ((*hw, 3), np.uint8)},
        batch_size=batch_size,
        num_slabs=_ring_size(arena_slabs, transfer, transfer_chunk),
    )

    def read(item) -> tuple:
        i, ref = item
        try:
            return read_blob(i), ref
        except Exception:
            ref.mark_hole()  # the slot was already assigned; don't leak it
            raise

    def decode(item):
        data, ref = item
        try:
            out = ref.slab.arrays["images"][ref.slot]
            dtype, shape, _ = parse_header(data)
            if tuple(shape) == tuple(out.shape) and dtype == out.dtype:
                decode_into(data, out)  # native size: decompress into the slot
            else:
                resize_nearest_into(decode_sample(data), out)
            return ref
        except Exception:
            ref.mark_hole()  # the row will never arrive; unblock the batch
            raise

    builder = PipelineBuilder().add_source(index_stream, name="sampler")
    if chunk > 1:
        # chunked binder: one executor call assigns N slots in order (the
        # stage is concurrency=1 and order-preserving, so the stateful
        # cursor is single-writer).  Arena exhaustion blocks the worker
        # thread — the same backpressure, minus a loop poll per item.
        next_slot = arena.slot_writer()

        def bind(item):
            return item, next_slot()

        builder.pipe(bind, concurrency=1, name="slot", chunk=chunk)
    else:
        builder.pipe(arena.binder(), concurrency=1, name="slot")  # blocks = backpressure
    builder.pipe(
        read, concurrency=read_concurrency, name="read",
        cache=cache_probe, chunk=chunk, straggler_after=straggler_after,
    ).pipe(
        decode, concurrency=decode_concurrency, name="decode", chunk=chunk,
        straggler_after=straggler_after,
        # the batch stage drains via get_many: a chunk-wide queue of slot
        # REFS (tickets, not pixels) lets it amortize its loop hops too
        queue_size=max(2, chunk),
    )
    if fuse_stages:
        builder.fuse("read", "decode")
    builder = builder.aggregate_into(arena, batch_size, drop_last=True, name="batch")
    pipe = (
        _pipe_transfer(builder, transfer, transfer_chunk)
        .add_sink(buffer_size=sink_buffer)
        .build(num_threads=num_threads, trace=trace)
    )
    pipe.add_stop_callback(arena.close)
    pipe.add_stop_callback(transfer.flush)
    return pipe


def build_lm_loader(
    dataset,
    *,
    seq_len: int,
    batch_size: int,
    sampler: CheckpointableSampler | None = None,
    read_concurrency: int = 4,
    decode_concurrency: int = 4,
    num_threads: int = 8,
    sink_buffer: int = 2,
    shardings: Any | None = None,
    seed: int = 0,
    zero_copy: bool = True,
    arena_slabs: int | None = None,  # None = sized from the consumer window
    chunk: int = 16,  # items per executor dispatch; 1 = per-item path
    straggler_after: float | None = None,  # soft deadline on the read stage
    trace=None,  # core.trace.Tracer: flight-recorder spans for every layer
    transfer_chunk: int = 2,  # batches per transfer dispatch; 1 = per-batch
) -> tuple[Pipeline, CheckpointableSampler]:
    """Returns (pipeline, sampler) — the sampler is checkpointed alongside
    model state (fault tolerance; see runtime/trainer.py).

    The zero-copy path packs rows straight into a packed-rows slab (one
    ``(batch, seq_len) int32`` buffer per field) and skips the collate stage
    entirely; see the module docstring for the slab ownership rules.

    ``chunk`` applies to the read and decode+pack stages (the packer stage
    stays ``concurrency=1`` — ordered chunk dispatch keeps its state
    single-writer — and is NOT fused with the wider read stage).  The
    module docstring's chunked checkpoint-bound caveat applies.

    ``straggler_after`` arms the slow lane on the *read* stage only (a
    slow shard fetch is the dominant tail here); the packer stage is
    stateful, which the slow lane's item-major execution cannot support.
    """
    if chunk < 1:
        raise ValueError("chunk must be >= 1")
    if transfer_chunk < 1:
        raise ValueError("transfer_chunk must be >= 1")
    if straggler_after is not None and chunk <= 1:
        raise ValueError("straggler_after requires chunk > 1 (see pipe())")
    sampler = sampler or CheckpointableSampler(
        len(dataset), batch_size=8, seed=seed, shuffle=True
    )
    packer = SequencePacker(seq_len)

    def doc_ids():
        for batch in sampler:
            yield from batch

    def read(i: int) -> bytes:
        return dataset.read_bytes(i)

    transfer = DeviceTransfer(
        shardings, consumer_window=sink_buffer,
        dispatch_chunk=transfer_chunk, tracer=trace,
    )
    doc_stream, cache_probe = _maybe_prefetch(doc_ids(), dataset)

    if not zero_copy:
        def pack(data: bytes) -> list[dict]:
            doc = decode_sample(data)
            return packer.add(doc)  # 0..k completed rows

        builder = (
            PipelineBuilder()
            .add_source(doc_stream, name="sampler")
            .pipe(read, concurrency=read_concurrency, name="read",
                  cache=cache_probe, chunk=chunk,
                  straggler_after=straggler_after)
            .pipe(pack, concurrency=1, name="decode+pack", chunk=chunk)  # stateful
            .disaggregate(name="rows")
            .aggregate(batch_size, drop_last=True, name="batch")
            .pipe(collate, concurrency=decode_concurrency, name="collate")
        )
        pipe = (
            _pipe_transfer(builder, transfer, transfer_chunk)
            .add_sink(buffer_size=sink_buffer)
            .build(num_threads=num_threads, trace=trace)
        )
        return pipe, sampler

    row_shape = ((seq_len,), np.int32)
    arena = SlabArena(
        {k: row_shape for k in ("tokens", "labels", "positions", "segment_ids")},
        batch_size=batch_size,
        num_slabs=_ring_size(arena_slabs, transfer, transfer_chunk),
    )
    next_slot = arena.slot_writer()  # only touched by the concurrency=1 packer

    def pack_into(data: bytes) -> list:
        doc = decode_sample(data)
        return packer.add_into(doc, next_slot)  # 0..k completed slot tickets

    builder = (
        PipelineBuilder()
        .add_source(doc_stream, name="sampler")
        .pipe(read, concurrency=read_concurrency, name="read",
              cache=cache_probe, chunk=chunk,
              straggler_after=straggler_after)
        .pipe(pack_into, concurrency=1, name="decode+pack", chunk=chunk)  # stateful
        .disaggregate(name="rows")
        .aggregate_into(arena, batch_size, drop_last=True, name="batch")
    )
    pipe = (
        _pipe_transfer(builder, transfer, transfer_chunk)
        .add_sink(buffer_size=sink_buffer)
        .build(num_threads=num_threads, trace=trace)
    )
    pipe.add_stop_callback(arena.close)
    pipe.add_stop_callback(transfer.flush)
    return pipe, sampler
