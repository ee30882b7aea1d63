"""``repro.data`` — the unified async host-pipeline subsystem.

The breakdown benchmark (paper Fig. 10) shows host-side work — neighbor
sampling plus feature staging (table snapshot → ``stack_batch`` →
``shard_arrays``) — dominating step time once RAF has removed network
traffic.  This package overlaps that host work with the device step, the
DistDGLv2/HopGNN recipe, behind three pieces:

:class:`~repro.data.prefetch.Prefetcher`
    The shared double-buffered background producer (bounded queue, one
    daemon thread, exception propagation into the consumer, ``close()``
    joins).  Both :class:`TokenPipeline` (LM path) and
    :class:`~repro.data.sample_stream.SampleStream` (HGNN path) sit on it.

:class:`~repro.data.sample_stream.SampleStream`
    The host-pipeline facade: runs sample → snapshot → stack → shard in the
    background and yields ``(batch, arrays, host_seconds)`` ready for the
    device step, from one ``Prefetcher`` thread or from the process pool
    below.  ``PipelineConfig.num_workers`` picks which, in one place
    (``Heta._sampler_workers``) for every sampling loop of the session.

:class:`~repro.data.worker_pool.WorkerPool`
    N sampler *processes* over a shared-memory graph store
    (``repro.graph.shm``), lifting the one-CPU-core ceiling of the thread
    producer (paper Fig. 10 — host sampling dominates once RAF removes
    network traffic).  Worker ``w`` samples the interleaved stripe
    ``w, w+N, ...``; per-worker bounded queues round-robined by the
    consumer reconstruct strict step order; ``batch_at`` purity makes any
    worker count bit-identical.  Staging placement follows the snapshot
    policy: frozen-table and learnable-"stale" batches are staged *inside*
    workers via the shared numpy core
    (``repro.data.staging.stack_batch_host``), while learnable-"fresh"
    staging stays on the consumer.  Architecture: DESIGN.md §9.

The **batch arena** (DESIGN.md §11) is the pool's only transport: workers
write sampled (and pre-staged) arrays directly into fixed seqlock-stamped
slots of one shared-memory ring buffer (``repro.graph.shm.create_arena``),
and the queue carries only a few-hundred-byte ``SlotRef`` descriptor — zero
pickled ndarrays on the hot path.  Slot layout, version-stamp discipline, the
bounded-staleness contract for learnable tables, and failure/unlink rules
are specified in DESIGN.md §11; ``repro.data.staging`` holds the slot
pack/unpack helpers and the write-into-slot staging variant.

**The staged-step protocol.**  Executors (``repro.api.executors``) split
one training step into two public methods::

    stage(sess, plan, batch)                 -> arrays   # host staging
    step_staged(sess, plan, state, batch, arrays)        # device step

``stage`` is pure host work (safe to run in the producer thread for a
*future* batch while the device trains the current one); ``step_staged``
owns the timed compute + sparse-update region.  ``Heta.step`` is the
serial composition of the two; ``Heta.fit`` overlaps them.

**Determinism.**  ``NeighborSampler`` derives each batch's RNG from
``(seed, epoch_seed, step)`` (the ``SyntheticCorpus`` trick), so
``batch_at`` is a pure function of position and pipeline-on/off produce
bit-identical batches regardless of prefetch depth or thread scheduling.

**Snapshot staleness policy.**  ``Heta.fit`` drives every executor through
a ``SampleStream``.  With frozen feature tables (or the resident path,
whose staging reads no table) staging is time-invariant, so the producer
stages too and the stream is bit-exact.  When learnable tables train
(``ModelConfig.train_learnable`` with an executor whose staging reads
them, e.g. ``raf_spmd`` on the host path), staging batch *i+k* in the
background would observe tables before steps *i..i+k-1* wrote back, so
under the default ``PipelineConfig(snapshot="fresh")`` the producer only
samples and table-reading staging runs on the consumer right before the
step: bit-exact parity with the serial loop, overlapping only the
sampling stage.  ``snapshot="stale"`` stages in the producer instead,
against a snapshot that may lag by at most ``depth + 1`` steps (the queue
bound; the ring depth in pool mode): losses track the serial path within
optimization noise, the bounded-staleness trade every async-pipeline
system makes.  The session decides this in one place
(``Heta._stages_ahead``).
"""

from repro.data.pipeline import SyntheticCorpus, TokenPipeline
from repro.data.prefetch import Prefetcher
from repro.data.sample_stream import SampleStream
from repro.data.staging import (
    StackRecipe,
    arena_fields,
    pack_batch_into,
    stack_batch_host,
    unpack_slot,
)
from repro.data.faults import FaultPlan, FaultSpec, InjectedFault
from repro.data.worker_pool import (
    EpochSchedule,
    HotnessCountTask,
    SampleStageTask,
    SlotRef,
    WorkerDiedError,
    WorkerPool,
)

__all__ = [
    "SyntheticCorpus",
    "TokenPipeline",
    "Prefetcher",
    "SampleStream",
    "StackRecipe",
    "stack_batch_host",
    "arena_fields",
    "pack_batch_into",
    "unpack_slot",
    "FaultPlan",
    "FaultSpec",
    "InjectedFault",
    "EpochSchedule",
    "HotnessCountTask",
    "SampleStageTask",
    "SlotRef",
    "WorkerDiedError",
    "WorkerPool",
]
