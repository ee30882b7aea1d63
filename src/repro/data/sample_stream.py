"""SampleStream — the async HGNN host pipeline facade.

Runs sample → snapshot → stack → shard in the background and yields
``(batch, arrays, host_seconds)`` ready for the device step, from one of
two producers (see the ``repro.data`` package docstring and DESIGN.md §9):

no ``pool`` (thread mode)
    One :class:`~repro.data.prefetch.Prefetcher` producer thread runs

      ``make_batch(i) -> batch``   deterministic batch for pipeline step ``i``
      ``stage(batch)  -> arrays``  the executor's host-staging seam

    ``defer_stage=True`` implements the ``"fresh"`` snapshot policy: the
    producer only samples and staging runs synchronously in ``__next__``.

a ``pool`` (pool mode)
    A running :class:`~repro.data.worker_pool.WorkerPool` of sampler
    processes, owned by the caller (the session keeps one across ``fit``
    calls, so spawn cost amortizes): the stream draws ``num_steps`` items
    and its ``close()`` leaves the pool alive.  Its task writes each batch
    (and, with a staging recipe, its staged host arrays) into a slot of
    the batch **arena** (DESIGN.md §11), and the queue items are
    :class:`~repro.data.worker_pool.SlotRef` descriptors.  The stream
    resolves each against ``arena``/``spec`` into zero-copy slot views,
    hands them to ``finish_stage(batch, host_arrays) -> arrays`` — the
    consumer-side completion (device placement of worker-staged arrays,
    or the executor's full ``stage`` when workers only sample) — and
    **defers the slot release by one step**: slot ``i`` is handed back to
    its writer only when step ``i+1`` is drawn (or on ``close()``), so the
    consuming device step may alias slot memory safely.  ``queue_bytes``
    records the pickled size of each queue item — the zero-pickle
    guarantee CI asserts on.  ``make_batch``/``stage``/``defer_stage`` are
    ignored in this mode; the time ``finish_stage`` spends on the consumer
    is added to the item's ``host_seconds`` (it is not overlapped).

In both modes ``host_seconds`` is the sample+stage time actually spent on
the item, measured where it ran, so the consumer can compute the overlap
fraction (host work that ran concurrently with the device step costs no
wall time).  In this process it is the summed duration of the item's
spans (``repro.obs``: ``heta.sample``, ``heta.stage``), which carry the
item's global step ``first_step + i`` and the caller's ``session``; the
consumer's wait for an item is the span ``heta.pipeline.wait``, and where
the item was already waiting (the consumer did not block) the counter
``heta.pipeline.ready`` counts 1 in that span.
Exceptions raised in any producer — thread or process — surface in the
consumer's ``__next__``; ``close()`` joins everything and is idempotent.
"""

from __future__ import annotations

import pickle
from typing import Callable, List, Optional, Tuple

from repro import obs
from repro.data.prefetch import Prefetcher

__all__ = ["SampleStream"]


class SampleStream:
    def __init__(
        self,
        make_batch: Optional[Callable[[int], object]] = None,
        stage: Optional[Callable[[object], object]] = None,
        num_steps: Optional[int] = None,
        depth: int = 2,
        defer_stage: bool = False,
        pool: Optional[object] = None,
        arena: Optional[object] = None,
        spec: Optional[object] = None,
        finish_stage: Optional[Callable[[object, object], object]] = None,
        first_step: int = 0,
        session: Optional[int] = None,
    ):
        self._stage = stage
        self._defer = defer_stage
        self._finish = finish_stage
        self._pool = pool
        self._num_steps = num_steps
        self._prefetcher = None
        self._arena = arena
        self._spec = spec
        self._pending_release = None  # (slot, use) alive through the step
        self._first_step = first_step
        self._session = session
        self._drawn = 0  # items handed to the consumer
        self.queue_bytes: List[int] = []  # pickled size of each queue item

        if pool is not None:
            missing = [name for name, v in (("arena", arena), ("spec", spec),
                                            ("finish_stage", finish_stage))
                       if v is None]
            if missing:
                raise ValueError(f"pool mode requires {', '.join(missing)}")
            return

        if make_batch is None or stage is None:
            raise ValueError("thread mode requires make_batch and stage")

        def produce(i: int) -> Tuple[object, object, float]:
            with obs.scope(first_step + i, session) as item:
                batch = make_batch(i)
                arrays = None if defer_stage else stage(batch)
            return batch, arrays, item.child_s

        self._prefetcher = Prefetcher(produce, depth=depth,
                                      num_items=num_steps,
                                      name="sample-stream")

    def __iter__(self) -> "SampleStream":
        return self

    def _release_pending(self) -> None:
        if self._pending_release is not None:
            slot, use = self._pending_release
            self._pending_release = None
            self._arena.release(slot, use)

    def __next__(self) -> Tuple[object, object, float]:
        if self._num_steps is not None and self._drawn >= self._num_steps:
            raise StopIteration  # without a wait for an item never made
        with obs.scope(self._first_step + self._drawn, self._session) as here:
            batch, arrays, host_s, wait = self._next_item()
            self._drawn += 1
            # the consumer-side part of the item's host time: its spans
            # here, less the wait for the item (not host work)
            return batch, arrays, host_s + here.child_s - wait.seconds

    @staticmethod
    def _draw(source):
        with obs.span("heta.pipeline.wait") as wait:
            item = next(source)
            if source.last_ready:
                obs.count("heta.pipeline.ready")
            return item, wait

    def _next_item(self) -> Tuple[object, object, float, obs.span]:
        if self._pool is not None:
            try:
                ref, wait = self._draw(self._pool)
            except BaseException:
                self._release_pending()
                raise
            from repro.data.staging import unpack_slot

            # the previous step's views (and any zero-copy device aliases)
            # are dead once the caller asks for the next item — only now
            # may the writer reuse that slot
            self._release_pending()
            self.queue_bytes.append(len(pickle.dumps(ref)))
            views = self._arena.resolve(ref.slot, ref.use)
            batch, host = unpack_slot(views, self._spec)
            arrays = self._finish(batch, host)
            self._pending_release = (ref.slot, ref.use)
            return batch, arrays, ref.host_s, wait
        (batch, arrays, host_s), wait = self._draw(self._prefetcher)
        if self._defer:
            # "fresh" snapshot policy: stage on the consumer, against the
            # current tables (this part of the host time is NOT overlapped)
            arrays = self._stage(batch)
        return batch, arrays, host_s, wait

    def close(self) -> None:
        self._release_pending()
        if self._prefetcher is not None:
            self._prefetcher.close()

    def __enter__(self) -> "SampleStream":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
