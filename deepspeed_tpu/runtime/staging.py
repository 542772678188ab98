"""Overlapped window staging: a double-buffered async input pipeline.

While accumulation window N computes on device, a background worker pulls
window N+1's micro-batches from the data source, host-stacks them into the
``[accum, ...]`` layout, and issues the (async) ``device_put`` into the
window's target shardings — by the time ``train_batch()`` dispatches,
its inputs are already on device and the host-side pull/stack/transfer
cost vanishes from the critical path. This is the TPU analog of the
reference's pinned-memory DeepSpeedDataLoader workers (reference:
deepspeed/pt/deepspeed_dataloader.py): there the overlap hid collate +
H2D copies behind CUDA kernels; here it hides them behind XLA windows.

Determinism contract: the stager owns the engine's RNG chain while it is
attached. Window N+1's dropout keys are PRE-SPLIT at staging time with
exactly the split sequence the unstaged path performs at dispatch time
(``rng, sub = split(rng); keys = split(sub, accum)``), and the
post-split state rides each staged window back to the engine at consume
time — staged and unstaged runs produce bit-identical key streams, so a
staged run is replayable against an unstaged one. Interleaving staged
``train_batch()`` with manual ``forward()`` calls on the SAME engine
advances the two chains independently and is not replayable against an
un-interleaved run.

Shutdown contract: ``close()`` stops the worker (bounded waits only — the
worker never blocks uninterruptibly), drains staged-but-unconsumed
windows so their device buffers free, and joins the thread. Staged
windows that were pulled from the source but never consumed are DROPPED
on close; for the preemption drain that is correct — the restart replays
the data order from the checkpointed step, so prefetched-but-unused
items belong to the discarded timeline.

Consumers: ``DeepSpeedEngine.train_batch`` (iterator-fed fast path,
``accum`` micro-batches per window) and ``DeepSpeedDataLoader`` (the
unfused ``_place`` path — the same stager with ``accum=1`` and an
identity stack, turning it into a device-placing prefetcher).
"""

import queue
import threading

import numpy as np

from ..telemetry.tracing import NOOP_TRACER, phase
from ..utils.logging import logger


def ragged_window_error(collected, accum):
    """The one place the mid-window-dry message is built: the unstaged
    ``train_batch`` loop and the stager raise the identical error."""
    err = RuntimeError(
        f"data iterator ran dry mid-window: collected {collected} of "
        f"gradient_accumulation_steps={accum} micro-batches. Size the "
        "dataset/loader so full accumulation windows divide it (the "
        "loader's drop_last does this), or stop at the previous window "
        "boundary."
    )
    # data exhaustion is the CALLER's sizing bug, not a transient fault:
    # the run supervisor must surface it, not roll back and re-train old
    # windows until its budget drains (resilience/supervisor.py)
    err.ds_unrecoverable = True
    return err


def _tree_nbytes(tree):
    """Host bytes of a pytree of numpy-like leaves (0 for leaves that
    don't expose nbytes — already-placed jax arrays are not re-counted)."""
    import jax

    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, np.ndarray):
            total += int(leaf.nbytes)
    return total


class _End:
    """Sentinel: the source raised StopIteration at a window boundary."""


class _Failure:
    """Sentinel: staging failed; the consumer re-raises ``exc``."""

    __slots__ = ("exc",)

    def __init__(self, exc):
        self.exc = exc


class StagedWindow:
    """One staged accumulation window, ready (or nearly ready) to dispatch."""

    __slots__ = (
        "arrays", "keys", "rng_after", "index", "nbytes",
        "placed", "tokens", "samples",
    )

    def __init__(self, arrays, keys, rng_after, index, nbytes,
                 placed, tokens, samples):
        self.arrays = arrays
        self.keys = keys
        self.rng_after = rng_after
        self.index = index
        self.nbytes = nbytes
        self.placed = placed
        self.tokens = tokens
        self.samples = samples


class WindowStager:
    """Background worker staging ``accum``-micro-batch windows from an
    iterator into device-resident arrays, ``buffers`` windows deep.

    Parameters
    ----------
    source: iterator yielding micro-batches (tuples, or bare arrays that
        will be 1-tuple-wrapped). Pulled ONLY from the worker thread.
    accum: micro-batches per window.
    stack_fn: list-of-micro-batch-tuples -> host-stacked window.
    place_fn: host window -> device arrays in the target shardings.
    rng / split_fn: optional RNG plumbing; ``split_fn(rng, accum)``
        returns ``(new_rng, keys)`` and mirrors the unstaged dispatch
        split exactly (see module docstring). When ``rng`` is None the
        staged windows carry ``keys=None``.
    meta_fn: optional per-micro-batch ``(tokens, samples)`` counter
        (summed over the window for throughput accounting).
    buffers: max staged-but-unconsumed windows (2 = double buffering).
    stage_to_device: issue the device_put on the worker; False defers
        placement to the consuming thread (host pull+stack still overlap).
    telemetry: the engine's Telemetry facade (or any object exposing the
        observe/set/count hooks; absent hooks are skipped).
    """

    def __init__(self, source, accum, stack_fn, place_fn, rng=None,
                 split_fn=None, meta_fn=None, buffers=2,
                 stage_to_device=True, telemetry=None, name="train_batch",
                 fault_fn=None):
        if accum < 1:
            raise ValueError(f"accum must be >= 1, got {accum}")
        if buffers < 1:
            raise ValueError(f"staging_buffers must be >= 1, got {buffers}")
        self._source = source
        self._accum = int(accum)
        # lifecycle accounting (GIL-atomic int updates): pulled counts
        # micro-batches consumed from the source by the worker, served
        # counts windows handed to the consumer — their difference at
        # close time is the data a torn-down stream discards
        self.pulled_micro_batches = 0
        self.windows_served = 0
        self._stack_fn = stack_fn
        self._place_fn = place_fn
        self._rng = rng
        self._split_fn = split_fn
        self._meta_fn = meta_fn
        self._stage_to_device = bool(stage_to_device)
        self._telemetry = telemetry
        # fault-injection hook (resilience/faults.py, site
        # "staging.worker"): called once per window assembly ON the worker
        # thread; an exception here is real worker death — it surfaces at
        # the consumer's next get_window like any staging failure
        self._fault_fn = fault_fn
        self._stop = threading.Event()
        self._closed = False
        # slots bound TOTAL staged-but-unconsumed windows to ``buffers``:
        # the worker takes a slot before pulling, the consumer returns it
        # at get — a bounded queue alone would let the worker hold one
        # extra fully-staged window while blocked on put()
        self._slots = threading.Semaphore(int(buffers))
        self._queue = queue.Queue()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name=f"ds-window-stager-{name}"
        )
        self._thread.start()

    # -- telemetry (duck-typed: the facade no-ops when disabled, and test
    # stubs that implement only some hooks are fine) --------------------
    def _tel(self, method, *args):
        fn = getattr(self._telemetry, method, None)
        if fn is not None:
            try:
                fn(*args)
            except Exception:  # telemetry must never kill the pipeline
                logger.exception("window-stager telemetry hook failed")

    # -- worker ---------------------------------------------------------
    def _run(self):
        tel = self._telemetry
        tracer = getattr(tel, "tracer", NOOP_TRACER)
        index = 0
        while not self._stop.is_set():
            if not self._slots.acquire(timeout=0.1):
                continue
            if self._stop.is_set():
                return
            with phase(
                "train.stage_window",
                tel.train_trace_ctx() if tracer.enabled else None,
                tracer, index=index,
            ) as staged:
                try:
                    item = self._assemble(index, staged)
                except Exception as exc:  # surfaced at get_window, not lost
                    item = _Failure(exc)
            if item is None:
                return
            if not isinstance(item, StagedWindow):
                self._queue.put(item)  # end of stream, clean or not
                return
            if self._stop.is_set():
                # closed while staging: dropping the window here (instead
                # of putting it into the drained queue) frees its device
                # buffers now and keeps close()'s occupancy=0 final
                return
            self._queue.put(item)
            self._tel("observe_staging_time", staged.seconds * 1e3)
            self._tel("set_staging_occupancy", self._queue.qsize())
            index += 1

    def _assemble(self, index, staged):
        """Pull, stack and place one window: a StagedWindow, ``_End`` or a
        ``_Failure`` where the source ran dry, None when closed meanwhile."""
        if self._fault_fn is not None:
            self._fault_fn()
        batches = []
        with phase("stage.pull"):
            try:
                for _ in range(self._accum):
                    # re-check between pulls: close() mid-window must
                    # not keep draining the LIVE iterator (a blocked
                    # next() itself cannot be interrupted, but the
                    # damage is bounded to one pull)
                    if self._stop.is_set():
                        return None
                    batch = next(self._source)
                    self.pulled_micro_batches += 1
                    if not isinstance(batch, (tuple, list)):
                        batch = (batch,)
                    batches.append(tuple(batch))
            except StopIteration:
                if batches:
                    return _Failure(
                        ragged_window_error(len(batches), self._accum)
                    )
                return _End
        tokens = samples = 0
        if self._meta_fn is not None:
            for b in batches:
                t, s = self._meta_fn(b)
                tokens += t
                samples += s
        if self._stop.is_set():  # closed while pulling: drop
            return None
        keys = None
        if self._rng is not None and self._split_fn is not None:
            self._rng, keys = self._split_fn(self._rng, self._accum)
        with phase("stage.stack"):
            stacked = self._stack_fn(batches)
        # bookkeeping tree walk only when someone is listening
        nbytes = 0
        if self._telemetry is not None:
            nbytes = _tree_nbytes(stacked)
            staged.set_attr("nbytes", nbytes)
        if self._stage_to_device:
            with phase("stage.h2d"):
                stacked = self._place_fn(stacked)
            self._tel("count_h2d_bytes", nbytes)
        return StagedWindow(
            arrays=stacked, keys=keys, rng_after=self._rng,
            index=index, nbytes=nbytes,
            placed=self._stage_to_device, tokens=tokens,
            samples=samples,
        )

    # -- consumer -------------------------------------------------------
    def get_window(self, timeout=60.0):
        """Next staged window; blocks until one is ready.

        Raises StopIteration when the source is cleanly exhausted (and
        closes the stager), re-raises staging failures (including the
        ragged-final-window RuntimeError), and detects a dead worker
        instead of hanging forever.
        """
        with phase("train.stage_wait") as waited:
            while True:
                try:
                    item = self._queue.get(timeout=timeout)
                    break
                except queue.Empty:
                    if not self._thread.is_alive() \
                            and self._queue.qsize() == 0:
                        raise RuntimeError(
                            "window-staging worker died without signalling "
                            "end-of-stream"
                        ) from None
                    # a slow source is not an error — keep waiting while
                    # the worker is demonstrably alive
        if item is _End:
            self.close()
            raise StopIteration
        if isinstance(item, _Failure):
            self.close()
            raise item.exc
        self._slots.release()
        self.windows_served += 1
        self._tel("observe_staging_wait", waited.seconds * 1e3)
        self._tel("set_staging_occupancy", self._queue.qsize())
        if not item.placed:
            item.arrays = self._place_fn(item.arrays)
            item.placed = True
            self._tel("count_h2d_bytes", item.nbytes)
        return item

    def occupancy(self):
        return self._queue.qsize()

    def unconsumed_micro_batches(self):
        """Micro-batches pulled from the source but never handed to the
        consumer — what a close() at this instant would discard."""
        return max(
            0, self.pulled_micro_batches - self.windows_served * self._accum
        )

    def alive(self):
        return self._thread.is_alive()

    @property
    def closed(self):
        return self._closed

    def close(self, timeout=5.0):
        """Stop the worker, drop staged-but-unconsumed windows (freeing
        their device buffers), and join the thread. Idempotent; safe to
        call from the preemption drain — all waits are bounded."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        # unblock a worker parked on slot acquire (extra permit is
        # harmless: the stop flag is re-checked after every acquire)
        self._slots.release()
        try:
            while True:
                self._queue.get_nowait()
        except queue.Empty:
            pass
        if self._thread is not threading.current_thread():
            self._thread.join(timeout=timeout)
            if self._thread.is_alive():  # pragma: no cover - defensive
                logger.warning(
                    "window-stager thread did not stop within %.1fs "
                    "(daemon; it cannot block process exit)", timeout,
                )
        self._tel("set_staging_occupancy", 0)
