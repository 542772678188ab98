"""Wall-clock and throughput timers.

Parity with the reference's deepspeed_timer.py:
- ``SynchronizedWallClockTimer`` (reference: deepspeed/pt/deepspeed_timer.py:20-94):
  named start/stop timers; on TPU the device fence is
  ``jax.block_until_ready`` / ``jax.effects_barrier`` instead of
  ``torch.cuda.synchronize``.
- ``ThroughputTimer`` (reference :97-171): samples/sec with a warmup
  ``start_step``, periodic reporting, host memory monitoring via psutil when
  available.
"""

import time

from .logging import log_dist, logger


_SYNC_FN = None
_SYNC_INPUTS = None


def _device_sync():
    """Block until all dispatched device work is done (timing fence).

    ``jax.effects_barrier()`` only waits for side-EFFECTING computations —
    on an async dispatch stream it returns immediately and a timer fenced
    with it measures host dispatch, not device time (observed: GPT-2 1.5B
    "forward: 3.3 ms" against a 774 ms real window). Enqueue a trivial
    program on EVERY local device and block on its results instead: a
    device runs its programs in the order they were enqueued, so each
    completion implies everything before it on that device finished
    (pinned on the chip by tests_tpu/test_kernel_numerics.py). Callers
    that hold a real output of the work being timed should block on that
    instead — it needs no extra dispatch (the engine's breakdown timers
    and its ThroughputTimer fence_fn do)."""
    global _SYNC_FN, _SYNC_INPUTS
    import jax

    if _SYNC_FN is None:
        import numpy as np

        _SYNC_FN = jax.jit(lambda x: x + 1)
        _SYNC_INPUTS = [
            jax.device_put(np.zeros((), np.int32), d)
            for d in jax.local_devices()
        ]
    jax.block_until_ready([_SYNC_FN(x) for x in _SYNC_INPUTS])


class SynchronizedWallClockTimer:
    class Timer:
        def __init__(self, name, synchronize=True):
            self.name_ = name
            self.synchronize = synchronize
            self.elapsed_ = 0.0
            self.started_ = False
            self.start_time = 0.0

        def start(self):
            assert not self.started_, f"timer {self.name_} has already been started"
            if self.synchronize:
                _device_sync()
            self.start_time = time.time()
            self.started_ = True

        def stop(self):
            assert self.started_, f"timer {self.name_} is not started"
            if self.synchronize:
                _device_sync()
            self.elapsed_ += time.time() - self.start_time
            self.started_ = False

        def reset(self):
            self.elapsed_ = 0.0
            self.started_ = False

        def elapsed(self, reset=True):
            started = self.started_
            if started:
                self.stop()
            elapsed = self.elapsed_
            if reset:
                self.reset()
            if started:
                self.start()
            return elapsed

    def __init__(self, synchronize=True):
        self.timers = {}
        self.synchronize = synchronize

    def __call__(self, name):
        if name not in self.timers:
            self.timers[name] = self.Timer(name, synchronize=self.synchronize)
        return self.timers[name]

    def has_timer(self, name):
        return name in self.timers

    def snapshot(self):
        """Non-destructive ``{name: elapsed_seconds}`` view including the
        running portion of started timers. No device fence and no timer
        state change — safe to call from another thread (the telemetry
        watchdog reads this for stall reports)."""
        now = time.time()
        out = {}
        # list(): the training thread may register a first-use timer while
        # the watchdog thread iterates; a live dict view would raise
        for name, timer in list(self.timers.items()):
            elapsed = timer.elapsed_
            if timer.started_:
                elapsed += now - timer.start_time
            out[name] = elapsed
        return out

    def log(self, names, normalizer=1.0, reset=True, ranks=None):
        assert normalizer > 0.0
        string = "time (ms)"
        for name in names:
            if name in self.timers:
                elapsed = self.timers[name].elapsed(reset=reset) * 1000.0 / normalizer
                string += f" | {name}: {elapsed:.2f}"
        log_dist(string, ranks=ranks or [0])

    @staticmethod
    def memory_usage():
        import jax

        stats = jax.local_devices()[0].memory_stats()
        if not stats:  # the CPU backend keeps none
            return "device mem: n/a"
        in_use = stats.get("bytes_in_use", 0) / (1024**3)
        peak = stats.get("peak_bytes_in_use", 0) / (1024**3)
        return f"device mem: {in_use:.2f} GB in use | {peak:.2f} GB peak"


class ThroughputTimer:
    def __init__(
        self,
        batch_size,
        num_workers,
        start_step=2,
        steps_per_output=50,
        monitor_memory=True,
        logging_fn=None,
        fence_fn=None,
    ):
        # fence_fn: callable draining the device before a report boundary.
        # The engine passes a block-on-real-output fence (no extra
        # dispatch); the default is _device_sync.
        self.fence_fn = fence_fn or _device_sync
        self.start_time = 0.0
        self.end_time = 0.0
        self.started = False
        self.batch_size = max(1, batch_size or 1)
        self.num_workers = num_workers
        self.start_step = start_step
        self.epoch_count = 0
        self.local_step_count = 0
        self.total_step_count = 0
        self.total_elapsed_time = 0.0
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory
        self.logging = logging_fn or logger.info
        self.initialized = False

    def update_epoch_count(self):
        self.epoch_count += 1
        self.local_step_count = 0

    def _init_timer(self):
        self.initialized = True

    def start(self):
        self._init_timer()
        self.started = True
        if self.total_step_count >= self.start_step:
            if self.total_step_count == self.start_step:
                # open the measurement on a quiet device; later steps run
                # UNFENCED — a per-step fence drains the dispatch queue,
                # so the host could never run ahead of the device and the
                # timer would slow the async train loop it is supposed to
                # observe (cost on a locally attached chip: not measured)
                self.fence_fn()
            self.start_time = time.time()

    def stop(self, report_speed=True):
        if not self.started:
            return
        self.started = False
        self.total_step_count += 1
        self.local_step_count += 1
        if self.total_step_count > self.start_step:
            if (
                report_speed
                and self.local_step_count % self.steps_per_output == 0
            ):
                # fence ONLY at report boundaries: the queue drain lands in
                # this window's duration, so the accumulated elapsed time
                # stays truthful without a per-step drain
                self.fence_fn()
            self.end_time = time.time()
            duration = self.end_time - self.start_time
            self.total_elapsed_time += duration
            if report_speed and self.local_step_count % self.steps_per_output == 0:
                avg = self.avg_samples_per_sec()
                if avg > 0:
                    # pre-warmup (or zero-elapsed) windows have no
                    # truthful rate yet — skip the line rather than log 0
                    self.logging(
                        "{}/{}, SamplesPerSec={:.3f}".format(
                            self.epoch_count,
                            self.local_step_count,
                            avg,
                        )
                    )
                if self.monitor_memory:
                    try:
                        import psutil

                        vm = psutil.virtual_memory()
                        self.logging(
                            f"{self.epoch_count}/{self.local_step_count}, "
                            f"vm percent: {vm.percent}"
                        )
                    except ImportError:
                        pass

    def avg_samples_per_sec(self):
        if self.total_step_count > self.start_step and self.total_elapsed_time > 0:
            samples = self.batch_size * (self.total_step_count - self.start_step)
            return samples / self.total_elapsed_time
        # Pre-warmup there is no measurement; the reference returned
        # float("-inf") here, which leaked into logs and scalar sinks as a
        # non-finite value. 0.0 is the no-data-yet sentinel (stop() skips
        # the report line while it holds).
        return 0.0
