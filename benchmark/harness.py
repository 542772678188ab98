"""What every loop shares: finding a cell's files by the names in
``BENCHMARK.json``, the host spans, the compile counters, the device's
account of itself, and the result line.

Nothing here knows a configuration, a traffic mix or a metric by name: a
later PR adds those as files (README.md).
"""

import contextlib
import importlib
import json
import os
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

# jax.monitoring's names (jax/_src/dispatch.py, compilation_cache.py)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as fd:
        return json.load(fd)


def load_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fd:
        return json.load(fd)


def load_cell(name):
    """(cell, config): ``workloads/<name>.json`` and the configuration file
    that ``BENCHMARK.json`` gives for the cell's configuration."""
    bench = load_benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = load_json("workloads", name + ".json")
    for key in ("config", "chips"):
        if cell[key] != entry[key]:
            raise SystemExit(
                f"workloads/{name}.json and BENCHMARK.json differ on {key}")
    conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
    with open(os.path.join(ROOT, conf["file"])) as fd:
        config = json.load(fd)
    return cell, config, bench


def plugin(kind, name):
    """``benchmark/<kind>/<name>.py``: a loop, a traffic generator, a
    reference, a reader or a cost function, found by its file's name."""
    return importlib.import_module(f"benchmark.{kind}.{name}")


# a configuration file's sections that are the harness's own, not the model's
OWN_SECTIONS = frozenset((
    "name", "source", "reduced", "published", "reduced_why", "deployment",
    "assumed", "reference", "program", "train", "serve", "toy", "toy_why"))


def sizes(config, rehearse):
    """What a published config holds, as program and reference get it: the
    numbers, strings, lists and dicts of the configuration's top level, where
    the published keys live, then the numbers, lists and dicts of ``assumed``
    (a string there is a note, whatever its key, and is not handed over; nor
    are the harness's own sections, any ``*_why`` and a null), with the toy
    overrides in a rehearsal. A list does not hash: hand this to ``jax.jit``
    inside a closure, never as a static argument."""
    def handed(section, kinds):
        return {k: v for k, v in section.items()
                if k not in OWN_SECTIONS and not k.endswith("_why")
                and isinstance(v, kinds)}

    out = handed(config, (int, float, str, list, dict))
    out.update(handed(config.get("assumed", {}), (int, float, list, dict)))
    if rehearse:
        out.update(config["toy"])
    return out


def place_compile_cache():
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` if the
    machine sets it, else at one fixed path inside the checkout. Set through
    the variable, before jax starts, so that the program (which fixes its
    directory in code only when the variable is unset) takes the same one."""
    if not os.environ.get(CACHE_DIR_ENV):
        os.environ[CACHE_DIR_ENV] = os.path.join(ROOT, ".jax_cache")
    os.makedirs(os.environ[CACHE_DIR_ENV], exist_ok=True)
    # cache every program, however quick its compile: hundreds of small
    # ones are most of a warm set-up otherwise
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    return os.environ[CACHE_DIR_ENV]


class CompileCounter:
    """Backend compiles and persistent-cache loads, counted by the
    benchmark's own ``jax.monitoring`` listeners (the arithmetic of the
    program's ``jax/recompiles`` and ``jax/compile_cache_hits`` counters,
    telemetry/registry.py, without reading the program's registry)."""

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def total(self):
        return self.compiles + self.cache_hits


class Spans:
    """The benchmark's own host spans, on ``time.perf_counter``; while a
    trace is being taken each is also written into the profiler's trace
    (``jax.profiler.TraceAnnotation``), where the reduction names the
    device's idle gaps by them."""

    def __init__(self):
        self.records = []   # (name, start, end, attrs)
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name, **attrs):
        with contextlib.ExitStack() as stack:
            if self.annotate:
                import jax

                stack.enter_context(jax.profiler.TraceAnnotation(name))
            t0 = time.perf_counter()
            try:
                yield attrs
            finally:
                self.records.append((name, t0, time.perf_counter(), attrs))

    def named(self, name):
        return [r for r in self.records if r[0] == name]


class Tracer:
    """Starts and stops the profiler around (part of) the measured window
    and remembers where the ``.xplane.pb`` went."""

    def __init__(self, enabled, spans, out_dir):
        self.enabled, self.spans, self.out_dir = enabled, spans, out_dir
        self.running = False
        self.path = None
        self.t_start = self.t_stop = None

    def start(self):
        if not self.enabled:
            return
        import shutil

        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # no per-call python events
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.out_dir, profiler_options=options)
        self.running = self.spans.annotate = True
        self.t_start = time.perf_counter()
        self._window = jax.profiler.TraceAnnotation("bench.window")
        self._window.__enter__()

    def stop(self):
        if not self.running:
            return
        import glob

        import jax

        self._window.__exit__(None, None, None)
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        self.running = self.spans.annotate = False
        found = glob.glob(os.path.join(
            self.out_dir, "plugins", "profile", "*", "*.xplane.pb"))
        self.path = found[0] if found else None


def device_report(devices):
    """The device as jax reports it, and the peak on the fullest chip."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(peaks),
    }


def setup_account(ctx, setup_s):
    """Where the set-up went. ``setup_s`` is counted from the moment the
    loop has its devices (``t_loop``): what the program and the cell do
    before the first timed window, each part under the benchmark's own span
    of its name, and ``unnamed_s`` what no span covers (the loop's imports
    and glue). ``before_the_loop_s`` (the interpreter, ``import jax``, the
    TPU runtime coming up: no line of the program) is NOT in it and is
    printed beside it, with their sum as ``process_to_first_window_s``."""
    spans = ctx["spans"]
    before = ctx["t_loop"] - ctx["t_process"]
    out = {"setup_s": setup_s,
           "before_the_loop_s": before,
           "process_to_first_window_s": before + setup_s,
           "backend_compiles": ctx["compiles"].compiles,
           "compile_cache_loads": ctx["compiles"].cache_hits}
    named = 0.0   # no span of a set-up lies inside another
    for name in sorted({r[0] for r in spans.records}):
        out[name + "_s"] = sum(t1 - t0 for _n, t0, t1, _a in spans.named(name))
        named += out[name + "_s"]
    out["unnamed_s"] = setup_s - named
    return out


def say(kind, **fields):
    """One earlier line: everything that is not the result."""
    print(json.dumps({"note": kind, **fields}, default=float), flush=True)
