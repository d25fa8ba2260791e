"""One run of one cell: find its configuration, traffic and entry kind by
name, build the system under test with the benchmark's weights, warm it
up, measure for the window, check what it produced, and assemble the
result line.

Everything that belongs to one configuration, one traffic mix, one entry
kind or one per-layer metric lives in a file of its own, found by name:

- ``BENCHMARK.json`` (at the checkout root): cells and metrics;
- ``bench/configs/<config>.json``: model sizes, param sets, limits;
- ``bench/archs/<arch>.py``: the weights, plain reference, control and
  work counts of one model architecture, named by a model's ``"arch"``;
- ``bench/traffic/<traffic>.json``: the entry kind and its load;
- ``bench/entries/<entry>.py``: ``setup(run)``, ``window(run)``,
  ``results(run)`` of one way of driving the system;
- ``bench/metrics/<metric>.py``: ``read(ctx)`` of one per-layer metric.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import shutil
import tempfile
import threading
import time
from typing import Dict

import numpy as np

from bench import archs, check, flops, mix, trace as tracemod
from bench.record import Recorder

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(Exception):
    """The machine does not hold what the cell needs."""


class TraceError(RuntimeError):
    """A traced run on the chip whose trace cannot give the slice's device
    numbers: no slice span, or no operation of the cell's devices."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(name: str, root: str = ROOT, bench_dir: str = HERE):
    """(benchmark, cell, configuration entry, configuration, traffic)."""
    bm = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    cell = cells[name]
    centry = {c["name"]: c for c in bm["configs"]}[cell["config"]]
    config = load_json(os.path.join(root, centry["file"]))
    traffic = mix.load_traffic(cell["traffic"], bench_dir)
    return bm, cell, centry, config, traffic


def devices_for(chips: int, require_tpu: bool = True):
    """The first ``chips`` devices; refuses a platform other than TPU,
    too few devices, and Pallas kernels that would run interpreted."""
    import jax
    devs = jax.devices()
    if require_tpu:
        from repro.kernels._compat import resolve_interpret
        if devs[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX found {devs[0].platform}")
        if resolve_interpret(None) is not False:
            raise NoChip("Pallas kernels would run interpreted")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} devices, JAX found "
                     f"{len(devs)}")
    return devs[:chips]


# -- models --------------------------------------------------------------------


def program_configs(config: dict) -> Dict[str, object]:
    """The program's ``ModelConfig`` for each model role, checked against
    every size and architecture key the configuration file states."""
    from repro.configs.registry import get_config, get_reduced
    get = get_reduced if config.get("program_preset") == "reduced" \
        else get_config
    out = {}
    for role, mdl in config["models"].items():
        cfg = get(mdl["registry"])
        want = dict(mdl["sizes"], **config["architecture"],
                    compute_dtype=config["compute_dtype"],
                    param_dtype=config["param_dtype"])
        bad = {k: (getattr(cfg, k), v) for k, v in want.items()
               if getattr(cfg, k) != v}
        if bad:
            raise ValueError(f"{role}: the program's {mdl['registry']} "
                             f"differs from the configuration: {bad}")
        out[role] = cfg
    return out


def check_layout(config: dict, cfgs: dict, weights: dict):
    """The benchmark's weights have the program's parameter layout."""
    import jax
    from repro.models import protein as prot
    init = {"generator": prot.init_progen, "scorer": prot.init_foldscore}
    for role, mdl in config["models"].items():
        want = jax.eval_shape(
            lambda k, f=init[mdl["kind"]], c=cfgs[role]: f(k, c),
            jax.random.PRNGKey(0))
        got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                           weights[role])
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise ValueError(f"{role}: weight layout differs from the "
                             f"program's")


@contextlib.contextmanager
def _init_returns(prot, generator=None, scorer=None):
    """While it is open, the program's init of a generator or a scorer
    returns the given weights. ``ProteinPayload``'s constructor takes no
    weights and initialises its default models itself; an init whose
    result is replaced at once costs set-up time and, at a large size, a
    second copy of the model on the chip."""
    saved = prot.init_progen, prot.init_foldscore
    if generator is not None:
        prot.init_progen = lambda key, cfg: generator
    if scorer is not None:
        prot.init_foldscore = lambda key, cfg: scorer
    try:
        yield
    finally:
        prot.init_progen, prot.init_foldscore = saved


def build_payload(config: dict, cfgs: dict, weights: dict, length: int):
    """A ``ProteinPayload`` serving the benchmark's weights under the
    configuration's param-set namespaces: its default generator and
    scorer are the configuration's ``default`` models, where it has
    them, built on the benchmark's weights and never initialised."""
    import jax
    from repro.core import ProteinPayload
    from repro.learn.param_store import ParamStore
    from repro.models import protein as prot
    default = {m["kind"]: role for role, m in config["models"].items()
               if m["param_set"] == "default"}
    gen, fold = default.get("generator"), default.get("scorer")
    with _init_returns(prot, weights.get(gen), weights.get(fold)):
        payload = ProteinPayload(
            jax.random.PRNGKey(0), gen_cfg=cfgs.get(gen),
            fold_cfg=cfgs.get(fold), length=length,
            reduced=config.get("program_preset") == "reduced")
    for role, mdl in config["models"].items():
        ns, cfg, p = mdl["param_set"], cfgs[role], weights[role]
        if ns == "default":
            continue
        if mdl["kind"] == "generator":
            payload.gen_stores[ns] = ParamStore(p)
            payload.gen_cfgs[ns] = cfg
        else:
            payload.fold_sets[ns] = (cfg, p)
    return payload


# -- the run ---------------------------------------------------------------------


class Run:
    """State of one run, shared by the harness, the entry and the metric
    readers."""

    def __init__(self, name, seed, seconds, trace, *, root=ROOT,
                 bench_dir=HERE, require_tpu=True, t_start=None):
        self.t_start = time.monotonic() if t_start is None else t_start
        self.bm, self.cell, _, self.config, self.traffic = find_cell(
            name, root, bench_dir)
        self.name, self.seed = name, int(seed)
        self.seconds, self.trace = float(seconds), bool(trace)
        self.root, self.bench_dir = root, bench_dir
        self.chips = int(self.cell["chips"])
        self.devices = devices_for(self.chips, require_tpu)
        self.tracing = False            # instruments count only while on
        self.traced = {}                # instrument totals over the slice
        self.registries = []            # executor metric registries
        self.extra = {}                 # numbers entries hand to readers
        self.peak = None
        self.entry = load_module(
            os.path.join(bench_dir, "entries", f"{self.traffic['entry']}.py"),
            f"bench_entry_{self.traffic['entry']}")

    # -- set-up --------------------------------------------------------------

    def build(self):
        from repro.session import enable_compilation_cache
        enable_compilation_cache()
        self.cfgs = program_configs(self.config)
        models = self.config["models"]
        self.sizes = {r: dict(m["sizes"], **self.config["architecture"])
                      for r, m in models.items()}
        self.archs = {r: archs.load(archs.name_of(m), self.bench_dir)
                      for r, m in models.items()}
        self.weights = archs.make_weights(
            np.random.SeedSequence([self.seed, 0]).generate_state(
                2, dtype=np.uint32),
            {r: (self.archs[r], m["kind"], self.sizes[r])
             for r, m in models.items()},
            device=self.devices[0])
        check_layout(self.config, self.cfgs, self.weights)
        self.payload = build_payload(self.config, self.cfgs, self.weights,
                                     max(mix.receptor_lens(self.traffic)))
        prefix = max(int(m["sizes"].get("frontend_seq", 0))
                     for m in self.config["models"].values())
        self.recorder = Recorder(prefix)

    def roles(self):
        """(kind, namespace) -> (role, sizes, architecture module)."""
        return {(m["kind"], m["param_set"]): (r, self.sizes[r],
                                              self.archs[r])
                for r, m in self.config["models"].items()}

    # -- instruments for the traced run ----------------------------------------

    def instrument(self):
        """Count the work of the paged decode step's kernels and of the
        scorer executables, as each model's architecture counts it, while
        the profiler runs: wraps the warm engines' ``step`` and the warm
        scorer executables of the payload's cache."""
        from repro.models.protein import PagedDecodeEngine
        try:
            self.peak = tracemod.peak_for(self.devices[0].device_kind,
                                          self.bench_dir)
        except KeyError:
            if self.devices[0].platform == "tpu":
                raise
            self.peak = None        # no device numbers off the chip
        p, roles = self.payload, self.roles()
        for key, val in list(p._cache.items()):
            kind = key[0]
            if isinstance(val, PagedDecodeEngine):
                ns = kind.split("@", 1)[1] if "@" in kind else "default"
                val.step = self._count_step(val, roles.get(("generator",
                                                            ns)))
            elif isinstance(kind, str) and kind.startswith("predict_mb"):
                shape, _, ns = kind.partition("@")
                per, L = shape[len("predict_mb"):].split("_L")
                p._cache[key] = self._count_call(
                    val, roles.get(("scorer", ns or "default")), int(per),
                    int(L))

    def _add(self, name, f, b):
        """One call's operations, bytes and least time on the chip."""
        t = self.traced.setdefault(name, [0, 0.0, 0.0, 0.0])
        t[0] += 1
        t[1] += f
        t[2] += b
        t[3] += flops.roofline_s(f, b, self.peak) if self.peak else 0.0

    def _count_step(self, eng, role):
        """``role``: the generator's (role, sizes, architecture), or None
        for a model the configuration does not state."""
        orig = eng.step

        def step(params, temperature):
            if self.tracing and role is not None:
                _, m, arch = role
                lens = eng.true_lens
                counts = arch.step_counts(m, np.where(lens > 0, lens + 1, 0))
                for name, (f, b) in counts.items():
                    self._add(name, f, b)
            return orig(params, temperature)
        return step

    def _count_call(self, fn, role, per, L):
        f, b = (role[2].scorer_call(role[1], per, L) if role is not None
                else (0, 0))

        def call(*a, **kw):
            if self.tracing:
                self._add("fold", f, b)
            return fn(*a, **kw)
        return call

    # -- the traced slice ------------------------------------------------------

    def _profile(self, t0):
        """Profile a steady slice of the window on a thread of its own.
        The slice is the ``bench.slice`` span, on the profiler's clock,
        around the time the instruments count: the reduction clips every
        device number to it, so none covers the profiler's start or stop."""
        import jax
        tr = self.traffic["trace"]
        lead = min(float(tr["lead_s"]), self.seconds / 4)
        span = min(float(tr["span_s"]), self.seconds / 2)
        self.trace_dir = tempfile.mkdtemp(prefix="bench-trace-")

        def body():
            time.sleep(max(0.0, t0 + lead - time.monotonic()))
            jax.profiler.start_trace(self.trace_dir)
            a = time.monotonic()
            with jax.profiler.TraceAnnotation(tracemod.SLICE):
                self.tracing = True
                time.sleep(span)
                self.tracing = False
            b = time.monotonic()
            jax.profiler.stop_trace()
            self.slice_s = b - a

        th = threading.Thread(target=body, name="bench-profile")
        th.start()
        return th

    # -- one run -----------------------------------------------------------------

    def execute(self) -> dict:
        from repro.obs import CompileWatcher, MetricsRegistry
        setup_reg = MetricsRegistry()
        with CompileWatcher(setup_reg):
            self.build()
            built = time.monotonic()
            self.entry.setup(self)
        self.setup_phases = {"build_s": built - self.t_start,
                             "entry_setup_s": time.monotonic() - built}
        # JAX's compile events of the set-up, [count, seconds] by event:
        # a set-up that finds its programs in the cache retrieves them
        # (``cache_retrieval_time_sec``) and compiles none
        # (``backend_compile_duration``).
        for key, h in setup_reg.series("jax.compile_s").items():
            self.setup_phases[dict(key[1:])["event"]] = [h.count, h.sum]
        if self.trace:
            self.instrument()
        self.t0 = time.monotonic()
        self.setup_s = self.t0 - self.t_start
        self.t1 = self.t0 + self.seconds
        from repro.core import payload as payload_mod
        logs = {"gen": payload_mod.gen_batch_log,
                "fold": payload_mod.batch_log}
        start = {k: len(v) for k, v in logs.items()}
        reg = MetricsRegistry()
        with CompileWatcher(reg):
            prof = self._profile(self.t0) if self.trace else None
            self.entry.window(self)
            if prof is not None:
                prof.join()
        self.dispatches = {k: list(v[start[k]:]) for k, v in logs.items()}
        self.window_compiles = int(reg.counter(
            "jax.compiles", event="backend_compile_duration").get())
        self.memory_peak = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in self.devices)
        out = self.entry.results(self)
        self.entry.teardown(self)
        self.payload = None         # the reference runs on a freed chip
        return out

    def compare(self) -> dict:
        words = np.random.SeedSequence([self.seed, 4]).generate_state(
            2, dtype=np.uint32)
        return check.compare(self.recorder, self.weights, self.roles(),
                             self.config["check"], words)

    # -- the result line ---------------------------------------------------------

    def metrics_for(self, kind: str):
        out = []
        for mtr in self.bm[kind]:
            cells = mtr.get("workloads")
            if cells is None or self.name in cells:
                out.append(mtr)
        return out

    def reduce_trace(self):
        """The slice's device numbers. On a TPU the trace must hold the
        slice span and an operation on each of the cell's devices; off
        the chip a trace without the span keeps the host's ``slice_s``."""
        red = tracemod.reduce(self.trace_dir, n_devices=self.chips)
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        if self.devices[0].platform == "tpu":
            if red["window_s"] is None:
                raise TraceError(f"the trace has no {tracemod.SLICE} span "
                                 f"on its host plane")
            if red["devices"] < self.chips:
                raise TraceError(f"the trace has {red['devices']} "
                                 f"{tracemod.DEVICE_PREFIX}<n> planes; the "
                                 f"cell runs on {self.chips} chips")
            if not red["busy_s"] > 0:
                raise TraceError("no operation ran on the device inside "
                                 "the slice")
        elif red["window_s"] is None:
            red["window_s"] = self.slice_s
        return red


def run(name, seed, seconds, trace, **kw) -> dict:
    """Run one cell; returns the result object (without printing)."""
    r = Run(name, seed, seconds, trace, **kw)
    e2e = r.execute()
    attempted, failed = e2e.pop("attempted"), e2e.pop("failed")
    numbers = r.compare()
    limits = r.config["limits"]
    checks = {k: {"value": numbers.get(k), "limit": float(limits[k])}
              for k in sorted(limits)}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    import jax
    dev = r.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(r.memory_peak)}
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "setup_phases": r.setup_phases,
              "window_compiles": r.window_compiles}
    metrics = {}
    if r.trace:
        red = r.reduce_trace()
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        ctx = {"run": r, "trace": red, "peak": r.peak, "e2e": e2e}
        for mtr in r.metrics_for("per_layer"):
            mod = load_module(os.path.join(r.bench_dir, "metrics",
                                           f"{mtr['name']}.py"),
                              "bench_metric_" + mtr["name"].replace(".", "_"))
            v = mod.read(ctx)
            if v is not None:
                metrics[mtr["name"]] = {"value": float(v),
                                        "unit": mtr["unit"]}
        result["breakdown"] = red["breakdown"]
    else:
        for mtr in r.metrics_for("end_to_end"):
            metrics[mtr["name"]] = {"value": float(e2e[mtr["name"]]),
                                    "unit": mtr["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = checks
    return result
