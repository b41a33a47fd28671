#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run_cell.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell is an entry of ``BENCHMARK.json``'s ``workloads``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<traffic>.json``).  Everything is found by name, so a new
cell is new files and a new entry, never an edit here.

One run, in one process that holds the chip:

  1. set-up (``setup_s``, from process start): the persistent compile
     cache, the deployed weights made from ``--seed`` on the device in one
     jitted call, the scheduler and its `ServingFrontend`, one admission
     per distinct prompt length of the mix (every program and every eager
     shape the window will use), then the load itself until every client
     holds a first token;
  2. the window: ``--seconds`` of the mix through `ServingFrontend.submit`,
     every token consumed and stamped at its client;
  3. the check: once the window has closed and the program's state is
     freed, the configuration's plain reference
     (``bench/reference/<reference>.py``) re-derives the weights from the
     seed and scores a seeded sample of the finished requests,
     teacher-forced (``correct``).

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` runs the
scheduler with its span tracer and profiler annotations, captures a few
seconds of the window with `jax.profiler`, and prints the cell's per-layer
metrics (each read by ``bench/metrics/<metric>.py``) with the device's busy
time and a breakdown.  The last line of standard output is the JSON
result; the numbers compared for ``correct`` close standard error, each
beside its limit.  With no TPU, or fewer chips than the cell needs, it
exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import numpy as np  # noqa: E402

from bench import loadgen, trace_reduce  # noqa: E402

TRACE_LEAD_S = 1.0           # capture starts this long after the window opens
TRACE_SECONDS = 3.0          # and lasts this long
SAMPLE_TOKENS = 512          # served tokens the check scores, at least
SAMPLE_MAX_REQUESTS = 16
CONTROLS = {"int4_prefill": {"prefill_format": "mxint4"}}


class NoChip(RuntimeError):
    """The platform or device count does not match the cell."""


@dataclasses.dataclass
class Cell:
    name: str
    spec: dict                 # the workloads entry
    config: dict               # bench/configs/<config>.json
    traffic: loadgen.Traffic
    limits: dict               # bench/limits/<cell>.json
    bench: dict                # BENCHMARK.json
    ref: types.ModuleType      # bench/reference/<config's reference>.py


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    specs = {w["name"]: w for w in bench["workloads"]}
    if name not in specs:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    spec = specs[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config_file = configs[spec["config"]]["file"]
    config = json.loads((root / config_file).read_text())
    traffic = loadgen.Traffic.from_json(spec["traffic"], json.loads(
        (root / "bench" / "traffic" / f"{spec['traffic']}.json").read_text()))
    limits = json.loads((root / "bench" / "limits" / f"{name}.json")
                        .read_text())
    return Cell(name, spec, config, traffic, limits, bench,
                _load_reference(config, config_file, root))


def _load_reference(config: dict, config_file: str,
                    root: pathlib.Path) -> types.ModuleType:
    """The module ``bench/reference/<reference>.py`` that the configuration
    names, once it is shown to implement the family of its ``arch``."""
    from repro import configs
    if "reference" not in config:
        raise ValueError(f"{config_file} names no reference")
    path = root / "bench" / "reference" / f"{config['reference']}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{config_file} names the reference {path}, "
                                "which does not exist")
    ref = _load_module(path)
    family = configs.get_config(config["arch"]).family
    if family not in ref.FAMILIES:
        raise ValueError(f"{config_file}: {config['arch']} is of the family "
                         f"{family!r}; {path} implements {ref.FAMILIES}")
    return ref


def _load_module(path: pathlib.Path):
    name = "bench_ext_" + path.stem.replace(".", "_").replace("-", "_")
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[name] = mod          # dataclasses look their module up
    mod_spec.loader.exec_module(mod)
    return mod


def per_layer_metrics(cell: Cell) -> list[dict]:
    """The per-layer metrics this cell reports: those that list it, and
    those with no list whose end-to-end metric the cell reports."""
    e2e = end_to_end_metrics(cell)
    out = []
    for m in cell.bench["per_layer"]:
        if cell.name in m.get("workloads", []) or (
                "workloads" not in m and m["moves"] in e2e):
            out.append(m)
    return out


def read_per_layer(cell: Cell, ctx, root: pathlib.Path = ROOT) -> dict:
    """Each per-layer metric of the cell, read by its own file; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in per_layer_metrics(cell):
        v = _load_module(root / "bench" / "metrics" / f"{m['name']}.py"
                         ).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def end_to_end_metrics(cell: Cell) -> list[str]:
    return [m["name"] for m in cell.bench["end_to_end"]
            if "workloads" not in m or cell.name in m["workloads"]]


# ---------------------------------------------------------------------------
# Device and the system under test
# ---------------------------------------------------------------------------


def check_devices(platform: str, chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != platform or len(devs) < chips:
        raise NoChip(f"cell needs {chips} x {platform}; JAX has "
                     f"{len(devs)} x {devs[0].platform}")
    return devs[:chips]


def model_config(cell: Cell):
    """The registry's `ModelConfig` with the configuration file's numbers."""
    from repro import configs
    return dataclasses.replace(configs.get_config(cell.config["arch"]),
                               **cell.config["model"])


def weights_fn(mc):
    """One jitted call: key -> the deployed weights, made on the device."""
    import jax
    from repro.models import deploy, lm
    _, _, paths = lm.init(mc, jax.random.key(0), abstract=True)
    return jax.jit(lambda key: deploy.deploy_quantize(lm.init(mc, key)[0],
                                                      paths))


def build_scheduler(cell: Cell, seed: int, *, trace: bool,
                    control: str | None):
    """(scheduler, tracer clock or None) over weights made from ``seed`` on
    the device in one jitted call."""
    import jax
    from repro.obs import Observability, Tracer
    from repro.serving import EngineSpec, InferenceEngine
    from repro.serving.sampling import GenerationConfig
    from repro.serving.scheduler import RequestScheduler

    c = cell.config
    mc = model_config(cell)
    engine_kw = dict(c["engine"])
    if control is not None:
        engine_kw.update(CONTROLS[control])
    spec = EngineSpec(**engine_kw, seed=seed)
    params = jax.block_until_ready(weights_fn(mc)(jax.random.key(seed)))
    obs = Observability()
    tracer_clock = None
    if trace:
        tracer_clock = _FirstCall()
        obs = Observability(tracer=Tracer(clock=tracer_clock), profile=True)
    engine = InferenceEngine.from_config(mc, spec, params=params, obs=obs)
    t = cell.traffic
    gen = GenerationConfig(max_new_tokens=t.lengths.new_max, stop_tokens=())
    sched = RequestScheduler(engine, n_slots=t.slots, cache_len=t.cache_len,
                             gen=gen, chunk_size=c["scheduler"]["chunk_size"],
                             obs=obs, key=jax.random.key(seed))
    return sched, tracer_clock


class _FirstCall:
    """perf_counter that remembers its first reading: the tracer's t0."""

    def __init__(self):
        self.t0 = None

    def __call__(self) -> float:
        t = time.perf_counter()
        if self.t0 is None:
            self.t0 = t
        return t


def warm(sched, traffic: loadgen.Traffic, vocab: int) -> None:
    """One admission per distinct prompt length, two tokens each: every
    chunk program, the pool step and the eager shapes of admission."""
    from repro.serving.scheduler import Request
    rng = np.random.default_rng(0)
    for i, plen in enumerate(traffic.prompt_lengths()):
        sched.submit(Request(uid=10 ** 9 + i,
                             prompt=rng.integers(1, vocab, plen).tolist(),
                             max_new_tokens=2))
    sched.run()


class CompileCounter:
    """Counts backend compiles while ``on`` (the window)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.on = False
        self.count = 0
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, _secs, **_kw):
        if self.on and name == self.EVENT:
            self.count += 1


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        root: pathlib.Path = ROOT, platform: str = "tpu",
        control: str | None = None, t_process: float = T_PROCESS,
        faults=None) -> dict:
    """One run; returns the result dict, ``check`` last.

    ``faults`` (tests) is called with the scheduler before the window, to
    break the timed path on purpose."""
    from repro.runtime.compile_cache import enable_compile_cache
    from repro.serving.frontend import FrontendConfig, ServingFrontend

    cell = load_cell(cell_name, root)
    enable_compile_cache()
    devices = check_devices(platform, cell.spec["chips"])
    counter = CompileCounter()
    vocab = cell.config["model"]["vocab_size"]

    sched, tracer_clock = build_scheduler(cell, seed, trace=trace,
                                          control=control)
    warm(sched, cell.traffic, vocab)
    if faults is not None:
        faults(sched)
    fe = ServingFrontend(sched,
                         config=FrontendConfig(**cell.config["frontend"]))
    requests = loadgen.Requests(cell.traffic, seed, vocab)
    trace_dir = root / "bench" / "out" / f"trace-{cell_name}"
    capture: dict = {}

    async def main():
        async with fe:
            def on_open(t_open):
                capture["setup_s"] = time.perf_counter() - t_process
                counter.on = True
                if trace:
                    capture["task"] = asyncio.ensure_future(_capture(
                        t_open, trace_dir, sched.obs.metrics.histogram(
                            "engine.prefill_chunk_tokens"), capture))
            drv = loadgen.Driver(fe, requests, on_open=on_open)
            await drv.run(seconds)
            counter.on = False
            if "task" in capture:
                await capture["task"]
            return drv

    drv = asyncio.run(main())
    win = loadgen.Window(drv.records, drv.t_open, drv.t_close)
    result = {"correct": None, "attempted": win.attempted(),
              "failed": win.failed(), "metrics": {},
              "device": {"platform": devices[0].platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": int(max(
                             (d.memory_stats() or {}).get(
                                 "peak_bytes_in_use", 0) for d in devices))}}
    if trace:
        ctx = _trace_context(cell, sched, win, capture, root, trace_dir,
                             tracer_clock)
        shutil.rmtree(trace_dir, ignore_errors=True)
        result["device"]["busy_s"] = ctx.reduced.busy_s
        result["device"]["window_s"] = ctx.capture_s
        result["metrics"] = read_per_layer(cell, ctx, root)
        result["breakdown"] = {
            "device_ops": ctx.reduced.top_ops(10),
            "idle_gaps": trace_reduce.idle_by_span(ctx.reduced, 10)}
    else:
        values = {"setup_s": capture["setup_s"],
                  "output_tokens_per_s": win.output_tokens_per_s(),
                  "itl_p95": loadgen.p95(win.itl_samples()),
                  "ttft_p95": loadgen.p95(win.ttft_samples())}
        units = {m["name"]: m["unit"] for m in cell.bench["end_to_end"]}
        for name in end_to_end_metrics(cell):
            if values.get(name) is not None:
                result["metrics"][name] = {"value": values[name],
                                           "unit": units[name]}
    result["compiles_in_window"] = counter.count

    finished = [r for r in drv.records if r.complete]
    del fe, sched, drv
    gc.collect()
    live = (devices[0].memory_stats() or {}).get("bytes_in_use")
    print(f"device bytes in use before the check: {live}", file=sys.stderr)
    check, result["readings"] = score(cell, seed, finished)
    result["correct"] = win.failed() == 0 and all(
        v is not None and v <= lim for v, lim in check.values())
    result["check"] = {k: {"value": v, "limit": lim}
                       for k, (v, lim) in check.items()}
    return result


async def _capture(t_open: float, trace_dir: pathlib.Path, chunks, out: dict):
    """Profile TRACE_SECONDS of the window, TRACE_LEAD_S after it opens."""
    import jax
    await asyncio.sleep(max(0.0, t_open + TRACE_LEAD_S - time.perf_counter()))
    trace_dir.mkdir(parents=True, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # spans and device ops, no Python calls
    jax.profiler.start_trace(str(trace_dir), profiler_options=options)
    out["t0"] = time.perf_counter()
    n0 = len(chunks.samples)
    await asyncio.sleep(TRACE_SECONDS)
    out["t1"] = time.perf_counter()
    out["chunks"] = [int(c) for c in chunks.samples[n0:]]
    jax.profiler.stop_trace()


@dataclasses.dataclass
class TraceContext:
    """What a per-layer metric reader may read."""

    reduced: trace_reduce.Reduced
    window: loadgen.Window
    t0: float                    # capture start, perf_counter
    t1: float                    # capture end
    tracer_events: list[dict]    # the scheduler's events, "t" in perf_counter
    captured_chunks: list[int]   # prompt-chunk lengths dispatched in capture
    kernels: dict                # program -> [(kernel, avals, grid, times)]
    decode_lane_bytes: float     # lane_bytes(cell): per decode step
    prefill_lane_bytes: dict     # lane_bytes(cell): chunk length -> bytes
    peaks: dict                  # this device kind's peaks
    root: pathlib.Path

    @property
    def capture_s(self) -> float:
        return self.t1 - self.t0

    def cost(self, kernel: str):
        return _load_module(self.root / "bench" / "costs" / f"{kernel}.py")

    def least_time(self, kernel: str, avals) -> float:
        """Seconds one call needs at the chip's peaks."""
        c = self.cost(kernel).cost(avals)
        return max(c["ops"] / self.peaks[c["peak"]],
                   c["bytes"] / self.peaks["hbm_bytes_per_s"])

    def kernel_time(self, kernel: str, program: str,
                    calls: set[int]) -> tuple[int, float]:
        """(executions, device seconds) of ``kernel`` in the executions of
        ``program`` that the capture holds.  Each has to make one of the
        numbers of ``calls`` that its jaxpr makes; only the first and the
        last, which the capture may cut, may differ, and are left out.  Any
        other difference means that the trace and the jaxpr count
        different calls: an error, not a reading."""
        per = self.reduced.kernel_calls(kernel, program)
        keep = [(n, t) for n, t in per if n in calls]
        odd = [i for i, (n, _) in enumerate(per) if n not in calls]
        if any(0 < i < len(per) - 1 for i in odd):
            raise ValueError(
                f"{kernel} in {program}: calls per execution "
                f"{sorted({per[i][0] for i in odd})} in the trace, "
                f"{sorted(calls)} in the jaxpr")
        return len(keep), sum(t for _, t in keep)

    def counter_values(self, name: str, a: float, b: float) -> list[float]:
        return [e["args"]["value"] for e in self.tracer_events
                if e["ph"] == "C" and e["name"] == name and a <= e["t"] < b]

    def spans(self, name: str) -> list[tuple[float, float]]:
        """(begin, end) of every closed span ``name``, on any track."""
        open_, out = {}, []
        for e in self.tracer_events:
            if e["name"] != name:
                continue
            if e["ph"] == "B":
                open_[e["tid"]] = e["t"]
            elif e["ph"] == "E" and e["tid"] in open_:
                out.append((open_.pop(e["tid"]), e["t"]))
        return out


def _trace_context(cell, sched, win, capture, root, trace_dir, tracer_clock):
    import jax
    files = sorted(glob.glob(str(trace_dir / "plugins" / "profile" / "*"
                                 / "*.xplane.pb")), key=os.path.getmtime)
    reduced = trace_reduce.reduce_file(files[-1])
    t0 = tracer_clock.t0
    events = [dict(e, t=t0 + e["ts"] * 1e-6) for e in sched.obs.tracer.events
              if "ts" in e]
    peaks = json.loads((root / "bench" / "peaks.json").read_text())
    kind = jax.devices()[0].device_kind
    if kind not in peaks["devices"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    decode_bytes, prefill_bytes = lane_bytes(cell)
    return TraceContext(
        reduced=reduced, window=win, t0=capture["t0"],
        t1=capture["t1"], tracer_events=events,
        captured_chunks=capture["chunks"],
        kernels=kernel_inventory(sched, cell),
        decode_lane_bytes=decode_bytes, prefill_lane_bytes=prefill_bytes,
        peaks=peaks["devices"][kind], root=root)


def lane_bytes(cell: Cell) -> tuple[float, dict[int, float]]:
    """The bytes of one busy lane's own state that a step moves, read plus
    written, as the cell's reference counts them: per decode step, the mean
    over the steps of the traffic's cycle; per prompt chunk, by its length,
    the mean over the cycle's chunks of that length."""
    ref, traffic = cell.ref, cell.traffic
    shape = ref.shape_of(cell.config)
    steps = [ref.lane_bytes(shape, q, 1) for q in traffic.decode_positions()]
    chunks: dict[int, list[int]] = {}
    for start, c in traffic.prefill_chunks(
            cell.config["scheduler"]["chunk_size"]):
        chunks.setdefault(c, []).append(ref.lane_bytes(shape, start, c))
    return (sum(steps) / len(steps),
            {c: sum(v) / len(v) for c, v in chunks.items()})


def kernel_inventory(sched, cell) -> dict:
    """The Pallas calls of each timed program, from its jaxpr: for the pool
    step and each chunk length the mix produces, a list of
    (kernel, operand avals, grid, calls per execution)."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm

    eng = sched.engine
    clen = cell.traffic.cache_len
    out = {}
    store = sched.pool.get_store(clen)
    jp = jax.make_jaxpr(sched._pool_step)(eng.params, sched._tokens[clen],
                                          store, sched._keys[clen])
    out["pool_step"] = _pallas_calls(jp.jaxpr)
    chunk = cell.config["scheduler"]["chunk_size"]
    sizes = {c for plen in cell.traffic.prompt_lengths()
             for c in loadgen.chunk_schedule(plen, chunk)}
    cache = jax.eval_shape(lambda: lm.make_decode_cache(
        eng.cfg, 1, clen, sched.pool.dtype, start_pos=0))
    for c in sorted(sizes):
        jp = jax.make_jaxpr(eng._prefill_chunk)(
            eng.params, {"tokens": jax.ShapeDtypeStruct((1, c), jnp.int32)},
            cache)
        out[f"prefill_chunk[{c}]"] = _pallas_calls(jp.jaxpr)
    return out


def _pallas_calls(jaxpr, times: int = 1) -> list:
    out = []
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            di = e.params["jaxpr"].debug_info
            src = str(getattr(di, "func_src_info", "") or "")
            kernel = pathlib.Path(src.split(" at ")[-1].split(":")[0]).stem
            out.append((kernel, [tuple(v.aval.shape) + (str(v.aval.dtype),)
                                 for v in e.invars],
                        tuple(e.params["grid_mapping"].grid), times))
            continue
        mult = e.params.get("length", 1) if e.primitive.name == "scan" else 1
        for v in e.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                out += _pallas_calls(sub, times * mult)
    return out


# ---------------------------------------------------------------------------
# The check
# ---------------------------------------------------------------------------


def sample_requests(finished, seed: int):
    """The longest finished request, then others in a seeded order until
    SAMPLE_TOKENS served tokens or SAMPLE_MAX_REQUESTS requests."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r.tokens), -r.uid))
    rest = [r for r in finished if r is not longest]
    order = np.random.default_rng((seed, 11)).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX_REQUESTS:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


def score(cell: Cell, seed: int, finished) -> tuple[dict, dict]:
    """({name: (value, limit)} of the numbers ``correct`` compares, and
    every reading of the gaps, compared or not).

    Compared are ``served_tokens_short`` and those of ``widest_gap`` and
    ``mean_gap`` that the cell's limits file names (a number is compared
    only where the control reads three times a sound run or more)."""
    import jax

    ref = cell.ref
    picked = sample_requests(finished, seed)
    served = sum(len(r.tokens) for r in picked)
    lim = cell.limits
    readings = {"requests": len(picked), "served_tokens": served,
                "widest_gap": None, "mean_gap": None}
    if picked:
        gaps = ref.served_gaps(seed, ref.shape_of(cell.config),
                               [(r.prompt, r.tokens) for r in picked],
                               cell.config["scheduler"]["chunk_size"])
        flat = np.concatenate(gaps)
        readings.update(
            widest_gap=float(flat.max()),
            mean_gap=float(flat.mean()),
            first_token_gap=float(max(g[0] for g in gaps)),
            p99_gap=float(np.percentile(flat, 99)),
            mismatch_share=float(np.mean(flat > 0)))
    jax.clear_caches()
    check = {"served_tokens_short": (max(0, lim["min_served_tokens"] - served),
                                     0)}
    for name in ("widest_gap", "mean_gap"):
        if name in lim:
            check[name] = (readings[name], lim[name])
    return check, readings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=sorted(CONTROLS),
                    help="run the low-precision control instead of the "
                         "configuration (calibration only)")
    args = ap.parse_args(argv)
    try:
        res = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  control=args.control)
    except NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    for k, v in res["check"].items():
        print(f"{k} {v['value']!r} limit {v['limit']!r}", file=sys.stderr)
    print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
