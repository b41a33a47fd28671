"""End-to-end serving CLI — thin shim over `repro.serving.InferenceEngine`.

Implements the paper's edge serving flow at any scale: quantize the model
(SmoothQuant + MXINT4, Section III), prefill the prompt in the W8A8 MMM
dataflow, then autoregressively decode in the W4A8 MVM dataflow — the decode
loop fused into one jitted `lax.while_loop` by the engine.  Batched requests;
LISO/SILO scenario presets matching the paper's evaluation.

All wiring lives in `repro.serving`; this module only parses flags and keeps
the historical `generate(...)` entry point for existing callers.

Usage (CPU, reduced config):
    PYTHONPATH=src python -m repro.launch.serve --arch retnet-1.3b --reduced \
        --scenario SILO --scale 0.1 --batch 2

Continuous-batching mode (`--requests N`) drives the `RequestScheduler`
instead: N staggered requests with mixed prompt lengths are chunk-admitted
(`--chunk-size`) into a paged cache pool while resident lanes decode — the
paper's sequencer behavior, with per-step stats printed at the end.
`--host-spill` (optionally with `--oversubscribe R`) turns on the pool's
host-memory tier: a late high-priority burst preempts resident lanes to CPU
DRAM, and they resume bit-exactly once device lanes free up.
`--prefix-cache` turns on shared-prefix reuse and reshapes the stream into
the repeated-system-prompt workload it targets: every request opens with one
shared prefix, later admissions adopt it from the page index and prefill
only their unique tail (hit stats printed at the end).

`--frontend` switches to the open-loop asyncio front end: seeded Poisson or
bursty arrivals (`--rate`, `--arrival`) submitted through SLO-aware
admission (`--ttft-slo`), goodput and shed rate reported at the end.
`--virtual-clock` runs it on deterministic virtual time — wall-clock-free —
and asserts the CI smoke contract (`make smoke-frontend`): nonzero goodput,
zero unexplained sheds.

`--trace FILE` records the full request lifecycle (submit → admit → prefill
chunks → first token → decode → preempt/resume → finish) through `repro.obs`
and writes Chrome-trace-event JSON loadable in Perfetto; `--metrics FILE`
dumps the run's counter/gauge/percentile-histogram snapshot.  `make
trace-demo` produces both from an oversubscribed scheduler run.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import edge_model
from repro.core.hsa import HSAEngine
from repro.models.config import ModelConfig
from repro.obs import Observability, Tracer
from repro.runtime.compile_cache import enable_compile_cache
from repro.serving import (EngineSpec, GenerationConfig, InferenceEngine,
                           Request, RequestScheduler, SamplingParams,
                           SpeculativeConfig)


def generate(cfg: ModelConfig, params, engine: HSAEngine, prompts: jax.Array,
             n_out: int, greedy: bool = True, key=None):
    """Legacy entry point: prefill + fused decode loop.

    prompts [B, S_in] -> (tokens [B, n_out], t_prefill_s, t_decode_s).
    Deprecated shim — construct an `InferenceEngine` directly instead.
    """
    eng = InferenceEngine(cfg, params, EngineSpec(), hsa=engine)
    sampling = SamplingParams() if greedy else SamplingParams(temperature=1.0)
    res = eng.generate(prompts,
                       GenerationConfig(max_new_tokens=n_out,
                                        sampling=sampling),
                       key=key)
    return res.tokens, res.prefill_s, res.decode_s


def _run_scheduler_demo(engine: InferenceEngine, args,
                        n_in: int, n_out: int) -> None:
    """Sequencer demo: mixed-length prompts chunk-admitted into a paged pool
    (a small + a large cache class) while resident lanes decode."""
    import time

    cfg = engine.cfg
    spec = (SpeculativeConfig(k=args.draft_k) if args.speculative else None)
    gen = GenerationConfig(
        max_new_tokens=n_out,
        sampling=SamplingParams(temperature=args.temperature,
                                top_k=args.top_k, top_p=args.top_p),
        speculative=spec)
    rng = np.random.default_rng(0)
    if args.prefix_cache:
        # The repeated-system-prompt workload prefix reuse targets: uniform
        # full-length prompts, each opening with the same shared prefix long
        # enough to clear the one-page adoption floor.
        lengths = [n_in] * args.requests
        shared_len = min(n_in - 1, max(16, int(n_in * 0.75)))
        shared = jax.random.randint(jax.random.key(5), (shared_len,), 1,
                                    cfg.vocab_size, dtype=jnp.int32).tolist()
    else:
        lengths = [max(2, int(n_in * f)) for f in
                   rng.choice([0.25, 0.5, 1.0], size=args.requests)]
    extra = spec.k if spec else 0        # verify blocks overrun by k slots
    small = max(2, int(n_in * 0.5)) + n_out + extra
    large = n_in + n_out + extra
    # A 1-slot pool cannot split into two classes (the max(1, ...) guards
    # would silently double it, under-delivering --oversubscribe's ratio).
    classes = ([(args.slots, large)] if small >= large or args.slots < 2 else
               [(args.slots // 2, small),
                (args.slots - args.slots // 2, large)])
    sched = RequestScheduler(engine, classes=classes, gen=gen,
                             chunk_size=args.chunk_size,
                             host_spill=args.host_spill,
                             prefix_cache=args.prefix_cache,
                             key=jax.random.key(2), obs=engine.obs)

    def make_request(uid: int, s: int) -> Request:
        prompt = jax.random.randint(jax.random.fold_in(jax.random.key(1), uid),
                                    (s,), 1, cfg.vocab_size, dtype=jnp.int32)
        tokens = prompt.tolist()
        if args.prefix_cache:
            tokens = shared + tokens[len(shared):]
        return Request(uid=uid, prompt=tokens)

    print(f"[serve] scheduler: {args.requests} requests, prompt lengths "
          f"{sorted(set(lengths))}, classes {classes}, "
          f"chunk={args.chunk_size}"
          + (", host-spill preemption on" if args.host_spill else "")
          + (f", prefix-cache on ({len(shared)}-token shared prefix)"
             if args.prefix_cache else ""))
    t0 = time.perf_counter()
    if args.host_spill and args.requests > 1:
        # Oversubscription demo: fill the pool with default-priority
        # residents first, then a late high-priority burst that preempts
        # them into the host tier (they resume once lanes free up).
        n_burst = max(1, args.requests // 3)
        for uid, s in list(enumerate(lengths))[:-n_burst]:
            sched.submit(make_request(uid, s))
        while sched.stats["admitted"] < min(args.requests - n_burst,
                                            sched.pool.n_slots):
            sched.step()
        for uid, s in list(enumerate(lengths))[-n_burst:]:
            sched.submit(make_request(uid, s), priority=1)
    else:
        for uid, s in enumerate(lengths):
            sched.submit(make_request(uid, s))
    results = sched.run()
    dt = time.perf_counter() - t0
    total = sum(len(r.tokens) for r in results.values()) + sum(lengths)
    print(f"[serve] {sched.stats['steps']} cycles, "
          f"{sched.stats['prefill_chunks']} prefill chunks, "
          f"{engine.prefill_compiles} prefill compiles, "
          f"{sched.stats['decode_stall_steps']} decode-stall steps")
    if args.host_spill:
        ss = sched.pool.spill_stats
        print(f"[serve] host tier: {sched.stats['preempted']} preempted / "
              f"{sched.stats['resumed']} resumed, {ss['spills']} spills "
              f"({ss['bytes_to_host']} B to host), {ss['fetches']} fetches "
              f"({ss['bytes_to_device']} B back)")
    if args.prefix_cache:
        px = sched.pool.prefix
        ps = px.stats
        print(f"[serve] prefix cache [{px.mode}]: {ps['prefix_hits']}/"
              f"{ps['prefix_lookups']} admissions adopted a cached prefix, "
              f"{ps['prefix_hit_tokens']} prefill tokens skipped, "
              f"{px.n_pages} pages resident "
              f"({ps['cow_copies']} COW copies)")
    if spec:
        for uid in sorted(results):
            r = results[uid]
            print(f"[serve]   req {uid}: {len(r.tokens)} tokens in "
                  f"{r.verify_steps} verify steps "
                  f"({r.tokens_per_step:.2f} tokens/step, "
                  f"{r.accepted_drafts} drafts accepted)")
        vs = max(1, sched.stats["verify_steps"])
        print(f"[serve] speculative: {sched.stats['accepted_drafts']} drafts "
              f"accepted over {vs} verify steps "
              f"({1 + sched.stats['accepted_drafts'] / vs:.2f} tokens/step)")
    print(f"[serve] tokens/s (paper convention, prompt+output): "
          f"{total / dt:.2f}")


def _run_frontend_demo(engine: InferenceEngine, args,
                       n_in: int, n_out: int) -> None:
    """Open-loop front-end demo: seeded arrivals (`--rate`, `--arrival`)
    submitted through the asyncio `ServingFrontend` with SLO-aware admission
    (`--ttft-slo`), reporting goodput / shed rate.  With `--virtual-clock`
    the run is wall-clock-free and deterministic, and doubles as the CI
    smoke contract: nonzero goodput, zero unexplained sheds."""
    from repro.serving import (BurstyArrivals, FrontendConfig, LengthMix,
                               MonotonicClock, PoissonArrivals,
                               ServingFrontend, VirtualClock, Workload,
                               run_open_loop)

    cfg = engine.cfg
    n_req = args.requests if args.requests > 0 else 8
    clock = VirtualClock() if args.virtual_clock else MonotonicClock()
    gen = GenerationConfig(
        max_new_tokens=n_out,
        sampling=SamplingParams(temperature=args.temperature,
                                top_k=args.top_k, top_p=args.top_p))
    mix = LengthMix(prompt_min=max(2, n_in // 4), prompt_max=n_in,
                    new_min=max(2, n_out // 2), new_max=n_out)
    sched = RequestScheduler(engine, classes=[(args.slots, n_in + n_out)],
                             gen=gen, chunk_size=args.chunk_size,
                             prefix_cache=args.prefix_cache,
                             key=jax.random.key(2), obs=engine.obs,
                             clock=clock.now)
    frontend = ServingFrontend(
        sched, config=FrontendConfig(ttft_slo_s=args.ttft_slo, journal=True),
        clock=clock)
    arrivals = (BurstyArrivals(args.rate) if args.arrival == "bursty"
                else PoissonArrivals(args.rate))
    workload = Workload(arrivals=arrivals, lengths=mix, n_requests=n_req,
                        vocab_size=cfg.vocab_size, seed=4)

    async def drive():
        async with frontend:
            return await run_open_loop(frontend, workload)

    print(f"[serve] frontend: {n_req} open-loop requests, {args.arrival} "
          f"arrivals at {args.rate:.1f} req/s, TTFT SLO {args.ttft_slo:.2f}s, "
          f"{'virtual' if args.virtual_clock else 'monotonic'} clock")
    report = clock.run(drive())
    print(f"[serve] elapsed {report.elapsed_s:.3f}s"
          f"{' (virtual)' if args.virtual_clock else ''}: "
          f"{report.completed}/{report.n_requests} completed, "
          f"{report.met_slo} met SLO -> goodput {report.goodput_rps:.2f} "
          f"req/s, shed rate {report.shed_rate:.2f}")
    ttft = report.to_dict().get("ttft")
    if ttft:
        print(f"[serve] TTFT p50/p95/p99: {ttft['p50']:.4f}/"
              f"{ttft['p95']:.4f}/{ttft['p99']:.4f} s")
    if args.virtual_clock:
        # The smoke contract `make smoke-frontend` relies on.
        if report.goodput_rps <= 0:
            raise SystemExit("[serve] frontend smoke FAILED: zero goodput")
        if report.sheds_unexplained:
            raise SystemExit(f"[serve] frontend smoke FAILED: "
                             f"{report.sheds_unexplained} unexplained sheds")
        print(f"[serve] frontend smoke OK: goodput {report.goodput_rps:.2f} "
              f"req/s, 0 unexplained sheds, {len(frontend.journal)} journal "
              f"events")


def _export_obs(obs: Observability, args) -> None:
    """Write the run's trace / metrics artifacts, when asked for."""
    if args.trace:
        obs.tracer.export(args.trace)
        print(f"[serve] trace: {len(obs.tracer.events)} events -> "
              f"{args.trace} (open in Perfetto / chrome://tracing)")
    if args.metrics:
        import json
        with open(args.metrics, "w") as f:
            json.dump(obs.metrics.snapshot(), f, indent=2)
            f.write("\n")
        print(f"[serve] metrics snapshot -> {args.metrics}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--scenario", choices=["LISO", "SILO"], default="SILO")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="scale LISO/SILO token counts (CPU-friendly)")
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 samples")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--no-quant", action="store_true",
                    help="serve fp master weights (ablation)")
    ap.add_argument("--unfused-norm", action="store_true",
                    help="disable the Eq.(4) fused RMSNorm (ablation)")
    ap.add_argument("--requests", type=int, default=0,
                    help="> 0: continuous-batching scheduler demo with this "
                         "many mixed-length requests")
    ap.add_argument("--slots", type=int, default=4,
                    help="scheduler mode: decode lanes in the cache pool")
    ap.add_argument("--chunk-size", type=int, default=32,
                    help="scheduler mode: prefill chunk size (tokens/cycle)")
    ap.add_argument("--speculative", action="store_true",
                    help="scheduler mode: multi-token speculative decode "
                         "(ngram drafter) — prints per-request acceptance")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="speculative mode: drafted tokens per verify step")
    ap.add_argument("--host-spill", action="store_true",
                    help="scheduler mode: enable the host-memory spill tier "
                         "— a late high-priority burst preempts resident "
                         "lanes to CPU DRAM instead of queueing behind them")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="scheduler mode: shared-prefix reuse — every "
                         "request opens with one shared system prompt; "
                         "later admissions adopt its cached pages and "
                         "prefill only their unique tail (hit stats "
                         "printed at the end)")
    ap.add_argument("--frontend", action="store_true",
                    help="open-loop asyncio front-end demo: seeded arrivals "
                         "(--rate/--arrival) through SLO-aware admission "
                         "(--ttft-slo), goodput + shed rate printed at the "
                         "end; --requests sets the request count (default 8)")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="frontend mode: offered load, requests/second")
    ap.add_argument("--arrival", choices=["poisson", "bursty"],
                    default="poisson",
                    help="frontend mode: arrival process (bursty = 2-state "
                         "Markov-modulated Poisson at the same mean rate)")
    ap.add_argument("--ttft-slo", type=float, default=2.0,
                    help="frontend mode: TTFT SLO target in seconds — the "
                         "admission policy sheds while the windowed p99 "
                         "breaches it")
    ap.add_argument("--virtual-clock", action="store_true",
                    help="frontend mode: run on deterministic virtual time "
                         "(wall-clock-free; the CI smoke contract)")
    ap.add_argument("--oversubscribe", type=float, default=0.0,
                    help="scheduler mode: request-to-lane ratio — shrinks "
                         "the pool to ~requests/R device lanes so demand "
                         "exceeds device capacity (pair with --host-spill)")
    ap.add_argument("--mesh", default=None,
                    help="serve sharded on a device mesh: 'dp,tp' (e.g. 2,2 "
                         "— axes data,model) or a named mesh from "
                         "launch.mesh; needs dp*tp devices (CPU smoke: "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    ap.add_argument("--trace", metavar="FILE", default=None,
                    help="record the request-lifecycle trace and write it to "
                         "FILE as Chrome-trace-event JSON (load in Perfetto "
                         "or chrome://tracing; `make trace-demo` shows one)")
    ap.add_argument("--metrics", metavar="FILE", default=None,
                    help="write the run's metrics-registry snapshot "
                         "(counters, gauges, p50/p95/p99 histograms) to "
                         "FILE as JSON")
    args = ap.parse_args()
    enable_compile_cache()
    if args.oversubscribe:
        if args.oversubscribe <= 1.0:
            ap.error("--oversubscribe is a request-to-lane ratio and must "
                     "be > 1.0 (omit it to disable)")
        if args.requests > 0:
            args.slots = max(1, round(args.requests / args.oversubscribe))

    scen = edge_model.LISO if args.scenario == "LISO" else edge_model.SILO
    n_in = max(2, int(scen.tokens_in * args.scale))
    n_out = max(2, int(scen.tokens_out * args.scale))

    spec = EngineSpec(quantize=not args.no_quant, reduced=args.reduced,
                      fuse_rmsnorm=not args.unfused_norm)
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(args.mesh)
        axes = dict(zip(mesh.axis_names, mesh.devices.shape))
        print(f"[serve] mesh: {axes} over {mesh.size} "
              f"{mesh.devices.flat[0].platform} devices "
              f"(params + cache sharded per ServeCell)")
    # One bundle across the engine + scheduler + pool: the trace interleaves
    # engine phases with per-request lifecycle tracks, and the metrics
    # snapshot carries every component's counters under one registry.
    obs = Observability()
    if args.trace:
        obs.tracer = Tracer()
    engine = InferenceEngine.from_config(args.arch, spec, mesh=mesh, obs=obs)
    cfg = engine.cfg
    if args.frontend:
        _run_frontend_demo(engine, args, n_in, n_out)
        return _export_obs(obs, args)
    if args.requests > 0:
        _run_scheduler_demo(engine, args, n_in, n_out)
        return _export_obs(obs, args)
    print(f"[serve] {cfg.name} scenario={scen.name} in/out={n_in}/{n_out} "
          f"batch={args.batch}")
    if not args.no_quant:
        print("[serve] deployed: W8A8 prefill / MXINT4 (4.25b) decode weights")

    prompts = jax.random.randint(jax.random.key(1), (args.batch, n_in), 1,
                                 cfg.vocab_size, dtype=jnp.int32)
    gen = GenerationConfig(
        max_new_tokens=n_out,
        sampling=SamplingParams(temperature=args.temperature,
                                top_k=args.top_k, top_p=args.top_p),
        speculative=(SpeculativeConfig(k=args.draft_k)
                     if args.speculative else None))
    res = engine.generate(prompts, gen, key=jax.random.key(2))
    if args.speculative:
        print(f"[serve] speculative: {res.verify_steps} verify steps, "
              f"{res.accepted_drafts}/{res.drafted} drafts accepted "
              f"({res.tokens_per_step:.2f} tokens/step)")
    total = n_in + n_out
    t_p, t_d = res.prefill_s, res.decode_s
    print(f"[serve] prefill {t_p*1e3:.0f} ms, decode {t_d*1e3:.0f} ms "
          f"({t_d/n_out*1e3:.1f} ms/token)")
    print(f"[serve] {scen.name} tokens/s (paper convention, prompt+output): "
          f"{args.batch * total / (t_p + t_d):.2f}")
    print(f"[serve] sample output tokens: {np.asarray(res.tokens[0, :16])}")
    _export_obs(obs, args)


if __name__ == "__main__":
    main()
