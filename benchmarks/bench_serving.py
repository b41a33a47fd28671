"""Serving-path benchmark: the chunked/bucketed admission path end to end,
plus the speculative multi-token decode path.

Drives the `RequestScheduler` (paged pool + chunk-granular admissions) over a
mixed LISO/SILO-ish request stream on the reduced RetNet config, then the
speculative draft/verify loop on a long-output prompt whose greedy
continuation saturates into repetition (the ngram drafter's best case — and
the regime the paper's EMA argument cares about: every accepted draft is one
fewer weight-stream read).  Each run *appends* to ``BENCH_serving.json`` so
successive PRs accumulate a perf trajectory instead of overwriting it:

    tokens_per_s          sustained prompt+output tokens / wall second
    prefill_compiles      distinct prefill shapes dispatched (ladder size —
                          the old admission path paid one per prompt length)
    decode_stall_steps    sequencer cycles that did admission work with no
                          resident lane emitting (ramp-up only, ideally)
    steps / prefill_chunks / emitted   raw sequencer counters
    speculative.tokens_per_step        committed tokens per verify step
                          (> 2.0 means > 1 accepted draft per weight read)
    speculative.acceptance_rate        accepted / drafted
    oversubscribed.*      the host-spill leg: requests > device lanes, a
                          high-priority burst preempting residents to host
                          memory (spills/fetches/bytes moved each way)
    decode_roofline.*     per-leg analytic decode-step roofline (modeled
                          bytes/token, step time, memory/compute bound) and
                          achieved_roofline_fraction = modeled / measured
                          decode wall (~0 on CPU CI — the model's constants
                          are the TPU chip — but trajectory-comparable)
    quantized_decode.*    the quantized-KV residency leg: the same greedy
                          generate with the cache fp32 vs int8_tok vs
                          mxint4_blk, with modeled + resident cache-byte
                          reduction ratios (the paper's EMA claim: >= 2x)
    sharded.*             the multi-chip leg: the same generate on a 2x2
                          (data, model) mesh of virtual host devices —
                          device count, axis shape, and per-device vs
                          global cache bytes per record (subprocess: the
                          XLA device-count flag must precede jax init)
    prefix_reuse.*        the shared-prefix leg: the same request stream at
                          0% / 50% / 100% repeated-system-prompt fractions
                          through a `prefix_cache=True` scheduler — per
                          fraction the prefix hit rate, prefill tokens
                          skipped (and the resulting prefill-token
                          reduction), prefill chunks dispatched, and p50
                          TTFT; the 100% leg also replays cold
                          (prefix_cache=False) to record the TTFT delta and
                          assert greedy outputs stay token-identical
    goodput_under_load.*  the open-loop front-end leg: seeded Poisson
                          arrivals through `ServingFrontend`'s SLO-aware
                          admission at >= 3 offered rates (multiples of a
                          calibrated closed-loop service rate, so the sweep
                          spans under- to over-load on any host) — per rate
                          the goodput (requests meeting the TTFT SLO per
                          second), shed rate, unexplained-shed count, and
                          the standard `latency` block; plus a greedy
                          token-identity check of the front end vs a direct
                          `RequestScheduler.run()` on the same request set
    latency.*             per-leg SLO block from the `repro.obs` registry:
                          p50/p95/p99 TTFT and inter-token latency, plus
                          queue-depth / cache-occupancy gauge summaries on
                          the scheduler-driven legs (every leg carries one)
    git_rev               short rev of the checkout, so trajectory points
                          correlate with PRs

    PYTHONPATH=src python -m benchmarks.bench_serving [out.json]
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp

from benchmarks.roofline import decode_step_model
from repro.obs import Observability
from repro.runtime.compile_cache import enable_compile_cache
from repro.serving import (EngineSpec, FrontendConfig, GenerationConfig,
                           InferenceEngine, LengthMix, MonotonicClock,
                           PoissonArrivals, Request, RequestScheduler,
                           ServingFrontend, SpeculativeConfig, Workload,
                           run_open_loop)

N_REQUESTS = 12
PROMPT_LENGTHS = [6, 11, 23, 37, 48, 75]     # mixed LISO/SILO-ish, 6 distinct
MAX_NEW_TOKENS = 12
CHUNK_SIZE = 16

# Oversubscribed leg: more requests than device lanes, resolved by the host
# spill tier + priority preemption instead of hard queueing.
OVER_REQUESTS = 6
OVER_LANES = 2
OVER_PROMPT = 16
OVER_NEW_TOKENS = 8


def git_rev() -> str:
    """Short git rev of the working tree, so trajectory points correlate
    with PRs; 'unknown' outside a checkout (e.g. an sdist install)."""
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10)
        return out.stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        return "unknown"

def decode_roofline(cfg, *, cache_len: int, n_tokens: int, wall_s: float,
                    batch: int = 1, cache_format: str | None = None) -> dict:
    """Modeled decode-step roofline + achieved fraction for one measured leg.

    The model (`benchmarks.roofline.decode_step_model`) prices the leg's
    *exact* config instance — the reduced CPU-scale one being benched, not
    the paper-scale arch — with MXINT4 weight streaming and the leg's cache
    residency format (None = the engine's fp32 cache).
    ``achieved_roofline_fraction`` = modeled decode wall / measured decode
    wall; ~0 on CPU CI (the model's peak/BW constants are the TPU chip) and
    meaningful on device, but its *trajectory* is comparable either way.
    """
    fmt = cache_format or "float32"
    m = decode_step_model(cfg, cache_len=cache_len, batch=batch,
                          cache_format=fmt)
    modeled_wall = m["step_s"] * (n_tokens / max(batch, 1))
    return {
        "cache_format": fmt,
        "modeled_step_s": m["step_s"],
        "modeled_bytes_per_token": round(m["bytes_per_token"], 1),
        "modeled_cache_bytes": round(m["cache_bytes"], 1),
        "bound": m["bound"],
        "achieved_roofline_fraction":
            round(modeled_wall / wall_s, 6) if wall_s > 0 else 0.0,
    }


def _round_stats(d: dict, nd: int = 6) -> dict:
    return {k: (round(v, nd) if isinstance(v, float) else v)
            for k, v in d.items()}


def latency_summary(obs: Observability, prefix: str) -> dict:
    """One leg's SLO latency block: p50/p95/p99 TTFT + inter-token latency
    (seconds, from the leg's `repro.obs` registry — ``prefix`` is ``sched``
    for scheduler-driven legs, ``engine`` for direct-generate legs) plus the
    queue-depth / occupancy gauge summaries the leg accumulated."""
    snap = obs.metrics.snapshot()
    hists, gauges = snap["histograms"], snap["gauges"]
    out = {
        "ttft_s": _round_stats(hists.get(f"{prefix}.ttft_s", {"count": 0})),
        "inter_token_s": _round_stats(
            hists.get(f"{prefix}.inter_token_s", {"count": 0})),
    }
    if gauges:
        out["occupancy"] = {name: _round_stats(g)
                            for name, g in sorted(gauges.items())}
    return out


# Speculative leg: reduced starcoder2's greedy continuation of this seed
# saturates into a repeating tail — the "long repetitive output" regime where
# prompt-lookup drafting pays (code generation / extraction analogue).
SPEC_ARCH = "starcoder2-15b"
SPEC_SEED = 9
SPEC_MAX_NEW = 96
SPEC_K = 4


def run_scheduler() -> dict:
    engine = InferenceEngine.from_config("retnet-1.3b",
                                         EngineSpec(reduced=True))
    gen = GenerationConfig(max_new_tokens=MAX_NEW_TOKENS)
    small = max(l for l in PROMPT_LENGTHS if l <= 24) + MAX_NEW_TOKENS
    large = max(PROMPT_LENGTHS) + MAX_NEW_TOKENS
    obs = Observability()
    sched = RequestScheduler(engine, classes=[(2, small), (2, large)],
                             gen=gen, chunk_size=CHUNK_SIZE,
                             key=jax.random.key(0), obs=obs)

    lengths = [PROMPT_LENGTHS[i % len(PROMPT_LENGTHS)]
               for i in range(N_REQUESTS)]
    for uid, s in enumerate(lengths):
        prompt = jax.random.randint(
            jax.random.fold_in(jax.random.key(1), uid), (s,), 1,
            engine.cfg.vocab_size, dtype=jnp.int32)
        sched.submit(Request(uid=uid, prompt=prompt.tolist()))

    t0 = time.perf_counter()
    results = sched.run()
    wall_s = time.perf_counter() - t0

    total_tokens = (sum(lengths)
                    + sum(len(r.tokens) for r in results.values()))
    return {
        "bench": "serving",
        "arch": engine.cfg.name,
        "n_requests": N_REQUESTS,
        "distinct_prompt_lengths": len(set(lengths)),
        "chunk_size": CHUNK_SIZE,
        "wall_s": round(wall_s, 3),
        "tokens_per_s": round(total_tokens / wall_s, 2),
        "prefill_compiles": engine.prefill_compiles,
        "decode_stall_steps": sched.stats["decode_stall_steps"],
        "steps": sched.stats["steps"],
        "prefill_chunks": sched.stats["prefill_chunks"],
        "emitted": sched.stats["emitted"],
        # Compiled-signature census per jit entry point (engine roots +
        # the scheduler's pool steps) — the raw numbers behind the
        # `python -m repro.analysis audit` recompile bound, kept in the
        # trajectory so a signature-count regression shows up PR-over-PR.
        "compiled_signatures": {**engine.compile_counts(),
                                **sched.compile_counts()},
        # Conservative: wall_s includes admission/prefill work, so this
        # under-reports the pure-decode fraction.
        "decode_roofline": decode_roofline(
            engine.cfg, cache_len=large, n_tokens=sched.stats["emitted"],
            wall_s=wall_s),
        "latency": latency_summary(obs, "sched"),
    }


def run_speculative() -> dict:
    engine = InferenceEngine.from_config(SPEC_ARCH, EngineSpec(reduced=True))
    gen = GenerationConfig(max_new_tokens=SPEC_MAX_NEW)
    prompt = jax.random.randint(jax.random.key(SPEC_SEED), (1, 10), 1,
                                engine.cfg.vocab_size, dtype=jnp.int32)
    spec_cfg = SpeculativeConfig(k=SPEC_K)
    # Warm both programs first: the plain while_loop and the speculative
    # loop compile separately, and on the reduced model trace+compile is a
    # large fraction of the decode walls being compared.
    engine.generate(prompt, gen)
    engine.generate(prompt, gen, speculative=spec_cfg)
    # Fresh bundle after warmup so the percentiles cover measured runs only
    # (the warm calls' walls are dominated by trace+compile).
    engine.obs = Observability()
    base = engine.generate(prompt, gen)
    spec = engine.generate(prompt, gen, speculative=spec_cfg)
    return {
        "arch": engine.cfg.name,
        "drafter": "ngram",
        "k": SPEC_K,
        "max_new_tokens": SPEC_MAX_NEW,
        "verify_steps": spec.verify_steps,
        "accepted_drafts": spec.accepted_drafts,
        "tokens_per_step": round(spec.tokens_per_step, 3),
        "acceptance_rate": round(spec.acceptance_rate, 3),
        "baseline_decode_s": round(base.decode_s, 3),
        "decode_s": round(spec.decode_s, 3),
        "decode_roofline": decode_roofline(
            engine.cfg, cache_len=10 + SPEC_MAX_NEW, n_tokens=SPEC_MAX_NEW,
            wall_s=base.decode_s),
        "latency": latency_summary(engine.obs, "engine"),
    }


def run_oversubscribed() -> dict:
    """Host-spill leg: OVER_REQUESTS requests over OVER_LANES device lanes.

    The default-priority residents fill the pool, then a high-priority burst
    preempts them into the host tier; everything drains (spilled lanes
    resume bit-exactly), and the record carries the spill/fetch/bytes-moved
    stats so the trajectory shows the host tier's traffic.
    """
    engine = InferenceEngine.from_config("retnet-1.3b",
                                         EngineSpec(reduced=True))
    gen = GenerationConfig(max_new_tokens=OVER_NEW_TOKENS)
    clen = OVER_PROMPT + OVER_NEW_TOKENS
    obs = Observability()
    sched = RequestScheduler(engine, classes=[(OVER_LANES, clen)], gen=gen,
                             chunk_size=CHUNK_SIZE, host_spill=True,
                             key=jax.random.key(0), obs=obs)

    def submit(uid, priority=0):
        prompt = jax.random.randint(
            jax.random.fold_in(jax.random.key(3), uid), (OVER_PROMPT,), 1,
            engine.cfg.vocab_size, dtype=jnp.int32)
        sched.submit(Request(uid=uid, prompt=prompt.tolist()),
                     priority=priority)

    t0 = time.perf_counter()
    for uid in range(OVER_LANES):
        submit(uid)
    while sched.stats["admitted"] < OVER_LANES:    # residents in place
        sched.step()
    for uid in range(OVER_LANES, OVER_REQUESTS):   # the high-priority burst
        submit(uid, priority=1)
    results = sched.run()
    wall_s = time.perf_counter() - t0

    assert len(results) == OVER_REQUESTS
    assert all(len(r.tokens) == OVER_NEW_TOKENS for r in results.values())
    total = OVER_REQUESTS * (OVER_PROMPT + OVER_NEW_TOKENS)
    return {
        "n_requests": OVER_REQUESTS,
        "device_lanes": OVER_LANES,
        "wall_s": round(wall_s, 3),
        "tokens_per_s": round(total / wall_s, 2),
        "preempted": sched.stats["preempted"],
        "resumed": sched.stats["resumed"],
        **sched.pool.spill_stats,
        "decode_roofline": decode_roofline(
            engine.cfg, cache_len=clen,
            n_tokens=OVER_REQUESTS * OVER_NEW_TOKENS, wall_s=wall_s),
        "latency": latency_summary(obs, "sched"),
    }


# Quantized-KV decode leg: the same greedy generate on a GQA arch with the
# decode-residency cache fp32 vs int8_tok vs MXINT4 — the tentpole's EMA
# claim, measured.  Modeled cache bytes/token must drop >= 2x quantized vs
# fp (the record carries the ratio so the trajectory proves it PR-over-PR).
QUANT_ARCH = "qwen3-8b"
QUANT_PROMPT = 12
QUANT_NEW = 16


def run_quantized_decode() -> dict:
    engine = InferenceEngine.from_config(QUANT_ARCH, EngineSpec(reduced=True))
    prompt = jax.random.randint(jax.random.key(7), (1, QUANT_PROMPT), 1,
                                engine.cfg.vocab_size, dtype=jnp.int32)
    clen = QUANT_PROMPT + QUANT_NEW
    legs: dict[str, dict] = {}
    base = None
    for fmt in (None, "int8_tok", "mxint4_blk"):
        gen = GenerationConfig(max_new_tokens=QUANT_NEW, cache_format=fmt)
        engine.generate(prompt, gen)                 # warm/compile
        engine.obs = Observability()                 # per-format latency leg
        res = engine.generate(prompt, gen)
        leg = decode_roofline(engine.cfg, cache_len=clen, n_tokens=QUANT_NEW,
                              wall_s=res.decode_s, cache_format=fmt)
        leg["latency"] = latency_summary(engine.obs, "engine")
        leg["decode_s"] = round(res.decode_s, 3)
        leg["resident_cache_nbytes"] = engine.cache_nbytes(
            clen, dtype=fmt or jnp.float32)
        base = base or leg
        leg["cache_bytes_reduction_x"] = round(
            base["modeled_cache_bytes"] / leg["modeled_cache_bytes"], 2)
        leg["resident_reduction_x"] = round(
            base["resident_cache_nbytes"] / leg["resident_cache_nbytes"], 2)
        legs[fmt or "fp32"] = leg
    return legs


# Shared-prefix leg: a repeated "system prompt" workload on the paged GQA
# arch.  The shared prefix is long relative to the per-request suffix (the
# system-prompt regime the radix tier targets): at PREFIX_SHARED tokens the
# prefill chunks an adoption skips cost far more than the page gather-copy
# that replaces them, so the TTFT trend is visible even on reduced CPU runs.
# PREFIX_SHARED is chunk-aligned so the hit length is format-independent.
PREFIX_ARCH = "qwen3-8b"
PREFIX_SHARED = 96
PREFIX_SUFFIX = 8
PREFIX_REQUESTS = 8
PREFIX_NEW_TOKENS = 8
PREFIX_CHUNK = 8
PREFIX_REPS = 3


def run_prefix_reuse() -> dict:
    """Shared-prefix reuse leg: sweep the fraction of requests that repeat
    one system prompt and record how hit rate buys back prefill work.

    Every fraction runs the same scheduler shape with ``prefix_cache=True``;
    the 100%-shared point additionally replays the identical stream cold
    (``prefix_cache=False``) to record ``ttft_p50_delta_s`` (warm − cold,
    negative is a win) and assert the greedy outputs are token-identical —
    adoption must be a pure prefill shortcut, never a sampling change.
    """
    engine = InferenceEngine.from_config(PREFIX_ARCH, EngineSpec(reduced=True))
    gen = GenerationConfig(max_new_tokens=PREFIX_NEW_TOKENS)
    clen = PREFIX_SHARED + PREFIX_SUFFIX + PREFIX_NEW_TOKENS

    def prompts_for(frac: float, base: int) -> list[list[int]]:
        shared = jax.random.randint(jax.random.key(base), (PREFIX_SHARED,),
                                    1, engine.cfg.vocab_size,
                                    dtype=jnp.int32).tolist()
        n_shared = round(PREFIX_REQUESTS * frac)
        out = []
        for uid in range(PREFIX_REQUESTS):
            head = shared if uid < n_shared else jax.random.randint(
                jax.random.fold_in(jax.random.key(base + 2), uid),
                (PREFIX_SHARED,), 1, engine.cfg.vocab_size,
                dtype=jnp.int32).tolist()
            tail = jax.random.randint(
                jax.random.fold_in(jax.random.key(base + 6), uid),
                (PREFIX_SUFFIX,), 1, engine.cfg.vocab_size,
                dtype=jnp.int32).tolist()
            out.append(head + tail)
        return out

    def drain(frac: float, prefix_cache: bool):
        """One measured leg.  The scheduler's decode dispatch is jitted per
        instance, so a warmup stream over a *disjoint* shared prefix (base
        seed 41: same lengths and adoption shapes, zero radix overlap) pays
        every trace/compile first; the registry then resets, and PREFIX_REPS
        back-to-back streams — each repeating its *own* fresh system prompt —
        accumulate clean counters and latency samples on the warm instance.
        """
        obs = Observability()
        sched = RequestScheduler(engine, classes=[(2, clen)], gen=gen,
                                 chunk_size=PREFIX_CHUNK,
                                 key=jax.random.key(0),
                                 prefix_cache=prefix_cache, obs=obs)
        for uid, p in enumerate(prompts_for(1.0, base=41)):
            sched.submit(Request(uid=1000 + uid, prompt=p))
        sched.run()
        obs.metrics.reset()
        results: dict[int, object] = {}
        wall_s = 0.0
        for rep in range(PREFIX_REPS):
            prompts = prompts_for(frac, base=11 + 13 * rep)
            for i, p in enumerate(prompts):
                sched.submit(Request(uid=100 * rep + i, prompt=p))
            t0 = time.perf_counter()
            results.update(sched.run())
            wall_s += time.perf_counter() - t0
        return results, sched, obs, wall_s

    total_prompt = PREFIX_REPS * PREFIX_REQUESTS * (PREFIX_SHARED
                                                    + PREFIX_SUFFIX)
    legs: dict[str, dict] = {}
    for frac in (0.0, 0.5, 1.0):
        results, sched, obs, wall_s = drain(frac, True)
        stats = sched.pool.prefix.stats
        skipped = stats["prefix_hit_tokens"]
        leg = {
            "shared_fraction": frac,
            "n_requests": PREFIX_REQUESTS,
            "reps": PREFIX_REPS,
            "wall_s": round(wall_s, 3),
            "prefix_lookups": stats["prefix_lookups"],
            "prefix_hits": stats["prefix_hits"],
            "hit_rate": round(stats["prefix_hits"]
                              / max(stats["prefix_lookups"], 1), 3),
            "prefill_tokens_total": total_prompt,
            "prefill_tokens_skipped": skipped,
            "prefill_token_reduction": round(skipped / total_prompt, 3),
            "prefill_chunks": sched.stats["prefill_chunks"],
            "cow_copies": stats["cow_copies"],
            "pages_inserted": stats["prefix_insert_pages"],
            "latency": latency_summary(obs, "sched"),
        }
        if frac == 1.0:
            cold_results, _, cold_obs, cold_wall = drain(1.0, False)
            leg["token_identical_vs_cold"] = all(
                results[u].tokens == cold_results[u].tokens
                for u in cold_results)
            warm_p50 = leg["latency"]["ttft_s"].get("p50", 0.0)
            cold_p50 = latency_summary(
                cold_obs, "sched")["ttft_s"].get("p50", 0.0)
            leg["cold_wall_s"] = round(cold_wall, 3)
            leg["ttft_p50_delta_s"] = round(warm_p50 - cold_p50, 6)
        legs[f"shared_{int(frac * 100)}"] = leg
    return legs


# Goodput-under-load leg: the open-loop front end on the REAL clock (wall
# time is the point of this leg; the virtual clock belongs to tests and the
# CI smoke).  Offered rates are multiples of a calibrated closed-loop
# service rate, so the sweep spans under- to over-load regardless of how
# fast the host is.
GOODPUT_ARCH = "retnet-1.3b"
GOODPUT_REQUESTS = 8
GOODPUT_PROMPT_MIN = 6
GOODPUT_PROMPT_MAX = 24
GOODPUT_NEW = 8
GOODPUT_LANES = 2
GOODPUT_CHUNK = 8
GOODPUT_RATE_MULTS = (0.5, 1.5, 4.0)


def run_goodput_under_load() -> dict:
    """Open-loop goodput sweep through `ServingFrontend`.

    Calibrates a closed-loop drain first (also the compile warmup shape),
    derives the TTFT SLO target and the base offered rate from it, then
    sweeps `GOODPUT_RATE_MULTS` x base with seeded Poisson arrivals — each
    leg on a fresh scheduler warmed and registry-reset before measurement.
    A final check drives the same request set through the front end and a
    direct ``RequestScheduler.run()`` and records greedy token identity.
    """
    engine = InferenceEngine.from_config(GOODPUT_ARCH, EngineSpec(reduced=True))
    gen = GenerationConfig(max_new_tokens=GOODPUT_NEW)
    mix = LengthMix(prompt_min=GOODPUT_PROMPT_MIN,
                    prompt_max=GOODPUT_PROMPT_MAX,
                    new_min=GOODPUT_NEW, new_max=GOODPUT_NEW)
    clen = GOODPUT_PROMPT_MAX + GOODPUT_NEW
    # One request-set shape for everything: calibration, warmups, the sweep
    # (per-leg arrival times differ; sizes/prompts are re-derived per seed).
    warm_wl = Workload(arrivals=PoissonArrivals(1.0), lengths=mix,
                       n_requests=GOODPUT_REQUESTS,
                       vocab_size=engine.cfg.vocab_size, seed=29)

    def make_sched(obs, clock):
        return RequestScheduler(engine, classes=[(GOODPUT_LANES, clen)],
                                gen=gen, chunk_size=GOODPUT_CHUNK,
                                key=jax.random.key(0), obs=obs,
                                clock=clock.now)

    def closed_drain(sched, uid_base=5000):
        for i, r in enumerate(warm_wl.requests()):
            sched.submit(Request(uid=uid_base + i, prompt=list(r.prompt),
                                 max_new_tokens=r.max_new_tokens))
        return sched.run()

    # Calibration: a warmed closed-loop drain of the request-set shape.
    obs = Observability()
    clock = MonotonicClock()
    sched = make_sched(obs, clock)
    closed_drain(sched, uid_base=5000)            # trace/compile warmup
    obs.metrics.reset()
    t0 = time.perf_counter()
    closed_drain(sched, uid_base=6000)
    calib_wall = max(time.perf_counter() - t0, 1e-6)
    base_rate = GOODPUT_REQUESTS / calib_wall
    calib = latency_summary(obs, "sched")
    # SLO target: 2x the calibrated closed-loop p50 TTFT (which already
    # includes queueing GOODPUT_REQUESTS over GOODPUT_LANES lanes) — met
    # comfortably under-load, breached under hard overload.
    slo_s = max(2.0 * calib["ttft_s"].get("p50", 0.05), 0.02)

    cfg = FrontendConfig(ttft_slo_s=slo_s, slo_window_s=max(4 * calib_wall,
                                                            1.0),
                         min_slo_samples=4, guaranteed_admit=GOODPUT_LANES)
    rates = []
    for mult in GOODPUT_RATE_MULTS:
        rate = base_rate * mult
        leg_obs = Observability()
        leg_clock = MonotonicClock()
        leg_sched = make_sched(leg_obs, leg_clock)
        closed_drain(leg_sched, uid_base=7000)    # warm this instance's jits
        leg_obs.metrics.reset()
        frontend = ServingFrontend(leg_sched, config=cfg, clock=leg_clock)
        workload = Workload(arrivals=PoissonArrivals(rate), lengths=mix,
                            n_requests=GOODPUT_REQUESTS,
                            vocab_size=engine.cfg.vocab_size, seed=13)

        async def drive():
            async with frontend:
                return await run_open_loop(frontend, workload)

        report = leg_clock.run(drive())
        rates.append({
            "rate_mult": mult,
            **{k: (round(v, 4) if isinstance(v, float) else v)
               for k, v in report.to_dict().items()},
            "latency": latency_summary(leg_obs, "sched"),
        })

    # Greedy token identity: the same request set through the front end
    # (admission policy off — identity needs every request admitted,
    # arrivals paced open-loop so the interleaving differs from closed
    # loop) vs a direct `RequestScheduler.run()` with the same key.
    import asyncio

    fe_sched = make_sched(Observability(), MonotonicClock())
    fe_clock = MonotonicClock(fe_sched._now)
    frontend = ServingFrontend(
        fe_sched, config=FrontendConfig(ttft_slo_s=slo_s, shed_action="off"),
        clock=fe_clock)
    id_wl = Workload(arrivals=PoissonArrivals(base_rate), lengths=mix,
                     n_requests=GOODPUT_REQUESTS,
                     vocab_size=engine.cfg.vocab_size, seed=17)
    id_requests = id_wl.requests()

    async def drive_identity() -> dict[int, list[int]]:
        tokens: dict[int, list[int]] = {}

        async def consume(stream):
            tokens[stream.uid] = [tok async for tok in stream]

        async with frontend:
            tasks = []
            t0 = fe_clock.now()
            for r in id_requests:
                await fe_clock.sleep(t0 + r.at_s - fe_clock.now())
                stream = frontend.submit(r.prompt, uid=r.uid,
                                         max_new_tokens=r.max_new_tokens)
                tasks.append(asyncio.ensure_future(consume(stream)))
            await asyncio.gather(*tasks)
        return tokens

    fe_tokens = fe_clock.run(drive_identity())
    direct = make_sched(Observability(), MonotonicClock())
    for r in id_requests:
        direct.submit(Request(uid=r.uid, prompt=list(r.prompt),
                              max_new_tokens=r.max_new_tokens))
    direct_results = direct.run()
    identical = (set(fe_tokens) == set(direct_results) and all(
        fe_tokens[uid] == direct_results[uid].tokens
        for uid in direct_results))

    return {
        "arch": engine.cfg.name,
        "n_requests": GOODPUT_REQUESTS,
        "device_lanes": GOODPUT_LANES,
        "arrival": "poisson",
        "calibrated_service_rps": round(base_rate, 3),
        "ttft_slo_s": round(slo_s, 5),
        "token_identical_vs_run": identical,
        "rates": rates,
    }


SHARDED_MESH = "2,2"
SHARDED_DEVICES = 4
SHARDED_PROMPT = 16
SHARDED_NEW_TOKENS = 8


def run_sharded() -> dict:
    """Multi-chip leg: a warm sharded generate on a 2x2 virtual-device mesh.

    Subprocess because ``--xla_force_host_platform_device_count`` must be
    set before any jax initialization (this process already holds a
    single-device jax).  It runs only on the CPU backend: on an accelerator
    this process holds the chip, a child could not reach it, and the
    sharded path on real chips is ``chip_smoke.py --chips 4``.  A failed
    leg raises, so the script exits nonzero.
    """
    backend = jax.default_backend()
    if backend != "cpu":
        return {"skipped": f"virtual-device leg runs on the cpu backend "
                           f"only (this run: {backend})"}
    code = textwrap.dedent(f"""
        import json, time
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_serving_mesh
        from repro.serving import (EngineSpec, GenerationConfig,
                                   InferenceEngine)

        mesh = make_serving_mesh({SHARDED_MESH!r})
        eng = InferenceEngine.from_config("retnet-1.3b",
                                          EngineSpec(reduced=True), mesh=mesh)
        prompts = jax.random.randint(jax.random.key(1),
                                     (1, {SHARDED_PROMPT}), 1,
                                     eng.cfg.vocab_size, dtype=jnp.int32)
        gen = GenerationConfig(max_new_tokens={SHARDED_NEW_TOKENS})
        eng.generate(prompts, gen)                       # warm/compile
        from repro.obs import Observability
        eng.obs = Observability()           # measured-run latency only
        t0 = time.perf_counter()
        eng.generate(prompts, gen)
        wall = time.perf_counter() - t0
        clen = {SHARDED_PROMPT} + {SHARDED_NEW_TOKENS}
        hists = eng.obs.metrics.snapshot()["histograms"]
        print("BENCH_SHARDED " + json.dumps({{
            "devices": jax.device_count(),
            "mesh_axes": {{a: int(n) for a, n in
                           zip(mesh.axis_names, mesh.devices.shape)}},
            "wall_s": round(wall, 3),
            "tokens_per_s": round(
                ({SHARDED_PROMPT} + {SHARDED_NEW_TOKENS}) / wall, 2),
            "cache_nbytes_global": eng.cache_nbytes(clen),
            "latency": {{
                "ttft_s": hists.get("engine.ttft_s", {{"count": 0}}),
                "inter_token_s": hists.get("engine.inter_token_s",
                                           {{"count": 0}}),
            }},
        }}))
    """)
    env = dict(
        os.environ,
        XLA_FLAGS=f"--xla_force_host_platform_device_count={SHARDED_DEVICES}",
        PYTHONPATH=os.pathsep.join(
            [os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                          "src")]
            + ([os.environ["PYTHONPATH"]] if os.environ.get("PYTHONPATH")
               else [])))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=1200)
    for line in out.stdout.splitlines():
        if line.startswith("BENCH_SHARDED "):
            return json.loads(line[len("BENCH_SHARDED "):])
    raise RuntimeError(f"sharded leg failed (exit {out.returncode}): "
                       f"{(out.stderr or 'no output')[-2000:]}")


def run(out_path: str = "BENCH_serving.json") -> dict:
    enable_compile_cache()
    record = run_scheduler()
    record["git_rev"] = git_rev()
    record["speculative"] = run_speculative()
    record["oversubscribed"] = run_oversubscribed()
    record["quantized_decode"] = run_quantized_decode()
    record["sharded"] = run_sharded()
    record["prefix_reuse"] = run_prefix_reuse()
    record["goodput_under_load"] = run_goodput_under_load()

    # Append to the trajectory (older single-record files become entry 0).
    history: list = []
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                prev = json.load(f)
            history = prev if isinstance(prev, list) else [prev]
        except (json.JSONDecodeError, OSError):
            history = []
    history.append(record)
    with open(out_path, "w") as f:
        json.dump(history, f, indent=2)
        f.write("\n")
    print(json.dumps(record, indent=2))
    return record


if __name__ == "__main__":
    run(sys.argv[1] if len(sys.argv) > 1 else "BENCH_serving.json")
