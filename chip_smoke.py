#!/usr/bin/env python3
"""Chip smoke test: serve retnet-1.3b at its published widths on a TPU.

    python chip_smoke.py             # one chip: build, generate, check
                                     # against the jnp reference, scheduler
    python chip_smoke.py --chips 4   # only the sharded path (1x4 mesh) and
                                     # the one-chip engine it is compared with

The model is the paper's own, at full width and depth (24 layers, d_model
2048, 8 heads, ffn 4096, vocab 32768), deployed as the paper deploys it:
W8A8 prefill and MXINT4 decode, through `InferenceEngine.from_config` and
`RequestScheduler`.  Weights and prompts come from fixed seeds; nothing is
read from disk.

Every phase either passes its check or raises, and the process exits
nonzero.  There is no CPU fallback: with no TPU the script exits 2 before
any phase.  The last line of stdout is one JSON object naming the device;
all other output (times, bytes, agreement figures) comes before it.  Times
are wall-clock observations on the device named there, each taken after
`block_until_ready`.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels import ops  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving import (EngineSpec, GenerationConfig,  # noqa: E402
                           InferenceEngine, Request, RequestScheduler)
from repro.serving.engine import pytree_nbytes  # noqa: E402

ARCH = "retnet-1.3b"
BATCH, PROMPT, NEW = 2, 256, 64      # PROMPT % 128 == 0: the retention kernel runs
AGREE_STEPS = 8
# Agreement of two serving paths (Pallas vs jnp, or 1x4 mesh vs one chip)
# is read at fp32 matmul precision, as the relative RMS difference of their
# logits, ||a - b|| / ||b||.  Two limits, because the two phases differ:
#
# * Prefill runs W8A8: every linear re-quantizes its input to int8, so an
#   fp32 summation-order difference flips a rounding now and then, and a
#   randomly initialized 24-layer model amplifies each flip: Pallas vs jnp
#   measured relative RMS 0.20 (correlation 0.98) on a v5e chip, below
#   the reference's own floor, which the phase prints beside it: itself
#   with EMBED_NOISE relative noise on its embedding table, 0.30.  Logits of a faulty
#   path are uncorrelated with the reference's, relative RMS ~1.4.
#   Hence 0.5.
# * Decode from one shared state is continuous (MXINT4 weights, f32
#   activations, no re-quantization), so only summation order separates
#   the paths: relative RMS 1.5e-4 at the first decode step on a v5e chip.
#   5e-2 leaves room for a different reduction layout (the mesh) and still
#   rejects any fault that moves logits by a percent.
#
# Greedy tokens are therefore compared from one shared prefill state: both
# paths decode AGREE_STEPS tokens from the reference's prefill cache and
# first token through the fused decode loop, and must agree exactly.
PREFILL_TOL = 0.5
DECODE_TOL = 5e-2
EMBED_NOISE = 1e-6
# One kernel call against its jnp oracle: only fp32 summation order differs
# (at most 1.2e-5 of the output's std on a v5e chip), with no int8
# re-quantization in between to amplify it.
KERNEL_TOL = 1e-4
# Scheduler requests (prompt, new tokens): the paper's LISO 750/50 and SILO
# 50/750 (cut to 128 new tokens) plus four mixed lengths, over two classes.
SCHED_CLASSES = [(2, 256), (2, 1024)]
SCHED_REQUESTS = [(750, 50), (50, 128), (640, 64), (390, 100), (34, 100),
                  (160, 64)]
SCHED_CHUNK = 128


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def timed(fn):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def prompts_for(engine, batch: int, length: int, seed: int) -> jax.Array:
    return jax.random.randint(jax.random.key(seed), (batch, length), 1,
                              engine.cfg.vocab_size, dtype=jnp.int32)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def build(spec: EngineSpec = EngineSpec(), **kw) -> InferenceEngine:
    engine, dt = timed(lambda: InferenceEngine.from_config(ARCH, spec, **kw))
    jax.block_until_ready(engine.params)
    cfg = engine.cfg
    log(f"build {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads, ffn {cfg.d_ff}, vocab {cfg.vocab_size}; "
        f"kernel_impl {spec.kernel_impl!r} -> {ops._resolve(spec.kernel_impl)}; "
        f"deployed param bytes {pytree_nbytes(engine.params)}; "
        f"init + deploy {dt:.3f} s")
    return engine


def phase_kernels() -> None:
    """Each Pallas kernel at serving widths against its jnp oracle, on the
    chip, both at fp32 matmul precision (interpret-mode tests on the CPU
    cannot see the chip's arithmetic)."""
    from repro.core import kvq, retention
    from repro.core.mxint4 import quantize_mxint4

    keys = iter(jax.random.split(jax.random.key(7), 64))

    def normal(*shape, scale=1.0):
        return jax.random.normal(next(keys), shape, jnp.float32) * scale

    def int8(*shape):
        return jax.random.randint(next(keys), shape, -127, 128, jnp.int8)

    cases = {}
    with jax.default_matmul_precision("highest"):
        for k, n in ((2048, 4096), (4096, 2048), (2048, 32768)):
            x, q = normal(8, k), quantize_mxint4(normal(k, n, scale=0.02))
            epilogue = normal(n), normal(8), normal(n)
            cases[f"mxint4_matmul 8x{k}x{n}"] = [
                ops.mxint4_matmul(x, q, *epilogue, impl=impl)
                for impl in ("pallas", "ref")]
        xq, wq, rs, b = int8(256, 2048), int8(2048, 4096), normal(256), \
            normal(4096)
        cases["w8a8_matmul 256x2048x4096"] = [
            ops.w8a8_matmul(xq, wq, jnp.float32(1e-4), rs, b, impl=impl)
            for impl in ("pallas", "ref")]
        qkv = [normal(2, 8, PROMPT, d, scale=0.06) for d in (256, 256, 512)]
        gamma = retention.head_decays(8)
        for i, name in enumerate(("y", "state")):
            cases[f"retention_chunkwise {name}"] = [
                ops.retention_chunkwise(*qkv, gamma, impl=impl)[i]
                for impl in ("pallas", "ref")]
        q, kv = normal(4, 8, 4, 128), normal(4, 2048, 8, 128)
        for fmt in ("fp", "int8_tok", "mxint4_blk"):
            leaf = kv if fmt == "fp" else kvq.encode(kv, fmt)
            cases[f"flash_decode gqa {fmt}"] = [
                ops.flash_decode(q, leaf, leaf, jnp.int32(1500), impl=impl)
                for impl in ("pallas", "ref")]
        y = normal(512, 2048)
        cases["rmsnorm_stats 512x2048"] = [
            ops.rmsnorm_stats(y, impl=impl) for impl in ("pallas", "ref")]
    worst = 0.0
    for name, (got, want) in cases.items():
        got, want = np.asarray(got), np.asarray(want)
        rel = float(np.max(np.abs(got - want))) / float(np.std(want))
        worst = max(worst, rel if np.isfinite(got).all() else np.inf)
        log(f"kernel {name}: max |pallas - ref| = {rel:.3e} x ref std")
    check(worst <= KERNEL_TOL, f"a kernel differs from its oracle by "
                               f"{worst:.3e} x std (tolerance {KERNEL_TOL})")


def phase_generate(engine, prompts) -> None:
    gen = GenerationConfig(max_new_tokens=NEW)
    res, dt = timed(lambda: engine.generate(prompts, gen))
    log(f"generate warm-up (includes compile): {dt:.3f} s")
    res, dt = timed(lambda: engine.generate(prompts, gen))
    toks = np.asarray(res.tokens)
    check(toks.shape == (BATCH, NEW), f"tokens shape {toks.shape}")
    check(((toks >= 0) & (toks < engine.cfg.padded_vocab)).all(),
          "token ids out of range")
    check((np.asarray(res.lengths) == NEW).all(), "short generation")
    log(f"generate B={BATCH} prompt={PROMPT} new={NEW}: observed "
        f"prefill {res.prefill_s:.4f} s, decode {res.decode_s:.4f} s "
        f"({res.decode_s / NEW * 1e3:.3f} ms/step), wall {dt:.4f} s")

    lowered = engine.lower_decode_loop(gen, batch=BATCH, s_in=PROMPT)
    compiled, dt = timed(lowered.compile)
    n_kernels = compiled.as_text().count("tpu_custom_call")
    check(n_kernels > 0, "no tpu_custom_call in the compiled decode loop")
    log(f"decode loop: {n_kernels} tpu_custom_call op(s) in the compiled "
        f"program (compile {dt:.3f} s)")


def _rel_rms(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    check(np.isfinite(got).all(), "non-finite logits")
    return float(np.sqrt(np.mean((got - want) ** 2) / np.mean(want ** 2)))


def compare(name: str, engine, ref, prompts) -> float:
    """`engine` against `ref` on the same prompts at fp32 matmul precision:
    prefill logits, then greedy decode from `ref`'s prefill state.  Returns
    the prefill relative RMS difference."""
    gen = GenerationConfig(max_new_tokens=AGREE_STEPS)
    with jax.default_matmul_precision("highest"):
        (lp, _), dt = timed(lambda: engine.prefill(prompts,
                                                   cache_len=PROMPT + NEW))
        lr, cr = ref.prefill(prompts, cache_len=PROMPT + NEW)
        pre = _rel_rms(lp, lr)
        log(f"{name}: prefill logits relative RMS difference {pre:.3e} "
            f"(limit {PREFILL_TOL}); prefill {dt:.3f} s incl. compile")
        check(pre <= PREFILL_TOL, f"{name}: prefill logits differ")

        tok0 = jnp.argmax(lr, axis=-1).astype(jnp.int32)
        dp, _ = engine.decode_step(tok0[:, None], engine.shard_cache(cr))
        dr, _ = ref.decode_step(tok0[:, None], cr)
        dec = _rel_rms(dp, dr)
        log(f"{name}: first decode step from the shared state, logits "
            f"relative RMS difference {dec:.3e} (limit {DECODE_TOL})")
        check(dec <= DECODE_TOL, f"{name}: decode logits differ")

        got = np.asarray(engine.resume_generate(
            tok0, engine.shard_cache(cr), gen).tokens)
        want = np.asarray(ref.resume_generate(tok0, cr, gen).tokens)
    log(f"{name}: greedy tokens from the shared state: {got.tolist()}")
    log(f"{name}: reference tokens:                  {want.tolist()}")
    check((got == want).all(), f"{name}: greedy tokens differ in the first "
                               f"{AGREE_STEPS} steps")
    log(f"{name}: identical greedy tokens for {AGREE_STEPS} steps x "
        f"{prompts.shape[0]} sequences")
    return pre


def phase_reference(engine, prompts) -> None:
    ref = InferenceEngine.from_config(ARCH, EngineSpec(kernel_impl="ref"),
                                      params=engine.params)
    compare("pallas vs ref", engine, ref, prompts)
    # The floor the prefill limit is read against: the reference against
    # itself with EMBED_NOISE relative noise on its embedding table.  Both
    # sides hold the table in f32, as a bf16 table cannot carry the noise
    # (and the upcast alone moves logits too).
    emb = ref.params["embed"].astype(jnp.float32)
    noise = jax.random.normal(jax.random.key(9), emb.shape, jnp.float32)
    logits = []
    for table in (emb, emb + EMBED_NOISE * jnp.std(emb) * noise):
        eng = InferenceEngine.from_config(
            ARCH, EngineSpec(kernel_impl="ref"),
            params=dict(ref.params, embed=table))
        with jax.default_matmul_precision("highest"):
            logits.append(eng.prefill(prompts, cache_len=PROMPT + NEW)[0])
        del eng
    log(f"floor: ref vs ref with {EMBED_NOISE} relative noise on its "
        f"(f32) embedding table, prefill logits relative RMS difference "
        f"{_rel_rms(*logits):.3e}")


def phase_scheduler(engine) -> None:
    gen = GenerationConfig(max_new_tokens=max(n for _, n in SCHED_REQUESTS))
    sched = RequestScheduler(engine, classes=SCHED_CLASSES, gen=gen,
                             chunk_size=SCHED_CHUNK, obs=engine.obs)
    rng = np.random.default_rng(3)
    for uid, (s, n) in enumerate(SCHED_REQUESTS):
        prompt = rng.integers(1, engine.cfg.vocab_size, size=s).tolist()
        sched.submit(Request(uid=uid, prompt=prompt, max_new_tokens=n))
    res, dt = timed(sched.run)
    for uid, (s, n) in enumerate(SCHED_REQUESTS):
        fin = res.get(uid)
        check(fin is not None and not fin.cancelled, f"request {uid} lost")
        check(len(fin.tokens) == n, f"request {uid}: {len(fin.tokens)} of "
                                    f"{n} tokens")
        log(f"request {uid}: prompt {s}, {len(fin.tokens)}/{n} tokens, "
            f"cache class {fin.cache_len}")
    st = dict(sched.stats)
    log(f"scheduler: {len(res)}/{len(SCHED_REQUESTS)} requests completed "
        f"with their budgets, chunk {SCHED_CHUNK}, classes {SCHED_CLASSES}; "
        f"{st['steps']} steps, {st['prefill_chunks']} prefill chunks; "
        f"observed wall {dt:.3f} s (includes compiles)")


def phase_sharded() -> None:
    from repro.launch.mesh import make_serving_mesh

    check(len(jax.devices()) >= 4, f"--chips 4 needs 4 devices, found "
                                   f"{len(jax.devices())}")
    mesh = make_serving_mesh("1,4")
    # XLA cannot partition a Mosaic kernel, so the mesh runs the jnp path
    # (ops.mesh_impl); the one-chip control runs the same path, so the
    # comparison isolates the partitioning.
    impl = ops.mesh_impl("auto", mesh.size)
    single = build(EngineSpec(kernel_impl=impl))
    log("the sharded engine adopts the one-chip engine's deployed params: "
        "from_config initializes them on the default device before "
        "device_put to the mesh, which fits one chip at 1.3B")
    sharded, dt = timed(lambda: InferenceEngine.from_config(
        ARCH, EngineSpec(), params=single.params, mesh=mesh))
    check(sharded.spec.kernel_impl == impl, "mesh kernel_impl mismatch")
    log(f"sharded engine on mesh {dict(mesh.shape)}, kernel_impl "
        f"{sharded.spec.kernel_impl!r} (Mosaic kernels are not partitioned "
        f"by XLA), built in {dt:.3f} s")
    total = pytree_nbytes(sharded.params)
    per_dev = {d.id: 0 for d in mesh.devices.flat}
    for leaf in jax.tree.leaves(sharded.params):
        for sh in leaf.addressable_shards:
            per_dev[sh.device.id] += sh.data.nbytes
    log(f"param bytes: global {total}, per device {per_dev}")
    check(max(per_dev.values()) < total / 2,
          "params are not spread across the mesh")

    prompts = prompts_for(single, BATCH, PROMPT, seed=1)
    compare("1x4 mesh vs one chip", sharded, single, prompts)

    gen = GenerationConfig(max_new_tokens=NEW)
    outs = {}
    for name, eng in (("one-chip", single), ("1x4 mesh", sharded)):
        eng.generate(prompts, gen)                      # warm-up / compile
        res, dt = timed(lambda: eng.generate(prompts, gen))
        outs[name] = np.asarray(res.tokens)
        log(f"{name}: generate B={BATCH} prompt={PROMPT} new={NEW}, observed "
            f"prefill {res.prefill_s:.4f} s, decode {res.decode_s:.4f} s "
            f"({res.decode_s / NEW * 1e3:.3f} ms/step)")
    same = outs["one-chip"] == outs["1x4 mesh"]
    log(f"free-running generate (each from its own W8A8 prefill, default "
        f"precision): {int(same.sum())}/{same.size} tokens equal")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path and its one-chip "
                         "comparison")
    args = ap.parse_args()

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"[smoke] no TPU: JAX reports {dev.platform!r} devices; this "
              "check runs only on the chip", file=sys.stderr)
        return 2
    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {dev.platform} {dev.device_kind}, {len(jax.devices())} "
        f"visible; jax {jax.__version__}")

    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded()
    else:
        phase_kernels()
        engine = build()
        prompts = prompts_for(engine, BATCH, PROMPT, seed=1)
        phase_generate(engine, prompts)
        phase_reference(engine, prompts)
        phase_scheduler(engine)
    log(f"all phases passed in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
