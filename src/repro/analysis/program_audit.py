"""Layer-2 program audit: inspect the serving hot path's *lowered programs*.

The AST lint (Layer 1) checks what the source says; this module checks what
XLA actually compiled.  Each audit instantiates a tiny-config
`InferenceEngine` and proves one of the software analogues of the paper's
accounting guarantees (utilization / external-memory-access minimality):

    recompiles   (A1) the power-of-two bucket ladder holds: driving every
                 prompt length 1..K produces O(log K) compiled prefill
                 signatures — not O(K) — on both the bucketed and the
                 chunked admission paths.
    donation     (A2) the chunked-prefill step's resident cache is donated
                 *in the compiled executable* (input_output_alias covers
                 every cache leaf): appending a chunk is in-place, not a
                 full cache copy per chunk.
    transfers    (A3) the fused decode / speculative-verify ``while_loop``
                 HLO contains no host callbacks and no async host/device
                 transfer ops — the MVM phase never round-trips off device.
    sharding     (A4) on a mesh, the ServeCell plan is *realized*: params
                 and caches lie where the rules engine said, every `_sjit`
                 entry's shardings come from the plan's mesh, and no entry
                 reshards its resident cache between input and output.
    decode-kernel (A5) the fused decode while_loop actually dispatches the
                 flash-decode attention kernel (`kernels/flash_decode.py`)
                 when ``kernel_impl='pallas'`` — traced-program (jaxpr)
                 inspection, for both fp and quantized caches — and that
                 'auto' resolution never smuggles a Pallas call onto a
                 non-TPU backend.
    donation-quant (A6) the donation guarantee (A2) survives the quantized
                 cache layout: with encoded dict leaves ({"q","s"} /
                 {"m","e"}) the chunk step still aliases (nearly) every
                 resident cache byte.
    observability (A7) the `repro.obs` layer is zero-overhead where it
                 counts: the compiled decode / speculative-verify programs
                 and the scheduler's vmapped pool step are byte-identical
                 with the full observability stack (tracer + profiler
                 annotations + metrics) on vs off.
    prefix-reuse (A8) shared-prefix adoption (serving/paging.py) is
                 invisible to the compiled programs: the adopted-prefix
                 decode HLO is byte-identical to the cold path, the
                 suffix-only chunked prefill reuses the cold chunk ladder
                 (zero new signatures) while keeping >= 90% cache-byte
                 donation, and a warm scheduler drain actually hits.

Run via ``python -m repro.analysis audit`` (`make audit-program`).  The
sharding audit needs >= 4 devices; the Makefile target forces 4 virtual
host devices so it exercises a real 2x2 (data, model) mesh everywhere.
"""

from __future__ import annotations

import dataclasses
import math
import re

__all__ = ["AuditResult", "AuditReport", "audit_recompiles",
           "audit_donation", "audit_transfers", "audit_sharding",
           "audit_decode_kernel", "audit_observability",
           "audit_prefix_reuse", "run_audits",
           "parse_io_aliases", "hlo_opcodes", "custom_call_targets"]

DEFAULT_ARCH = "retnet-1.3b"

# Host-communication HLO opcodes: any of these inside the decode loop means
# the MVM phase blocks on the host/network per step.
_TRANSFER_OPS = frozenset({"infeed", "outfeed", "send", "recv",
                           "send-done", "recv-done",
                           "copy-start", "copy-done"})
# custom-call targets that reach back into the host Python process.
_HOST_CALLBACK_RE = re.compile(r"callback|host|py_func|python", re.I)


@dataclasses.dataclass
class AuditResult:
    name: str
    ok: bool
    detail: str
    metrics: dict

    def render(self) -> str:
        return f"[{'ok' if self.ok else 'FAIL'}] {self.name}: {self.detail}"


@dataclasses.dataclass
class AuditReport:
    results: list[AuditResult]

    @property
    def ok(self) -> bool:
        return all(r.ok for r in self.results)

    def render(self) -> str:
        lines = [r.render() for r in self.results]
        lines.append("audit: " + ("PASS" if self.ok else "FAIL")
                     + f" ({sum(r.ok for r in self.results)}"
                       f"/{len(self.results)})")
        return "\n".join(lines)

    def to_dict(self) -> dict:
        return {"ok": self.ok,
                "results": [dataclasses.asdict(r) for r in self.results]}


# -- HLO text inspection -----------------------------------------------------

_ALIAS_RE = re.compile(r"\{\s*(\d+(?:\s*,\s*\d+)*)?\s*\}:\s*\((\d+),\s*\{\}")


def parse_io_aliases(hlo_text: str) -> list[tuple[tuple[int, ...], int]]:
    """(output index path, parameter number) pairs of the module's
    ``input_output_alias`` annotation — the compiled spelling of buffer
    donation."""
    m = re.search(r"input_output_alias=\{(.*?)\}\s*,\s*entry_computation",
                  hlo_text, re.S)
    section = m.group(1) if m else hlo_text
    out = []
    for idx, param in _ALIAS_RE.findall(section):
        path = tuple(int(p) for p in re.split(r"\s*,\s*", idx)) if idx else ()
        out.append((path, int(param)))
    return out


def hlo_opcodes(hlo_text: str) -> set[str]:
    """Opcode set of an HLO module text (covers all computations, so the
    bodies of while/fusion computations are included)."""
    return set(re.findall(r"=\s*[\w\[\],{}() ]*?\s([a-z][a-z0-9-]*)\(",
                          hlo_text))


def custom_call_targets(hlo_text: str) -> set[str]:
    return set(re.findall(r'custom_call_target="([^"]+)"', hlo_text))


_LOCATION_TABLES = ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames")
_OP_METADATA_RE = re.compile(r", metadata=\{[^}]*\}")


def _compiled_text(lowered) -> str:
    """Compiled HLO text without source locations.  jax 0.9 prints the
    Python stack that built every op (header tables plus per-op
    ``metadata``), which differs between call sites of the same program;
    the audits compare and parse the program itself."""
    lines, in_table = [], False
    for line in lowered.compile().as_text().splitlines():
        if line in _LOCATION_TABLES:
            in_table = True
        elif in_table:
            in_table = bool(line.strip())
        else:
            lines.append(_OP_METADATA_RE.sub("", line))
    return "\n".join(lines)


# -- engine construction -----------------------------------------------------

def tiny_engine(arch: str = DEFAULT_ARCH, *, mesh=None):
    """Reduced fp engine — small enough that compiling its programs is a CI
    step, faithful enough that the audited programs are the real hot path."""
    from repro.serving import EngineSpec, InferenceEngine
    return InferenceEngine.from_config(
        arch, EngineSpec(reduced=True, quantize=False), mesh=mesh)


# -- A1: recompile audit -----------------------------------------------------

def audit_recompiles(arch: str = DEFAULT_ARCH, *, max_len: int = 24,
                     chunk_size: int = 8) -> AuditResult:
    """Drive EVERY prompt length 1..max_len through the bucketed and the
    chunked admission paths and bound the compiled-signature counts."""
    import jax
    import jax.numpy as jnp
    from repro.serving.engine import bucket_length

    engine = tiny_engine(arch)
    cache_len = bucket_length(max_len)
    for s in range(1, max_len + 1):
        tokens = jax.random.randint(jax.random.key(s), (1, s), 1,
                                    engine.cfg.vocab_size, dtype=jnp.int32)
        engine.prefill(tokens, bucket=True)
        engine.prefill_chunked(tokens, cache_len=cache_len,
                               chunk_size=chunk_size)

    counts = engine.compile_counts()
    n_prefill = counts["prefill"]
    if n_prefill < 0:                       # no _cache_size on this jax
        n_prefill = len({k for k in engine.prefill_shape_keys
                         if k[0] == "bucket"})
    n_chunk = counts["prefill_chunk"]
    if n_chunk < 0:
        n_chunk = len({k for k in engine.prefill_shape_keys
                       if k[0] == "chunk"})
    bucket_bound = int(math.log2(cache_len)) + 1
    chunk_bound = int(math.log2(chunk_size)) + 1
    ok = 0 < n_prefill <= bucket_bound and 0 < n_chunk <= chunk_bound
    return AuditResult(
        "recompiles", ok,
        f"{max_len} prompt lengths -> {n_prefill} bucketed prefill "
        f"signature(s) (bound {bucket_bound}) and {n_chunk} chunk "
        f"signature(s) (bound {chunk_bound})",
        {"max_len": max_len, "prefill_signatures": n_prefill,
         "bucket_bound": bucket_bound, "chunk_signatures": n_chunk,
         "chunk_bound": chunk_bound, "compile_counts": counts})


# -- A2: donation audit ------------------------------------------------------

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}
_SHAPE_RE = re.compile(r"([a-z]+\d*)\[([\d,]*)\]")


def entry_param_bytes(hlo_text: str) -> list[int]:
    """Byte size of each entry-computation parameter, in parameter order,
    parsed from the ``entry_computation_layout`` signature."""
    m = re.search(r"entry_computation_layout=\{\((.*?)\)\s*->", hlo_text, re.S)
    if not m:
        return []
    out = []
    for dt, dims in _SHAPE_RE.findall(m.group(1)):
        n = _DTYPE_BYTES.get(dt, 4)
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append(n)
    return out


def audit_donation(arch: str = DEFAULT_ARCH, *, chunk: int = 8,
                   cache_len: int = 32, cache_dtype=None,
                   engine=None) -> AuditResult:
    """Compile the chunked-prefill step and verify the executable aliases
    the donated resident cache instead of silently copying it.

    `jax.jit` prunes unused args (`keep_unused=False`), so cache leaves the
    chunk step never reads (stat scalars it recomputes) do not survive to
    the entry computation — counting aliased *leaves* would under-count.
    The invariant that matters for external-memory traffic is byte
    coverage: the aliased parameter bytes must cover (nearly) the whole
    resident cache, i.e. the KV megabuffer is updated in place and never
    copied once per chunk.

    ``cache_dtype`` accepts the same values as `lm.make_decode_cache`: a jnp
    dtype, or a `core.kvq` format string ('int8_tok' | 'mxint4_blk') — the
    latter audits the *quantized* resident layout (A6, 'donation-quant'):
    the encoded dict leaves ({"q","s"} / {"m","e"}) must alias just like the
    fp megabuffer does."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm

    engine = engine or tiny_engine(arch)
    quant = isinstance(cache_dtype, str)
    cache_dtype = jnp.float32 if cache_dtype is None else cache_dtype
    lowered = engine.lower_prefill_chunk(chunk=chunk, cache_len=cache_len,
                                         cache_dtype=cache_dtype)
    text = _compiled_text(lowered)
    aliases = parse_io_aliases(text)
    sizes = entry_param_bytes(text)

    cache_abs = jax.eval_shape(
        lambda: lm.make_decode_cache(engine.cfg, 1, cache_len, cache_dtype,
                                     start_pos=0))
    cache_bytes = sum(l.size * l.dtype.itemsize
                      for l in jax.tree.leaves(cache_abs))
    aliased = sum(sizes[p] for _, p in aliases if p < len(sizes))
    frac = aliased / cache_bytes if cache_bytes else 0.0
    ok = bool(aliases) and frac >= 0.9
    return AuditResult(
        f"donation-quant[{cache_dtype}]" if quant else "donation", ok,
        f"{len(aliases)} alias(es) keep {aliased}/{cache_bytes} cache bytes "
        f"({frac:.1%}) in place" if ok else
        f"aliases cover only {aliased}/{cache_bytes} cache bytes "
        f"({frac:.1%}): the donated cache is being copied",
        {"aliases": len(aliases), "aliased_bytes": aliased,
         "cache_bytes": cache_bytes, "fraction": round(frac, 4)})


# -- A3: transfer audit ------------------------------------------------------

def _scan_transfers(text: str) -> tuple[set[str], set[str]]:
    bad_ops = hlo_opcodes(text) & _TRANSFER_OPS
    bad_calls = {t for t in custom_call_targets(text)
                 if _HOST_CALLBACK_RE.search(t)}
    return bad_ops, bad_calls


def audit_transfers(arch: str = DEFAULT_ARCH, *, max_new_tokens: int = 8,
                    spec_k: int = 2, engine=None) -> AuditResult:
    """Scan the fused decode and speculative-verify while_loop HLO for host
    callbacks and transfer ops — there must be none: one dispatch runs the
    whole MVM phase on device."""
    from repro.serving import GenerationConfig, SpeculativeConfig

    engine = engine or tiny_engine(arch)
    gen = GenerationConfig(max_new_tokens=max_new_tokens)
    text = _compiled_text(engine.lower_decode_loop(gen))
    bad_ops, bad_calls = _scan_transfers(text)

    spec_bad_ops: set[str] = set()
    spec_bad_calls: set[str] = set()
    spec_gen = GenerationConfig(max_new_tokens=max_new_tokens,
                                speculative=SpeculativeConfig(k=spec_k))
    spec_text = _compiled_text(engine.lower_spec_loop(spec_gen))
    s_ops, s_calls = _scan_transfers(spec_text)
    spec_bad_ops |= s_ops
    spec_bad_calls |= s_calls

    bad = sorted(bad_ops | bad_calls | spec_bad_ops | spec_bad_calls)
    ok = not bad
    return AuditResult(
        "transfers", ok,
        "decode + verify while_loops are host-callback- and transfer-free"
        if ok else f"host/transfer ops in the fused loops: {bad}",
        {"decode_bad": sorted(bad_ops | bad_calls),
         "verify_bad": sorted(spec_bad_ops | spec_bad_calls)})


# -- A4: sharding audit ------------------------------------------------------

# Known cache argument positions per `_sjit` root name:
# (cache index in in_shardings, cache index in out_shardings).
_CACHE_ARGS = {"prefill_chunk": (2, 1), "decode": (2, 1),
               "loop": (2, 2), "resume_loop": (2, 2), "spec_loop": (5, 2)}


def audit_sharding(arch: str = DEFAULT_ARCH, *, mesh_spec: str = "2,2",
                   max_new_tokens: int = 4) -> AuditResult:
    """Drive the sharded engine's serving paths on a (data, model) mesh and
    prove the ServeCell plan is realized — `runtime.sharding
    .sharding_mismatches` over live arrays plus a replay of every `_sjit`
    entry's recorded shardings."""
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_serving_mesh
    from repro.runtime import sharding as shd
    from repro.serving import GenerationConfig, Request, RequestScheduler

    need = 1
    for p in re.split(r"[x,]", mesh_spec):
        need *= int(p)
    if jax.device_count() < need:
        return AuditResult(
            "sharding", True,
            f"skipped: {jax.device_count()} device(s) < {need} "
            f"(run under XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{need}, as `make audit-program` does)",
            {"skipped": True, "devices": jax.device_count()})

    mesh = make_serving_mesh(mesh_spec)
    engine = tiny_engine(arch, mesh=mesh)
    gen = GenerationConfig(max_new_tokens=max_new_tokens)
    s_in, cache_len = 8, 8 + max_new_tokens
    prompts = jax.random.randint(jax.random.key(0), (1, s_in), 1,
                                 engine.cfg.vocab_size, dtype=jnp.int32)

    mismatches: list[str] = []

    # Params: placed exactly as the ServeCell plan says.
    for m in shd.sharding_mismatches(engine.params, engine.param_shardings):
        mismatches.append(f"params/{m}")

    # Prefill -> decode_step -> fused loop: every returned cache lies under
    # the rules engine's placement.
    logits, cache = engine.prefill(prompts, cache_len=cache_len)
    for m in shd.sharding_mismatches(cache, engine.cache_shardings(cache)):
        mismatches.append(f"prefill_cache/{m}")
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    _, cache2 = engine.decode_step(tok, cache)
    for m in shd.sharding_mismatches(cache2, engine.cache_shardings(cache2)):
        mismatches.append(f"decode_cache/{m}")
    engine.generate(prompts, gen)

    # Chunked admission + pool: the stacked stores stay on-plan after a
    # scheduler drain.
    sched = RequestScheduler(engine, n_slots=2, cache_len=cache_len, gen=gen,
                             chunk_size=4)
    for uid in range(2):
        sched.submit(Request(uid=uid, prompt=prompts[0].tolist()))
    sched.run()
    for m in sched.pool.placement_mismatches():
        mismatches.append(f"pool/{m}")

    # Every _sjit entry: shardings come from the plan's mesh, the params arg
    # carries the plan's exact placement, and no entry reshards its resident
    # cache between input and output.
    entries = engine.jit_entries()
    pkey = shd.shardings_key(engine.param_shardings)
    for entry in entries:
        name = entry["name"]
        root = name[0] if isinstance(name[0], str) else str(name[0])
        ins, outs = entry["in_shardings"], entry["out_shardings"]
        for s in shd.sharding_leaves((ins, outs)):
            if getattr(s, "mesh", None) is not None and s.mesh != mesh:
                mismatches.append(f"sjit[{root}]: sharding {s} targets a "
                                  f"foreign mesh")
        if shd.shardings_key(ins[0]) != pkey:
            mismatches.append(f"sjit[{root}]: params in_sharding departs "
                              f"from the ServeCell plan")
        pos = _CACHE_ARGS.get(root)
        if pos is not None:
            cin, cout = ins[pos[0]], outs[pos[1]]
            if shd.shardings_key(cin) != shd.shardings_key(cout):
                mismatches.append(f"sjit[{root}]: cache resharded between "
                                  f"input and output")

    ok = not mismatches and bool(entries)
    detail = (f"{len(entries)} jit entr(ies) + live params/caches/pool all "
              f"on the {mesh_spec} ServeCell plan" if ok else
              ("; ".join(mismatches[:8]) or "no _sjit entries recorded"))
    return AuditResult("sharding", ok, detail,
                       {"mesh": mesh_spec, "jit_entries": len(entries),
                        "mismatches": mismatches})


# -- A5: decode-kernel audit -------------------------------------------------

# The decode-kernel audit needs an arch whose decode path actually attends
# over a KV cache; DEFAULT_ARCH (retnet) is attention-free.
KERNEL_ARCH = "qwen3-8b"


def _count_pallas(engine, logits, cache, gen) -> int:
    """pallas_call occurrences in the traced fused-decode-loop jaxpr.

    Traced (jaxpr), not compiled: off-TPU, XLA:CPU cannot *compile* a real
    Pallas TPU kernel, but tracing still records exactly which primitive the
    `kernels.ops.flash_decode` wrapper resolved to — which is the invariant
    under audit."""
    import functools
    import jax

    key = jax.eval_shape(lambda: jax.random.key(0))
    jaxpr = jax.make_jaxpr(functools.partial(engine._loop_impl, gen=gen))(
        engine.params, logits, cache, key)
    return str(jaxpr).count("pallas_call")


def audit_decode_kernel(arch: str = KERNEL_ARCH, *, s_in: int = 8,
                        cache_len: int = 12) -> AuditResult:
    """Trace the fused decode loop and prove kernel dispatch honesty:

      * ``kernel_impl='pallas'`` puts the flash-decode `pallas_call` inside
        the while_loop body — for the fp cache AND for a quantized
        ('int8_tok') cache, i.e. dequantization is fused into the kernel's
        KV loads rather than materializing an fp cache first;
      * ``kernel_impl='auto'`` on a non-TPU backend resolves to the jnp
        reference path — zero pallas_calls smuggled onto a backend that
        cannot run them (on TPU, 'auto' must instead match 'pallas').
    """
    import jax
    from repro.models import lm
    from repro.serving import EngineSpec, GenerationConfig, InferenceEngine

    gen = GenerationConfig(max_new_tokens=4)
    on_tpu = jax.default_backend() == "tpu"

    def counts(impl: str) -> tuple[int, int]:
        eng = InferenceEngine.from_config(
            arch, EngineSpec(reduced=True, quantize=False, kernel_impl=impl))
        logits, cache = eng._abstract_prefill(s_in, cache_len)
        qcache = jax.eval_shape(
            lambda c: lm.quantize_cache(c, eng.cfg, "int8_tok"), cache)
        return (_count_pallas(eng, logits, cache, gen),
                _count_pallas(eng, logits, qcache, gen))

    n_pallas_fp, n_pallas_q = counts("pallas")
    n_auto_fp, n_auto_q = counts("auto")

    want_auto = (n_auto_fp > 0 and n_auto_q > 0) if on_tpu \
        else (n_auto_fp == 0 and n_auto_q == 0)
    ok = n_pallas_fp > 0 and n_pallas_q > 0 and want_auto
    backend = jax.default_backend()
    return AuditResult(
        "decode-kernel", ok,
        f"pallas: {n_pallas_fp} fp / {n_pallas_q} quantized pallas_call(s) "
        f"in the fused loop; auto on {backend}: {n_auto_fp} fp / "
        f"{n_auto_q} quantized"
        + ("" if ok else " — dispatch does not match the impl policy"),
        {"arch": arch, "backend": backend,
         "pallas_fp": n_pallas_fp, "pallas_quant": n_pallas_q,
         "auto_fp": n_auto_fp, "auto_quant": n_auto_q})


# -- A7: observability audit -------------------------------------------------

def audit_observability(arch: str = DEFAULT_ARCH, *, max_new_tokens: int = 8,
                        spec_k: int = 2) -> AuditResult:
    """Prove the observability layer is zero-overhead where it counts: the
    compiled decode and speculative-verify programs, and the scheduler's
    vmapped pool step (the program every benchmark cell times), are
    **byte-identical** with the full `repro.obs` stack enabled (live tracer
    + profiler annotations + metrics) vs absent.

    The layer's contract is host-side-only recording at step/drain
    boundaries — nothing it does may reach the traced computation.  A
    metric read that forced a reshape, an annotation that entered the
    jaxpr, or a tracer arg that materialized inside the loop would all
    change the compiled text; comparing the bytes catches every such leak
    at once."""
    from repro.obs import Observability, Tracer
    from repro.serving import (EngineSpec, GenerationConfig, InferenceEngine,
                               RequestScheduler, SpeculativeConfig)

    plain = tiny_engine(arch)
    obs = Observability(tracer=Tracer(), profile=True)
    traced = InferenceEngine.from_config(
        arch, EngineSpec(reduced=True, quantize=False), obs=obs)

    def lower_pool_step(e):
        sched = RequestScheduler(e, n_slots=2, cache_len=32, obs=e.obs)
        return sched._pool_step.lower(e.params, sched._tokens[32],
                                      sched.pool.get_store(32),
                                      sched._keys[32])

    gen = GenerationConfig(max_new_tokens=max_new_tokens)
    spec_gen = GenerationConfig(max_new_tokens=max_new_tokens,
                                speculative=SpeculativeConfig(k=spec_k))
    programs = (("decode", lambda e: e.lower_decode_loop(gen)),
                ("verify", lambda e: e.lower_spec_loop(spec_gen)),
                ("pool_step", lower_pool_step))
    diffs = []
    for name, lower in programs:
        base = _compiled_text(lower(plain))
        instr = _compiled_text(lower(traced))
        if base != instr:
            diffs.append(f"{name} ({len(base)} vs {len(instr)} bytes)")
    ok = not diffs
    return AuditResult(
        "observability", ok,
        "decode + verify + pool-step programs byte-identical with obs on "
        "vs off" if ok else f"observability changed compiled program(s): "
                            f"{', '.join(diffs)}",
        {"programs": [name for name, _ in programs], "diffs": diffs})


# -- A8: prefix-reuse audit ---------------------------------------------------

def audit_prefix_reuse(arch: str = KERNEL_ARCH, *, cache_len: int = 24,
                       chunk_size: int = 8) -> AuditResult:
    """Prove shared-prefix adoption (serving/paging.py) is invisible to the
    compiled programs:

      * the cache `PrefixCache` assembles for an adopted prefix has avals
        identical to a cold decode cache, so the decode step compiles to
        **byte-identical** HLO warm vs cold — adoption can never push the
        MVM phase onto a different (slower) program;
      * the suffix-only chunked prefill stays inside the cold admission's
        compiled chunk ladder (zero new prefill signatures) and every
        suffix chunk length still donates >= 90% of the resident cache
        bytes (the A2 guarantee survives a nonzero start offset);
      * a warm scheduler drain actually hits the index — the audit fails
        loudly if adoption silently degrades to cold admissions.
    """
    import jax
    import jax.numpy as jnp
    from repro.models import lm
    from repro.serving import (GenerationConfig, PrefixCache, Request,
                               RequestScheduler)

    engine = tiny_engine(arch)
    donor = jax.random.randint(jax.random.key(1), (1, 16), 1,
                               engine.cfg.vocab_size, dtype=jnp.int32)
    suffix = jax.random.randint(jax.random.key(2), (1, 5), 1,
                                engine.cfg.vocab_size, dtype=jnp.int32)
    query = jnp.concatenate([donor, suffix], axis=1)
    problems: list[str] = []

    # Register the donor's prefix, then adopt it for the query.
    _, donor_cache = engine.prefill_chunked(donor, cache_len=cache_len,
                                            chunk_size=chunk_size)
    pc = PrefixCache(engine.cfg, jnp.float32, enabled=True, page_size=4)
    pc.register(donor[0].tolist(), donor_cache, cache_len)
    p, warm = pc.lookup(query[0].tolist(), cache_len, slot=0,
                        chunk_size=chunk_size)
    if p != donor.shape[1]:
        problems.append(f"adopted {p}/{donor.shape[1]} donor tokens")

    # (1) Assembled-cache avals == cold-cache avals => the decode step
    # lowers and compiles to byte-identical HLO on either.
    cold = lm.make_decode_cache(engine.cfg, 1, cache_len, jnp.float32,
                                start_pos=p)
    shape_of = jax.eval_shape
    if shape_of(lambda: warm) != shape_of(lambda: cold):
        problems.append("assembled prefix cache avals differ from cold")
    tok = jnp.zeros((1, 1), jnp.int32)
    text_warm = _compiled_text(
        jax.jit(engine._decode_impl).lower(engine.params, tok, warm))
    text_cold = _compiled_text(
        jax.jit(engine._decode_impl).lower(engine.params, tok, cold))
    hlo_identical = text_warm == text_cold
    if not hlo_identical:
        problems.append(f"decode HLO differs warm vs cold "
                        f"({len(text_warm)} vs {len(text_cold)} bytes)")

    # (2) Suffix-only prefill: same compiled ladder as a cold admission of
    # the same prompt, and per-chunk donation still >= 90%.
    engine.prefill_chunked(query, cache_len=cache_len,
                           chunk_size=chunk_size)       # the cold ladder
    before = set(engine.prefill_shape_keys)
    cp = engine.begin_chunked_prefill(query, cache_len=cache_len,
                                      chunk_size=chunk_size,
                                      initial_cache=warm, start_offset=p)
    while not cp.done:
        cp.advance()
    new_keys = sorted(set(engine.prefill_shape_keys) - before)
    if new_keys:
        problems.append(f"adopted admission compiled new prefill "
                        f"signature(s): {new_keys}")
    fractions = {}
    for c in sorted(set(cp.schedule)):
        r = audit_donation(arch, chunk=c, cache_len=cache_len, engine=engine)
        fractions[c] = r.metrics["fraction"]
        if not r.ok:
            problems.append(f"suffix chunk {c}: only "
                            f"{r.metrics['fraction']:.1%} cache bytes donated")

    # (3) A warm scheduler drain hits the index end to end.
    sched = RequestScheduler(engine, n_slots=2, cache_len=2 * cache_len,
                             gen=GenerationConfig(max_new_tokens=4),
                             chunk_size=chunk_size, prefix_cache=True)
    sched.submit(Request(uid=0, prompt=donor[0].tolist()))
    sched.submit(Request(uid=1, prompt=query[0].tolist()))
    sched.run()
    st = sched.pool.prefix.stats
    if st["prefix_hits"] < 1:
        problems.append("warm scheduler drain never hit the prefix index")

    ok = not problems
    return AuditResult(
        "prefix-reuse", ok,
        f"adopted {p} tokens: decode HLO byte-identical, suffix chunks "
        f"{sorted(set(cp.schedule))} reuse the cold ladder with "
        f"{min(fractions.values()):.1%}+ cache bytes donated, "
        f"{st['prefix_hit_tokens']} tokens skipped in a scheduler drain"
        if ok else "; ".join(problems),
        {"arch": arch, "adopted_tokens": int(p),
         "hlo_identical": hlo_identical, "new_prefill_keys": new_keys,
         "suffix_donation": fractions,
         "sched_hits": st["prefix_hits"],
         "sched_hit_tokens": st["prefix_hit_tokens"]})


# -- driver ------------------------------------------------------------------

def run_audits(arch: str = DEFAULT_ARCH, *, mesh_spec: str = "2,2",
               max_len: int = 24) -> AuditReport:
    engine = tiny_engine(arch)
    results = [
        audit_recompiles(arch, max_len=max_len),
        audit_donation(arch, engine=engine),
        # quantized-layout donation needs an arch that *has* an attention
        # KV cache to encode; DEFAULT_ARCH (retnet) is attention-free.
        audit_donation(KERNEL_ARCH, cache_dtype="int8_tok"),
        audit_transfers(arch, engine=engine),
        audit_sharding(arch, mesh_spec=mesh_spec),
        audit_decode_kernel(),
        audit_observability(arch),
        # Prefix adoption needs a *pageable* (dense-attention) cache;
        # DEFAULT_ARCH (retnet) takes the snapshot path instead.
        audit_prefix_reuse(KERNEL_ARCH),
    ]
    return AuditReport(results)
