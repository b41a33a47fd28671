"""`InferenceEngine` — the one public entry point for serving a model.

Owns the full deployment chain the paper describes for its accelerator and
that every caller used to hand-wire:

    lm.init -> deploy.deploy_quantize -> HSAEngine -> jitted prefill/decode

plus a *fused* decode loop: instead of one Python-level ``jax.jit`` dispatch
per generated token (host-bound; the seed `generate()` re-built its jits per
call on top of that), the whole MVM phase runs as a single jitted
``lax.while_loop`` that samples, checks stop tokens, and advances the online
RoPE unit on-device.  Greedy decoding through the fused loop is token-
identical to the per-token Python loop (tests/test_serving_engine.py).

Usage::

    from repro.serving import EngineSpec, GenerationConfig, InferenceEngine

    engine = InferenceEngine.from_config("retnet-1.3b", EngineSpec(reduced=True))
    result = engine.generate(prompts, GenerationConfig(max_new_tokens=32))
    result.tokens        # [B, max_new_tokens], pad-filled after stop tokens
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import time
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat, configs
from repro.core.hsa import HSAConfig, HSAEngine
from repro.kernels import ops as kops
from repro.models import deploy, lm
from repro.models.config import InputShape, ModelConfig
from repro.obs import ENGINE_TRACK, Observability
from repro.runtime import sharding as shd
from repro.serving import speculative as spec_mod
from repro.serving.sampling import (GenerationConfig, SpeculativeConfig,
                                    sample)

Params = dict[str, Any]

# Prompt-length bucket ladder: prompts pad (bucketed) or decompose (chunked)
# to powers of two >= this floor, so K distinct lengths hit at most
# ~log2(max_len) cached prefill compiles instead of K.
MIN_BUCKET = 8


class CacheCapacityError(ValueError):
    """A request needs more KV slots than its cache provides.

    Raised at admission (`ChunkedPrefill`, `RequestScheduler.submit`)
    instead of letting the linear-cache decode path hit its slot clamp:
    `layers.gqa_decode` writes token ``pos`` at ``min(pos, C-1)``, so an
    overflowing request would silently overwrite its own last cache row
    on every subsequent step — degraded output, no error.
    """


def _jit_cache_size(fn) -> int:
    """Entries in one jitted callable's XLA compile cache; -1 when this jax
    does not expose it (the audit then falls back to the shape-key proxy)."""
    try:
        return int(fn._cache_size())
    except (AttributeError, TypeError):
        return -1


def pytree_nbytes(tree, *, per_device: bool = False) -> int:
    """Total bytes across a pytree's array (or ShapeDtypeStruct) leaves —
    the currency of the host-spill tier's transfer accounting.

    ``per_device=True`` reports what ONE chip holds: sharded leaves count
    their local shard (`sharding.shard_shape`) instead of the global array —
    the number that has to fit a single device's DRAM on a mesh.  Unsharded
    / abstract leaves count in full either way.
    """
    total = 0
    for leaf in jax.tree.leaves(tree):
        n = math.prod(leaf.shape)
        if per_device and getattr(leaf, "sharding", None) is not None:
            try:
                n = math.prod(leaf.sharding.shard_shape(leaf.shape))
            except (AttributeError, ValueError):
                pass                       # odd sharding: count globally
        total += n * jnp.dtype(leaf.dtype).itemsize
    return total


def bucket_length(s: int, min_bucket: int = MIN_BUCKET) -> int:
    """Smallest ladder bucket (power of two >= min_bucket) holding s tokens."""
    if s < 1:
        raise ValueError(f"prompt length must be >= 1, got {s}")
    b = min_bucket
    while b < s:
        b *= 2
    return b


def chunk_schedule(s: int, chunk_size: int,
                   min_bucket: int = MIN_BUCKET) -> list[int]:
    """Decompose a prompt of length s into exact ladder-sized chunks.

    Full `chunk_size` chunks, then the remainder split into descending
    powers of two (its binary decomposition) — no padding, so recurrent
    caches (RetNet state, Mamba h/conv) continue exactly, and the set of
    compiled chunk shapes stays <= log2(chunk_size) + 1 across *all* prompt
    lengths.  `min_bucket` is not applied here: exactness beats one or two
    extra tiny-chunk compiles.
    """
    if chunk_size < 1:
        raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
    sched = [chunk_size] * (s // chunk_size)
    rem = s % chunk_size
    p = 1
    while p <= rem:
        p *= 2
    p //= 2
    while rem:
        if p <= rem:
            sched.append(p)
            rem -= p
        p //= 2
    return sched


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """How to build the serving stack around a model config.

    The default is the paper's deployment: SmoothQuant-ready W8A8 prefill
    (MMM dataflow) and MXINT4 W4A8 decode (MVM dataflow) with the Eq. (4)
    fused RMSNorm and the online RoPE unit, `kernel_impl='auto'` picking the
    Pallas kernels on TPU and the jnp reference path elsewhere.
    """

    quantize: bool = True               # PTQ-deploy master weights
    prefill_format: str = "w8a8"        # 'w8a8' | 'fp'
    decode_format: str = "mxint4"       # 'mxint4' | 'w8a8' | 'fp'
    fuse_rmsnorm: bool = True           # C3 ablation switch
    kernel_impl: str = "auto"           # 'auto' | 'pallas' | 'ref'
    reduced: bool = False               # use cfg.reduced() (CPU-scale)
    seed: int = 0                       # init key when params aren't supplied

    def hsa_config(self) -> HSAConfig:
        fmt_p = self.prefill_format if self.quantize else "fp"
        fmt_d = self.decode_format if self.quantize else "fp"
        return HSAConfig(prefill_format=fmt_p, decode_format=fmt_d,
                         fuse_rmsnorm=self.fuse_rmsnorm,
                         kernel_impl=self.kernel_impl)


@dataclasses.dataclass
class GenerationResult:
    """Output of `InferenceEngine.generate`."""

    tokens: jax.Array        # i32 [B, max_new_tokens]; pad after stop token
    lengths: jax.Array       # i32 [B] — emitted tokens incl. the stop token
    prefill_s: float         # wall-clock MMM phase (includes compile on miss)
    decode_s: float          # wall-clock MVM phase
    # Speculative-path stats (zero on the plain fused loop):
    verify_steps: int = 0    # verify dispatches (weight-stream reads)
    accepted_drafts: int = 0  # drafted tokens the target model accepted
    drafted: int = 0         # total drafted tokens (verify_steps * k)

    @property
    def tokens_per_step(self) -> float:
        """Committed tokens per verify step (1.0 means no speculation win)."""
        if not self.verify_steps:
            return 1.0
        return 1.0 + self.accepted_drafts / self.verify_steps

    @property
    def acceptance_rate(self) -> float:
        return self.accepted_drafts / self.drafted if self.drafted else 0.0


class ChunkedPrefill:
    """One in-flight chunked prompt admission (MMM phase, cache-resident).

    Built by `InferenceEngine.begin_chunked_prefill`; the sequencer calls
    `advance()` once per cycle, so a long prompt overlaps ~n_chunks decode
    steps instead of blocking them.  After the final chunk, `logits` holds
    the last-token logits and `cache` the warm decode cache (identical — up
    to fp summation order — to a monolithic `prefill` of the same prompt).

    Shared-prefix adoption: with ``start_offset=p`` and an ``initial_cache``
    already warm over positions [0, p) (assembled by the pool's
    `PrefixCache` from shared pages or a snapshot), only the suffix
    ``tokens[:, p:]`` is scheduled — the first p tokens of prefill are
    skipped outright.  The chunk step reads its start position from the
    traced ``cache["pos"]``, so a nonzero offset reuses the same compiled
    ladder as a cold admission.  ``initial_cache`` is consumed (the chunk
    jit donates its cache argument): callers must hand in a private copy,
    never a shared/registered pytree.
    """

    def __init__(self, engine: "InferenceEngine", tokens: jax.Array,
                 cache_len: int, chunk_size: int, cache_dtype=jnp.float32,
                 *, initial_cache=None, start_offset: int = 0):
        tokens = jnp.asarray(tokens, jnp.int32)
        if tokens.ndim != 2:
            raise ValueError(f"tokens must be [B, S], got {tokens.shape}")
        s = tokens.shape[1]
        if s < 1:
            raise ValueError("prompt must have at least one token")
        if s > cache_len:
            raise CacheCapacityError(
                f"prompt ({s}) exceeds cache_len ({cache_len})")
        if not 0 <= start_offset < s:
            raise ValueError(f"start_offset ({start_offset}) must be in "
                             f"[0, prompt length {s})")
        if start_offset and initial_cache is None:
            raise ValueError("start_offset > 0 requires an initial_cache "
                             "warm over the adopted prefix")
        w = engine.cfg.sliding_window
        if w:
            chunk_size = min(chunk_size, w)   # ring scatter: chunk <= window
        self.engine = engine
        self.tokens = tokens
        self.schedule = chunk_schedule(s - start_offset, chunk_size)
        if initial_cache is None:
            initial_cache = lm.make_decode_cache(
                engine.cfg, tokens.shape[0], cache_len, cache_dtype,
                start_pos=0)
        self.cache = engine.shard_cache(initial_cache)
        self.cache_len = cache_len
        self.start_offset = start_offset
        self.logits: jax.Array | None = None
        self._off = start_offset
        self._next = 0

    @property
    def done(self) -> bool:
        return self._next >= len(self.schedule)

    @property
    def n_chunks(self) -> int:
        return len(self.schedule)

    def advance(self) -> jax.Array | None:
        """Run one chunk; returns the final logits once all chunks ran."""
        if self.done:
            return self.logits
        c = self.schedule[self._next]
        chunk = self.tokens[:, self._off:self._off + c]
        eng = self.engine
        eng.prefill_shape_keys.add(("chunk", c, self.cache_len))
        with eng.obs.span("engine.prefill_chunk", ENGINE_TRACK):
            self.logits, self.cache = eng._run_prefill_chunk(
                {"tokens": chunk}, self.cache)
        eng.obs.metrics.counter("engine.prefill_chunks").inc()
        eng.obs.metrics.histogram("engine.prefill_chunk_tokens").record(c)
        self._off += c
        self._next += 1
        return self.logits if self.done else None


class InferenceEngine:
    """Deployed model + HSA engine + jit-cached prefill / fused decode.

    Construct via `from_config`.  All jitted callables are built once per
    engine; repeated `generate` calls with the same shapes and
    `GenerationConfig` hit jax's compilation cache instead of re-tracing
    (the `GenerationConfig` itself is a hashable static argument).
    """

    def __init__(self, cfg: ModelConfig, params: Params, spec: EngineSpec,
                 hsa: HSAEngine | None = None, *, mesh: Mesh | None = None,
                 policy: "shd.ShardingPolicy | None" = None, cell=None,
                 obs: Observability | None = None):
        if mesh is not None:
            spec = dataclasses.replace(spec, kernel_impl=kops.mesh_impl(
                spec.kernel_impl, mesh.size))
        self.cfg = cfg
        self.spec = spec
        self.hsa = hsa or HSAEngine(spec.hsa_config())
        # Observability: host-side only (metrics registry + `obs.span`
        # around jit dispatch: tracer spans and profiler annotations).  The
        # A7 program audit proves the compiled decode/verify programs are
        # byte-identical whether this is the default bundle or a live tracer.
        self.obs = obs if obs is not None else Observability()

        # Multi-chip serving: with a mesh, the whole stack runs sharded —
        # params live under the `ServeCell` shardings, caches under
        # `lm.cache_axes` resolved by the same rules engine, and every jit
        # below is re-issued through `compat.jit_sharded` with explicit
        # in/out shardings (see `_sjit`), so prefill -> decode -> spill ->
        # resume never bounces through an unsharded host round trip.
        self.mesh = mesh
        self.cell = cell
        self.policy = None
        self._cache_axes = lm.cache_axes(cfg)
        if mesh is not None:
            self.policy = (policy or (cell.policy if cell is not None
                                      else None) or shd.ShardingPolicy())
            if cell is not None:
                self.param_shardings = cell.param_shardings
            else:
                axes = self._infer_param_axes(params)
                self.param_shardings = shd.tree_shardings(params, axes, mesh,
                                                          self.policy)
            params = jax.device_put(params, self.param_shardings)
            self._rep = NamedSharding(mesh, P())
            self._sjits: dict = {}
            self._sjit_entries: list[dict] = []
            self._csh_cache: dict = {}
        self.params = params

        self._prefill = jax.jit(self._prefill_impl,
                                static_argnames=("cache_len",
                                                 "return_hidden"))
        # The chunk step's resident cache is donated on every path: the
        # caller (ChunkedPrefill) rebinds to the returned cache, so the
        # input buffer is dead — donation makes the append in-place instead
        # of a full cache copy per chunk (the program audit's donation leg
        # verifies the compiled executable actually aliases it).
        self._prefill_chunk = jax.jit(self._prefill_chunk_impl,
                                      donate_argnums=(2,))
        self._decode = jax.jit(self._decode_impl)
        self._loop = jax.jit(self._loop_impl, static_argnames=("gen",))
        self._resume_loop = jax.jit(self._resume_loop_impl,
                                    static_argnames=("gen",))
        self._spec_loop = jax.jit(self._spec_loop_impl,
                                  static_argnames=("gen",))
        # Distinct prefill-entry shape keys = XLA compiles triggered by this
        # engine's admission paths (the bench/tests watch the ladder keep
        # this ~log-sized as distinct prompt lengths grow).
        self.prefill_shape_keys: set[tuple] = set()

    # -- construction -------------------------------------------------------

    @classmethod
    def from_config(cls, cfg: ModelConfig | str,
                    spec: EngineSpec = EngineSpec(), *,
                    params: Params | None = None,
                    linear_paths: list[tuple[str, ...]] | None = None,
                    mesh: Mesh | None = None,
                    policy: "shd.ShardingPolicy | None" = None,
                    obs: Observability | None = None,
                    ) -> "InferenceEngine":
        """Build the serving stack: init (or adopt) params, PTQ-deploy, wire
        the HSA engine.

        ``cfg`` may be an architecture name (``configs.get_config``) or a
        ready `ModelConfig`.  Pass ``params`` (+ the matching
        ``linear_paths`` from `lm.init`) to serve trained weights; otherwise
        fresh ones are initialized from ``spec.seed``.  Already-deployed
        trees (no master ``'w'`` under the lm_head) are adopted as-is.

        ``mesh`` switches the engine to multi-chip serving: a `ServeCell`
        plan is built (``engine.cell``), params are `jax.device_put` under
        its shardings, and every generate path (plain, chunked prefill,
        speculative, warm resume) runs with explicit in/out shardings on the
        mesh — greedy output stays token-identical to the single-device
        engine (tests/test_serving_sharded.py).
        """
        if isinstance(cfg, str):
            cfg = configs.get_config(cfg)
        if spec.reduced:
            cfg = cfg.reduced()

        if params is None:
            params, _, linear_paths = lm.init(cfg, jax.random.key(spec.seed))
        if spec.quantize and _is_master_tree(params):
            if linear_paths is None:
                _, _, linear_paths = lm.init(cfg, jax.random.key(spec.seed),
                                             abstract=True)
            params = deploy.deploy_quantize(params, linear_paths)
        cell = None
        if mesh is not None:
            from repro.serving import cell as cell_mod   # deferred: cycle
            spec = dataclasses.replace(spec, kernel_impl=kops.mesh_impl(
                spec.kernel_impl, mesh.size))
            cell = cell_mod.build_serve(
                cfg, mesh,
                InputShape("serve", seq_len=128, global_batch=1,
                           kind="decode"),
                policy=policy, kernel_impl=spec.kernel_impl,
                quantize=not _is_master_tree(params))
        return cls(cfg, params, spec, mesh=mesh, policy=policy, cell=cell,
                   obs=obs)

    # -- jitted building blocks --------------------------------------------

    def _prefill_impl(self, params, batch, cache_len: int,
                      return_hidden: bool = False):
        return lm.forward_prefill(params, batch, self.cfg, self.hsa,
                                  cache_len=cache_len,
                                  return_hidden=return_hidden)

    def _prefill_chunk_impl(self, params, batch, cache):
        return lm.forward_prefill_chunk(params, batch, cache, self.cfg,
                                        self.hsa)

    def _decode_impl(self, params, tokens, cache):
        return lm.forward_decode(params, tokens, cache, self.cfg, self.hsa)

    def _loop_impl(self, params, logits0, cache, key,
                   gen: GenerationConfig):
        """The fused MVM phase: sample/emit/stop/decode in one while_loop.

        Matches the reference Python loop exactly: ``out[:, i]`` is sampled
        from the logits *before* decode step ``i`` (the first token comes
        from the prefill logits), and the loop exits as soon as every
        sequence has hit a stop token — the remaining slots stay
        ``pad_token_id``.
        """
        key, sub = jax.random.split(key)
        tok0 = sample(logits0, gen.sampling, sub)
        return self._loop_from(params, tok0, cache, key, gen)

    def _resume_loop_impl(self, params, tok0, cache, key,
                          gen: GenerationConfig):
        """The fused loop entered from a *pending token* instead of prefill
        logits — the host-spill warm-resume path: the first emitted token is
        ``tok0`` itself (it was sampled before the preemption)."""
        return self._loop_from(params, tok0, cache, key, gen)

    def _loop_from(self, params, tok0, cache, key, gen: GenerationConfig):
        b = tok0.shape[0]
        n = gen.max_new_tokens
        stop = (jnp.asarray(gen.stop_tokens, jnp.int32)
                if gen.stop_tokens else None)

        def hit_stop(tok):                       # tok i32 [B]
            if stop is None:
                return jnp.zeros((b,), bool)
            return jnp.any(tok[:, None] == stop[None, :], axis=-1)

        out0 = jnp.full((b, n), gen.pad_token_id, jnp.int32)
        state = (jnp.int32(0), tok0, cache, jnp.zeros((b,), bool), out0,
                 jnp.zeros((b,), jnp.int32), key)

        def cond(st):
            i, _, _, done, _, _, _ = st
            return (i < n) & ~jnp.all(done)

        def body(st):
            i, tok, cache, done, out, lengths, key = st
            out = out.at[:, i].set(jnp.where(done, gen.pad_token_id, tok))
            lengths = lengths + (~done).astype(jnp.int32)
            done = done | hit_stop(tok)
            logits, cache = lm.forward_decode(params, tok[:, None], cache,
                                              self.cfg, self.hsa)
            key, sub = jax.random.split(key)
            tok = sample(logits, gen.sampling, sub)
            return (i + 1, tok, cache, done, out, lengths, key)

        _, _, cache, _, out, lengths, _ = jax.lax.while_loop(cond, body, state)
        return out, lengths, cache

    def _spec_loop_impl(self, params, logits0, hidden0, hist0, hist_len0,
                        cache, key, gen: GenerationConfig):
        """The speculative MVM phase: draft k / verify-in-one-MMM-dispatch /
        commit-with-rollback, emitting 1..k+1 tokens per while_loop step
        (serving/speculative.py)."""
        return spec_mod.speculative_loop(params, logits0, hidden0, hist0,
                                         hist_len0, cache, key, cfg=self.cfg,
                                         hsa=self.hsa, gen=gen)

    # -- multi-chip placement -----------------------------------------------

    def _infer_param_axes(self, params: Params) -> Params:
        """Logical axes matching ``params``' deployment state (master fp
        tree vs PTQ-deployed tree) — used when no `ServeCell` was built."""
        _, axes, paths = lm.init(self.cfg, jax.random.key(self.spec.seed),
                                 abstract=True)
        if not _is_master_tree(params):
            axes = deploy.deployed_axes(axes, paths)
        return axes

    @property
    def sharded(self) -> bool:
        return self.mesh is not None

    def cache_shardings(self, cache: Params) -> Params:
        """NamedSharding tree for a cache pytree under the cell's policy
        (`lm.cache_axes` through the divisibility-fallback rules engine).

        Memoized by (treedef, leaf shapes) — the full resolution is a
        Python tree walk, and per-token callers (`decode_step`) would
        otherwise pay it on every emitted token.
        """
        leaves, treedef = jax.tree.flatten(cache)
        key = (treedef, tuple(jnp.shape(l) for l in leaves))
        sh = self._csh_cache.get(key)
        if sh is None:
            sh = shd.tree_shardings(cache, self._cache_axes, self.mesh,
                                    self.policy)
            self._csh_cache[key] = sh
        return sh

    def shard_cache(self, cache: Params) -> Params:
        """Place a cache pytree on the mesh (no-op on a single device)."""
        if self.mesh is None:
            return cache
        return jax.device_put(cache, self.cache_shardings(cache))

    def _trace_ctx(self):
        """Sharding context active while a sharded jit traces, so model-
        internal logical constraints (`shd.constrain`) resolve on-mesh."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return shd.sharding_ctx(self.mesh, self.policy)

    def _sjit(self, name, impl, in_shardings, out_shardings, *,
              donate_argnums=()):
        """`compat.jit_sharded` with a per-placement cache: one jit object
        (hence one XLA compile cache) per distinct (name, shardings) key, so
        varying cache shapes reuse jits whenever they resolve to the same
        placement instead of re-tracing every call.

        ``impl`` must take positional dynamic args only (pjit rejects kwargs
        under explicit in_shardings) — static knobs are pre-bound with
        `functools.partial` and folded into ``name``.
        """
        key = (name, donate_argnums, shd.shardings_key(in_shardings),
               shd.shardings_key(out_shardings))
        fn = self._sjits.get(key)
        if fn is None:
            fn = compat.jit_sharded(impl, in_shardings=in_shardings,
                                    out_shardings=out_shardings,
                                    donate_argnums=donate_argnums)
            self._sjits[key] = fn
            # Introspection registry: what this entry point promised the
            # mesh (repro.analysis.program_audit replays these against the
            # ServeCell plan — the sharding audit).
            self._sjit_entries.append({
                "name": name if isinstance(name, tuple) else (name,),
                "fn": fn,
                "in_shardings": in_shardings,
                "out_shardings": out_shardings,
                "donate_argnums": donate_argnums,
            })
        return fn

    def _batch_shardings(self, batch: Params) -> Params:
        """Input placement for a token batch: leading dim over the DP axes
        where divisible (B=1 serving falls through to replicated)."""
        return shd.shardings_from_specs(
            shd.batch_specs(batch, self.mesh, self.policy), self.mesh)

    def _sharded_prefill(self, batch: Params, cache_len: int,
                         return_hidden: bool):
        impl = functools.partial(self._prefill_impl, cache_len=cache_len,
                                 return_hidden=return_hidden)
        with self._trace_ctx():
            out_abs = jax.eval_shape(impl, self.params, batch)
        csh = self.cache_shardings(out_abs[1])
        out_sh = (self._rep, csh) + ((self._rep,) if return_hidden else ())
        fn = self._sjit(("prefill", cache_len, return_hidden), impl,
                        (self.param_shardings, self._batch_shardings(batch)),
                        out_sh)
        with self._trace_ctx():
            return fn(self.params, batch)

    def _run_prefill_chunk(self, batch: Params, cache: Params):
        """Chunk step dispatcher: sharded, the resident cache is a donated
        arg with matching in/out shardings (in-place on-mesh append)."""
        if self.mesh is None:
            return self._prefill_chunk(self.params, batch, cache)
        csh = self.cache_shardings(cache)
        fn = self._sjit("prefill_chunk", self._prefill_chunk_impl,
                        (self.param_shardings, self._batch_shardings(batch),
                         csh),
                        (self._rep, csh), donate_argnums=(2,))
        with self._trace_ctx():
            return fn(self.params, batch, cache)

    def _run_loop(self, logits0, cache, key, gen: GenerationConfig):
        if self.mesh is None:
            return self._loop(self.params, logits0, cache, key, gen=gen)
        csh = self.cache_shardings(cache)
        fn = self._sjit(("loop", gen),
                        functools.partial(self._loop_impl, gen=gen),
                        (self.param_shardings, self._rep, csh, self._rep),
                        (self._rep, self._rep, csh))
        with self._trace_ctx():
            return fn(self.params, logits0, cache, key)

    def _run_resume_loop(self, tok0, cache, key, gen: GenerationConfig):
        if self.mesh is None:
            return self._resume_loop(self.params, tok0, cache, key, gen=gen)
        cache = self.shard_cache(cache)       # e.g. fetched from host tier
        csh = self.cache_shardings(cache)
        fn = self._sjit(("resume_loop", gen),
                        functools.partial(self._resume_loop_impl, gen=gen),
                        (self.param_shardings, self._rep, csh, self._rep),
                        (self._rep, self._rep, csh))
        with self._trace_ctx():
            return fn(self.params, tok0, cache, key)

    def _run_spec_loop(self, logits0, hidden0, hist0, hist_len0, cache, key,
                       gen: GenerationConfig):
        if self.mesh is None:
            return self._spec_loop(self.params, logits0, hidden0, hist0,
                                   hist_len0, cache, key, gen=gen)
        csh = self.cache_shardings(cache)
        rep = self._rep
        fn = self._sjit(("spec_loop", gen),
                        functools.partial(self._spec_loop_impl, gen=gen),
                        (self.param_shardings, rep, rep, rep, rep, csh, rep),
                        (rep, rep, csh, rep, rep))
        with self._trace_ctx():
            return fn(self.params, logits0, hidden0, hist0, hist_len0,
                      cache, key)

    # -- introspection hooks (repro.analysis) --------------------------------

    @property
    def prefill_compiles(self) -> int:
        """Distinct prefill shapes this engine has dispatched (compile proxy)."""
        return len(self.prefill_shape_keys)

    def jit_entries(self) -> list[dict]:
        """The sharded jit registry: one record per distinct `_sjit` entry
        (name tuple, jit object, in/out shardings, donated argnums).  Empty
        on a single-device engine.  The program audit's sharding leg checks
        every record against the `ServeCell` plan."""
        return list(getattr(self, "_sjit_entries", ()))

    def compile_counts(self) -> dict[str, int]:
        """Compiled-signature count per entry point — the real XLA compile
        cache sizes, not the shape-key proxy.  Sharded entry points
        aggregate over their `_sjit` placements under the same root name
        (``prefill``, ``prefill_chunk``, ``decode``, ``loop``, ...), so the
        number means the same thing on one chip and on a mesh.

        `bench_serving` records this next to every trajectory point; the
        recompile audit asserts it stays O(log max_len) under the ladder.
        """
        counts: dict[str, int] = {}
        for root, fn in (("prefill", self._prefill),
                         ("prefill_chunk", self._prefill_chunk),
                         ("decode", self._decode),
                         ("loop", self._loop),
                         ("resume_loop", self._resume_loop),
                         ("spec_loop", self._spec_loop)):
            counts[root] = _jit_cache_size(fn)
        for entry in self.jit_entries():
            root = entry["name"][0]
            root = root if isinstance(root, str) else str(root)
            counts[root] = counts.get(root, 0) + _jit_cache_size(entry["fn"])
        return counts

    def _abstract_prefill(self, s_in: int, cache_len: int, *,
                          return_hidden: bool = False, batch: int = 1):
        """(logits, cache[, hidden]) ShapeDtypeStructs of a prefill — the
        abstract operands the lowering hooks below feed the hot-path jits."""
        tokens = jax.ShapeDtypeStruct((batch, s_in), jnp.int32)
        impl = functools.partial(self._prefill_impl, cache_len=cache_len,
                                 return_hidden=return_hidden)
        with self._trace_ctx():
            return jax.eval_shape(impl, self.params, {"tokens": tokens})

    def lower_prefill_chunk(self, *, batch: int = 1, chunk: int = 16,
                            cache_len: int = 64, cache_dtype=jnp.float32):
        """Lowering of the chunked-prefill step on abstract operands.

        The donation audit compiles this and verifies the executable aliases
        the resident cache's buffers (input_output_alias) instead of
        silently copying a whole cache per chunk.
        """
        tokens = {"tokens": jax.ShapeDtypeStruct((batch, chunk), jnp.int32)}
        cache = jax.eval_shape(
            lambda: lm.make_decode_cache(self.cfg, batch, cache_len,
                                         cache_dtype, start_pos=0))
        if self.mesh is None:
            return self._prefill_chunk.lower(self.params, tokens, cache)
        csh = self.cache_shardings(cache)
        fn = self._sjit("prefill_chunk", self._prefill_chunk_impl,
                        (self.param_shardings, self._batch_shardings(tokens),
                         csh),
                        (self._rep, csh), donate_argnums=(2,))
        with self._trace_ctx():
            return fn.lower(self.params, tokens, cache)

    def lower_decode_loop(self, gen: GenerationConfig, *, batch: int = 1,
                          s_in: int = 8, cache_len: int | None = None):
        """Lowering of the fused decode ``while_loop`` on abstract operands
        (the transfer audit scans its HLO for host callbacks / transfers)."""
        cache_len = cache_len or s_in + gen.max_new_tokens
        logits, cache = self._abstract_prefill(s_in, cache_len, batch=batch)
        key = jax.eval_shape(lambda: jax.random.key(0))
        if self.mesh is None:
            return self._loop.lower(self.params, logits, cache, key, gen=gen)
        csh = self.cache_shardings(cache)
        fn = self._sjit(("loop", gen),
                        functools.partial(self._loop_impl, gen=gen),
                        (self.param_shardings, self._rep, csh, self._rep),
                        (self._rep, self._rep, csh))
        with self._trace_ctx():
            return fn.lower(self.params, logits, cache, key)

    def lower_spec_loop(self, gen: GenerationConfig, *, batch: int = 1,
                        s_in: int = 8):
        """Lowering of the speculative draft/verify ``while_loop`` on
        abstract operands — the verify-path twin of `lower_decode_loop`."""
        if gen.speculative is None:
            raise ValueError("lower_spec_loop needs gen.speculative")
        k = gen.speculative.k
        cache_len = s_in + gen.max_new_tokens + k
        logits, cache, hidden = self._abstract_prefill(
            s_in, cache_len, return_hidden=True, batch=batch)
        hist = jax.ShapeDtypeStruct(
            (batch, s_in + gen.max_new_tokens + k + 1), jnp.int32)
        hist_len = jax.ShapeDtypeStruct((), jnp.int32)
        key = jax.eval_shape(lambda: jax.random.key(0))
        if self.mesh is None:
            return self._spec_loop.lower(self.params, logits, hidden, hist,
                                         hist_len, cache, key, gen=gen)
        csh = self.cache_shardings(cache)
        rep = self._rep
        fn = self._sjit(("spec_loop", gen),
                        functools.partial(self._spec_loop_impl, gen=gen),
                        (self.param_shardings, rep, rep, rep, rep, csh, rep),
                        (rep, rep, csh, rep, rep))
        with self._trace_ctx():
            return fn.lower(self.params, logits, hidden, hist, hist_len,
                            cache, key)

    # -- public API ---------------------------------------------------------

    def prefill(self, tokens: jax.Array, *, cache_len: int | None = None,
                extras: Params | None = None, bucket: bool = False,
                return_hidden: bool = False):
        """MMM phase: prompts [B, S] -> (last-token logits [B, V], caches).

        ``bucket=True`` pads the prompt up to the power-of-two ladder and
        passes the real length in as a *traced* scalar, so every prompt
        length in a bucket shares one compile; logits/cache positions are
        taken at the real prompt end (token-identical to the exact-length
        call).  ``cache_len`` is rounded up onto the same ladder — it is a
        static jit argument, so a per-request value (prompt + budget) would
        otherwise re-trigger one compile per length, defeating the bucket.
        The cache is at least bucket-sized so the padded tail stays
        addressable (decode masks, then overwrites it).
        """
        tokens = jnp.asarray(tokens, jnp.int32)
        s = tokens.shape[1]
        batch = {"tokens": tokens, **(extras or {})}
        if bucket:
            b = bucket_length(s)
            if b > s:
                batch["tokens"] = jnp.pad(tokens, ((0, 0), (0, b - s)))
            batch["prompt_len"] = jnp.int32(s)
            cache_len = bucket_length(max(cache_len or s, b))
            self.prefill_shape_keys.add(("bucket", b, cache_len))
        else:
            cache_len = cache_len or s
            self.prefill_shape_keys.add(("prefill", s, cache_len))
        if self.mesh is not None:
            return self._sharded_prefill(batch, cache_len, return_hidden)
        return self._prefill(self.params, batch, cache_len=cache_len,
                             return_hidden=return_hidden)

    def decode_step(self, tokens: jax.Array, cache: Params
                    ) -> tuple[jax.Array, Params]:
        """One MVM step: tokens [B, 1] + warm cache -> (logits [B, V], cache)."""
        if self.mesh is None:
            return self._decode(self.params, tokens, cache)
        csh = self.cache_shardings(cache)
        fn = self._sjit("decode", self._decode_impl,
                        (self.param_shardings, self._rep, csh),
                        (self._rep, csh))
        with self._trace_ctx():
            return fn(self.params, tokens, cache)

    def begin_chunked_prefill(self, tokens: jax.Array, *, cache_len: int,
                              chunk_size: int = 32, cache_dtype=jnp.float32,
                              initial_cache=None,
                              start_offset: int = 0) -> ChunkedPrefill:
        """Start a chunk-granular admission; the caller paces `advance()`.

        ``initial_cache``/``start_offset`` adopt an already-warm prefix:
        only ``tokens[:, start_offset:]`` is prefilled (see ChunkedPrefill).
        """
        return ChunkedPrefill(self, tokens, cache_len, chunk_size, cache_dtype,
                              initial_cache=initial_cache,
                              start_offset=start_offset)

    def prefill_chunked(self, tokens: jax.Array, *, cache_len: int,
                        chunk_size: int = 32, cache_dtype=jnp.float32
                        ) -> tuple[jax.Array, Params]:
        """Drive a chunked prefill to completion: (last logits [B,V], cache)."""
        cp = self.begin_chunked_prefill(tokens, cache_len=cache_len,
                                        chunk_size=chunk_size,
                                        cache_dtype=cache_dtype)
        while not cp.done:
            cp.advance()
        return cp.logits, cp.cache

    def generate(self, prompts: jax.Array,
                 gen: GenerationConfig = GenerationConfig(), *,
                 key: jax.Array | None = None,
                 extras: Params | None = None,
                 speculative: SpeculativeConfig | None = None
                 ) -> GenerationResult:
        """Prefill + fused decode.  prompts [B, S_in] -> GenerationResult.

        ``key`` seeds stochastic sampling; it is ignored under greedy
        decoding and defaults to a fixed key so greedy calls never touch
        host RNG state.  ``speculative`` (or ``gen.speculative``) switches
        the MVM phase to the multi-token draft/verify loop; greedy output is
        token-identical to the plain loop, stochastic output is distributed
        identically (see serving/speculative.py).
        """
        prompts = jnp.asarray(prompts, jnp.int32)
        if speculative is not None:
            gen = dataclasses.replace(gen, speculative=speculative)
        if gen.speculative is not None:
            return self._generate_speculative(prompts, gen, key=key,
                                              extras=extras)
        cache_len = prompts.shape[1] + gen.max_new_tokens
        if key is None:
            key = jax.random.key(0)

        with self.obs.span("engine.generate", ENGINE_TRACK):
            t0 = time.perf_counter()
            with self.obs.span("engine.prefill", ENGINE_TRACK):
                logits, cache = self.prefill(prompts, cache_len=cache_len,
                                             extras=extras)
                cache = self._encode_cache(cache, gen)
                jax.block_until_ready(logits)
            t_prefill = time.perf_counter() - t0

            t0 = time.perf_counter()
            with self.obs.span("engine.decode_loop", ENGINE_TRACK):
                tokens, lengths, _ = self._run_loop(logits, cache, key, gen)
                jax.block_until_ready(tokens)
            t_decode = time.perf_counter() - t0
            self.obs.tracer.instant("finish", ENGINE_TRACK, lengths=lengths,
                                    batch=prompts.shape[0],
                                    prompt_len=prompts.shape[1])
        res = GenerationResult(tokens=tokens, lengths=lengths,
                               prefill_s=t_prefill, decode_s=t_decode)
        self._observe_generate(res)
        return res

    def resume_generate(self, pending: jax.Array, cache: Params,
                        gen: GenerationConfig = GenerationConfig(), *,
                        key: jax.Array | None = None) -> GenerationResult:
        """Warm-resume the fused MVM loop from a pending token and a warm
        decode cache — the host-spill re-entry point: no prefill runs and no
        prefill shape compiles; the cache (e.g. fetched back from the pool's
        host tier) is consumed as-is.

        ``pending`` is i32 ``[B]`` (or a scalar for a batch-1 cache): the
        token sampled *before* the interruption, which becomes the first
        emitted token — matching the fused loop's convention that step i's
        token was sampled from step i-1's logits.  Under greedy decoding the
        resumed stream is token-identical to the uninterrupted run.
        """
        pending = jnp.asarray(pending, jnp.int32)
        if pending.ndim == 0:
            pending = pending[None]
        if key is None:
            key = jax.random.key(0)
        t0 = time.perf_counter()
        with self.obs.span("engine.resume_loop", ENGINE_TRACK):
            tokens, lengths, _ = self._run_resume_loop(pending, cache, key,
                                                       gen)
            jax.block_until_ready(tokens)
        res = GenerationResult(tokens=tokens, lengths=lengths,
                               prefill_s=0.0,
                               decode_s=time.perf_counter() - t0)
        self._observe_generate(res)
        return res

    def _observe_generate(self, res: GenerationResult) -> None:
        """Record one finished generate into the engine's metrics registry.

        Runs strictly *after* the fused loop's `block_until_ready`, so the
        `lengths` read costs a drained-buffer copy, not a new device sync.
        The fused loop commits every token in one dispatch, so TTFT at this
        level is the prefill wall, and inter-token latency the decode wall
        per loop iteration (iterations = the longest sequence emitted).
        """
        m = self.obs.metrics
        b = res.tokens.shape[0]
        emitted = int(jnp.sum(res.lengths))
        steps = int(jnp.max(res.lengths))
        m.counter("engine.requests").inc(b)
        m.counter("engine.emitted").inc(emitted)
        if res.prefill_s:
            m.histogram("engine.ttft_s").record(res.prefill_s)
        m.histogram("engine.decode_s").record(res.decode_s)
        if steps > 0:
            m.histogram("engine.inter_token_s").record(res.decode_s / steps)
        if res.verify_steps:
            m.counter("engine.verify_steps").inc(res.verify_steps)
            m.counter("engine.accepted_drafts").inc(res.accepted_drafts)
            m.histogram("engine.tokens_per_verify_step").record(
                res.tokens_per_step)

    def _encode_cache(self, cache: Params, gen: GenerationConfig) -> Params:
        """Apply ``gen.cache_format`` at the prefill/decode boundary: the
        MMM phase ran fp, the MVM residency streams packed bytes.  No-op
        when the request keeps the fp cache."""
        if gen.cache_format is None:
            return cache
        cache = lm.quantize_cache(cache, self.cfg, gen.cache_format)
        return self.shard_cache(cache)

    def cache_nbytes(self, cache_len: int, *, batch: int = 1,
                     dtype=jnp.float32) -> int:
        """Bytes of one decode cache at ``cache_len`` — what a `CachePool`
        lane holds on device and what one host spill moves.  Computed from
        abstract shapes (`jax.eval_shape`): no cache is materialized."""
        tree = jax.eval_shape(
            lambda: lm.make_decode_cache(self.cfg, batch, cache_len, dtype))
        return pytree_nbytes(tree)

    def _generate_speculative(self, prompts: jax.Array, gen: GenerationConfig,
                              *, key: jax.Array | None = None,
                              extras: Params | None = None
                              ) -> GenerationResult:
        spec = gen.speculative
        cfg = self.cfg
        if cfg.is_encdec or cfg.frontend:
            raise NotImplementedError("speculative decode targets text "
                                      "decoder-only models")
        if cfg.sliding_window and spec.k + 1 > cfg.sliding_window:
            raise ValueError(
                f"verify block k+1 ({spec.k + 1}) must fit the sliding "
                f"window ({cfg.sliding_window}): a larger block would "
                "overwrite its own ring writes")
        b, s_in = prompts.shape
        n = gen.max_new_tokens
        # Verify may append up to k tokens past the last committed budget
        # position before rolling back — reserve them.
        cache_len = s_in + n + spec.k
        if key is None:
            key = jax.random.key(0)

        with self.obs.span("engine.generate", ENGINE_TRACK):
            t0 = time.perf_counter()
            with self.obs.span("engine.prefill", ENGINE_TRACK):
                logits, cache, hidden = self.prefill(prompts,
                                                     cache_len=cache_len,
                                                     extras=extras,
                                                     return_hidden=True)
                cache = self._encode_cache(cache, gen)
                jax.block_until_ready(logits)
            t_prefill = time.perf_counter() - t0

            hist0 = jnp.zeros((b, s_in + n + spec.k + 1),
                              jnp.int32).at[:, :s_in].set(prompts)
            t0 = time.perf_counter()
            with self.obs.span("engine.spec_loop", ENGINE_TRACK):
                tokens, lengths, _, steps, accepted = self._run_spec_loop(
                    logits, hidden, hist0, jnp.int32(s_in), cache, key, gen)
                jax.block_until_ready(tokens)
            t_decode = time.perf_counter() - t0
            steps, accepted = int(steps), int(accepted)
            self.obs.tracer.instant("finish", ENGINE_TRACK, batch=b,
                                    prompt_len=s_in, k=spec.k,
                                    verify_steps=steps,
                                    accepted_drafts=accepted)
        res = GenerationResult(tokens=tokens, lengths=lengths,
                               prefill_s=t_prefill, decode_s=t_decode,
                               verify_steps=steps, accepted_drafts=accepted,
                               drafted=steps * spec.k)
        self._observe_generate(res)
        return res


def _is_master_tree(params: Params) -> bool:
    """True when the tree still carries master linear weights (pre-deploy)."""
    head = params.get("lm_head")
    return isinstance(head, dict) and "w" in head and "w8_vals" not in head
