"""Continuous-batching request scheduler over a paged, slot-based cache pool.

Mirrors the HSA sequencer (paper Sec. IV): the engine's *prefill* path (MMM
dataflow) admits new requests into free cache slots while the resident slots
advance through the *decode* path (MVM dataflow) one token per step.  Two
refinements over the original slot pool make the admission path match the
paper's LISO scenario (750-token prompts entering a busy decode batch):

  * **Chunk-granular admission** — `_admit` advances at most ONE prefill
    chunk per `step()` (`InferenceEngine.begin_chunked_prefill`), so a long
    prompt overlaps ~n_chunks decode cycles instead of stalling every lane
    for one monolithic MMM pass, and the ladder-sized chunks keep the number
    of compiled prefill shapes logarithmic in prompt length.

  * **Paged pool** — `CachePool` holds *classes* of slots (small/medium/
    large cache lengths over the same stacked-pytree layout) instead of one
    global `cache_len`; admission picks the smallest class that fits
    ``prompt + budget``, so short requests stop paying the longest request's
    KV memory.

  * **Host spill tier + preemption** — the capacity rung below the device
    slots is host DRAM (the paper's edge memory hierarchy): `CachePool.spill`
    parks a slot's whole cache pytree in host memory bit-exactly and frees
    its lane, `fetch` restores it, and with ``host_spill=True`` the
    scheduler preempts the lowest-priority resident lane when a
    higher-priority request finds the pool full — oversubscription instead
    of a hard admission failure.

`CachePool` builds each class over `lm.make_decode_cache`: every per-model
cache kind (KV rings, MXINT4-decoded MoE experts, Mamba conv state, RetNet's
O(1) retention state, the online RoPE angle memory, the per-sequence
position) is just a pytree leaf with a leading ``[n_slots]`` axis.  The
decode step vmaps `lm.forward_decode` over that axis — one dispatch per
*class* with at least one resident request (free lanes still compute garbage
that is never read: one compiled shape per class, no re-trace as occupancy
fluctuates, the same trade the fixed-size PE array makes in silicon).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import lm
from repro.obs import SCHED_TRACK, Observability, request_track
from repro.serving import speculative as spec_mod
from repro.serving.engine import (CacheCapacityError, InferenceEngine,
                                  pytree_nbytes)
from repro.serving.sampling import GenerationConfig, sample

Params = dict[str, Any]


@dataclasses.dataclass
class Request:
    """One generation request; `max_new_tokens` overrides the scheduler's.

    ``priority``: higher admits first; FIFO among equal priorities (0 is the
    default class, negative deprioritizes).
    """

    uid: int
    prompt: Any                          # int sequence [S_in]
    max_new_tokens: int | None = None
    priority: int = 0


@dataclasses.dataclass
class FinishedRequest:
    uid: int
    prompt_len: int
    tokens: list[int]                    # emitted tokens incl. any stop token
    slot: int                            # pool slot handle (for tests/stats)
    cache_len: int = 0                   # cache class the request ran in
    cancelled: bool = False              # retired early via `cancel(uid)`
    # Speculative-decode stats (zero on the per-token path):
    verify_steps: int = 0                # verify dispatches while resident
    accepted_drafts: int = 0             # drafted tokens verification accepted

    @property
    def tokens_per_step(self) -> float:
        if not self.verify_steps:
            return 1.0
        return 1.0 + self.accepted_drafts / self.verify_steps


class CachePool:
    """Paged decode-cache pool: slot *classes* of increasing cache length,
    backed by a device tier and a host (CPU DRAM) spill tier.

    ``classes`` is a sequence of ``(n_slots, cache_len)`` pairs; the legacy
    single-class form ``CachePool(cfg, n_slots, cache_len)`` still works.
    Each class is one stacked pytree (``[n_slots_c, ...]`` per leaf) over
    `lm.make_decode_cache` (batch=1 per slot), so the slot layout is
    identical for every cache kind the model zoo produces.  Prefilled
    batch-1 caches are scattered into a slot with ``write``; the scheduler
    advances each class in one vmapped decode step.

    Slot ids are *request-lifetime handles*, not lane indices: ``acquire``
    binds a fresh id to a free device lane in the smallest fitting class,
    ``spill`` moves the slot's whole cache pytree to host memory via
    ``jax.device_put`` (freeing the lane for another request — this is what
    lets the pool oversubscribe its device capacity), and ``fetch`` binds a
    free lane again and restores the cache bit-exactly.  ``residency(slot)``
    reports which tier a slot lives in; ``spill_stats`` counts spills,
    fetches, and bytes moved each way.

    With ``prefix_cache=True`` the pool also owns a `PrefixCache`
    (serving/paging.py): a refcounted-page radix index (or whole-cache
    snapshot index on recurrent archs) over finished prompt prefixes.
    ``prefix_lookup`` at admission returns an adopted-prefix length plus an
    assembled warm cache; ``prefix_register`` indexes a finished prefill;
    page leases are tied to slot ids and dropped in ``release``;
    ``prefix_maintain`` runs the LRU/host-migration cycle once per
    scheduler step.
    """

    def __init__(self, cfg, n_slots: int | None = None,
                 cache_len: int | None = None, *,
                 classes: Sequence[tuple[int, int]] | None = None,
                 dtype=jnp.float32, mesh=None, policy=None,
                 obs: Observability | None = None,
                 prefix_cache: bool = False, prefix_page_size: int = 16,
                 max_prefix_pages: int | None = None,
                 device_prefix_pages: int | None = None):
        if classes is None:
            classes = [(n_slots if n_slots is not None else 4,
                        cache_len if cache_len is not None else 128)]
        classes = sorted(classes, key=lambda c: c[1])
        if not classes or any(n < 1 or length < 1 for n, length in classes):
            raise ValueError(f"bad cache classes: {classes}")
        if len({length for _, length in classes}) != len(classes):
            raise ValueError(f"duplicate class cache_len: {classes}")
        self.cfg = cfg
        self.classes = [(int(n), int(length)) for n, length in classes]
        self.n_slots = sum(n for n, _ in self.classes)
        self.cache_len = self.classes[-1][1]      # largest class (compat)
        self.dtype = dtype

        # Multi-chip pool: with a mesh, every class store lives sharded under
        # `lm.cache_axes` (lane axis never sharded — it is addressing, not
        # distribution), spill gathers a slot to host memory, and fetch
        # re-places it under the same cache shardings bit-exactly.
        from repro.runtime import sharding as shd
        self.mesh = mesh
        self.policy = (policy or shd.ShardingPolicy()) if mesh is not None \
            else None
        self._axes = lm.cache_axes(cfg)
        self._store_shardings: dict[int, Params] = {}

        self._stores: dict[int, Params] = {}
        self._lanes: dict[int, list[int]] = {}          # clen -> free lanes
        self._lane_of: dict[int, tuple[int, int]] = {}  # sid -> (clen, lane)
        self._class_of: dict[int, int] = {}             # live sid -> clen
        self._host: dict[int, Params] = {}              # sid -> host cache
        # Slot ids are issued monotonically, so "released" vs "unknown" is a
        # generation check against _next_sid — no per-request tombstones, so
        # a long-running pool's bookkeeping stays O(live slots).
        self._next_sid = 0
        for n, clen in self.classes:
            template = lm.make_decode_cache(cfg, 1, clen, dtype)
            store = jax.tree.map(
                lambda x: jnp.zeros((n,) + x.shape, x.dtype), template)
            if mesh is not None:
                sh = shd.tree_shardings(store, shd.stacked_axes(self._axes),
                                        mesh, self.policy)
                store = jax.device_put(store, sh)
                self._store_shardings[clen] = sh
            self._stores[clen] = store
            self._lanes[clen] = list(range(n))
        # The spill target: host CPU memory.  On a CPU-only backend the
        # "transfer" is a same-device copy — the tiering logic (and its
        # bit-exactness) is identical, which is what the tests pin.  With
        # no CPU backend at all there is no host tier: `host_device` raises
        # rather than letting a "spill" stay on the accelerator.
        try:
            self._host_device = jax.devices("cpu")[0]
        except RuntimeError:                             # no cpu backend
            self._host_device = None
        if mesh is None:
            leaf = jax.tree.leaves(self._stores[self.classes[0][1]])[0]
            self._device = getattr(leaf, "device", None) or next(iter(
                leaf.devices()))
        else:
            self._device = None          # fetch re-places by sharding tree
        # Observability: the historical `spill_stats` dict survives as a
        # live view over the metrics registry (same keys, same `+=`
        # spelling); per-transfer byte histograms ride alongside.  A pool
        # built by a `RequestScheduler` shares the scheduler's bundle, so
        # one registry carries the whole serving stack's metrics.
        self.obs = obs if obs is not None else Observability()
        self.spill_stats = self.obs.metrics.counter_view(
            "pool.", ["spills", "fetches", "bytes_to_host",
                      "bytes_to_device"])
        for n, clen in self.classes:
            g = self.obs.metrics.gauge(f"pool.device_bytes[{clen}]")
            g.set(pytree_nbytes(self._stores[clen]))
        # Shared-prefix tier (opt-in): refcounted pages / snapshots indexed
        # by token prefix, leased per slot, maintained once per step.
        from repro.serving.paging import PrefixCache
        self.prefix = PrefixCache(cfg, self.dtype, enabled=prefix_cache,
                                  page_size=prefix_page_size,
                                  max_pages=max_prefix_pages,
                                  device_pages=device_prefix_pages,
                                  obs=self.obs)

    # -- slot accounting ----------------------------------------------------

    @property
    def free_slots(self) -> int:
        """Free *device lanes* (host-resident slots hold no lane)."""
        return sum(len(f) for f in self._lanes.values())

    @property
    def host_resident(self) -> int:
        return len(self._host)

    @property
    def host_bytes(self) -> int:
        """Bytes currently parked in the host tier."""
        return sum(pytree_nbytes(c) for c in self._host.values())

    @property
    def device_bytes(self) -> int:
        """Bytes of the device-resident stacked stores (all lanes, global
        across the mesh — the whole distributed working set)."""
        return sum(pytree_nbytes(s) for s in self._stores.values())

    @property
    def device_bytes_per_device(self) -> int:
        """Bytes ONE chip holds of the stacked stores: sharded leaves count
        their local shard only — the number that must fit a single edge
        device's DRAM.  Equals `device_bytes` on a single device."""
        return sum(pytree_nbytes(s, per_device=True)
                   for s in self._stores.values())

    def slot_shardings(self, slot: int) -> Params:
        """NamedSharding tree for one slot's batch-1 cache pytree (what
        `fetch` restores a host-resident slot under)."""
        from repro.runtime import sharding as shd
        if self.mesh is None:
            raise ValueError("slot_shardings needs a mesh-backed pool")
        template = jax.eval_shape(
            lambda: lm.make_decode_cache(self.cfg, 1, self.slot_len(slot),
                                         self.dtype))
        return shd.tree_shardings(template, self._axes, self.mesh,
                                  self.policy)

    def fits(self, min_len: int) -> bool:
        """Could a request needing `min_len` cache positions EVER be placed?"""
        return min_len <= self.cache_len

    def slot_len(self, slot: int) -> int:
        """Cache length of a *live* (device- or host-resident) slot."""
        if slot not in self._class_of:
            raise ValueError(f"slot {slot} is not live ({self._where(slot)})")
        return self._class_of[slot]

    def locate(self, slot: int) -> tuple[int, int]:
        """(cache_len, lane) of a *device-resident* slot."""
        if slot not in self._lane_of:
            raise ValueError(f"slot {slot} is not device-resident "
                             f"({self._where(slot)})")
        return self._lane_of[slot]

    def residency(self, slot: int) -> str:
        """'device' | 'host' for a live slot; ValueError otherwise."""
        where = self._where(slot)
        if where not in ("device", "host"):
            raise ValueError(f"slot {slot} is not resident ({where})")
        return where

    def _where(self, slot: int) -> str:
        if slot in self._lane_of:
            return "device"
        if slot in self._host:
            return "host"
        return "released" if 0 <= slot < self._next_sid else "unknown"

    def has_free_lane(self, clen: int) -> bool:
        return bool(self._lanes[clen])

    def acquire(self, min_len: int = 0) -> int | None:
        """Smallest-class-first placement: the cheapest free lane that fits.

        Returns a fresh slot id bound to that lane, or None when every
        fitting class is busy (the caller may then `spill` a victim).
        """
        for _, clen in self.classes:
            if clen >= min_len and self._lanes[clen]:
                lane = self._lanes[clen].pop(0)
                sid = self._next_sid
                self._next_sid += 1
                self._lane_of[sid] = (clen, lane)
                self._class_of[sid] = clen
                return sid
        return None

    def release(self, slot: int) -> None:
        """Retire a slot: free its device lane, or drop its host copy.
        Any prefix-page leases the slot holds are dropped with it (this is
        the single refcount-decrement path — retire, cancel, and preempted
        cancel all route through here)."""
        if slot in self._lane_of:
            clen, lane = self._lane_of.pop(slot)
            self._lanes[clen].append(lane)
        elif slot in self._host:
            del self._host[slot]
        elif 0 <= slot < self._next_sid:
            raise ValueError(f"slot {slot} double-released")
        else:
            raise ValueError(f"release of unknown slot id {slot}")
        del self._class_of[slot]
        self.prefix.release(slot)

    # -- shared-prefix tier --------------------------------------------------

    def prefix_lookup(self, prompt, slot: int, *, chunk_size: int = 1
                      ) -> tuple[int, Params | None]:
        """Longest adoptable cached prefix of ``prompt`` for ``slot``:
        ``(n_tokens, warm_batch1_cache)`` or ``(0, None)``.  Leases the
        backing pages under the slot until `release`."""
        return self.prefix.lookup(prompt, self.slot_len(slot), slot,
                                  chunk_size=chunk_size)

    def prefix_register(self, prompt, cache: Params, slot: int) -> int:
        """Index a finished prefill's prefix for future adopters."""
        return self.prefix.register(prompt, cache, self.slot_len(slot))

    def prefix_maintain(self) -> None:
        """One prefix-tier bookkeeping cycle (LRU eviction, proactive host
        migration of cold unreferenced pages, gauge refresh)."""
        self.prefix.maintain()

    # -- host spill tier ----------------------------------------------------

    @property
    def host_device(self):
        """The CPU device spills land on (single-device pools; a sharded
        pool gathers to host numpy instead)."""
        if self._host_device is None:
            raise RuntimeError(
                "host spill needs a CPU device, and this process has no cpu "
                "backend (JAX_PLATFORMS excludes it): a spill would stay on "
                f"{self._device}")
        return self._host_device

    def spill(self, slot: int) -> None:
        """Move a slot's full cache pytree (KV/rings, recurrent state, RoPE
        angle memory, position) to host memory and free its device lane.

        The transfer is bit-exact (`jax.device_put` round trip); the freed
        lane's stale contents are overwritten by the next `write`.
        """
        if slot in self._host:
            raise ValueError(f"slot {slot} already spilled")
        clen, lane = self.locate(slot)
        cache = jax.tree.map(lambda x: x[lane], self._stores[clen])
        if self.mesh is not None:
            # Sharded slot: gather every leaf's shards into one host copy
            # (device_get is the cross-sharding-safe gather on every jax
            # this repo targets; device_put onto one device is not).
            host = jax.device_get(cache)
        else:
            host = jax.block_until_ready(
                jax.device_put(cache, self.host_device))
        del self._lane_of[slot]
        self._lanes[clen].append(lane)
        self._host[slot] = host
        nbytes = pytree_nbytes(host)
        self.spill_stats["spills"] += 1
        self.spill_stats["bytes_to_host"] += nbytes
        self.obs.metrics.histogram("pool.spill_bytes").record(nbytes)

    def fetch(self, slot: int) -> None:
        """Bind a spilled slot to a free lane in its class and restore its
        cache to the device, bit-exactly.  The caller checks
        ``has_free_lane(slot_len(slot))`` first (or handles the raise)."""
        if slot not in self._host:
            raise ValueError(f"slot {slot} is not spilled to host "
                             f"({self._where(slot)})")
        clen = self._class_of[slot]
        if not self._lanes[clen]:
            raise ValueError(f"no free lane in class {clen} to fetch "
                             f"slot {slot} into")
        host = self._host.pop(slot)
        lane = self._lanes[clen].pop(0)
        self._lane_of[slot] = (clen, lane)
        nbytes = pytree_nbytes(host)
        self.spill_stats["fetches"] += 1
        self.spill_stats["bytes_to_device"] += nbytes
        self.obs.metrics.histogram("pool.fetch_bytes").record(nbytes)
        if self.mesh is not None:
            # Re-place under the slot's cache shardings — the round trip
            # restores both the bits and the distribution.
            self.write(slot, jax.device_put(host, self.slot_shardings(slot)))
        else:
            self.write(slot, jax.device_put(host, self._device))

    # -- stacked stores -----------------------------------------------------

    def placement_mismatches(self) -> list[str]:
        """Array leaves of the stacked class stores whose on-device sharding
        departs from the pool's plan — the sharding audit's pool leg.
        Empty on a single-device pool (no plan to depart from)."""
        from repro.runtime import sharding as shd
        if self.mesh is None:
            return []
        bad: list[str] = []
        for clen, store in self._stores.items():
            for m in shd.sharding_mismatches(store,
                                             self._store_shardings[clen]):
                bad.append(f"class[{clen}]/{m}")
        return bad

    @property
    def store(self) -> Params:
        """Legacy single-class view of the stacked store."""
        if len(self.classes) != 1:
            raise ValueError("`store` is single-class; use get_store(clen)")
        return self._stores[self.classes[0][1]]

    def get_store(self, clen: int) -> Params:
        return self._stores[clen]

    def set_store(self, clen: int, store: Params) -> None:
        if self.mesh is not None:
            # Keep the stacked-store placement invariant regardless of what
            # sharding the producing computation's outputs resolved to (a
            # no-op when they already match).
            store = jax.device_put(store, self._store_shardings[clen])
        self._stores[clen] = store

    def write(self, slot: int, cache: Params) -> None:
        """Scatter one batch-1 cache (e.g. fresh from prefill) into a slot.

        The incoming pytree must match the slot class's structure and leaf
        shapes — a cache built for another class would silently corrupt the
        stacked store otherwise.
        """
        clen, lane = self.locate(slot)
        store = self._stores[clen]
        if jax.tree.structure(cache) != jax.tree.structure(store):
            raise ValueError(
                f"cache pytree structure does not match slot {slot}'s "
                f"class (cache_len {clen})")
        for p, c in zip(jax.tree.leaves(store), jax.tree.leaves(cache)):
            if tuple(p.shape[1:]) != tuple(jnp.shape(c)):
                raise ValueError(
                    f"cache leaf shape {tuple(jnp.shape(c))} does not match "
                    f"slot {slot}'s class shape {tuple(p.shape[1:])} "
                    f"(cache_len {clen})")
        new = jax.tree.map(
            lambda pool, c: pool.at[lane].set(
                jnp.asarray(c).astype(pool.dtype)), store, cache)
        if self.mesh is not None:
            new = jax.device_put(new, self._store_shardings[clen])
        self._stores[clen] = new


class RequestScheduler:
    """Admit-while-decoding serving loop around one `InferenceEngine`.

    ``step()`` performs one sequencer cycle: (1) advance the in-flight
    admission by at most one prefill chunk (starting the next queued request
    that fits a free slot class when idle), (2) advance every resident class
    one token through the vmapped MVM decode path, (3) retire slots that hit
    a stop token or their token budget.  ``run()`` drains the queue.

    ``on_token(uid, token)`` streams tokens as they are emitted;
    ``on_finish(finished)`` fires once per terminal `FinishedRequest`
    (retire, in-flight cancel) — the async front end's completion hook;
    ``cancel(uid)`` drops a queued request, aborts an in-flight admission, or
    retires an active slot (its partial output is returned with
    ``cancelled=True``).  ``clock`` injects the timebase for every latency
    stamp (virtual time in tests; monotonic by default).

    Admission order is FIFO with skip: a request whose smallest fitting class
    is momentarily full does not block later requests that fit elsewhere.

    ``host_spill=True`` adds priority preemption over the pool's host tier:
    when a queued request finds no free lane, the lowest-priority (tie:
    oldest-admitted) resident lane of *strictly lower* priority is spilled —
    its cache pytree moves to host memory (``CachePool.spill``) along with
    its sampling key, pending token, and speculative history — and parks on
    a resumable list.  Resume re-enters the vmapped decode through the
    pool's ``fetch`` + slot ``write``: no re-prefill, no new compiles, and
    greedy output is token-identical to an unpreempted run.

    Stochastic sampling stays per-request reproducible: each request draws
    from ``fold_in(key, uid)`` regardless of which slot it lands in or what
    else shares the batch.
    """

    def __init__(self, engine: InferenceEngine, *, n_slots: int = 4,
                 cache_len: int = 128,
                 classes: Sequence[tuple[int, int]] | None = None,
                 gen: GenerationConfig = GenerationConfig(),
                 key: jax.Array | None = None,
                 chunk_size: int = 32,
                 host_spill: bool = False,
                 cache_dtype=None,
                 on_token: Callable[[int, int], None] | None = None,
                 on_finish: Callable[[FinishedRequest], None] | None = None,
                 obs: Observability | None = None,
                 clock: Callable[[], float] | None = None,
                 prefix_cache: bool = False, prefix_page_size: int = 16,
                 max_prefix_pages: int | None = None,
                 device_prefix_pages: int | None = None):
        self.engine = engine
        self.gen = gen
        # The timebase for every latency stamp (submit, queue-wait, TTFT,
        # inter-token, request latency).  Injectable so the async front end
        # can run the whole serving loop on a virtual clock in tests; real
        # deployments keep the monotonic default.  Histogram records carry
        # `t=self._now()` so windowed percentiles share this timebase.
        self._now = clock if clock is not None else time.perf_counter
        # Each scheduler defaults to its OWN bundle (schedulers built over a
        # shared engine must not accumulate into one registry); pass the
        # engine's bundle explicitly (`obs=engine.obs`) to unify them, as
        # `repro.launch.serve` does.  The pool shares the scheduler's bundle.
        self.obs = obs if obs is not None else Observability()
        self._tr = self.obs.tracer
        # The pool-wide cache dtype policy: an explicit ``cache_dtype`` wins;
        # otherwise `gen.cache_format` (the request-level knob) selects the
        # quantized residency for every class; fp32 is the legacy default.
        # Chunked admission appends straight into the encoded layout
        # (`ChunkedPrefill(cache_dtype=pool.dtype)` below), so the stacked
        # stores never hold an fp copy.
        if cache_dtype is None:
            cache_dtype = gen.cache_format or jnp.float32
        self.pool = CachePool(engine.cfg, n_slots, cache_len, classes=classes,
                              dtype=cache_dtype,
                              mesh=getattr(engine, "mesh", None),
                              policy=getattr(engine, "policy", None),
                              obs=self.obs,
                              prefix_cache=prefix_cache,
                              prefix_page_size=prefix_page_size,
                              max_prefix_pages=max_prefix_pages,
                              device_prefix_pages=device_prefix_pages)
        self.base_key = key if key is not None else jax.random.key(0)
        self.chunk_size = chunk_size
        self.host_spill = host_spill
        if host_spill and self.pool.mesh is None:
            self.pool.host_device        # raises when there is no host tier
        self.on_token = on_token
        self.on_finish = on_finish
        self._class_nbytes: dict[int, int] = {}   # clen -> lane bytes memo

        self._queue: list[Request] = []
        self._admitting: dict | None = None      # the one in-flight prefill
        self._active: dict[int, dict] = {}       # sid -> per-request state
        self._preempted: list[dict] = []         # parked, host-resident
        self._seq = 0                            # admission order stamp
        self._finished: list[FinishedRequest] = []
        # Per class: current token per slot [N_c, 1, 1] (lane-major so vmap
        # sees [1, 1], the [B=1, T=1] shape forward_decode expects) and the
        # per-slot sampling keys (set on admit).
        self._tokens = {clen: jnp.zeros((n, 1, 1), jnp.int32)
                        for n, clen in self.pool.classes}
        self._keys = {clen: jax.random.split(self.base_key, n)
                      for n, clen in self.pool.classes}
        # The historical stats dict is now a live view over the metrics
        # registry: same keys, same `+=` spelling, and a `snapshot()` of the
        # registry sees every count under the `sched.` prefix.
        self.stats = self.obs.metrics.counter_view(
            "sched.", ["steps", "emitted", "prefill_chunks", "admitted",
                       "cancelled", "decode_stall_steps", "verify_steps",
                       "accepted_drafts", "preempted", "resumed"])
        self._t_submit: dict[int, float] = {}    # uid -> submit wall clock

        # Speculative decode: each slot is its own batch lane, so acceptance
        # depth is per-request (no lockstep min over the batch like the
        # engine's fused loop) and each lane carries its own token history
        # for the prompt-lookup drafter.
        self._spec = gen.speculative
        if self._spec is not None:
            if self._spec.drafter != "ngram":
                raise ValueError(
                    "RequestScheduler speculative decode supports the "
                    "model-free 'ngram' drafter (the MTP drafter needs "
                    "per-lane hidden state; use engine.generate)")
            w = engine.cfg.sliding_window
            if w and self._spec.k + 1 > w:
                raise ValueError(f"verify block k+1 ({self._spec.k + 1}) "
                                 f"must fit the sliding window ({w})")
            cap = self._spec.k + 1
            self._hist = {clen: jnp.zeros((n, clen + cap), jnp.int32)
                          for n, clen in self.pool.classes}
            self._hist_len = {clen: jnp.zeros((n,), jnp.int32)
                              for n, clen in self.pool.classes}

        # Same split-then-sample order as the engine's fused loop, so a
        # request's token stream is identical whether it runs here or through
        # engine.generate with key = fold_in(base_key, uid).
        def pool_step(params, tokens, store, keys):
            def one(tok, cache, key):
                logits, new_cache = lm.forward_decode(
                    params, tok, cache, engine.cfg, engine.hsa)
                key, sub = jax.random.split(key)
                nxt = sample(logits[0], gen.sampling, sub)
                return nxt, new_cache, key
            return jax.vmap(one)(tokens, store, keys)

        self._pool_step = jax.jit(pool_step)

        # Speculative sibling: per slot, draft k from the lane's history,
        # verify the k+1 block in ONE chunk-shaped dispatch against the
        # lane's resident cache, commit the accepted prefix (exact rollback)
        # and hand the Python side a variable-length token block.  Built on
        # the same `NgramDrafter`/`verify_block` core as the engine's fused
        # loop — each lane is a batch-1 instance, so the commit depth is the
        # lane's own acceptance (no lockstep min over the batch).
        def spec_pool_step(params, tokens, store, keys, hist, hlen):
            spec = self._spec
            k = spec.k
            drafter = spec_mod.NgramDrafter(k=k, m=spec.ngram)

            def one(tok, cache, key, h, hl):
                pend = tok[:, 0]                              # [1]
                dstate = {"hist": h[None, :], "len": hl}
                drafts = drafter.draft(params, dstate, pend)
                block = jnp.concatenate([pend[:, None], drafts], axis=1)
                key, sub = jax.random.split(key)
                cand, acc, hidden_all, ver = spec_mod.verify_block(
                    params, block, cache, sub, cfg=engine.cfg,
                    hsa=engine.hsa, gen=gen)
                a = acc[0]
                n_commit = a + 1
                new_cache = lm.commit_verified_cache(cache, ver, n_commit,
                                                     k + 1, engine.cfg)
                nxt = jax.lax.dynamic_index_in_dim(cand[0], a,
                                                   keepdims=False)
                dstate = drafter.observe(dstate, block, n_commit, hidden_all,
                                         nxt[None])
                return (block[0], n_commit, nxt[None, None], new_cache, key,
                        dstate["hist"][0], dstate["len"])
            return jax.vmap(one)(tokens, store, keys, hist, hlen)

        self._spec_pool_step = jax.jit(spec_pool_step)

    def compile_counts(self) -> dict[str, int]:
        """Compiled-signature count per scheduler dispatch (one per resident
        class is the contract; `repro.analysis` and the bench watch it)."""
        from repro.serving.engine import _jit_cache_size
        return {"pool_step": _jit_cache_size(self._pool_step),
                "spec_pool_step": _jit_cache_size(self._spec_pool_step)}

    # -- queue management ---------------------------------------------------

    def submit(self, request: Request, priority: int | None = None) -> None:
        """Enqueue; ``priority`` (or ``request.priority``) orders admission:
        higher priorities admit first, FIFO within a level.  A ``priority``
        argument is submission-scoped: the caller's Request is not mutated
        (the queue holds a copy carrying the effective priority).

        Sizing is validated *here*, at the submission boundary: a request
        whose ``max_new_tokens`` is invalid or that could never fit any pool
        class raises immediately, so the drain loop (`run`) can never throw
        mid-flight and abandon queued + resident work.
        """
        if priority is not None:
            request = dataclasses.replace(request, priority=priority)
        if request.max_new_tokens is not None and request.max_new_tokens < 1:
            raise ValueError(f"request {request.uid}: max_new_tokens must be "
                             f">= 1, got {request.max_new_tokens}")
        need, budget = self._request_need(request)
        if not self.pool.fits(need):
            # Decode writes cache positions s .. s+budget-1; past-capacity
            # positions would silently clamp onto the last linear-cache slot
            # (gqa_decode), so reject instead of corrupting attention.
            # Speculative verify blocks write up to k tokens past the last
            # budget position before rolling back — reserved in `need` too.
            raise CacheCapacityError(
                f"request {request.uid}: prompt ({len(request.prompt)}) + "
                f"max_new_tokens ({budget}) exceeds every pool class "
                f"(largest cache_len {self.pool.cache_len})")
        i = len(self._queue)
        while i > 0 and self._queue[i - 1].priority < request.priority:
            i -= 1
        self._queue.insert(i, request)
        self._t_submit[request.uid] = self._now()
        rt = request_track(request.uid)
        self._tr.begin("request", rt, prompt_len=len(request.prompt),
                       priority=request.priority)
        self._tr.begin("queued", rt)

    def _request_need(self, req: Request) -> tuple[int, int]:
        """(cache positions needed, effective token budget).  An explicit
        ``max_new_tokens`` always wins — ``0`` must not silently fall back
        to the scheduler default (it is rejected at submit)."""
        budget = (req.max_new_tokens if req.max_new_tokens is not None
                  else self.gen.max_new_tokens)
        need = len(req.prompt) + budget
        if self._spec is not None:
            need += self._spec.k
        return need, budget

    @property
    def pending(self) -> int:
        return (len(self._queue) + len(self._active) + len(self._preempted)
                + (1 if self._admitting is not None else 0))

    def cancel(self, uid: int) -> bool:
        """Drop a queued request / abort its admission / retire its slot —
        including a preempted slot parked in the host tier."""
        for i, req in enumerate(self._queue):
            if req.uid == uid:
                self._queue.pop(i)
                self.stats["cancelled"] += 1
                self._t_submit.pop(uid, None)
                rt = request_track(uid)
                self._tr.end("queued", rt)
                self._tr.instant("cancel", rt)
                self._tr.end("request", rt)
                return True
        if self._admitting is not None and self._admitting["req"].uid == uid:
            # Abort mid-chunked-prefill.  Clear `_admitting` *before* the
            # release and record a FinishedRequest like every other in-flight
            # cancel path: release drops the slot's lane and any prefix-page
            # leases the partial prefill adopted, and `_finish`'s on_finish
            # callback may re-enter the scheduler — it must observe the
            # admission already gone, and a front end awaiting this uid needs
            # the terminal record (previously this path recorded nothing and
            # `run()` silently forgot the request).
            adm = self._admitting
            self._admitting = None
            clen = self.pool.slot_len(adm["slot"])
            self.pool.release(adm["slot"])
            self.stats["cancelled"] += 1
            self._t_submit.pop(uid, None)
            rt = request_track(uid)
            self._tr.end("admit", rt)
            self._tr.instant("cancel", rt)
            self._tr.end("request", rt)
            self._finish(FinishedRequest(
                uid=uid, prompt_len=len(adm["req"].prompt), tokens=[],
                slot=adm["slot"], cache_len=clen, cancelled=True))
            return True
        for slot, st in self._active.items():
            if st["req"].uid == uid:
                self._retire(slot, cancelled=True)
                self.stats["cancelled"] += 1
                return True
        for entry in self._preempted:
            if entry["req"].uid == uid:
                self._preempted.remove(entry)
                clen = self.pool.slot_len(entry["slot"])
                self.pool.release(entry["slot"])   # drops the host copy
                self.stats["cancelled"] += 1
                rt = request_track(uid)
                self._tr.end("preempted", rt)
                self._tr.instant("cancel", rt)
                self._tr.end("request", rt)
                self._finish(FinishedRequest(
                    uid=uid, prompt_len=len(entry["req"].prompt),
                    tokens=entry["emitted"], slot=entry["slot"],
                    cache_len=clen, cancelled=True,
                    verify_steps=entry["verify_steps"],
                    accepted_drafts=entry["accepted_drafts"]))
                return True
        return False

    # -- the sequencer cycle ------------------------------------------------

    def _start_admission(self) -> None:
        """Pick the next admission: resume a parked (preempted) request or
        start the first queued request that fits a free slot class.

        Sizing was validated at `submit`, so this loop never throws under
        load — the drain loop cannot abandon queued + resident work.  Any
        failure after acquisition releases the slot (no slot leaks).

        With ``host_spill``, a queued request that finds no free lane may
        preempt the lowest-priority (tie: oldest-admitted) resident lane of
        strictly lower priority — `_preempt` spills it to the pool's host
        tier and parks it.  Parked requests resume ahead of queued arrivals
        at the same or lower priority; a strictly higher-priority arrival
        admits first (and may itself preempt).
        """
        best_queued = self._queue[0].priority if self._queue else None
        for entry in self._resume_order():
            if (best_queued is not None
                    and best_queued > entry["req"].priority):
                break              # the higher-priority arrival admits first
            if self._try_resume(entry):
                return
        for i, req in enumerate(self._queue):
            need, budget = self._request_need(req)
            slot = self.pool.acquire(need)
            if slot is None and self.host_spill:
                victim = self._pick_victim(req.priority, need)
                if victim is not None:
                    self._preempt(victim)
                    slot = self.pool.acquire(need)
            if slot is None:
                continue                 # fitting classes all busy: try next
            self._queue.pop(i)
            t_sub = self._t_submit.get(req.uid)
            if t_sub is not None:
                t_adm = self._now()
                self.obs.metrics.histogram("sched.queue_wait_s").record(
                    t_adm - t_sub, t=t_adm)
            prompt = jnp.asarray(req.prompt, jnp.int32)[None, :]
            try:
                # Shared-prefix adoption: walk the pool's prefix index and
                # start the chunked prefill at the longest cached prefix —
                # those tokens are never prefilled.  The assembled warm
                # cache is private to this slot (pages are copied in), so
                # handing it to the donating chunk step is safe; the pages
                # themselves stay leased until the slot releases.
                hit, warm = self.pool.prefix_lookup(
                    req.prompt, slot, chunk_size=self.chunk_size)
                prefill = self.engine.begin_chunked_prefill(
                    prompt, cache_len=self.pool.slot_len(slot),
                    chunk_size=self.chunk_size,
                    cache_dtype=self.pool.dtype,
                    initial_cache=warm, start_offset=hit)
            except Exception:
                self.pool.release(slot)
                raise
            rt = request_track(req.uid)
            self._tr.end("queued", rt)
            self._tr.begin("admit", rt,
                           cache_len=self.pool.slot_len(slot),
                           prefix_hit=hit)
            self._admitting = {"req": req, "slot": slot, "prefill": prefill,
                               "budget": budget}
            return
        # Nothing queued could start: resume any parked request that fits,
        # ignoring the priority gate above — it only *defers* resumes behind
        # admissible higher-priority arrivals, and must never deadlock the
        # drain loop when those arrivals cannot be placed yet.
        for entry in self._resume_order():
            if self._try_resume(entry):
                return

    # -- host-spill preemption ---------------------------------------------

    def _resume_order(self) -> list[dict]:
        """Parked requests in resume order: priority desc, admission asc."""
        return sorted(self._preempted,
                      key=lambda e: (-e["req"].priority, e["seq"]))

    def _slot_nbytes(self, clen: int) -> int:
        """Bytes one lane of class ``clen`` holds (memoized abstract-shape
        walk via `engine.cache_nbytes`) — the spill's transfer cost and the
        device memory a preemption frees."""
        n = self._class_nbytes.get(clen)
        if n is None:
            n = self.engine.cache_nbytes(clen, dtype=self.pool.dtype)
            self._class_nbytes[clen] = n
        return n

    def _pick_victim(self, priority: int, need: int) -> int | None:
        """Byte-aware preemption: among resident lanes of strictly lower
        priority whose class could hold ``need`` positions, pick the lowest
        priority first, then the lane *freeing the most device bytes* (the
        largest cache class — one spill should buy the most placement
        headroom per transfer), then the oldest admission."""
        best = None
        for slot, st in self._active.items():
            if st["req"].priority >= priority:
                continue
            if self.pool.slot_len(slot) < need:
                continue
            rank = (st["req"].priority,
                    -self._slot_nbytes(self.pool.slot_len(slot)), st["seq"])
            if best is None or rank < best[0]:
                best = (rank, slot)
        return None if best is None else best[1]

    def _preempt(self, slot: int) -> None:
        """Spill a resident lane to the host tier and park it, resumable
        bit-exactly: cache pytree (via `CachePool.spill`), sampling key,
        pending token, and — on the speculative path — the lane's draft
        history all survive the round trip."""
        st = self._active.pop(slot)
        clen, lane = self.pool.locate(slot)
        entry = {"req": st["req"], "slot": slot, "seq": st["seq"],
                 "budget": st["budget"], "emitted": st["emitted"],
                 "verify_steps": st["verify_steps"],
                 "accepted_drafts": st["accepted_drafts"],
                 "t_submit": st.get("t_submit"), "t_last": st.get("t_last"),
                 "token": int(self._tokens[clen][lane, 0, 0]),
                 "key": self._keys[clen][lane]}
        if self._spec is not None:
            entry["hist"] = jax.device_get(self._hist[clen][lane])
            entry["hist_len"] = int(self._hist_len[clen][lane])
        self.pool.spill(slot)
        self._preempted.append(entry)
        self.stats["preempted"] += 1
        rt = request_track(st["req"].uid)
        self._tr.end("decode", rt)
        self._tr.instant("preempt", rt, cache_len=clen)
        self._tr.begin("preempted", rt)

    def _try_resume(self, entry: dict) -> bool:
        """Fetch a parked request's cache back into a free lane of its class
        and rejoin the vmapped decode — no re-prefill, no new compiles (the
        slot `write` is the same scatter admission uses)."""
        slot = entry["slot"]
        if not self.pool.has_free_lane(self.pool.slot_len(slot)):
            return False
        self.pool.fetch(slot)
        clen, lane = self.pool.locate(slot)
        self._tokens[clen] = self._tokens[clen].at[lane, 0, 0].set(
            entry["token"])
        self._keys[clen] = self._keys[clen].at[lane].set(entry["key"])
        if self._spec is not None:
            self._hist[clen] = self._hist[clen].at[lane].set(
                jnp.asarray(entry["hist"]))
            self._hist_len[clen] = self._hist_len[clen].at[lane].set(
                entry["hist_len"])
        self._active[slot] = {"req": entry["req"], "emitted": entry["emitted"],
                              "budget": entry["budget"], "seq": entry["seq"],
                              "verify_steps": entry["verify_steps"],
                              "accepted_drafts": entry["accepted_drafts"],
                              "t_submit": entry.get("t_submit"),
                              "t_last": entry.get("t_last")}
        self._preempted.remove(entry)
        self.stats["resumed"] += 1
        rt = request_track(entry["req"].uid)
        self._tr.end("preempted", rt)
        self._tr.instant("resume", rt, cache_len=clen)
        self._tr.begin("decode", rt)
        return True

    def _admit(self) -> None:
        """MMM phase: advance the in-flight admission by at most one chunk."""
        if self._admitting is None:
            self._start_admission()
        if self._admitting is None:
            return
        adm = self._admitting
        rt = request_track(adm["req"].uid)
        with self.obs.span("prefill_chunk", rt):
            logits = adm["prefill"].advance()
        self.stats["prefill_chunks"] += 1
        if not adm["prefill"].done:
            return
        req, slot = adm["req"], adm["slot"]
        self.pool.write(slot, adm["prefill"].cache)
        # The finished prompt's warm cache covers [0, len(prompt)) — index
        # it so later admissions sharing this prefix skip their prefill.
        # (`write` does not donate, so the cache is still whole here.)
        self.pool.prefix_register(req.prompt, adm["prefill"].cache, slot)
        key = jax.random.fold_in(self.base_key, req.uid)
        key, sub = jax.random.split(key)
        tok = sample(logits[0], self.gen.sampling, sub)
        clen, local = self.pool.locate(slot)
        self._tokens[clen] = self._tokens[clen].at[local, 0, 0].set(tok)
        self._keys[clen] = self._keys[clen].at[local].set(key)
        if self._spec is not None:
            prompt = jnp.asarray(req.prompt, jnp.int32)
            row = jnp.zeros((self._hist[clen].shape[1],),
                            jnp.int32).at[:prompt.shape[0]].set(prompt)
            self._hist[clen] = self._hist[clen].at[local].set(row)
            self._hist_len[clen] = self._hist_len[clen].at[local].set(
                prompt.shape[0])
        self._active[slot] = {"req": req, "emitted": [],
                              "budget": adm["budget"], "seq": self._seq,
                              "verify_steps": 0, "accepted_drafts": 0,
                              "t_submit": self._t_submit.pop(req.uid, None),
                              "t_last": None}
        self._seq += 1
        self._admitting = None
        self.stats["admitted"] += 1
        self._tr.end("admit", rt)
        self._tr.begin("decode", rt)

    def _finish(self, fr: FinishedRequest) -> None:
        """The single completion sink: every `FinishedRequest` — retired,
        cancelled mid-admission, or cancelled while preempted — lands here,
        so `on_finish` observers (the async front end resolving a request's
        token stream) see every terminal state exactly once.  Scheduler
        bookkeeping is already consistent when the callback fires: the
        callback may re-enter `cancel`/`submit`/`pending` safely."""
        self._finished.append(fr)
        if self.on_finish is not None:
            self.on_finish(fr)

    def _retire(self, slot: int, cancelled: bool = False) -> None:
        st = self._active.pop(slot)
        clen = self.pool.slot_len(slot)
        self.pool.release(slot)
        t_sub = st.get("t_submit")
        if t_sub is not None:
            t_fin = self._now()
            self.obs.metrics.histogram("sched.request_latency_s").record(
                t_fin - t_sub, t=t_fin)
        rt = request_track(st["req"].uid)
        self._tr.end("decode", rt)
        self._tr.instant("finish", rt, tokens=len(st["emitted"]),
                         cancelled=cancelled)
        self._tr.end("request", rt)
        self._finish(FinishedRequest(
            uid=st["req"].uid, prompt_len=len(st["req"].prompt),
            tokens=st["emitted"], slot=slot,
            cache_len=clen, cancelled=cancelled,
            verify_steps=st["verify_steps"],
            accepted_drafts=st["accepted_drafts"]))

    def step(self) -> int:
        """One admit+decode cycle; returns the number of tokens emitted.

        Spans (``obs.span``, scheduler track): ``sched.step`` around the
        cycle, holding ``sched.admit`` while an admission is queued or in
        flight, then per resident class ``sched.token_sync`` (the host
        waiting for the previous step's tokens) and ``sched.pool_step``
        (the dispatch), and ``sched.drain`` around the delivery."""
        with self.obs.span("sched.step"):
            if self._admitting is not None or self._queue or self._preempted:
                with self.obs.span("sched.admit"):
                    self._admit()
            self.pool.prefix_maintain()
            self.stats["steps"] += 1
            # Occupancy gauges + trace counter series, sampled once per cycle
            # at the step boundary (no device access: queue/active/preempted
            # are python containers, host_bytes sums host-resident leaves).
            m = self.obs.metrics
            m.gauge("sched.queue_depth").set(len(self._queue))
            m.gauge("sched.active").set(len(self._active))
            m.gauge("sched.preempted_depth").set(len(self._preempted))
            m.gauge("pool.host_bytes").set(self.pool.host_bytes)
            if self._tr.enabled:
                self._tr.counter("queue_depth", len(self._queue))
                self._tr.counter("active", len(self._active))
            if not self._active:
                if self._admitting is not None:
                    self.stats["decode_stall_steps"] += 1
                return 0

            # Snapshot this step's token block per active slot *before*
            # decoding: like the fused loop, the tokens emitted at step i were
            # sampled from the previous step's (or prefill's) logits.  One
            # vmapped dispatch per resident class.  The per-token path emits
            # a 1-token block; the speculative path a 1..k+1-token block per
            # slot.
            active_classes = sorted({self.pool.locate(s)[0]
                                     for s in self._active})
            stepped: dict[int, tuple[np.ndarray, np.ndarray]] = {}
            for clen in active_classes:
                toks = self._tokens[clen]
                if self._spec is not None:
                    with self.obs.span("sched.spec_pool_step"):
                        (blocks, counts, nxt, new_store, self._keys[clen],
                         self._hist[clen], self._hist_len[clen]) = \
                            self._spec_pool_step(
                                self.engine.params, toks,
                                self.pool.get_store(clen),
                                self._keys[clen], self._hist[clen],
                                self._hist_len[clen])
                    with self.obs.span("sched.token_sync"):
                        stepped[clen] = (np.asarray(jax.device_get(blocks)),
                                         np.asarray(jax.device_get(counts)))
                    self._tokens[clen] = nxt
                else:
                    with self.obs.span("sched.token_sync"):
                        snap = np.asarray(jax.device_get(toks[:, 0, 0]))
                    stepped[clen] = (snap[:, None],
                                     np.ones(snap.shape[0], np.int64))
                    with self.obs.span("sched.pool_step"):
                        nxt, new_store, self._keys[clen] = self._pool_step(
                            self.engine.params, toks,
                            self.pool.get_store(clen), self._keys[clen])
                    self._tokens[clen] = nxt[:, None, None]
                self.pool.set_store(clen, new_store)

            with self.obs.span("sched.drain"):
                emitted = 0
                now = self._now()
                for slot in list(self._active):
                    st = self._active.get(slot)
                    if st is None:     # retired by an on_token cancel mid-loop
                        continue
                    clen, local = self.pool.locate(slot)
                    blocks, counts = stepped[clen]
                    block = [int(t)
                             for t in blocks[local][:int(counts[local])]]
                    if self._spec is not None:
                        st["verify_steps"] += 1
                        st["accepted_drafts"] += len(block) - 1
                        self.stats["verify_steps"] += 1
                        self.stats["accepted_drafts"] += len(block) - 1
                        m.histogram("sched.tokens_per_verify_step").record(
                            len(block))
                    if block:
                        # SLO latencies, stamped at the drain boundary (the
                        # tokens were already gathered above; no extra sync).
                        # TTFT covers submit → first drained token;
                        # inter-token spreads the gap since the previous
                        # drain over this drain's block (the first block's
                        # same-drain extras carry no previous gap to spread).
                        if st.get("t_last") is None:
                            if st.get("t_submit") is not None:
                                m.histogram("sched.ttft_s").record(
                                    now - st["t_submit"], t=now)
                            self._tr.instant("first_token",
                                             request_track(st["req"].uid))
                        else:
                            dt = (now - st["t_last"]) / len(block)
                            for _ in block:
                                m.histogram("sched.inter_token_s").record(
                                    dt, t=now)
                        st["t_last"] = now
                    for tok in block:
                        st["emitted"].append(tok)
                        emitted += 1
                        if self.on_token is not None:
                            # The callback may cancel() any request —
                            # including this one, which retires the slot
                            # before the stop/budget check below.
                            self.on_token(st["req"].uid, tok)
                        if slot not in self._active:
                            break
                        if (tok in self.gen.stop_tokens
                                or len(st["emitted"]) >= st["budget"]):
                            # Committed-but-over-budget/post-stop block
                            # tokens are dropped; the slot retires either way.
                            self._retire(slot)
                            break
                self.stats["emitted"] += emitted
                return emitted

    def run(self) -> dict[int, FinishedRequest]:
        """Drain queue + active slots; returns results keyed by request uid."""
        while self.pending:
            self.step()
        return {f.uid: f for f in self._finished}
