"""Host-memory spill tier + priority preemption over the paged cache pool:
spill->fetch bit-exactness per cache architecture, preemption/resume greedy
token-identity vs an unpreempted run, oversubscription draining without
leaks, and the pool/scheduler hardening (submit-time validation, real
ValueErrors on release/write misuse)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import (assert_tokens_identical, fp_engine,
                      prompt_list as _prompt_list, run_in_devices)

from repro.models import lm
from repro.serving import (CachePool, EngineSpec, GenerationConfig,
                           InferenceEngine, Request, RequestScheduler,
                           SpeculativeConfig, pytree_nbytes)


def _slot_snapshot(pool, sid):
    clen, lane = pool.locate(sid)
    return jax.tree.map(lambda x: np.asarray(x[lane]), pool.get_store(clen))


# -- spill / fetch round trip ------------------------------------------------


def test_spill_fetch_roundtrip_bit_exact(cache_arch):
    """A slot's full cache pytree (KV/rings, recurrent state, RoPE angle
    memory, position) survives the host round trip bit-exactly, and the
    lane is genuinely free while the slot is host-resident."""
    engine = fp_engine(cache_arch)
    pool = CachePool(engine.cfg, classes=[(2, 16)])
    _, cache = engine.prefill(jnp.asarray([_prompt_list(engine, 10)],
                                          jnp.int32), cache_len=16)
    sid = pool.acquire(12)
    pool.write(sid, cache)
    before = _slot_snapshot(pool, sid)

    pool.spill(sid)
    assert pool.residency(sid) == "host"
    assert pool.host_resident == 1 and pool.host_bytes > 0
    assert pool.free_slots == 2                     # the lane is reusable
    with pytest.raises(ValueError, match="not device-resident"):
        pool.write(sid, cache)                      # host slots can't scatter

    pool.fetch(sid)
    assert pool.residency(sid) == "device"
    assert pool.host_resident == 0 and pool.free_slots == 1
    after = _slot_snapshot(pool, sid)
    for b, a in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(a, b)

    st = pool.spill_stats
    assert st["spills"] == 1 and st["fetches"] == 1
    assert st["bytes_to_host"] == st["bytes_to_device"] > 0


def test_double_spill_and_fetch_without_lane_raise():
    engine = fp_engine("retnet-1.3b")
    pool = CachePool(engine.cfg, classes=[(1, 8)])
    sid = pool.acquire(4)
    pool.spill(sid)
    with pytest.raises(ValueError, match="already spilled"):
        pool.spill(sid)
    other = pool.acquire(4)                         # takes the only lane
    with pytest.raises(ValueError, match="no free lane"):
        pool.fetch(sid)
    pool.release(other)
    pool.fetch(sid)                                 # lane free again
    assert pool.residency(sid) == "device"


def test_spill_without_cpu_backend_raises(monkeypatch):
    """With no CPU backend there is no host tier: a spill (and a
    host_spill scheduler) raises instead of leaving the cache on the
    accelerator under the name of a spill."""
    engine = fp_engine("retnet-1.3b")

    def no_cpu(backend=None):
        if backend == "cpu":
            raise RuntimeError("Unknown backend cpu")
        return jax.local_devices()
    monkeypatch.setattr(jax, "devices", no_cpu)
    pool = CachePool(engine.cfg, classes=[(1, 8)])
    sid = pool.acquire(4)
    with pytest.raises(RuntimeError, match="host spill needs a CPU device"):
        pool.spill(sid)
    assert pool.residency(sid) == "device"
    with pytest.raises(RuntimeError, match="host spill needs a CPU device"):
        RequestScheduler(engine, classes=[(1, 8)], host_spill=True)


# -- preemption / resume token identity --------------------------------------


def _drain(engine, arch_gen, preempt: bool, *, classes, chunk_size=8,
           p0=None, p1=None):
    """One-lane scheduler drain; with `preempt`, uid 1 arrives mid-decode at
    high priority and bumps uid 0 into the host tier."""
    sched = RequestScheduler(engine, classes=classes, gen=arch_gen,
                             chunk_size=chunk_size, host_spill=preempt)
    sched.submit(Request(uid=0, prompt=p0))
    if preempt:
        while not sched._active:                    # uid 0 resident...
            sched.step()
        sched.step()                                # ...and emitting
        sched.submit(Request(uid=1, prompt=p1), priority=5)
    else:
        sched.submit(Request(uid=1, prompt=p1))
    res = sched.run()
    return {u: r.tokens for u, r in res.items()}, sched


def test_preemption_resume_token_identity(cache_arch):
    """Greedy output with host-spill preemption enabled is token-identical
    to the no-spill run for every cache architecture: the preempted lane's
    cache + sampling key + pending token survive the host round trip."""
    engine = fp_engine(cache_arch)
    gen = GenerationConfig(max_new_tokens=6)
    p0 = _prompt_list(engine, 8, seed=11)
    p1 = _prompt_list(engine, 8, seed=12)
    classes = [(1, 8 + 6)]
    base, base_sched = _drain(engine, gen, False, classes=classes,
                              p0=p0, p1=p1)
    pre, pre_sched = _drain(engine, gen, True, classes=classes, p0=p0, p1=p1)
    assert base_sched.stats["preempted"] == 0
    assert pre_sched.stats["preempted"] >= 1        # it really happened
    assert pre_sched.stats["resumed"] == pre_sched.stats["preempted"]
    assert pre_sched.pool.host_resident == 0        # nothing left parked
    assert pre == base, cache_arch


def test_preemption_resume_identity_speculative():
    """The speculative lane's draft history is part of the preempted state:
    a preempted ngram-drafter run stays token-identical, and its acceptance
    stats keep accumulating across the spill."""
    engine = fp_engine("retnet-1.3b")
    k = 2
    gen = GenerationConfig(max_new_tokens=6,
                           speculative=SpeculativeConfig(k=k))
    p0 = _prompt_list(engine, 8, seed=21)
    p1 = _prompt_list(engine, 8, seed=22)
    classes = [(1, 8 + 6 + k)]
    base, _ = _drain(engine, gen, False, classes=classes, p0=p0, p1=p1)
    pre, sched = _drain(engine, gen, True, classes=classes, p0=p0, p1=p1)
    assert sched.stats["preempted"] >= 1
    assert pre == base


def test_resume_priority_order():
    """Parked requests resume priority-first (tie: oldest admitted)."""
    engine = fp_engine("retnet-1.3b")
    gen = GenerationConfig(max_new_tokens=4)
    sched = RequestScheduler(engine, classes=[(1, 12)], gen=gen,
                             chunk_size=8, host_spill=True)
    sched.submit(Request(uid=0, prompt=[2, 3, 4]))           # priority 0
    while not sched._active:
        sched.step()
    sched.submit(Request(uid=1, prompt=[3, 4, 5]), priority=2)
    while not any(st["req"].uid == 1 for st in sched._active.values()):
        sched.step()                                 # uid 1 preempted uid 0
    sched.submit(Request(uid=2, prompt=[4, 5, 6]), priority=9)
    res = sched.run()
    assert len(res) == 3 and sched.stats["preempted"] == 2
    finish_order = [f.uid for f in sched._finished]
    # uid 2 (pri 9) finishes first; uid 1 (pri 2) resumes before uid 0.
    assert finish_order == [2, 1, 0]
    assert all(len(r.tokens) == 4 for r in res.values())


def test_preemption_requires_strictly_lower_priority():
    """Equal-priority arrivals queue instead of thrashing residents."""
    engine = fp_engine("retnet-1.3b")
    gen = GenerationConfig(max_new_tokens=4)
    sched = RequestScheduler(engine, classes=[(1, 12)], gen=gen,
                             chunk_size=8, host_spill=True)
    sched.submit(Request(uid=0, prompt=[2, 3, 4], priority=1))
    while not sched._active:
        sched.step()
    sched.submit(Request(uid=1, prompt=[3, 4, 5], priority=1))
    res = sched.run()
    assert sched.stats["preempted"] == 0
    assert len(res) == 2


# -- oversubscription ---------------------------------------------------------


def test_oversubscription_drains_without_leaks():
    """More submitted requests than device lanes: a high-priority burst
    preempts the residents to host, everything completes with its full
    budget, and the pool ends with every lane free and nothing parked."""
    engine = fp_engine("retnet-1.3b")
    gen = GenerationConfig(max_new_tokens=4)
    sched = RequestScheduler(engine, classes=[(2, 12)], gen=gen,
                             chunk_size=8, host_spill=True)
    for uid in range(2):
        sched.submit(Request(uid=uid, prompt=_prompt_list(engine, 6,
                                                          seed=uid)))
    while sched.stats["admitted"] < 2:
        sched.step()
    for uid in range(2, 6):                          # burst: 4 over 2 lanes
        sched.submit(Request(uid=uid, prompt=_prompt_list(engine, 6,
                                                          seed=uid)),
                     priority=1)
    res = sched.run()
    assert sorted(res) == list(range(6))
    assert all(len(r.tokens) == 4 for r in res.values())
    assert sched.stats["preempted"] == sched.stats["resumed"] == 2
    assert sched.pool.free_slots == 2                # no lane leaked
    assert sched.pool.host_resident == 0             # no host-tier leak
    st = sched.pool.spill_stats
    assert st["spills"] == st["fetches"] == 2
    assert st["bytes_to_host"] == st["bytes_to_device"]


def test_cancel_preempted_request():
    """cancel() reaches a parked (host-resident) request: its partial output
    comes back cancelled and the host copy is dropped."""
    engine = fp_engine("retnet-1.3b")
    gen = GenerationConfig(max_new_tokens=6)
    sched = RequestScheduler(engine, classes=[(1, 12)], gen=gen,
                             chunk_size=8, host_spill=True)
    sched.submit(Request(uid=0, prompt=[2, 3, 4]))
    while not sched._active:
        sched.step()
    sched.step()
    sched.submit(Request(uid=1, prompt=[3, 4, 5]), priority=5)
    while not sched._preempted:
        sched.step()
    assert sched.pool.host_resident == 1
    assert sched.cancel(0)
    assert sched.pool.host_resident == 0
    res = sched.run()
    assert res[0].cancelled and 0 < len(res[0].tokens) < 6
    assert not res[1].cancelled and len(res[1].tokens) == 6


# -- warm-resume engine entry + size accounting -------------------------------


def test_engine_resume_generate_warm_identity():
    """`resume_generate` re-enters the fused loop from (pending token, warm
    cache) with no prefill: same greedy stream, no new prefill shapes."""
    engine = fp_engine("retnet-1.3b")
    gen = GenerationConfig(max_new_tokens=6)
    prompts = jnp.asarray([_prompt_list(engine, 9, seed=31)], jnp.int32)
    want = engine.generate(prompts, gen).tokens
    logits, cache = engine.prefill(prompts, cache_len=9 + 6)
    shapes_before = set(engine.prefill_shape_keys)
    tok0 = jnp.argmax(logits, -1).astype(jnp.int32)
    got = engine.resume_generate(tok0, cache, gen)
    assert_tokens_identical(got, want)
    assert got.prefill_s == 0.0
    assert engine.prefill_shape_keys == shapes_before


def test_cache_nbytes_matches_concrete_cache():
    engine = fp_engine("retnet-1.3b")
    concrete = lm.make_decode_cache(engine.cfg, 1, 16, jnp.float32)
    assert engine.cache_nbytes(16) == pytree_nbytes(concrete) > 0
    assert engine.cache_nbytes(16, batch=2) > engine.cache_nbytes(16)


# -- hardening: release / write / submit --------------------------------------


def test_release_rejects_double_release_and_unknown_ids():
    engine = fp_engine("retnet-1.3b")
    pool = CachePool(engine.cfg, classes=[(2, 8)])
    sid = pool.acquire(4)
    pool.release(sid)
    with pytest.raises(ValueError, match="double-released"):
        pool.release(sid)
    with pytest.raises(ValueError, match="unknown slot"):
        pool.release(12345)
    assert pool.free_slots == 2


def test_release_of_host_resident_slot_drops_host_copy():
    engine = fp_engine("retnet-1.3b")
    pool = CachePool(engine.cfg, classes=[(1, 8)])
    sid = pool.acquire(4)
    pool.spill(sid)
    pool.release(sid)
    assert pool.host_resident == 0 and pool.free_slots == 1
    with pytest.raises(ValueError, match="double-released"):
        pool.release(sid)


def test_write_validates_cache_class_shape():
    """A cache built for another class (or a malformed pytree) must raise
    instead of silently corrupting the stacked store.  Linear-KV arch: its
    cache leaves actually carry cache_len (RetNet's O(1) state would not)."""
    engine = fp_engine("qwen3-8b")
    pool = CachePool(engine.cfg, classes=[(1, 8), (1, 32)])
    sid = pool.acquire(32)                           # the 32-class slot
    assert pool.slot_len(sid) == 32
    small = lm.make_decode_cache(engine.cfg, 1, 8, jnp.float32)
    with pytest.raises(ValueError, match="shape"):
        pool.write(sid, small)
    with pytest.raises(ValueError, match="structure"):
        pool.write(sid, {"pos": jnp.int32(0)})
    ok = lm.make_decode_cache(engine.cfg, 1, 32, jnp.float32)
    pool.write(sid, ok)                              # matching class: fine


def test_submit_rejects_zero_max_new_tokens():
    """Regression for `budget = req.max_new_tokens or default`: an explicit
    0 used to silently fall back to the scheduler default; it is now
    rejected at the submission boundary."""
    engine = fp_engine("retnet-1.3b")
    sched = RequestScheduler(engine, n_slots=1, cache_len=16,
                             gen=GenerationConfig(max_new_tokens=12))
    with pytest.raises(ValueError, match="max_new_tokens must be >= 1"):
        sched.submit(Request(uid=0, prompt=[2, 3], max_new_tokens=0))
    # An explicit small budget is honored (not `or`-clobbered).
    sched.submit(Request(uid=1, prompt=[2, 3], max_new_tokens=1))
    res = sched.run()
    assert len(res[1].tokens) == 1


def test_submit_rejects_never_fitting_request_and_run_never_throws():
    """Capacity is validated at submit(): a never-fitting request raises at
    the submission boundary, so run() can't die mid-drain and abandon
    queued + resident work."""
    engine = fp_engine("retnet-1.3b")
    gen = GenerationConfig(max_new_tokens=4)
    sched = RequestScheduler(engine, n_slots=2, cache_len=16, gen=gen,
                             chunk_size=8)
    sched.submit(Request(uid=0, prompt=[2, 3, 4]))
    free_before = sched.pool.free_slots
    with pytest.raises(ValueError, match="exceeds every pool class"):
        sched.submit(Request(uid=1, prompt=list(range(2, 40))))  # 38+4 > 16
    assert sched.pool.free_slots == free_before      # nothing acquired
    sched.submit(Request(uid=2, prompt=[5, 6, 7]))
    res = sched.run()                                # drains untouched
    assert sorted(res) == [0, 2]
    assert all(len(r.tokens) == 4 for r in res.values())


# -- sharded spill round trip (multi-device subprocess) -----------------------


def test_sharded_spill_roundtrip_restores_shardings():
    """On a 2x2 mesh, `spill` gathers a *sharded* slot pytree to host and
    `fetch` re-places it bit-exactly under the original cache shardings, for
    every cache architecture; per-device byte accounting stays below the
    global footprint.  Subprocess: the virtual-device flag must precede any
    jax init (the main pytest process keeps 1 device)."""
    out = run_in_devices("""
import jax, numpy as np, jax.numpy as jnp
import conftest
from repro.launch.mesh import make_serving_mesh
from repro.runtime import sharding as shd
from repro.serving import CachePool

mesh = make_serving_mesh("2,2")
for arch in conftest.SERVING_ARCHS:
    engine = conftest.fp_engine(arch, mesh=mesh)
    pool = CachePool(engine.cfg, classes=[(2, 16)], mesh=mesh,
                     policy=engine.policy)
    _, cache = engine.prefill(conftest.prompt_ids(engine, 10), cache_len=16)
    sid = pool.acquire(12)
    pool.write(sid, cache)
    clen, lane = pool.locate(sid)
    before = jax.tree.map(lambda x: np.asarray(x[lane]), pool.get_store(clen))

    pool.spill(sid)
    assert pool.residency(sid) == "host" and pool.host_bytes > 0, arch
    pool.fetch(sid)
    clen, lane = pool.locate(sid)
    after = jax.tree.map(lambda x: np.asarray(x[lane]), pool.get_store(clen))
    for b, a in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(a, b, err_msg=arch)   # bit-exact

    bad = shd.sharding_mismatches(pool.get_store(clen),
                                  pool._store_shardings[clen])
    assert not bad, (arch, bad)                    # shardings restored
    assert 0 < pool.device_bytes_per_device < pool.device_bytes, arch
    st = pool.spill_stats
    assert st["bytes_to_host"] == st["bytes_to_device"] > 0, arch
    print("ARCH_OK", arch)
print("SHARDED_SPILL_OK")
""")
    assert "SHARDED_SPILL_OK" in out
    assert out.count("ARCH_OK") == 5
