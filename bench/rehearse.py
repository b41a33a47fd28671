#!/usr/bin/env python3
"""Compile each cell's programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [cell ...]

For every cell (all of ``BENCHMARK.json`` by default) it compiles, with the
Pallas kernels forced to their compiled Mosaic path, the programs a run
drives: the one jitted call that makes the deployed weights from a seed,
the scheduler's pool step for the cell's lanes, and the prompt-chunk
program for every chunk length the mix produces.  It prints, per program,
the compile seconds, the ``tpu_custom_call`` count and
``memory_analysis()``; a refusal by the TPU compiler raises here instead of
on the chip.  A compile that passes is not a chip run.
"""

from __future__ import annotations

import os
import pathlib
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def _abstract(tree, sharding):
    import jax
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


def _compile(name, fn, *args):
    t = time.perf_counter()
    compiled = fn.lower(*args).compile()
    secs = time.perf_counter() - t
    mem = compiled.memory_analysis()
    calls = compiled.as_text().count("tpu_custom_call")
    gib = 2.0 ** 30
    print(f"  {name}: {secs:.1f} s, {calls} tpu_custom_call, "
          f"args {mem.argument_size_in_bytes / gib:.3f} GiB, "
          f"out {mem.output_size_in_bytes / gib:.3f} GiB, "
          f"temp {mem.temp_size_in_bytes / gib:.3f} GiB", flush=True)


def rehearse(cell_name: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import run_cell
    from bench.loadgen import chunk_schedule
    from repro.kernels import ops
    from repro.models import lm
    from repro.serving import EngineSpec, InferenceEngine
    from repro.serving.sampling import GenerationConfig
    from repro.serving.scheduler import RequestScheduler

    ops._interpret = lambda interpret: interpret      # Mosaic, not interpret
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    cell = run_cell.load_cell(cell_name)
    c, t = cell.config, cell.traffic
    mc = run_cell.model_config(cell)
    spec = EngineSpec(**dict(c["engine"], kernel_impl="pallas"))
    print(cell_name, flush=True)

    make = run_cell.weights_fn(mc)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=chip)
    _compile("init+deploy", make, key)

    params = _abstract(jax.eval_shape(make, jax.random.key(0)), chip)
    engine = InferenceEngine(mc, params, spec)
    gen = GenerationConfig(max_new_tokens=t.lengths.new_max, stop_tokens=())
    sched = RequestScheduler(engine, n_slots=t.slots, cache_len=t.cache_len,
                             gen=gen, chunk_size=c["scheduler"]["chunk_size"])
    clen = t.cache_len
    _compile(f"pool_step[{t.slots} lanes]", sched._pool_step, params,
             _abstract(sched._tokens[clen], chip),
             _abstract(sched.pool.get_store(clen), chip),
             _abstract(sched._keys[clen], chip))
    cache = _abstract(jax.eval_shape(lambda: lm.make_decode_cache(
        mc, 1, clen, sched.pool.dtype, start_pos=0)), chip)
    sizes = sorted({n for plen in t.prompt_lengths()
                    for n in chunk_schedule(plen,
                                            c["scheduler"]["chunk_size"])})
    for n in sizes:
        tokens = {"tokens": jax.ShapeDtypeStruct((1, n), jnp.int32,
                                                 sharding=chip)}
        _compile(f"prefill_chunk[{n}]", engine._prefill_chunk, params,
                 tokens, cache)


def main(argv) -> int:
    import json

    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name in argv or [w["name"] for w in bench["workloads"]]:
        rehearse(name)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
