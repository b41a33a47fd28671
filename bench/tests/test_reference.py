"""The plain reference draws the program's weights from the seed by itself,
and agrees with the program's forward pass at a tiny size."""

import jax
import numpy as np

from bench.reference import retnet as ref
from bench.tests.cells import TINY_MODEL

SEED = 2 ** 31 + 17


def _program(seed):
    import dataclasses

    from repro import configs
    from repro.models import deploy, lm
    cfg = dataclasses.replace(configs.get_config("retnet-1.3b"), **TINY_MODEL)
    masters, _, paths = lm.init(cfg, jax.random.key(seed))
    return cfg, masters, deploy.deploy_quantize(masters, paths)


def _one_step_apart(a, b, share=1e-4):
    """Equal but for rounding: at most ``share`` of the int8 values one
    step apart (the two sides divide by the scale in their own programs)."""
    d = np.abs(np.asarray(a, np.int32) - np.asarray(b, np.int32))
    assert d.max() <= 1 and d.sum() <= max(1, share * d.size)


def test_weights_match_the_program():
    cfg, masters, deployed = _program(SEED)
    s = ref.shape_of({"model": TINY_MODEL})
    embed, head8, head4, keys = ref.model_ends(SEED, s)
    np.testing.assert_array_equal(embed, masters["embed"])
    _one_step_apart(head8[0], deployed["lm_head"]["w8_vals"])
    assert float(head8[1]) == float(deployed["lm_head"]["w8_scale"])
    from repro.core import mxint4 as mx
    q = mx.MXINT4Weight(deployed["lm_head"]["mx_packed"],
                        deployed["lm_head"]["mx_exps"],
                        masters["lm_head"]["w"].shape)
    np.testing.assert_array_equal(head4, mx.dequantize_mxint4(q))
    names = {"wq": ("ret", "wq"), "wk": ("ret", "wk"), "wv": ("ret", "wv"),
             "wg": ("ret", "wg"), "wo": ("ret", "wo"), "wi": ("mlp", "wi"),
             "wd": ("mlp", "wo")}
    for layer in range(s.n_layers):
        np.testing.assert_array_equal(
            ref._layer_masters(keys[layer], s)["wq"],
            masters["blocks"]["ret"]["wq"]["w"][layer])
        w8, _ = ref._deployed_layer(keys[layer], s)
        for mine, (blk, name) in names.items():
            p = deployed["blocks"][blk][name]
            _one_step_apart(w8[mine][0], p["w8_vals"][layer])
            assert float(w8[mine][1]) == float(p["w8_scale"][layer])


def test_chunk_schedule():
    assert ref.chunk_schedule(300, 128) == [128, 128, 32, 8, 4]
    assert ref.chunk_schedule(128, 128) == [128]
    assert ref.chunk_schedule(7, 128) == [4, 2, 1]


def test_greedy_tokens_of_the_program_score_near_zero():
    from repro.serving import EngineSpec, InferenceEngine
    from repro.serving.sampling import GenerationConfig
    from repro.serving.scheduler import Request, RequestScheduler
    cfg, _, deployed = _program(SEED)
    eng = InferenceEngine.from_config(cfg, EngineSpec(), params=deployed)
    sched = RequestScheduler(eng, n_slots=2, cache_len=96, chunk_size=32,
                             gen=GenerationConfig(max_new_tokens=20,
                                                  stop_tokens=()))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 512, n).tolist() for n in (45, 70)]
    for i, p in enumerate(prompts):
        sched.submit(Request(uid=i, prompt=p, max_new_tokens=20))
    out = sched.run()
    gaps = ref.served_gaps(SEED, ref.shape_of({"model": TINY_MODEL}),
                           [(p, out[i].tokens) for i, p in enumerate(prompts)],
                           32)
    assert [len(g) for g in gaps] == [20, 20]
    assert max(float(g.max()) for g in gaps) < 1e-4
    # A token the reference would not choose scores a visible gap.
    bad = [(prompts[0], [(t + 1) % 512 for t in out[0].tokens])]
    assert float(ref.served_gaps(SEED, ref.shape_of({"model": TINY_MODEL}),
                                 bad, 32)[0].max()) > 1e-3
