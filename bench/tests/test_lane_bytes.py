"""What a busy lane moves of its own state, from the cell's reference to the
two readers that count it: `run_cell.lane_bytes`, `mfu.decode`,
`mfu.prefill`."""

import dataclasses

import pytest

from bench import run_cell
from bench.tests.cells import ROOT
from bench.tests.test_loadgen import CYCLES


@pytest.mark.parametrize("cell", ["retnet-1.3b.silo-8", "retnet-1.3b.liso-4",
                                  "retnet-6.7b.silo-1"])
def test_retnet_lane_bytes_are_its_state_read_and_written(cell):
    c = run_cell.load_cell(cell)
    m = c.config["model"]
    # f32 [layers, heads, dk, dv], dv = 2 dk, read and written once: the
    # same at every position and for every chunk.
    state = m["n_layers"] * 2 * m["d_model"] ** 2 // m["n_heads"] * 4
    assert state == {"retnet-1.3b": 100_663_296,
                     "retnet-6.7b": 134_217_728}[c.spec["config"]]
    decode, prefill = run_cell.lane_bytes(c)
    assert decode == 2 * state
    assert prefill and set(prefill.values()) == {2 * state}


KV_REFERENCE = '''
def shape_of(config):
    return None


def lane_bytes(shape, position, tokens):
    # 1000 B of recurrent state read and written; 10 B of keys and values
    # per position, those below read, the step's own written.
    return 2 * 1000 + 10 * position + 10 * tokens
'''


class _Capture:
    """(executions, device seconds) of each program in a capture."""

    def program_seconds(self, pattern):
        return {"pool_step": (4, 0.5), "prefill_chunk": (3, 0.25)}[pattern]


def _read(metric, ctx):
    return run_cell._load_module(ROOT / "bench" / "metrics"
                                 / f"{metric}.py").read(ctx)


def test_a_kv_reference_is_counted_once_at_the_traffic_positions(tmp_path):
    (tmp_path / "kv.py").write_text(KV_REFERENCE)
    cell = dataclasses.replace(run_cell.load_cell("retnet-1.3b.liso-4"),
                               ref=run_cell._load_module(tmp_path / "kv.py"))
    decode, prefill = run_cell.lane_bytes(cell)

    # liso-4's 571 decode steps take in positions that add up to
    # 520300 + 12017 (test_loadgen.CYCLES).
    want_decode = 2000 + 10 * (520300 + 12017) / 571 + 10
    # Its 128-token chunks: a prompt of p tokens has k = p // 128, starting
    # at 0, 128, ..., 128 (k - 1).
    ks = [p // 128 for p, _ in CYCLES["liso-4"][0]]
    want_128 = 2000 + 1280 + 1280 * sum(k * (k - 1) / 2 for k in ks) / sum(ks)
    # Its 32-token chunks, read off by hand: the prompts of 482, 885, 1087,
    # 419 and 805 tokens put one each at 448, 832, 1024, 384 and 768.
    want_32 = 2000 + 320 + 10 * (448 + 832 + 1024 + 384 + 768) / 5
    assert decode == pytest.approx(want_decode, rel=1e-12)
    assert prefill[128] == pytest.approx(want_128, rel=1e-12)
    assert prefill[32] == pytest.approx(want_32, rel=1e-12)

    costs = tmp_path / "bench" / "costs"
    costs.mkdir(parents=True)
    (costs / "toy.py").write_text(
        "def cost(avals):\n"
        "    return {'weight_bytes': 10000, 'k': 10, 'n': 10}\n")
    calls = [("toy", None, None, 1)]
    ctx = run_cell.TraceContext(
        _Capture(), None, 0.0, 1.0,
        [{"ph": "C", "name": "active", "t": t, "args": {"value": 2}}
         for t in (0.1, 0.2)],
        [128, 128, 32],
        {"pool_step": calls, "prefill_chunk[128]": calls,
         "prefill_chunk[32]": calls},
        decode, prefill,
        {"bf16_flops": 1e15, "int8_ops": 1e15, "hbm_bytes_per_s": 1e6},
        tmp_path)
    # Bytes bound both.  A pool step: 10000 B of weights and two busy
    # lanes' bytes, at 1e6 B/s; 4 of them in 0.5 s.
    assert _read("mfu.decode", ctx) == pytest.approx(
        100 * 4 * (10000 + 2 * want_decode) / 1e6 / 0.5, rel=1e-12)
    # A chunk: 10000 B of weights and the lane's bytes at its length; 3
    # chunk programs, the mean of the captured chunks each, in 0.25 s.
    mean_chunk = (2 * (10000 + want_128) + (10000 + want_32)) / 3 / 1e6
    assert _read("mfu.prefill", ctx) == pytest.approx(
        100 * 3 * mean_chunk / 0.25, rel=1e-12)
