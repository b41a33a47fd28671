"""The harness end to end on the CPU, at a tiny size: a sound run is
correct, the low-precision control and each fault of the timed path are
not, a new cell is found by name, and no chip means no result."""

import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

from bench import run_cell
from bench.tests.cells import ROOT, TINY_CELL


def _run(root, seed=2 ** 31 + 5, **kw):
    return run_cell.run(TINY_CELL, seed, 2.0, False, root=root,
                        platform="cpu", t_process=time.perf_counter(), **kw)


def test_sound_run(tiny_root):
    r = _run(tiny_root)
    assert r["correct"] is True
    assert list(r)[-1] == "check"
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"output_tokens_per_s", "itl_p95",
                                 "ttft_p95", "setup_s"}
    assert r["compiles_in_window"] == 0
    assert r["check"]["widest_gap"]["value"] < 1e-4
    assert r["device"]["platform"] == "cpu"


def test_low_precision_control_is_not_correct(tiny_root):
    r = _run(tiny_root, control="int4_prefill")
    assert r["correct"] is False
    assert r["check"]["widest_gap"]["value"] > r["check"]["widest_gap"][
        "limit"]


def _state_unchanged(sched):
    step = sched._pool_step
    sched._pool_step = lambda p, t, s, k: (lambda o: (o[0], s, o[2]))(
        step(p, t, s, k))


def _half_batch(sched):
    import jax
    import jax.numpy as jnp
    step = sched._pool_step

    def half(p, t, s, k):
        nxt, new, keys = step(p, t, s, k)
        n = nxt.shape[0]
        keep = jnp.arange(n) < (n + 1) // 2
        new = jax.tree.map(lambda a, b: jnp.where(
            keep.reshape((n,) + (1,) * (a.ndim - 1)), a, b), new, s)
        return jnp.where(keep, nxt, t[:, 0, 0]), new, keys
    sched._pool_step = half


def _token_altered(sched):
    step = sched._pool_step

    def altered(p, t, s, k):
        nxt, new, keys = step(p, t, s, k)
        return nxt.at[0].set((nxt[0] + 1) % 512), new, keys
    sched._pool_step = altered


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered],
                         ids=["state_unchanged", "half_batch",
                              "token_altered"])
def test_fault_in_the_timed_path_is_not_correct(tiny_root, fault):
    r = _run(tiny_root, faults=fault)
    assert r["correct"] is False


def test_a_new_cell_is_found_by_name(tiny_root):
    b = json.loads((tiny_root / "BENCHMARK.json").read_text())
    bench = tiny_root / "bench"
    (bench / "traffic" / "tiny-open.json").write_text(json.dumps(
        {"loop": "open", "slots": 2, "arrivals": {"kind": "poisson",
                                                  "rate_rps": 20.0},
         "prompt": {"min": 8, "max": 9}, "output": {"min": 4, "max": 12}}))
    (bench / "limits" / "tiny.tiny-open.json").write_text(json.dumps(
        {"widest_gap": 1e-3, "mean_gap": 1e-4, "min_served_tokens": 16}))
    (bench / "metrics" / "tiny.steps.py").write_text(
        "def read(ctx):\n    return float(len(ctx.window.records))\n")
    b["workloads"].append({"name": "tiny.tiny-open", "config": "tiny",
                           "traffic": "tiny-open", "chips": 1, "why": "t"})
    b["per_layer"].append({"name": "tiny.steps", "unit": "requests",
                           "better": "higher", "source": "program_counter",
                           "layer": "test", "moves": "output_tokens_per_s",
                           "workloads": ["tiny.tiny-open"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(b))

    cell = run_cell.load_cell("tiny.tiny-open", tiny_root)
    assert cell.traffic.loop == "open"
    assert [m["name"] for m in run_cell.per_layer_metrics(cell)
            ] == ["tiny.steps"]

    class Ctx:
        window = type("W", (), {"records": [1, 2, 3]})
    assert run_cell.read_per_layer(cell, Ctx, tiny_root) == {
        "tiny.steps": {"value": 3.0, "unit": "requests"}}
    r = run_cell.run("tiny.tiny-open", 7, 2.0, False, root=tiny_root,
                     platform="cpu", t_process=time.perf_counter())
    assert r["correct"] is True and r["attempted"] > 0


OFFSET = """

_exact = served_gaps


def served_gaps(seed, s, requests, chunk):
    return [g + 0.25 for g in _exact(seed, s, requests, chunk)]
"""


def _edit_config(root, edit):
    path = root / "bench" / "configs" / "tiny.json"
    cfg = json.loads(path.read_text())
    edit(cfg)
    path.write_text(json.dumps(cfg))


def test_the_reference_the_configuration_names_scores_the_cell(tiny_root):
    # A copy of the reference whose gaps are all 0.25 too wide: a sound run
    # scored by it is not correct, so the file named is the one used.
    ref = tiny_root / "bench" / "reference"
    (ref / "offset.py").write_text((ref / "retnet.py").read_text() + OFFSET)
    _edit_config(tiny_root, lambda c: c.update(reference="offset"))
    r = _run(tiny_root)
    assert r["correct"] is False
    assert 0.25 <= r["check"]["widest_gap"]["value"] < 0.25 + 1e-4


def _no_reference(cfg):
    del cfg["reference"]


def _missing_reference(cfg):
    cfg["reference"] = "nowhere"


def _wrong_family(cfg):
    cfg["arch"] = "hymba-1.5b"


@pytest.mark.parametrize("edit, error, says", [
    (_no_reference, ValueError, "tiny.json names no reference"),
    (_missing_reference, FileNotFoundError, "reference/nowhere.py"),
    (_wrong_family, ValueError, "family 'hybrid'")],
    ids=["no_reference", "missing_reference", "wrong_family"])
def test_a_reference_that_cannot_score_the_model_fails_at_load(
        tiny_root, monkeypatch, edit, error, says):
    _edit_config(tiny_root, edit)
    with pytest.raises(error, match=says):
        run_cell.load_cell(TINY_CELL, tiny_root)
    monkeypatch.setattr(run_cell, "weights_fn",
                        lambda mc: pytest.fail("weights drawn"))
    with pytest.raises(error, match=says):
        _run(tiny_root)


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run_cell.py", "--workload",
         "retnet-1.3b.silo-8", "--seed", "0", "--seconds", "10",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_chip_no_result():
    p = _cli(ROOT)
    assert p.returncode == 2 and p.stdout == ""


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    p = _cli(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0 and p.stdout == ""
    assert pathlib.Path(tmp_path / "BENCHMARK.json").exists()
