"""A tiny cell (two layers, d_model 128) in a bench root of its own, for
the CPU tests."""

import json
import pathlib
import shutil
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY_CELL = "tiny.tiny-3"
TINY_MODEL = {"n_layers": 2, "d_model": 128, "n_heads": 4, "n_kv_heads": 4,
              "d_ff": 192, "vocab_size": 512, "vocab_pad_multiple": 64,
              "param_dtype": "bfloat16"}


def make_root(path: pathlib.Path, *, limit: float = 1e-3) -> pathlib.Path:
    """A bench root holding one tiny closed-loop cell, with the real
    metric readers, kernel costs and references, and CPU peaks."""
    bench = path / "bench"
    for sub in ("configs", "traffic", "limits"):
        (bench / sub).mkdir(parents=True, exist_ok=True)
    for sub in ("metrics", "costs", "reference"):
        shutil.copytree(ROOT / "bench" / sub, bench / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    cfg = json.loads((ROOT / "bench" / "configs" / "retnet-1.3b.json")
                     .read_text())
    cfg.update(name="tiny", model=TINY_MODEL, scheduler={"chunk_size": 32})
    (bench / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "tiny-3.json").write_text(json.dumps(
        {"loop": "closed", "clients": 3, "slots": 3,
         "prompt": {"min": 33, "max": 36}, "output": {"min": 8, "max": 40}}))
    (bench / "limits" / f"{TINY_CELL}.json").write_text(json.dumps(
        {"widest_gap": limit, "mean_gap": limit / 10,
         "min_served_tokens": 64}))
    (bench / "peaks.json").write_text(json.dumps(
        {"source": "test", "devices": {"cpu": {
            "bf16_flops": 1e12, "int8_ops": 2e12,
            "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}}}))
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["configs"] = [{"name": "tiny", "source": "test",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test"}]
    b["workloads"] = [{"name": TINY_CELL, "config": "tiny",
                       "traffic": "tiny-3", "chips": 1, "why": "test"}]
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [TINY_CELL]
    (path / "BENCHMARK.json").write_text(json.dumps(b))
    return path
