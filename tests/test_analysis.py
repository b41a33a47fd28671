"""repro.analysis: AST lint rules (Layer 1) + program-audit smoke (Layer 2).

Layer-1 cases run `lint_source` on inline snippets — per rule one violating,
one clean, and one suppressed case — so the rules are pinned independently of
what the live tree happens to contain.  Layer-2 reuses the shared reduced
engine to smoke the donation/transfer/recompile audits and the HLO-text
parsers they stand on.
"""

import json

import pytest

from conftest import fp_engine
from repro.analysis import lint, lint_source, lint_tree, program_audit


def rules_of(src: str, path: str = "serving/x.py") -> list[str]:
    return [f.rule for f in lint_source(src, path)]


# -- R1: compat-api ----------------------------------------------------------

class TestCompatApiRule:
    def test_violating(self):
        src = ("import jax\n"
               "def make(g):\n"
               "    return jax.jit(g, in_shardings=(None,))\n")
        assert rules_of(src) == ["compat-api"]

    def test_violating_renamed_import(self):
        # an import alias must not hide the origin
        src = ("from jax.experimental.shard_map import shard_map as smap\n"
               "y = smap(f, mesh=m, in_specs=(), out_specs=())\n")
        assert "compat-api" in rules_of(src)

    def test_clean_via_compat(self):
        src = ("from repro.compat import jit_sharded\n"
               "f = jit_sharded(g, in_shardings=(None,))\n")
        assert rules_of(src) == []

    def test_clean_plain_jit(self):
        src = ("import jax\n"
               "def make(g):\n"
               "    return jax.jit(g, donate_argnums=(0,))\n")
        assert rules_of(src) == []

    def test_compat_module_exempt(self):
        src = "import jax\nm = jax.make_mesh((1,), ('data',))\n"
        assert rules_of(src, "compat.py") == []
        assert rules_of(src, "launch/train.py") == ["compat-api"]

    def test_suppressed(self):
        src = ("import jax\n"
               "def make(g):\n"
               "    return jax.jit(g, in_shardings=(None,))"
               "  # repro: allow(compat-api)\n")
        assert rules_of(src) == []


# -- R2: bare-assert ---------------------------------------------------------

class TestBareAssertRule:
    def test_violating(self):
        src = "def f(x):\n    assert x > 0\n    return x\n"
        assert rules_of(src, "core/x.py") == ["bare-assert"]

    def test_clean(self):
        src = ("def f(x):\n"
               "    if x <= 0:\n"
               "        raise ValueError(x)\n"
               "    return x\n")
        assert rules_of(src, "core/x.py") == []

    def test_suppressed_prev_line(self):
        src = ("def f(x):\n"
               "    # repro: allow(bare-assert)\n"
               "    assert x > 0\n")
        assert rules_of(src, "core/x.py") == []


# -- R3: host-sync -----------------------------------------------------------

class TestHostSyncRule:
    def test_violating_item(self):
        src = "def step(self, x):\n    return x.item()\n"
        assert rules_of(src, "serving/x.py") == ["host-sync"]

    def test_violating_device_get(self):
        src = ("import jax\n"
               "def step(x):\n    return jax.device_get(x)\n")
        assert rules_of(src, "serving/x.py") == ["host-sync"]

    def test_violating_int_of_indexed(self):
        src = "def f(t, i):\n    return int(t[i, 0])\n"
        assert rules_of(src, "serving/x.py") == ["host-sync"]

    def test_clean_int_of_python_math(self):
        # host-side python arithmetic is not a device sync
        src = "def f(n, k):\n    return int(n * k / 2)\n"
        assert rules_of(src, "serving/x.py") == []

    def test_scoped_to_hot_packages(self):
        src = "def f(x):\n    return x.item()\n"
        assert rules_of(src, "runtime/x.py") == []

    def test_allowlisted_drain_site(self):
        # the scheduler's batched post-step drain is the sanctioned sync point
        src = ("class RequestScheduler:\n"
               "    def step(self):\n"
               "        return int(self._tokens[0][0, 0, 0])\n")
        assert rules_of(src, "serving/scheduler.py") == []

    def test_suppressed(self):
        src = ("def step(x):\n"
               "    return x.item()  # repro: allow(host-sync)\n")
        assert rules_of(src, "serving/x.py") == []

    def test_tracer_flush_is_the_only_obs_sync_site(self):
        # obs/ is in the rule's scope; only Tracer.flush may gather.
        src = ("import jax\n"
               "class Tracer:\n"
               "    def flush(self):\n"
               "        return jax.device_get({})\n"
               "    def begin(self):\n"
               "        return jax.device_get({})\n")
        assert rules_of(src, "obs/trace.py") == ["host-sync"]
        assert rules_of(src, "obs/other.py") == ["host-sync", "host-sync"]


# -- R4: module-scope-compute ------------------------------------------------

class TestModuleScopeComputeRule:
    def test_violating(self):
        src = "import jax.numpy as jnp\nTABLE = jnp.arange(1024)\n"
        assert rules_of(src, "models/x.py") == ["module-scope-compute"]

    def test_clean_inside_function(self):
        src = ("import jax.numpy as jnp\n"
               "def table():\n    return jnp.arange(1024)\n")
        assert rules_of(src, "models/x.py") == []

    def test_clean_numpy_constant(self):
        src = "import numpy as np\nTABLE = np.arange(1024)\n"
        assert rules_of(src, "models/x.py") == []

    def test_suppressed(self):
        src = ("import jax.numpy as jnp\n"
               "T = jnp.arange(4)  # repro: allow(module-scope-compute)\n")
        assert rules_of(src, "models/x.py") == []


# -- driver: tree walk, baseline, live tree ----------------------------------

class TestLintDriver:
    def test_live_tree_is_clean(self):
        # THE invariant this PR establishes: empty baseline, zero findings.
        report = lint_tree(lint.default_root(),
                           lint.load_baseline(lint.default_baseline_path()))
        assert report.new == [], report.render(verbose=True)

    def test_baseline_grandfathers_and_goes_stale(self, tmp_path):
        root = tmp_path / "pkg"
        (root / "core").mkdir(parents=True)
        bad = root / "core" / "x.py"
        bad.write_text("def f(x):\n    assert x\n")
        report = lint_tree(str(root), [])
        assert [f.rule for f in report.new] == ["bare-assert"]

        bl = tmp_path / "baseline.json"
        lint.save_baseline(str(bl), report.new)
        report2 = lint_tree(str(root), lint.load_baseline(str(bl)))
        assert report2.new == [] and len(report2.grandfathered) == 1

        bad.write_text("def f(x):\n    return x\n")   # fixed -> entry stale
        report3 = lint_tree(str(root), lint.load_baseline(str(bl)))
        assert report3.new == [] and len(report3.stale_baseline) == 1

    def test_baseline_round_trip(self, tmp_path):
        bl = tmp_path / "b.json"
        findings = lint_source("def f(x):\n    assert x\n", "core/x.py")
        lint.save_baseline(str(bl), findings)
        entries = lint.load_baseline(str(bl))
        assert json.load(open(bl)) and entries[0][0] == "bare-assert"


# -- Layer 2: HLO parsers + program-audit smoke ------------------------------

class TestProgramAudit:
    def test_parse_io_aliases(self):
        text = ('HloModule m, input_output_alias={ {1}: (13, {}, may-alias),'
                ' {2}: (14, {}, may-alias) }, entry_computation_layout='
                '{(f32[2,8]{1,0}, s32[])->(f32[2,8]{1,0})}')
        assert program_audit.parse_io_aliases(text) == [((1,), 13),
                                                        ((2,), 14)]

    def test_entry_param_bytes(self):
        text = ('entry_computation_layout={(f32[2,8]{1,0}, s32[], '
                'bf16[4]{0})->(f32[2,8]{1,0})}')
        assert program_audit.entry_param_bytes(text) == [64, 4, 8]

    def test_hlo_opcode_scan(self):
        text = ('  %r = f32[8]{0} copy-start(f32[8]{0} %x)\n'
                '  %c = f32[] custom_call(), custom_call_target='
                '"xla_ffi_python_cpu_callback"\n')
        ops, calls = program_audit._scan_transfers(text)
        assert "copy-start" in ops and calls

    def test_donation_audit_smoke(self):
        res = program_audit.audit_donation(
            engine=fp_engine("retnet-1.3b"), chunk=8, cache_len=32)
        assert res.ok, res.detail
        assert res.metrics["fraction"] >= 0.9

    def test_transfer_audit_smoke(self):
        res = program_audit.audit_transfers(
            engine=fp_engine("retnet-1.3b"), max_new_tokens=4, spec_k=2)
        assert res.ok, res.detail

    def test_recompile_audit_smoke(self):
        res = program_audit.audit_recompiles(max_len=9, chunk_size=4)
        assert res.ok, res.detail
        assert res.metrics["prefill_signatures"] <= res.metrics["bucket_bound"]

    def test_observability_audit_smoke(self):
        res = program_audit.audit_observability(max_new_tokens=4, spec_k=2)
        assert res.ok, res.detail
        assert res.metrics["diffs"] == []
        assert res.metrics["programs"] == ["decode", "verify", "pool_step"]

    def test_report_render_and_dict(self):
        r = program_audit.AuditResult("x", True, "fine", {})
        rep = program_audit.AuditReport([r])
        assert rep.ok and "PASS" in rep.render()
        assert rep.to_dict()["results"][0]["name"] == "x"
