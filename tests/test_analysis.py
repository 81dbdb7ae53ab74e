"""Plan-integrity analyzer contract (repro.analysis; docs/analysis.md).

  1. LINT — every rule fires on a synthetic true positive and stays
     quiet on the adjacent near-miss; the ``# analysis: ignore[rule]``
     pragma suppresses exactly its own rule; the repo itself lints
     clean.
  2. SPECKEY — the static audit passes on the real sources and
     catches a deliberately dropped SearchSpec field / keyless plan
     site; the runtime audit passes and catches a ``_plan_key`` that
     forgets znorm.
  3. SANITIZE — NaN/±inf pad canaries leave results bit-identical on
     the real engine, and an intentionally broken id mask is caught.
  4. SURFACE — importing ``repro.analysis`` and running the lint +
     static-speckey CLI never initializes jax; exit codes gate on
     findings; ``launch/discord.py --selfcheck`` is wired up.
  5. IRLINT — ``plan_kind_registry`` covers every ``PLAN_KINDS`` kind;
     the static lane/FLOP model equals the runtime formulas (all 23
     kinds, 1/2/4 devs) and the *executed* ``tile_lanes`` deltas
     (quantized kinds decompose into bound + refinement lanes); the
     repo's jaxprs audit clean; each IR rule fires on a synthetic
     true positive (f64 literal, unpinned dot_general — including a
     bare bf16 bound dot, smuggled callback, oversized const,
     miscounted lane model) and stays quiet on the near-miss.
  6. SHADOW — f64 replay is clean on the real engines; the regret
     comparator flags drifted positions and diverging nnds; inflated
     tile numerics are caught end to end; the quantized kinds replay
     per precision under the same regret gate and must prune on the
     benign series (a vacuous bound radius is flagged).
  7. CLI — the wall-clock budget and the new passes gate exit codes
     and populate the v2 report counts.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.analysis import (Finding, lint_source, report_dict,
                            run_lint, static_audit, write_report)
from repro.analysis.lint import package_root
from repro.analysis.speckey import coverage

SRC = os.path.join(os.path.dirname(__file__), os.pardir, "src")


# ---------------------------------------------------------------------
# 1. LINT: per-rule true positive + near-miss
# ---------------------------------------------------------------------
def _rules(src, relpath):
    return sorted({f.rule for f in lint_source(src, relpath)})


class TestTileMathRule:
    def test_matmul_operator_positive(self):
        assert _rules("d = q @ c.T\n", "core/foo.py") == ["tile-math"]

    def test_dot_general_positive(self):
        src = "out = lax.dot_general(a, b, dims)\n"
        assert "tile-math" in _rules(src, "core/foo.py")

    def test_manual_d2_positive(self):
        src = "d2 = np.sum((a - b) ** 2, axis=1)\n"
        assert "tile-math" in _rules(src, "core/foo.py")

    def test_method_call_sum_positive(self):
        src = "d2 = ((a - b) ** 2).sum(axis=1)\n"
        assert "tile-math" in _rules(src, "core/foo.py")

    def test_plain_sum_near_miss(self):
        # a sum that is not a squared difference is fine
        src = "tot = np.sum(a * b, axis=1)\ncs = np.cumsum(x ** 2)\n"
        assert _rules(src, "core/foo.py") == []

    def test_allowlisted_tile_layer(self):
        src = "d2 = np.sum((a - b) ** 2, axis=1)\n"
        assert _rules(src, "core/tiles.py") == []
        assert _rules(src, "core/serial/brute.py") == []

    def test_out_of_scope_lm_scaffolding(self):
        # models/ legitimately matmuls — not this rule's business
        assert _rules("y = x @ w\n", "models/attention.py") == []


class TestHostSyncRule:
    def test_item_in_build_positive(self):
        src = ("def build():\n"
               "    def fn(x):\n"
               "        return x.max().item()\n"
               "    return fn\n")
        assert "host-sync" in _rules(src, "core/engine.py")

    def test_numpy_call_in_build_positive(self):
        src = ("def build():\n"
               "    def fn(x):\n"
               "        return np.asarray(x)\n"
               "    return fn\n")
        assert "host-sync" in _rules(src, "core/engine.py")

    def test_float_and_block_until_ready_positive(self):
        src = ("def build():\n"
               "    def fn(x):\n"
               "        y = float(x[0])\n"
               "        return x.block_until_ready()\n"
               "    return fn\n")
        assert _rules(src, "core/engine.py") == ["host-sync"]

    def test_outside_build_near_miss(self):
        # host code outside a plan builder is the normal case
        src = ("def search(self, x):\n"
               "    xp = np.asarray(x)\n"
               "    return float(xp.max())\n")
        assert _rules(src, "core/engine.py") == []

    def test_pan_engine_method_positive(self):
        src = ("class PanEngine:\n"
               "    def rows(self, q):\n"
               "        return np.asarray(q)\n")
        assert "host-sync" in _rules(src, "core/pan.py")

    def test_pan_module_level_near_miss(self):
        src = "def canonical_ladder(lad):\n    return np.sort(lad)\n"
        assert _rules(src, "core/pan.py") == []


class TestDeferredHostSyncRule:
    """The serve/telemetry dispatch paths: output syncs and nested
    flushes are banned, host-side *input* staging is not."""

    def test_output_sync_in_exec_group_positive(self):
        src = ("class DiscordServer:\n"
               "    def _exec_group(self, chunk):\n"
               "        out = self._dispatch(chunk)\n"
               "        return np.asarray(out)\n")
        assert "host-sync" in _rules(src, "serve/discord.py")

    def test_item_and_block_positive(self):
        src = ("class DiscordServer:\n"
               "    def _exec_group(self, chunk):\n"
               "        n = self.counter.item()\n"
               "        return self.out.block_until_ready()\n")
        assert _rules(src, "serve/discord.py") == ["host-sync"]

    def test_nested_flush_positive(self):
        src = ("class TelemetryMonitor:\n"
               "    def _prepare_metric(self, name, x):\n"
               "        self.server.flush()\n"
               "        return name\n")
        assert "host-sync" in _rules(src, "telemetry/monitor.py")

    def test_input_staging_near_miss(self):
        # np.stack/np.array input staging and host float() math are
        # the dispatch path's normal business — only *output* syncs
        # (np.asarray/to_np/.item()) break the deferred contract
        src = ("class DiscordServer:\n"
               "    def _exec_group(self, chunk):\n"
               "        stack = np.stack([op['xp'] for op in chunk])\n"
               "        loc = float(stack.mean())\n"
               "        return self._dispatch(stack, loc)\n")
        assert _rules(src, "serve/discord.py") == []

    def test_other_method_near_miss(self):
        # the same syncs outside the deferred scopes are fine (the
        # response path _finish_group is where blocking folds live)
        src = ("class DiscordServer:\n"
               "    def _finish_group(self, chunk, out):\n"
               "        return np.asarray(out)\n")
        assert _rules(src, "serve/discord.py") == []

    def test_repo_scopes_exist(self):
        # the deferred scopes must keep pointing at real methods
        import ast
        from repro.analysis.lint import HostSyncRule
        root = package_root()
        rule = HostSyncRule()
        for rel, names in rule.DEFERRED.items():
            tree = ast.parse((root / rel).read_text())
            found = {n.name for n in ast.walk(tree)
                     if isinstance(n, ast.FunctionDef)}
            for name in names:
                assert name in found, f"{rel} lost {name}"


class TestF64KernelRule:
    def test_dtype_attribute_positive(self):
        src = "acc = jnp.zeros(n, jnp.float64)\n"
        assert "f64-kernel" in _rules(src, "kernels/foo.py")

    def test_dtype_string_positive(self):
        src = "x = x.astype('float64')\n"
        assert "f64-kernel" in _rules(src, "kernels/foo.py")

    def test_bare_dot_general_positive(self):
        src = "t = lax.dot_general(q, c, dims)\n"
        assert "f64-kernel" in _rules(src, "kernels/foo.py")

    def test_pinned_dot_general_near_miss(self):
        src = ("t = lax.dot_general(q, c, dims, "
               "preferred_element_type=jnp.float32)\n")
        assert _rules(src, "kernels/foo.py") == []

    def test_f32_near_miss(self):
        src = "x = jnp.asarray(x, jnp.float32)\n"
        assert _rules(src, "kernels/foo.py") == []

    def test_core_out_of_scope(self):
        # f64 is the *host-side* accuracy convention outside kernels/
        src = "x = np.asarray(x, np.float64)\n"
        assert "f64-kernel" not in _rules(src, "core/engine.py")


class TestUntrackedJitRule:
    def test_module_level_jit_positive(self):
        src = "fn = jax.jit(body)\n"
        assert "untracked-jit" in _rules(src, "core/foo.py")

    def test_decorator_jit_positive(self):
        src = ("@functools.partial(jax.jit, static_argnames=('s',))\n"
               "def impl(x, *, s):\n"
               "    return x\n")
        assert "untracked-jit" in _rules(src, "core/foo.py")

    def test_inside_get_plan_near_miss(self):
        src = ("def _get_plan(self, key, build):\n"
               "    return jax.jit(build())\n")
        assert _rules(src, "core/foo.py") == []

    def test_kernels_out_of_scope(self):
        assert _rules("fn = jax.jit(body)\n", "kernels/foo.py") == []


class TestIgnorePragma:
    SRC_SAME = "fn = jax.jit(body)  # analysis: ignore[untracked-jit]\n"
    SRC_ABOVE = ("# why: standalone plane.  "
                 "# analysis: ignore[untracked-jit]\n"
                 "fn = jax.jit(body)\n")

    def test_same_line(self):
        assert _rules(self.SRC_SAME, "core/foo.py") == []

    def test_line_above(self):
        assert _rules(self.SRC_ABOVE, "core/foo.py") == []

    def test_other_rule_not_suppressed(self):
        src = "d = q @ c.T  # analysis: ignore[untracked-jit]\n"
        assert _rules(src, "core/foo.py") == ["tile-math"]

    def test_comma_list(self):
        src = ("d = jax.jit(lambda: q @ c.T)  "
               "# analysis: ignore[untracked-jit, tile-math]\n")
        assert _rules(src, "core/foo.py") == []


def test_repo_lints_clean():
    assert run_lint() == []


# ---------------------------------------------------------------------
# 2. SPECKEY
# ---------------------------------------------------------------------
ENGINE_PATH = package_root() / "core" / "engine.py"


def test_static_audit_clean_on_repo():
    assert static_audit() == []


def test_coverage_names_every_field():
    import dataclasses

    cov = coverage()
    # jax-free cross-check against the dataclass via source parse is
    # what static_audit does; here just pin the audited surface
    assert set(cov) == {"s", "k", "method", "znorm", "backend", "P",
                        "alpha", "seed", "r", "block", "ndev",
                        "precision"}
    assert "UNCOVERED" not in cov.values()


def test_static_audit_catches_dropped_field():
    src = ENGINE_PATH.read_text()
    broken = src.replace(
        'PLAN_KEY_FIELDS = ("s", "backend", "znorm", "block", "ndev",\n'
        '                   "precision")',
        'PLAN_KEY_FIELDS = ("s", "backend", "block", "ndev",\n'
        '                   "precision")')
    assert broken != src
    findings = static_audit(engine_source=broken)
    assert any(f.rule == "field-partition" and "znorm" in f.message
               for f in findings)


def test_static_audit_catches_gutted_plan_key():
    src = ENGINE_PATH.read_text()
    broken = src.replace(
        'return (self.backend, self.spec.znorm, self.spec.block,\n'
        '                self.spec.precision) + tuple(key)',
        'return tuple(key)')
    assert broken != src
    findings = static_audit(engine_source=broken)
    rules = {f.rule for f in findings}
    assert "plan-key-prefix" in rules


def test_static_audit_catches_nonliteral_key():
    src = ("PLAN_KEY_FIELDS = (\"s\", \"backend\", \"znorm\", "
           "\"block\", \"ndev\", \"precision\")\n"
           "KIND_DISPATCH_FIELDS = (\"method\",)\n"
           "TRACE_INVARIANT_FIELDS = (\"k\", \"P\", \"alpha\", "
           "\"seed\", \"r\")\n"
           "class DiscordEngine:\n"
           "    def _plan_key(self, key):\n"
           "        return (self.backend, self.spec.znorm,\n"
           "                self.spec.block,\n"
           "                self.spec.precision) + tuple(key)\n"
           "    def _profile_plan(self, s, Lb):\n"
           "        return self._get_plan(make_key(s, Lb), build)\n")
    findings = static_audit(engine_source=src)
    assert any(f.rule == "plan-key-sites" for f in findings)


def test_static_audit_catches_duplicate_plan_kind():
    # a dict literal keeps the last of two equal keys: a second
    # "profile" entry would silently replace the first
    src = ENGINE_PATH.read_text()
    line = '    "tail": _PlanKind("tail", "local"),\n'
    assert line in src
    broken = src.replace(line, line.replace('"tail"', '"profile"', 1))
    findings = static_audit(engine_source=broken)
    assert any(f.rule == "plan-key-sites" and "duplicate" in f.message
               for f in findings)


def test_static_audit_catches_key_without_mesh_shape():
    src = ENGINE_PATH.read_text()
    broken = src.replace("            key += ((ndev,),)\n", "")
    assert broken != src
    findings = static_audit(engine_source=broken)
    assert any(f.rule == "plan-key-sites" and "mesh shape" in f.message
               for f in findings)


def test_runtime_audit_clean_on_repo():
    from repro.analysis.speckey import runtime_audit
    assert runtime_audit(backend="numpy") == []


def test_runtime_audit_catches_incomplete_plan_key(monkeypatch):
    from repro.analysis.speckey import runtime_audit
    from repro.core.engine import DiscordEngine

    def bad_plan_key(self, key):        # drops znorm (and the rest)
        return tuple(key)

    monkeypatch.setattr(DiscordEngine, "_plan_key", bad_plan_key)
    findings = runtime_audit(backend="numpy")
    assert any(f.rule == "key-collision" and "znorm" in f.message
               for f in findings)


# ---------------------------------------------------------------------
# 3. SANITIZE
# ---------------------------------------------------------------------
def test_sanitizer_clean_on_local_kinds():
    from repro.analysis.sanitize import run_sanitizer
    findings, checked = run_sanitizer(
        backends=("numpy",), znorms=(True, False),
        kinds=("profile", "tail", "pan"))
    assert findings == []
    assert len(checked) == 6


def test_sanitizer_catches_broken_mask(monkeypatch):
    from repro.analysis.sanitize import run_sanitizer
    from repro.core.tiles import TileEngine

    # an identity _mask_ids leaves the bucket's pad windows live —
    # exactly the masked-id -1 violation the pass exists to catch
    monkeypatch.setattr(TileEngine, "_mask_ids", lambda self, ids: ids)
    findings, _ = run_sanitizer(backends=("numpy",), znorms=(True,),
                                kinds=("profile",))
    assert any(f.rule in ("poison-leak", "poison-crash")
               for f in findings)


def test_pad_fill_restored_on_error():
    from repro.analysis.sanitize import pad_fill
    from repro.core import engine as engine_mod
    with pytest.raises(RuntimeError):
        with pad_fill(float("nan")):
            raise RuntimeError("boom")
    assert engine_mod.PAD_FILL == 0.0


def test_selfcheck_maps_spec_to_kind_family():
    from repro.analysis.sanitize import _kinds_for_spec
    from repro.core.spec import SearchSpec
    assert _kinds_for_spec(SearchSpec(s=24, method="matrix_profile")) \
        == ("profile", "batched", "tail")
    assert _kinds_for_spec(SearchSpec(s=(16, 24),
                                      method="matrix_profile")) \
        == ("pan", "pan_lb", "pan_tail", "pan_batched")
    assert _kinds_for_spec(SearchSpec(s=24, method="hst")) == ()
    assert _kinds_for_spec(SearchSpec(
        s=24, method="matrix_profile", precision="bf16")) \
        == ("qsweep", "qsweep_tail")
    assert _kinds_for_spec(SearchSpec(
        s=24, method="ring", precision="int8")) == ("qsweep_ring",)


# ---------------------------------------------------------------------
# 4. SURFACE: report schema, jax-freedom, CLI exit codes
# ---------------------------------------------------------------------
def test_report_schema(tmp_path):
    f = Finding("lint", "tile-math", "core/x.py", 3, "nope")
    doc = write_report(str(tmp_path / "r.json"), [f],
                       meta={"passes": ["lint"]},
                       counts={"lint": {"files": 94},
                               "speckey": {"fields": 11}})
    loaded = json.loads((tmp_path / "r.json").read_text())
    assert loaded == doc
    assert loaded["ok"] is False
    # coverage numbers survive, finding totals fold in, and a clean
    # pass still reports its scope (findings: 0)
    assert loaded["counts"] == {
        "lint": {"files": 94, "findings": 1},
        "speckey": {"fields": 11, "findings": 0}}
    assert loaded["findings"][0]["rule"] == "tile-math"
    assert report_dict([])["ok"] is True
    assert report_dict([])["counts"] == {}
    assert str(f) == "core/x.py:3: [lint/tile-math] nope"


def test_report_key_order_deterministic(tmp_path):
    f = Finding("lint", "tile-math", "core/x.py", 3, "nope")
    a, b = tmp_path / "a.json", tmp_path / "b.json"
    write_report(str(a), [f], meta={"z": 1, "a": 2},
                 counts={"lint": {"rules": 4, "files": 94}})
    write_report(str(b), [f], meta={"a": 2, "z": 1},
                 counts={"lint": {"files": 94, "rules": 4}})
    assert a.read_text() == b.read_text()


def test_lint_and_static_speckey_are_jax_free():
    code = ("import sys\n"
            "from repro.analysis import run_lint, static_audit\n"
            "run_lint(); static_audit()\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n"
            "print('ok')\n")
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


def test_cli_lint_exit_codes(tmp_path):
    env = dict(os.environ, PYTHONPATH=SRC)
    rp = tmp_path / "rep.json"
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "lint", "speckey",
         "--static-only", "--report", str(rp)],
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr + out.stdout
    assert json.loads(rp.read_text())["ok"] is True
    # corrupt tree -> findings -> exit 1 (run lint against a copy)
    bad = tmp_path / "pkg"
    (bad / "core").mkdir(parents=True)
    (bad / "core" / "oops.py").write_text("d = q @ c.T\n")
    code = ("import sys\n"
            "from pathlib import Path\n"
            "from repro.analysis import run_lint\n"
            f"fs = run_lint(Path({str(bad)!r}))\n"
            "sys.exit(1 if fs else 0)\n")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 1

    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "nonsense"],
        env=env, capture_output=True, text=True, timeout=60)
    assert out.returncode == 2


def test_launcher_selfcheck_flag_in_help():
    from repro.launch.discord import build_parser
    assert "--selfcheck" in build_parser().format_help()


# ---------------------------------------------------------------------
# 5. IRLINT: plan-kind registry, lane model, per-rule TP + near-miss
# ---------------------------------------------------------------------
def _fake_cell(fn, *, backend="pallas", znorm=True,
               avals=(((8,), "float32"),), const_bytes=None,
               **overrides):
    """Run _audit_cell on an arbitrary traced fn by grafting it onto
    a registry entry (the 'engine' plans it for every kind)."""
    import dataclasses
    from types import SimpleNamespace

    from repro.analysis.irlint import DEFAULT_CONST_BYTES, _audit_cell
    from repro.core.engine import plan_kind_registry
    entry = dataclasses.replace(
        plan_kind_registry()["profile"], geom=(), avals=tuple(avals),
        **overrides)
    eng = SimpleNamespace(plan=lambda kind, *geom: fn)
    return _audit_cell(entry, eng, backend, znorm,
                       const_bytes=const_bytes or DEFAULT_CONST_BYTES)


def test_plan_kind_registry_covers_every_builder():
    # the registry is derived from the table: one entry per kind, in
    # table order, each building through plan(); no builder method
    # exists beside the table
    from repro.analysis.irlint import coverage_audit
    from repro.core.engine import (PLAN_KINDS, DiscordEngine,
                                   plan_kind_registry)
    reg = plan_kind_registry()
    assert len(reg) == 23
    assert list(reg) == list(PLAN_KINDS)
    for kind in ("qsweep", "qsweep_refine", "qsweep_tail",
                 "qsweep_tail_refine", "qsweep_ring"):
        assert kind in reg, f"registry lost quantized kind {kind}"
        assert PLAN_KINDS[kind].quant
    assert not [n for n in dir(DiscordEngine)
                if n.endswith("_plan") and n.startswith("_")
                and n not in ("_get_plan", "_require_profile_plan")]
    assert coverage_audit() == []


def test_coverage_audit_catches_unregistered_kind(monkeypatch):
    # a table kind with no registry entry, and one whose layout has
    # no method, are both coverage findings
    from repro.analysis import irlint
    from repro.core import engine
    kinds = dict(engine.PLAN_KINDS,
                 fake=engine._PlanKind("profile", "nowhere"))
    monkeypatch.setattr(irlint, "PLAN_KINDS", kinds)
    found = {(f.path, f.rule) for f in irlint.coverage_audit()}
    assert found == {("core/engine.py::fake", "ir-kind-coverage")}
    msgs = [f.message for f in irlint.coverage_audit()]
    assert any("no plan_kind_registry entry" in m for m in msgs)
    assert any("_layout_nowhere" in m for m in msgs)


def test_lane_model_matches_runtime_formula_every_kind():
    # static half of the acceptance bar: the width-normalized lane
    # count derived from each entry's declared dot pattern equals the
    # tile_lanes the runtime accounting formulas book, at 1/2/4 devs
    from repro.core.engine import plan_kind_registry
    for ndev in (1, 2, 4):
        for e in plan_kind_registry(ndev=ndev).values():
            assert e.model_lanes() == e.lanes, (e.kind, ndev)


def test_lane_model_matches_executed_tile_lanes():
    # executed half: run one kind per plan family at the pinned audit
    # geometry and compare the engine's booked tile_lanes delta
    import numpy as np

    from repro.core.engine import DiscordEngine, plan_kind_registry
    from repro.core.spec import SearchSpec
    reg = plan_kind_registry(ndev=1)
    x = np.sin(0.31 * np.arange(90.0))
    base = dict(k=2, znorm=True, backend="xla", block=32)

    def delta(eng, run):
        before = eng.stats.tile_lanes
        run(eng)
        return eng.stats.tile_lanes - before

    mp = DiscordEngine(SearchSpec(s=24, method="matrix_profile",
                                  **base))
    assert delta(mp, lambda e: e.search(x)) == reg["profile"].lanes
    assert delta(mp, lambda e: e.open_stream(
        s=24, history=x[:70]).append(x[70:]).discords()) \
        == reg["profile"].lanes + reg["tail"].lanes
    pan = DiscordEngine(SearchSpec(s=(16, 24, 32),
                                   method="matrix_profile", **base))
    assert delta(pan, lambda e: e.search_pan(x)) == reg["pan"].lanes
    ring = DiscordEngine(SearchSpec(s=24, method="ring", ndev=1,
                                    **base))
    assert delta(ring, lambda e: e.search(x)) == reg["ring"].lanes
    # quantized plane: the registry entry carries the bound pass;
    # refinement lanes are data-dependent and booked on top
    q = DiscordEngine(SearchSpec(s=24, method="matrix_profile",
                                 precision="bf16", **base))
    before = q.stats.tile_lanes
    rq = q.search(x)
    assert q.stats.tile_lanes - before \
        == reg["qsweep"].lanes + rq.extra["refine_calls"]


def test_irlint_repo_clean():
    from repro.analysis.irlint import run_irlint
    findings, meta = run_irlint(backends=("numpy", "xla"))
    assert findings == []
    assert len(meta["lane_model"]) == 23
    for entry in meta["lane_model"].values():
        assert entry["model_lanes"] == entry["tile_lanes"]


def test_irlint_f64_literal_tp_and_near_miss():
    import jax

    def fn(v):
        return v * 2.0

    with jax.enable_x64(True):
        findings, _ = _fake_cell(fn, avals=(((4,), "float64"),))
    assert any(f.rule == "ir-f64" for f in findings)
    findings, _ = _fake_cell(fn, avals=(((4,), "float32"),))
    assert [f.rule for f in findings] == []


def test_irlint_dot_pet_tp_and_near_miss():
    import jax.numpy as jnp
    from jax import lax
    dn = (((1,), (0,)), ((), ()))
    avals = (((4, 5), "float32"), ((5, 6), "float32"))

    findings, _ = _fake_cell(lambda a, b: lax.dot_general(a, b, dn),
                             avals=avals)
    assert any(f.rule == "ir-dot-pet" for f in findings)
    findings, _ = _fake_cell(
        lambda a, b: lax.dot_general(
            a, b, dn, preferred_element_type=jnp.float32),
        avals=avals)
    assert [f.rule for f in findings] == []


def test_irlint_bf16_dot_pet_tp_and_near_miss():
    # the qsweep bound tiles cast to bf16 and must pin the MXU
    # accumulator back to f32 — a bare bf16 dot (bf16 accumulation /
    # bf16 output) is exactly the drift the rule exists to catch
    import jax.numpy as jnp
    from jax import lax
    dn = (((1,), (1,)), ((), ()))
    avals = (((4, 8), "float32"), ((6, 8), "float32"))

    findings, _ = _fake_cell(
        lambda a, b: lax.dot_general(a.astype(jnp.bfloat16),
                                     b.astype(jnp.bfloat16), dn),
        avals=avals)
    assert any(f.rule == "ir-dot-pet" for f in findings)
    findings, _ = _fake_cell(
        lambda a, b: lax.dot_general(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), dn,
            preferred_element_type=jnp.float32),
        avals=avals)
    assert not any(f.rule == "ir-dot-pet" for f in findings)


def test_irlint_clean_on_qsweep_kinds():
    from repro.analysis.irlint import run_irlint
    findings, meta = run_irlint(
        backends=("xla",),
        kinds=("qsweep", "qsweep_refine", "qsweep_tail",
               "qsweep_tail_refine"))
    assert findings == []
    for kind, entry in meta["lane_model"].items():
        assert entry["model_lanes"] == entry["tile_lanes"], kind


def test_irlint_callback_smuggled_into_device_plan():
    import jax
    import numpy as np

    def fn(v):
        return jax.pure_callback(
            lambda a: np.asarray(a),
            jax.ShapeDtypeStruct((4,), np.float32), v)

    # a host callback traced into a device-backend (ring/mb-capable)
    # plan is the violation; the numpy reference backend declares it
    findings, _ = _fake_cell(fn, backend="xla", avals=(((4,),
                                                        "float32"),))
    assert any(f.rule == "ir-callback" for f in findings)
    findings, _ = _fake_cell(fn, backend="numpy",
                             avals=(((4,), "float32"),))
    assert not any(f.rule == "ir-callback" for f in findings)


def test_irlint_oversized_const_tp_and_near_miss():
    import jax.numpy as jnp
    big = jnp.zeros((256, 256), jnp.float32)       # 256 KiB baked
    findings, _ = _fake_cell(lambda v: v[0] + big)
    assert any(f.rule == "ir-const" for f in findings)
    small = jnp.zeros((64, 64), jnp.float32)       # 16 KiB: fine
    findings, _ = _fake_cell(lambda v: v[0] + small)
    assert not any(f.rule == "ir-const" for f in findings)


def test_irlint_catches_miscounted_lane_model():
    import dataclasses

    from repro.analysis.irlint import _audit_cell, _Engines
    from repro.core.engine import plan_kind_registry
    entry = plan_kind_registry(ndev=1)["profile"]
    eng = _Engines(s=24, ladder=(16, 24, 32), block=32,
                   ndev=1).get("mp", "xla", True)
    findings, _ = _audit_cell(entry, eng, "xla", True,
                              const_bytes=1 << 20)
    assert findings == []      # the real entry audits clean
    wrong = dataclasses.replace(entry, lanes=entry.lanes + 1)
    findings, _ = _audit_cell(wrong, eng, "xla", True,
                              const_bytes=1 << 20)
    assert any(f.rule == "ir-lane-model" for f in findings)
    tampered = dataclasses.replace(entry, pattern=((123, 45),))
    findings, _ = _audit_cell(tampered, eng, "xla", True,
                              const_bytes=1 << 20)
    assert any(f.rule == "ir-flop-model" for f in findings)


# ---------------------------------------------------------------------
# 6. SHADOW: f64 replay clean on the repo, drift/divergence caught
# ---------------------------------------------------------------------
def test_shadow_clean_on_core_kinds():
    from repro.analysis.shadow import DEFAULT_TOL, run_shadow
    findings, meta = run_shadow(backends=("xla",),
                                kinds=("profile", "tail", "pan"))
    assert findings == []
    assert len(meta["checked"]) == 6       # 3 kinds x znorm True/False
    for kind, worst in meta["worst_by_kind"].items():
        assert worst["worst_rel"] < DEFAULT_TOL, kind
        assert worst["min_margin"] is None or worst["min_margin"] > 0


def test_shadow_comparator_detects_drift_and_divergence():
    import math
    from types import SimpleNamespace

    import numpy as np

    from repro.analysis.shadow import (_compare_discord,
                                       hostile_series, ref_profile,
                                       ref_topk)
    x, _ = hostile_series(90)
    prof = ref_profile(x, 24, True)
    pos, vals, _margin = ref_topk(prof, 2, 24)

    def run(res):
        findings, cell = [], {"worst_rel": 0.0, "worst_ulp": 0.0,
                              "min_margin": math.inf}
        _compare_discord("t", res, x, 24, True, 2, 0.05, findings,
                         cell)
        return findings, cell

    findings, cell = run(SimpleNamespace(positions=pos, nnds=vals))
    assert findings == [] and cell["worst_rel"] == 0.0
    # 20% nnd error at the right positions -> divergence
    findings, _ = run(SimpleNamespace(positions=pos,
                                      nnds=[v * 1.2 for v in vals]))
    assert any(f.rule == "nnd-divergence" for f in findings)
    # rank-0 pointing at the *least* discordant window -> drift
    worst_pos = int(np.argmin(np.where(np.isfinite(prof), prof,
                                       np.inf)))
    findings, _ = run(SimpleNamespace(positions=[worst_pos, pos[1]],
                                      nnds=vals))
    assert any(f.rule == "topk-drift" for f in findings)


def test_shadow_qsweep_replays_with_nonzero_benign_prune():
    from repro.analysis.shadow import run_shadow
    findings, meta = run_shadow(backends=("xla",), znorms=(True,),
                                kinds=("qsweep",),
                                precisions=("bf16", "int8"))
    assert findings == []
    for prec in ("bf16", "int8"):
        cell = meta["cells"][f"qsweep:{prec}[xla,znorm=True]"]
        # hostile series: the offset inflates the radius, pruning is
        # legitimately vacuous there — but the benign replay must prune
        assert cell["hostile_prune_ratio"] == 0.0
        assert cell["benign_prune_ratio"] > 0.0


def test_shadow_catches_vacuous_bound(monkeypatch):
    # inflate the error radius beyond use: bounds stay sound (wider),
    # every exactness gate still passes, but the benign-series replay
    # must flag the dead prune
    from repro.analysis.shadow import run_shadow
    from repro.core import engine as engine_mod

    orig = engine_mod.bound_dot_radius
    monkeypatch.setattr(
        engine_mod, "bound_dot_radius",
        lambda *a, **kw: orig(*a, **kw) + 1e30)
    findings, _ = run_shadow(backends=("xla",), znorms=(True,),
                             kinds=("qsweep",), precisions=("bf16",))
    assert any(f.rule == "qsweep-no-prune" for f in findings)
    assert not any(f.rule in ("topk-drift", "nnd-divergence")
                   for f in findings)


def test_shadow_catches_inflated_tile_numerics(monkeypatch):
    from repro.analysis.shadow import run_shadow
    from repro.core.tiles import TileEngine

    # a 21% d² inflation models a broken accumulator/σ clamp: the
    # f64 reference is independent, so every nnd lands ~10% high
    orig = TileEngine.d2
    monkeypatch.setattr(
        TileEngine, "d2",
        lambda self, *a, **kw: 1.21 * orig(self, *a, **kw))
    findings, _ = run_shadow(backends=("xla",), znorms=(True,),
                             kinds=("profile",))
    assert any(f.rule in ("nnd-divergence", "topk-drift")
               for f in findings)


# ---------------------------------------------------------------------
# 7. CLI: new passes + wall-clock budget
# ---------------------------------------------------------------------
def test_cli_budget_finding(tmp_path):
    env = dict(os.environ, PYTHONPATH=SRC)
    rp = tmp_path / "rep.json"
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "lint",
         "--budget-s", "1e-9", "--report", str(rp)],
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 1, out.stderr + out.stdout
    doc = json.loads(rp.read_text())
    assert any(f["rule"] == "wall-clock" for f in doc["findings"])
    assert doc["counts"]["budget"]["findings"] == 1
    # 0 disables the budget entirely
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "lint",
         "--budget-s", "0", "--report", "-"],
        env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr + out.stdout


def test_cli_irlint_pass(tmp_path):
    env = dict(os.environ, PYTHONPATH=SRC)
    rp = tmp_path / "rep.json"
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "irlint",
         "--backends", "xla", "--kinds", "profile,tail",
         "--report", str(rp)],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr + out.stdout
    doc = json.loads(rp.read_text())
    assert doc["ok"] is True
    assert doc["counts"]["irlint"] == {"cells": 4, "findings": 0,
                                       "kinds": 2}
    for entry in doc["meta"]["irlint"]["lane_model"].values():
        assert entry["model_lanes"] == entry["tile_lanes"]
