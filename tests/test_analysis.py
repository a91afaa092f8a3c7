"""Tests for the trace-discipline analyzer (repro.analysis).

Stage 1 (lint) is tested against golden fixtures in
``tests/data/analysis/``: every line carrying an ``# EXPECT: <rules>``
marker must be flagged with exactly those rule ids, and nothing else in
the fixture may be flagged.  Stage 2 (jaxpr audit) is tested by
sabotage: a planted ``jax.debug.callback``, a planted ``.item()`` in the
slot-pool decode body, and an engine whose decode jit keys on the start
position must each fail the gate.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.findings import Finding, Report, load_baseline
from repro.analysis.lint import run_lint
from repro.serving.queueing import require_positive_rate

FIXTURES = os.path.join(os.path.dirname(__file__), "data", "analysis")
EXPECT_RE = re.compile(r"#\s*EXPECT:\s*([A-Z0-9,\s]+?)\s*$")


def _expected(path):
    """{line: sorted [rule, ...]} parsed from # EXPECT: markers."""
    out = {}
    with open(path) as fh:
        for i, line in enumerate(fh, start=1):
            m = EXPECT_RE.search(line)
            if m:
                out[i] = sorted(r.strip() for r in m.group(1).split(",")
                                if r.strip())
    return out


def _lint(name, rules):
    path = os.path.join(FIXTURES, name)
    return path, run_lint(FIXTURES, repo_root=FIXTURES, paths=[path],
                          rule_ids=rules)


@pytest.mark.parametrize("name,rule", [
    ("bad_r001.py", "R001"),
    ("bad_r002.py", "R002"),
    ("bad_r003.py", "R003"),
    ("bad_r004.py", "R004"),
    ("bad_r005.py", "R005"),
])
def test_lint_fixture_golden(name, rule):
    path, findings = _lint(name, rules=[rule])
    got = {}
    for f in findings:
        assert f.rule == rule
        got.setdefault(f.line, []).append(f.rule)
    got = {k: sorted(v) for k, v in got.items()}
    assert got == _expected(path)


def test_pragmas_suppress_and_r000():
    path, findings = _lint("pragmas.py", rules=None)
    by_rule_line = {(f.rule, f.line) for f in findings}
    # Documented pragmas (lines 10 and 12->13) suppress their findings.
    assert not any(f.line in (10, 12, 13) for f in findings)
    # The undocumented pragma suppresses nothing: both the original
    # violation and the R000 meta-finding land on line 15.
    assert ("R001", 15) in by_rule_line
    assert ("R000", 15) in by_rule_line


def test_lint_findings_have_hints_and_keys():
    _path, findings = _lint("bad_r001.py", rules=["R001"])
    assert findings
    for f in findings:
        assert f.hint, f
        assert f.key.startswith("R001:")


def test_baseline_grandfathers_by_key(tmp_path):
    f = Finding(rule="R001", path="x.py", line=12, message="np call")
    report = Report(findings=[f])
    base = tmp_path / "baseline.json"
    base.write_text('{"findings": [{"rule": "R001", "path": "x.py", '
                    '"message": "np call"}]}')
    assert report.new_findings(load_baseline(str(base))) == []
    # Line numbers must not affect matching; messages must.
    other = Finding(rule="R001", path="x.py", line=99, message="different")
    assert Report(findings=[other]).new_findings(
        load_baseline(str(base))) == [other]


def test_cli_gate_exit_codes(tmp_path):
    from repro.analysis.__main__ import main
    bad = tmp_path / "pkg"
    bad.mkdir()
    (bad / "mod.py").write_text(
        "import jax\nimport numpy as np\n\n"
        "@jax.jit\ndef f(x):\n    return np.abs(x)\n")
    baseline = str(tmp_path / "missing_baseline.json")
    assert main(["--lint", "--root", str(bad), "--baseline", baseline]) == 1
    (bad / "mod.py").write_text(
        "import jax\nimport jax.numpy as jnp\n\n"
        "@jax.jit\ndef f(x):\n    return jnp.abs(x)\n")
    assert main(["--lint", "--root", str(bad), "--baseline", baseline]) == 0


# ---------------------------------------------------------------------------
# Stage 2: jaxpr audit sabotage
# ---------------------------------------------------------------------------


class _WrapBundle:
    """Pass-through bundle wrapper for planting trace poison."""

    def __init__(self, inner):
        self._inner = inner
        self.cfg = inner.cfg

    def init_cache(self, batch, max_len):
        return self._inner.init_cache(batch, max_len)

    def init_params(self, key):
        return self._inner.init_params(key)

    def prefill(self, params, toks, cache, attn_mask=None):
        return self._inner.prefill(params, toks, cache,
                                   attn_mask=attn_mask)

    def decode_step(self, params, tok, cache, pos, attn_mask=None):
        return self._inner.decode_step(params, tok, cache, pos,
                                       attn_mask=attn_mask)


def test_audit_flags_planted_debug_callback(tmp_path):
    from repro.analysis.jaxpr_audit import _smoke_bundle, run_audit

    bundle, params = _smoke_bundle("smollm-360m")

    class CallbackBundle(_WrapBundle):
        def prefill(self, params, toks, cache, attn_mask=None):
            jax.debug.callback(lambda: None)
            return self._inner.prefill(params, toks, cache,
                                       attn_mask=attn_mask)

    findings, _rows = run_audit(
        budgets_path=str(tmp_path / "budgets.json"),
        families=["smollm-360m"],
        bundles={"smollm-360m": (CallbackBundle(bundle), params)},
        include_retrace=False, include_engine=False)
    assert any(f.rule == "A101" and f.entry == "smollm-360m/prefill"
               and "debug_callback" in f.message for f in findings)
    # decode_step was left clean: no callback finding there.
    assert not any(f.rule == "A101" and f.entry == "smollm-360m/decode_step"
                   for f in findings)


def test_audit_item_in_fused_decode_fails_gate(tmp_path):
    """Planting a host sync (.item()) in the decode body must fail the
    gate: the entry point no longer traces (A106)."""
    from repro.analysis.jaxpr_audit import default_engine_factory, run_audit

    def sabotaged():
        eng = default_engine_factory()

        class ItemBundle(_WrapBundle):
            def decode_step(self, params, tok, cache, pos, attn_mask=None):
                logits, cache = self._inner.decode_step(
                    params, tok, cache, pos, attn_mask=attn_mask)
                logits.sum().item()       # the planted host sync
                return logits, cache

        eng.bundle = ItemBundle(eng.bundle)
        return eng

    findings, _rows = run_audit(
        budgets_path=str(tmp_path / "budgets.json"),
        families=[], engine_factory=sabotaged,
        include_retrace=False)
    assert any(f.rule == "A106" and f.entry == "engine/continuous_decode"
               for f in findings)


def test_retrace_audit_flags_value_keyed_decode_cache():
    """An engine whose decode-loop jit keys on the start position value
    (static_argnums instead of a traced scalar) must fail A105 when the
    prompt bucket changes."""
    from repro.analysis.jaxpr_audit import (default_engine_factory,
                                            retrace_audit)

    def sabotaged():
        eng = default_engine_factory()
        fn = eng._fused_continuous_fn
        wrapped = jax.jit(
            lambda p, tok, cache, mask, start_pos, *rest: fn(
                p, tok, cache, mask, jnp.asarray(start_pos, jnp.int32),
                *rest),
            static_argnums=(4, 10))

        class Shim:
            def __call__(self, p, tok, cache, mask, start_pos, *rest):
                return wrapped(p, tok, cache, mask, int(start_pos), *rest)

            def _cache_size(self):
                return wrapped._cache_size()

        eng._fused_continuous = Shim()
        return eng

    findings = retrace_audit(engine_factory=sabotaged)
    assert any(f.rule == "A105" and "decode_continuous" in f.message
               for f in findings)


def test_retrace_audit_clean_on_default_engine():
    from repro.analysis.jaxpr_audit import retrace_audit
    assert retrace_audit() == []


# ---------------------------------------------------------------------------
# Satellite: typed arrival-rate validation
# ---------------------------------------------------------------------------


def test_require_positive_rate():
    assert require_positive_rate(2.5) == 2.5
    assert require_positive_rate(np.float32(1.0)) == 1.0
    for bad in (0.0, -1.0, float("nan"), float("inf")):
        with pytest.raises(ValueError, match="arrival_rate"):
            require_positive_rate(bad)
    with pytest.raises(ValueError, match="interval_s"):
        require_positive_rate(-3, knob="interval_s")
    with pytest.raises(TypeError, match="arrival_rate"):
        require_positive_rate("fast")


def test_environments_reject_bad_rates():
    from repro.serving import energy, simulator
    board, work = energy.JETSON_AGX_ORIN, energy.LLAMA32_1B_ORIN
    with pytest.raises(ValueError, match="arrival_rate"):
        simulator.LandscapeEnv(board, work, arrival_rate=0.0)
    with pytest.raises(ValueError, match="interval_s"):
        simulator.EventEnvironment(board, work, interval_s=-1.0)
