"""jaxlint static-analysis suite + transfer-guard runtime sanitizer.

Three layers:

1. per-rule fixture tests — one known-bad snippet per rule asserting
   the rule fires at the right line with the right id, plus a clean
   twin asserting no false positive on the sanctioned idiom;
2. the package-wide clean run (tier-1): ``lightgbm_tpu`` must lint
   clean, so every future PR inherits the gate;
3. the runtime complement: a warmed ``GBDT.train_one_iter`` under
   ``jax.transfer_guard("disallow")`` — the dynamic check that keeps
   JLT001's static approximation honest (zero implicit host transfers
   in a full training iteration, exact AND quantized mode).
"""
import json
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from tools.jaxlint import check_source  # noqa: E402
from tools.jaxlint.engine import run as jaxlint_run  # noqa: E402


def lint(src, relpath="treelearner/somefile.py", select=None):
    findings, suppressed = check_source(
        textwrap.dedent(src), relpath, select=select)
    return findings, suppressed


def rules_at(findings):
    return [(f.rule, f.line) for f in findings]


# ---------------------------------------------------------------------------
# JLT001 — host sync
# ---------------------------------------------------------------------------

class TestJLT001:
    def test_item_fires(self):
        findings, _ = lint("""\
            import jax.numpy as jnp

            def f(x):
                s = jnp.sum(x)
                return s.item()
            """)
        assert ("JLT001", 5) in rules_at(findings)

    def test_float_of_tainted_name_fires(self):
        findings, _ = lint("""\
            import jax.numpy as jnp

            def f(x):
                s = jnp.sum(x)
                return float(s)
            """)
        assert ("JLT001", 5) in rules_at(findings)

    def test_device_get_and_block_until_ready_fire(self):
        findings, _ = lint("""\
            import jax

            def f(x):
                jax.device_get(x)
                x.block_until_ready()
            """)
        assert ("JLT001", 4) in rules_at(findings)
        assert ("JLT001", 5) in rules_at(findings)

    def test_np_asarray_of_jax_call_fires(self):
        findings, _ = lint("""\
            import jax.numpy as jnp
            import numpy as np

            def f(x):
                return np.asarray(jnp.cumsum(x))
            """)
        assert ("JLT001", 5) in rules_at(findings)

    def test_taint_inside_with_block_fires(self):
        # the shape nearly all hot-path code takes: taint assigned and
        # synced within one `with obs.scope(...)` block
        findings, _ = lint("""\
            import jax.numpy as jnp

            def f(x, obs):
                with obs.scope("tree::grow"):
                    s = jnp.sum(x)
                    return float(s)
            """)
        assert ("JLT001", 6) in rules_at(findings)

    def test_taint_inside_loop_body_fires(self):
        findings, _ = lint("""\
            import jax.numpy as jnp

            def f(xs):
                out = []
                for x in xs:
                    s = jnp.sum(x)
                    out.append(float(s))
                return out
            """)
        assert ("JLT001", 7) in rules_at(findings)

    def test_host_values_clean(self):
        findings, _ = lint("""\
            import jax
            import numpy as np

            def f(meta):
                label = np.asarray(meta.label, dtype=np.float64)
                devs = np.array(jax.devices())
                n = int(jax.process_count())
                return float(label.mean()), devs, n
            """)
        assert findings == []

    def test_exempt_modules_clean(self):
        bad = """\
            import jax

            def f(x):
                return jax.device_get(x)
            """
        for rel in ("obs/registry.py", "serve/server.py",
                    "tests/test_x.py"):
            findings, _ = lint(bad, rel)
            assert findings == [], rel


# ---------------------------------------------------------------------------
# JLT002 — PRNG key reuse
# ---------------------------------------------------------------------------

class TestJLT002:
    def test_double_draw_fires(self):
        findings, _ = lint("""\
            import jax

            def f(key):
                a = jax.random.uniform(key, (3,))
                b = jax.random.normal(key, (3,))
                return a + b
            """)
        assert ("JLT002", 5) in rules_at(findings)

    def test_split_between_draws_clean(self):
        findings, _ = lint("""\
            import jax

            def f(key):
                k1, k2 = jax.random.split(key)
                a = jax.random.uniform(k1, (3,))
                b = jax.random.normal(k2, (3,))
                return a + b
            """)
        assert findings == []

    def test_fold_in_derivation_clean(self):
        findings, _ = lint("""\
            import jax

            def f(key, n):
                out = []
                for i in range(n):
                    k = jax.random.fold_in(key, i)
                    out.append(jax.random.uniform(k, (3,)))
                return out
            """)
        assert findings == []

    def test_reuse_inside_loop_fires(self):
        findings, _ = lint("""\
            import jax

            def f(key, n):
                out = []
                for i in range(n):
                    out.append(jax.random.uniform(key, (3,)))
                return out
            """)
        assert any(f.rule == "JLT002" for f in findings)

    def test_helper_call_consumes(self):
        findings, _ = lint("""\
            import jax

            def f(self, key):
                a = self._draw(key)
                b = jax.random.uniform(key, (3,))
                return a + b
            """)
        assert ("JLT002", 5) in rules_at(findings)

    def test_exclusive_branches_clean(self):
        findings, _ = lint("""\
            import jax

            def f(key, flag):
                if flag:
                    return jax.random.uniform(key, (3,))
                else:
                    return jax.random.normal(key, (3,))
            """)
        assert findings == []


# ---------------------------------------------------------------------------
# JLT003 — raw jax.jit
# ---------------------------------------------------------------------------

class TestJLT003:
    def test_raw_jit_fires(self):
        findings, _ = lint("""\
            import jax

            def make(fn):
                return jax.jit(fn, donate_argnums=(0,))
            """)
        assert ("JLT003", 4) in rules_at(findings)

    def test_decorator_and_from_import_fire(self):
        findings, _ = lint("""\
            from functools import partial
            import jax
            from jax import jit

            @partial(jax.jit, static_argnums=0)
            def f(self, x):
                return x

            @jit
            def g(x):
                return x
            """)
        lines = [l for r, l in rules_at(findings) if r == "JLT003"]
        assert 5 in lines and 9 in lines

    def test_owner_module_clean(self):
        findings, _ = lint("""\
            import jax

            def instrument_jit(name, fun, **kw):
                return jax.jit(fun, **kw)
            """, "obs/compile.py")
        assert findings == []

    def test_instrument_jit_clean(self):
        findings, _ = lint("""\
            from ..obs import compile as obs_compile

            def make(fn):
                return obs_compile.instrument_jit("x", fn)
            """)
        assert findings == []


# ---------------------------------------------------------------------------
# JLT004 — churn-prone static args
# ---------------------------------------------------------------------------

class TestJLT004:
    def test_list_at_static_position_fires(self):
        findings, _ = lint("""\
            import jax

            f = jax.jit(lambda a, b: a, static_argnums=(1,))
            out = f(x, [1, 2, 3])
            """)
        assert ("JLT004", 4) in rules_at(findings)

    def test_dict_for_static_name_fires(self):
        findings, _ = lint("""\
            from ..obs import compile as obs_compile

            f = obs_compile.instrument_jit(
                "x", fn, static_argnames=("cfg",))
            out = f(x, cfg={"a": 1})
            """)
        assert any(f.rule == "JLT004" for f in findings)

    def test_tuple_static_clean(self):
        findings, _ = lint("""\
            import jax

            f = jax.jit(lambda a, b: a, static_argnums=(1,))
            out = f(x, (8, False))
            """, select=["JLT004"])  # raw jax.jit is JLT003's business
        assert findings == []


# ---------------------------------------------------------------------------
# JLT005 — collectives
# ---------------------------------------------------------------------------

class TestJLT005:
    def test_axisless_and_unnamed_fire(self):
        findings, _ = lint("""\
            import jax

            def f(h):
                return jax.lax.psum(h)
            """)
        got = [f for f in findings if f.rule == "JLT005"]
        assert len(got) == 2  # missing axis_name AND missing scope
        assert all(f.line == 4 for f in got)

    def test_named_scope_with_axis_clean(self):
        findings, _ = lint("""\
            import jax

            def f(h, axis):
                with jax.named_scope("obs_psum_votes"):
                    return jax.lax.psum(h, axis)
            """)
        assert findings == []

    def test_wrong_scope_name_fires(self):
        findings, _ = lint("""\
            import jax

            def f(h, axis):
                with jax.named_scope("my_reduction"):
                    return jax.lax.psum(h, axis)
            """)
        assert [f.rule for f in findings] == ["JLT005"]


# ---------------------------------------------------------------------------
# JLT006 — dtype widening (scoped to the quantized modules)
# ---------------------------------------------------------------------------

class TestJLT006:
    def test_float_literal_where_arm_fires(self):
        findings, _ = lint("""\
            import jax.numpy as jnp

            def f(mask, x):
                return jnp.where(mask, x, 0.0)
            """, "ops/histogram.py")
        assert ("JLT006", 4) in rules_at(findings)

    def test_dtype_preserving_where_clean(self):
        findings, _ = lint("""\
            import jax.numpy as jnp

            def f(mask, x):
                zero = jnp.zeros((), dtype=x.dtype)
                return jnp.where(mask, x, zero)
            """, "ops/quantize.py")
        assert findings == []

    def test_float_arith_on_int_tainted_fires(self):
        findings, _ = lint("""\
            import jax.numpy as jnp

            def f(gh):
                acc = gh.astype(jnp.int32)
                return acc * 0.5
            """, "ops/histogram.py")
        assert ("JLT006", 5) in rules_at(findings)

    def test_int_taint_inside_if_body_fires(self):
        findings, _ = lint("""\
            import jax.numpy as jnp

            def f(gh, quantized):
                if quantized:
                    acc = gh.astype(jnp.int32)
                    return acc * 0.5
                return gh
            """, "ops/histogram.py")
        assert ("JLT006", 6) in rules_at(findings)

    def test_out_of_scope_module_clean(self):
        findings, _ = lint("""\
            import jax.numpy as jnp

            def f(mask, x):
                return jnp.where(mask, x, 0.0)
            """, "treelearner/serial.py")
        assert findings == []


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

class TestSuppressions:
    BAD = """\
        import jax

        def f(x):
            return jax.device_get(x)  # jaxlint: disable=JLT001 -- sync pt
        """

    def test_same_line_suppression_honored(self):
        findings, suppressed = lint(self.BAD)
        assert findings == []
        assert suppressed == 1

    def test_preceding_comment_suppression_honored(self):
        findings, suppressed = lint("""\
            import jax

            def f(x):
                # jaxlint: disable=JLT001 -- deliberate per-batch sync
                # (two-line rationale keeps working)
                return jax.device_get(x)
            """)
        assert findings == []
        assert suppressed == 1

    def test_bare_suppression_reports_jlt000(self):
        findings, suppressed = lint("""\
            import jax

            def f(x):
                return jax.device_get(x)  # jaxlint: disable=JLT001
            """)
        assert suppressed == 1  # still suppresses JLT001 ...
        assert [f.rule for f in findings] == ["JLT000"]  # ... loudly

    def test_directive_inside_docstring_inert(self):
        # suppression syntax QUOTED in documentation must neither
        # suppress anything nor produce a phantom JLT000
        findings, suppressed = lint('''\
            """Docs.

            Example::

                x = jax.device_get(r)  # jaxlint: disable=JLT001

            # jaxlint: disable=JLT002
            """
            import jax

            def f(x):
                return jax.device_get(x)
            ''')
        assert suppressed == 0
        assert [f.rule for f in findings] == ["JLT001"]

    def test_duplicate_findings_deduped(self):
        # loop bodies are walked twice (JLT002); a reuse inside a loop
        # must still be reported exactly once per offending call
        findings, _ = lint("""\
            import jax

            def f(key, n):
                for i in range(n):
                    a = jax.random.uniform(key, (3,))
                    b = jax.random.normal(key, (3,))
                return a + b
            """)
        keyed = [(f.rule, f.line, f.col) for f in findings]
        assert len(keyed) == len(set(keyed))

    def test_wrong_rule_id_does_not_suppress(self):
        findings, suppressed = lint("""\
            import jax

            def f(x):
                return jax.device_get(x)  # jaxlint: disable=JLT003 -- no
            """)
        assert any(f.rule == "JLT001" for f in findings)


# ---------------------------------------------------------------------------
# JLT007 — unused suppressions
# ---------------------------------------------------------------------------

class TestJLT007:
    def test_unused_trailing_suppression_fires(self):
        findings, suppressed = lint("""\
            import jax

            def f(x):
                return x + 1  # jaxlint: disable=JLT001 -- stale note
            """)
        assert suppressed == 0
        assert ("JLT007", 4) in rules_at(findings)

    def test_unused_standalone_suppression_fires_at_directive(self):
        findings, _ = lint("""\
            import jax

            def f(x):
                # jaxlint: disable=JLT001 -- this sync was removed
                return x + 1
            """)
        assert ("JLT007", 4) in rules_at(findings)

    def test_used_suppression_clean(self):
        findings, suppressed = lint("""\
            import jax

            def f(x):
                return jax.device_get(x)  # jaxlint: disable=JLT001 -- ok
            """)
        assert suppressed == 1
        assert findings == []

    def test_partially_used_multi_rule_directive(self):
        # one directive naming two rules, only one of which fires:
        # the dead half is a finding, the live half suppresses
        findings, suppressed = lint("""\
            import jax

            def f(x):
                return jax.device_get(x)  # jaxlint: disable=JLT001,JLT002 -- ok
            """)
        assert suppressed == 1
        assert [f.rule for f in findings] == ["JLT007"]
        assert "JLT002" in findings[0].message

    def test_jlt000_suppression_is_dead_by_construction(self):
        findings, _ = lint("""\
            import jax

            def f(x):
                return jax.device_get(x)  # jaxlint: disable=JLT000,JLT001 -- why
            """)
        assert any(f.rule == "JLT007" and "JLT000" in f.message
                   for f in findings)

    def test_unknown_rule_id_flagged_on_full_run(self):
        findings, _ = lint("""\
            def f(x):
                return x  # jaxlint: disable=JLT999 -- typo
            """)
        assert any(f.rule == "JLT007" and "JLT999" in f.message
                   for f in findings)

    def test_select_excluded_rule_not_judged(self):
        # under --select JLT001, a JLT003 suppression might well be
        # load-bearing on a full run — it must not be called unused
        findings, _ = lint("""\
            import jax

            def f(x):
                return x + 1  # jaxlint: disable=JLT003 -- real on full run
            """, select=["JLT001", "JLT007"])
        assert findings == []

    def test_directive_with_no_following_code_is_unused(self):
        findings, _ = lint("""\
            import jax

            def f(x):
                return jax.device_get(x)  # jaxlint: disable=JLT001 -- ok
            # jaxlint: disable=JLT001 -- dangles at EOF
            """)
        assert ("JLT007", 5) in rules_at(findings)

    def test_list_rules_includes_jlt007(self, tmp_path):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.jaxlint", "--list-rules"],
            capture_output=True, text=True, cwd=str(REPO), timeout=60)
        assert proc.returncode == 0
        assert "JLT007" in proc.stdout


# ---------------------------------------------------------------------------
# CLI: JSON output + exit codes (the standalone CI gate)
# ---------------------------------------------------------------------------

def lint_tree(tmp_path, files, select=None):
    """Write {relpath: source} under tmp_path and lint the tree as one
    project (cross-module rules see the full index)."""
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src))
    report = jaxlint_run([str(tmp_path)], select=select)
    return report.pop("_findings")


# ---------------------------------------------------------------------------
# JLT008 — cross-function key flow
# ---------------------------------------------------------------------------

class TestJLT008:
    def test_fresh_key_from_helper_consumed_twice(self):
        findings, _ = lint("""
            import jax

            def make_key(seed):
                return jax.random.PRNGKey(seed)

            def sample(seed):
                k = make_key(seed)
                a = jax.random.uniform(k)
                b = jax.random.normal(k)
                return a + b
        """, select=["JLT008"])
        assert rules_at(findings) == [("JLT008", 10)]
        assert "crossed a function boundary" in findings[0].message

    def test_split_between_draws_is_clean(self):
        findings, _ = lint("""
            import jax

            def make_key(seed):
                return jax.random.PRNGKey(seed)

            def sample(seed):
                k = make_key(seed)
                k1, k2 = jax.random.split(k)
                a = jax.random.uniform(k1)
                b = jax.random.normal(k2)
                return a + b
        """, select=["JLT008"])
        assert findings == []

    def test_passthrough_target_born_consumed(self):
        # draw() consumed its key parameter AND returned it: the
        # unpacked alias holds an already-used stream
        findings, _ = lint("""
            import jax

            def draw(key):
                val = jax.random.uniform(key)
                return val, key

            def use(key):
                val, fresh = draw(key)
                extra = jax.random.normal(fresh)
                return val + extra
        """, select=["JLT008"])
        assert rules_at(findings) == [("JLT008", 10)]
        assert "passed through" in findings[0].message

    def test_passthrough_without_consume_is_clean(self):
        findings, _ = lint("""
            import jax

            def wrap(key):
                return 1.0, key

            def use(key):
                val, fresh = wrap(key)
                extra = jax.random.normal(fresh)
                return val + extra
        """, select=["JLT008"])
        assert findings == []

    def test_transitive_helper_chain(self):
        findings, _ = lint("""
            import jax

            def outer_key(s):
                return inner_key(s)

            def inner_key(s):
                return jax.random.PRNGKey(s)

            def use(s):
                k = outer_key(s)
                x = jax.random.uniform(k)
                y = jax.random.normal(k)
                return x + y
        """, select=["JLT008"])
        assert rules_at(findings) == [("JLT008", 13)]

    def test_key_named_target_stays_jlt002s(self):
        # a key-named name either rule could see reports exactly ONCE,
        # under JLT002 (the rule that saw it first)
        findings, _ = lint("""
            import jax

            def make_key(seed):
                return jax.random.PRNGKey(seed)

            def sample(seed):
                key = make_key(seed)
                a = jax.random.uniform(key)
                b = jax.random.normal(key)
                return a + b
        """)
        assert [f.rule for f in findings] == ["JLT002"]

    def test_cross_module_helper(self, tmp_path):
        findings = lint_tree(tmp_path, {
            "ops/keys.py": """
                import jax

                def make_key(seed):
                    return jax.random.PRNGKey(seed)
            """,
            "learner/use.py": """
                import jax
                from ops.keys import make_key

                def sample(seed):
                    k = make_key(seed)
                    a = jax.random.uniform(k)
                    b = jax.random.normal(k)
                    return a + b
            """,
        }, select=["JLT008"])
        assert [(f.rule, f.line) for f in findings] == [("JLT008", 8)]


# ---------------------------------------------------------------------------
# JLT009 — cross-module static-arg call sites
# ---------------------------------------------------------------------------

_JLT009_OPS = """
    from obs.compile import instrument_jit

    def _body(a, b, spec):
        return a

    _hist = instrument_jit("h", _body, static_argnums=(2,))
"""


class TestJLT009:
    def test_mutable_literal_across_modules(self, tmp_path):
        findings = lint_tree(tmp_path, {
            "ops/histo.py": _JLT009_OPS,
            "learner/use.py": """
                from ops.histo import _hist

                def go(x, y):
                    return _hist(x, y, [16, 16])
            """,
        }, select=["JLT009"])
        assert [(f.rule, f.line) for f in findings] == [("JLT009", 5)]
        assert "static position 2" in findings[0].message

    def test_fresh_ctor_and_nested_tuple(self, tmp_path):
        findings = lint_tree(tmp_path, {
            "ops/histo.py": _JLT009_OPS,
            "learner/use.py": """
                from ops.histo import _hist

                def go(x, y):
                    a = _hist(x, y, dict(n=2))
                    b = _hist(x, y, (1, [2]))
                    return a + b
            """,
        }, select=["JLT009"])
        assert [(f.rule, f.line) for f in findings] == \
            [("JLT009", 5), ("JLT009", 6)]

    def test_frozen_tuple_is_clean(self, tmp_path):
        findings = lint_tree(tmp_path, {
            "ops/histo.py": _JLT009_OPS,
            "learner/use.py": """
                from ops.histo import _hist

                def go(x, y):
                    return _hist(x, y, (16, 16))
            """,
        }, select=["JLT009"])
        assert findings == []

    def test_same_module_site_is_jlt004s(self, tmp_path):
        # one finding per site, one owner per gap: the same-file call
        # must come from JLT004, never doubled by JLT009
        findings = lint_tree(tmp_path, {
            "ops/histo.py": """
                from obs.compile import instrument_jit

                def _body(a, b, spec):
                    return a

                _hist = instrument_jit("h", _body,
                                       static_argnums=(2,))

                def go(x, y):
                    return _hist(x, y, [16, 16])
            """,
        })
        assert [f.rule for f in findings] == ["JLT004"]


# ---------------------------------------------------------------------------
# JLT010 — Pallas kernel invariants
# ---------------------------------------------------------------------------

class TestJLT010:
    def test_index_map_arity_vs_grid(self):
        findings, _ = lint("""
            import jax
            import jax.numpy as jnp
            from jax.experimental import pallas as pl

            PALLAS_VMEM_BUDGET = 1 << 20

            def run(x):
                return pl.pallas_call(
                    lambda x_ref, o_ref: None,
                    grid=(4, 2),
                    in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                    out_specs=pl.BlockSpec((8, 128),
                                           lambda i, j: (i, 0)),
                    out_shape=jax.ShapeDtypeStruct((32, 128),
                                                   jnp.float32),
                )(x)
        """, relpath="ops/k.py", select=["JLT010"])
        assert rules_at(findings) == [("JLT010", 12)]
        assert "grid has 2 dimension" in findings[0].message

    def test_dot_without_preferred_element_type(self):
        findings, _ = lint("""
            import jax.numpy as jnp
            from jax.experimental import pallas as pl

            PALLAS_VMEM_BUDGET = 1 << 20

            def _acc_kernel_body(x_ref, w_ref, o_ref):
                o_ref[...] = jnp.dot(x_ref[...], w_ref[...])
        """, relpath="ops/k.py", select=["JLT010"])
        assert rules_at(findings) == [("JLT010", 8)]
        assert "preferred_element_type" in findings[0].message

    def test_missing_vmem_budget(self):
        findings, _ = lint("""
            import jax
            import jax.numpy as jnp
            from jax.experimental import pallas as pl

            def run(x):
                return pl.pallas_call(
                    lambda x_ref, o_ref: None,
                    grid=(1,),
                    out_shape=jax.ShapeDtypeStruct((8,), jnp.float32),
                )(x)
        """, relpath="ops/k.py", select=["JLT010"])
        assert rules_at(findings) == [("JLT010", 7)]
        assert "VMEM budget" in findings[0].message

    def test_misaligned_row_tile(self):
        findings, _ = lint("""
            from jax.experimental import pallas as pl

            PALLAS_ROW_TILE = 100
        """, relpath="ops/k.py", select=["JLT010"])
        assert rules_at(findings) == [("JLT010", 4)]

    def test_invocation_arity_vs_in_specs(self):
        findings, _ = lint("""
            import jax
            import jax.numpy as jnp
            from jax.experimental import pallas as pl

            PALLAS_VMEM_BUDGET = 1 << 20

            def run(x, w):
                return pl.pallas_call(
                    lambda x_ref, w_ref, o_ref: None,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0)),
                              pl.BlockSpec((128, 16),
                                           lambda i: (0, 0))],
                    out_specs=pl.BlockSpec((8, 16), lambda i: (i, 0)),
                    out_shape=jax.ShapeDtypeStruct((32, 16),
                                                   jnp.float32),
                )(x)
        """, relpath="ops/k.py", select=["JLT010"])
        assert rules_at(findings) == [("JLT010", 9)]
        assert "invoked with 1 array" in findings[0].message

    def test_a_spec_bound_to_a_name_counts_as_one(self):
        """An in_specs element that is a name (a spec chosen by shape
        above the call) is one operand's spec: the arity checks count
        the list's elements, and skip only what they cannot read."""
        source = """
            import jax
            import jax.numpy as jnp
            from jax.experimental import pallas as pl

            PALLAS_VMEM_BUDGET = 1 << 20

            def _acc_kernel_body(x_ref, w_ref, o_ref):
                o_ref[...] = x_ref[...]

            def run(x, w, whole):
                w_spec = (pl.BlockSpec(memory_space=pl.ANY) if whole
                          else pl.BlockSpec((128, 16), lambda i: (0, 0)))
                return pl.pallas_call(
                    _acc_kernel_body,
                    grid=(4,),
                    in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0)),
                              w_spec],
                    out_specs=pl.BlockSpec((8, 16), lambda i: (i, 0)),
                    out_shape=jax.ShapeDtypeStruct((32, 16),
                                                   jnp.float32),
                )(%s)
        """
        findings, _ = lint(source % "x, w", relpath="ops/k.py",
                           select=["JLT010"])
        assert findings == []
        findings, _ = lint(source % "x", relpath="ops/k.py",
                           select=["JLT010"])
        assert [f.rule for f in findings] == ["JLT010"]
        assert "declares 2 in_specs" in findings[0].message

    def test_consistent_kernel_is_clean(self):
        findings, _ = lint("""
            import functools

            import jax
            import jax.numpy as jnp
            from jax.experimental import pallas as pl

            PALLAS_ROW_TILE = 2048
            PALLAS_VMEM_BUDGET = 64 * 1024 * 1024

            def _pallas_fits(nbytes):
                return nbytes < PALLAS_VMEM_BUDGET

            def _acc_kernel_body(scale, x_ref, w_ref, o_ref):
                o_ref[...] = jax.lax.dot_general(
                    x_ref[...], w_ref[...],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale

            def run(x, w):
                return pl.pallas_call(
                    functools.partial(_acc_kernel_body, 3),
                    grid=(4,),
                    in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0)),
                              pl.BlockSpec((128, 16),
                                           lambda i: (0, 0))],
                    out_specs=pl.BlockSpec((8, 16), lambda i: (i, 0)),
                    out_shape=jax.ShapeDtypeStruct((32, 16),
                                                   jnp.float32),
                )(x, w)
        """, relpath="ops/k.py", select=["JLT010"])
        assert findings == []

    def test_package_histogram_kernel_is_clean(self):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.jaxlint",
             str(REPO / "lightgbm_tpu" / "ops" / "histogram.py"),
             "--select", "JLT010"],
            cwd=str(REPO), capture_output=True, text=True)
        assert proc.returncode == 0, proc.stdout


# ---------------------------------------------------------------------------
# JLT101/102/103 — concurrency discipline (threaded modules only)
# ---------------------------------------------------------------------------

_JLT101_BAD = """
    import threading

    class Server:
        def __init__(self):
            self._lock = threading.Lock()
            self.stats = {"n": 0}
            self._thread = threading.Thread(target=self._run)

        def _run(self):
            self.stats["n"] += 1

        def read(self):
            with self._lock:
                return self.stats["n"]
"""


class TestJLT101:
    def test_unguarded_worker_write(self):
        findings, _ = lint(_JLT101_BAD, relpath="serve/x.py",
                           select=["JLT101"])
        assert [f.rule for f in findings] == ["JLT101"]
        assert findings[0].line == 11

    def test_guarded_write_is_clean(self):
        findings, _ = lint("""
            import threading

            class Server:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.stats = {"n": 0}
                    self._thread = threading.Thread(target=self._run)

                def _run(self):
                    with self._lock:
                        self.stats["n"] += 1

                def read(self):
                    with self._lock:
                        return self.stats["n"]
        """, relpath="serve/x.py", select=["JLT101"])
        assert findings == []

    def test_scoped_to_threaded_modules(self):
        # same source under treelearner/ is out of scope by design
        findings, _ = lint(_JLT101_BAD, relpath="treelearner/x.py",
                           select=["JLT101"])
        assert findings == []

    def test_locked_suffix_contract(self):
        # a *_locked method writes without the lock (the caller holds
        # it) — but CALLING it without the lock held is the violation
        findings, _ = lint("""
            import threading

            class Server:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.stats = {"n": 0}
                    self._thread = threading.Thread(target=self._run)

                def _bump_locked(self):
                    self.stats["n"] += 1

                def _run(self):
                    with self._lock:
                        self._bump_locked()

                def poke(self):
                    self._bump_locked()

                def read(self):
                    with self._lock:
                        return self.stats["n"]
        """, relpath="serve/x.py", select=["JLT101"])
        assert [(f.rule, f.line) for f in findings] == [("JLT101", 18)]
        assert "_locked" in findings[0].message


class TestJLT102:
    def test_sleep_under_lock(self):
        findings, _ = lint("""
            import threading
            import time

            class S:
                def __init__(self):
                    self._lock = threading.Lock()

                def tick(self):
                    with self._lock:
                        time.sleep(0.1)
        """, relpath="serve/x.py", select=["JLT102"])
        assert rules_at(findings) == [("JLT102", 11)]

    def test_sleep_outside_lock_is_clean(self):
        findings, _ = lint("""
            import threading
            import time

            class S:
                def __init__(self):
                    self._lock = threading.Lock()

                def tick(self):
                    with self._lock:
                        n = 1
                    time.sleep(0.1)
        """, relpath="serve/x.py", select=["JLT102"])
        assert findings == []

    def test_emit_with_flush_via_helper(self):
        # the PR 10 shed-accounting bug as a rule: a flushed emit one
        # call away from the lock still blocks the hot path
        findings, _ = lint("""
            import threading

            from ..obs import events

            class S:
                def __init__(self):
                    self._lock = threading.Lock()
                    self.shed = 0

                def _account(self):
                    self.shed += 1
                    events.emit("shed", n=self.shed)
                    events.flush()

                def submit(self):
                    with self._lock:
                        self._account()
        """, relpath="serve/x.py", select=["JLT102"])
        assert [f.rule for f in findings] == ["JLT102"]
        assert findings[0].line == 18


class TestJLT103:
    def test_inverted_order_in_one_class(self):
        findings, _ = lint("""
            import threading

            class S:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self):
                    with self._a:
                        with self._b:
                            pass

                def two(self):
                    with self._b:
                        with self._a:
                            pass
        """, relpath="serve/x.py", select=["JLT103"])
        assert {f.rule for f in findings} == {"JLT103"}
        assert "inversion" in findings[0].message

    def test_consistent_order_is_clean(self):
        findings, _ = lint("""
            import threading

            class S:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def one(self):
                    with self._a:
                        with self._b:
                            pass

                def two(self):
                    with self._a:
                        with self._b:
                            pass
        """, relpath="serve/x.py", select=["JLT103"])
        assert findings == []

    def test_call_mediated_inversion(self):
        findings, _ = lint("""
            import threading

            class S:
                def __init__(self):
                    self._a = threading.Lock()
                    self._b = threading.Lock()

                def _inner(self):
                    with self._b:
                        pass

                def one(self):
                    with self._a:
                        self._inner()

                def two(self):
                    with self._b:
                        with self._a:
                            pass
        """, relpath="serve/x.py", select=["JLT103"])
        assert {f.rule for f in findings} == {"JLT103"}


class TestFamilySelect:
    def test_jlt10x_wildcard(self):
        findings, _ = lint(_JLT101_BAD, relpath="serve/x.py",
                           select=["JLT10x"])
        assert [f.rule for f in findings] == ["JLT101"]

    def test_unknown_family_is_usage_error(self):
        with pytest.raises(SystemExit):
            lint("x = 1", select=["JLT99x"])


class TestCLI:
    def test_json_format_and_nonzero_exit(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("import jax\n\n\ndef f(x):\n"
                       "    return jax.device_get(x)\n")
        proc = subprocess.run(
            [sys.executable, "-m", "tools.jaxlint", str(bad),
             "--format", "json"],
            cwd=str(REPO), capture_output=True, text=True)
        assert proc.returncode == 1
        report = json.loads(proc.stdout)
        assert report["counts"] == {"JLT001": 1}
        assert report["findings"][0]["rule"] == "JLT001"
        assert report["findings"][0]["line"] == 5

    def test_clean_file_exits_zero(self, tmp_path):
        ok = tmp_path / "ok.py"
        ok.write_text("def f(x):\n    return x\n")
        proc = subprocess.run(
            [sys.executable, "-m", "tools.jaxlint", str(ok)],
            cwd=str(REPO), capture_output=True, text=True)
        assert proc.returncode == 0

    def test_single_file_keeps_package_relpath(self):
        # per-file invocation must classify identically to a package
        # scan: the jit owner stays exempt, obs/ stays host-sync-exempt
        for rel in ("obs/compile.py", "obs/registry.py",
                    "serve/server.py"):
            proc = subprocess.run(
                [sys.executable, "-m", "tools.jaxlint",
                 str(REPO / "lightgbm_tpu" / rel)],
                cwd=str(REPO), capture_output=True, text=True)
            assert proc.returncode == 0, (rel, proc.stdout)

    def test_exit_zero_flag(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text("import jax\n\n\ndef f(x):\n"
                       "    return jax.device_get(x)\n")
        proc = subprocess.run(
            [sys.executable, "-m", "tools.jaxlint", str(bad),
             "--exit-zero"],
            cwd=str(REPO), capture_output=True, text=True)
        assert proc.returncode == 0


class TestBaselineCLI:
    BAD = ("import jax\n\n\ndef f(x):\n"
           "    return jax.device_get(x)\n")

    def _run(self, *argv):
        return subprocess.run(
            [sys.executable, "-m", "tools.jaxlint"] + list(argv),
            cwd=str(REPO), capture_output=True, text=True)

    def test_known_findings_pass_new_ones_gate(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(self.BAD)
        base = tmp_path / "baseline.json"
        proc = self._run(str(bad), "--baseline", str(base),
                         "--write-baseline")
        assert proc.returncode == 0 and base.exists()
        # unchanged file: the known finding is baselined, exit 0
        proc = self._run(str(bad), "--baseline", str(base))
        assert proc.returncode == 0, proc.stdout
        assert "1 known baselined" in proc.stdout
        # a NEW finding gates, and only it is reported
        bad.write_text(self.BAD +
                       "\n\ndef g(y):\n    return jax.device_get(y)\n")
        proc = self._run(str(bad), "--baseline", str(base))
        assert proc.returncode == 1
        assert proc.stdout.count("JLT001") == 1
        assert ":9:" in proc.stdout  # the new site, not the known one

    def test_missing_baseline_is_usage_error(self, tmp_path):
        bad = tmp_path / "bad.py"
        bad.write_text(self.BAD)
        proc = self._run(str(bad), "--baseline",
                         str(tmp_path / "nope.json"))
        assert proc.returncode == 2

    def test_list_rules_covers_new_catalog(self):
        proc = self._run("--list-rules")
        assert proc.returncode == 0
        for rid in ("JLT008", "JLT009", "JLT010", "JLT101",
                    "JLT102", "JLT103", "JLT000", "JLT007"):
            assert rid in proc.stdout, rid


# ---------------------------------------------------------------------------
# tier-1 gate: the package lints clean
# ---------------------------------------------------------------------------

class TestPackageClean:
    def test_package_lints_clean(self):
        report = jaxlint_run([str(REPO / "lightgbm_tpu")])
        findings = report.pop("_findings")
        assert findings == [], "\n".join(f.text() for f in findings)
        # the suppressions that ARE in the tree all carry rationales
        # (a bare one would have surfaced as a JLT000 finding above)
        assert report["suppressed"] > 0
        assert report["files_scanned"] > 50


# ---------------------------------------------------------------------------
# runtime sanitizer: transfer_guard("disallow") over a full iteration
# ---------------------------------------------------------------------------

def _train_warm(params, n_warm=2):
    from lightgbm_tpu.boosting import create_boosting
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import BinnedDataset
    rng = np.random.RandomState(7)
    X = rng.randn(500, 6)
    if params.get("objective") == "multiclass":
        y = np.digitize(X[:, 0], [-0.5, 0.5]).astype(np.float64)
    else:
        y = (X[:, 0] + 0.5 * X[:, 1] - 0.2 * X[:, 2] > 0) \
            .astype(np.float64)
    cfg = Config.from_params(dict(params, num_iterations=10,
                                  verbosity=-1))
    ds = BinnedDataset.from_matrix(X, cfg, label=y)
    booster = create_boosting(cfg, ds)
    for _ in range(n_warm):
        booster.train_one_iter()
    return booster


class TestTransferGuardSanitizer:
    """One full warmed training iteration must perform ZERO implicit
    host transfers: every scalar/array that crosses to the device does
    so through an explicit jnp.asarray/device_put (utils/scalars.py),
    and the only device→host reads are the documented explicit
    jax.device_get sync points. This is the dynamic check that keeps
    JLT001's static approximation honest."""

    @pytest.mark.parametrize("params", [
        {"objective": "binary", "num_leaves": 7},
        {"objective": "regression", "num_leaves": 7},
        {"objective": "regression", "num_leaves": 7,
         "use_quantized_grad": True},
        {"objective": "multiclass", "num_class": 3, "num_leaves": 7},
        {"objective": "binary", "num_leaves": 7,
         "bagging_fraction": 0.7, "bagging_freq": 1},
    ], ids=["binary", "regression", "quantized8", "multiclass",
            "bagging"])
    def test_train_iteration_no_implicit_transfers(self, params):
        # bagging rides the matrix since the pipelined-boosting
        # refactor: the in-bag draw is one jitted device dispatch
        # (boost.bag_draw), no host RNG and no per-iteration bag
        # transfer left in the loop
        import jax
        booster = _train_warm(params)
        with jax.transfer_guard("disallow"):
            booster.train_one_iter()
        assert booster.iter == 3

    @pytest.mark.parametrize("params", [
        {"objective": "binary", "num_leaves": 7,
         "bagging_fraction": 0.7, "bagging_freq": 1},
        {"objective": "binary", "num_leaves": 7,
         "use_quantized_grad": True,
         "bagging_fraction": 0.7, "bagging_freq": 1},
    ], ids=["batched-exact-bagging", "batched-quantized8-bagging"])
    def test_batched_step_no_implicit_transfers(self, params):
        """ISSUE 13 satellite: a warmed BATCHED multi-iteration step
        (train_batch -> train_many scan) under the guard. With the
        gradient pass, the bagging draw, gh staging/quantization and
        the score update all folded into the scan, the only transfers
        per batch are the explicit seed/iteration staging
        (device_put), the utils/scalars device scalars, and the single
        deliberate record read-back (device_get)."""
        import jax
        booster = _train_warm(dict(params, tree_learner="data",
                                   mesh_shape="data=1"))
        assert booster.can_train_batched()
        booster.train_batch(2)          # warm the scan compile
        with jax.transfer_guard("disallow"):
            booster.train_batch(2)
        assert booster.iter == 6

    @pytest.mark.parametrize("params", [
        {"objective": "binary", "num_leaves": 7},
        {"objective": "regression", "num_leaves": 7,
         "use_quantized_grad": True},
    ], ids=["sharded-exact", "sharded-quantized8"])
    def test_sharded_iteration_stages_shards_explicitly(self, params,
                                                        tmp_path):
        """A warmed SHARDED training iteration under the guard: the
        prefetcher's ``jax.device_put`` staging (io/shards.py
        ``_device_put``) is the only sanctioned host→device transfer in
        the shard sweep — every loop scalar rides the utils/scalars
        cache and the per-split record read-backs are explicit
        ``jax.device_get`` syncs. The guard is set GLOBALLY (not the
        thread-local context manager) so it also covers the
        prefetcher's worker thread, where the staging actually runs —
        explicit device_put stays allowed under "disallow", implicit
        transfers anywhere (either thread) raise."""
        import jax
        from lightgbm_tpu.boosting import create_boosting
        from lightgbm_tpu.config import Config
        from lightgbm_tpu.io.shards import ShardedBinnedDataset
        from lightgbm_tpu.obs.registry import registry
        rng = np.random.RandomState(7)
        X = rng.randn(600, 6)
        y = (X[:, 0] + 0.5 * X[:, 1] - 0.2 * X[:, 2] > 0) \
            .astype(np.float32)

        def src():
            for lo in range(0, 600, 200):
                yield X[lo:lo + 200], y[lo:lo + 200]

        cfg = Config.from_params(dict(params, num_iterations=10,
                                      verbosity=-1))
        ds = ShardedBinnedDataset.from_chunk_source(
            src, cfg, str(tmp_path), shard_rows=250, total_rows=600)
        booster = create_boosting(cfg, ds)
        for _ in range(2):
            booster.train_one_iter()
        staged0 = registry.count("io/shards_staged")
        jax.config.update("jax_transfer_guard", "disallow")
        try:
            booster.train_one_iter()
        finally:
            jax.config.update("jax_transfer_guard", "allow")
        assert booster.iter == 3
        # the sweep really re-staged shards inside the guarded
        # iteration (one per shard per sweep: root + each split)
        assert registry.count("io/shards_staged") - staged0 >= 3

    def test_guard_actually_guards(self):
        # meta-check: the guard in this jax version really does reject
        # implicit transfers (otherwise the tests above prove nothing)
        import jax
        import jax.numpy as jnp
        with jax.transfer_guard("disallow"):
            with pytest.raises(Exception, match="[Dd]isallowed"):
                jnp.ones(4)
