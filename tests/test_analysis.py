"""Contract auditor: each AST pass catches its known-bad fixture and
passes clean code; jaxpr audits flag f64 leaks / broken donation /
host callbacks; baselines ratchet (new fails, pinned passes, budgets
only go down); and the committed tree itself audits clean."""

import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import run_audit
from repro.analysis import baseline as bl
from repro.analysis.jaxpr_audit import (PathReport, audit_jaxpr,
                                        count_aliased_outputs,
                                        donation_of_jitted, jit_cache_size)
from repro.analysis.passes import (FaultSiteHygienePass, NoSilentExceptPass,
                                   NoWallclockPass, TypedSpillErrorsPass,
                                   X64ScopingPass, parse_unit, run_passes)


def _scan(src, modpath="core/device_pipeline.py", passes=None, extra=()):
    unit = parse_unit(f"src/repro/{modpath}", modpath,
                      textwrap.dedent(src))
    return run_passes([unit, *extra], passes)


# ---------------------------------------------------------------------------
# pass (a): no-wallclock
# ---------------------------------------------------------------------------

def test_wallclock_fixture_caught():
    bad = """\
        import time
        import numpy as np
        import random
        from datetime import datetime

        def f():
            t = time.time()
            r = random.random()
            x = np.random.rand(3)
            g = np.random.default_rng()
            d = datetime.now()
            return t, r, x, g, d
    """
    idents = {f.ident for f in _scan(bad, passes=[NoWallclockPass()])}
    assert idents == {"time.time", "random.random", "np.random.rand",
                      "np.random.default_rng", "datetime.datetime.now"}


def test_wallclock_clean_code_passes():
    clean = """\
        import time
        import numpy as np
        import jax

        def f(seed):
            time.sleep(0.1)                      # spends time, reads none
            rng = np.random.default_rng(seed)    # explicit seed
            key = jax.random.PRNGKey(seed)
            return rng, jax.random.uniform(key, (3,))
    """
    assert _scan(clean, passes=[NoWallclockPass()]) == []


def test_wallclock_only_in_critical_modules():
    bad = "import time\nt = time.time()\n"
    assert _scan(bad, modpath="core/report.py",
                 passes=[NoWallclockPass()]) == []
    assert len(_scan(bad, modpath="kernels/sample_attr/ops.py",
                     passes=[NoWallclockPass()])) == 1


def test_wallclock_sees_through_aliases():
    bad = "import time as t\nx = t.monotonic()\n"
    (f,) = _scan(bad, passes=[NoWallclockPass()])
    assert f.ident == "time.monotonic"


# ---------------------------------------------------------------------------
# pass (b): typed-spill-errors
# ---------------------------------------------------------------------------

def test_builtin_oserror_raise_caught():
    bad = """\
        def publish(path):
            raise IOError(f"spill failed: {path}")
    """
    (f,) = _scan(bad, modpath="core/exchange.py",
                 passes=[TypedSpillErrorsPass()])
    assert f.ident == "IOError" and f.line == 2


def test_typed_spill_raise_passes():
    clean = """\
        from repro.core.faults import CorruptShardError

        def publish(path):
            raise CorruptShardError(f"bad crc: {path}")
    """
    assert _scan(clean, modpath="checkpoint/ckpt.py",
                 passes=[TypedSpillErrorsPass()]) == []


def test_bare_reraise_passes():
    clean = """\
        def f():
            try:
                g()
            except IOError:
                raise
    """
    assert _scan(clean, modpath="core/exchange.py",
                 passes=[TypedSpillErrorsPass()]) == []


# ---------------------------------------------------------------------------
# pass (c): no-silent-except
# ---------------------------------------------------------------------------

def test_silent_except_variants_caught():
    bad = """\
        def f():
            try:
                g()
            except ValueError:
                pass
            try:
                g()
            except IOError:
                return None
            for _ in range(3):
                try:
                    g()
                except Exception:
                    print("oops")   # log-and-continue, no counter
    """
    found = _scan(bad, modpath="serve/engine.py",
                  passes=[NoSilentExceptPass()])
    assert len(found) == 3


def test_handled_except_passes():
    clean = """\
        def f(stats):
            try:
                g()
            except IOError as e:
                stats["errors"] += 1
            try:
                g()
            except ValueError as e:
                raise RuntimeError("ctx") from e
    """
    assert _scan(clean, modpath="core/exchange.py",
                 passes=[NoSilentExceptPass()]) == []


def test_pragma_suppresses_with_reason_block():
    ok = """\
        def f():
            try:
                g()
            # audit: allow(no-silent-except) absence means empty here —
            # callers treat a missing dir as no durable state
            except FileNotFoundError:
                return None
    """
    assert _scan(ok, modpath="core/exchange.py",
                 passes=[NoSilentExceptPass()]) == []


def test_pragma_is_per_pass():
    wrong_pass = """\
        def f():
            try:
                g()
            # audit: allow(no-wallclock) wrong pass name
            except FileNotFoundError:
                return None
    """
    assert len(_scan(wrong_pass, modpath="core/exchange.py",
                     passes=[NoSilentExceptPass()])) == 1


# ---------------------------------------------------------------------------
# pass (d): fault-site-hygiene
# ---------------------------------------------------------------------------

def _registry_unit(sites='("a.x", "b.y")'):
    return parse_unit("src/repro/core/faults.py", "core/faults.py",
                      f"FAULT_SITES = {sites}\n")


def test_fault_sites_clean():
    decls = 'from repro.core.faults import declare_site\n' \
            '_A = declare_site("a.x")\n_B = declare_site("b.y")\n'
    assert _scan(decls, modpath="core/seam.py",
                 passes=[FaultSiteHygienePass()],
                 extra=[_registry_unit()]) == []


def test_unregistered_site_caught():
    decls = '_C = declare_site("c.z")\n_A = declare_site("a.x")\n' \
            '_B = declare_site("b.y")\n'
    idents = {f.ident for f in _scan(decls, modpath="core/seam.py",
                                     passes=[FaultSiteHygienePass()],
                                     extra=[_registry_unit()])}
    assert idents == {"unregistered:c.z"}


def test_duplicate_and_undeclared_sites_caught():
    decls = '_A1 = declare_site("a.x")\n_A2 = declare_site("a.x")\n'
    idents = {f.ident for f in _scan(decls, modpath="core/seam.py",
                                     passes=[FaultSiteHygienePass()],
                                     extra=[_registry_unit()])}
    assert idents == {"duplicate:a.x", "undeclared:b.y"}


def test_non_literal_site_caught():
    decls = 'NAME = "a.x"\n_A = declare_site(NAME)\n' \
            '_B = declare_site("b.y")\n'
    idents = {f.ident for f in _scan(decls, modpath="core/seam.py",
                                     passes=[FaultSiteHygienePass()],
                                     extra=[_registry_unit()])}
    assert "<non-literal>" in idents


def test_runtime_registry_matches_static_declarations():
    """The live FAULT_SITES registry and the declared-site map agree:
    every site the static pass expects is declared at import time by
    the module the comments say owns it."""
    import repro.checkpoint.ckpt         # noqa: F401  (declares ckpt.*)
    import repro.core.exchange           # noqa: F401
    import repro.core.sampler            # noqa: F401
    import repro.core.sensors            # noqa: F401
    import repro.serve.engine            # noqa: F401  (declares serve.*)
    import repro.serve.recovery          # noqa: F401
    import repro.serve.scheduler         # noqa: F401
    from repro.core.faults import FAULT_SITES, declared_sites
    assert set(declared_sites()) == set(FAULT_SITES)


def test_runtime_declare_rejects_unknown_and_cross_module_dup():
    from repro.core import faults
    with pytest.raises(ValueError, match="unregistered fault site"):
        faults.declare_site("nope.nope", module="m1")
    faults.declare_site("spiller.publish",
                        module="repro.core.exchange")     # idempotent
    with pytest.raises(ValueError, match="already declared"):
        faults.declare_site("spiller.publish", module="somewhere.else")


# ---------------------------------------------------------------------------
# pass (e): x64-scoping
# ---------------------------------------------------------------------------

def test_unscoped_x64_caught():
    bad = """\
        import jax
        from jax import enable_x64

        enable_x64()                                  # never entered
        jax.config.update("jax_enable_x64", True)     # global flip
    """
    idents = {f.ident for f in _scan(bad, modpath="core/anything.py",
                                     passes=[X64ScopingPass()])}
    assert idents == {"enable_x64-unscoped", "jax_enable_x64-global"}


def test_scoped_x64_passes():
    clean = """\
        from jax import enable_x64

        def f():
            with enable_x64():
                return 1
    """
    assert _scan(clean, modpath="core/anything.py",
                 passes=[X64ScopingPass()]) == []


def test_x64_scoping_reads_jax_namespace_spelling():
    src = """\
        import jax

        def f():
            with jax.enable_x64():
                return 1

        jax.enable_x64()                              # never entered
    """
    idents = [f.ident for f in _scan(src, modpath="core/anything.py",
                                     passes=[X64ScopingPass()])]
    assert idents == ["enable_x64-unscoped"]


# ---------------------------------------------------------------------------
# baseline ratchet (layer 1)
# ---------------------------------------------------------------------------

def _bad_unit():
    return parse_unit("src/repro/core/exchange.py", "core/exchange.py",
                      'def f():\n    raise IOError("x")\n')


def test_baseline_absorbs_pinned_and_fails_new(tmp_path):
    findings = run_passes([_bad_unit()], [TypedSpillErrorsPass()])
    assert len(findings) == 1

    # Unbaselined: the finding is new.
    res = bl.check_findings(findings, {})
    assert not res.ok and len(res.new) == 1

    # Pin it; same findings now absorb. Round-trip through the file.
    path = str(tmp_path / "baseline.json")
    bl.save_counts(bl.finding_counts(findings), path)
    res = bl.check_findings(findings, bl.load_counts(path))
    assert res.ok and len(res.baselined) == 1 and not res.stale_keys

    # A second identical violation exceeds the pinned count.
    two = parse_unit(
        "src/repro/core/exchange.py", "core/exchange.py",
        'def f():\n    raise IOError("x")\n'
        'def g():\n    raise IOError("y")\n')
    findings2 = run_passes([two], [TypedSpillErrorsPass()])
    res = bl.check_findings(findings2, bl.load_counts(path))
    assert not res.ok and len(res.new) == 1 and len(res.baselined) == 1


def test_baseline_reports_stale_keys(tmp_path):
    findings = run_passes([_bad_unit()], [TypedSpillErrorsPass()])
    path = str(tmp_path / "baseline.json")
    bl.save_counts(bl.finding_counts(findings), path)
    res = bl.check_findings([], bl.load_counts(path))
    assert res.ok and len(res.stale_keys) == 1


# ---------------------------------------------------------------------------
# jaxpr audit fixtures (layer 2)
# ---------------------------------------------------------------------------

def test_f64_leak_flagged():
    from jax import enable_x64
    with enable_x64():
        def leaky(x):
            return jnp.asarray(x, jnp.float64) * 2.0 + 1.0
        stats = audit_jaxpr(jax.make_jaxpr(leaky)(
            jnp.ones(4, jnp.float32)))
    assert stats.f64_ops >= 2
    assert stats.f64_widenings >= 1


def test_f32_code_not_flagged():
    def fine(x):
        return x * 2.0 + 1.0
    stats = audit_jaxpr(jax.make_jaxpr(fine)(jnp.ones(4, jnp.float32)))
    assert stats.f64_ops == 0 and stats.f64_widenings == 0


def test_audit_recurses_into_control_flow():
    from jax import enable_x64
    with enable_x64():
        def looped(x):
            return jax.lax.fori_loop(
                0, 3, lambda i, c: c + jnp.float64(1.5), x)
        stats = audit_jaxpr(jax.make_jaxpr(looped)(
            jnp.zeros((), jnp.float64)))
    assert any(p in stats.f64_by_prim for p in ("add", "convert_element_type"))


def test_non_donating_fn_flagged():
    x = jnp.ones(8, jnp.float32)
    plain = jax.jit(lambda a: a + 1.0)
    _, aliased = donation_of_jitted(plain, x, expected=1)
    assert aliased == 0

    donating = jax.jit(lambda a: a + 1.0, donate_argnums=(0,))
    _, aliased = donation_of_jitted(donating, x, expected=1)
    assert aliased == 1


def test_host_callback_detected():
    def chatty(x):
        jax.debug.print("x = {x}", x=x)
        return x * 2
    stats = audit_jaxpr(jax.make_jaxpr(chatty)(jnp.ones(3)))
    assert stats.host_callbacks >= 1


def test_count_aliased_outputs_parses_lowered_text():
    x = jnp.ones(8, jnp.float32)
    donating = jax.jit(lambda a, b: (a + b, b * 2), donate_argnums=(0, 1))
    text = donating.lower(x, jnp.ones(8, jnp.float32)).as_text()
    assert count_aliased_outputs(text) == 2


def test_jit_cache_size_counts_specializations():
    f = jax.jit(lambda a: a * 2)
    assert jit_cache_size(f) == 0
    f(jnp.ones(4, jnp.float32))
    f(jnp.ones(4, jnp.float32))      # same shape: cached
    assert jit_cache_size(f) == 1
    f(jnp.ones(5, jnp.float32))      # new shape: one more compile
    assert jit_cache_size(f) == 2


# ---------------------------------------------------------------------------
# x64 budget ratchet (layer 2)
# ---------------------------------------------------------------------------

def _report(name="p", f64=5, widen=1, cb=0, don=(0, 0)):
    return PathReport(name=name, eqn_count=10, f64_ops=f64,
                      f64_by_prim={"mul": f64}, f64_widenings=widen,
                      host_callbacks=cb, callback_prims=(),
                      donated_expected=don[0], donated_aliased=don[1])


def test_budget_over_and_under():
    budget = {"p": {"f64_ops": 5, "f64_widenings": 1, "host_callbacks": 0}}
    assert bl.check_budget([_report()], budget) == []
    assert bl.check_budget([_report(f64=4)], budget) == []   # ratchet down ok
    over = bl.check_budget([_report(f64=6)], budget)
    assert len(over) == 1 and "f64_ops grew" in over[0].message


def test_budget_unknown_path_fails():
    (v,) = bl.check_budget([_report()], {})
    assert "not in x64_budget.json" in v.message


def test_budget_donation_is_absolute():
    budget = {"p": {"f64_ops": 5, "f64_widenings": 1, "host_callbacks": 0}}
    (v,) = bl.check_budget([_report(don=(5, 4))], budget)
    assert "donation broken" in v.message
    assert bl.check_budget([_report(don=(5, 5))], budget) == []


def test_budget_update_refuses_increase(tmp_path):
    path = str(tmp_path / "budget.json")
    bl.save_budget(bl.merge_budget([_report(f64=5)], {}), path)
    existing = bl.load_budget(path)
    with pytest.raises(ValueError, match="refusing to raise"):
        bl.merge_budget([_report(f64=6)], existing)
    merged = bl.merge_budget([_report(f64=6)], existing,
                             allow_increase=True)
    assert merged["p"]["f64_ops"] == 6
    # Ratcheting down needs no force and rewrites the lower count.
    merged = bl.merge_budget([_report(f64=3)], existing)
    assert merged["p"]["f64_ops"] == 3


# ---------------------------------------------------------------------------
# the committed tree audits clean against its committed baseline
# ---------------------------------------------------------------------------

def test_repo_layer1_clean():
    result = run_audit(jaxpr=False)
    assert result.ratchet.ok, "\n".join(
        f.render() for f in result.ratchet.new)
    assert not result.ratchet.stale_keys
