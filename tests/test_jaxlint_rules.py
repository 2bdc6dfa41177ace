"""jaxlint rule self-tests: positive / negative / pragma-suppressed fixture
snippets per rule (R1-R5), so rule regressions are caught independently of
the package's own code (which the gate in test_jaxlint_gate.py covers)."""

import textwrap

import pytest

from lightgbm_tpu.analysis import run


def _scan(tmp_path, sources, rules=None):
    """sources: {filename: code} written into one scanned root."""
    root = tmp_path / "fixture_pkg"
    root.mkdir()
    for name, code in sources.items():
        (root / name).write_text(textwrap.dedent(code))
    return run([root], rules)


# ---------------------------------------------------------------------------
# R1 host-sync-in-hot-path
# ---------------------------------------------------------------------------

def test_r1_positive_sync_in_jit(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            y = np.asarray(x)
            z = x.item()
            return float(x) + y + z
    """}, rules=["R1"])
    lines = sorted(f.line for f in rep.findings)
    assert len(rep.findings) == 3, rep.findings
    assert all(f.rule == "R1" for f in rep.findings)
    assert lines == [7, 8, 9]


def test_r1_positive_sync_in_host_driver_loop(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import jax
        import numpy as np

        @jax.jit
        def step(s):
            return s + 1

        def drive(s):
            for _ in range(3):
                s = step(s)
                k = np.asarray(s)
            return k
    """}, rules=["R1"])
    assert len(rep.findings) == 1
    assert rep.findings[0].line == 12


def test_r1_positive_reachable_helper_in_other_module(tmp_path):
    """Host-sync in a helper REACHABLE from a jitted function through a
    relative import is still hot."""
    rep = _scan(tmp_path, {
        "helper.py": """
            def pull(x):
                return x.item()
        """,
        "mod.py": """
            import jax
            from .helper import pull

            @jax.jit
            def f(x):
                return pull(x)
        """,
    }, rules=["R1"])
    assert len(rep.findings) == 1
    assert rep.findings[0].file.endswith("helper.py")


def test_r1_positive_submodule_attribute_call(tmp_path):
    """`from . import sub; sub.jitted(x)` in a host loop must resolve —
    the module-attribute call style gbdt/basic use for the predict ops."""
    rep = _scan(tmp_path, {
        "kern.py": """
            import jax

            @jax.jit
            def f(s):
                return s + 1
        """,
        "mod.py": """
            import numpy as np
            from . import kern

            def drive(s):
                for _ in range(3):
                    s = kern.f(s)
                    k = np.asarray(s)
                return k
        """,
    }, rules=["R1"])
    assert len(rep.findings) == 1
    assert rep.findings[0].file.endswith("mod.py")


def test_r1_positive_through_init_reexport(tmp_path):
    """A hot-path sync reached through a package __init__ re-export
    (`from .sub import helper` where sub/__init__.py re-exports it from
    sub/impl.py) must still resolve: relative imports inside __init__
    modules resolve at the package's own level, and re-export chains are
    followed to the defining module."""
    root = tmp_path / "fixture_pkg"
    (root / "sub").mkdir(parents=True)
    (root / "sub" / "__init__.py").write_text(
        "from .impl import helper\n")
    (root / "sub" / "impl.py").write_text(
        "def helper(x):\n    return x.item()\n")
    (root / "main.py").write_text(
        "import jax\nfrom .sub import helper\n\n"
        "@jax.jit\ndef f(x):\n    return helper(x)\n")
    rep = run([root], ["R1"])
    assert len(rep.findings) == 1, rep.findings
    assert rep.findings[0].file.endswith("impl.py")


def test_r1_negative_shape_and_cold_code(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import jax
        import jax.numpy as jnp
        import numpy as np

        @jax.jit
        def f(x):
            n = float(x.shape[0])
            m = int(len(x))
            return x * n * m

        def host_setup(data):
            return np.asarray(data)
    """}, rules=["R1"])
    assert rep.findings == []


def test_r1_pragma_suppressed(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            y = np.asarray(x)  # jaxlint: disable=R1 (fixture: documented exception)
            return y
    """}, rules=["R1"])
    assert rep.findings == []
    assert len(rep.suppressed) == 1
    assert rep.suppressed[0][1].reason == "fixture: documented exception"


# ---------------------------------------------------------------------------
# R2 recompile-hazard
# ---------------------------------------------------------------------------

def test_r2_positive_jit_per_call(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import jax

        def make(x):
            f = jax.jit(lambda v: v + 1)
            return f(x)

        def outer(x):
            @jax.jit
            def inner(v):
                return v * 2
            return inner(x)
    """}, rules=["R2"])
    assert len(rep.findings) == 2
    assert all(f.rule == "R2" for f in rep.findings)


def test_r2_negative_cached_factory_and_module_jit(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import functools
        import jax

        @functools.lru_cache(maxsize=4)
        def make():
            return jax.jit(lambda v: v + 1)

        @functools.partial(jax.jit, static_argnames=("k",))
        def g(x, k):
            return x * k
    """}, rules=["R2"])
    assert rep.findings == []


def test_r2_positive_unhashable_static_literal(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("opts",))
        def g(x, opts):
            return x

        def call(x):
            return g(x, opts=[1, 2])
    """}, rules=["R2"])
    assert len(rep.findings) == 1
    assert "unhashable" in rep.findings[0].message


def test_r2_positive_unhashable_static_kwarg_by_argnum(tmp_path):
    """A static param named via static_argnums but passed by KEYWORD must
    still be checked for unhashable literals."""
    rep = _scan(tmp_path, {"mod.py": """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnums=(1,))
        def g(x, cfg):
            return x

        def call(x):
            return g(x, cfg=[1, 2])
    """}, rules=["R2"])
    assert len(rep.findings) == 1
    assert "unhashable" in rep.findings[0].message


def test_r2_negative_hashable_static(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import functools
        import jax

        @functools.partial(jax.jit, static_argnames=("opts",))
        def g(x, opts):
            return x

        def call(x):
            return g(x, opts=(1, 2))
    """}, rules=["R2"])
    assert rep.findings == []


def test_r2_pragma_suppressed(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import jax

        def make(x):
            f = jax.jit(lambda v: v + 1)  # jaxlint: disable=R2 (fixture: cached by caller)
            return f(x)
    """}, rules=["R2"])
    assert rep.findings == []
    assert len(rep.suppressed) == 1


# ---------------------------------------------------------------------------
# R3 use-after-donate
# ---------------------------------------------------------------------------

def test_r3_positive_read_after_donate(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def upd(state, d):
            return state + d

        def bad(state, d):
            out = upd(state, d)
            return state + out
    """}, rules=["R3"])
    assert len(rep.findings) == 1
    assert rep.findings[0].line == 11
    assert "donated" in rep.findings[0].message


def test_r3_negative_linear_threading(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def upd(state, d):
            return state + d

        def good(state, d):
            for _ in range(3):
                state = upd(state, d)
            return state
    """}, rules=["R3"])
    assert rep.findings == []


def test_r3_positive_donate_argnames(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnames=("state",))
        def upd(state, d):
            return state + d

        def bad(state, d):
            out = upd(state=state, d=d)
            probe = state.sum()
            return out, probe
    """}, rules=["R3"])
    assert len(rep.findings) == 1
    assert rep.findings[0].line == 11


def test_r3_pragma_suppressed(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def upd(state, d):
            return state + d

        def checked(state, d):
            out = upd(state, d)
            assert state.is_deleted()  # jaxlint: disable=R3 (fixture: donation assertion itself)
            return out
    """}, rules=["R3"])
    assert rep.findings == []
    assert len(rep.suppressed) == 1


# ---------------------------------------------------------------------------
# R4 collective-axis-name
# ---------------------------------------------------------------------------

_MESH = """
    DATA_AXIS = "data"
    FEATURE_AXIS = "feature"
"""


def test_r4_positive_undeclared_literal(tmp_path):
    rep = _scan(tmp_path, {
        "mesh.py": _MESH,
        "mod.py": """
            import jax

            def reduce(x):
                return jax.lax.psum(x, "rows")
        """,
    }, rules=["R4"])
    assert len(rep.findings) == 1
    assert "'rows'" in rep.findings[0].message


def test_r4_negative_declared_and_dynamic(tmp_path):
    rep = _scan(tmp_path, {
        "mesh.py": _MESH,
        "mod.py": """
            import jax
            from .mesh import DATA_AXIS

            def reduce(x):
                return jax.lax.psum(x, DATA_AXIS)

            def literal(x):
                return jax.lax.pmax(x, "feature")

            def dynamic(x, axis_name):
                return jax.lax.psum(x, axis_name)
        """,
    }, rules=["R4"])
    assert rep.findings == []


def test_r4_axis_index_first_positional(tmp_path):
    rep = _scan(tmp_path, {
        "mesh.py": _MESH,
        "mod.py": """
            import jax

            def rank(x):
                return jax.lax.axis_index("machines")
        """,
    }, rules=["R4"])
    assert len(rep.findings) == 1


def test_r4_positive_imported_nonaxis_constant(tmp_path):
    """A Name-bound axis arg that resolves to a module-level string
    constant which is NOT a declared axis must be flagged."""
    rep = _scan(tmp_path, {
        "mesh.py": _MESH,
        "misc.py": """
            SOME_NAME = "rows"
        """,
        "mod.py": """
            import jax
            from .misc import SOME_NAME

            def reduce(x):
                return jax.lax.psum(x, SOME_NAME)
        """,
    }, rules=["R4"])
    assert len(rep.findings) == 1
    assert "'rows'" in rep.findings[0].message


def test_r4_pragma_suppressed(tmp_path):
    rep = _scan(tmp_path, {
        "mesh.py": _MESH,
        "mod.py": """
            import jax

            def reduce(x):
                return jax.lax.psum(x, "rows")  # jaxlint: disable=R4 (fixture: axis from a test-only mesh)
        """,
    }, rules=["R4"])
    assert rep.findings == []
    assert len(rep.suppressed) == 1


# ---------------------------------------------------------------------------
# R5 impure-under-jit
# ---------------------------------------------------------------------------

def test_r5_positive_time_rng_global(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import time
        import numpy as np
        import jax

        COUNT = 0

        @jax.jit
        def f(x):
            global COUNT
            COUNT += 1
            t = time.time()
            r = np.random.rand()
            return x + t + r
    """}, rules=["R5"])
    assert len(rep.findings) == 3, rep.findings
    assert any("global" in f.message for f in rep.findings)
    assert any("time.time" in f.message for f in rep.findings)
    assert any("np.random.rand" in f.message for f in rep.findings)


def test_r5_negative_jax_random_and_host_code(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import time
        import numpy as np
        import jax

        @jax.jit
        def f(x, key):
            return x + jax.random.uniform(key, x.shape)

        def host_bench():
            t0 = time.time()
            rng = np.random.RandomState(0)
            return time.time() - t0, rng.rand()
    """}, rules=["R5"])
    assert rep.findings == []


def test_r5_pragma_suppressed(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import time
        import jax

        @jax.jit
        def f(x):
            t = time.time()  # jaxlint: disable=R5 (fixture: trace-time stamp is intended)
            return x + t
    """}, rules=["R5"])
    assert rep.findings == []
    assert len(rep.suppressed) == 1


# ---------------------------------------------------------------------------
# pragma hygiene + CLI plumbing
# ---------------------------------------------------------------------------

def test_pragma_without_reason_is_a_finding(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return np.asarray(x)  # jaxlint: disable=R1
    """})
    assert any(f.rule == "P0" for f in rep.findings)
    # and the R1 is NOT suppressed by the reasonless pragma
    assert any(f.rule == "R1" for f in rep.findings)


def test_pragma_unknown_rule_is_a_finding(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        x = 1  # jaxlint: disable=R99 (no such rule)
    """})
    assert any(f.rule == "P0" for f in rep.findings)


def test_comment_only_pragma_covers_next_line(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            # jaxlint: disable=R1 (fixture: pragma on its own line)
            return np.asarray(x)
    """}, rules=["R1"])
    assert rep.findings == []
    assert len(rep.suppressed) == 1


def test_no_duplicate_findings_for_nested_defs(tmp_path):
    """A defect inside a nested def must be reported exactly once (nested
    functions are their own FuncInfos AND appear in include_nested walks —
    a regression here double-reports every nested finding)."""
    rep = _scan(tmp_path, {"mod.py": """
        import functools
        import time
        import jax

        @functools.partial(jax.jit, static_argnames=("cfg",))
        def g(x, cfg):
            return x

        def outer(x):
            def inner(v):
                return g(v, cfg=[1, 2])
            return inner(x)

        @jax.jit
        def traced(x):
            def helper(v):
                return v + time.time()
            return helper(x)
    """})
    r2 = [f for f in rep.findings if f.rule == "R2"]
    r5 = [f for f in rep.findings if f.rule == "R5"]
    assert len(r2) == 1, r2
    assert len(r5) == 1, r5


def test_comment_only_pragma_skips_blank_lines(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            # jaxlint: disable=R1 (fixture: blank line between pragma and code)

            return np.asarray(x)
    """}, rules=["R1"])
    assert rep.findings == []
    assert len(rep.suppressed) == 1


def test_unknown_rule_selection_raises(tmp_path):
    with pytest.raises(ValueError):
        _scan(tmp_path, {"mod.py": "x = 1\n"}, rules=["R42"])


def test_syntax_error_is_reported_not_fatal(tmp_path):
    rep = _scan(tmp_path, {"mod.py": "def broken(:\n"})
    assert any(f.rule == "E0" for f in rep.findings)


# ---------------------------------------------------------------------------
# R6 fusable-round-loop
# ---------------------------------------------------------------------------

_R6_TWO_PHASE = """
    import functools
    import jax

    @functools.partial(jax.jit, donate_argnums=(0,))
    def admit(state):
        return state + 1, state * 2

    @functools.partial(jax.jit, donate_argnums=(0,))
    def run_pass(state, w):
        return state - w

    def drive(state, w):
        for _ in range(5):
            state, info = admit(state)
            state = run_pass(state, w)
        return state
"""


def test_r6_positive_two_phase_round_loop(tmp_path):
    rep = _scan(tmp_path, {"mod.py": _R6_TWO_PHASE}, rules=["R6"])
    assert len(rep.findings) == 1, rep.findings
    f = rep.findings[0]
    assert f.rule == "R6" and f.line == 16  # the second dispatch
    assert "run_pass" in f.message and "admit" in f.message


def test_r6_negative_host_consumer_between(tmp_path):
    """A host read of the first phase's output between the dispatches is a
    real data dependency — the loop cannot be fused blindly (that sync is
    R1's business, and the async-read protocol the hint points at)."""
    rep = _scan(tmp_path, {"mod.py": """
        import functools
        import jax
        import numpy as np

        @functools.partial(jax.jit, donate_argnums=(0,))
        def admit(state):
            return state + 1, state * 2

        @functools.partial(jax.jit, donate_argnums=(0,))
        def run_pass(state, w):
            return state - w

        def drive(state, w):
            for _ in range(5):
                state, info = admit(state)
                k = int(np.asarray(info)[0])
                state = run_pass(state, k)
            return state
    """}, rules=["R6"])
    assert rep.findings == []


def test_r6_negative_undonated_calls(tmp_path):
    """Without donation the two dispatches do not thread an in-place
    state buffer — nothing forces them into one round body."""
    rep = _scan(tmp_path, {"mod.py": """
        import jax

        @jax.jit
        def admit(state):
            return state + 1

        @jax.jit
        def run_pass(state, w):
            return state - w

        def drive(state, w):
            for _ in range(5):
                state = admit(state)
                state = run_pass(state, w)
            return state
    """}, rules=["R6"])
    assert rep.findings == []


def test_r6_negative_outside_loop(tmp_path):
    """Back-to-back donated dispatches NOT in a loop are a one-off cost,
    not the per-round dispatch class."""
    rep = _scan(tmp_path, {"mod.py": """
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def admit(state):
            return state + 1

        @functools.partial(jax.jit, donate_argnums=(0,))
        def run_pass(state):
            return state * 2

        def setup(state):
            state = admit(state)
            state = run_pass(state)
            return state
    """}, rules=["R6"])
    assert rep.findings == []


def test_r6_pragma_suppressed(tmp_path):
    src = _R6_TWO_PHASE.replace(
        "state = run_pass(state, w)",
        "state = run_pass(state, w)  "
        "# jaxlint: disable=R6 (phases keep separate Mosaic budgets)")
    rep = _scan(tmp_path, {"mod.py": src}, rules=["R6"])
    assert rep.findings == []
    assert len(rep.suppressed) == 1


def test_r6_negative_sequential_single_dispatch_loops(tmp_path):
    """Two SEPARATE loops, each already one dispatch per iteration, must
    not pair across loop boundaries (they cannot be fused per-round)."""
    rep = _scan(tmp_path, {"mod.py": """
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def admit(state):
            return state + 1

        @functools.partial(jax.jit, donate_argnums=(0,))
        def run_pass(state):
            return state * 2

        def drive(state):
            for _ in range(5):
                state = admit(state)
            for _ in range(5):
                state = run_pass(state)
            return state
    """}, rules=["R6"])
    assert rep.findings == []


def test_r6_negative_consumer_on_second_dispatch_line(tmp_path):
    """A host read of the first phase's output INSIDE the second call's
    argument list is still a real data dependency — not fusable."""
    rep = _scan(tmp_path, {"mod.py": """
        import functools
        import jax
        import numpy as np

        @functools.partial(jax.jit, donate_argnums=(0,))
        def admit(state):
            return state + 1, state * 2

        @functools.partial(jax.jit, donate_argnums=(0,))
        def run_pass(state, w):
            return state - w

        def drive(state):
            for _ in range(5):
                state, info = admit(state)
                state = run_pass(state, int(np.asarray(info)[0]))
            return state
    """}, rules=["R6"])
    assert rep.findings == []


def test_r6_negative_bare_read_of_first_dispatch_output(tmp_path):
    """A bare read of the first dispatch's side output between the calls
    (`if info[0]: break` — no recognizable sync call) still implies a
    host data dependency; R6 suppresses conservatively."""
    rep = _scan(tmp_path, {"mod.py": """
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def admit(state):
            return state + 1, state * 2

        @functools.partial(jax.jit, donate_argnums=(0,))
        def run_pass(state, w):
            return state - w

        def drive(state, w):
            for _ in range(5):
                state, info = admit(state)
                if info[0] == 0:
                    break
                state = run_pass(state, w)
            return state
    """}, rules=["R6"])
    assert rep.findings == []


def test_r6_positive_side_output_as_device_argument(tmp_path):
    """Passing the first dispatch's side output straight into the second
    jitted call is device-to-device data flow — the flagship fusable
    shape, NOT a host consumer."""
    rep = _scan(tmp_path, {"mod.py": """
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def admit(state):
            return state + 1, state * 2

        @functools.partial(jax.jit, donate_argnums=(0,))
        def run_pass(state, w):
            return state - w

        def drive(state):
            for _ in range(5):
                state, info = admit(state)
                state = run_pass(state, info)
            return state
    """}, rules=["R6"])
    assert len(rep.findings) == 1, rep.findings
    assert rep.findings[0].rule == "R6"


def test_r6_negative_mutually_exclusive_branches(tmp_path):
    """Dispatches in if/else arms of the same conditional: only one runs
    per iteration — nothing to fuse."""
    rep = _scan(tmp_path, {"mod.py": """
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def fast(state):
            return state + 1

        @functools.partial(jax.jit, donate_argnums=(0,))
        def slow(state):
            return state * 2

        def drive(state, big):
            for _ in range(5):
                if big:
                    state = fast(state)
                else:
                    state = slow(state)
            return state
    """}, rules=["R6"])
    assert rep.findings == []


def test_r6_negative_match_case_arms(tmp_path):
    """match/case arms are mutually exclusive per iteration, exactly like
    if/else."""
    rep = _scan(tmp_path, {"mod.py": """
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def fast(state):
            return state + 1

        @functools.partial(jax.jit, donate_argnums=(0,))
        def slow(state):
            return state * 2

        def drive(state, phase):
            for _ in range(5):
                match phase:
                    case 0:
                        state = fast(state)
                    case _:
                        state = slow(state)
            return state
    """}, rules=["R6"])
    assert rep.findings == []


# ---------------------------------------------------------------------------
# R7 host-nonfinite-guard
# ---------------------------------------------------------------------------

def test_r7_positive_np_isnan_in_driver_loop(tmp_path):
    """Host np.isnan on a per-round tensor inside a grower loop — one
    blocking device pull per round, the guard anti-pattern."""
    rep = _scan(tmp_path, {"mod.py": """
        import jax
        import numpy as np

        @jax.jit
        def step(s):
            return s + 1

        def drive(s):
            for _ in range(5):
                s = step(s)
                if np.isnan(s).any():
                    raise ValueError("nan")
            return s
    """}, rules=["R7"])
    assert len(rep.findings) == 1, rep.findings
    assert rep.findings[0].rule == "R7"
    assert "np.isnan" in rep.findings[0].message


def test_r7_positive_math_isnan_and_float_jnp_pull(tmp_path):
    """math.isnan(...) and bool(jnp.isfinite(...)) in the loop are the
    same sync wearing different costumes."""
    rep = _scan(tmp_path, {"mod.py": """
        import math
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(s):
            return s + 1

        def drive(s, g):
            for _ in range(5):
                s = step(s)
                if math.isnan(g):
                    break
                if bool(jnp.isfinite(s).all()):
                    continue
            return s
    """}, rules=["R7"])
    assert len(rep.findings) == 2, rep.findings
    assert all(f.rule == "R7" for f in rep.findings)


def test_r7_negative_outside_loop_and_device_side(tmp_path):
    """np.isfinite BEFORE the loop is a once-per-call boundary check, and
    jnp.isfinite folded into the dispatched step is the supported
    device-side guard — neither is flagged."""
    rep = _scan(tmp_path, {"mod.py": """
        import jax
        import jax.numpy as jnp
        import numpy as np

        @jax.jit
        def step(s):
            return s + 1, jnp.isfinite(s).all()

        def drive(s, label):
            if not np.isfinite(label).all():
                raise ValueError("bad label")
            for _ in range(5):
                s, flag = step(s)
            return s, flag
    """}, rules=["R7"])
    assert rep.findings == []


def test_r7_negative_non_driver_function(tmp_path):
    """A plain host function (no jit dispatch in its loops) may isnan all
    it likes — numpy-on-numpy is not a device pull."""
    rep = _scan(tmp_path, {"mod.py": """
        import numpy as np

        def clean(rows):
            for r in rows:
                if np.isnan(r).any():
                    raise ValueError("nan row")
            return rows
    """}, rules=["R7"])
    assert rep.findings == []


def test_r7_pragma_suppression(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import jax
        import numpy as np

        @jax.jit
        def step(s):
            return s + 1

        def drive(s):
            for _ in range(5):
                s = step(s)
                if np.isnan(s).any():  # jaxlint: disable=R7 (debug harness, not a hot loop)
                    raise ValueError("nan")
            return s
    """}, rules=["R7"])
    assert rep.findings == []
    assert len(rep.suppressed) == 1


def test_r7_positive_implicit_bool_branch(tmp_path):
    """`if jnp.isnan(x).any():` in a driver loop triggers __bool__ on a
    device array — the implicit form of the sync, flagged exactly once
    (no double count with the explicit-cast check)."""
    rep = _scan(tmp_path, {"mod.py": """
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(s):
            return s + 1

        def drive(s):
            for _ in range(5):
                s = step(s)
                if jnp.isnan(s).any():
                    raise ValueError("nan")
                while jnp.isfinite(s).all():
                    break
            return s
    """}, rules=["R7"])
    assert len(rep.findings) == 2, rep.findings
    assert all("implicit bool" in f.message for f in rep.findings)


# ---------------------------------------------------------------------------
# R8 unbucketed-predict-entry
# ---------------------------------------------------------------------------

def test_r8_positive_boolean_mask_subscript_in_loop(tmp_path):
    """The exact pre-round-9 early-stop anti-pattern: the active set
    shrinks host-side and a jitted entry sees a new leading dim per
    chunk."""
    rep = _scan(tmp_path, {"mod.py": """
        import jax
        import numpy as np

        @jax.jit
        def predict_chunk(x):
            return x.sum(axis=1)

        def predict_early_stop(X, margin):
            raw = np.zeros(X.shape[0])
            active = np.ones(X.shape[0], dtype=bool)
            for _ in range(10):
                raw[active] += predict_chunk(X[active])
                active &= np.abs(raw) < margin
            return raw
    """}, rules=["R8"])
    assert len(rep.findings) == 1, rep.findings
    assert rep.findings[0].rule == "R8"
    assert "active" in rep.findings[0].message


def test_r8_positive_inline_comparison_mask(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import jax
        import numpy as np

        @jax.jit
        def score(x):
            return x * 2

        def drive(X, raw):
            for _ in range(4):
                raw = raw + score(X[raw < 0.5])
            return raw
    """}, rules=["R8"])
    assert len(rep.findings) == 1, rep.findings


def test_r8_negative_padded_bucket_with_device_mask(tmp_path):
    """The supported serving pattern: full padded batch + mask ARGUMENT
    (not subscript) — nothing to flag."""
    rep = _scan(tmp_path, {"mod.py": """
        import jax
        import numpy as np

        @jax.jit
        def predict_chunk(x, active):
            import jax.numpy as jnp
            return jnp.where(active, x.sum(axis=1), 0.0)

        def predict_early_stop(X, margin):
            raw = np.zeros(X.shape[0])
            active = np.ones(X.shape[0], dtype=bool)
            for _ in range(10):
                raw = raw + predict_chunk(X, active)
                active &= np.abs(raw) < margin
            return raw
    """}, rules=["R8"])
    assert not rep.findings, rep.findings


def test_r8_negative_static_subscripts_and_outside_loop(tmp_path):
    """Constant/slice subscripts and one-off calls before the loop keep a
    stable shape — not the recompile class R8 hunts."""
    rep = _scan(tmp_path, {"mod.py": """
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return x + 1

        def drive(X, mask):
            warm = step(X[mask])  # once per call, outside the loop
            s = X[:128]
            for i in range(4):
                s = step(s)
                s = step(X[0:128])
            return warm + s
    """}, rules=["R8"])
    assert not rep.findings, rep.findings


def test_r8_pragma_suppression(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return x

        def drive(X):
            m = np.ones(4, bool)
            for _ in range(3):
                m &= np.abs(step(X[m])) < 1.0  # jaxlint: disable=R8 (tiny fixed cap, measured cheaper than padding)
            return m
    """}, rules=["R8"])
    assert not rep.findings
    assert len(rep.suppressed) == 1


# ---------------------------------------------------------------------------
# R9 untimed-device-section
# ---------------------------------------------------------------------------

def test_r9_positive_perf_counter_around_dispatch(tmp_path):
    """The async-dispatch mistiming anti-pattern: the delta reads before
    any host pull, so it measures the ~1 ms enqueue, not the device."""
    rep = _scan(tmp_path, {"mod.py": """
        import time
        import jax

        @jax.jit
        def step(x):
            return x + 1

        def bench(x):
            t0 = time.perf_counter()
            x = step(x)
            dt = time.perf_counter() - t0
            return x, dt
    """}, rules=["R9"])
    assert len(rep.findings) == 1, rep.findings
    assert rep.findings[0].rule == "R9"
    assert rep.findings[0].line == 12


def test_r9_positive_time_time_in_loop(tmp_path):
    """time.time() deltas around a loop of dispatches are the same class
    (the ISSUE names both timer spellings)."""
    rep = _scan(tmp_path, {"mod.py": """
        import time
        import jax

        @jax.jit
        def step(x):
            return x * 2

        def bench(x):
            t0 = time.time()
            for _ in range(5):
                x = step(x)
            print(time.time() - t0)
            return x
    """}, rules=["R9"])
    assert len(rep.findings) == 1, rep.findings


def test_r9_negative_host_pull_between(tmp_path):
    """An np.asarray of the dispatched value before the read drains the
    queue — the delta is honest, nothing to flag."""
    rep = _scan(tmp_path, {"mod.py": """
        import time
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return x + 1

        def bench(x):
            t0 = time.perf_counter()
            x = step(x)
            _ = np.asarray(x)
            dt = time.perf_counter() - t0
            return x, dt
    """}, rules=["R9"])
    assert not rep.findings, rep.findings


def test_r9_positive_two_var_delta(tmp_path):
    """The stored-second-read spelling — t1 = perf_counter(); dt = t1 - t0
    — is the same mistiming with no inline timer call in the Sub."""
    rep = _scan(tmp_path, {"mod.py": """
        import time
        import jax

        @jax.jit
        def step(x):
            return x + 1

        def bench(x):
            t0 = time.perf_counter()
            x = step(x)
            t1 = time.perf_counter()
            dt = t1 - t0
            return x, dt
    """}, rules=["R9"])
    assert len(rep.findings) == 1, rep.findings
    assert rep.findings[0].line == 13


def test_r9_negative_same_line_pull(tmp_path):
    """np.asarray(step(x)) — the one-line pull-the-dispatch idiom the
    hint itself recommends — syncs on the dispatch's own line and must
    not be flagged."""
    rep = _scan(tmp_path, {"mod.py": """
        import time
        import jax
        import numpy as np

        @jax.jit
        def step(x):
            return x + 1

        def bench(x):
            t0 = time.perf_counter()
            r = np.asarray(step(x))
            dt = time.perf_counter() - t0
            return r, dt
    """}, rules=["R9"])
    assert not rep.findings, rep.findings


def test_r9_negative_async_pull_protocol_and_no_dispatch(tmp_path):
    """A pipelined driver's shape: an async_pull_result between dispatch
    and read accounts the section; a delta with no dispatch inside its
    window is plain host timing."""
    rep = _scan(tmp_path, {"mod.py": """
        import time
        import jax

        @jax.jit
        def round_fused(s):
            return s, s

        def drive(s, san):
            t_last = time.perf_counter()
            pend = []
            for _ in range(4):
                s, info = round_fused(s)
                pend.append(info)
                got = san.async_pull_result(pend.pop(0))
                t_now = time.perf_counter()
                print(t_now - t_last, got)
                t_last = t_now
            return s

        def host_only(a, b):
            t0 = time.perf_counter()
            c = a + b
            return c, time.perf_counter() - t0
    """}, rules=["R9"])
    assert not rep.findings, rep.findings


def test_r9_pragma_suppression(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import time
        import jax

        @jax.jit
        def step(x):
            return x

        def bench(x):
            t0 = time.perf_counter()
            x = step(x)
            dt = time.perf_counter() - t0  # jaxlint: disable=R9 (fixture: enqueue latency is the quantity under test)
            return x, dt
    """}, rules=["R9"])
    assert not rep.findings
    assert len(rep.suppressed) == 1


# ---------------------------------------------------------------------------
# R10 sync-in-span-close
# ---------------------------------------------------------------------------

def test_r10_positive_pull_in_span_exit(tmp_path):
    """A Span __exit__ that pulls the device value to 'drain for the
    timer' — one hidden blocking sync per span, the class R10 exists
    for."""
    rep = _scan(tmp_path, {"mod.py": """
        import time
        import numpy as np

        class TraceSpan:
            def __enter__(self):
                self.t0 = time.perf_counter()
                return self

            def __exit__(self, *exc):
                _ = np.asarray(self.result)
                self.dur = time.perf_counter() - self.t0
    """}, rules=["R10"])
    assert len(rep.findings) == 1, rep.findings
    assert rep.findings[0].rule == "R10"
    assert rep.findings[0].line == 11


def test_r10_positive_block_until_ready_in_span_close(tmp_path):
    """close()-spelled span finalizers are the same close path, and
    block_until_ready is the same fresh drain."""
    rep = _scan(tmp_path, {"mod.py": """
        class SpanRecorder:
            def close(self):
                self.out.block_until_ready()
                self.done = True
    """}, rules=["R10"])
    assert len(rep.findings) == 1, rep.findings


def test_r10_positive_contextmanager_span_tail(tmp_path):
    """A @contextmanager generator named like a span: the code after the
    yield IS the close path."""
    rep = _scan(tmp_path, {"mod.py": """
        import contextlib
        import numpy as np

        @contextlib.contextmanager
        def device_span(name, x):
            yield
            _ = np.asarray(x)
    """}, rules=["R10"])
    assert len(rep.findings) == 1, rep.findings
    assert rep.findings[0].line == 8


def test_r10_negative_clean_close_and_accounted_sync(tmp_path):
    """A close that only reads the host clock is the designed pattern;
    sanitizer-routed accounted reads (sync_pull/async_pull_result) are
    closing AT an accounted sync — allowed, not flagged.  Pulls before
    the yield (the OPEN path of a cm span) are not close-path either."""
    rep = _scan(tmp_path, {"mod.py": """
        import contextlib
        import time
        import numpy as np

        class Span:
            def __exit__(self, *exc):
                self.dur = time.perf_counter() - self.t0
                self.ring.append(self.dur)

        class ResolveSpan:
            def __exit__(self, *exc):
                info = self.san.async_pull_result(self.pending)
                self.attrs["k"] = int(info[0])

        @contextlib.contextmanager
        def warmup_span(x):
            _ = np.asarray(x)
            yield
    """}, rules=["R10"])
    assert not rep.findings, rep.findings


def test_r10_negative_non_span_close_not_matched(tmp_path):
    """Ordinary resource closes pull-at-will — R10 is scoped to span
    closes, not every __exit__ in the tree."""
    rep = _scan(tmp_path, {"mod.py": """
        import numpy as np

        class FileSink:
            def __exit__(self, *exc):
                self.fh.write(str(np.asarray(self.buf)))
                self.fh.close()
    """}, rules=["R10"])
    assert not rep.findings, rep.findings


def test_r10_pragma_suppression(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import numpy as np

        class DebugSpan:
            def __exit__(self, *exc):
                _ = np.asarray(self.x)  # jaxlint: disable=R10 (fixture: debug span, sync cost accepted)
    """}, rules=["R10"])
    assert not rep.findings
    assert len(rep.suppressed) == 1


# ---------------------------------------------------------------------------
# R11 whole-array-vmem-staging
# ---------------------------------------------------------------------------

def test_r11_positive_whole_array_block(tmp_path):
    """The v1 partition kernel's exact shape: a variable-size dimension
    staged as ONE block (constant index map) — O(N) staging traffic and a
    VMEM row cap."""
    rep = _scan(tmp_path, {"mod.py": """
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def call_kernel(kernel, order, n):
            return pl.pallas_call(
                kernel,
                in_specs=[
                    pl.BlockSpec((1, n), lambda s: (0, 0),
                                 memory_space=pltpu.VMEM),
                ],
            )(order)
    """}, rules=["R11"])
    assert len(rep.findings) == 1, rep.findings
    assert rep.findings[0].rule == "R11"
    assert "VMEM" in rep.findings[0].message


def test_r11_positive_missing_index_map_defaults_to_whole(tmp_path):
    """No index map at all stages the array whole too — same finding."""
    rep = _scan(tmp_path, {"mod.py": """
        from jax.experimental import pallas as pl

        def build_spec(n_pad):
            return pl.BlockSpec((n_pad,))
    """}, rules=["R11"])
    assert len(rep.findings) == 1, rep.findings


def test_r11_positive_keyword_form(tmp_path):
    """The same anti-pattern written with keyword arguments
    (block_shape=/index_map=) is flagged too."""
    rep = _scan(tmp_path, {"mod.py": """
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def build_spec(n_pad):
            return pl.BlockSpec(block_shape=(1, n_pad),
                                index_map=lambda s: (0, 0),
                                memory_space=pltpu.VMEM)
    """}, rules=["R11"])
    assert len(rep.findings) == 1, rep.findings
    assert rep.findings[0].rule == "R11"


def test_r11_negative_hbm_ref_and_grid_blocking_and_fixed_tiles(tmp_path):
    """The three normal idioms stay clean: the HBM-ref fix pattern
    (memory_space=ANY), real grid blocking (index map uses a grid arg),
    and literal fixed-size tiles."""
    rep = _scan(tmp_path, {"mod.py": """
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def specs(n, row_tile, nc):
            hbm = pl.BlockSpec(memory_space=pl.ANY)
            hbm2 = pl.BlockSpec((1, n), lambda s: (0, 0),
                                memory_space=pl.ANY)
            grid_blocked = pl.BlockSpec((row_tile, nc), lambda j, i: (i, 0),
                                        memory_space=pltpu.VMEM)
            fixed = pl.BlockSpec((1, 512), lambda s: (0, 0),
                                 memory_space=pltpu.VMEM)
            return hbm, hbm2, grid_blocked, fixed
    """}, rules=["R11"])
    assert not rep.findings, rep.findings


def test_r11_negative_no_pallas_import_not_scanned(tmp_path):
    """BlockSpec-named calls outside pallas modules are someone else's
    API — not scanned."""
    rep = _scan(tmp_path, {"mod.py": """
        def f(layout, n):
            return layout.BlockSpec((1, n), lambda s: (0, 0))
    """}, rules=["R11"])
    assert not rep.findings, rep.findings


def test_r11_positive_data_sized_vmem_scratch(tmp_path):
    """Round-16 extension: a pltpu.VMEM SCRATCH allocation sized by a
    data-dependent dimension is whole-array staging by another name."""
    rep = _scan(tmp_path, {"mod.py": """
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        import jax.numpy as jnp

        def scratches(n, n_pad):
            a = pltpu.VMEM((2, n), jnp.int32)
            b = pltpu.VMEM((1, n_pad), jnp.float32)
            return a, b
    """}, rules=["R11"])
    assert len(rep.findings) == 2, rep.findings
    assert all("scratch" in f.message for f in rep.findings)


def test_r11_negative_const_and_caps_vmem_scratch(tmp_path):
    """Fixed tiles stay clean: literal dims, module-level int constants
    (the partition kernel's _CHUNK), and ALL-CAPS config-tile names (the
    megakernel's budget-derived FB) are the normal idiom."""
    rep = _scan(tmp_path, {"mod.py": """
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        import jax.numpy as jnp

        _CHUNK = 512

        def scratches(T, FB, B):
            a = pltpu.VMEM((2, 1, _CHUNK), jnp.int32)
            b = pltpu.VMEM((T, 3, FB, B), jnp.float32)
            c = pltpu.VMEM((4, 128), jnp.float32)
            return a, b, c
    """}, rules=["R11"])
    assert not rep.findings, rep.findings


def test_r11_vmem_scratch_pragma_suppression(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu
        import jax.numpy as jnp

        def scratch(n_seg):
            # jaxlint: disable=R11 (fixture: O(S) per-segment table)
            return pltpu.VMEM((1, n_seg), jnp.int32)
    """}, rules=["R11"])
    assert not rep.findings
    assert len(rep.suppressed) == 1


def test_r11_pragma_suppression(tmp_path):
    """An intentionally staged SMALL variable-size block (O(S) segment
    table) documents itself with the pragma + reason."""
    rep = _scan(tmp_path, {"mod.py": """
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def spec(S):
            # jaxlint: disable=R11 (fixture: O(S) table, a few KB)
            return pl.BlockSpec((1, S), lambda s: (0, 0),
                                memory_space=pltpu.VMEM)
    """}, rules=["R11"])
    assert not rep.findings
    assert len(rep.suppressed) == 1


# ---------------------------------------------------------------------------
# R12 raw-model-write
# ---------------------------------------------------------------------------

def test_r12_positive_raw_open_write_of_model_artifact(tmp_path):
    """A raw open(..., 'w'/'wb') of a model/snapshot path outside the
    checkpoint helper is the torn-file class the atomic writer exists to
    exclude."""
    rep = _scan(tmp_path, {"mod.py": """
        def save(model_path, text, snap):
            with open(model_path, "w") as fh:
                fh.write(text)
            with open(snap + ".snapshot_iter_3", "wb") as fh:
                fh.write(text.encode())
    """}, rules=["R12"])
    assert len(rep.findings) == 2, rep.findings
    assert all(f.rule == "R12" for f in rep.findings)


def test_r12_positive_np_save_and_os_replace(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import os
        import numpy as np

        def persist(arrays, tmp, manifest_path):
            np.savez("ensemble_snapshot.npz", **arrays)
            os.replace(tmp, manifest_path)
    """}, rules=["R12"])
    assert len(rep.findings) == 2, rep.findings


def test_r12_negative_non_artifact_writes_and_reads(tmp_path):
    """Logs, predictions, data paths: not artifacts.  Reading a model is
    not a write.  Mode must actually contain 'w'."""
    rep = _scan(tmp_path, {"mod.py": """
        import numpy as np

        def ok(log_path, model_path, data):
            with open(log_path, "w") as fh:
                fh.write("line")
            with open(model_path) as fh:
                text = fh.read()
            with open(model_path, "rb") as fh:
                raw = fh.read()
            np.savez("bins_cache.npz", bins=data)
            return text, raw
    """}, rules=["R12"])
    assert not rep.findings, rep.findings


def test_r12_negative_checkpoint_module_exempt(tmp_path):
    """utils/checkpoint.py IS the sanctioned writer — its own raw
    open/os.replace are the implementation, not a violation."""
    rep = _scan(tmp_path, {"checkpoint.py": """
        import os

        def atomic_write_text(model_path, text, tmp):
            with open(tmp, "w") as fh:
                fh.write(text)
            os.replace(tmp, model_path)
    """}, rules=["R12"])
    assert not rep.findings, rep.findings


def test_r12_pragma_suppression(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        def convert(cfg, code):
            with open(cfg.convert_model, "w") as fh:  # jaxlint: disable=R12 (fixture: generated source, not a loadable artifact)
                fh.write(code)
    """}, rules=["R12"])
    assert not rep.findings
    assert len(rep.suppressed) == 1


# ---------------------------------------------------------------------------
# R13 collective-outside-fused-round
# ---------------------------------------------------------------------------

def test_r13_positive_eager_collective_in_round_loop(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def round_fused(state, grad):
            return state + grad, state.sum()

        def drive(state, grad):
            for _ in range(10):
                state, hist = round_fused(state, grad)
                merged = jax.lax.psum(hist, "data")
            return merged
    """}, rules=["R13"])
    assert len(rep.findings) == 1, rep.findings
    assert rep.findings[0].rule == "R13"
    assert "psum" in rep.findings[0].message


def test_r13_positive_jitted_collective_helper_per_round(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import functools
        import jax

        @jax.jit
        def merge_hists(h):
            return jax.lax.psum_scatter(h, "data")

        @functools.partial(jax.jit, donate_argnums=(0,))
        def round_fused(state, grad):
            return state + grad, state.sum()

        def drive(state, grad):
            for _ in range(10):
                state, hist = round_fused(state, grad)
                hist = merge_hists(hist)
            return hist
    """}, rules=["R13"])
    assert len(rep.findings) == 1, rep.findings
    assert "merge_hists" in rep.findings[0].message


def test_r13_negative_collective_inside_donated_round(tmp_path):
    """The FIX pattern: the collective lives inside the donated round
    body (in-dispatch merge) — no finding."""
    rep = _scan(tmp_path, {"mod.py": """
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def round_fused(state, grad):
            hist = jax.lax.psum(grad, "data")
            return state + hist, hist.sum()

        def drive(state, grad):
            for _ in range(10):
                state, info = round_fused(state, grad)
            return state
    """}, rules=["R13"])
    assert rep.findings == []


def test_r13_negative_loop_without_donated_dispatch(tmp_path):
    """Collectives in setup/eval loops with no donated round dispatch
    are out of scope (not the per-round regression class)."""
    rep = _scan(tmp_path, {"mod.py": """
        import jax

        @jax.jit
        def evaluate(score):
            return score.sum()

        def eval_all(scores):
            out = []
            for s in scores:
                loss = evaluate(s)
                out.append(jax.lax.psum(loss, "data"))
            return out
    """}, rules=["R13"])
    assert rep.findings == []


def test_r13_pragma_suppression(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import functools
        import jax

        @functools.partial(jax.jit, donate_argnums=(0,))
        def round_fused(state, grad):
            return state + grad, state.sum()

        def drive(state, grad):
            for _ in range(10):
                state, hist = round_fused(state, grad)
                merged = jax.lax.psum(hist, "data")  # jaxlint: disable=R13 (fixture: debug-only fleet probe)
            return merged
    """}, rules=["R13"])
    assert rep.findings == []


# ---------------------------------------------------------------------------
# R14 metadata-via-device-pull
# ---------------------------------------------------------------------------

def test_r14_positive_asarray_shape(tmp_path):
    """The PR-9 review class: reading a length through a whole-array
    conversion of a (possibly jitted) output."""
    rep = _scan(tmp_path, {"mod.py": """
        import numpy as np

        def f(x):
            return np.asarray(x).shape[0]
    """}, rules=["R14"])
    assert len(rep.findings) == 1, rep.findings
    assert rep.findings[0].rule == "R14"
    assert rep.findings[0].line == 5


def test_r14_positive_len_of_asarray_and_dtype(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import numpy as np

        def f(x, y):
            n = len(np.asarray(x))
            out = np.full(n, 0, dtype=np.array(y).dtype)
            return out
    """}, rules=["R14"])
    assert len(rep.findings) == 2, rep.findings
    assert sorted(f.line for f in rep.findings) == [5, 6]


def test_r14_positive_shape_item(tmp_path):
    """.item() on a shape entry: shape entries are already Python ints."""
    rep = _scan(tmp_path, {"mod.py": """
        def f(x):
            return x.shape[0].item()
    """}, rules=["R14"])
    assert len(rep.findings) == 1, rep.findings


def test_r14_negative_direct_metadata_and_bound_conversion(tmp_path):
    """Reading .shape/.dtype directly, np.shape(), and converting ONCE
    into a binding whose data is then used are all clean."""
    rep = _scan(tmp_path, {"mod.py": """
        import numpy as np

        def f(x):
            n = x.shape[0]
            m = np.shape(x)[0]
            a = np.asarray(x)
            return a.dtype, a[: n + m]
    """}, rules=["R14"])
    assert rep.findings == [], rep.findings


def test_r14_pragma_suppression(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import numpy as np

        def f(x):
            return np.asarray(x).shape  # jaxlint: disable=R14 (x is a host list; conversion is how we learn the shape)
    """}, rules=["R14"])
    assert rep.findings == []
    assert len(rep.suppressed) == 1


# ---------------------------------------------------------------------------
# R15 staging-alloc-in-serve-loop
# ---------------------------------------------------------------------------

def test_r15_positive_fresh_alloc_in_serve_loop(tmp_path):
    """The anti-pattern the pinned-buffer serving design exists to
    prevent: a fresh staging buffer allocated per request iteration."""
    rep = _scan(tmp_path, {"mod.py": """
        import numpy as np

        def serve_loop(g, requests):
            outs = []
            for X in requests:
                buf = np.zeros((128, X.shape[1]), np.float32)
                buf[: X.shape[0]] = X
                outs.append(g.predict_raw(buf))
            return outs
    """}, rules=["R15"])
    assert len(rep.findings) == 1, rep.findings
    assert rep.findings[0].rule == "R15"
    assert rep.findings[0].line == 7


def test_r15_positive_upload_of_fresh_host_array(tmp_path):
    """jnp.asarray over a freshly constructed host array inside the loop:
    allocate-then-upload per call — ONE finding, not two (the wrapped
    alloc reports as the upload form)."""
    rep = _scan(tmp_path, {"mod.py": """
        import jax.numpy as jnp
        import numpy as np
        from .san import sync_pull

        def drive(entry, reqs):
            for X in reqs:
                out = entry(jnp.asarray(np.empty((8, 4), np.float32)))
                sync_pull(out)
    """, "san.py": """
        def sync_pull(x):
            return x
    """}, rules=["R15"])
    assert len(rep.findings) == 1, rep.findings
    assert "allocate-then-upload" in rep.findings[0].message


def test_r15_negative_pinned_buffer_reused_across_iterations(tmp_path):
    """The sanctioned pattern: the buffer hoisted out of the loop, filled
    per request, uploaded BY NAME — exactly serve/runtime.py's staging."""
    rep = _scan(tmp_path, {"mod.py": """
        import jax.numpy as jnp
        import numpy as np

        def serve_loop(g, requests, f):
            buf = np.zeros((128, f), np.float32)  # pinned, reused
            outs = []
            for X in requests:
                buf[: X.shape[0]] = X
                outs.append(g.predict_raw(jnp.asarray(buf)))
            return outs
    """}, rules=["R15"])
    assert rep.findings == [], rep.findings


def test_r15_negative_alloc_in_non_predict_loop(tmp_path):
    """Loops with no accounted predict entry (setup, training drivers)
    are out of scope — R1/R14 own their allocation hygiene."""
    rep = _scan(tmp_path, {"mod.py": """
        import numpy as np

        def build_tables(sizes):
            tables = []
            for n in sizes:
                tables.append(np.zeros((n, 4), np.float32))
            return tables
    """}, rules=["R15"])
    assert rep.findings == [], rep.findings


def test_r15_pragma_suppression(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import numpy as np

        def replay(g, reqs):
            for X in reqs:
                pad = np.zeros((64, 4), np.float32)  # jaxlint: disable=R15 (fixture: one-shot replay tool, not a serving loop)
                pad[: X.shape[0]] = X
                g.predict_raw(pad)
    """}, rules=["R15"])
    assert rep.findings == []
    assert len(rep.suppressed) == 1


# ---------------------------------------------------------------------------
# stale-pragma detection (P1)
# ---------------------------------------------------------------------------

def test_stale_pragma_reported_as_warning_by_default(tmp_path):
    """A suppression whose line no longer triggers the named rule is
    reported in Report.stale but does not fail the default run."""
    rep = _scan(tmp_path, {"mod.py": """
        import numpy as np

        def f(x):
            return x + 1  # jaxlint: disable=R1 (retired: the pull was removed)
    """})
    assert rep.findings == []
    assert len(rep.stale) == 1
    assert rep.stale[0].rule == "P1"
    assert "R1" in rep.stale[0].message


def test_stale_pragma_fails_under_strict(tmp_path):
    import textwrap
    root = tmp_path / "fixture_pkg"
    root.mkdir()
    (root / "mod.py").write_text(textwrap.dedent("""
        def f(x):
            return x  # jaxlint: disable=R5 (retired)
    """))
    rep = run([root], strict_pragmas=True)
    assert not rep.ok
    assert any(f.rule == "P1" for f in rep.findings)


def test_live_pragma_is_not_stale(tmp_path):
    """A pragma that still suppresses a real finding stays untouched."""
    rep = _scan(tmp_path, {"mod.py": """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return np.asarray(x)  # jaxlint: disable=R1 (fixture: intentional)
    """})
    assert rep.findings == []
    assert rep.stale == []
    assert len(rep.suppressed) == 1


def test_stale_pragma_subset_run_does_not_misjudge(tmp_path):
    """A subset run (--rules) cannot conclude staleness for unselected
    rules: a pragma naming an unselected rule is left alone."""
    rep = _scan(tmp_path, {"mod.py": """
        def f(x):
            return x  # jaxlint: disable=R5 (would be stale under a full run)
    """}, rules=["R1"])
    assert rep.stale == []


def test_pragma_inside_docstring_is_ignored(tmp_path):
    """Pragma-shaped text in a string literal is documentation, not a
    suppression — it must neither suppress nor count as stale."""
    rep = _scan(tmp_path, {"mod.py": '''
        def f(x):
            """Example: y = np.asarray(d)  # jaxlint: disable=R1 (why)"""
            return x
    '''})
    assert rep.stale == []
    assert rep.suppressed == []


# ---------------------------------------------------------------------------
# R16 mutation-outside-version-bump
# ---------------------------------------------------------------------------

def _scan_tree(tmp_path, sources, rules=None):
    """Like _scan, but filenames may carry subdirectories — R16 is scoped
    to serve/ and continual/ paths."""
    root = tmp_path / "fixture_pkg"
    for name, code in sources.items():
        p = root / name
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(code))
    return run([root], rules)


def test_r16_positive_models_subscript_write_in_serve(tmp_path):
    rep = _scan_tree(tmp_path, {"serve/swap.py": """
        def hot_patch(g, i, tree):
            g.models[i] = tree
            return g
    """}, rules=["R16"])
    assert len(rep.findings) == 1
    assert rep.findings[0].rule == "R16"
    assert ".models" in rep.findings[0].message


def test_r16_positive_leaf_write_and_list_mutator_in_continual(tmp_path):
    rep = _scan_tree(tmp_path, {"continual/refitlike.py": """
        def renew(g, new_lv, extra_tree):
            for i, t in enumerate(g.models):
                t.leaf_value = new_lv[i]
            g._models.append(extra_tree)
    """}, rules=["R16"])
    assert len(rep.findings) == 2, rep.findings
    assert {f.rule for f in rep.findings} == {"R16"}


def test_r16_negative_mutation_routed_through_bump(tmp_path):
    rep = _scan_tree(tmp_path, {"continual/refitlike.py": """
        def renew(g, new_lv):
            for i, t in enumerate(g.models):
                t.leaf_value = new_lv[i]
            g._invalidate_pred_cache("renew")
    """}, rules=["R16"])
    assert rep.findings == []


def test_r16_negative_outside_scoped_dirs(tmp_path):
    """The identical mutation OUTSIDE serve/continual paths is out of
    scope (the versioned key's n_models component and the runtime pins
    own it — docs/ANALYSIS.md static-limits note)."""
    rep = _scan_tree(tmp_path, {"models/trainer.py": """
        def grow(g, tree):
            g._models.append(tree)
    """}, rules=["R16"])
    assert rep.findings == []


def test_r16_pragma_suppression(tmp_path):
    rep = _scan_tree(tmp_path, {"serve/swap.py": """
        def hot_patch(g, i, tree):
            g.models[i] = tree  # jaxlint: disable=R16 (fixture: caller holds the pack lock and bumps)
            return g
    """}, rules=["R16"])
    assert rep.findings == []
    assert len(rep.suppressed) == 1


# ---------------------------------------------------------------------------
# R17 full-histogram-over-dcn
# ---------------------------------------------------------------------------

def test_r17_positive_full_hist_psum_over_dcn(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import jax

        def merge(fresh_hists):
            return jax.lax.psum(fresh_hists, "dcn")
    """}, rules=["R17"])
    assert len(rep.findings) == 1
    assert rep.findings[0].rule == "R17"
    assert "dcn" in rep.findings[0].message


def test_r17_positive_all_gather_hist_via_axis_constant(tmp_path):
    """The DCN axis referenced through the mesh constant (incl. a
    both-axes tuple) is still the dcn axis."""
    rep = _scan(tmp_path, {"mod.py": """
        import jax

        ICI_AXIS = "ici"
        DCN_AXIS = "dcn"

        def gather(hist0):
            return jax.lax.all_gather(hist0, DCN_AXIS)

        def both(cand_hist):
            return jax.lax.psum(cand_hist, (ICI_AXIS, DCN_AXIS))
    """}, rules=["R17"])
    assert len(rep.findings) == 2
    assert all(f.rule == "R17" for f in rep.findings)


def test_r17_negative_topk_shaped_and_scalar_operands(tmp_path):
    """The sanctioned shapes: an elected top-k histogram subset
    (take_along_axis by the vote's indices) and scalar/gain traffic
    cross dcn clean; the full merge stays on ici."""
    rep = _scan(tmp_path, {"mod.py": """
        import jax
        import jax.numpy as jnp

        def election(cand_hists, g_idx, vote_gain, total):
            sub_hists = jnp.take_along_axis(
                cand_hists, g_idx[:, None, :, None], axis=2)
            sub_hists = jax.lax.psum(sub_hists, "dcn")
            gains = jax.lax.all_gather(vote_gain, "dcn")
            worst = jax.lax.pmax(total, ("ici", "dcn"))
            slice_hists = jax.lax.psum(cand_hists, "ici")
            return sub_hists, gains, worst, slice_hists
    """}, rules=["R17"])
    assert rep.findings == []


def test_r17_negative_full_hist_inside_slice(tmp_path):
    """The intra-slice full merge is the design, not a finding."""
    rep = _scan(tmp_path, {"mod.py": """
        import jax

        DATA_AXIS = "data"

        def merge(fresh_hists, hist0):
            a = jax.lax.psum(fresh_hists, "ici")
            b = jax.lax.psum_scatter(hist0, DATA_AXIS,
                                     scatter_dimension=2, tiled=True)
            return a, b
    """}, rules=["R17"])
    assert rep.findings == []


def test_r17_pragma_suppression(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import jax

        def debug_merge(dbg_hists):
            return jax.lax.psum(dbg_hists, "dcn")  # jaxlint: disable=R17 (fixture: one-off debug parity probe, never the round path)
    """}, rules=["R17"])
    assert rep.findings == []
    assert len(rep.suppressed) == 1


def test_r17_nested_def_neither_duplicates_nor_misses_enclosing_gather(
        tmp_path):
    """Nested defs are walked through their enclosing function only: a
    top-k gather assigned in the ENCLOSING scope sanctions a dcn
    collective inside a nested def (no false positive), and a genuine
    violation inside a nested def reports exactly once."""
    rep = _scan(tmp_path, {"mod.py": """
        import jax
        import jax.numpy as jnp

        def outer_clean(cand_hists, g_idx):
            sub_hists = jnp.take_along_axis(
                cand_hists, g_idx[:, None, :, None], axis=2)

            def merge():
                return jax.lax.psum(sub_hists, "dcn")
            return merge

        def outer_bad(fresh_hists):
            def merge():
                return jax.lax.psum(fresh_hists, "dcn")
            return merge
    """}, rules=["R17"])
    assert len(rep.findings) == 1, rep.findings
    assert "outer_bad" in rep.findings[0].message


# ---------------------------------------------------------------------------
# R19 unbounded-retry
# ---------------------------------------------------------------------------

def test_r19_positive_hot_retry_loop(tmp_path):
    """The canonical anti-pattern: swallow everything, loop straight back
    into the next attempt — no pacing, no budget, no deadline."""
    rep = _scan(tmp_path, {"mod.py": """
        import requests

        def poll(url):
            while True:
                try:
                    return requests.get(url)
                except Exception:
                    continue
    """}, rules=["R19"])
    assert len(rep.findings) == 1
    assert rep.findings[0].rule == "R19"
    assert "backoff" in rep.findings[0].message


def test_r19_positive_bare_except_swallow(tmp_path):
    """A bare except that logs and spins is the same hazard; re-dispatch
    spellings (predict/send) count as IO-ish."""
    rep = _scan(tmp_path, {"mod.py": """
        def drive(runtime, batch, log):
            while True:
                try:
                    runtime.predict(batch)
                except:
                    log.warning("dispatch failed")
    """}, rules=["R19"])
    assert len(rep.findings) == 1, rep.findings
    assert "predict" in rep.findings[0].message


def test_r19_negative_paced_or_bounded(tmp_path):
    """Pacing (sleep/backoff), a retry budget, or a deadline check each
    bound the loop — any one of them clears the finding."""
    rep = _scan(tmp_path, {"mod.py": """
        import time
        import requests

        def paced(url):
            backoff = 0.05
            while True:
                try:
                    return requests.get(url)
                except Exception:
                    time.sleep(backoff)
                    backoff *= 2

        def budgeted(url, clock):
            deadline = clock() + 30.0
            while clock() < deadline:
                try:
                    return requests.get(url)
                except Exception:
                    pass
            raise TimeoutError(url)
    """}, rules=["R19"])
    assert rep.findings == []


def test_r19_negative_narrow_catch_and_worker_loop(tmp_path):
    """A narrow catch names the one expected failure instead of swallowing
    all of them, and a worker loop blocking on a bare queue ``.get()`` for
    its next item cannot hot-spin (the serve dispatcher shape); a handler
    that re-raises or breaks surfaces the failure instead of retrying."""
    rep = _scan(tmp_path, {"mod.py": """
        import queue

        def worker(hand, runtime):
            while True:
                try:
                    item = hand.get()
                    runtime.predict(item)
                except queue.Empty:
                    continue

        def surfaced(runtime, batch):
            while True:
                try:
                    return runtime.predict(batch)
                except Exception:
                    raise
    """}, rules=["R19"])
    assert rep.findings == []


def test_r19_pragma_suppression(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import requests

        def poll(url):
            while True:
                try:  # jaxlint: disable=R19 (fixture: chaos-harness spin probe, bounded by the harness timeout)
                    return requests.get(url)
                except Exception:
                    continue
    """}, rules=["R19"])
    assert rep.findings == []
    assert len(rep.suppressed) == 1


# ---------------------------------------------------------------------------
# R20 feature-axis-hist-collective
# ---------------------------------------------------------------------------

def test_r20_positive_hist_psum_over_feature_literal(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import jax

        def merge(leaf_hists):
            return jax.lax.psum(leaf_hists, "feature")
    """}, rules=["R20"])
    assert len(rep.findings) == 1
    assert rep.findings[0].rule == "R20"
    assert "feature axis" in rep.findings[0].message


def test_r20_positive_axis_constant_and_tuple(tmp_path):
    """The feature axis referenced through the mesh constant or a
    feature_axis_name variable — including in a both-axes tuple — is
    still the feature axis."""
    rep = _scan(tmp_path, {"mod.py": """
        import jax

        DATA_AXIS = "data"
        FEATURE_AXIS = "feature"

        def gather(hist0):
            return jax.lax.all_gather(hist0, FEATURE_AXIS)

        def both(cand_hist, feature_axis_name):
            return jax.lax.psum(cand_hist, (DATA_AXIS, feature_axis_name))
    """}, rules=["R20"])
    assert len(rep.findings) == 2
    assert all(f.rule == "R20" for f in rep.findings)


def test_r20_negative_row_merge_and_non_hist_broadcast(tmp_path):
    """The sanctioned feature2d traffic: the histogram merge over the ROW
    axis, the winner's go/no-go row broadcast (not hist-named), and
    election scalars cross the feature axis clean."""
    rep = _scan(tmp_path, {"mod.py": """
        import jax
        import jax.numpy as jnp

        DATA_AXIS = "data"
        FEATURE_AXIS = "feature"

        def round_body(fresh_hists, go_left, own_pos, gain):
            merged_hists = jax.lax.psum(fresh_hists, DATA_AXIS)
            go_left = jax.lax.psum(
                jnp.where(own_pos, go_left, False).astype(jnp.int32),
                FEATURE_AXIS) > 0
            best = jax.lax.pmax(gain, (DATA_AXIS, FEATURE_AXIS))
            return merged_hists, go_left, best
    """}, rules=["R20"])
    assert rep.findings == []


def test_r20_negative_topk_shaped_subset(tmp_path):
    """An elected top-k histogram subset (take_along_axis by the vote's
    indices) may cross the feature axis — the R17 escape carries over."""
    rep = _scan(tmp_path, {"mod.py": """
        import jax
        import jax.numpy as jnp

        def election(cand_hists, g_idx):
            sub_hists = jnp.take_along_axis(
                cand_hists, g_idx[:, None, :, None], axis=2)
            return jax.lax.psum(sub_hists, "feature")
    """}, rules=["R20"])
    assert rep.findings == []


def test_r20_pragma_suppression(tmp_path):
    rep = _scan(tmp_path, {"mod.py": """
        import jax

        def debug_merge(dbg_hists):
            return jax.lax.psum(dbg_hists, "feature")  # jaxlint: disable=R20 (fixture: one-off parity probe, never the round path)
    """}, rules=["R20"])
    assert rep.findings == []
    assert len(rep.suppressed) == 1


# ---------------------------------------------------------------------------
# R21 unlinked-cross-thread-span
# ---------------------------------------------------------------------------

def test_r21_positive_implicit_span_in_thread_target(tmp_path):
    """A record_span with no ctx/parent/links inside a Thread target:
    the worker's thread-local span stack is empty, so the span roots a
    fresh trace instead of joining the crossing request."""
    rep = _scan_tree(tmp_path, {"serve/worker.py": """
        import threading
        from ..obs import trace as _trace

        class Runtime:
            def start(self):
                self._t = threading.Thread(target=self._dispatch_loop,
                                           daemon=True)
                self._t.start()

            def _dispatch_loop(self):
                while True:
                    batch = self._pop()
                    _trace.record_span("serve.batch", 0.001, rows=8)
    """}, rules=["R21"])
    assert len(rep.findings) == 1
    assert rep.findings[0].rule == "R21"
    assert "_dispatch_loop" in rep.findings[0].message


def test_r21_positive_executor_submitted_span_context_manager(tmp_path):
    """executor.submit(fn) marks fn as a thread entry too; a bare
    span() context manager there is the same empty-stack trap."""
    rep = _scan_tree(tmp_path, {"continual/roller.py": """
        from ..obs import trace as _trace

        class Runner:
            def kick(self, pool):
                pool.submit(self._rollover)

            def _rollover(self):
                with _trace.span("continual.rollover", mode="refit"):
                    self._do_roll()
    """}, rules=["R21"])
    assert len(rep.findings) == 1
    assert rep.findings[0].rule == "R21"


def test_r21_negative_explicit_ctx_and_links(tmp_path):
    """Spans that carry their causal identity explicitly — ctx= on the
    leg span, links= adopting the batch members — are the designed
    cross-thread pattern and pass clean."""
    rep = _scan_tree(tmp_path, {"serve/worker.py": """
        import threading
        from ..obs import trace as _trace

        class Runtime:
            def start(self):
                self._t = threading.Thread(target=self._dispatch_loop,
                                           daemon=True)

            def _dispatch_loop(self):
                while True:
                    batch = self._pop()
                    leg = batch[0].ctx.sibling()
                    _trace.record_span("serve.batch", 0.001, ctx=leg,
                                       links=[r.ctx for r in batch])
    """}, rules=["R21"])
    assert rep.findings == []


def test_r21_negative_outside_scoped_dirs_and_non_entry(tmp_path):
    """Both escapes at once: the identical implicit span OUTSIDE
    serve//continual/ paths is out of scope, and a function never handed
    to Thread/submit is not an entry even inside them."""
    rep = _scan_tree(tmp_path, {
        "obs/exporter.py": """
            import threading
            from . import trace as _trace

            def start(self):
                threading.Thread(target=_flush_loop, daemon=True).start()

            def _flush_loop():
                _trace.record_span("obs.flush", 0.001)
        """,
        "serve/helpers.py": """
            from ..obs import trace as _trace

            def note_admit(runtime):
                _trace.record_span("serve.admit", 0.0001)
        """}, rules=["R21"])
    assert rep.findings == []


def test_r21_pragma_suppression(tmp_path):
    rep = _scan_tree(tmp_path, {"serve/worker.py": """
        import threading
        from ..obs import trace as _trace

        class Runtime:
            def start(self):
                self._t = threading.Thread(target=self._gc_loop, daemon=True)

            def _gc_loop(self):
                while True:
                    _trace.record_span("serve.gc", 0.001)  # jaxlint: disable=R21 (fixture: maintenance sweep owns no request; rootless by design)
    """}, rules=["R21"])
    assert rep.findings == []
    assert len(rep.suppressed) == 1
