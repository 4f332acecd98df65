"""figaro-lint: every rule fires on its known-bad fixture and stays quiet on
the fixed tree; suppressions, the unused report, and the committed baseline
stay exact."""

import json
import textwrap
from pathlib import Path

import pytest

from repro.analysis import (Baseline, analyze_paths, analyze_source,
                            load_baseline, unused_report)
from repro.analysis.baseline import empty_baseline, write_baseline
from repro.analysis.rules import all_rules

REPO = Path(__file__).resolve().parents[1]


def _findings(source, path="src/repro/core/fixture.py"):
    return analyze_source(textwrap.dedent(source), path, all_rules())


def _rules_fired(source, path="src/repro/core/fixture.py"):
    return {f.rule for f in _findings(source, path)}


# -- FIG001 compat pin -------------------------------------------------------

FIG001_BAD = """
    from jax.sharding import AxisType, PartitionSpec
    from jax.experimental.shard_map import shard_map
    import jax

    def mesh(devices):
        return jax.make_mesh((len(devices),), ("data",))
"""

FIG001_GOOD = """
    from jax.sharding import PartitionSpec
    from repro.compat import AxisType, make_mesh, shard_map

    def mesh(devices):
        return make_mesh((len(devices),), ("data",))
"""


def test_fig001_fires_on_direct_imports():
    findings = [f for f in _findings(FIG001_BAD) if f.rule == "FIG001"]
    msgs = "\n".join(f.message for f in findings)
    assert "AxisType" in msgs
    assert "shard_map" in msgs
    assert "jax.make_mesh" in msgs
    # PartitionSpec is version-stable: not flagged.
    assert "PartitionSpec" not in msgs


def test_fig001_quiet_on_compat_routed():
    assert "FIG001" not in _rules_fired(FIG001_GOOD)


def test_fig001_exempts_the_shim_itself():
    assert "FIG001" not in _rules_fired(FIG001_BAD,
                                        path="src/repro/compat.py")


# -- FIG002 retrace hazards --------------------------------------------------

FIG002_PLAN_CLOSURE = """
    import jax

    def make_fn(plan, dtype):
        def fn(data):
            return run(plan, data, dtype)
        return jax.jit(fn)
"""

FIG002_PLAN_ARG = """
    import jax

    def make_fn(dtype):
        def fn(plan, data):
            return run(plan, data, dtype)
        return jax.jit(fn)
"""

FIG002_BAD_STATIC_NAMES = """
    import functools
    import jax

    @functools.partial(jax.jit, static_argnames=("dtype", "methodd"))
    def solve(data, *, dtype, method=None):
        return data
"""

FIG002_UNHASHABLE = """
    import functools
    import jax

    @functools.partial(jax.jit, static_argnames=("opts",))
    def solve(data, *, opts=[]):
        return data
"""


def test_fig002_plan_closure():
    msgs = [f.message for f in _findings(FIG002_PLAN_CLOSURE)
            if f.rule == "FIG002"]
    assert any("captures plan value" in m for m in msgs)


def test_fig002_plan_as_argument_is_quiet():
    assert "FIG002" not in _rules_fired(FIG002_PLAN_ARG)


def test_fig002_unknown_static_name():
    msgs = [f.message for f in _findings(FIG002_BAD_STATIC_NAMES)
            if f.rule == "FIG002"]
    assert any("methodd" in m for m in msgs)


def test_fig002_unhashable_static_default():
    msgs = [f.message for f in _findings(FIG002_UNHASHABLE)
            if f.rule == "FIG002"]
    assert any("unhashable" in m for m in msgs)


# -- FIG003 dtype drift ------------------------------------------------------

FIG003_BAD = """
    import jax.numpy as jnp

    def scan(x):
        acc = x.astype(jnp.float32)
        return acc.sum()
"""

FIG003_GOOD = """
    import jax.numpy as jnp

    def scan(x, *, dtype=jnp.float32):
        acc_dtype = jnp.float64 if x.dtype == jnp.float64 else jnp.float32
        acc = x.astype(acc_dtype)
        return acc.sum()
"""


def test_fig003_fires_on_hardcoded_narrowing():
    assert "FIG003" in _rules_fired(FIG003_BAD,
                                    path="src/repro/kernels/fix.py")


def test_fig003_quiet_on_accumulator_idiom_and_defaults():
    assert "FIG003" not in _rules_fired(FIG003_GOOD,
                                        path="src/repro/kernels/fix.py")


def test_fig003_out_of_scope_paths_ignored():
    # The policy covers core/ and kernels/; models/ may pick working dtypes.
    assert "FIG003" not in _rules_fired(FIG003_BAD,
                                        path="src/repro/models/fix.py")


def test_fig003_counts_file_rejects_even_the_idiom():
    fired = _findings(FIG003_GOOD, path="src/repro/core/counts.py")
    msgs = [f.message for f in fired if f.rule == "FIG003"]
    assert any("float64" in m and "2^24" in m for m in msgs)


# -- FIG004 pallas kernel sites ----------------------------------------------

FIG004_BAD = """
    import jax
    from jax.experimental import pallas as pl

    def launch(x, bm, bn):
        m, n = x.shape
        grid = (m // bm, n // bn)
        return pl.pallas_call(kernel, grid=grid,
                              out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                              )(x)
"""

FIG004_GOOD = """
    import jax
    from jax.experimental import pallas as pl
    from repro.kernels._platform import resolve_interpret

    def launch(x, bm, bn, *, interpret=None):
        m, n = x.shape
        mp = -(-m // bm) * bm
        np_ = -(-n // bn) * bn
        grid = (mp // bm, np_ // bn)
        return pl.pallas_call(kernel, grid=grid,
                              out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
                              interpret=resolve_interpret(interpret),
                              )(x)
"""

FIG004_FORWARD = """
    def launch(x, *, interpret=None):
        return inner(x, interpret=interpret)
"""

FIG004_AUTOTUNE_BAD = """
    AUTOTUNE = {
        (4, 128): (512, 200),
        (4, None): (4096, 4096),
        (8, 512): (132, 256),
    }
"""

FIG004_AUTOTUNE_GOOD = """
    AUTOTUNE = {
        (4, 128): (512, 128),
        (4, None): (128, 512),
        (8, 512): (128, 256),
        (8, None): (64, 512),
    }
"""


def test_fig004_missing_interpret_and_unpadded_grid():
    msgs = [f.message for f in _findings(FIG004_BAD) if f.rule == "FIG004"]
    joined = "\n".join(msgs)
    assert "without interpret=" in joined
    assert "floor-divides" in joined


def test_fig004_resolved_interpret_and_padded_grid_quiet():
    assert "FIG004" not in _rules_fired(FIG004_GOOD)


def test_fig004_raw_interpret_forwarding():
    msgs = [f.message for f in _findings(FIG004_FORWARD)
            if f.rule == "FIG004"]
    assert any("forwards its unresolved interpret" in m for m in msgs)


def test_fig004_autotune_budget_alignment_catchall():
    msgs = [f.message for f in _findings(FIG004_AUTOTUNE_BAD)
            if f.rule == "FIG004"]
    joined = "\n".join(msgs)
    assert "lane-aligned" in joined        # (512, 200)
    assert "VMEM" in joined                # (4096, 4096) busts the budget
    assert "sublane-aligned" in joined     # (132, 256)
    assert "catch-all" in joined           # itemsize 8 has no None bound


def test_fig004_autotune_good_table_quiet():
    assert "FIG004" not in _rules_fired(FIG004_AUTOTUNE_GOOD)


# -- FIG005 lock discipline --------------------------------------------------

FIG005_BAD = """
    import threading

    class Server:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0

        def bump(self):
            self.count += 1
"""

FIG005_GOOD = """
    import threading

    class Server:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0

        def bump(self):
            with self._lock:
                self.count += 1

        def read(self):
            return self.count
"""

FIG005_NO_LOCKS = """
    class Plain:
        def __init__(self):
            self.count = 0

        def bump(self):
            self.count += 1
"""


def test_fig005_unlocked_write_fires():
    msgs = [f.message for f in _findings(FIG005_BAD) if f.rule == "FIG005"]
    assert any("Server.bump" in m and "self.count" in m for m in msgs)


def test_fig005_locked_write_and_reads_quiet():
    assert "FIG005" not in _rules_fired(FIG005_GOOD)


def test_fig005_lockless_classes_exempt():
    assert "FIG005" not in _rules_fired(FIG005_NO_LOCKS)


# -- FIG006 cross-thread escape ----------------------------------------------

FIG006_BAD = """
    import threading

    class Server:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
            self.items = []

        def bump(self):
            with self._lock:
                self.count += 1
                self.items.append(1)

        def stats(self):
            return self.count

        def note(self):
            self.items.append(2)
"""

FIG006_GOOD = """
    import threading
    import queue

    class Server:
        _san_atomic = ("flag",)

        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
            self.frozen = 41
            self.q = queue.Queue()
            self.flag = False

        def bump(self):
            with self._lock:
                self.count += 1
                self._grow()

        def _grow(self):
            self.count += 1

        def stats(self):
            with self._lock:
                return self.count

        def lockfree(self):
            self.q.put(1)           # thread-safe factory
            self.flag = True        # figaro-lint: disable=FIG005 -- atomic
            return self.frozen + (1 if self.flag else 0)
"""

FIG006_THREAD_ENTRY = """
    import threading

    class Server:
        def __init__(self):
            self._lock = threading.Lock()
            self.count = 0
            threading.Thread(target=self._loop).start()

        def _loop(self):
            return self.count

        def bump(self):
            with self._lock:
                self.count += 1
"""


def test_fig006_unlocked_read_and_mutcall_fire():
    msgs = [f.message for f in _findings(FIG006_BAD) if f.rule == "FIG006"]
    assert any("Server.stats reads" in m and "self.count" in m for m in msgs)
    assert any("Server.note mutates (in place)" in m and "self.items" in m
               for m in msgs)
    # the locked accesses in bump() are not findings
    assert not any("Server.bump" in m for m in msgs)


def test_fig006_exemptions_quiet():
    """Locked reads, immutable attrs, thread-safe factories, _san_atomic
    annotations, and interprocedurally-locked private helpers all pass."""
    assert "FIG006" not in _rules_fired(FIG006_GOOD)


def test_fig006_thread_entry_never_inherits_lock():
    """A method whose bound reference escapes to a Thread target is a thread
    entry: its unlocked read is a finding even though its only in-class
    'call site' is the escape itself."""
    msgs = [f.message for f in _findings(FIG006_THREAD_ENTRY)
            if f.rule == "FIG006"]
    assert any("Server._loop reads" in m and "self.count" in m for m in msgs)


def test_fig006_does_not_duplicate_fig005_writes():
    """Plain unlocked writes stay FIG005 findings only."""
    findings = _findings(FIG005_BAD)
    assert "FIG005" in {f.rule for f in findings}
    assert "FIG006" not in {f.rule for f in findings}


# -- FIG007 sanitizer routing ------------------------------------------------

FIG007_BAD = """
    import threading

    def start(worker):
        lock = threading.Lock()
        t = threading.Thread(target=worker, daemon=True)
        t.start()
        return lock, t
"""

FIG007_GOOD = """
    import threading

    from repro.sanitizer.locks import san_lock
    from repro.sanitizer.threads import san_thread

    def start(worker):
        lock = san_lock("start.lock")
        t = san_thread(worker, daemon=True)
        t.start()
        gate = threading.Event()      # not modelled: allowed raw
        sem = threading.Semaphore(4)  # not modelled: allowed raw
        return lock, t, gate, sem
"""


def test_fig007_raw_threading_in_src_fires():
    msgs = [f.message for f in _findings(FIG007_BAD) if f.rule == "FIG007"]
    assert any("threading.Lock" in m and "san_lock" in m for m in msgs)
    assert any("threading.Thread" in m and "san_thread" in m for m in msgs)


def test_fig007_wrappers_and_unmodelled_primitives_quiet():
    assert "FIG007" not in _rules_fired(FIG007_GOOD)


def test_fig007_out_of_scope_paths_ignored():
    assert "FIG007" not in _rules_fired(
        FIG007_BAD, path="tests/test_stress.py")
    assert "FIG007" not in _rules_fired(
        FIG007_BAD, path="src/repro/sanitizer/locks.py")


# -- FIG008 jax-free planner -------------------------------------------------

FIG008_BAD = """
    import jax
    import jax.numpy as jnp
    from repro.core.join_tree import JoinTree

    def score(tree):
        return jnp.sum(jax.numpy.ones(3))
"""

FIG008_GOOD = """
    from typing import TYPE_CHECKING

    import numpy as np

    from repro.planner.stats import DatabaseStats
    from .cost import orientation_cost

    if TYPE_CHECKING:
        from repro.core.join_tree import JoinTree  # typing only: erased

    def score(stats):
        return float(np.sum([1.0]))
"""


def test_fig008_fires_on_jax_and_runtime_imports_in_planner():
    msgs = [f.message for f in _findings(
        FIG008_BAD, path="src/repro/planner/fixture.py")
        if f.rule == "FIG008"]
    assert any("`jax`" in m for m in msgs)
    assert any("`jax.numpy`" in m for m in msgs)
    assert any("repro.core.join_tree" in m and "duck-type" in m
               for m in msgs)


def test_fig008_quiet_on_numpy_stdlib_and_type_checking():
    assert "FIG008" not in _rules_fired(
        FIG008_GOOD, path="src/repro/planner/fixture.py")


def test_fig008_out_of_scope_paths_ignored():
    # jax imports everywhere else in the runtime are the normal state.
    assert "FIG008" not in _rules_fired(
        FIG008_BAD, path="src/repro/core/fixture.py")


def test_fig008_planner_sources_are_clean():
    findings = analyze_paths([str(REPO / "src" / "repro" / "planner")],
                             rules=all_rules(), root=str(REPO))
    assert [f for f in findings if f.rule == "FIG008"] == []


def test_fix_hint_rendered_in_human_output():
    finding = next(f for f in _findings(FIG007_BAD) if f.rule == "FIG007")
    rendered = finding.render()
    assert "\n    fix: " in rendered and finding.fix_hint in rendered


# -- suppressions ------------------------------------------------------------

def test_line_suppression_silences_only_that_line():
    src = """
    import jax.numpy as jnp

    def f(x):
        a = x.astype(jnp.float32)  # figaro-lint: disable=FIG003 -- test
        b = x.astype(jnp.float32)
        return a + b
    """
    findings = _findings(src, path="src/repro/core/fix.py")
    lines = [f.line for f in findings if f.rule == "FIG003"]
    assert len(lines) == 1  # only the unsuppressed write remains


def test_file_suppression_silences_the_module():
    src = """
    # figaro-lint: disable-file=FIG003 -- fixture corpus
    import jax.numpy as jnp

    def f(x):
        return x.astype(jnp.float32)
    """
    assert "FIG003" not in _rules_fired(src, path="src/repro/core/fix.py")


def test_suppression_in_string_literal_is_inert():
    src = '''
    import jax.numpy as jnp

    NOTE = "# figaro-lint: disable-file=FIG003 -- not a comment"

    def f(x):
        return x.astype(jnp.float32)
    '''
    assert "FIG003" in _rules_fired(src, path="src/repro/core/fix.py")


def test_syntax_error_surfaces_as_fig000():
    findings = _findings("def broken(:\n    pass\n")
    assert [f.rule for f in findings] == ["FIG000"]


# -- baseline ----------------------------------------------------------------

def test_baseline_roundtrip_and_staleness(tmp_path):
    findings = _findings(FIG003_BAD, path="src/repro/kernels/fix.py")
    assert findings
    path = tmp_path / "baseline.json"
    write_baseline(str(path), findings)
    baseline = load_baseline(str(path))
    new, baselined = baseline.split(findings)
    assert not new and len(baselined) == len(findings)
    assert baseline.stale(findings) == []
    # After the violation is fixed the entry goes stale.
    assert baseline.stale([]) == [f.fingerprint() for f in findings]


def test_empty_baseline_covers_nothing():
    findings = _findings(FIG003_BAD, path="src/repro/kernels/fix.py")
    new, baselined = empty_baseline().split(findings)
    assert new == findings and baselined == []


# -- the real tree -----------------------------------------------------------

def test_repo_matches_committed_baseline_exactly():
    """The committed analysis_baseline.json is exact: no un-baselined
    findings in src/, and no stale entries (fixed violations must drop out
    of the baseline)."""
    findings = analyze_paths([str(REPO / "src")], root=str(REPO))
    baseline = load_baseline(str(REPO / "analysis_baseline.json"))
    new, _ = baseline.split(findings)
    assert new == [], "non-baselined findings:\n" + \
        "\n".join(f.render() for f in new)
    assert baseline.stale(findings) == []


def test_repo_import_graph_has_no_orphans():
    report = unused_report(src_root=str(REPO / "src"))
    assert report["orphans"] == [], (
        "dead modules (unreachable and unreferenced): "
        f"{report['orphans']}")
    # The quarantined seed scaffolding stays listed, not silently dropped.
    for mod, info in report["modules"].items():
        if info["class"] == "external-only":
            assert info["referenced_by"], mod


def test_unused_report_on_synthetic_package(tmp_path):
    src = tmp_path / "src"
    pkg = src / "repro"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "figaro.py").write_text("from repro import used\n")
    (pkg / "used.py").write_text("X = 1\n")
    (pkg / "dead.py").write_text("Y = 2\n")
    (pkg / "tested.py").write_text("Z = 3\n")
    tests = tmp_path / "tests"
    tests.mkdir()
    (tests / "test_t.py").write_text("import repro.tested\n")
    report = unused_report(src_root=str(src),
                           external_dirs=[str(tests)],
                           roots=["repro.figaro"])
    classes = {m: i["class"] for m, i in report["modules"].items()}
    assert classes["repro.used"] == "facade"
    assert classes["repro.tested"] == "external-only"
    assert classes["repro.dead"] == "orphan"
    assert report["orphans"] == ["repro.dead"]


# -- figaro-flow: call graph -------------------------------------------------

import ast as _ast  # noqa: E402

from repro.analysis.callgraph import Program  # noqa: E402
from repro.analysis.framework import FileContext, load_program  # noqa: E402


def _program(*files):
    """Program over in-memory (path, source) modules."""
    ctxs = []
    for path, source in files:
        src = textwrap.dedent(source)
        ctxs.append(FileContext(path, src, _ast.parse(src)))
    return Program(ctxs)


def test_callgraph_aliased_import_resolution():
    prog = _program(
        ("src/repro/core/alib.py", """
            def helper(x):
                return x + 1
        """),
        ("src/repro/core/blib.py", """
            from repro.core.alib import helper as h

            def caller(x):
                return h(x)
        """))
    edges = prog.graph.edges["repro.core.blib:caller"]
    assert "repro.core.alib:helper" in edges


def test_callgraph_self_dispatch_and_jit_decorator():
    prog = _program(("src/repro/core/eng.py", """
        import jax

        class Eng:
            def _qr_impl(self, plan, data):
                return self._one(data)

            def _one(self, d):
                return d

        @jax.jit
        def fast(x):
            return slow(x)

        def slow(x):
            return x

        def host(x):
            return x
    """))
    g = prog.graph
    assert "repro.core.eng:Eng._one" in g.edges["repro.core.eng:Eng._qr_impl"]
    assert g.roots["repro.core.eng:Eng._qr_impl"].kind == "engine-impl"
    assert g.roots["repro.core.eng:fast"].kind == "jax.jit"
    # Transitivity: slow is traced via fast; host stays host.
    assert "repro.core.eng:slow" in g.traced
    assert "repro.core.eng:Eng._one" in g.traced
    assert "repro.core.eng:host" not in g.traced


def test_callgraph_shard_map_and_function_arg_roots():
    prog = _program(("src/repro/core/dist.py", """
        from repro.compat import shard_map

        def body(block):
            return combine(block)

        def combine(b):
            return b

        def launch(mesh, x):
            return shard_map(body, mesh=mesh)(x)
    """))
    g = prog.graph
    assert g.roots["repro.core.dist:body"].kind == "shard_map"
    assert "repro.core.dist:combine" in g.traced


def test_callgraph_report_renders_classification():
    prog = _program(("src/repro/core/eng.py", """
        import jax

        @jax.jit
        def fast(x):
            return x
    """))
    text = prog.graph.render_text()
    assert "traced root [jax.jit]" in text
    dot = prog.graph.render_dot()
    assert "digraph figaro_flow" in dot and "fast" in dot
    js = prog.graph.to_json()
    assert js["functions"]["repro.core.eng:fast"]["root"] == "jax.jit"


def test_load_program_over_repo_src():
    prog = load_program([str(REPO / "src" / "repro" / "analysis")],
                        root=str(REPO))
    assert len(prog.graph.functions) > 50
    # The analysis package is jax-free: no traced regions at all.
    assert not prog.graph.roots


# -- FIG009 host sync (figaro-flow dataflow) ---------------------------------

FIG009_BAD_CHAIN = """
    import jax
    import numpy as np

    @jax.jit
    def entry(x):
        return level1(x)

    def level1(a):
        return level2(a * 2)

    def level2(b):
        return np.asarray(b)
"""

FIG009_GOOD_META = """
    import jax
    import numpy as np

    @jax.jit
    def entry(x):
        rows = int(x.shape[0])
        return level1(x, rows)

    def level1(a, rows):
        return a * rows
"""

FIG009_GOOD_HOST = """
    import numpy as np

    def host_path(x):
        return np.asarray(x)
"""


def test_fig009_fires_through_three_deep_chain():
    findings = [f for f in _findings(FIG009_BAD_CHAIN)
                if f.rule == "FIG009"]
    assert findings, "np.asarray on traced value two calls deep must fire"
    f = findings[0]
    assert "np.asarray" in f.message
    # The dataflow fixpoint attributes the sink to level2, traced via the
    # root chain.
    assert f.traced_context[0] == "entry"
    assert f.traced_context[-1] == "level2"
    assert f.to_json()["traced_context"] == list(f.traced_context)


def test_fig009_metadata_and_host_paths_quiet():
    assert "FIG009" not in _rules_fired(FIG009_GOOD_META)
    assert "FIG009" not in _rules_fired(FIG009_GOOD_HOST)


def test_fig009_static_kwonly_param_is_concrete():
    src = """
        class Eng:
            def _qr_impl(self, plan, data, *, panel):
                cols = int(panel)
                return data * cols
    """
    assert "FIG009" not in _rules_fired(src)


def test_fig009_item_sink_on_traced_value():
    src = """
        import jax

        @jax.jit
        def f(x):
            return float(x.sum())
    """
    findings = [f for f in _findings(src) if f.rule == "FIG009"]
    assert findings and "float()" in findings[0].message


# -- FIG010 trace effects ----------------------------------------------------

FIG010_BAD = """
    import jax

    CALLS = []

    @jax.jit
    def f(x):
        CALLS.append(1)
        print("tracing")
        return x * 2
"""

FIG010_BAD_SELF = """
    class Eng:
        def _qr_impl(self, plan, data):
            self.count = self.count + 1
            return data
"""

FIG010_GOOD_LOCKED = """
    import threading
    import jax

    _lock = threading.Lock()
    COUNT = [0]

    @jax.jit
    def f(x):
        with _lock:
            COUNT[0] += 1
        return x * 2
"""

FIG010_GOOD_LOCAL = """
    import jax

    @jax.jit
    def f(x):
        acc = []
        acc.append(x)
        out = {}
        out["y"] = x * 2
        return out["y"]
"""


def test_fig010_fires_on_global_mutation_and_print():
    msgs = [f.message for f in _findings(FIG010_BAD) if f.rule == "FIG010"]
    joined = "\n".join(msgs)
    assert "CALLS" in joined
    assert "print" in joined


def test_fig010_fires_on_self_write_in_impl():
    findings = [f for f in _findings(FIG010_BAD_SELF)
                if f.rule == "FIG010"]
    assert findings and "self.count" in findings[0].message


def test_fig010_lock_guarded_and_local_state_quiet():
    assert "FIG010" not in _rules_fired(FIG010_GOOD_LOCKED)
    assert "FIG010" not in _rules_fired(FIG010_GOOD_LOCAL)


# -- FIG011 donation after dispatch ------------------------------------------

FIG011_BAD_STRAIGHT = """
    def run(plan, batch):
        eng = FigaroEngine()
        r = eng.qr(plan, batch)
        return batch, r
"""

FIG011_BAD_LOOP = """
    def stream(plan, buf, n):
        eng = FigaroEngine()
        outs = []
        for _ in range(n):
            outs.append(eng.r0(plan, buf))
        return outs
"""

FIG011_GOOD_NO_DONATE = """
    def run(plan, batch):
        eng = FigaroEngine(donate_data=False)
        r = eng.qr(plan, batch)
        return batch, r
"""

FIG011_GOOD_REBIND = """
    def stream(plan, batches, n):
        eng = FigaroEngine()
        outs = []
        for buf in batches:
            outs.append(eng.r0(plan, buf))
        return outs
"""

FIG011_GOOD_FACTORY = """
    def run(plan, batch):
        eng = default_engine()
        r = eng.qr(plan, batch)
        return batch, r
"""


def test_fig011_fires_on_read_after_donating_dispatch():
    findings = [f for f in _findings(FIG011_BAD_STRAIGHT)
                if f.rule == "FIG011"]
    assert findings and "donated data position" in findings[0].message


def test_fig011_fires_on_loop_without_rebind():
    findings = [f for f in _findings(FIG011_BAD_LOOP)
                if f.rule == "FIG011"]
    assert findings and "never rebinds" in findings[0].message


def test_fig011_quiet_on_non_donating_and_rebinding_paths():
    assert "FIG011" not in _rules_fired(FIG011_GOOD_NO_DONATE)
    assert "FIG011" not in _rules_fired(FIG011_GOOD_REBIND)
    assert "FIG011" not in _rules_fired(FIG011_GOOD_FACTORY)


# -- FIG012 slab layout proofs -----------------------------------------------

FIG012_STALE_BUMP = """
    import dataclasses

    def layout(specs, preorder, make):
        row_acc = 0
        for i in reversed(preorder):
            sp = specs[i]
            specs[i] = dataclasses.replace(sp, tail_row0=row_acc,
                                           out_row0=row_acc + sp.m)
            row_acc += sp.m
        return make(r0_rows=row_acc,
                    total_rows=sum(sp.m for sp in specs))
"""

FIG012_STALE_OUT = """
    import dataclasses

    def layout(specs, preorder, make):
        row_acc = 0
        for i in reversed(preorder):
            sp = specs[i]
            specs[i] = dataclasses.replace(sp, tail_row0=row_acc,
                                           out_row0=row_acc)
            row_acc += sp.m + sp.K
        return make(r0_rows=row_acc,
                    total_rows=sum(sp.m for sp in specs))
"""

FIG012_GOOD_LAYOUT = """
    import dataclasses

    def layout(specs, preorder, make):
        row_acc = 0
        for i in reversed(preorder):
            sp = specs[i]
            specs[i] = dataclasses.replace(sp, tail_row0=row_acc,
                                           out_row0=row_acc + sp.m)
            row_acc += sp.m + sp.K
        total_rows = sum(sp.m for sp in specs)
        return make(r0_rows=row_acc, total_rows=total_rows)
"""

FIG012_BAD_BAND = """
    def bands(nodes, preorder):
        out = []
        for i in reversed(preorder):
            sp = nodes[i]
            out.append(SlabBand(node=i, kind="tail", row0=sp.out_row0,
                                rows=sp.m, col0=sp.col_start, width=sp.n))
        return out
"""

FIG012_BAD_POW2 = """
    def next_pow2(x):
        return 1 << int(x).bit_length()
"""

FIG012_BAD_PARTIAL_BUCKET = """
    import dataclasses

    def bucket(spec):
        return [dataclasses.replace(sp, m=next_pow2(sp.m),
                                    K=sp.K + 1)
                for sp in spec.nodes]
"""

FIG012_BAD_COL = """
    def columns(order, widths):
        col_start = {}
        acc = 0
        for nme in order:
            col_start[nme] = acc + 1
            acc += widths[nme]
        num_cols = acc
        return col_start, num_cols
"""

FIG012_GOOD_COL = """
    def columns(order, widths):
        col_start = {}
        acc = 0
        for nme in order:
            col_start[nme] = acc
            acc += widths[nme]
        num_cols = acc
        return col_start, num_cols
"""


def test_fig012_stale_row_bump_fires():
    msgs = [f.message for f in _findings(FIG012_STALE_BUMP)
            if f.rule == "FIG012"]
    assert any("advance by" in m for m in msgs)


def test_fig012_stale_out_row0_fires():
    msgs = [f.message for f in _findings(FIG012_STALE_OUT)
            if f.rule == "FIG012"]
    assert any("out_row0" in m for m in msgs)


def test_fig012_canonical_layout_quiet():
    assert "FIG012" not in _rules_fired(FIG012_GOOD_LAYOUT)


def test_fig012_band_contract_violation_fires():
    msgs = [f.message for f in _findings(FIG012_BAD_BAND)
            if f.rule == "FIG012"]
    assert any("tail_row0" in m for m in msgs)


def test_fig012_noncanonical_pow2_fires():
    msgs = [f.message for f in _findings(FIG012_BAD_POW2)
            if f.rule == "FIG012"]
    assert any("canonical" in m for m in msgs)


def test_fig012_partial_bucketing_fires():
    msgs = [f.message for f in _findings(FIG012_BAD_PARTIAL_BUCKET)
            if f.rule == "FIG012"]
    assert any("`K`" in m for m in msgs)


def test_fig012_column_prefix_sums():
    assert "FIG012" in _rules_fired(FIG012_BAD_COL)
    assert "FIG012" not in _rules_fired(FIG012_GOOD_COL)


def test_fig012_real_layout_modules_prove_clean():
    findings = analyze_paths(
        [str(REPO / "src" / "repro" / "core" / "join_tree.py"),
         str(REPO / "src" / "repro" / "core" / "plan_cache.py")],
        root=str(REPO))
    assert [f for f in findings if f.rule == "FIG012"] == []


# -- FIG004 upgrades: backend rows + grid one call level ---------------------

FIG004_AUTOTUNE_GPU_BAD = """
    AUTOTUNE = {
        ("gpu", 4, 128): (96, 128),
        ("gpu", 4, None): (32, 512),
        ("gpu", 8, None): (16, 512),
    }
"""

FIG004_AUTOTUNE_GPU_GOOD = """
    AUTOTUNE = {
        ("gpu", 4, 128): (128, 128),
        ("gpu", 4, None): (16, 512),
        ("gpu", 8, 128): (64, 128),
        ("gpu", 8, None): (16, 512),
    }
"""

FIG004_GRID_HELPERS_GOOD = """
    from repro.kernels._platform import resolve_interpret
    from jax.experimental import pallas as pl

    def _pad_to(x, b):
        return -(-x // b) * b

    def _grid_for(mp, np_, bm, bn):
        return (np_ // bn, mp // bm)

    def launch(kernel, m, n, bm, bn, interpret=None):
        mp = _pad_to(m, bm)
        np_ = _pad_to(n, bn)
        return pl.pallas_call(
            kernel, grid=_grid_for(mp, np_, bm, bn),
            interpret=resolve_interpret(interpret))
"""

FIG004_GRID_HELPERS_BAD = """
    from repro.kernels._platform import resolve_interpret
    from jax.experimental import pallas as pl

    def _grid_for(m, n, bm, bn):
        return (n // bn, m // bm)

    def launch(kernel, m, n, bm, bn, interpret=None):
        return pl.pallas_call(
            kernel, grid=_grid_for(m, n, bm, bn),
            interpret=resolve_interpret(interpret))
"""


def test_fig004_gpu_rows_power_of_two_and_f64_catchall():
    msgs = [f.message for f in _findings(FIG004_AUTOTUNE_GPU_BAD)
            if f.rule == "FIG004"]
    joined = "\n".join(msgs)
    assert "power of two" in joined        # (96, 128)
    assert "f64 itemsize" in joined        # (4, None)=(32,512) at 8 bytes


def test_fig004_gpu_good_table_quiet():
    assert "FIG004" not in _rules_fired(FIG004_AUTOTUNE_GPU_GOOD)


def test_fig004_grid_through_helpers():
    assert "FIG004" not in _rules_fired(FIG004_GRID_HELPERS_GOOD)
    msgs = [f.message for f in _findings(FIG004_GRID_HELPERS_BAD)
            if f.rule == "FIG004"]
    assert any("floor-divides" in m for m in msgs)


def test_real_autotune_table_passes_budget_model():
    findings = analyze_paths(
        [str(REPO / "src" / "repro" / "kernels" / "node_fused" /
             "kernel.py")], root=str(REPO))
    assert [f for f in findings if f.rule == "FIG004"] == []
