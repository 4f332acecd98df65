"""figaro-lint: repo-specific static analysis for the invariants the paper
reproduction lives or dies by.

The headline numerical claim — rounding errors tracking *database* size
rather than join size — and the headline performance claims — zero-retrace
plan refreshes, one executable per static signature — are invariants of the
implementation, not of any one function. Three of the last four PRs fixed
hand-found violations of exactly these invariants (float32 count overflow
past 2^24, a hardcoded-f32 kernel accumulator, a dtype-dropping
normalize_sign). This package encodes them as AST-based rules so CI catches
the next violation before a human has to:

  FIG001  compat-pin        version-sensitive JAX symbols (shard_map,
                            make_mesh, AxisType, AbstractMesh, axis_size)
                            imported anywhere outside repro/compat.py
  FIG002  retrace-hazard    static_argnames naming non-parameters or
                            unhashable defaults, jitted closures capturing
                            plan objects
  FIG003  dtype-drift       hardcoded narrowing dtype literals in core/ and
                            kernels/ bodies (the I/O-dtype policy derives
                            from inputs), count accumulation narrower
                            than f64
  FIG004  pallas-kernel     pallas_call sites not routing interpret=
                            through kernels/_platform.resolve_interpret,
                            grids that floor-divide unpadded dims,
                            AUTOTUNE block sizes past the VMEM budget model
  FIG005  lock-discipline   mutable attributes of lock-owning classes
                            (AsyncFigaroServer, PlanHolder, FigaroEngine)
                            written outside a `with self._lock` region
  FIG006  thread-escape     shared mutable state read/mutated without the
                            owning lock from thread-reachable methods
  FIG007  san-routing       sanitizer findings bypassing the SanitizerState
                            registry/reporting chain
  FIG008  jaxfree-planner   jax imports leaking into the planner/analysis
                            layers that must stay stdlib-only
  FIG009  host-sync         np.asarray/float()/.item()/.tolist()/
                            .block_until_ready()/jax.device_get on a traced
                            value transitively reachable from a jit region
                            (figaro-flow: call graph + dataflow fixpoint)
  FIG010  trace-effects     self./global/closure writes, print, counter
                            bumps inside traced-context functions (lock-
                            guarded trace bookkeeping exempted)
  FIG011  donation          a buffer re-read after passing through the
                            engine's donated data position (straight-line
                            or loop re-dispatch)
  FIG012  slab-layout       symbolic proofs over PlanSpec/bucket_spec/
                            SlabBand arithmetic: row bands partition
                            capacity rows, column prefix sums close, pow2
                            bucketing stays canonical and total

FIG009–FIG011 ride on **figaro-flow** (`repro.analysis.callgraph` +
`repro.analysis.dataflow`): a whole-program call graph with jit-region
inference (engine `_<kind>_impl` bodies, `jax.jit`/`pallas_call` arguments,
`shard_map` bodies, transitively) and a per-function traced/concrete/host
taint summary composed to a fixpoint. Inspect the classification with

    python -m repro.analysis --report callgraph [--dot graph.dot] src/

Pure stdlib `ast` — no third-party imports, so the CLI runs in CI without
installing jax.  Run it:

    python -m repro.analysis [--baseline analysis_baseline.json] src/

Suppress a deliberate violation on its own line, with a reason:

    return jax.jit(fn)  # figaro-lint: disable=FIG002 -- plan-closed on purpose

or file-wide near the top of the module:

    # figaro-lint: disable-file=FIG003 -- f32 accumulate is the flash standard

See `repro.analysis.framework` for the rule API and `examples/quickstart.py`
section 9 for a walkthrough.
"""

from .baseline import Baseline, load_baseline  # noqa: F401
from .callgraph import CallGraph, Program  # noqa: F401
from .dataflow import Dataflow  # noqa: F401
from .framework import (Finding, Rule, Severity, analyze_paths,  # noqa: F401
                        analyze_source, load_program)
from .imports import ImportGraph, unused_report  # noqa: F401
from .rules import all_rules  # noqa: F401

__all__ = ["Finding", "Rule", "Severity", "analyze_paths", "analyze_source",
           "all_rules", "Baseline", "load_baseline", "ImportGraph",
           "unused_report", "CallGraph", "Program", "Dataflow",
           "load_program"]
