"""Quickstart: QR/PCA over a database join, without the join.

The whole FiGaRo path goes through ONE surface — `repro.figaro`
(`Session` / `JoinDataset`):

  1. ingest a small star-schema database and fix the join tree;
  2. `ds.qr()` — the paper's pipeline (counts -> heads/tails -> R0 -> TSQR),
     touching only the INPUT relations; verified against the classical
     baseline (materialize the join, Householder QR) while reading ~10x
     fewer values;
  3. `ds.pca(k=)` / `ds.lsq(label)` — downstream ML reads off the same R;
  4. batched serving: a leading batch axis answers B feature-sets in one
     compiled dispatch (sharded over a device mesh when the Session has one);
  5. `ds.append(...)` — online data refresh with ZERO retraces (capacity is
     the compile signature, live size is data);
  6. `ds.serve(kind=...)` — the standing batched serving endpoint;
  7. async serving: `server.submit(...)` -> futures, micro-batch coalescing,
     and streaming `submit` + `server.append` off one shared plan state;
  8. accelerator knobs: `Session(use_kernel=, assembly=)` — the fused
     per-node Pallas kernel and band-wise R0 assembly, numerics-preserving
     and cached per static signature;
  9. figaro-lint: `python -m repro.analysis` — the repo's own static
     analyzer machine-checks the invariants steps 1-8 rely on;
 10. figaro-san: `FIGARO_SAN=1` — runtime race/retrace/numerics detectors
     over the same serving stack;
 11. figaro-plan: `join(edges)` with no root — the cost-based optimizer
     picks the join-tree orientation, `ds.explain()` shows the ranking, and
     appends can adaptively re-root the live plan.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax
jax.config.update("jax_enable_x64", True)

from repro.compile_cache import enable_compile_cache

enable_compile_cache()

import jax.numpy as jnp
import numpy as np

from repro import figaro
from repro.core.materialize import join_output_rows, materialize_join
from repro.core.qr import materialized_qr

rng = np.random.default_rng(0)

# --- 1. ingest + join: Orders + Customers + Products + Reviews --------------
n_cust, n_prod, n_orders = 50, 30, 2000
tables = {
    "Orders": ({"cust": rng.integers(0, n_cust, n_orders),
                "prod": rng.integers(0, n_prod, n_orders)},
               rng.normal(size=(n_orders, 2)), ["amount", "qty"]),
    "Customers": ({"cust": np.arange(n_cust)},
                  rng.normal(size=(n_cust, 3)), ["age", "income", "tenure"]),
    "Products": ({"prod": np.arange(n_prod)},
                 rng.normal(size=(n_prod, 2)), ["price", "weight"]),
    # many-to-many: ~6 reviews per product -> the join blows up 6x
    "Reviews": ({"prod": rng.integers(0, n_prod, n_prod * 6)},
                rng.normal(size=(n_prod * 6, 1)), ["stars"]),
}
edges = [("Orders", "Customers"), ("Orders", "Products"),
         ("Products", "Reviews")]

# One Session = one engine + dtype/mesh/bucketing policy. headroom reserves
# row capacity per relation so streaming appends stay inside the compiled
# signature (see step 5).
sess = figaro.Session(dtype=jnp.float64, headroom=16)
ds = sess.ingest(tables).join("Orders", edges)  # fact table at the root

# --- 2. FiGaRo QR vs the classical baseline ---------------------------------
r_figaro = ds.qr()  # first compute: builds the capacity plan, compiles once

a = materialize_join(ds.tree)          # ONLY for the baseline/verification
r_baseline = materialized_qr(ds.tree)
err = np.abs(np.asarray(r_figaro) - np.asarray(r_baseline)).max() \
    / np.abs(np.asarray(r_baseline)).max()

rows_in = ds.tree.db.total_rows
rows_join = join_output_rows(ds.tree)
print(f"input rows          : {rows_in}")
print(f"join rows           : {rows_join}  ({rows_join / rows_in:.1f}x blowup)")
print(f"R shape             : {r_figaro.shape}   columns: {ds.columns[:3]}...")
print(f"max rel. difference : {err:.2e}")
assert err < 1e-10
print("OK — FiGaRo matches the materialized-join QR without building the join.")

# --- 3. downstream ML off the same R: PCA + ridge regression ----------------
pca = ds.pca(k=3)
beta, resid = ds.lsq("price", ridge=0.1)  # label column by name
ac = a - a.mean(axis=0)
ev_ref = np.sort(np.linalg.eigvalsh(ac.T @ ac / (a.shape[0] - 1)))[::-1][:3]
assert np.allclose(np.asarray(pca.explained_variance), ev_ref, rtol=1e-8)
print(f"PCA top-3 variance  : {np.asarray(pca.explained_variance).round(3)}")
print(f"ridge lsq           : beta {beta.shape}, residual {float(resid):.3f}")
print("OK — regression/PCA read off R; the join is never materialized.")

# --- 4. batched serving: one dispatch, many feature-sets --------------------
# A leading batch axis on the data switches to the batched (vmapped)
# executable; with figaro.Session(mesh=make_data_mesh()) the same call
# shards the batch over every device (one executable per plan+mesh
# signature). Requests sized to the LIVE row counts are padded to capacity
# inside the dataset.
B = 8  # e.g. 8 users' feature-set variants over the same join structure
batch = tuple(np.stack([np.asarray(d) * (1.0 + 0.01 * i) for i in range(B)])
              for d in ds.plan.data)
r_batch = ds.qr(batch)
assert r_batch.shape == (B, ds.plan.num_cols, ds.plan.num_cols)
r0_check = np.asarray(ds.qr([d[0] for d in batch]))
assert np.abs(np.asarray(r_batch[0]) - r0_check).max() < 1e-10
ds.qr(batch)  # cache hit: same signature, launch-only
st = ds.stats()
assert st["traces"]["qr_batched"] == 1
print(f"engine              : served {B} feature-sets in one dispatch, "
      f"{st['trace_count']} compilations total")
print("OK — batched serving off one cached executable.")

# --- 5. online append: capacity is the signature, live size is data ---------
# The capacity plan buckets every node's (rows, keys, parent-keys) up to
# powers of two (+ headroom) and carries a live-row mask as a pytree LEAF:
# appending rows only rewrites leaf values, so a refresh inside the buckets
# re-dispatches the cached executable with ZERO retraces. The compile count
# tracks tenant *shapes* (buckets), not databases or refreshes.
compiles = st["traces"]["qr"]
in_capacity = ds.append("Reviews", {"prod": rng.integers(0, n_prod, 5)},
                        rng.normal(size=(5, 1)))  # 5 fresh reviews
assert in_capacity, "append within headroom must keep the plan signature"
r_new = ds.qr()
st = ds.stats()
assert st["traces"]["qr"] == compiles, "append must not retrace"
r_check = materialized_qr(ds.tree)
assert np.abs(np.asarray(r_new) - np.asarray(r_check)).max() \
    / np.abs(np.asarray(r_check)).max() < 1e-10
live = st["nodes"]["Reviews"]
print(f"refresh             : +5 rows, {st['traces']['qr'] - compiles} new "
      f"compilations; Reviews live/capacity = "
      f"{live['live_rows']}/{live['capacity_rows']}")
print("OK — incremental refresh: appends are launch-only.")

# --- 6. a standing serving endpoint -----------------------------------------
server = ds.serve(kind="qr")  # also: svd / pca / lsq(label_col=...)
r_served = server(tuple(np.stack([np.asarray(d)] * 2) for d in ds.plan.data))
assert np.asarray(r_served).shape == (2, ds.plan.num_cols, ds.plan.num_cols)
print("OK — ds.serve(): batched FigaroServer with online server.append().")

# --- 7. async serving: submit -> futures -> streaming append -----------------
# The server is async-first: `submit(request)` enqueues one request (per-node
# [rows_i, n_i] leaves — or a [B, rows_i, n_i] sub-batch) and returns a
# FigaroFuture immediately. Pending requests coalesce into ONE bucketed
# micro-batch dispatch, and with queue_depth >= 2 the next batch's H2D
# staging overlaps the in-flight executable (the blocking `server(batch)`
# of step 6 is just `submit(batch).result()` over this same pipeline).
compiles_b = ds.stats()["traces"].get("qr_batched", 0)
requests = [tuple(np.asarray(d) * (1.0 + 0.1 * i) for d in ds.plan.data)
            for i in range(6)]
futures = [server.submit(r) for r in requests]          # returns immediately
answers = [np.asarray(f.result()) for f in futures]     # submission order
assert all(a.shape == (ds.plan.num_cols,) * 2 for a in answers)

# Streaming append joins the same stream: it drains in-flight requests, then
# refreshes the SHARED plan holder — ds.plan / ds.stats() and the server can
# never fork, and in-capacity refreshes keep the executable (zero retraces).
in_capacity = server.append("Reviews", ({"prod": rng.integers(0, n_prod, 3)},
                                        rng.normal(size=(3, 1))))
assert in_capacity and ds.plan is server.plan
live = tuple(rng.normal(size=(ds.stats()["nodes"][nm]["live_rows"],
                              ds.tree.db[nm].num_data_cols))
             for nm in ds.tree.preorder())
r_after = server.submit(live).result()  # live-sized request, padded inside
assert np.asarray(r_after).shape == (ds.plan.num_cols, ds.plan.num_cols)
st = ds.stats()
assert st["traces"]["qr_batched"] - compiles_b <= 2  # B=2, B=1 buckets only
print(f"async serving       : {len(requests)} futures answered, then "
      f"append+submit with {st['traces']['qr_batched'] - compiles_b} "
      f"batch-bucket compilations (streaming appends retrace nothing)")
server.close()
print("OK — async pipelined serving: submit -> futures -> streaming append.")

# --- 8. accelerator knobs: fused node kernel + band-wise R0 assembly --------
# Two per-dispatch (or per-Session) flags, both numerics-preserving:
#
#   use_kernel=True  routes each join-tree node through the fused
#       `kernels.node_fused` Pallas kernel — live-row masking, segmented
#       head/tail extraction, phi-weight scaling and slab emission in ONE
#       HBM round-trip per node instead of three-plus. On TPU/GPU it runs
#       compiled; on CPU it executes interpret=True (correct but slow — keep
#       the default XLA path for CPU serving).
#   assembly="band"  materializes R0 band-by-band from (col0, width) slab
#       metadata on the plan instead of padding every slab to full width —
#       assembly traffic drops from O(rows * N) to O(sum rows_i * width_i)
#       (`figaro.assembly_traffic` is the analytic model; BENCH_engine.json
#       tracks both wall-clock and bytes).
#
# Both flags ride the STATIC half of the dispatch signature: each (use_kernel,
# assembly) corner compiles once and repeats are launch-only, so flipping a
# corner never invalidates the others' cached executables.
r_band = ds.qr(assembly="band")  # same data as ds.qr(), band-assembled R0
assert np.abs(np.asarray(r_band) - np.asarray(ds.qr())).max() < 1e-10
from repro.core.figaro import assembly_traffic
bytes_padded = assembly_traffic(ds.plan.spec, assembly="padded")
bytes_band = assembly_traffic(ds.plan.spec, assembly="band")
print(f"band assembly       : {bytes_band / bytes_padded:.2f}x the padded "
      f"assembly bytes ({bytes_padded} -> {bytes_band})")
print("OK — Session(use_kernel=, assembly=) select the accelerated paths.")

# --- 9. running figaro-lint: the invariants above, machine-checked ----------
# Everything this example leaned on is a structural invariant nothing at
# runtime enforces: version-sensitive JAX spellings live only in
# repro/compat.py (FIG001); jit's static_argnames name real parameters
# and plans pass THROUGH jit, never closed over (FIG002 — the zero-retrace
# story of steps 4-7); core/ and kernels/ derive
# dtypes from inputs instead of hardcoding float32 (FIG003); every
# pallas_call routes interpret= through kernels/_platform.resolve_interpret
# and grids divide ceil-padded dims (FIG004 — step 8's kernels); the async
# server's shared state is written under its locks (FIG005 — step 7), read
# under them too (FIG006 — unlocked reads of shared mutable attrs are
# cross-thread escapes), and every thread/lock in src/ is constructed
# through the figaro-san wrappers so the runtime sanitizer of step 10 can
# observe it (FIG007).
#
# The analyzer is pure stdlib (no jax import), so CI runs it uninstalled:
#
#   PYTHONPATH=src python -m repro.analysis src/                  # all rules
#   PYTHONPATH=src python -m repro.analysis --baseline analysis_baseline.json src/
#   PYTHONPATH=src python -m repro.analysis --report unused       # dead code
#
# Deliberate violations carry a trailing suppression with a reason:
#
#   return jax.jit(fn)  # figaro-lint: disable=FIG002 -- plan-closed by design
#
# (`disable-file=` at any line suppresses a rule module-wide.) Anything not
# suppressed must be fixed or added to analysis_baseline.json with a
# justification — CI fails on non-baselined findings. To add a rule: drop a
# module in src/repro/analysis/rules/ subclassing `framework.Rule` (set
# rule_id/severity/fix_hint, yield findings from check(ctx)), register it in
# rules/__init__.all_rules, and give it known-bad/known-good fixtures in
# tests/test_analysis.py.
print("OK — see `python -m repro.analysis --help` for the linter surface.")

# --- 10. figaro-san: the runtime counterpart, FIGARO_SAN=1 ------------------
# figaro-lint checks what the source says; figaro-san checks what the
# process does. `FIGARO_SAN=1 python ...` (or `sanitizer.enable()`) arms
# three detectors with near-zero cost when off (the instrumentation hooks
# are physically removed from the classes on disable()):
#
#   race     lockset detection on the @shared_state classes (engine caches,
#            PlanHolder counters, server queues) + a lock-order graph that
#            flags acquisition cycles (potential deadlocks) without needing
#            the unlucky interleaving to actually hang;
#   retrace  every engine compile records its dispatch signature; after
#            `sanitizer.expect_no_retrace()` any further compile is a
#            finding naming the diverged signature component;
#   numerics sampled float64 shadow dispatches assert the f32 error against
#            the paper's database-size budget (eps * slack * Σ relation
#            rows — FiGaRo's rounding error scales with DATABASE size, not
#            join size), plus NaN/Inf tripwires on every sampled output.
from repro import sanitizer

sanitizer.enable()
np.asarray(ds.qr())  # the serving path from the steps above, sanitized
assert sanitizer.findings() == []  # nothing to report on the real stack

# A detector firing looks like this — the classic AB/BA lock inversion:
from repro.sanitizer.locks import san_lock

a, b = san_lock("demo.A"), san_lock("demo.B")
with a:
    with b:
        pass
with b:
    with a:  # reversed order: a cycle in the acquisition graph
        pass
(cycle_finding,) = sanitizer.findings("lock-order")
print("figaro-san          :", cycle_finding.message)
print(sanitizer.report().splitlines()[0])
sanitizer.reset()
sanitizer.disable()

# Adding a runtime check mirrors adding a lint rule (step 9): drop a module
# in src/repro/sanitizer/ that calls `_state.STATE.add_finding(check, msg,
# details=..., dedupe_key=...)` from its instrumentation points, wire its
# enable/reset into sanitizer.enable()/reset(), and give it a fires-on-bad /
# quiet-on-good pair in tests/test_sanitizer.py. CI runs the async serving
# suite and a multi-threaded stress test under FIGARO_SAN=1 asserting zero
# findings, so a new detector immediately guards the real serving stack.
print("OK — FIGARO_SAN=1 arms the race/retrace/numerics sanitizers.")

# --- 11. figaro-plan: cost-based join-tree choice, root="auto" --------------
# Table 2 of the paper shows the join-tree orientation changes FiGaRo's
# runtime by orders of magnitude without changing R. Leaving the root out of
# `join(...)` (or passing root="auto") hands that choice to figaro-plan
# (src/repro/planner/): it keeps EXACT per-relation statistics (row counts,
# distinct join keys, per-edge fan-outs — pure numpy, collected at ingest,
# merged incrementally on append) and scores every rooted orientation of the
# acyclic join graph with the paper's complexity model. The chosen tree is
# built through the same code path as a hand-rooted one, so when the planner
# agrees with you the compiled executable is shared: auto costs zero extra
# retraces.
traces_before = sess.engine.trace_count()
auto = sess.ingest(tables).join(edges)    # no root: the planner picks one
print(auto.explain())                     # ranked orientations + breakdown
assert auto.tree.root == "Orders"         # recovers the step-1 hand choice
np.asarray(auto.qr())
assert sess.engine.trace_count() == traces_before, "auto reused the plan"

# Auto-rooted datasets re-plan adaptively: every append folds the new keys
# into the statistics, and when growth makes another orientation cheaper by
# more than the hysteresis margin — `join(edges, reroot=True,
# hysteresis=0.5)` are the knobs — the dataset rebuilds on the better root
# at a drain point (in-flight server futures still answer on the old plan;
# re-read `ds.columns` afterwards, the column order follows the live tree).
# This star schema keeps its fact table cheapest, so appends never flip it:
auto.append("Orders", {"cust": np.array([0, 1]), "prod": np.array([2, 3])},
            rng.normal(size=(2, 2)))
st = auto.stats()
assert (st["auto_root"], st["reroots"]) == (True, 0)
print(f"after append        : root={st['root']} (re-roots: {st['reroots']}, "
      f"appended rows: {st['append_volume']})")
print("OK — figaro-plan picks the orientation; appends keep it honest.")

# --- 12. figaro-flow: interprocedural analysis + writing a rule on it -------
# Steps 9's rules are per-file; the invariants they can't see are the ones
# that live BETWEEN files: a helper three modules away from the jit boundary
# calling np.asarray on a traced array (FIG009), a traced function bumping a
# module counter once per trace instead of once per call (FIG010), a buffer
# re-read after the engine's donated dispatch consumed it (FIG011), and the
# R0 slab-layout arithmetic drifting between join_tree/plan_cache/PlanSpec
# (FIG012). figaro-flow (repro.analysis.callgraph + .dataflow, still pure
# stdlib) powers them: it builds a whole-program call graph, marks every
# function transitively reachable from an engine `_<kind>_impl`, a
# `jax.jit`/`pallas_call` argument or a `shard_map` body as *traced-context*,
# and runs a per-function taint fixpoint (params -> returns/effects, with
# static/kwonly params, closure constants and .shape/.dtype metadata held
# concrete) over that graph. Inspect the classification directly:
#
#   PYTHONPATH=src python -m repro.analysis --report callgraph src/
#   PYTHONPATH=src python -m repro.analysis --report callgraph --dot flow.dot src/
#   PYTHONPATH=src python -m repro.analysis --report callgraph --json src/
#
# Writing an interprocedural rule: subclass `framework.Rule` as in step 9,
# but implement `check_program(self, program)` instead of (or on top of)
# `check(ctx)`. The driver calls it once per run with the whole-program
# view; `program.graph.traced` is the traced-context set with root chains,
# `program.dataflow().sinks` the taint fixpoint's host-sync sites, and
# `program.traced_chain(qname)` the root->function attribution a finding
# should carry via `self.finding(..., traced_context=chain)` — it lands in
# `--json` as `traced_context` so tooling can jump the whole chain. Per-file
# rules get the same power through `self.program` (FIG006 uses it to verify
# a "private helper" really has no cross-module callers before exempting
# it). The program below shows the classification on a miniature engine:
from repro.analysis import analyze_source, all_rules
from repro.analysis.callgraph import Program
from repro.analysis.framework import FileContext
import ast as _ast
import textwrap as _tw

_MINI = _tw.dedent("""
    import jax
    import numpy as np

    @jax.jit
    def entry(x):
        return helper(x)

    def helper(a):
        return np.asarray(a)      # host sync, two hops from the jit
""")
ctx = FileContext("src/repro/core/mini.py", _MINI, _ast.parse(_MINI))
flow = Program([ctx])
assert "repro.core.mini:helper" in flow.graph.traced
hits = [f for f in analyze_source(_MINI, "src/repro/core/mini.py",
                                  all_rules()) if f.rule == "FIG009"]
assert hits and hits[0].traced_context == ("entry", "helper")
print(f"figaro-flow: {len(flow.graph.functions)} fn(s), "
      f"{len(flow.graph.traced)} traced; FIG009 chain "
      f"{' -> '.join(hits[0].traced_context)}")
print("OK — figaro-flow classifies the jit frontier; rules query it.")
