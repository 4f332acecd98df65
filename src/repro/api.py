"""One façade for the join-factorization stack: `Session` / `JoinDataset`.

FiGaRo is one capability — QR/SVD/PCA/least-squares over a join without
materializing it — and this module is its one user-facing surface (exported
as ``repro.figaro``). A `Session` owns the compute configuration (engine,
dtype policy, mesh/sharding, bucketing defaults); a `JoinDataset` owns one
join's **plan lifecycle** (lazy capacity-plan build, online appends, stats)
and exposes the fluent compute methods::

    from repro import figaro

    sess = figaro.Session(mesh=mesh, headroom=64)     # compute config, once
    ds = sess.ingest(tables).join("Orders", edges)    # -> JoinDataset
    r = ds.qr()                                       # compiles lazily
    pca = ds.pca(k=3)
    beta, resid = ds.lsq("price", ridge=0.1)          # label by column name
    ds.append("Reviews", {"prod": keys}, rows)        # zero-retrace append
    ds.qr()                                           # launch-only
    server = ds.serve(kind="qr")                      # async pipelined server
    fut = server.submit(request)                      # -> FigaroFuture
    r = fut.result()                                  # submission-order answer

Everything underneath — `FigaroEngine` executable caching, plan-as-pytree
jit, `plan_cache` bucketing/refreshes, `shard_map` serving — is the machinery
of PRs 1-3; this module only decides *when* each piece runs.

Migration table (old call -> new call)
--------------------------------------

===================================================  ==========================================
legacy entry point                                   Session / JoinDataset
===================================================  ==========================================
``Database.from_arrays(t)`` + ``full_reduce``        ``sess.ingest(t).join(root, edges)``
  + ``JoinTree.from_edges`` + ``build_plan``
``join(root, edges)`` (hand-picked root)             ``join(edges, root="auto")`` (figaro-plan)
``figaro_qr(plan, dtype=...)``                       ``ds.qr(dtype=...)``
``figaro_qr_batched(plan, batch)``                   ``ds.qr(batch)`` (leading batch axis)
``svd_over_join(plan)``                              ``ds.svd()``
``pca_over_join(plan, k)``                           ``ds.pca(k=k)``
``least_squares_over_join(plan, label_col=j)``       ``ds.lsq(j)`` / ``ds.lsq("col_name")``
``build_capacity_plan(tree, headroom=h)``            ``Session(headroom=h).from_tree(tree)``
``refresh_plan(plan, {n: (keys, rows)})``            ``ds.append(n, keys, rows)``
``engine.qr(plan, b, batched=True, shard=mesh)``     ``Session(mesh=mesh)`` ... ``ds.qr(b)``
``make_figaro_server(plan, kind=..., mesh=...)``     ``ds.serve(kind=...)``
``server(batch)`` (blocking one-shot)                ``server.submit(...)`` -> `FigaroFuture`
``default_engine()``                                 ``default_session().engine``
===================================================  ==========================================

(``server(batch)`` still works — it is now ``submit(batch).result()`` over
the same async pipeline; prefer ``submit`` to let requests coalesce and
overlap, and use ``server.append(...)`` / ``ds.append(...)``
interchangeably — dataset and server share one plan holder.)

The legacy entry points still work — they are thin delegations onto the
module-level `default_session()` — but new code should start here: future
capabilities (delta-aware counts, randomized sketching front-ends, TPU
kernels) land as Session options and JoinDataset methods, the way async
serving (`train.async_serve`) landed behind ``ds.serve()``.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from repro.core.counts import (check_counts_exact, compute_counts_reference,
                               largest_count)
from repro.core.engine import FigaroEngine, default_engine, plan_for
from repro.core.figaro import r0_nonzero_rows_bound
from repro.core.join_tree import FigaroPlan, JoinTree, build_plan
from repro.core.plan_cache import (PlanHolder, _append_rows, bucket_spec,
                                   build_capacity_plan, pad_data, pad_plan,
                                   spec_fits)
from repro.core.relation import Database, full_reduce
from repro.planner import (DatabaseStats, Replanner, choose_root,
                           explain_text, rank_orientations, validate_names)
from repro.planner.stats import normalize_edges
from repro.train.async_serve import SERVE_KINDS, validate_serve_kind

__all__ = ["Session", "TableSet", "JoinDataset", "default_session",
           "SERVE_KINDS"]

_UNSET = object()

# Per-kind dtype defaults when the session does not pin one — identical to
# the legacy module-level entry points (QR serves in float32 by default; the
# spectral/regression reads default to float64 like the paper's evaluation).
_KIND_DTYPES = {
    "r0": jnp.float32,
    "qr": jnp.float32,
    "svd": jnp.float64,
    "pca": jnp.float64,
    "least_squares": jnp.float64,
}

# serve() kind -> engine pipeline kind (for dtype policy resolution). The
# kind *list* itself is `SERVE_KINDS` (re-exported from
# `repro.train.async_serve` — one source of truth, one eager validator,
# shared with `make_figaro_server`).
_SERVE_ENGINE_KINDS = {"qr": "qr", "svd": "svd", "pca": "pca",
                       "lsq": "least_squares"}
assert tuple(_SERVE_ENGINE_KINDS) == SERVE_KINDS


class Session:
    """Owns the compute configuration of the join-factorization stack.

    One `Session` = one engine (executable cache + trace/eviction counters),
    one dtype policy, one mesh/sharding choice, and one bucketing default.
    Datasets made from it (`ingest(...).join(...)` / `from_tree(...)`)
    inherit that configuration; per-call keyword overrides always win.

    Parameters
    ----------
    engine:      a `FigaroEngine` to share (default: a fresh engine built
                 from ``donate_data`` / ``max_cached``). Sharing one engine
                 across sessions shares its executable cache.
    mesh:        a `jax.sharding.Mesh`; batched dispatches shard their
                 request-batch axis over ``mesh[shard_axis]`` (one executable
                 per (plan signature, mesh signature) answers the global
                 batch). ``None`` = single-device dispatch.
    dtype:       pin every pipeline to one dtype; ``None`` (default) keeps
                 the per-kind legacy defaults (qr/r0: float32, svd/pca/lsq:
                 float64).
    bucket:      ``True`` (default): datasets build **bucketed** capacity
                 plans (power-of-two node sizes) and ad-hoc plans are padded
                 into their buckets at dispatch, so near-miss shapes share
                 one executable. ``False``: capacities equal the exact live
                 sizes — bit-identical to the pre-Session exact path, but
                 every append regrows the plan (one retrace each).
    headroom:    extra row capacity per node reserved at plan build, so a
                 known append rate cannot immediately overflow a bucket.
    leaf_rows, use_kernel, assembly:
                 pipeline defaults forwarded to every dispatch:
                 ``leaf_rows`` is the row count of post-processing's TSQR
                 leaves (`repro.core.postprocess`), ``use_kernel=True``
                 routes each join-tree node through the fused Pallas pass
                 (`repro.kernels.node_fused`; compiled on TPU/GPU,
                 interpreted on CPU), ``assembly`` ("padded" |
                 "band") picks the R₀ materialization (`repro.core.figaro`).
                 All are static options — part of the executable cache key.
    donate_data, max_cached:
                 forwarded to the engine constructor; combining either with
                 ``engine=`` raises (configure the engine directly instead).
                 Sessions default to non-donating engines (safe for repeated
                 dispatch of the same buffers); ``max_cached`` bounds the
                 per-kind executable cache (LRU, evictions counted).

    Capacity vs live size (the contract `JoinDataset` operates under)
    -----------------------------------------------------------------
    **Capacity** is static: each node's bucketed ``(rows, keys,
    parent-keys)`` plus the R₀ row layout are part of the plan's treedef and
    are baked into the compiled executable. **Live size** is dynamic: the
    live-row mask and the zeroed dead ``group_count`` slots are pytree
    *leaves*, so they change per dispatch without retracing. Dead rows carry
    Givens weight 0 and emit exactly-zero R₀ rows — a capacity plan computes
    exactly what the underlying exact plan computes.

    Compile-count contract
    ----------------------
    One compilation per (pipeline kind, plan signature, mesh signature,
    static options). ``ds.append(...)`` that stays within capacity keeps the
    signature — the next dispatch is launch-only, **zero retraces**
    (`ds.stats()` exposes the engine's per-kind trace counters so callers
    can assert this instead of guessing). An append that overflows a bucket
    regrows the capacities: exactly one retrace on the next dispatch, and
    ``ds.stats()["regrows"]`` counts it. With ``max_cached=``, evicted
    signatures recompile on next use (counted by both counters).
    """

    def __init__(self, *, engine: FigaroEngine | None = None, mesh=None,
                 shard_axis: str = "data", dtype=None, bucket: bool = True,
                 headroom: int = 0, leaf_rows: int = 256,
                 use_kernel: bool = False, assembly: str = "padded",
                 donate_data: bool | None = None,
                 max_cached: int | None = None):
        if engine is not None and (max_cached is not None
                                   or donate_data is not None):
            raise ValueError("pass max_cached=/donate_data= to the engine's "
                             "constructor when supplying engine=")
        self.engine = engine if engine is not None else FigaroEngine(
            donate_data=bool(donate_data), max_cached=max_cached)
        self.mesh = mesh
        self.shard_axis = shard_axis
        self.dtype = dtype
        self.bucket = bucket
        self.headroom = headroom
        self.leaf_rows = leaf_rows
        self.use_kernel = use_kernel
        self.assembly = assembly

    # -- dataset construction ------------------------------------------------

    def ingest(self, tables) -> "TableSet":
        """Wrap raw tables for the fluent chain: ``ingest(t).join(root, e)``.

        ``tables`` is either a ready `Database` or the
        ``{name: (key_columns, data_matrix, column_names)}`` mapping of
        `Database.from_arrays`.
        """
        if isinstance(tables, Database):
            return TableSet(self, tables)
        if isinstance(tables, dict):
            return TableSet(self, Database.from_arrays(tables))
        raise TypeError(
            f"ingest() expects a Database or a {{name: (keys, data, cols)}} "
            f"dict, got {type(tables).__name__}")

    def from_tree(self, tree: JoinTree) -> "JoinDataset":
        """A `JoinDataset` over an existing `JoinTree`."""
        if not isinstance(tree, JoinTree):
            raise TypeError(f"from_tree() expects a JoinTree, "
                            f"got {type(tree).__name__}")
        return JoinDataset(self, tree)

    # -- option resolution ---------------------------------------------------

    def _dtype_for(self, kind: str, override):
        if override is not None:
            return override
        if self.dtype is not None:
            return self.dtype
        return _KIND_DTYPES[kind]

    def _post_opts(self, kind: str, dtype, leaf_rows, use_kernel,
                   assembly) -> dict:
        return dict(
            dtype=self._dtype_for(kind, dtype),
            leaf_rows=self.leaf_rows if leaf_rows is None else leaf_rows,
            use_kernel=self.use_kernel if use_kernel is None else use_kernel,
            assembly=self.assembly if assembly is None else assembly)

    @staticmethod
    def _is_batched(data, batched) -> bool:
        """A leading batch axis ([B, m_i, n_i] leaves) switches to the
        batched (vmapped) dispatch; per-node plan data is always 2-D."""
        if batched is not None:
            return batched
        if data is None:
            return False
        leaves = list(data)
        return bool(leaves) and np.ndim(leaves[0]) == 3

    def _shard_for(self, batched: bool):
        if not batched or self.mesh is None:
            return None
        return (self.mesh, self.shard_axis)

    def _dispatch_opts(self, data, batched, shard, bucket):
        batched = self._is_batched(data, batched)
        return dict(
            batched=batched,
            shard=self._shard_for(batched) if shard is _UNSET else shard,
            bucket=self.bucket if bucket is None else bucket)

    # -- plan-level compute (the legacy delegation surface) ------------------

    def r0(self, tree_or_plan, data=None, *, batched=None, shard=_UNSET,
           bucket=None, dtype=None, use_kernel=None, assembly=None):
        """R₀ of Algorithm 2 under this session's configuration."""
        return self.engine.r0(
            plan_for(tree_or_plan), data,
            dtype=self._dtype_for("r0", dtype),
            use_kernel=self.use_kernel if use_kernel is None else use_kernel,
            assembly=self.assembly if assembly is None else assembly,
            **self._dispatch_opts(data, batched, shard, bucket))

    def qr(self, tree_or_plan, data=None, *, batched=None, shard=_UNSET,
           bucket=None, dtype=None, leaf_rows=None, use_kernel=None,
           assembly=None):
        """Upper-triangular R of the join's QR ([B, N, N] when batched)."""
        return self.engine.qr(
            plan_for(tree_or_plan), data,
            **self._post_opts("qr", dtype, leaf_rows, use_kernel, assembly),
            **self._dispatch_opts(data, batched, shard, bucket))

    def svd(self, tree_or_plan, data=None, *, k: int | None = None,
            batched=None, shard=_UNSET, bucket=None, dtype=None,
            leaf_rows=None, use_kernel=None, assembly=None):
        """Singular values + right-singular vectors; ``k`` keeps the top-k."""
        s, vt = self.engine.svd(
            plan_for(tree_or_plan), data,
            **self._post_opts("svd", dtype, leaf_rows, use_kernel, assembly),
            **self._dispatch_opts(data, batched, shard, bucket))
        if k is not None:
            s, vt = s[..., :k], vt[..., :k, :]
        return s, vt

    def pca(self, tree_or_plan, data=None, *, k: int | None = None,
            center: bool = True, batched=None, shard=_UNSET, bucket=None,
            dtype=None, leaf_rows=None, use_kernel=None, assembly=None):
        """PCA of the join matrix from R (+ factorized means)."""
        return self.engine.pca(
            plan_for(tree_or_plan), data, k=k, center=center,
            **self._post_opts("pca", dtype, leaf_rows, use_kernel, assembly),
            **self._dispatch_opts(data, batched, shard, bucket))

    def least_squares(self, tree_or_plan, label_col: int, data=None, *,
                      ridge: float = 0.0, batched=None, shard=_UNSET,
                      bucket=None, dtype=None, leaf_rows=None,
                      use_kernel=None, assembly=None):
        """argmin_β ‖A[:, feats]·β − A[:, label]‖² over the join."""
        return self.engine.least_squares(
            plan_for(tree_or_plan), label_col, data, ridge=ridge,
            **self._post_opts("least_squares", dtype, leaf_rows, use_kernel,
                              assembly),
            **self._dispatch_opts(data, batched, shard, bucket))

    def serve(self, tree_or_plan, *, kind: str = "qr", label_col=None,
              k=None, ridge: float = 0.0, dtype=None, leaf_rows=None,
              use_kernel=None, assembly=None, mesh=_UNSET, shard_axis=None,
              max_batch: int = 32, queue_depth: int = 2):
        """An async pipelined serving endpoint for one join structure (see
        `train.serve.make_figaro_server`): ``submit(request)`` returns a
        `FigaroFuture`, pending requests coalesce up to ``max_batch`` rows,
        and ``queue_depth`` batches pipeline through the engine (depth >= 2
        overlaps the next batch's H2D staging with the in-flight dispatch).
        Engine/mesh/dtype default to this session's configuration.
        ``tree_or_plan`` may also be a `plan_cache.PlanHolder` to share plan
        state (what `JoinDataset.serve` passes)."""
        from repro.train.serve import make_figaro_server

        validate_serve_kind(kind)
        target = tree_or_plan if isinstance(tree_or_plan, PlanHolder) \
            else plan_for(tree_or_plan)
        return make_figaro_server(
            target, kind=kind, label_col=label_col, k=k,
            ridge=ridge, engine=self.engine,
            dtype=self._dtype_for(_SERVE_ENGINE_KINDS[kind], dtype),
            leaf_rows=self.leaf_rows if leaf_rows is None else leaf_rows,
            use_kernel=self.use_kernel if use_kernel is None else use_kernel,
            assembly=self.assembly if assembly is None else assembly,
            mesh=self.mesh if mesh is _UNSET else mesh,
            shard_axis=self.shard_axis if shard_axis is None else shard_axis,
            max_batch=max_batch, queue_depth=queue_depth)

    def partitioned_qr(self, tree: JoinTree, num_parts: int, *, mesh=_UNSET,
                       dtype=None, use_kernel=None, assembly=None):
        """Fact-partitioned multi-device QR (`distributed` layer) through
        this session's engine/mesh."""
        from repro.core.distributed import partitioned_figaro_qr

        return partitioned_figaro_qr(
            tree, num_parts, engine=self.engine,
            mesh=self.mesh if mesh is _UNSET else mesh,
            dtype=(dtype if dtype is not None else
                   self.dtype if self.dtype is not None else jnp.float64),
            use_kernel=self.use_kernel if use_kernel is None else use_kernel,
            assembly=self.assembly if assembly is None else assembly)


@dataclasses.dataclass
class TableSet:
    """Ingested tables awaiting a join choice: ``ingest(t).join(edges)``."""

    session: Session
    db: Database

    def join(self, *args, root: str | None = None, edges=None,
             reduce: bool = True, reroot: bool | None = None,
             hysteresis: float = 0.5) -> "JoinDataset":
        """Fix the join tree over ``edges`` (undirected pairs, any
        orientation) and return a `JoinDataset`.

        Accepted call shapes::

            join(edges)                    # root="auto": figaro-plan picks it
            join(edges, root="auto")       # same, explicit
            join(edges, root="Orders")     # hand-rooted
            join("Orders", edges)          # legacy positional order

        With ``root="auto"`` (or omitted) the planner
        (`repro.planner.choose_root`) enumerates every rooted orientation of
        the acyclic join graph and picks the cheapest under the paper's cost
        model; ``ds.explain()`` shows the ranking. The chosen tree is built
        through the same `JoinTree.from_edges` as a hand-rooted join, so when
        the planner picks the root you would have picked, the plan signature
        — and therefore the compiled executable — is identical: auto costs
        zero extra retraces.

        ``reroot`` enables adaptive re-rooting (defaults to on iff the root
        was auto-chosen): appends update the planner's exact statistics, and
        when growth makes another orientation cheaper by more than the
        ``hysteresis`` margin the dataset rebuilds on it at the next drain
        point (in-flight server futures still answer on the old plan).

        ``reduce`` drops dangling tuples first (`full_reduce`), which the
        FiGaRo pipeline requires of its inputs. Unknown relation names in
        ``root``/``edges`` raise `ValueError` here, eagerly, listing the
        ingested relations.
        """
        if len(args) == 2:  # legacy: join(root, edges)
            pos_root, pos_edges = args
        elif len(args) == 1:
            # join(edges) or join(edges, root=...) — a lone str is a root
            # (legacy partial form join("Orders", edges=...)).
            pos_root, pos_edges = (args[0], None) \
                if isinstance(args[0], str) else (None, args[0])
        elif len(args) == 0:
            pos_root, pos_edges = None, None
        else:
            raise TypeError(f"join() takes at most 2 positional arguments "
                            f"(root, edges), got {len(args)}")
        if pos_root is not None and root is not None:
            raise TypeError("join() got multiple values for 'root'")
        if pos_edges is not None and edges is not None:
            raise TypeError("join() got multiple values for 'edges'")
        root = pos_root if root is None else root
        edges = pos_edges if edges is None else edges
        if edges is None:
            raise TypeError("join() is missing 'edges'")
        edges = [tuple(e) for e in edges]
        auto = root is None or (root == "auto"
                                and "auto" not in self.db.relations)
        validate_names(self.db.names, edges, None if auto else root)
        db = full_reduce(self.db, edges) if reduce else self.db
        if auto:
            root = choose_root(db, edges)
        return JoinDataset(self.session, JoinTree.from_edges(db, root, edges),
                           edges=edges, auto=auto,
                           reroot=auto if reroot is None else reroot,
                           hysteresis=hysteresis)


class JoinDataset:
    """One join's plan lifecycle + fluent compute handle.

    The capacity plan is built lazily on first compute
    (`plan_cache.build_capacity_plan` under the session's
    ``bucket``/``headroom`` policy) and refreshed in place by
    ``append(...)`` (`plan_cache.refresh_plan`): appends that stay within
    the bucketed capacities keep the plan signature, so the next dispatch
    reuses the cached executable with **zero retraces** — ``stats()``
    surfaces the trace/eviction counters and per-node capacity vs live rows
    so callers can assert that instead of guessing.

    Compute methods (``qr`` / ``svd`` / ``pca`` / ``lsq`` and raw ``r0``)
    read everything off the factorized R. Passing ``data`` overrides the
    ingested tables' values: 2-D per-node leaves dispatch a single pipeline;
    a leading batch axis ([B, rows_i, n_i]) switches to the batched
    (vmapped) dispatch — sharded over the session's mesh when it has one.
    Request leaves sized to the *live* row counts are zero-padded up to
    capacity here; any other row count raises (a stale batch built before an
    ``append`` must be rebuilt, not silently zero-filled).
    """

    def __init__(self, session: Session, tree: JoinTree, *, edges=None,
                 auto: bool = False, reroot: bool = False,
                 hysteresis: float = 0.5):
        self._session = session
        self._tree = tree  # pre-plan only; once built, holder.plan owns it
        # The holder is the ONE plan state for this join: servers spawned by
        # `serve()` share it, so an append through either surface (dataset or
        # server) is visible to both — no silent plan fork.
        self._holder = PlanHolder(
            on_regrow=None if session.bucket else self._exact_regrow)
        # figaro-plan state: the undirected edge set (so every orientation
        # stays reachable), whether the root was auto-chosen, the adaptive
        # re-rooting policy, and warm capacity plans per alternative root.
        self._edges = normalize_edges(edges if edges is not None
                                      else tree.edges())
        self._auto = auto
        self._reroot_enabled = reroot
        self._hysteresis = hysteresis
        self._replanner: Replanner | None = None
        self._warm_plans: dict[str, FigaroPlan] = {}
        self._counted: tuple[FigaroPlan, dict] | None = None

    # -- plan lifecycle ------------------------------------------------------

    @property
    def tree(self) -> JoinTree:
        plan = self._holder.plan
        return plan.source_tree if plan is not None else self._tree

    @property
    def plan(self) -> FigaroPlan:
        """The capacity plan (built lazily on first access; shared — through
        a `plan_cache.PlanHolder` — with every server from `serve()`)."""
        plan = self._holder.plan
        if plan is None:
            if self._auto and self._holder.counters()[0] > 0:
                # Pre-plan appends may have shifted the ranking; nothing is
                # built yet, so re-choosing the root is free.
                best = choose_root(self._tree.db, self._edges)
                if best != self._tree.root:
                    self._tree = JoinTree.from_edges(
                        self._tree.db, best, list(self._edges))
            if self._session.bucket:
                plan = build_capacity_plan(
                    self._tree, headroom=self._session.headroom)
            else:
                plan = self._exact_capacity_plan(self._tree)
            self._holder.set(plan)
            if self._auto and self._session.bucket:
                self._warm_runner_up()
        return plan

    def _warm_runner_up(self) -> None:
        # Keep the second-cheapest orientation's capacity plan warm: pure
        # numpy ingest + bucketing, no compile — if appends later flip the
        # ranking, the re-root re-pads into this spec (when it still fits)
        # instead of re-deriving capacities from scratch.
        tree = self.tree
        ranking = rank_orientations(tree.db, self._edges)
        if len(ranking) < 2:
            return
        runner_up = ranking[1].root
        self._warm_plans[runner_up] = build_capacity_plan(
            JoinTree.from_edges(tree.db, runner_up, list(self._edges)),
            headroom=self._session.headroom)

    def _exact_capacity_plan(self, tree: JoinTree) -> FigaroPlan:
        # Exact capacities: bit-identical numerics to the exact plan, but
        # any append overflows and regrows (one retrace each).
        exact = build_plan(tree)
        plan = pad_plan(exact, exact.spec)
        plan.source_tree = tree
        plan.capacity_headroom = self._session.headroom
        return plan

    def _exact_regrow(self, new_plan: FigaroPlan) -> FigaroPlan:
        # Keep the session's bucket=False contract on regrow: refresh_plan
        # grows into power-of-two buckets, but this dataset's capacities must
        # stay exact (bit-identical path, one retrace per append).
        return self._exact_capacity_plan(new_plan.source_tree)

    def append(self, node: str, keys, rows) -> bool:
        """Append rows to one relation; returns True when the refresh stayed
        within the plan's capacities (next dispatch is launch-only).

        ``keys`` maps key-attribute name -> integer array, ``rows`` is a
        [rows, n_i] data matrix — the `plan_cache.refresh_plan` convention.
        Before the first compute the tables are simply grown (the capacity
        plan has not been built yet, so there is nothing to refresh). Once
        servers exist, the refresh first drains their in-flight work, and
        they serve the refreshed plan from the next dispatch on.

        With adaptive re-rooting on (``join(..., root="auto")``), each append
        also updates the planner's exact statistics; when growth makes a
        different orientation cheaper past the hysteresis margin, the dataset
        rebuilds on it right here — at a drain point, so requests already
        submitted to a live server are still answered on the old plan — and
        returns False (the new orientation's first dispatch compiles). Column
        layout follows the live tree: re-read ``ds.columns`` after appends
        rather than caching it.
        """
        if self._holder.plan is None:
            rels = dict(self._tree.db.relations)
            if node not in rels:
                raise KeyError(f"unknown relation {node!r}; "
                               f"have {sorted(rels)}")
            rels[node] = _append_rows(rels[node], keys, rows)
            self._tree = JoinTree(Database(rels), dict(self._tree.parent))
            self._holder.note_external_append(
                node, rows=int(np.atleast_2d(np.asarray(rows)).shape[0]))
            return True
        in_capacity = self._holder.refresh({node: (keys, rows)})
        if self._reroot_enabled:
            if self._replanner is None:
                # First post-plan append: collect stats now (they already
                # include the rows this refresh just ingested).
                self._replanner = self._make_replanner()
            else:
                self._replanner.note_append(node, self._key_rows(node, keys))
            proposal = self._replanner.proposal()
            if proposal is not None:
                self._reroot_to(proposal)
                in_capacity = False  # new orientation => new signature
        return in_capacity

    # -- figaro-plan: explain + adaptive re-rooting --------------------------

    def explain(self) -> str:
        """Human-readable ranking of every join-tree orientation under the
        planner's cost model (`repro.planner`), cheapest first, with the
        winner's per-node breakdown. ``*`` marks the planner's current pick,
        ``=`` the orientation this dataset is actually running — they can
        differ between an append that shifts the estimates and the re-root
        that follows (or permanently, for a hand-rooted join)."""
        rp = self._replanner
        ranking = rp.ranking() if rp is not None else \
            rank_orientations(self.tree.db, self._edges)
        return explain_text(ranking, chosen=ranking[0].root,
                            current=self.tree.root)

    def _key_rows(self, node: str, keys) -> np.ndarray:
        attrs = self.tree.db[node].key_attrs
        cols = [np.atleast_1d(np.asarray(keys[a], dtype=np.int64))
                for a in attrs]
        return np.stack(cols, axis=1) if cols else \
            np.zeros((1, 0), dtype=np.int64)

    def _make_replanner(self) -> Replanner:
        tree = self.tree
        return Replanner(
            stats=DatabaseStats.collect(tree.db, self._edges),
            names=tuple(tree.db.names), edges=self._edges,
            current_root=tree.root, hysteresis=self._hysteresis)

    def _reroot_to(self, root: str) -> None:
        """Rebuild the capacity plan on a new orientation and swap it in at a
        drain point (`PlanHolder.replace`). The displaced orientation's plan
        becomes the new warm alternative."""
        old = self._holder.plan
        tree = JoinTree.from_edges(old.source_tree.db, root,
                                   list(self._edges))
        if self._session.bucket:
            exact = build_plan(tree)
            warm = self._warm_plans.pop(root, None)
            cap = warm.spec if warm is not None \
                and spec_fits(exact.spec, warm.spec) \
                else bucket_spec(exact.spec, headroom=self._session.headroom)
            plan = pad_plan(exact, cap)
            plan.source_tree = tree
            plan.capacity_headroom = self._session.headroom
        else:
            plan = self._exact_capacity_plan(tree)
        self._holder.replace(plan)
        self._warm_plans[old.source_tree.root] = old
        if self._replanner is not None:
            self._replanner.on_reroot(root)

    def _join_counts(self, plan: FigaroPlan) -> dict:
        """The host's exact counts of ``plan`` (kept until the plan
        changes): join size, R₀ rows and the largest per-key count."""
        if self._counted is None or self._counted[0] is not plan:
            counts = compute_counts_reference(plan)
            self._counted = (plan, {
                "join_rows": int(counts[plan.spec.root]["full"].sum()),
                "r0_rows": plan.spec.r0_rows,
                "r0_nonzero_rows_bound": r0_nonzero_rows_bound(plan),
                "largest_count": largest_count(counts)})
        return self._counted[1]

    def _check_counts(self, kind: str, dtype) -> FigaroPlan:
        """The plan, once its counts are known to be exact in the dtype
        ``kind`` runs in (`counts.check_counts_exact`)."""
        plan = self.plan
        check_counts_exact(self._join_counts(plan)["largest_count"],
                           self._session._dtype_for(kind, dtype))
        return plan

    def stats(self) -> dict:
        """Lifecycle + compile counters: per-node capacity vs live rows,
        appends/regrows, and the session engine's per-kind trace counts,
        eviction counts, and cache size. A zero-retrace append shows up as
        ``traces`` staying flat across dispatches. Appends made through a
        live server (``server.append``) are counted here too — the dataset
        and its servers share one plan holder.

        Once the plan is built, what the join adds: ``join_rows`` (the exact
        join size, counted on the host in int64), ``r0_rows`` (R₀'s rows at
        capacity) and ``r0_nonzero_rows_bound`` (those that can be non-zero,
        `figaro.r0_nonzero_rows_bound`); None before."""
        engine = self._session.engine
        plan = self._holder.plan
        nodes = {}
        if plan is not None:
            for sp, ix in zip(plan.spec.nodes, plan.index):
                live = int(ix.row_mask.sum()) if ix.row_mask is not None \
                    else sp.m
                nodes[sp.name] = {"capacity_rows": sp.m, "live_rows": live}
        else:
            for name in self._tree.preorder():
                nodes[name] = {"capacity_rows": None,
                               "live_rows": self._tree.db[name].num_rows}
        appends, regrows = self._holder.counters()
        joined = self._join_counts(plan) if plan is not None else {}
        return {
            "plan_built": plan is not None,
            "join_rows": joined.get("join_rows"),
            "r0_rows": joined.get("r0_rows"),
            "r0_nonzero_rows_bound": joined.get("r0_nonzero_rows_bound"),
            "appends": appends,
            "regrows": regrows,
            "root": self.tree.root,
            "auto_root": self._auto,
            "reroots": self._holder.reroot_count(),
            "append_volume": self._holder.append_volumes(),
            "nodes": nodes,
            "traces": self._session.engine.trace_counts(),
            "trace_count": engine.trace_count(),
            "evictions": engine.eviction_count(),
            "cached_executables": engine.cache_size(),
        }

    # -- column naming -------------------------------------------------------

    @property
    def columns(self) -> tuple[str, ...]:
        """Qualified global column names (``"Node.attr"``) in the plan's
        preorder column layout. Follows the *live* tree: an adaptive re-root
        changes the preorder, and with it the column order of R."""
        tree = self.tree
        return tuple(f"{name}.{a}" for name in tree.preorder()
                     for a in tree.db[name].data_attrs)

    def column_index(self, col) -> int:
        """Global column index of ``col``: an int (validated), a bare
        attribute name (must be unique across relations), or a qualified
        ``"Node.attr"``."""
        cols = self.columns
        if isinstance(col, (int, np.integer)):
            if not 0 <= int(col) < len(cols):
                raise IndexError(f"column index {col} out of range "
                                 f"[0, {len(cols)})")
            return int(col)
        if not isinstance(col, str):
            raise TypeError(f"column must be an int or str, "
                            f"got {type(col).__name__}")
        if "." in col:
            if col in cols:
                return cols.index(col)
            raise KeyError(f"unknown column {col!r}; have {list(cols)}")
        hits = [i for i, c in enumerate(cols) if c.split(".", 1)[1] == col]
        if not hits:
            raise KeyError(f"unknown column {col!r}; have {list(cols)}")
        if len(hits) > 1:
            raise KeyError(f"column name {col!r} is ambiguous: "
                           f"{[cols[i] for i in hits]} — qualify it")
        return hits[0]

    # -- compute -------------------------------------------------------------

    def _request_data(self, data):
        """Pad live-sized request leaves up to capacity (see class doc)."""
        if data is None:
            return None
        plan = self.plan
        data = tuple(data)
        if len(data) != len(plan.spec.nodes):
            raise ValueError(
                f"expected one data leaf per relation "
                f"({len(plan.spec.nodes)}: {list(plan.spec.names)}), "
                f"got {len(data)}")
        sizes = [(int(ix.row_mask.sum()) if ix.row_mask is not None
                  else sp.m, sp)
                 for sp, ix in zip(plan.spec.nodes, plan.index)]
        if all(np.shape(d)[-2] == sp.m for d, (_, sp) in zip(data, sizes)):
            return data  # already capacity-shaped: no host round trip
        for d, (live, sp) in zip(data, sizes):
            if np.shape(d)[-2] not in (live, sp.m):
                raise ValueError(
                    f"{sp.name}: request data has {np.shape(d)[-2]} rows; "
                    f"expected the live size ({live}) or the capacity "
                    f"({sp.m}) — rebuild request buffers after append()")
        return pad_data(data, plan.spec)

    def r0(self, data=None, **overrides):
        plan = self._check_counts("r0", overrides.get("dtype"))
        return self._session.r0(plan, self._request_data(data), **overrides)

    def qr(self, data=None, **overrides):
        """R of the join's QR; ``data`` with a leading batch axis serves the
        whole batch in one (mesh-sharded, when configured) dispatch."""
        plan = self._check_counts("qr", overrides.get("dtype"))
        return self._session.qr(plan, self._request_data(data), **overrides)

    def svd(self, data=None, *, k: int | None = None, **overrides):
        """(s, Vᵀ) of the join matrix; ``k`` keeps the top-k."""
        plan = self._check_counts("svd", overrides.get("dtype"))
        return self._session.svd(plan, self._request_data(data), k=k,
                                 **overrides)

    def pca(self, data=None, *, k: int | None = None, center: bool = True,
            **overrides):
        """`PCAResult` (components, explained variance, factorized mean)."""
        plan = self._check_counts("pca", overrides.get("dtype"))
        return self._session.pca(plan, self._request_data(data), k=k,
                                 center=center, **overrides)

    def lsq(self, y, data=None, *, ridge: float = 0.0, **overrides):
        """Closed-form linear regression of label column ``y`` (index, bare
        name, or ``"Node.attr"``) against all other columns."""
        plan = self._check_counts("least_squares", overrides.get("dtype"))
        return self._session.least_squares(
            plan, self.column_index(y), self._request_data(data),
            ridge=ridge, **overrides)

    def serve(self, kind: str = "qr", *, label_col=None, **kw):
        """An async pipelined serving endpoint over this dataset's capacity
        plan (`train.serve.make_figaro_server`): ``submit(request)`` returns
        a `FigaroFuture`; ``server(batch)`` blocks for its answer.

        The server shares this dataset's plan *holder*: ``server.append``
        and ``ds.append`` refresh one plan state (draining the server's
        in-flight work first), so ``ds.plan`` / ``ds.stats()`` and the
        served plan can never fork. Like every compute method, it refuses a
        dtype whose counts would not be exact (`counts.check_counts_exact`).
        """
        validate_serve_kind(kind)
        if label_col is not None:
            label_col = self.column_index(label_col)
        # Builds the capacity plan before the holder is shared.
        self._check_counts(_SERVE_ENGINE_KINDS[kind], kw.get("dtype"))
        return self._session.serve(self._holder, kind=kind,
                                   label_col=label_col, **kw)


_DEFAULT_SESSION: Session | None = None


def default_session() -> Session:
    """Process-wide `Session` behind the legacy module-level entry points
    (`figaro_qr`, `svd_over_join`, ...): shares `default_engine()`'s
    executable cache and keeps the pre-Session defaults (no bucketing, no
    mesh, per-kind dtypes)."""
    global _DEFAULT_SESSION
    if _DEFAULT_SESSION is None:
        _DEFAULT_SESSION = Session(engine=default_engine(), bucket=False)
    return _DEFAULT_SESSION
