"""Compiled FiGaRo engine: one executable per plan signature, batched serving.

`FigaroEngine` fronts the whole plan → counts → rotations → post-process
pipeline (`qr` / `svd` / `pca` / `least_squares`, plus raw `r0`) behind
`jax.jit` with the `FigaroPlan` passed **through** the jit boundary as a
pytree argument:

  * the plan's static `PlanSpec` is treedef metadata, so the executable cache
    keys on (spec, data shapes/dtypes, static options). Two different
    databases with the same join signature share one compiled program — no
    per-plan closure rebuild, no retrace on refreshed data;
  * data buffers are passed as their own argument and (optionally) **donated**
    to the executable, the serving configuration where request buffers are
    consumed by the dispatch that answers them;
  * `batched=True` vmaps the pipeline over a leading batch axis of the
    per-node data matrices with the plan held fixed — one join structure
    serving many feature-sets/users per dispatch. This is the "one
    factorization, many downstream reads" leverage: everything downstream
    (SVD, PCA, regression) reads off the one R. Each kind has one traced
    body, ``_<kind>_impl``; its batched kind is that body under `jax.vmap`,
    and the body's keyword-only options are the static arguments.

  * ``shard=mesh`` (or ``shard=(mesh, axis)``) additionally splits the leading
    request-batch axis of a batched dispatch over the mesh axis with
    `shard_map`: one cached executable answers a *global* batch across all
    devices. The batch is padded up to a multiple of the axis size (by
    repeating the trailing request, so no degenerate all-zero pipelines run on
    the pad) and the pad is sliced off the result — batch sizes in the same
    padded bucket share one executable. The executable cache keys on the mesh
    signature as well as the plan signature.

  * ``bucket=True`` rounds the plan's static sizes up to powers of two
    (`repro.core.plan_cache`) before dispatching, so plans that differ only
    within one bucket land on the same cached executable — this is what
    bounds the compile count under heavy multi-tenant load. Capacity plans
    built with `plan_cache.build_capacity_plan` / refreshed with
    `plan_cache.refresh_plan` dispatch the same way without any per-call
    padding: an append that keeps the bucketed signature is retrace-free.

The pipeline bodies run each phase under a `jax.named_scope` (see
`core.figaro`): Algorithm 1's counts (``figaro.counts``, here also for the
PCA means), Algorithm 2's passes, ``figaro.postprocess``, and the
downstream svd / eigh / solve (``figaro.downstream``), so a device trace
splits an executable's time by phase.

Trace counts are tracked per pipeline kind (`trace_count`) so tests and
benchmarks can assert cache hits instead of guessing. ``max_cached=`` bounds
the per-kind executable cache with LRU eviction (`eviction_count`) — without
it, heavy multi-tenant bucket misses grow the cache without bound.

`repro.api` (`repro.figaro`) wraps this engine in the user-facing
`Session` / `JoinDataset` façade; new code should usually start there.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import inspect

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import shard_map
from repro.sanitizer import _state as _san_state
from repro.sanitizer import numerics as _san_numerics
from repro.sanitizer import retrace as _san_retrace
from repro.sanitizer.locks import san_lock, san_rlock
from repro.sanitizer.races import shared_state

from .counts import compute_counts
from .figaro import figaro_r0
from .join_tree import FigaroPlan, JoinTree, build_plan
from .plan_cache import bucket_spec, pad_data, pad_plan
from .postprocess import postprocess_r0

__all__ = ["FigaroEngine", "PCAResult", "default_engine", "plan_for"]


def _repeat_pad(data, pad: int):
    """Pad the leading request-batch axis by repeating the trailing request
    — near-miss batch sizes then share an executable, and the pad rides
    through a well-posed pipeline (an all-zero pad would push singular
    systems through lsq/svd). The pad is sliced off the result."""
    return tuple(jnp.concatenate([jnp.asarray(d)] + [jnp.asarray(d)[-1:]]
                                 * pad) for d in data)


@functools.lru_cache(maxsize=None)
def _backend_supports_donation() -> bool:
    """CPU's PJRT client ignores buffer donation and warns on every dispatch
    that requests it. Requesting donation only where it works keeps serving
    loops quiet without touching the process-global warnings filters (a
    per-dispatch ``warnings.catch_warnings()`` save/restore is not
    thread-safe once the async serving threads dispatch concurrently with
    the caller's thread)."""
    return jax.default_backend() != "cpu"


def _bucketize(plan: FigaroPlan, data):
    """Pad an exact plan (and its data) into its power-of-two buckets so
    near-miss shapes share an executable; capacity plans pass through."""
    if any(ix.row_mask is not None for ix in plan.index):
        return plan, data  # already capacity-padded (its spec IS the bucket)
    cap = bucket_spec(plan.spec)
    padded = pad_plan(plan, cap)
    if data is not None:
        data = pad_data(data, cap)
    return padded, data


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PCAResult:
    components: jnp.ndarray  # [k, N] principal directions (rows)
    explained_variance: jnp.ndarray  # [k]
    mean: jnp.ndarray  # [N] column means over the join
    num_rows: jnp.ndarray  # scalar: |join|


def _column_moments(plan: FigaroPlan, data, dtype):
    """Factorized column sums & row count of the join (no materialization).

    Row r of relation i appears in exactly Φ°_i(key(r)) join rows, so
    Σ_join A[:, Y_i] = Σ_r data_i[r] · Φ°_i(key(r)) — a per-node weighted sum.
    Node columns are preorder-contiguous, so the global vector is a concat.
    """
    with jax.named_scope("figaro.counts"):
        counts = compute_counts(plan, dtype=dtype)
    with jax.named_scope("figaro.downstream"):
        parts = []
        for sp, ix, d in zip(plan.spec.nodes, plan.index, data):
            w = counts[sp.idx]["phi_circ"][jnp.asarray(ix.row_to_group)]
            if ix.row_mask is not None:  # capacity plan: dead rows weigh 0
                w = w * jnp.asarray(ix.row_mask, dtype)
            parts.append(w @ jnp.asarray(d, dtype))
        sums = jnp.concatenate(parts)
        total = counts[plan.spec.root]["full"].sum()
    return sums, total


@shared_state({"_jitted": "_cache_lock", "_trace_counts": "_count_lock",
               "_evictions": "_count_lock"})
class FigaroEngine:
    """Executable cache + dispatch for the compiled FiGaRo pipeline.

    One engine holds one `jax.jit` wrapper per (pipeline kind, donation)
    pair; jit's own cache then keys on the plan signature. Use a single
    long-lived engine per process (see `default_engine`) to get cross-call and
    cross-plan executable reuse — e.g. `partitioned_figaro_qr` runs every
    partition and every repeat call through the same engine.

    ``donate_data=True`` (default) donates caller-provided data buffers to the
    dispatch (serving mode: request buffers are consumed). Buffers taken from
    ``plan.data`` are never donated — the plan stays reusable. Pass
    ``donate_data=False`` when callers re-dispatch the same buffers
    (benchmark loops).

    ``max_cached=`` caps the number of cached executables **per pipeline
    kind** (``qr``, ``qr_batched``, ...). The cache is LRU: dispatching a new
    signature past the cap evicts the least-recently-used executable of that
    kind (its compiled program is dropped); re-dispatching an evicted
    signature recompiles (visible in `trace_count`). Evictions are counted
    per kind next to the trace counters — `eviction_count(kind)`. The default
    (``None``) keeps every executable, the pre-existing behavior.
    """

    def __init__(self, *, donate_data: bool = True,
                 max_cached: int | None = None):
        if max_cached is not None and max_cached < 1:
            raise ValueError(f"max_cached must be >= 1 or None, "
                             f"got {max_cached}")
        self.donate_data = donate_data
        self.max_cached = max_cached
        # Executable cache, keyed on the FULL dispatch signature (kind, mesh,
        # plan treedef + leaf shapes/dtypes, static options) with one jit
        # wrapper per entry, so eviction can drop exactly one executable.
        # Insertion/access order is the LRU order. The locks make cache
        # bookkeeping and counter bumps safe under concurrent dispatch (the
        # async serving path dispatches from a background thread while the
        # owning session may keep dispatching from the caller's thread); they
        # are sanitizer-aware wrappers (FIG007) so FIGARO_SAN=1 can observe
        # lock order and cross-thread access. Locks are created before the
        # state they guard so the race detector can resolve them mid-__init__.
        self._cache_lock = san_rlock("engine._cache_lock")
        self._count_lock = san_lock("engine._count_lock")
        self._trace_counts: collections.Counter = collections.Counter()
        self._evictions: collections.Counter = collections.Counter()
        self._jitted: collections.OrderedDict = collections.OrderedDict()

    # -- cache plumbing ------------------------------------------------------

    def trace_count(self, kind: str | None = None) -> int:
        """Number of traces (compilations) since construction; cache-hit tests
        assert this stays flat across same-signature dispatches."""
        with self._count_lock:
            if kind is None:
                return sum(self._trace_counts.values())
            return self._trace_counts[kind]

    def trace_counts(self) -> dict[str, int]:
        """Per-kind trace counts as a plain dict (for stats surfaces)."""
        with self._count_lock:
            return {k: int(v) for k, v in sorted(self._trace_counts.items())}

    def eviction_count(self, kind: str | None = None) -> int:
        """Executables evicted by the ``max_cached`` LRU policy (0 when
        unbounded); tracked per kind, next to the trace counters."""
        with self._count_lock:
            if kind is None:
                return sum(self._evictions.values())
            return self._evictions[kind]

    def cache_size(self, kind: str | None = None) -> int:
        """Number of live cached executables (per kind, or total)."""
        with self._cache_lock:
            if kind is None:
                return len(self._jitted)
            return sum(1 for k in self._jitted if k[0] == kind)

    def _bump(self, kind: str) -> None:
        with self._count_lock:
            self._trace_counts[kind] += 1

    @staticmethod
    def _abstract(leaves) -> tuple:
        return tuple((np.shape(l), np.dtype(getattr(l, "dtype", None)
                                            or np.asarray(l).dtype).str)
                     for l in leaves)

    def _signature(self, kind: str, plan: FigaroPlan, data, donate: bool,
                   mesh, axis, options) -> tuple:
        """Hashable key covering everything a dispatch compiles against.

        The plan half (treedef + index-leaf shapes/dtypes) is cached on the
        plan object: flattening ~dozens of leaves per dispatch costs ~100µs,
        and plan lifecycles (`plan_cache.refresh_plan`, `with_data`) replace
        plan objects rather than mutating array shapes in place."""
        plan_sig = getattr(plan, "_engine_sig", None)
        if plan_sig is None:
            leaves, treedef = jax.tree_util.tree_flatten(plan.without_data())
            plan_sig = plan._engine_sig = (treedef, self._abstract(leaves))
        return (kind, donate, mesh, axis, plan_sig,
                self._abstract(data), tuple(sorted(options.items())))

    def _evict_lru(self, kind: str) -> None:
        """Drop least-recently-used executables of ``kind`` past the cap."""
        if self.max_cached is None:
            return
        while self.cache_size(kind) > self.max_cached:
            oldest = next(k for k in self._jitted if k[0] == kind)
            fn = self._jitted.pop(oldest)
            clear = getattr(fn, "clear_cache", None)
            if clear is not None:  # free the compiled program eagerly
                clear()
            with self._count_lock:
                self._evictions[kind] += 1

    @staticmethod
    def _normalize_shard(shard) -> tuple[Mesh | None, str | None]:
        """``shard=mesh`` or ``shard=(mesh, axis)`` → (mesh, axis)."""
        if shard is None:
            return None, None
        mesh, axis = shard if isinstance(shard, tuple) else (shard, "data")
        if axis not in mesh.shape:
            raise ValueError(
                f"shard axis {axis!r} not in mesh axes {tuple(mesh.shape)}")
        return mesh, axis

    def _make_jitted(self, kind: str, donate: bool, mesh, axis, key: tuple):
        base = kind.removesuffix("_batched")
        impl = getattr(self, f"_{base}_impl")
        # Every keyword-only option of the body is static: part of the
        # executable cache key, concrete at trace time.
        static = tuple(name for name, p in
                       inspect.signature(impl).parameters.items()
                       if p.kind is inspect.Parameter.KEYWORD_ONLY)
        if kind != base:
            one = impl

            # A batched kind vmaps the body over the request axis with the
            # plan held unmapped. Its name stays `_<kind>_impl`, so the
            # compiled module is `jit__<kind>_impl`, the name device traces
            # are classified by.
            @functools.wraps(one)
            def impl(plan, data, **options):
                return jax.vmap(lambda d: one(plan, d, **options))(data)

            impl.__name__ = impl.__qualname__ = f"_{kind}_impl"
        if mesh is None:
            inner = impl
        else:
            def inner(plan, data, **options):
                # Per-shard body: the plan (index arrays) is replicated, the
                # leading request-batch axis of every data leaf is split over
                # ``mesh[axis]``; every output leaf has a leading batch axis.
                body = lambda p, d: impl(p, d, **options)
                # check_vma=False: pallas_call (the fused node kernel) has no
                # replication rule, and nothing here relies on the check —
                # the plan is replicated in, all outputs are P(axis)-sharded.
                mapped = shard_map(body, mesh=mesh,
                                   in_specs=(P(), P(axis)),
                                   out_specs=P(axis),
                                   check_vma=False)
                return mapped(plan, data)

        # wraps() keeps impl's signature visible so static_argnames resolve,
        # and putting the bump here (outside shard_map) guarantees exactly one
        # count per compilation however many times shard_map replays the body.
        # Shadow (float64 reference) dispatches from the numerics sanitizer
        # must not count as traces or feed the retrace sanitizer — they are
        # sanitizer-internal, not part of the serving contract.
        @functools.wraps(impl)
        def wrapper(plan, data, **options):
            if not _san_state.STATE.shadow_active():
                self._bump(kind)
                if _san_state.STATE.enabled and _san_state.STATE.retrace:
                    _san_retrace.note_trace(kind, key)
            return inner(plan, data, **options)

        return jax.jit(wrapper, static_argnames=static,
                       donate_argnums=(1,) if donate else ())

    def _dispatch(self, kind: str, plan: FigaroPlan, data, *, shard=None,
                  bucket: bool = False, batch_capacity: int | None = None,
                  **options):
        if not isinstance(plan, FigaroPlan):
            raise TypeError(_plan_arg_error("plan", plan))
        if bucket:
            plan, data = _bucketize(plan, data)
        mesh, axis = self._normalize_shard(shard)
        if mesh is not None and not kind.endswith("_batched"):
            raise ValueError(
                f"shard= requires a batched dispatch, got kind={kind!r}")
        if batch_capacity is not None and not kind.endswith("_batched"):
            raise ValueError(f"batch_capacity= requires a batched dispatch, "
                             f"got kind={kind!r}")
        if data is None:
            if mesh is not None:
                # plan.data is per-node [m_i, n_i] — there is no request-batch
                # axis to shard; padding it would fail deep inside vmap.
                raise ValueError(
                    "shard= needs an explicit [B, m_i, n_i] data batch")
            data, donate = plan.data, False  # plan-owned buffers stay alive
        else:
            data = tuple(data)
            # Never donate buffers the plan owns, even when the caller passes
            # them explicitly — donation would kill plan.data for later
            # dispatches on backends with real donation.
            plan_owned = {id(d) for d in plan.data}
            donate = (self.donate_data and _backend_supports_donation()
                      and not any(id(d) in plan_owned for d in data))
        b_live = cap_pad = 0
        if batch_capacity is not None and data:
            # Partial-batch bucket selection: pad the request axis up to the
            # chosen batch capacity (repeating the trailing request, as the
            # mesh path does) so live batch sizes in one bucket share one
            # executable; the pad is sliced off the result below. B=0 cannot
            # repeat a trailing request — it dispatches at its own (cheap to
            # compile) signature instead.
            b_live = int(np.shape(data[0])[0])
            cap_pad = batch_capacity - b_live
            if b_live and cap_pad > 0:
                data = _repeat_pad(data, cap_pad)
                # padded buffers are fresh — never plan-owned
                donate = self.donate_data and _backend_supports_donation()
            elif cap_pad < 0:
                raise ValueError(
                    f"batch_capacity={batch_capacity} smaller than the live "
                    f"request batch ({b_live})")
            else:
                cap_pad = 0
        b = pad = 0
        if mesh is not None:
            p = mesh.shape[axis]
            b = int(data[0].shape[0])
            if b == 0:
                # Nothing to shard — the pad-by-repeating-the-trailing-request
                # bucketing would index an empty batch out of range. Answer
                # through the unsharded batched executable, which vmaps over
                # the empty axis and returns correctly-shaped empty results.
                return self._dispatch(kind, plan, data, **options)
            pad = -(-b // p) * p - b
            if pad:
                # Bucket the batch to a multiple of the mesh axis.
                data = _repeat_pad(data, pad)
                # padded buffers are fresh — never plan-owned
                donate = self.donate_data and _backend_supports_donation()
            data = jax.device_put(data, NamedSharding(mesh, P(axis)))
        key = self._signature(kind, plan, data, donate, mesh, axis, options)
        shadow = None
        if _san_state.STATE.enabled and _san_state.STATE.numerics:
            # Host-copy the request before the jit call: donation may consume
            # the device buffers, and the float64 shadow re-dispatch needs
            # the original values.
            shadow = _san_numerics.prepare_shadow(self, kind, plan, data,
                                                  options)
        with self._cache_lock:
            fn = self._jitted.get(key)
            if fn is None:
                fn = self._jitted[key] = self._make_jitted(kind, donate, mesh,
                                                           axis, key)
                self._evict_lru(kind)
            else:
                self._jitted.move_to_end(key)  # LRU: most-recent at the tail
        out = fn(plan.without_data(), data, **options)
        if shadow is not None:
            # Before pad slicing: the shadow ran the same padded inputs, so
            # the comparable shapes line up exactly.
            _san_numerics.after_dispatch(self, shadow, out)
        if pad:
            out = jax.tree.map(lambda x: x[:b], out)
        if cap_pad:
            out = jax.tree.map(lambda x: x[:b_live], out)
        return out

    @staticmethod
    def _canon(dtype) -> np.dtype:
        return np.dtype(dtype)

    def stage(self, data, *, shard=None):
        """Start the H2D transfer of request leaves ahead of their dispatch.

        `jax.device_put` is asynchronous, so staging the *next* batch while
        the current dispatch is still executing overlaps its host-to-device
        copy with compute — with ``donate_data=True`` each staged slab is
        consumed by the dispatch that answers it, so a pipeline of queue
        depth 2 is exactly engine-level double buffering of donated inputs.
        With a mesh ``shard``, leaves are placed with the dispatch's batch
        sharding directly (the request axis should already be padded to a
        multiple of the axis — `launch.mesh.serving_batch_capacity`).
        """
        mesh, axis = self._normalize_shard(shard)
        if mesh is None:
            return tuple(jax.device_put(jnp.asarray(d)) for d in data)
        sharding = NamedSharding(mesh, P(axis))
        return tuple(jax.device_put(jnp.asarray(d), sharding) for d in data)

    # -- traced pipeline bodies (run once per executable) --------------------

    def _r0_impl(self, plan, data, *, dtype, use_kernel, assembly):
        return figaro_r0(plan, list(data), dtype=dtype, use_kernel=use_kernel,
                         assembly=assembly)

    def _qr_impl(self, plan, data, *, dtype, leaf_rows, use_kernel, assembly):
        r0 = self._r0_impl(plan, data, dtype=dtype, use_kernel=use_kernel,
                           assembly=assembly)
        return postprocess_r0(r0, leaf_rows=leaf_rows)

    def _svd_impl(self, plan, data, *, dtype, leaf_rows, use_kernel,
                  assembly):
        r = self._qr_impl(plan, data, dtype=dtype, leaf_rows=leaf_rows,
                          use_kernel=use_kernel, assembly=assembly)
        with jax.named_scope("figaro.downstream"):
            _, s, vt = jnp.linalg.svd(r)
        return s, vt

    def _pca_impl(self, plan, data, *, k, center, dtype, leaf_rows,
                  use_kernel, assembly):
        r = self._qr_impl(plan, data, dtype=dtype, leaf_rows=leaf_rows,
                          use_kernel=use_kernel, assembly=assembly)
        sums, total = _column_moments(plan, data, dtype)
        with jax.named_scope("figaro.downstream"):
            mean = sums / total
            gram = r.T @ r
            if center:
                gram = gram - total * jnp.outer(mean, mean)
            cov = gram / jnp.maximum(total - 1.0, 1.0)
            evals, evecs = jnp.linalg.eigh(cov)  # ascending
            # The centered-Gram subtraction can leave tiny negative
            # eigenvalues (a variance); clamp before the top-k select so
            # near-constant columns report 0, not -1e-17.
            evals = jnp.maximum(evals, jnp.zeros((), evals.dtype))
            order = jnp.argsort(-evals)[:k]
        return PCAResult(components=evecs[:, order].T,
                         explained_variance=evals[order],
                         mean=mean, num_rows=total)

    def _least_squares_impl(self, plan, data, *, label_col, ridge, dtype,
                            leaf_rows, use_kernel, assembly):
        r = self._qr_impl(plan, data, dtype=dtype, leaf_rows=leaf_rows,
                          use_kernel=use_kernel, assembly=assembly)
        with jax.named_scope("figaro.downstream"):
            n = plan.spec.num_cols
            feat = jnp.array([j for j in range(n) if j != label_col])
            # Permute label last, re-triangularize the permuted R (cheap: N×N).
            perm = jnp.concatenate([feat, jnp.array([label_col])])
            rp = r[:, perm]
            rr = jnp.linalg.qr(rp, mode="r")[:n]
            r_ff = rr[: n - 1, : n - 1]
            r_fl = rr[: n - 1, n - 1]
            if ridge:
                g = r_ff.T @ r_ff + ridge * jnp.eye(n - 1, dtype=dtype)
                beta = jnp.linalg.solve(g, r_ff.T @ r_fl)
                # The ridge solution does not zero the projected residual, so
                # ‖Aβ − y‖ keeps both terms: ‖r_ff·β − r_fl‖² + rr[n−1,n−1]².
                resid = jnp.sqrt(jnp.sum(jnp.square(r_ff @ beta - r_fl))
                                 + jnp.square(rr[n - 1, n - 1]))
            else:
                beta = jax.scipy.linalg.solve_triangular(r_ff, r_fl,
                                                         lower=False)
                resid = jnp.abs(rr[n - 1, n - 1])
        return beta, resid

    # -- public API ----------------------------------------------------------

    def r0(self, plan: FigaroPlan, data=None, *, batched: bool = False,
           shard=None, bucket: bool = False, batch_capacity: int | None = None,
           dtype=jnp.float32, use_kernel: bool = False,
           assembly: str = "padded") -> jnp.ndarray:
        """R₀ of Algorithm 2; ``batched`` expects [B, m_i, n_i] data leaves.

        ``shard`` (a `Mesh` or ``(mesh, axis)``; requires ``batched=True``)
        splits the batch axis over the mesh — one executable per
        (plan signature, mesh signature) answers the global batch.

        ``bucket=True`` pads the plan (and data rows) to its power-of-two
        capacities first, so near-miss plan shapes share one executable; R₀
        then carries extra all-zero rows at the capacity layout. Long-lived
        callers should hold a `plan_cache.build_capacity_plan` plan instead
        (same executables, no per-dispatch host padding).

        ``batch_capacity`` (requires ``batched=True``) pads a partial request
        batch up to the given bucket (repeating the trailing request; the pad
        is sliced off the result), so the executable cache tracks batch
        *buckets*, not every live batch size — the micro-batching serving
        queue (`train.async_serve`) picks its buckets this way.

        ``use_kernel`` routes each node through the fused Pallas pass
        (`kernels/node_fused`); ``assembly`` ("padded" | "band") picks the R₀
        materialization (see `core.figaro`). Both are static options — part
        of the executable cache key.
        """
        return self._dispatch("r0_batched" if batched else "r0", plan, data,
                              shard=shard, bucket=bucket,
                              batch_capacity=batch_capacity,
                              dtype=self._canon(dtype),
                              use_kernel=use_kernel, assembly=assembly)

    def qr(self, plan: FigaroPlan, data=None, *, batched: bool = False,
           shard=None, bucket: bool = False, batch_capacity: int | None = None,
           dtype=jnp.float32, leaf_rows: int = 256, use_kernel: bool = False,
           assembly: str = "padded") -> jnp.ndarray:
        """Upper-triangular R of the join's QR ([B, N, N] when batched)."""
        return self._dispatch(
            "qr_batched" if batched else "qr", plan, data, shard=shard,
            bucket=bucket, batch_capacity=batch_capacity,
            dtype=self._canon(dtype), leaf_rows=leaf_rows,
            use_kernel=use_kernel, assembly=assembly)

    def svd(self, plan: FigaroPlan, data=None, *, batched: bool = False,
            shard=None, bucket: bool = False,
            batch_capacity: int | None = None, dtype=jnp.float64,
            leaf_rows: int = 256, use_kernel: bool = False,
            assembly: str = "padded"):
        """Singular values + right-singular vectors of the join matrix."""
        return self._dispatch(
            "svd_batched" if batched else "svd", plan, data, shard=shard,
            bucket=bucket, batch_capacity=batch_capacity,
            dtype=self._canon(dtype), leaf_rows=leaf_rows,
            use_kernel=use_kernel, assembly=assembly)

    def pca(self, plan: FigaroPlan, data=None, *, batched: bool = False,
            shard=None, bucket: bool = False,
            batch_capacity: int | None = None, k: int | None = None,
            center: bool = True, dtype=jnp.float64, leaf_rows: int = 256,
            use_kernel: bool = False,
            assembly: str = "padded") -> PCAResult:
        """PCA of the join matrix from R (+ factorized means when centering)."""
        n = plan.spec.num_cols
        k = n if k is None else min(k, n)
        return self._dispatch(
            "pca_batched" if batched else "pca", plan, data, shard=shard,
            bucket=bucket, batch_capacity=batch_capacity, k=k, center=center,
            dtype=self._canon(dtype), leaf_rows=leaf_rows,
            use_kernel=use_kernel, assembly=assembly)

    def least_squares(self, plan: FigaroPlan, label_col: int, data=None, *,
                      batched: bool = False, shard=None, bucket: bool = False,
                      batch_capacity: int | None = None,
                      ridge: float = 0.0, dtype=jnp.float64,
                      leaf_rows: int = 256, use_kernel: bool = False,
                      assembly: str = "padded"):
        """argmin_β ‖A[:, feats]·β − A[:, label]‖² over the unmaterialized join."""
        return self._dispatch(
            "least_squares_batched" if batched else "least_squares", plan,
            data, shard=shard, bucket=bucket, batch_capacity=batch_capacity,
            label_col=label_col,
            ridge=float(ridge), dtype=self._canon(dtype),
            leaf_rows=leaf_rows, use_kernel=use_kernel, assembly=assembly)


def _plan_arg_error(arg_name: str, value) -> str:
    """A clear TypeError message for a non-plan handed to a plan argument.

    Without this, a `Database` or a raw ``{name: array}`` table dict sinks
    into pytree flattening and surfaces as a deep, unrelated error."""
    from .relation import Database

    got = type(value).__name__
    if isinstance(value, Database):
        hint = ("a Database is not executable yet — pick a join tree first: "
                "JoinTree.from_edges(db, root, edges), or use the façade: "
                "repro.figaro.Session().ingest(db).join(root, edges)")
    elif isinstance(value, dict) or (
            isinstance(value, (list, tuple)) and value
            and isinstance(value[0], np.ndarray)):
        hint = ("raw tables must be ingested first: "
                "repro.figaro.Session().ingest(tables).join(root, edges), or "
                "Database.from_arrays(tables) + JoinTree.from_edges")
    else:
        hint = ("build one with join_tree.build_plan(tree) or "
                "plan_cache.build_capacity_plan(tree)")
    return (f"argument {arg_name!r} must be a JoinTree or FigaroPlan, "
            f"got {got}: {hint}")


_DEFAULT_ENGINE: FigaroEngine | None = None


def default_engine() -> FigaroEngine:
    """Process-wide shared engine (non-donating, safe for repeated dispatch of
    the same buffers) — the cross-call executable cache behind the module-level
    `qr`/`svd` convenience APIs and `partitioned_figaro_qr`."""
    global _DEFAULT_ENGINE
    if _DEFAULT_ENGINE is None:
        _DEFAULT_ENGINE = FigaroEngine(donate_data=False)
    return _DEFAULT_ENGINE


def plan_for(tree_or_plan: JoinTree | FigaroPlan) -> FigaroPlan:
    """Accept either a `JoinTree` (compiled here) or a ready `FigaroPlan`.

    Anything else — a `Database`, a raw table dict — raises a `TypeError`
    naming the offending argument instead of failing deep inside pytree
    flattening."""
    if isinstance(tree_or_plan, FigaroPlan):
        return tree_or_plan
    if isinstance(tree_or_plan, JoinTree):
        return build_plan(tree_or_plan)
    raise TypeError(_plan_arg_error("tree_or_plan", tree_or_plan))
