"""Serving: prefill / decode steps, a batched greedy/temperature sampler, and
the batched FiGaRo factorization server.

``make_prefill`` / ``make_decode_step`` are the functions the dry-run lowers
for the prefill_* / decode_* / long_* shapes. The KV cache is sharded batch-
over-(pod,data) normally, and sequence-over-data for global_batch==1
long-context decode (context parallelism — GSPMD inserts the online-softmax
combine collectives).

``make_figaro_server`` is the linear-algebra-over-joins counterpart: one join
structure (a `FigaroPlan`), many concurrent users' feature-sets — each dispatch
vmaps Algorithm 2 + post-processing over a leading batch axis through a
`FigaroEngine` with donated request buffers, so serving cost per request is
one cached executable launch. The server is async-first
(`repro.train.async_serve`): ``submit(request)`` returns a `FigaroFuture`,
pending requests coalesce into bucketed micro-batches, and queue depth >= 2
overlaps the next batch's H2D staging with the in-flight dispatch; the
synchronous `FigaroServer` call is a ``submit(...).result()`` wrapper.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.engine import FigaroEngine
from repro.core.join_tree import FigaroPlan
from repro.core.plan_cache import PlanHolder
from repro.models import transformer as tf
from repro.models.config import ModelConfig
from repro.sharding.rules import data_axes
from repro.train.async_serve import (AsyncFigaroServer, FigaroFuture,
                                     SERVE_KINDS, validate_serve_kind)

__all__ = ["make_prefill", "make_decode_step", "cache_specs", "sample_loop",
           "make_figaro_server", "FigaroServer", "AsyncFigaroServer",
           "FigaroFuture", "SERVE_KINDS", "validate_serve_kind"]


class FigaroServer(AsyncFigaroServer):
    """The synchronous face of `AsyncFigaroServer` — behavior-preserving for
    pre-async callers.

    ``server(data_batch)`` is exactly ``server.submit(data_batch).result()``:
    the request rides the same micro-batching queue and pipelined dispatch,
    the call just blocks for its own answer. ``server.append(node, rows)``
    (``rows = (key_columns, data_rows)`` as in `plan_cache.refresh_plan`)
    drains in-flight work and refreshes the shared plan holder: as long as
    the new live sizes fit the plan's bucketed capacities, the next dispatch
    reuses the cached executable — zero retraces under streaming appends.

    Capacity contract for requests: batch leaves are [B, rows_i, n_i] in the
    plan's (sorted) row order with ``rows_i`` either the node's live size or
    its full capacity; live-sized leaves are zero-padded up to capacity
    (the dead rows are masked out inside the pipeline regardless).
    """


def make_figaro_server(plan: FigaroPlan | PlanHolder, *, kind: str = "qr",
                       label_col: int | None = None, k: int | None = None,
                       ridge: float = 0.0,
                       dtype=jnp.float32, leaf_rows: int = 256,
                       use_kernel: bool = False,
                       assembly: str = "padded",
                       engine: FigaroEngine | None = None,
                       mesh: Mesh | None = None, shard_axis: str = "data",
                       max_batch: int = 32,
                       queue_depth: int = 2) -> FigaroServer:
    """Batched FiGaRo serving endpoint for one join structure.

    Returns a `FigaroServer` (an `AsyncFigaroServer` whose ``__call__``
    blocks) — ``server.submit(request)`` enqueues per-node [m_i, n_i]
    request leaves (or a [B, m_i, n_i] sub-batch) and returns a
    `FigaroFuture`; ``server(data_batch)`` answers synchronously:

      kind="qr"   -> R      [B, N, N]
      kind="svd"  -> (s [B, N], Vt [B, N, N])
      kind="pca"  -> PCAResult with a leading batch axis (top-``k``)
      kind="lsq"  -> (betas [B, N-1], residuals [B]) against ``label_col``

    Pending requests are coalesced up to ``max_batch`` rows and the batch is
    padded to its bucketed capacity (powers of two, aligned to the mesh
    axis), so every kind — lsq and pca included — answers the whole
    coalesced batch with ONE cached executable launch, and the executable
    cache tracks batch *buckets*, not every live batch size. ``queue_depth``
    coalesced batches may be in flight at once: at depth >= 2 the next
    batch's staging (async H2D of donated input slabs) overlaps the
    in-flight dispatch — engine-level double buffering.
    With a ``mesh``, the request-batch axis is additionally sharded over
    ``mesh[shard_axis]`` via `shard_map`: one executable per (plan signature,
    mesh signature) serves the global batch across all devices.

    With a capacity plan (`plan_cache.build_capacity_plan`) the server also
    exposes ``server.append(node, rows)`` for online data refreshes; appends
    that keep the bucketed signature never retrace. Pass a
    `plan_cache.PlanHolder` to share plan state with other surfaces (this is
    what ``JoinDataset.serve`` does — dataset and server then see one plan,
    never a fork).

    The engine donates request buffers (they are consumed by the dispatch that
    answers them) and compiles once per plan signature — subsequent batches,
    and other plans with the same signature, are launch-only.

    `repro.figaro` (`Session.serve` / `JoinDataset.serve`) is the façade over
    this constructor — it fills engine/mesh/dtype from the session config and
    resolves ``label_col`` by column name.
    """
    # Validate up front — a bad kind must fail at construction with the full
    # list of supported kinds, not at (or after) the first dispatch.
    validate_serve_kind(kind, label_col=label_col, check_label=True)
    if isinstance(plan, PlanHolder):
        holder = plan
    else:
        if not isinstance(plan, FigaroPlan):
            from repro.core.engine import _plan_arg_error

            raise TypeError(_plan_arg_error("plan", plan))
        holder = PlanHolder(plan)
    engine = engine if engine is not None else FigaroEngine(donate_data=True)
    shard = None if mesh is None else (mesh, shard_axis)

    # use_kernel / assembly ride the static half of every dispatch, so the
    # serving executables are the fused-kernel / band-assembly programs when
    # the session (or caller) asked for them — same cache-key discipline as
    # direct engine calls.
    common = dict(batched=True, shard=shard, dtype=dtype,
                  leaf_rows=leaf_rows, use_kernel=use_kernel,
                  assembly=assembly)
    dispatch = {
        "qr": lambda plan, batch, cap: engine.qr(
            plan, batch, batch_capacity=cap, **common),
        "svd": lambda plan, batch, cap: engine.svd(
            plan, batch, batch_capacity=cap, **common),
        "pca": lambda plan, batch, cap: engine.pca(
            plan, batch, batch_capacity=cap, k=k, **common),
        "lsq": lambda plan, batch, cap: engine.least_squares(
            plan, label_col, batch, batch_capacity=cap, ridge=ridge,
            **common),
    }[kind]
    server = FigaroServer(holder, dispatch, engine=engine,
                          axis_size=1 if mesh is None
                          else int(mesh.shape[shard_axis]),
                          max_batch=max_batch, queue_depth=queue_depth)
    holder.attach(server)
    return server


def make_prefill(cfg: ModelConfig, max_len: int):
    def prefill_fn(params, batch):
        return tf.prefill(params, cfg, batch, max_len)

    return prefill_fn


def make_decode_step(cfg: ModelConfig):
    def decode_fn(params, cache, tokens):
        return tf.decode_step(params, cfg, cache, tokens)

    return decode_fn


def cache_specs(cfg: ModelConfig, mesh: Mesh, *, shard_seq: bool = False,
                kv_seq_over_model: bool = True):
    """PartitionSpec pytree for the decode cache.

    Batch-sharded by default; ``shard_seq`` shards attention KV slots over
    `data` (long_500k, global_batch=1). SSM states are O(1) in seq — they
    stay batch-sharded (or replicated at batch 1).

    ``kv_seq_over_model`` (§Perf iteration C2): when the kv-head count does
    not divide the model axis (all assigned archs: kv=8 < 16), the KV slots
    shard over `model` — blockwise attention then runs on local slots and
    only the online-softmax stats (m, l, [B,H,1,hd] partials) cross shards.
    The pre-hillclimb layout sharded head_dim instead, which forced a
    re-gather of every KV block inside the attention scan (measured
    43 GB/device/token on command-r decode_32k).
    """
    dp = data_axes(mesh)
    msz = mesh.shape.get("model", 1)
    # kv heads shard over `model` when divisible; otherwise shard the KV
    # slots (sequence) over `model` — or, pre-hillclimb, the head_dim.
    kv_ax = "model" if cfg.n_kv_heads % msz == 0 else None
    seq_model_ax = None
    if kv_ax is None and kv_seq_over_model:
        hd_ax = None
        seq_model_ax = "model"
    else:
        hd_ax = None if kv_ax else "model"

    def _fit(spec: P, shape) -> P:
        """Drop axis shardings that do not divide the dim (reduced configs on
        the production mesh would otherwise hit uneven-tiling errors)."""
        fixed = []
        for dim, ax in zip(shape, tuple(spec) + (None,) * len(shape)):
            if ax is None:
                fixed.append(None)
                continue
            size = 1
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                size *= mesh.shape[a]
            fixed.append(ax if dim >= size and dim % size == 0 else None)
        return P(*fixed)

    def spec(path, leaf):
        names = [getattr(p, "key", str(p)) for p in path]
        ndim = len(leaf.shape)
        if "pos" in names[-1:]:
            return P()
        batch_ax = None if shard_seq else dp
        if names[-1] in ("k", "v"):  # [n_blocks, B, S, kv, hd]
            if shard_seq:  # batch == 1: context parallelism over data(+model)
                seq_ax = ("data", "model") if seq_model_ax else "data"
            else:
                seq_ax = seq_model_ax
            return _fit(P(None, batch_ax, seq_ax, kv_ax, hd_ax), leaf.shape)
        if names[-1] in ("conv", "shift"):  # [n_blocks, B, w, di]
            return _fit(P(None, batch_ax, None, "model"), leaf.shape)
        if names[-1] == "ssm":  # [n_blocks, B, di, d_state]
            return _fit(P(None, batch_ax, "model", None), leaf.shape)
        if names[-1] == "state":  # rwkv [n_blocks, B, h, hd, hd]
            return _fit(P(None, batch_ax, "model", None, None), leaf.shape)
        return P(*([None] * ndim))

    return spec


def sample_loop(params, cfg: ModelConfig, batch, *, steps: int,
                max_len: int, temperature: float = 0.0, key=None):
    """Greedy / temperature sampling driver (examples + integration tests)."""
    logits, cache = tf.prefill(params, cfg, batch, max_len)
    toks = []
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    decode = jax.jit(make_decode_step(cfg))
    for i in range(steps):
        toks.append(tok)
        logits, cache = decode(params, cache, tok)
        if temperature > 0:
            key = jax.random.fold_in(key, i)
            tok = jax.random.categorical(key, logits / temperature)[:, None]
            tok = tok.astype(jnp.int32)
        else:
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    return jnp.concatenate(toks, axis=1)
