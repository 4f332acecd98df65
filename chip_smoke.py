#!/usr/bin/env python3
"""Chip smoke run: the FiGaRo serving path on a TPU, through its entry points.

One process, no child processes, no ``XLA_FLAGS``. With no arguments (one
chip) it runs, in order:

  1. full size: ``figaro.Session().from_tree(yelp_like(scale=1_000_000))``
     serves ``qr``, ``svd``, ``pca`` and ``lsq`` (4 requests each, pre-loaded
     with ``pause()``/``resume()`` so they coalesce into one batch), then
     ``qr`` again on ``Session(dtype=float32, use_kernel=True)``, the fused
     Pallas path, at the largest halved scale whose program the compiler
     fits on one chip (``KERNEL_SCALE_CUT`` records the cut and the numbers
     that forced it). No reference exists at this size: every output must be
     finite and every R upper-triangular. The gap between σ(R) from ``qr``
     and ``svd``'s σ is printed, not judged.
  2. reference: the same kinds at ``yelp_like``'s default scale (a 600-row
     Review table), compared with numpy float64 over the materialized join
     (`repro.core.materialize.materialize_join`).

``--chips 4`` runs only the four-chip phase: one coalesced ``qr`` serve
batch over a 4-device ``data`` mesh and ``partitioned_figaro_qr`` over that
mesh, each compared with the same work on one device.

Tolerances follow the paper's error model as the numerics sanitizer states
it (`repro.sanitizer.numerics.error_budget`): FiGaRo's rounding error grows
with the database size, not the join size, so a relative error is held to

    eps(dtype) * 64 * database_rows

— about 1e-11 for the float64 kinds and 7e-3 for float32 ``qr`` at the
reference size (about 900 database rows). Errors are relative Frobenius
norms: R after sign normalization (diag >= 0), σ, the PCA eigenvalues and
the lsq β. The four-chip phase compares float32 results with the same work
on one device, to ``FOUR_CHIP_RTOL``.

The last line of standard output is ``{"ok": true, "device": {...}}`` only
when every check passed on a TPU. Any failure, and any run that finds no
TPU, exits non-zero without that line.

Run:  python3 chip_smoke.py                       (one TPU chip)
      python3 chip_smoke.py --chips 4             (four TPU chips)
      JAX_PLATFORMS=cpu python3 chip_smoke.py --tiny
            (rehearsal at a tiny scale with the kernels interpreted; it
             runs every phase and still refuses to print the ok line)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from pathlib import Path

FULL_SCALE = 1_000_000  # yelp_like: 2M Review rows, capacity 2^21
TINY_SCALE = 2_000
FOUR_CHIP_SCALE = 100_000
# Four chips vs one device: the same float32 arithmetic placed on other
# devices, where only the reduction order may differ (partitioned QR
# combines partial Rs by TSQR), so agreement is held to ~100 float32 eps.
# The database-size budget would be vacuous here (2.2 at 294k rows).
FOUR_CHIP_RTOL = 1e-5
REQUESTS = 4
KINDS = ("qr", "svd", "pca", "lsq")
LABEL = "stars"  # Review's one column: the lsq target
PERTURB = 0.1    # requests: plan leaves times (1 + 0.1·N(0, 1))


def say(phase: str, **fields) -> None:
    print(phase, " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (all threads)."""

    def __init__(self, monitoring):
        self._lock = threading.Lock()
        self.total = 0.0
        monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            with self._lock:
                self.total += secs

    def read(self) -> float:
        with self._lock:
            return self.total


class Smoke:
    def __init__(self, jax, np, clock):
        self.jax, self.np, self.clock = jax, np, clock
        self.failures: list[str] = []

    def check(self, ok: bool, what: str) -> None:
        if not ok:
            self.failures.append(what)
            say("FAIL", what=what)

    def peak_bytes(self, device=None, key="peak_bytes_in_use"):
        """A device memory peak: ``peak_bytes_in_use`` counts arrays,
        ``peak_bytes_reserved`` the programs' scratch space."""
        stats = (device or self.jax.devices()[0]).memory_stats()
        return None if not stats else stats.get(key)

    def host(self, out):
        return self.jax.tree.map(self.np.asarray, out)

    def finite(self, outs) -> bool:
        np = self.np
        return all(bool(np.isfinite(leaf).all())
                   for leaf in self.jax.tree.leaves(outs))

    def serve(self, ds, kind: str, requests, label_col=None):
        """One coalesced batch through ``ds.serve``; results in submission
        order (device arrays) plus what the run printed."""
        kw = {"label_col": label_col} if kind == "lsq" else {}
        server = ds.serve(kind=kind, max_batch=len(requests), **kw)
        try:
            c0 = self.clock.read()
            server.pause()  # pre-load: one maximally coalesced batch
            futures = [server.submit(r) for r in requests]
            t0 = time.perf_counter()
            server.resume()
            outs = [futures[0].result()]
            first = time.perf_counter() - t0
            outs += [f.result() for f in futures[1:]]
            batch = time.perf_counter() - t0
            isolated = server.stats()["isolated_redispatches"]
        finally:
            server.close()
        info = dict(compile_s=self.clock.read() - c0, first_result_s=first,
                    batch_s=batch, isolated_redispatches=isolated,
                    peak_bytes_in_use=self.peak_bytes(),
                    peak_bytes_reserved=self.peak_bytes(
                        key="peak_bytes_reserved"))
        self.check(isolated == 0,
                   f"{kind}: {isolated} isolated re-dispatches (a coalesced "
                   f"batch failed and was answered request by request)")
        return outs, info


def capacities(ds) -> str:
    return ",".join(f"{name}:{n['capacity_rows']}"
                    for name, n in ds.stats()["nodes"].items())


def perturbed(leaves, rng, n: int, np):
    return [tuple(np.asarray(d) * (1.0 + PERTURB * rng.standard_normal(
        np.shape(d))) for d in leaves) for _ in range(n)]


def sign_normalized(r, np):
    s = np.sign(np.diag(r))
    return r * np.where(s == 0, 1.0, s)[:, None]


def rel(a, b, np) -> float:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-300))


KERNEL = {"dtype": "float32", "use_kernel": True}
# The one scale cut of the full-size phase: the TPU compiler refuses the
# batch-of-4 kernel qr program for one v5e (15.75 GB of HBM usable) at
# 1,000,000 (34.55 GB) and at 500,000 (17.28 GB); it takes it at 250,000.
# Every other kind runs at full scale.
KERNEL_SCALE_CUT = 4


def run_full(sm: Smoke, scale: int, seed: int) -> None:
    """Phase 1: the full-size serving path (no reference at this size)."""
    from repro import figaro
    np = sm.np

    results = serve_at_scale(sm, figaro.Session(), scale, KINDS, seed)
    gap = max(float(np.abs(np.linalg.svd(
        r.astype(np.float64), compute_uv=False) - s).max() / s[0])
        for r, (s, _) in zip(results["qr"], results["svd"]))
    say(f"full scale={scale} sigma", qr_float32_vs_svd_float64_max_rel_gap=gap)
    serve_at_scale(sm, figaro.Session(**KERNEL), scale // KERNEL_SCALE_CUT,
                   ("qr",), seed)


def serve_at_scale(sm: Smoke, sess, scale: int, kinds, seed: int) -> dict:
    """Serve one coalesced batch of each kind over ``yelp_like(scale)``;
    checks finiteness, R's shape and, on the kernel path, that the compiled
    program holds the Pallas kernels. Returns host results by kind."""
    from repro.data.relational import yelp_like
    np = sm.np

    t0 = time.perf_counter()
    tree = yelp_like(scale=scale)
    ds = sess.from_tree(tree)
    plan = ds.plan
    tag = f"full scale={scale}"
    say(f"{tag} setup", db_rows=tree.db.total_rows, num_cols=plan.num_cols,
        capacities=capacities(ds), r0_rows=plan.spec.r0_rows,
        host_setup_s=time.perf_counter() - t0)
    requests = perturbed(plan.data, np.random.default_rng(seed + scale),
                         REQUESTS, np)
    results = {}
    for kind in kinds:
        name = f"{kind} use_kernel=True" if sess.use_kernel else kind
        outs, info = sm.serve(ds, kind, requests, label_col=LABEL)
        outs = results[kind] = sm.host(outs)
        sm.check(sm.finite(outs), f"{tag} {name}: non-finite output")
        if kind == "qr":
            sm.check(all(not np.tril(r, -1).any() for r in outs),
                     f"{tag} {name}: R not upper-triangular")
        if sess.use_kernel:
            has = "tpu_custom_call" in compiled_text(
                sess.engine, f"{kind}_batched", plan)
            sm.check(has, f"{tag} {name}: no tpu_custom_call in the "
                     f"compiled program")
            info["tpu_custom_call"] = has
        say(f"{tag} {name}", **info)
    say(f"{tag} traces", session={k: getattr(sess, k) for k in KERNEL},
        **ds.stats()["traces"])
    return results


def compiled_text(engine, kind: str, plan) -> str:
    """Compiled HLO of the engine's one cached ``kind`` executable, lowered
    again from its dispatch signature (a persistent-cache hit)."""
    import jax

    (key, fn), = [(k, f) for k, f in engine._jitted.items() if k[0] == kind]
    _, _, _, _, _, data_sig, options = key
    data = tuple(jax.ShapeDtypeStruct(shape, dtype) for shape, dtype in data_sig)
    return fn.lower(plan.without_data(), data, **dict(options)) \
        .compile().as_text()


def references(tree, label: int, np):
    """float64 numpy answers over the materialized join."""
    from repro.core.materialize import materialize_join

    a = materialize_join(tree)
    feats = [j for j in range(a.shape[1]) if j != label]
    evals = np.linalg.eigvalsh(np.cov(a, rowvar=False))[::-1]
    return {
        "join_rows": a.shape[0],
        "qr": sign_normalized(np.linalg.qr(a, mode="r"), np),
        "svd": np.linalg.svd(a, compute_uv=False),
        "pca": np.maximum(evals, 0.0),
        "lsq": np.linalg.lstsq(a[:, feats], a[:, label], rcond=None)[0],
    }


def run_reference(sm: Smoke, seed: int) -> None:
    """Phase 2: the same kinds at a size whose join can be materialized."""
    from repro import figaro
    from repro.core.join_tree import JoinTree, build_plan
    from repro.core.relation import Database, Relation
    from repro.data.relational import yelp_like
    from repro.sanitizer.numerics import error_budget
    np = sm.np

    tree = yelp_like()
    rng = np.random.default_rng(seed + 1)
    trees = []
    for _ in range(REQUESTS):  # perturb the relations, then take plan leaves
        rels = {r.name: Relation(r.name, r.key_attrs, r.data_attrs, r.keys,
                                 r.data * (1.0 + PERTURB * rng.standard_normal(
                                     r.data.shape)))
                for r in tree.db}
        trees.append(JoinTree(Database(rels), dict(tree.parent)))
    requests = [build_plan(t).data for t in trees]
    ds = figaro.Session().from_tree(tree)
    _ = ds.plan  # build the capacity plan now, so its sizes can be printed
    label = ds.column_index(LABEL)
    refs = [references(t, label, np) for t in trees]
    db_rows = tree.db.total_rows
    say("reference setup", scale="default", db_rows=db_rows,
        join_rows=refs[0]["join_rows"], capacities=capacities(ds))

    def got(kind, out):  # R, (σ, Vᵀ), PCAResult, (β, residual)
        return out if kind == "qr" else \
            out.explained_variance if kind == "pca" else out[0]

    runs = [(kind, ds, kind) for kind in KINDS]
    ds_k = figaro.Session(**KERNEL).from_tree(tree)
    runs.append(("qr use_kernel=True", ds_k, "qr"))
    for name, d, kind in runs:
        outs, info = sm.serve(d, kind, requests, label_col=LABEL)
        outs = sm.host(outs)
        dtype = np.asarray(got(kind, outs[0])).dtype
        budget = error_budget(dtype, db_rows)
        err = max(rel(got(kind, o), ref[kind], np)
                  for o, ref in zip(outs, refs))
        sm.check(sm.finite(outs), f"reference {name}: non-finite output")
        sm.check(err <= budget, f"reference {name}: error {err!r} above "
                 f"budget {budget!r}")
        say(f"reference {name}", dtype=dtype.name, max_rel_err=err,
            budget=budget, **info)


def run_four_chips(sm: Smoke, scale: int, seed: int) -> None:
    """The four-chip phase: sharded serving and partitioned QR, each
    against the same work on one device."""
    import jax.numpy as jnp

    from repro import figaro
    from repro.data.relational import yelp_like
    from repro.launch.mesh import make_data_mesh
    jax, np = sm.jax, sm.np

    devices = jax.devices()[:4]
    mesh = make_data_mesh(4)
    tree = yelp_like(scale=scale)
    budget = FOUR_CHIP_RTOL
    say("four setup", scale=scale, db_rows=tree.db.total_rows,
        mesh=dict(mesh.shape), rtol=budget)

    # Partitioned QR first, so each device's peak shows whether it worked.
    before = [sm.peak_bytes(d) for d in devices]
    c0, t0 = sm.clock.read(), time.perf_counter()
    r_part = np.asarray(figaro.Session(mesh=mesh).partitioned_qr(
        tree, 4, dtype=jnp.float32))
    part_s, part_c = time.perf_counter() - t0, sm.clock.read() - c0
    after = [sm.peak_bytes(d) for d in devices]
    worked = [b is not None and a is not None and b > a
              for a, b in zip(before, after)]
    ds1 = figaro.Session().from_tree(tree)
    r_one = np.asarray(ds1.qr())
    err = rel(sign_normalized(r_part, np), r_one, np)
    sm.check(all(worked), f"partitioned qr: devices that allocated "
             f"{worked}, expected all 4")
    sm.check(sm.finite(r_part) and err <= budget,
             f"partitioned qr vs one device: error {err!r} above {budget!r}")
    say("four partitioned_qr", parts=4, dtype="float32", seconds=part_s,
        compile_s=part_c, peak_bytes_before=before, peak_bytes_after=after,
        max_rel_err_vs_one_device=err)

    dsm = figaro.Session(mesh=mesh).from_tree(tree)
    requests = perturbed(ds1.plan.data, np.random.default_rng(seed),
                         REQUESTS, np)
    outs_m, info_m = sm.serve(dsm, "qr", requests)
    served_on = set().union(*(o.devices() for o in outs_m))
    outs_1, info_1 = sm.serve(ds1, "qr", requests)
    batch = dsm.qr(tuple(np.stack(leaves) for leaves in zip(*requests)))
    span = len(batch.sharding.device_set)
    err = max(rel(a, b, np) for a, b in zip(sm.host(outs_m), sm.host(outs_1)))
    err_batch = rel(batch, np.stack(sm.host(outs_1)), np)
    sm.check(span == 4, f"sharded qr batch spans {span} devices, expected 4")
    sm.check(len(served_on) == 4,
             f"served results live on {len(served_on)} devices, expected 4")
    sm.check(sm.finite(sm.host(outs_m)) and max(err, err_batch) <= budget,
             f"sharded qr vs one device: error {max(err, err_batch)!r} "
             f"above {budget!r}")
    say("four sharded qr", batch_devices=span,
        served_result_devices=len(served_on), max_rel_err_vs_one_device=err,
        direct_batch_rel_err=err_batch, **info_m)
    say("four one-device qr", **info_1)
    say("four traces", sharded=dsm.stats()["traces"],
        one_device=ds1.stats()["traces"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the request perturbations")
    ap.add_argument("--tiny", action="store_true",
                    help=f"rehearsal at scale {TINY_SCALE}: runs on any "
                         f"backend, but prints the ok line only on a TPU")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    # The TPU library logs under /tmp unless told otherwise.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        import jax
        import numpy as np
        from jax import monitoring

        from repro.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repo's code: {e}",
              file=sys.stderr)
        return 2

    jax.config.update("jax_enable_x64", True)
    cache = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    say("device", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(devices), compile_cache=cache)
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.tiny:
        print(f"chip_smoke: no TPU (platform {dev.platform!r}); nothing run",
              file=sys.stderr)
        return 1
    if args.chips == 4 and len(devices) < 4:
        print(f"chip_smoke: --chips 4 needs 4 devices, found {len(devices)}",
              file=sys.stderr)
        return 1

    sm = Smoke(jax, np, CompileClock(monitoring))
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips(sm, TINY_SCALE if args.tiny else FOUR_CHIP_SCALE,
                           args.seed)
        else:
            run_full(sm, TINY_SCALE if args.tiny else FULL_SCALE, args.seed)
            run_reference(sm, args.seed)
    except Exception as e:  # report, then fail without the ok line
        import traceback

        traceback.print_exc()
        sm.check(False, f"{type(e).__name__}: {e}")
    sm.check(on_tpu, f"ran on {dev.platform!r}, not a TPU")
    say("done", seconds=time.perf_counter() - t0,
        compile_s=sm.clock.read(), failures=len(sm.failures))
    if sm.failures:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
