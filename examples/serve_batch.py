"""Batched serving examples.

Default (LM) mode: prefill a batch of prompts, then decode with the
per-architecture KV/state caches (attention KV, Mamba conv+SSM state, RWKV
wkv state, sliding-window ring buffers) — the same make_prefill /
make_decode_step functions the multi-pod dry-run lowers for the
decode_32k / long_500k shapes.

``--figaro`` mode: the linear-algebra-over-joins serving path — one join
structure, a stream of single requests submitted to the async pipelined
server (`Session(mesh=...)` ... ``ds.serve()`` -> ``submit`` -> futures):
pending requests coalesce into bucketed micro-batches sharded over the
local ``data`` mesh, queue depth 2 overlaps the next batch's staging with
the in-flight dispatch, and a streaming ``server.append`` rides the same
stream with zero retraces. One cached executable per (plan signature, mesh
signature) answers every coalesced batch.

Run:  PYTHONPATH=src python examples/serve_batch.py [--arch rwkv6-1.6b]
      PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
          python examples/serve_batch.py --figaro [--batch 8]
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def lm_demo(args) -> None:
    from repro.configs import get_config
    from repro.models import transformer as tf
    from repro.train.serve import sample_loop

    cfg = get_config(args.arch, smoke=True)
    params = tf.init_params(jax.random.PRNGKey(0), cfg)

    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                          (args.batch, args.prompt_len), 0,
                                          cfg.vocab)}
    if cfg.is_enc_dec:
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(2), (args.batch, cfg.encoder_len, cfg.d_model),
            jnp.bfloat16)
    if cfg.patch_positions:
        batch["patches"] = jax.random.normal(
            jax.random.PRNGKey(3),
            (args.batch, cfg.patch_positions, cfg.d_model), jnp.bfloat16)

    max_len = args.prompt_len + args.steps + cfg.patch_positions + 1
    t0 = time.time()
    toks = sample_loop(params, cfg, batch, steps=args.steps, max_len=max_len,
                       temperature=0.8, key=jax.random.PRNGKey(4))
    dt = time.time() - t0
    toks = np.asarray(toks)
    assert toks.shape == (args.batch, args.steps)
    assert (toks >= 0).all() and (toks < cfg.vocab).all()
    tput = args.batch * args.steps / dt
    print(f"arch           : {cfg.name}")
    print(f"generated      : {toks.shape} tokens  "
          f"(first row: {toks[0][:12]}...)")
    print(f"decode rate    : {tput:.1f} tok/s total "
          "(1 CPU core, reduced config)")
    print("OK — batched prefill+decode with per-arch caches.")


def figaro_demo(args) -> None:
    jax.config.update("jax_enable_x64", True)
    from repro import figaro
    from repro.launch.mesh import make_data_mesh

    rng = np.random.default_rng(0)
    tables = {
        "Orders": ({"cust": rng.integers(0, 50, 1500),
                    "prod": rng.integers(0, 30, 1500)},
                   rng.normal(size=(1500, 2)), ["amount", "qty"]),
        "Customers": ({"cust": np.arange(50)}, rng.normal(size=(50, 3)),
                      ["age", "income", "tenure"]),
        "Products": ({"prod": np.arange(30)}, rng.normal(size=(30, 2)),
                     ["price", "weight"]),
    }
    edges = [("Orders", "Customers"), ("Orders", "Products")]

    # One Session owns the mesh + dtype policy; every batched dispatch it
    # makes shards the request axis over mesh["data"] via shard_map.
    mesh = make_data_mesh()  # every local device on a 1-D `data` axis
    sess = figaro.Session(mesh=mesh, dtype=jnp.float64)
    ds = sess.ingest(tables).join("Orders", edges)
    serve_qr = ds.serve(kind="qr", max_batch=args.batch, queue_depth=2)
    serve_lsq = ds.serve(kind="lsq", label_col="amount")

    def requests(k=None):
        return [tuple(np.asarray(d) * (1.0 + 0.02 * i) for d in ds.plan.data)
                for i in range(args.batch if k is None else k)]

    # -- async submit: single requests coalesce into one sharded dispatch ----
    serve_qr.pause()  # pre-load the queue -> one maximally-coalesced batch
    futures = [serve_qr.submit(r) for r in requests()]
    serve_qr.resume()
    rs = [np.asarray(f.result()) for f in futures]  # submission order
    n = ds.plan.num_cols
    assert all(r.shape == (n, n) for r in rs)

    # warm path: pipelined submit stream. pause() pre-loads the queue so the
    # timed stream coalesces into the SAME batch bucket the warm-up compiled
    # — an unpaused race could split it into fresh (uncompiled) buckets and
    # report XLA compilation as serving latency.
    reqs = requests()
    serve_qr.pause()
    futures = [serve_qr.submit(r) for r in reqs]
    t0 = time.time()
    serve_qr.resume()
    rs2 = [np.asarray(f.result()) for f in futures]
    dt = time.time() - t0
    for a, b in zip(rs, rs2):
        assert np.abs(a - b).max() < 1e-9

    # streaming append joins the same stream — shared plan, zero retraces
    in_cap = serve_qr.append("Orders", ({"cust": rng.integers(0, 50, 4),
                                         "prod": rng.integers(0, 30, 4)},
                                        rng.normal(size=(4, 2))))
    live = tuple(rng.normal(size=(ds.stats()["nodes"][nm]["live_rows"],
                                  ds.tree.db[nm].num_data_cols))
                 for nm in ds.tree.preorder())
    serve_qr.submit(live).result()
    assert ds.plan is serve_qr.plan  # one plan state, no fork

    betas, resids = serve_lsq(tuple(np.stack(leaves) for leaves in
                                    zip(*requests())))
    assert betas.shape == (args.batch, n - 1)
    stats = ds.stats()
    print(f"mesh           : {mesh.shape['data']} device(s) on axis 'data'")
    print(f"requests       : {args.batch} futures -> coalesced micro-batches "
          f"(bucketed to a multiple of the mesh inside the engine)")
    print(f"qr stream      : {dt * 1e3:.1f} ms pipelined "
          f"({dt * 1e3 / args.batch:.2f} ms/request, queue depth 2)")
    print(f"append         : in_capacity={in_cap} "
          f"(zero retraces while live sizes fit)")
    print(f"compilations   : qr={stats['traces']['qr_batched']}, "
          f"lsq={stats['traces']['least_squares_batched']} "
          "(one per plan+mesh+bucket signature)")
    serve_qr.close()
    serve_lsq.close()
    print("OK — async sharded FiGaRo serving off one cached executable.")


def main() -> None:
    ap = argparse.ArgumentParser()
    from repro.configs import ARCH_NAMES
    ap.add_argument("--arch", choices=ARCH_NAMES, default="granite-3-8b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--figaro", action="store_true",
                    help="serve FiGaRo factorizations over the data mesh "
                         "instead of the LM demo")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.figaro:
        figaro_demo(args)
    else:
        lm_demo(args)


if __name__ == "__main__":
    main()
