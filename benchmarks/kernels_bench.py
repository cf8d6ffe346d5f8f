"""Kernel + engine microbenchmarks.

Kernels: interpret-mode correctness + host-timed oracle comparison across
the hot-spot shapes. On-TPU timing needs real hardware; here ``us_per_call``
is the pure-jnp oracle (the XLA-fused baseline the Pallas kernel must beat
on TPU), and ``derived`` records kernel/oracle max-abs error.

Batched grids (DESIGN.md §15): for the two protocol hot-spots (k-means
assignment, SDPA estimation) the fold-native entries are timed three ways —
ONE ``(B, …)`` grid launch vs ``jax.vmap`` of the XLA reference vs B
sequential single-instance kernel launches — so the crossover the ops.py
headers cite is measured, not asserted. Under CPU interpret mode the
absolute kernel numbers are interpretation overhead; the launch-count
ratio (one dispatch vs B dispatches) is the portable signal.

Engine: end-to-end wall time of one multi-client local-SSL session, the
vmap-over-clients scanned program, including trace/compile (what a
protocol run actually pays).

``--out BENCH_kernels.json`` records every row (name, us_per_call, derived)
plus backend/interpret context as a JSON blob.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp


def _time(fn, *args, iters=3):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / iters * 1e6


def _emit(rows, name, us, derived=""):
    print(f"{name},{us:.1f},{derived}")
    rows.append({"name": name, "us_per_call": round(us, 1),
                 "derived": derived})


def bench_engine(rows) -> None:
    """One homogeneous 4-party SSL session, compile included."""
    from repro import engine
    from repro.core.ssl import SSLConfig
    from repro.models.extractors import make_classifier, make_mlp_extractor

    parties, n_l, n_u, feat = 4, 256, 1024, 32
    ext = make_mlp_extractor(rep_dim=16, hidden=(64,))
    head = make_classifier(2)
    ssl_cfg = SSLConfig(modality="tabular")
    key = jax.random.PRNGKey(0)
    tasks = []
    for k in range(parties):
        kp, kl, ku, ky = jax.random.split(jax.random.fold_in(key, k), 4)
        x_l = jax.random.normal(kl, (n_l, feat))
        x_u = jax.random.normal(ku, (n_u, feat))
        y = jax.random.randint(ky, (n_l,), 0, 2)
        params = engine.PartyParams(ext.init(kp, x_l[:2]),
                                    head.init(kp, jnp.zeros((1, 16))))
        tasks.append(engine.PartyTask(ext, head, params, ssl_cfg, x_l, y, x_u,
                                      feature_mean=jnp.mean(x_u, axis=0)))
    hp = engine.SSLHParams(epochs=3, batch_size=32)

    t0 = time.time()
    params, _ = engine.train_task_groups(
        list(jax.random.split(jax.random.PRNGKey(1), parties)), tasks, hp)
    jax.block_until_ready(jax.tree_util.tree_leaves(params))
    _emit(rows, f"engine/ssl_vmap_jit/K{parties}e{hp.epochs}",
          (time.time() - t0) * 1e6)


def bench_batched_grids(rows) -> None:
    """The fold-native batched entries, three routes per kernel:

    - ``grid``  — ONE (B, …) Pallas grid launch (the fold-native entry)
    - ``vmap``  — jax.vmap of the pure-jnp XLA reference (the baseline the
                  kernel must beat on TPU)
    - ``seq``   — B sequential width-1 kernel launches (what the retired
                  per-entry fallback used to pay: B dispatches + B pads)

    Shapes are the ones the ops.py headers cite (B=8 ≙ a 2-seed × 1-group ×
    4-party stacked fold). Agreement is gated here, not just recorded: the
    batched grid must be bit-equal to the reference for k-means and ≤1e-5
    for SDPA.
    """
    from repro.kernels.kmeans import ops as km_ops, ref as km_ref
    from repro.kernels.sdpa_estimator import ops as sd_ops, ref as sd_ref

    # k-means assignment, step-③ fold shape
    b, n, d, c = 8, 2048, 128, 10
    x = jax.random.normal(jax.random.PRNGKey(0), (b, n, d))
    cen = jax.random.normal(jax.random.PRNGKey(1), (b, c, d))
    grid_fn = jax.jit(km_ops.kmeans_assign_batched)
    vmap_fn = jax.jit(jax.vmap(km_ref.kmeans_assign))

    def km_seq(x, cen):
        return [km_ops.kmeans_assign(x[i], cen[i]) for i in range(b)]

    shape = f"B{b}x{n}x{d}x{c}"
    us_grid = _time(grid_fn, x, cen)
    us_vmap = _time(vmap_fn, x, cen)
    us_seq = _time(km_seq, x, cen)
    agree = float(jnp.mean(grid_fn(x, cen) == vmap_fn(x, cen)))
    assert agree == 1.0, f"batched k-means grid != vmapped oracle ({agree})"
    _emit(rows, f"kernel/kmeans_batched_grid/{shape}", us_grid,
          f"agree={agree:.4f}")
    _emit(rows, f"kernel/kmeans_batched_vmap_ref/{shape}", us_vmap,
          f"grid_vs_vmap={us_vmap / us_grid:.2f}x")
    _emit(rows, f"kernel/kmeans_batched_seq_launch/{shape}", us_seq,
          f"grid_vs_seq={us_seq / us_grid:.2f}x")

    # SDPA estimation, few-shot ③' fold shape
    b, nu, no, d = 8, 4096, 256, 128
    hu = jax.random.normal(jax.random.PRNGKey(0), (b, nu, d))
    hoa = jax.random.normal(jax.random.PRNGKey(1), (b, no, d))
    hob = jax.random.normal(jax.random.PRNGKey(2), (b, no, d))
    grid_fn = jax.jit(sd_ops.sdpa_estimate_batched)
    vmap_fn = jax.jit(jax.vmap(sd_ref.sdpa_estimate))

    def sd_seq(hu, hoa, hob):
        return [sd_ops.sdpa_estimate(hu[i], hoa[i], hob[i]) for i in range(b)]

    shape = f"B{b}x{nu}x{no}x{d}"
    us_grid = _time(grid_fn, hu, hoa, hob)
    us_vmap = _time(vmap_fn, hu, hoa, hob)
    us_seq = _time(sd_seq, hu, hoa, hob)
    err = float(jnp.max(jnp.abs(grid_fn(hu, hoa, hob)
                                - vmap_fn(hu, hoa, hob))))
    assert err <= 1e-5, f"batched SDPA grid off the oracle by {err:.2e}"
    _emit(rows, f"kernel/sdpa_batched_grid/{shape}", us_grid,
          f"maxerr={err:.2e}")
    _emit(rows, f"kernel/sdpa_batched_vmap_ref/{shape}", us_vmap,
          f"grid_vs_vmap={us_vmap / us_grid:.2f}x")
    _emit(rows, f"kernel/sdpa_batched_seq_launch/{shape}", us_seq,
          f"grid_vs_seq={us_seq / us_grid:.2f}x")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=None,
                    help="write all rows as a JSON blob to this path")
    args = ap.parse_args(argv)

    rows: list = []
    print("name,us_per_call,derived")
    bench_engine(rows)

    # kmeans assignment: the paper's step-③ shape (N_o grads × C classes)
    from repro.kernels.kmeans import ops as km_ops, ref as km_ref
    for (n, d, c) in [(2048, 128, 10), (4096, 256, 100)]:
        x = jax.random.normal(jax.random.PRNGKey(0), (n, d))
        cen = jax.random.normal(jax.random.PRNGKey(1), (c, d))
        ref_fn = jax.jit(km_ref.kmeans_assign)
        us = _time(ref_fn, x, cen)
        agree = float(jnp.mean(km_ops.kmeans_assign(x, cen) == ref_fn(x, cen)))
        _emit(rows, f"kernel/kmeans/{n}x{d}x{c}", us, f"agree={agree:.4f}")

    # SDPA estimator: the few-shot server shape (N_u >> N_o)
    from repro.kernels.sdpa_estimator import ops as sd_ops, ref as sd_ref
    for (nu, no, d) in [(4096, 256, 128), (8192, 512, 128)]:
        hu = jax.random.normal(jax.random.PRNGKey(0), (nu, d))
        hoa = jax.random.normal(jax.random.PRNGKey(1), (no, d))
        hob = jax.random.normal(jax.random.PRNGKey(2), (no, d))
        ref_fn = jax.jit(sd_ref.sdpa_estimate)
        us = _time(ref_fn, hu, hoa, hob)
        err = float(jnp.max(jnp.abs(sd_ops.sdpa_estimate(hu, hoa, hob)
                                    - ref_fn(hu, hoa, hob))))
        _emit(rows, f"kernel/sdpa/{nu}x{no}x{d}", us, f"maxerr={err:.2e}")

    # the fold-native batched grids (DESIGN.md §15)
    bench_batched_grids(rows)

    # fused rmsnorm: per-layer shape of the biggest assigned arch
    from repro.kernels.rmsnorm import ops as rn_ops, ref as rn_ref
    for (rows_, d) in [(4096, 1024), (2048, 4096)]:
        x = jax.random.normal(jax.random.PRNGKey(0), (rows_, d))
        s = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (d,))
        ref_fn = jax.jit(rn_ref.rms_norm)
        us = _time(ref_fn, x, s)
        err = float(jnp.max(jnp.abs(rn_ops.rms_norm(x, s) - ref_fn(x, s))))
        _emit(rows, f"kernel/rmsnorm/{rows_}x{d}", us, f"maxerr={err:.2e}")

    # decode attention: serving shape
    from repro.kernels.decode_attention import ops as da_ops, ref as da_ref
    for (b, h, hkv, s, dh) in [(8, 32, 8, 2048, 128)]:
        q = jax.random.normal(jax.random.PRNGKey(0), (b, h, dh))
        kc = jax.random.normal(jax.random.PRNGKey(1), (b, hkv, s, dh))
        vc = jax.random.normal(jax.random.PRNGKey(2), (b, hkv, s, dh))
        ref_fn = jax.jit(da_ref.decode_attention)
        us = _time(ref_fn, q, kc, vc)
        err = float(jnp.max(jnp.abs(da_ops.decode_attention(q, kc, vc)
                                    - ref_fn(q, kc, vc))))
        _emit(rows, f"kernel/decode_attn/b{b}h{h}s{s}", us, f"maxerr={err:.2e}")

    if args.out:
        from repro.kernels import interpret_mode
        blob = {"backend": jax.default_backend(),
                "interpret": interpret_mode(),
                "rows": rows}
        with open(args.out, "w") as f:
            json.dump(blob, f, indent=2)
        print(f"wrote {len(rows)} rows to {args.out}")


if __name__ == "__main__":
    main()
