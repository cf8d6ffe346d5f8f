"""The paper's protocol as a multi-pod collective schedule (DESIGN.md §3).

Each pod is one VFL party: party-private features and extractor weights live
in that pod (sharded over the pod's own data/model axes); true labels live
with the "server" which we co-locate with party 0. The *only* tensors that
may cross the pod axis are the ones the protocol exchanges:

  vanilla VFL   : per training step — all-gather of minibatch representations
                  (+ the implicit partial-grad return inside the same jitted
                  step), i.e. Θ(steps) pod-crossing collectives;
  one-shot VFL  : the whole session is ONE jitted program with exactly three
                  rep/grad exchanges; all local-SSL iterations run inside a
                  lax.fori_loop with zero pod-axis communication.

Both schedules are expressed with shard_map over the "pod" axis so the
dry-run's HLO makes the collective-count difference inspectable — this is
the paper's 330× communication claim restated in collectives.

The party-local computation is NOT a toy re-implementation: the extractor is
``repro.models.make_mlp_extractor``, the pseudo-labels come from the real
jittable k-means (``repro.core.clustering``), and the SSL iterations inside
the fori_loop are the engine's ``make_ssl_step_fn`` — the same step function
``repro.core.protocol`` trains with (DESIGN.md §2). The collective counts
below are therefore measured against the real local training program.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import clustering
from repro.core.ssl import SSLConfig, cross_entropy
from repro.engine.local_ssl import (PartyParams, SSLHParams, make_ssl_optimizer,
                                    make_ssl_step_fn)
from repro.models.extractors import make_classifier, make_mlp_extractor


def _make_extractor(feat_dim: int, hidden: int, rep_dim: int):
    del feat_dim  # the apply fn reads the input dim from the params
    return make_mlp_extractor(rep_dim=rep_dim, hidden=(hidden,))


def extractor_shapes(feat_dim: int, hidden: int, rep_dim: int, parties: int):
    """ShapeDtypeStructs of the per-party extractor params (leading pod dim),
    matching ``make_mlp_extractor(rep_dim, hidden=(hidden,))``'s pytree."""
    return {
        "w0": jax.ShapeDtypeStruct((parties, feat_dim, hidden), jnp.float32),
        "b0": jax.ShapeDtypeStruct((parties, hidden), jnp.float32),
        "w1": jax.ShapeDtypeStruct((parties, hidden, rep_dim), jnp.float32),
        "b1": jax.ShapeDtypeStruct((parties, rep_dim), jnp.float32),
    }


def make_vanilla_vfl_step(mesh: Mesh, feat_dim: int, hidden: int, rep_dim: int,
                          num_classes: int, lr: float = 0.01) -> Callable:
    """One SplitNN iteration: reps all-gather across pods, joint loss, local
    backprop. Inputs carry a leading party axis sharded over "pod"."""
    ext = _make_extractor(feat_dim, hidden, rep_dim)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P("pod"), P("pod", "data"), P("data"), P(None, None)),
        out_specs=(P("pod"), P()),
        check_vma=False)
    def step(params, x, y, w_head):
        # params leaves (1, f, h) locally; x (1, b_local, f)
        wp = jax.tree_util.tree_map(lambda a: a[0], params)
        xl = x[0]

        def loss_fn(wp):
            rep = ext.apply(wp, xl)                         # (b, r)
            # ① upload: all-gather representations across parties (pod axis)
            reps = jax.lax.all_gather(rep, "pod")           # (K, b, r)
            joint = jnp.moveaxis(reps, 0, 1).reshape(xl.shape[0], -1)
            logits = joint @ w_head
            return jnp.mean(cross_entropy(logits, y))

        # ② the partial-grad return is the transpose of the all-gather
        loss, grads = jax.value_and_grad(loss_fn)(wp)
        new = jax.tree_util.tree_map(lambda p, g: p - lr * g, wp, grads)
        new = jax.tree_util.tree_map(lambda a: a[None], new)
        return new, jnp.array([loss])[0]

    return step


def make_oneshot_vfl_session(mesh: Mesh, feat_dim: int, hidden: int,
                             rep_dim: int, num_classes: int,
                             local_steps: int, lr: float = 0.01,
                             rep_dtype=jnp.float32,
                             kmeans_iters: int = 8,
                             ssl_cfg: SSLConfig = SSLConfig(modality="tabular"),
                             ) -> Callable:
    """The WHOLE one-shot session as one program with exactly 3 pod-axis
    exchanges: reps up → partial grads down → refreshed reps up. Everything
    between the exchanges is party-local: the real jittable k-means over the
    returned partial gradients (Alg. 1 l.28, restarts=1 to keep the compiled
    program lean) and ``local_steps`` iterations of the engine's SSL step —
    full-batch FixMatch-tab on (overlap ∘ pseudo-labels, private pool) — in
    a lax.fori_loop with zero collectives inside."""
    ext = _make_extractor(feat_dim, hidden, rep_dim)
    head = make_classifier(num_classes)
    tx = make_ssl_optimizer(SSLHParams(epochs=0, learning_rate=lr))
    ssl_step = make_ssl_step_fn(ext, head, ssl_cfg, tx)

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P("pod"), P("pod", "data"), P("pod", "data"),
                  P("data"), P(None, None)),
        out_specs=(P("pod"), P()),
        check_vma=False)
    def session(params, x_o, x_u, y, w_head):
        wp = jax.tree_util.tree_map(lambda a: a[0], params)
        xo, xu = x_o[0], x_u[0]
        my = jax.lax.axis_index("pod")

        # ①: upload overlap reps (all-gather = pod exchange #1) — §Perf C:
        # the exchange payload travels in rep_dtype (bf16 halves inter-pod
        # bytes; the paper's accounting assumes f32)
        rep_o = ext.apply(wp, xo)
        # optimization_barrier keeps the cast from being folded away by the
        # excess-precision simplifier — the wire format really is rep_dtype
        rep_q = jax.lax.optimization_barrier(rep_o.astype(rep_dtype))
        reps = jax.lax.optimization_barrier(
            jax.lax.all_gather(rep_q, "pod"))   # exchange 1
        joint = jnp.moveaxis(reps, 0, 1).reshape(xo.shape[0], -1).astype(jnp.float32)

        # ②: partial gradients of the server loss wrt local reps — computed
        # where the labels are and returned to each party (exchange #2 is the
        # transpose of the gather; expressed via psum of the masked grad)
        def server_loss(j):
            return jnp.mean(cross_entropy(j @ w_head, y))

        g_joint = jax.grad(server_loss)(joint)              # (b, K·r)
        g_local = jax.lax.dynamic_slice_in_dim(g_joint, my * rep_dim, rep_dim, 1)
        g_q = jax.lax.optimization_barrier(g_local.astype(rep_dtype))
        g_local = (jax.lax.optimization_barrier(jax.lax.psum(g_q, "pod"))
                   / jax.lax.psum(1, "pod")).astype(jnp.float32)  # exchange 2

        # ③: pseudo-labels — the REAL gradient k-means (party-local; the
        # whole Lloyd loop runs inside this program with no collectives)
        k_km = jax.random.fold_in(jax.random.PRNGKey(0), my)
        pseudo = clustering.gradient_pseudo_labels(
            k_km, g_local, num_classes, kmeans_iters, use_kernel=False,
            restarts=1)

        # ④: LOCAL SSL via the engine step — zero pod-axis collectives
        # inside this loop. Full-batch: labeled = (overlap, pseudo),
        # unlabeled = the party-private pool.
        h_params = head.init(jax.random.fold_in(jax.random.PRNGKey(1), my),
                             ext.apply(wp, xo[:1]))
        fm = jnp.mean(xu, axis=0)            # party-local x̄ for FixMatch-tab
        pp = PartyParams(wp, h_params)
        opt_state = tx.init(pp)
        k_ssl = jax.random.fold_in(jax.random.PRNGKey(2), my)

        def local_step(i, carry):
            pp, opt_state = carry
            pp, opt_state, _ = ssl_step(pp, opt_state, fm,
                                        jax.random.fold_in(k_ssl, i),
                                        xo, pseudo, xu)
            return pp, opt_state

        pp, _ = jax.lax.fori_loop(0, local_steps, local_step, (pp, opt_state))
        wp = pp.extractor

        # ⑤: refreshed overlap reps up (exchange #3)
        rep_o2 = ext.apply(wp, xo)
        rep2_q = jax.lax.optimization_barrier(rep_o2.astype(rep_dtype))
        reps2 = jax.lax.optimization_barrier(
            jax.lax.all_gather(rep2_q, "pod"))  # exchange 3
        joint2 = jnp.moveaxis(reps2, 0, 1).reshape(xo.shape[0], -1).astype(jnp.float32)
        final_loss = jnp.mean(cross_entropy(joint2 @ w_head, y))

        wp = jax.tree_util.tree_map(lambda a: a[None], wp)
        return wp, final_loss

    return session


def count_pod_collectives(compiled_text: str, parties: int = 2) -> Dict[str, int]:
    """Count collectives (and their payload bytes) whose replica groups span
    pods, vs pod-internal ones."""
    import re
    dtype_bytes = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "pred": 1}
    pod_crossing = 0
    internal = 0
    crossing_bytes = 0
    for m in re.finditer(
            r"= ([a-z0-9]+)\[([0-9,]*)\][^\n]*?(all-gather|all-reduce|"
            r"reduce-scatter|all-to-all|collective-permute)[^\n]*"
            r"replica_groups=\{\{([0-9,]+)", compiled_text):
        dt, dims, kind, group_s = m.groups()
        group = [int(v) for v in group_s.split(",")]
        if len(group) >= 2 and max(group) - min(group) >= 256:
            pod_crossing += 1
            n = 1
            for d in (dims.split(",") if dims else []):
                n *= int(d)
            crossing_bytes += n * dtype_bytes.get(dt, 4)
        else:
            internal += 1
    return {"pod_crossing": pod_crossing, "pod_internal": internal,
            "pod_crossing_bytes": crossing_bytes}
