"""Iterative split-NN VFL sessions as ONE cached, jitted engine program.

The iterative baselines (vanilla SplitNN, the FedCVT-style cross-view
baseline) used to build an ad-hoc ``jax.jit`` step inside every
``run_*`` call: each invocation re-traced and re-compiled identical step
math, so scenario sweeps (``benchmarks/frontier.py`` runs every baseline
across an overlap sweep of one task) paid full compile time per scenario
point. This module is the iterative counterpart of ``engine.local_ssl``
(DESIGN.md §8):

* ``make_splitnn_step_fn`` — THE jointly-differentiated split-NN iteration
  (reps up, rep-gradients down; the communication is logged by the caller
  with the true tensor sizes);
* ``make_fedcvt_step_fn``  — the same iteration plus FedCVT-style
  cross-view training: unaligned batches whose missing-party reps are
  SDPA-estimated from the overlap batch join the loss when their
  pseudo-label confidence clears a threshold;
* ``make_fedbcd_step_fn`` — FedBCD-p [20]: one rep/partial-gradient
  exchange then Q parallel stale-gradient local updates per round;
* ``run_iterative_session`` — executes S iterations as one jitted
  ``lax.scan`` over a precomputed minibatch schedule;
* ``run_iterative_session_seeds`` — the seed-axis fold (DESIGN.md §11):
  every array argument carries a leading seed axis and the whole
  multi-seed session runs as ONE ``vmap``-of-``lax.scan`` program. The
  single-seed ``run_iterative_session`` is its width-1 case, so one
  cached program serves every seed count (the cache key has no batch
  width — ``jax.jit`` re-specializes per stacked shape).

Compiled callables are cached in the engine-wide session cache
(``engine.sessions``, domain ``"iterative"``), keyed on the *semantic*
identity of the party models (apply-fn code object + closure cells — the
same guarantee ``local_ssl._apply_fns_match`` relies on) plus the
optimizer hyper-parameters, so repeated sessions (another seed, another
scenario point with equal minibatch shapes) re-use the compiled program
instead of re-tracing. ``sessions.session_cache_stats("iterative")``
exposes hit/miss counters; tests pin the no-recompile contract with them.

Communication stays host-side: callers log per-round ledger events
around the jitted session, so the ledger is a function of shapes alone.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.data.loader import epoch_batches
from repro.engine import sessions
from repro.models.extractors import Model


@dataclass(frozen=True)
class IterHParams:
    """Optimizer hyper-parameters of one iterative session (hashable — part
    of the session-cache key)."""
    client_lr: float = 0.01
    server_lr: float = 0.01
    momentum: float = 0.9
    fedcvt_threshold: float = 0.95


# ------------------------------------------------------------ step factories
def make_splitnn_step_fn(extractors: Sequence[Model], classifier: Model,
                         hp: IterHParams):
    """One SplitNN iteration: joint value_and_grad over every party's
    extractor and the server classifier. Gradients are computed in one
    backward pass for efficiency, but the *communication* of the iteration
    is exactly reps-up + rep-grads-down (the caller logs it).

    Returns ``step(carry, xs, y, xs_u=None) -> (carry, loss)`` with
    ``carry = (client_params, server_params, opt_states, opt_state_s)``.
    """
    from repro.core.server import concat_reps   # deferred: core imports engine
    from repro.core.ssl import cross_entropy

    extractors = tuple(extractors)
    txs = tuple(optim.sgd(hp.client_lr, momentum=hp.momentum)
                for _ in extractors)
    tx_s = optim.sgd(hp.server_lr, momentum=hp.momentum)

    def step(carry, xs, y, xs_u=None):
        del xs_u
        cp, sp, oss, os_s = carry

        def loss_fn(cp_t, sp_):
            reps = [ext.apply(p.extractor, x)
                    for ext, p, x in zip(extractors, cp_t, xs)]
            logits = classifier.apply(sp_, concat_reps(reps))
            return jnp.mean(cross_entropy(logits, y))

        loss, (g_c, g_s) = jax.value_and_grad(loss_fn, argnums=(0, 1))(cp, sp)
        new_cp, new_os = [], []
        for p, g, tx, os_ in zip(cp, g_c, txs, oss):
            upd, os_ = tx.update(g, os_, p)
            new_cp.append(optim.apply_updates(p, upd))
            new_os.append(os_)
        upd_s, os_s = tx_s.update(g_s, os_s, sp)
        sp = optim.apply_updates(sp, upd_s)
        return (tuple(new_cp), sp, tuple(new_os), os_s), loss

    return step


def make_fedcvt_step_fn(extractors: Sequence[Model], classifier: Model,
                        hp: IterHParams):
    """SplitNN iteration + FedCVT-style cross-view expansion: each party's
    unaligned batch is completed with SDPA-estimated missing-party reps and
    joins the loss where the (stop-gradient) pseudo-label confidence clears
    ``hp.fedcvt_threshold``. Signature matches ``make_splitnn_step_fn`` with
    ``xs_u`` required."""
    from repro.core import estimator          # deferred: core imports engine
    from repro.core.server import concat_reps
    from repro.core.ssl import cross_entropy

    extractors = tuple(extractors)
    txs = tuple(optim.sgd(hp.client_lr, momentum=hp.momentum)
                for _ in extractors)
    tx_s = optim.sgd(hp.server_lr, momentum=hp.momentum)
    K = len(extractors)

    def step(carry, xs, y, xs_u):
        cp, sp, oss, os_s = carry

        def loss_fn(cp_t, sp_):
            reps_o = [ext.apply(p.extractor, x)
                      for ext, p, x in zip(extractors, cp_t, xs)]
            logits = classifier.apply(sp_, concat_reps(reps_o))
            loss = jnp.mean(cross_entropy(logits, y))
            for k_idx in range(K):
                h_u = extractors[k_idx].apply(cp_t[k_idx].extractor,
                                              xs_u[k_idx])
                parts = []
                for j in range(K):
                    if j == k_idx:
                        parts.append(h_u)
                    else:
                        parts.append(estimator.sdpa_transform(
                            h_u, reps_o[k_idx], reps_o[j]))
                logits_u = classifier.apply(sp_, concat_reps(parts))
                p_u = jax.nn.softmax(jax.lax.stop_gradient(logits_u), axis=-1)
                pseudo = jnp.argmax(p_u, axis=-1)
                mask = (jnp.max(p_u, axis=-1)
                        > hp.fedcvt_threshold).astype(jnp.float32)
                ce = cross_entropy(logits_u, pseudo)
                loss = loss + jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask),
                                                               1.0)
            return loss

        loss, (g_c, g_s) = jax.value_and_grad(loss_fn, argnums=(0, 1))(cp, sp)
        new_cp, new_os = [], []
        for p, g, tx, os_ in zip(cp, g_c, txs, oss):
            upd, os_ = tx.update(g, os_, p)
            new_cp.append(optim.apply_updates(p, upd))
            new_os.append(os_)
        upd_s, os_s = tx_s.update(g_s, os_s, sp)
        sp = optim.apply_updates(sp, upd_s)
        return (tuple(new_cp), sp, tuple(new_os), os_s), loss

    return step


def make_fedbcd_step_fn(extractors: Sequence[Model], classifier: Model,
                        hp: IterHParams, q: int):
    """One FedBCD-p communication round [20]: fresh reps up and partial
    gradients down ONCE, then ``q`` parallel local updates — clients on the
    stale rep-gradients (the ⟨stale ∂L/∂H, f_k(x;θ)⟩ surrogate), the server
    on the stale reps. Signature matches ``make_splitnn_step_fn``; the loss
    returned is the round-entry joint loss (before any local update)."""
    from repro.core.server import concat_reps   # deferred: core imports engine
    from repro.core.ssl import cross_entropy

    extractors = tuple(extractors)
    txs = tuple(optim.sgd(hp.client_lr, momentum=hp.momentum)
                for _ in extractors)
    tx_s = optim.sgd(hp.server_lr, momentum=hp.momentum)

    def step(carry, xs, y, xs_u=None):
        del xs_u
        cp, sp, oss, os_s = carry
        reps = [ext.apply(p.extractor, x)
                for ext, p, x in zip(extractors, cp, xs)]

        def rep_loss(rep_list, sp_):
            logits = classifier.apply(sp_, concat_reps(rep_list))
            return jnp.mean(cross_entropy(logits, y))

        loss, g_reps = jax.value_and_grad(rep_loss, argnums=0)(reps, sp)

        new_cp, new_os = [], []
        for ext, p, os_, tx, x, g in zip(extractors, cp, oss, txs, xs,
                                         g_reps):
            def q_body(_, c, ext=ext, tx=tx, x=x, g=g):
                p_, os__ = c

                def local_obj(pp):
                    return jnp.sum(jax.lax.stop_gradient(g)
                                   * ext.apply(pp.extractor, x))

                gq = jax.grad(local_obj)(p_)
                upd, os__ = tx.update(gq, os__, p_)
                return optim.apply_updates(p_, upd), os__

            p, os_ = jax.lax.fori_loop(0, q, q_body, (p, os_))
            new_cp.append(p)
            new_os.append(os_)

        def s_body(_, c):
            sp_, os_s_ = c
            gs = jax.grad(lambda spp: rep_loss(
                [jax.lax.stop_gradient(r) for r in reps], spp))(sp_)
            upd, os_s_ = tx_s.update(gs, os_s_, sp_)
            return optim.apply_updates(sp_, upd), os_s_

        sp, os_s = jax.lax.fori_loop(0, q, s_body, (sp, os_s))
        return (tuple(new_cp), sp, tuple(new_os), os_s), loss

    return step


# -------------------------------------------------------------- schedules
def build_iteration_schedule(seed: int, n: int, batch_size: int,
                             iterations: int) -> jnp.ndarray:
    """(S, bs) int32 minibatch indices: shuffled epochs, drop-remainder,
    truncated/cycled to exactly ``iterations`` rows — materialized up front
    so every seed's batches travel into the session as data."""
    bs = min(batch_size, n)
    if iterations <= 0:                      # a no-op session is valid
        return jnp.zeros((0, bs), jnp.int32)
    rows: List[np.ndarray] = []
    e = 0
    while len(rows) < iterations:
        for b in epoch_batches(n, bs, seed + e):
            rows.append(b)
            if len(rows) == iterations:
                break
        e += 1
    return jnp.asarray(np.stack(rows), jnp.int32)


def build_unaligned_schedule(seed: int, pool_sizes: Sequence[int],
                             batch_size: int, iterations: int
                             ) -> Tuple[jnp.ndarray, ...]:
    """Per-party (S, bs) uniform draws from each private pool (FedCVT's
    unaligned batches). An EMPTY pool (a full-overlap party) yields
    zero-width (S, 0) rows — the step's masked unaligned term then sums
    over nothing and contributes exactly 0, mirroring the SSL engine's
    ``n_unlabeled == 0`` guard (regression: the full-catalog smoke runs
    fedcvt on edge/full-overlap)."""
    rng = np.random.RandomState(seed)
    return tuple(
        jnp.zeros((iterations, 0), jnp.int32) if n_u == 0
        else jnp.asarray(rng.randint(0, n_u, size=(iterations, batch_size)),
                         jnp.int32)
        for n_u in pool_sizes)


# ---------------------------------------------------------------- sessions
def run_iterative_session_seeds(
    cache_key: tuple,
    make_step: Callable[[], Callable],
    carry,
    xs: Sequence[jnp.ndarray],
    y: jnp.ndarray,
    schedule: jnp.ndarray,
    xs_u: Optional[Sequence[jnp.ndarray]] = None,
    u_schedules: Optional[Sequence[jnp.ndarray]] = None,
    mesh=None,
    active_steps: Optional[jnp.ndarray] = None,
):
    """The seed-axis fold (DESIGN.md §11): run every seed's whole session
    as one program.

    ``active_steps`` (optional, (S,) int32 — DESIGN.md §16) is the fault
    axis: seed ``s`` commits only its first ``active_steps[s]`` steps — a
    dropped party stalls the round loop there, so the carry freezes
    (params AND optimizer state). Every step still COMPUTES (losses keep
    shape (S, iters); frozen steps report the loss at the frozen carry),
    so the faulted session is the same fixed-shape program with the
    truncation point as data. ``None`` (fault-free) keeps the historical
    cache key and program byte-identical.

    Every array argument carries a leading seed axis S: ``carry`` leaves
    are stacked on axis 0, ``xs``/``xs_u`` are per-party tuples of
    ``(S, n, d)`` stacks, ``y`` is ``(S, n)``, and the schedules are
    ``(S, iters, bs)`` — per-seed randomness lives in the schedule
    *contents*, which travel as arguments, never in the compiled program.

    It executes ONE cached ``jax.vmap``-of-``lax.scan`` program under the
    SAME session-cache key as the single-seed session (the key has no
    batch width, so folding seeds adds zero fresh session builds;
    ``jax.jit`` re-specializes the cached program per stacked shape).

    With a resolved ``mesh`` the seed axis shards over the device mesh
    (DESIGN.md §14): stacked arguments pad to a device-count multiple
    with copies of seed 0, the vmap-of-scan runs under ``shard_map``, and
    results are stripped back host-side. The cache key gains the mesh
    identity.

    Returns ``(carry, losses)`` with the same stacking and ``losses`` of
    shape ``(S, iters)``.
    """
    from repro.engine import parallel        # sibling: mesh plumbing

    mesh = parallel.resolve_mesh(mesh)
    xs = tuple(xs)
    num_seeds = schedule.shape[0]
    if schedule.shape[1] == 0:               # zero iterations: no-op session
        return carry, jnp.zeros((num_seeds, 0))
    has_u = xs_u is not None
    if has_u:
        xs_u = tuple(xs_u)
        u_schedules = tuple(u_schedules)

    # the whole multi-seed session is one jitted program with a
    # donated stacked carry — vmap's batch axis IS the seed axis. Under a
    # mesh that axis pads to a device-count multiple and shards (§14).
    # A faulted session (active_steps given) is a distinct cached program
    # (the carry-select adds structure) — the FAULT-FREE key stays
    # byte-identical to the historical one, and the truncation points
    # themselves are arguments, so faulted sweeps of any mask re-serve it.
    pad = parallel.pad_width(num_seeds, mesh)
    mkey = (parallel.mesh_key(mesh),)
    faulted = active_steps is not None
    fkey = ("faulted",) if faulted else ()
    if faulted:
        active = parallel.pad_stacked(
            jnp.asarray(active_steps, jnp.int32), pad)
    if has_u:
        def build():
            step = make_step()

            if faulted:
                def session(carry, xs, y, schedule, xs_u, u_scheds, active):
                    def body(c, inp):
                        i, il, ius = inp
                        new_c, loss = step(
                            c, tuple(x[il] for x in xs), y[il],
                            tuple(xu[iu] for xu, iu in zip(xs_u, ius)))
                        # past the truncation point the carry freezes —
                        # computed, never committed (loss stays recorded)
                        new_c = jax.tree_util.tree_map(
                            lambda a, b: jnp.where(i < active, a, b),
                            new_c, c)
                        return new_c, loss

                    steps = jnp.arange(schedule.shape[0])
                    return jax.lax.scan(body, carry,
                                        (steps, schedule, u_scheds))
            else:
                def session(carry, xs, y, schedule, xs_u, u_scheds):
                    def body(c, inp):
                        il, ius = inp
                        return step(c, tuple(x[il] for x in xs), y[il],
                                    tuple(xu[iu] for xu, iu in zip(xs_u, ius)))

                    return jax.lax.scan(body, carry, (schedule, u_scheds))

            return parallel.shard_jit(jax.vmap(session), mesh)

        session = sessions.cached_session(
            "iterative", ("scan", True) + fkey + cache_key + mkey, build)
        args = (parallel.pad_stacked(carry, pad),
                parallel.pad_stacked(xs, pad),
                parallel.pad_stacked(y, pad),
                parallel.pad_stacked(schedule, pad),
                parallel.pad_stacked(xs_u, pad),
                parallel.pad_stacked(u_schedules, pad))
        out, losses = session(*(args + (active,) if faulted else args))
        return parallel.strip_stacked(out, num_seeds), losses[:num_seeds]

    def build():
        step = make_step()

        if faulted:
            def session(carry, xs, y, schedule, active):
                def body(c, inp):
                    i, il = inp
                    new_c, loss = step(c, tuple(x[il] for x in xs),
                                       y[il], None)
                    new_c = jax.tree_util.tree_map(
                        lambda a, b: jnp.where(i < active, a, b), new_c, c)
                    return new_c, loss

                steps = jnp.arange(schedule.shape[0])
                return jax.lax.scan(body, carry, (steps, schedule))
        else:
            def session(carry, xs, y, schedule):
                def body(c, il):
                    return step(c, tuple(x[il] for x in xs), y[il], None)

                return jax.lax.scan(body, carry, schedule)

        return parallel.shard_jit(jax.vmap(session), mesh)

    session = sessions.cached_session(
        "iterative", ("scan", False) + fkey + cache_key + mkey, build)
    args = (parallel.pad_stacked(carry, pad), parallel.pad_stacked(xs, pad),
            parallel.pad_stacked(y, pad), parallel.pad_stacked(schedule, pad))
    out, losses = session(*(args + (active,) if faulted else args))
    return parallel.strip_stacked(out, num_seeds), losses[:num_seeds]


def run_iterative_session(
    cache_key: tuple,
    make_step: Callable[[], Callable],
    carry,
    xs: Sequence[jnp.ndarray],
    y: jnp.ndarray,
    schedule: jnp.ndarray,
    xs_u: Optional[Sequence[jnp.ndarray]] = None,
    u_schedules: Optional[Sequence[jnp.ndarray]] = None,
):
    """Run S = ``schedule.shape[0]`` iterations of ``make_step()``'s step —
    the width-1 case of :func:`run_iterative_session_seeds` (one cached
    program serves every seed count).

    ``cache_key`` identifies the step math (models + hyper-parameters);
    the compiled step/session is cached under it so later sessions with
    the same key (and minibatch shapes) never recompile. Training data
    travels as *arguments*, never in the cached closure, so one compiled
    session serves every seed/scenario point of equal shapes.

    Returns ``(carry, losses)`` with ``losses`` of shape (S,).
    """
    xs = tuple(xs)
    if schedule.shape[0] == 0:               # zero iterations: no-op session
        return carry, jnp.zeros((0,))
    has_u = xs_u is not None
    carry1 = jax.tree_util.tree_map(lambda a: jnp.asarray(a)[None], carry)
    out, losses = run_iterative_session_seeds(
        cache_key, make_step, carry1, tuple(x[None] for x in xs), y[None],
        schedule[None],
        xs_u=(tuple(x[None] for x in xs_u) if has_u else None),
        u_schedules=(tuple(s[None] for s in u_schedules)
                     if has_u else None))
    return jax.tree_util.tree_map(lambda a: a[0], out), losses[0]


def session_cache_key(kind: str, extractors, classifier, hp: IterHParams,
                      q: Optional[int] = None) -> tuple:
    """THE cache key of one iterative step kind ("splitnn" | "fedcvt" |
    "fedbcd"): model semantics + hyper-parameters (+ Q for FedBCD). Both
    the single-seed sessions below and the seed fold
    (``engine.batched.*_sessions_seeds``) build their keys here, so the
    width-1 program and the fold can never drift onto separate cache
    entries."""
    key = (kind, tuple(sessions.model_key(e) for e in extractors),
           sessions.model_key(classifier), hp)
    return key if q is None else key + (int(q),)


def splitnn_session(extractors, classifier, hp: IterHParams, carry, xs, y,
                    schedule):
    """SplitNN session with the cache key derived from model semantics."""
    return run_iterative_session(
        session_cache_key("splitnn", extractors, classifier, hp),
        lambda: make_splitnn_step_fn(extractors, classifier, hp),
        carry, xs, y, schedule)


def fedcvt_session(extractors, classifier, hp: IterHParams, carry, xs, y,
                   schedule, xs_u, u_schedules):
    """FedCVT-style session with the cache key derived from model semantics."""
    return run_iterative_session(
        session_cache_key("fedcvt", extractors, classifier, hp),
        lambda: make_fedcvt_step_fn(extractors, classifier, hp),
        carry, xs, y, schedule, xs_u=xs_u, u_schedules=u_schedules)
