"""Seed-batched engine execution (DESIGN.md §10).

The vmapped SSL session's client axis is a plain batch axis: nothing in the
compiled program knows that entry ``i`` is "party i" rather than "party
i mod K of seed i // K". This module exploits that to make multi-seed
sweeps a *compiled* capability instead of a Python loop over seeds:

* :func:`train_clients_ssl_seeds` — S seeds × K parties fold into ONE
  stacked axis of S·K entries and train as one jitted program (per-party
  feature dims: one S-entry program per party signature). The session
  cache (``engine.sessions``, domain ``"ssl"``) keys on semantic model
  identity + hyper-parameters, never on batch width, so seeds ≥ 2 add zero
  fresh session builds over a single-seed run (``jax.jit`` re-specializes
  the one cached session per stacked shape).
* :func:`pseudo_labels_seeds` — the step-③ gradient k-means over all
  S·K gradient matrices as one cached program (bit-identical to the
  per-call path; pinned in tests/test_seed_batched.py). Under
  ``use_kernels`` the fold HOLDS: every entry's final assignment runs in
  ONE batched ``(B, N/BN)`` Pallas grid (DESIGN.md §15).
* :func:`fewshot_probs_seeds` — few-shot ③' for one party over the
  stacked seed axis: Eq. 10 estimation (one batched SDPA grid per missing
  party on the kernel route) + the Eq. 8-9 gate as one vmapped cached
  session (domains ``"sdpa"`` / ``"fewshot_gate"``).
* :func:`fit_sessions_batched` — the server classifier fits
  (``core.server._fit``'s ``lax.scan`` session) vmapped over a leading
  batch axis: a multi-seed scenario point's K·S aux fits + S joint fits
  run as a handful of batched calls against one cached program.
* :func:`splitnn_sessions_seeds` / :func:`fedcvt_sessions_seeds` /
  :func:`fedbcd_sessions_seeds` — the ITERATIVE seed fold (DESIGN.md
  §11): the whole-session ``lax.scan`` carries of the SplitNN / FedCVT /
  FedBCD baselines (all parties' extractor params, the server head, both
  optimizer states) gain a leading seed axis and S seeds train as one
  ``vmap``-of-scan program, under the same session-cache keys as the
  single-seed sessions (zero fresh session builds for S ≥ 2).

Per-seed randomness is *reproduced*, not re-derived: every fold takes the
exact per-seed keys/schedules the single-seed path would have consumed, so
``core.protocol.run_seeds`` matches a Python loop of single-seed runs at
atol 1e-5 (bit-exact on CPU for the k-means and fit folds).

The batch axis is fully ANONYMOUS: "seed" never appears inside a fold, so
any flat list of shape-homogeneous entries may ride it. DESIGN.md §12
exploits exactly this — ``core.protocol.run_scenarios_seeds`` flattens C
grouped scenarios × S seeds scenario-major into these same entry points,
turning a whole frontier group into one stacked S·C·K program with zero
new engine code and zero new session-cache keys (the keys carry neither
batch width nor data shapes, so a C ≥ 2 fold against a warm C = 1 cache
compiles nothing fresh at the session level).

Per-party feature dims split the SSL fold into one session per party
signature. Ragged gradient dims fall back to per-entry k-means — same
numerics, no fold — and the fallback is recorded in the caller's
diagnostics (``kernel_fold`` 1) plus logged once, never silent. The Pallas
kernel path is NOT a fallback trigger anymore: batch is a native leading
grid dimension of both kernels (DESIGN.md §15).
"""
from __future__ import annotations

import logging
import os
from typing import Any, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.engine import parallel, sessions
from repro.engine.local_ssl import (PartyParams, PartyTask, SSLHParams,
                                    tasks_are_homogeneous, train_clients_ssl,
                                    train_task_groups)


def flatten_seed_tasks(tasks_per_seed: Sequence[Sequence[PartyTask]]
                       ) -> List[PartyTask]:
    """[[seed0 party0..K-1], [seed1 ...], …] → seed-major flat list."""
    return [t for seed_tasks in tasks_per_seed for t in seed_tasks]


def unflatten_seed_results(flat: Sequence[Any], num_seeds: int,
                           num_parties: int) -> List[List[Any]]:
    """Inverse of :func:`flatten_seed_tasks` for per-task results."""
    return [list(flat[s * num_parties:(s + 1) * num_parties])
            for s in range(num_seeds)]


# ------------------------------------------------------- SSL: the S·K fold
def train_clients_ssl_seeds(keys: Sequence[jax.Array],
                            tasks_per_seed: Sequence[Sequence[PartyTask]],
                            hp: SSLHParams, mode: str = "auto", mesh=None
                            ) -> Tuple[List[List[PartyParams]],
                                       List[List[dict]], List[str]]:
    """Every seed's every party's SSL session; returns per-seed
    ``(params, metrics)`` lists plus the engine path each seed trained on.

    ``S == 1`` delegates verbatim to :func:`train_clients_ssl` (the
    single-seed dispatcher). ``S > 1`` flattens the S·K tasks seed-major
    and trains one vmapped session per group of
    :func:`~repro.engine.local_ssl.ssl_task_groups`: a homogeneous task set
    is one S·K-entry session, a party-heterogeneous one (per-party feature
    dims) one S-entry session per party signature. Each seed's per-party
    keys are split exactly as the single-seed dispatcher splits them, so
    the fold and the per-party loop (``mode="python"``, the parity oracle)
    consume identical schedules and PRNG streams.
    """
    num_seeds = len(tasks_per_seed)
    if num_seeds == 1:
        params, metrics, vmapped = train_clients_ssl(
            keys[0], tasks_per_seed[0], hp, mode=mode, mesh=mesh)
        return [params], [metrics], ["vmap" if vmapped else "python"]

    if mode not in ("auto", "vmap", "python"):
        raise ValueError(f"unknown engine mode {mode!r}")
    k = len(tasks_per_seed[0])
    flat = flatten_seed_tasks(tasks_per_seed)
    if mode == "vmap" and not tasks_are_homogeneous(flat):
        raise ValueError("engine mode 'vmap' requires homogeneous party "
                         "tasks across every seed of the fold; use "
                         "mode='auto' or 'python'")
    # multi-seed "auto" folds — the whole point of batching seeds — unless
    # the CI matrix forces the loop (same knob the single-seed dispatcher
    # honors)
    if mode == "vmap" or (mode == "auto" and os.environ.get(
            "REPRO_ENGINE_MODE", "") != "python"):
        flat_keys = [kk for key in keys for kk in jax.random.split(key, k)]
        params, metrics = train_task_groups(flat_keys, flat, hp, mesh=mesh)
        return (unflatten_seed_results(params, num_seeds, k),
                unflatten_seed_results(metrics, num_seeds, k),
                ["vmap"] * num_seeds)
    out_p, out_m, paths = [], [], []
    for key, tasks in zip(keys, tasks_per_seed):
        params, metrics, vmapped = train_clients_ssl(key, tasks, hp,
                                                     mode=mode)
        out_p.append(params)
        out_m.append(metrics)
        paths.append("vmap" if vmapped else "python")
    return out_p, out_m, paths


# --------------------------------------------- k-means: fold over the batch
_log = logging.getLogger(__name__)
_ragged_fallback_logged = False


def _note_ragged_fallback(what: str) -> None:
    """Log the per-entry fallback ONCE per process — a degraded fold should
    be visible (diagnostics record it per row; this flags the first one)."""
    global _ragged_fallback_logged
    if not _ragged_fallback_logged:
        _ragged_fallback_logged = True
        _log.warning("%s: ragged entry shapes — per-entry fallback "
                     "(fold width 1); diagnostics record kernel_fold=1",
                     what)


def pseudo_labels_seeds(keys: Sequence[jax.Array],
                        partial_grads: Sequence[jnp.ndarray],
                        num_classes: int, kmeans_iters: int = 25,
                        use_kernels: bool = False, restarts: int = 4,
                        mesh=None, info: dict = None) -> List[jnp.ndarray]:
    """Step ③ for a flat (seed-major) batch of gradient matrices: one
    cached compiled program when every entry shares one shape —
    bit-identical per entry to the per-call path. ``use_kernels`` KEEPS the
    fold (batch is a native grid dimension of the Pallas kmeans kernel —
    one ``(B, N/BN)`` launch for the whole batch, DESIGN.md §15); only
    genuinely ragged gradient shapes fall back to per-entry execution,
    recorded in ``info`` (→ ``diagnostics["kernel_fold"]``) and logged
    once. ``info``, when given, receives ``{"fold": width}`` plus
    ``"fallback"`` with the reason on the degraded path."""
    from repro.engine.dispatch import (pseudo_labels,   # deferred: same package
                                       pseudo_labels_batched)
    n = len(partial_grads)
    if len({g.shape for g in partial_grads}) != 1:
        if info is not None:
            info["fold"] = 1
            info["fallback"] = "ragged gradient shapes"
        _note_ragged_fallback("pseudo_labels_seeds")
        return [pseudo_labels(k, g, num_classes, kmeans_iters,
                              use_kernels=use_kernels, restarts=restarts)
                for k, g in zip(keys, partial_grads)]
    mesh = parallel.resolve_mesh(mesh)
    out = pseudo_labels_batched(
        jnp.stack(parallel.pad_entries(keys, mesh)),
        jnp.stack(parallel.pad_entries(partial_grads, mesh)),
        num_classes, kmeans_iters=kmeans_iters, use_kernels=use_kernels,
        restarts=restarts, mesh=mesh)
    if info is not None:
        info["fold"] = n
    return [out[i] for i in range(n)]


# ------------------------------------------ few-shot ③': the seed-axis fold
def fewshot_probs_seeds(servers: Sequence[Any], k_idx: int,
                        h_u_stack: jnp.ndarray,
                        h_o_stacks: Sequence[jnp.ndarray],
                        threshold: float, use_kernels: bool = False,
                        mesh=None) -> jnp.ndarray:
    """Few-shot ③' for party ``k_idx`` over the stacked seed axis: Eq. 10
    estimation of every missing party + the Eq. 8-9 ``infer_prob`` gate,
    folded — no per-(seed, party) Python loop (DESIGN.md §15).

    ``h_u_stack`` (S, N_u, d_k) stacks the party's unaligned reps over
    seeds; ``h_o_stacks[j]`` (S, N_o, d_j) the per-party overlap reps.
    ``servers[s]`` supplies seed ``s``'s fitted aux/joint classifiers
    (asserted semantically equal across the fold, like every seed-batched
    model stack). Returns the (S, N_u) gating probabilities p̂.

    Two cached sessions serve any S — estimation (domain ``"sdpa"``, via
    ``dispatch.estimate_missing_batched``: ONE batched Pallas grid per
    missing party under ``use_kernels``, a vmapped jnp oracle otherwise)
    and gating (domain ``"fewshot_gate"``, keyed on the classifiers'
    semantic identity + threshold + mesh). The single-seed path is the
    width-1 case under the same keys.
    """
    from repro.core import estimator          # deferred: core imports engine
    from repro.engine import dispatch

    mesh = parallel.resolve_mesh(mesh)
    num_seeds = h_u_stack.shape[0]
    pad = parallel.pad_width(num_seeds, mesh)
    h_u_p = parallel.pad_stacked(h_u_stack, pad)
    h_o_p = [parallel.pad_stacked(h, pad) for h in h_o_stacks]
    ests = dispatch.estimate_missing_batched(h_u_p, h_o_p, k_idx,
                                             use_kernels=use_kernels,
                                             mesh=mesh)
    parts, ei = [], 0
    for j in range(len(h_o_stacks)):
        if j == k_idx:
            parts.append(h_u_p)
        else:
            parts.append(ests[ei])
            ei += 1
    full = jnp.concatenate(parts, axis=-1)    # concat_reps on the stacked axis

    aux_model = servers[0].aux_classifiers[k_idx]
    joint_model = servers[0].classifier
    amk = sessions.model_key(aux_model)
    jmk = sessions.model_key(joint_model)
    for srv in servers[1:]:
        if (sessions.model_key(srv.aux_classifiers[k_idx]) != amk
                or sessions.model_key(srv.classifier) != jmk):
            raise ValueError(
                "seed-batched few-shot gating requires semantically equal "
                "aux/joint classifiers across every seed of the fold")
    aux_stack = stack_carries(parallel.pad_entries(
        [srv.aux_params[k_idx] for srv in servers], mesh))
    joint_stack = stack_carries(parallel.pad_entries(
        [srv.params for srv in servers], mesh))

    def build():
        def one(h_u, full_rep, aux_p, joint_p):
            return estimator.infer_prob(
                lambda h: aux_model.apply(aux_p, h),
                lambda h: joint_model.apply(joint_p, h),
                h_u, full_rep, threshold)

        return parallel.shard_jit(jax.vmap(one), mesh, donate_params=False)

    fn = sessions.cached_session(
        "fewshot_gate", (amk, jmk, float(threshold),
                         parallel.mesh_key(mesh)), build)
    probs = fn(h_u_p, full, aux_stack, joint_stack)
    return probs[:num_seeds]


# ------------------------------------------- iterative baselines: seed fold
def stack_carries(carries: Sequence[Any]):
    """Per-seed session carries → one carry whose leaves have a leading
    seed axis (the inverse of :func:`unstack_carries`)."""
    return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *carries)


def unstack_carries(carry, num_seeds: int) -> List[Any]:
    """Split a stacked carry back into per-seed carries."""
    return [jax.tree_util.tree_map(lambda a: a[s], carry)
            for s in range(num_seeds)]


def _stack_party_data(per_seed: Sequence[Sequence[jnp.ndarray]]
                      ) -> Tuple[jnp.ndarray, ...]:
    """[[seed0 party0..K-1], …] → per-party tuple of (S, n, d) stacks.

    Parties may have heterogeneous feature dims — each party stacks only
    across seeds, where one scenario point's shapes agree by construction."""
    num_parties = len(per_seed[0])
    return tuple(jnp.stack([seed_xs[k] for seed_xs in per_seed])
                 for k in range(num_parties))


def _assert_seed_models_equal(extractors_per_seed, classifiers) -> None:
    ek0 = tuple(sessions.model_key(e) for e in extractors_per_seed[0])
    ck0 = sessions.model_key(classifiers[0])
    for exts, clf in zip(extractors_per_seed[1:], classifiers[1:]):
        if (tuple(sessions.model_key(e) for e in exts) != ek0
                or sessions.model_key(clf) != ck0):
            raise ValueError(
                "seed-batched iterative sessions require semantically equal "
                "party extractors and server classifier across every seed "
                "of the fold")


def splitnn_sessions_seeds(extractors_per_seed, classifiers,
                           hp, carries: Sequence[Any],
                           xs_per_seed, ys, schedules,
                           mode: str = "auto", mesh=None,
                           active_steps=None):
    """S seeds of one SplitNN session as ONE folded program.

    ``extractors_per_seed[s]`` / ``classifiers[s]`` are each seed's models
    (asserted semantically equal — one compiled step serves the fold);
    ``carries[s]`` the per-seed session carry; ``xs_per_seed[s]`` /
    ``ys[s]`` / ``schedules[s]`` the per-seed data and minibatch schedule.
    ``active_steps`` (optional, (S,) — DESIGN.md §16) truncates each
    seed's committed steps at a fault point, carry frozen past it.
    Returns ``(per-seed carries, (S, iters) losses)``.
    """
    from repro.engine import iterative        # deferred: sibling module

    _assert_seed_models_equal(extractors_per_seed, classifiers)
    exts, clf = extractors_per_seed[0], classifiers[0]
    carry, losses = iterative.run_iterative_session_seeds(
        iterative.session_cache_key("splitnn", exts, clf, hp),
        lambda: iterative.make_splitnn_step_fn(exts, clf, hp),
        stack_carries(carries), _stack_party_data(xs_per_seed),
        jnp.stack(list(ys)), jnp.stack(list(schedules)), mode, mesh=mesh,
        active_steps=active_steps)
    return unstack_carries(carry, len(carries)), losses


def fedcvt_sessions_seeds(extractors_per_seed, classifiers, hp,
                          carries: Sequence[Any], xs_per_seed, ys,
                          schedules, xs_u_per_seed, u_schedules,
                          mode: str = "auto", mesh=None,
                          active_steps=None):
    """S seeds of one FedCVT-style session as ONE folded program; the
    per-party unaligned pools and their draw schedules stack on the same
    seed axis. ``active_steps`` as in :func:`splitnn_sessions_seeds`.
    Returns ``(per-seed carries, (S, iters) losses)``."""
    from repro.engine import iterative        # deferred: sibling module

    _assert_seed_models_equal(extractors_per_seed, classifiers)
    exts, clf = extractors_per_seed[0], classifiers[0]
    num_parties = len(u_schedules[0])
    carry, losses = iterative.run_iterative_session_seeds(
        iterative.session_cache_key("fedcvt", exts, clf, hp),
        lambda: iterative.make_fedcvt_step_fn(exts, clf, hp),
        stack_carries(carries), _stack_party_data(xs_per_seed),
        jnp.stack(list(ys)), jnp.stack(list(schedules)), mode,
        xs_u=_stack_party_data(xs_u_per_seed),
        u_schedules=tuple(jnp.stack([us[k] for us in u_schedules])
                          for k in range(num_parties)), mesh=mesh,
        active_steps=active_steps)
    return unstack_carries(carry, len(carries)), losses


def fedbcd_sessions_seeds(extractors_per_seed, classifiers, hp, q: int,
                          carries: Sequence[Any], xs_per_seed, ys,
                          schedules, mode: str = "auto", mesh=None,
                          active_steps=None):
    """S seeds of one FedBCD-p session (Q local updates per round) as ONE
    folded program. ``active_steps`` as in :func:`splitnn_sessions_seeds`
    (units: communication ROUNDS). Returns ``(per-seed carries,
    (S, rounds) losses)``."""
    from repro.engine import iterative        # deferred: sibling module

    _assert_seed_models_equal(extractors_per_seed, classifiers)
    exts, clf = extractors_per_seed[0], classifiers[0]
    carry, losses = iterative.run_iterative_session_seeds(
        iterative.session_cache_key("fedbcd", exts, clf, hp, q),
        lambda: iterative.make_fedbcd_step_fn(exts, clf, hp, q),
        stack_carries(carries), _stack_party_data(xs_per_seed),
        jnp.stack(list(ys)), jnp.stack(list(schedules)), mode, mesh=mesh,
        active_steps=active_steps)
    return unstack_carries(carry, len(carries)), losses


# --------------------------------------------- server fits: vmapped sessions
def fit_sessions_batched(model, lr: float, params_list: Sequence[Any],
                         xs: Sequence[jnp.ndarray], ys: Sequence[jnp.ndarray],
                         schedules: Sequence[jnp.ndarray],
                         mesh=None) -> List[Any]:
    """A batch of server classifier fits as ONE cached vmapped ``lax.scan``
    session (domain ``"server_fit"``, keyed next to the plain session).

    Every entry must share the (x, y, schedule) shapes — true by
    construction for one scenario point's seeds, whose schedules differ
    only in *contents* (they travel as arguments). Entries may belong to
    different seeds or different aux-classifier parties alike: the batch
    axis is anonymous, exactly like the SSL fold's."""
    from repro.core.server import _fit_session        # deferred: core imports engine

    mesh = parallel.resolve_mesh(mesh)
    n = len(params_list)
    fitv = sessions.cached_session(
        "server_fit", ("vmap", sessions.model_key(model), float(lr),
                       parallel.mesh_key(mesh)),
        lambda: parallel.shard_jit(jax.vmap(_fit_session(model, lr)), mesh))
    stacked = jax.tree_util.tree_map(
        lambda *a: jnp.stack(a), *parallel.pad_entries(params_list, mesh))
    out = fitv(stacked, jnp.stack(parallel.pad_entries(xs, mesh)),
               jnp.stack(parallel.pad_entries(ys, mesh)),
               jnp.stack(parallel.pad_entries(schedules, mesh)))
    return [jax.tree_util.tree_map(lambda a: a[i], out) for i in range(n)]
