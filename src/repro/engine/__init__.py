"""VFL engine layer — the shared jit-compiled multi-client training path.

``repro.core.protocol`` (host-scale orchestration) and
``repro.launch.vfl_step`` (multi-pod shard_map schedule) both build their
local-SSL training from the step functions defined here, so the paper's
"all client computation happens between the exchanges" claim is one
implementation, not two. See DESIGN.md §2.

Multi-seed scenario sweeps fold into the same machinery: the vmapped
session's client axis is a plain batch axis, so ``engine.batched`` stacks
S seeds × K parties into one S·K-entry program (DESIGN.md §10).

Kernel dispatch for the protocol's two Pallas hot-spots (k-means assignment,
SDPA estimation) is funneled through :func:`pseudo_labels` /
:func:`estimate_missing` and their fold-native batched counterparts
:func:`pseudo_labels_batched` / :func:`estimate_missing_batched` behind a
single ``use_kernels`` switch — the batched entries serve a whole stacked
fold as ONE Pallas grid launch (DESIGN.md §15).
"""
from repro.engine.local_ssl import (
    PartyParams,
    PartyTask,
    Schedule,
    SSLHParams,
    build_schedule,
    build_schedules,
    make_ssl_optimizer,
    make_ssl_step_fn,
    parties_are_homogeneous,
    schedule_steps,
    ssl_task_groups,
    tasks_are_homogeneous,
    train_clients_ssl,
    train_parties_ssl_vmapped,
    train_party_ssl,
    train_task_groups,
)
from repro.engine.dispatch import (
    estimate_missing,
    estimate_missing_batched,
    estimate_missing_fused,
    pseudo_labels,
    pseudo_labels_batched,
)
from repro.engine import batched, iterative, parallel, sessions
from repro.engine.parallel import device_fold, mesh_key, resolve_mesh
from repro.engine.batched import (
    fedbcd_sessions_seeds,
    fedcvt_sessions_seeds,
    fewshot_probs_seeds,
    fit_sessions_batched,
    flatten_seed_tasks,
    pseudo_labels_seeds,
    splitnn_sessions_seeds,
    stack_carries,
    train_clients_ssl_seeds,
    unflatten_seed_results,
    unstack_carries,
)
from repro.engine.sessions import (clear_session_cache, session_cache_stats,
                                   session_cache_stats_by_domain)

__all__ = [
    "batched",
    "iterative",
    "parallel",
    "sessions",
    "clear_session_cache",
    "device_fold",
    "mesh_key",
    "resolve_mesh",
    "session_cache_stats",
    "session_cache_stats_by_domain",
    "PartyParams",
    "PartyTask",
    "Schedule",
    "SSLHParams",
    "build_schedule",
    "build_schedules",
    "estimate_missing",
    "estimate_missing_batched",
    "estimate_missing_fused",
    "fedbcd_sessions_seeds",
    "fedcvt_sessions_seeds",
    "fewshot_probs_seeds",
    "fit_sessions_batched",
    "flatten_seed_tasks",
    "make_ssl_optimizer",
    "make_ssl_step_fn",
    "parties_are_homogeneous",
    "pseudo_labels",
    "pseudo_labels_batched",
    "pseudo_labels_seeds",
    "schedule_steps",
    "splitnn_sessions_seeds",
    "ssl_task_groups",
    "stack_carries",
    "tasks_are_homogeneous",
    "train_clients_ssl",
    "train_clients_ssl_seeds",
    "train_parties_ssl_vmapped",
    "train_party_ssl",
    "train_task_groups",
    "unflatten_seed_results",
    "unstack_carries",
]
