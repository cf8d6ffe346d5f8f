"""Per-step host loops over the engine's step functions: the references the
engine's scanned sessions are compared against. Not a test module (no
``test_`` prefix); tests import it as ``engine_oracles``.

* :func:`ssl_party_loop` — one party's local-SSL session, one jitted step
  per dispatch, over the schedule ``engine.build_schedule`` gives its key;
* :func:`ssl_loop_seeds` — a stand-in for ``engine.train_clients_ssl_seeds``
  that runs every seed's every party through :func:`ssl_party_loop`;
* :func:`iterative_loop_seeds` — a stand-in for
  ``engine.iterative.run_iterative_session_seeds`` that loops seeds × steps.

As in the scanned sessions, an invalid step (``step_valid`` 0, or a step at
or past ``active_steps``) computes but does not commit. Steps are jitted
with plain ``jax.jit`` and cached here, outside the engine's session cache,
so cache-count tests see only the program's own sessions.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro import engine
from repro.engine import sessions
from repro.engine.local_ssl import ssl_compiler_options

_STEPS = {}


def _jitted(key, build):
    if key not in _STEPS:
        _STEPS[key] = build()
    return _STEPS[key]


def ssl_party_loop(key, task, hp):
    """One party's SSL session as a host loop; returns (params, metrics)."""
    tx = engine.make_ssl_optimizer(hp)
    step = _jitted(
        (
            "ssl",
            sessions.model_key(task.extractor),
            sessions.model_key(task.head),
            task.ssl_cfg,
            hp.learning_rate,
            hp.momentum,
            hp.grad_clip,
        ),
        lambda: jax.jit(
            engine.make_ssl_step_fn(task.extractor, task.head, task.ssl_cfg, tx),
            compiler_options=ssl_compiler_options(),
        ),
    )
    sched = engine.build_schedule(key, task.x_labeled.shape[0], task.x_unlabeled.shape[0], hp)
    params, opt_state = task.params, tx.init(task.params)
    idx_l = np.asarray(sched.idx_labeled)
    idx_u = np.asarray(sched.idx_unlabeled)
    m_l, m_u = task.labeled_mask, task.unlabeled_mask
    sv = None if task.step_valid is None else np.asarray(task.step_valid)
    metrics = {}
    for i in range(idx_l.shape[0]):
        new_params, new_opt, m = step(
            params,
            opt_state,
            task.feature_mean,
            sched.step_keys[i],
            task.x_labeled[idx_l[i]],
            task.y_pseudo[idx_l[i]],
            task.x_unlabeled[idx_u[i]],
            None if m_l is None else m_l[idx_l[i]],
            None if m_u is None else m_u[idx_u[i]],
        )
        if sv is None or sv[i] > 0:
            params, opt_state = new_params, new_opt
        metrics = m
    return params, {k: float(v) for k, v in metrics.items()}


def ssl_loop_seeds(keys, tasks_per_seed, hp, mesh=None):
    """Every seed's every party's session, each seed's key split into its
    per-party keys as the engine splits it; returns per-seed
    ``(params, metrics)`` lists. ``mesh`` is ignored."""
    del mesh
    params, metrics = [], []
    for key, tasks in zip(keys, tasks_per_seed):
        runs = [ssl_party_loop(k, t, hp) for k, t in zip(jax.random.split(key, len(tasks)), tasks)]
        params.append([p for p, _ in runs])
        metrics.append([m for _, m in runs])
    return params, metrics


def iterative_loop_seeds(
    cache_key,
    make_step,
    carry,
    xs,
    y,
    schedule,
    xs_u=None,
    u_schedules=None,
    mesh=None,
    active_steps=None,
):
    """The iterative seed fold as a loop over seeds and steps; returns
    ``(carry, losses)`` stacked like the fold's. ``mesh`` is ignored."""
    del mesh
    num_seeds = schedule.shape[0]
    if schedule.shape[1] == 0:
        return carry, jnp.zeros((num_seeds, 0))
    has_u = xs_u is not None
    step = _jitted(("iterative", has_u) + cache_key, lambda: jax.jit(make_step()))
    act = None if active_steps is None else np.asarray(active_steps, np.int64)
    out_carries, out_losses = [], []
    for s in range(num_seeds):
        c = jax.tree_util.tree_map(lambda a: a[s], carry)
        sched = np.asarray(schedule[s])
        u_scheds = [np.asarray(us[s]) for us in u_schedules] if has_u else None
        losses = []
        for i in range(sched.shape[0]):
            xb = tuple(x[s][sched[i]] for x in xs)
            xub = tuple(xu[s][us[i]] for xu, us in zip(xs_u, u_scheds)) if has_u else None
            new_c, loss = step(c, xb, y[s][sched[i]], xub)
            if act is None or i < act[s]:
                c = new_c
            losses.append(loss)
        out_carries.append(c)
        out_losses.append(jnp.stack(losses))
    return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *out_carries), jnp.stack(out_losses)
