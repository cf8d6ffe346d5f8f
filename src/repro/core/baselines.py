"""Baseline VFL methods from the paper's evaluation (§5.1):

* ``run_vanilla``  — genuine per-round SplitNN iterative VFL: every iteration
  uploads minibatch representations and downloads partial gradients (2 comm
  events per client per iteration, CommLedger-instrumented per round). Also
  used as the end-to-end finetuning stage of "few-shot + finetune" (Tab. 1
  last row).
* ``run_fedbcd``   — FedBCD [20]: Q local updates per communication round
  using the *stale* partial gradients.
* ``run_fedcvt``   — FedCVT-style semi-supervised cross-view baseline [15]:
  iterative VFL where each party's unaligned batch joins training with
  attention-estimated missing-party representations and confidence-gated
  pseudo-labels (the cross-view-training idea, without the paper's full
  5-loss apparatus — see DESIGN.md §7).

Every baseline executes through the engine's iterative session path
(``repro.engine.iterative``): the whole S-iteration session is one jitted
``lax.scan`` program, and the compiled session is cached across calls so
scenario sweeps never recompile identical step math.

Each runner is the S = 1 case of a seed-batched ``*_seeds`` entry
(DESIGN.md §11, mirroring the protocol's ``_one_shot_seeds`` pattern):
``run_vanilla_seeds`` / ``run_fedcvt_seeds`` / ``run_fedbcd_seeds`` stack
S seeds' whole-session carries on a leading seed axis and train them as
ONE ``vmap``-of-scan program (``engine.batched``), with each seed's exact
single-seed key/schedule discipline reproduced host-side and the
communication ledger — a function of shapes, which are seed-invariant —
produced once and shared by every per-seed result.
``core.protocol.run_seeds`` routes the baselines here.

All baselines train *only* on information the respective method is allowed
to see; all transfers go through the CommLedger.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.core.client import ClientParams, VFLClient
from repro.core.comm import CommLedger
from repro.core.protocol import VFLResult, _build_clients, _evaluate
from repro.core.server import VFLServer, concat_reps
from repro.core.ssl import SSLConfig
from repro.data.loader import epoch_batches
from repro.engine import batched, iterative, parallel
from repro.models.extractors import Model, make_classifier


@dataclass(frozen=True)
class IterativeConfig:
    """Frozen (use ``dataclasses.replace`` to derive variants — runner
    signatures default to None and construct a fresh instance, so no call
    ever observes another caller's mutations)."""
    iterations: int = 2000
    batch_size: int = 32
    client_lr: float = 0.01
    server_lr: float = 0.01
    momentum: float = 0.9
    fedbcd_q: int = 5               # Q (paper: 5)
    fedcvt_threshold: float = 0.95
    eval_every: int = 200
    mesh: object = None             # device mesh for the stacked seed axis
                                    # (DESIGN.md §14): None | device count |
                                    # jax.sharding.Mesh; None consults the
                                    # REPRO_DEVICE_COUNT env knob

    def iter_hparams(self) -> iterative.IterHParams:
        return iterative.IterHParams(client_lr=self.client_lr,
                                     server_lr=self.server_lr,
                                     momentum=self.momentum,
                                     fedcvt_threshold=self.fedcvt_threshold)


def _init_server(key, server: VFLServer, reps):
    h = concat_reps(reps)
    server.classifier = make_classifier(server.num_classes)
    server.params = server.classifier.init(key, h)
    return server


def _session_carry(clients: Sequence[VFLClient], server: VFLServer,
                   cfg: IterativeConfig):
    """(client_params, server_params, opt_states, opt_state_s) — the engine
    session carry, initialized from the current client/server state."""
    tx_c = optim.sgd(cfg.client_lr, momentum=cfg.momentum)
    tx_s = optim.sgd(cfg.server_lr, momentum=cfg.momentum)
    cp = tuple(ClientParams(*c.params) for c in clients)
    return (cp, server.params,
            tuple(tx_c.init(p) for p in cp), tx_s.init(server.params))


def _log_iterative_rounds(ledger: CommLedger, clients: Sequence[VFLClient],
                          iterations: int, bs: int, payload_factor: int = 1
                          ) -> None:
    """Per-iteration accounting: reps up + rep-grads down per client, both
    (bs, rep_dim) float32 (× payload_factor when a method ships extra
    batches, e.g. FedCVT's unaligned reps). Logged host-side around the
    jitted session so every engine mode produces the identical ledger."""
    for _ in range(iterations):
        r_up, r_dn = ledger.next_round(), ledger.next_round()
        for c in clients:
            num = payload_factor * bs * c.extractor.rep_dim * 4
            ledger.log_bytes(c.index, "up", "reps_batch", num, round=r_up)
            ledger.log_bytes(c.index, "down", "grads_batch", num, round=r_dn)


def _iterative_fault_plan(faults, clients, n_steps: int, bs: int,
                          payload_factor: int = 1):
    """Per-entry ledgers + dropout modeling for an iterative baseline fold
    (DESIGN.md §16). The synchronous round loop has no estimator to
    recover a dropped party, so the session stalls at the drop step:
    normal per-iteration accounting runs to ``t_drop``, then the server
    burns ``retry_rounds`` extra communication rounds — every surviving
    client re-uploads its batch while the dropped party gets a 4-byte
    timeout probe — before the method gives up with the carry it has.
    Straggler / dp_upload / representation_only faults have no analogue
    in the round loop: those entries run fault-free and are marked
    ``fault_modeled: False``.

    Returns ``(ledgers, active_steps | None, per_entry_diags)``;
    ``active_steps`` is the (S,) per-entry commit horizon the engine's
    faulted scan variant consumes (``iterative.run_iterative_session_seeds``).
    """
    num = len(faults)
    num_parties = len(clients)
    ledgers = [CommLedger() for _ in range(num)]
    diags: List[dict] = [{} for _ in range(num)]
    active = [n_steps] * num
    any_drop = False
    for s, fa in enumerate(faults):
        if fa is None or fa.kind != "dropout":
            _log_iterative_rounds(ledgers[s], clients, n_steps, bs,
                                  payload_factor)
            diags[s]["fault_kind"] = "none" if fa is None else fa.kind
            diags[s]["parties_survived"] = num_parties
            if fa is not None:
                diags[s]["fault_modeled"] = False
            continue
        any_drop = True
        t_drop = fa.iterative_active_steps(n_steps)
        active[s] = t_drop
        _log_iterative_rounds(ledgers[s], clients, t_drop, bs,
                              payload_factor)
        retry_bytes = 0
        for _ in range(fa.retry_rounds):
            r_up, r_dn = ledgers[s].next_round(), ledgers[s].next_round()
            for c in clients:
                if c.index == fa.party:
                    continue
                nb = payload_factor * bs * c.extractor.rep_dim * 4
                ledgers[s].log_bytes(c.index, "up", "retry_reps", nb,
                                     round=r_up)
                retry_bytes += nb
            ledgers[s].log_bytes(fa.party, "down", "retry_timeout", 4,
                                 round=r_dn)
            retry_bytes += 4
        diags[s].update({"fault_kind": fa.kind, "fault_stage": fa.stage,
                         "parties_survived":
                             fa.parties_survived(num_parties),
                         "fault_modeled": True,
                         "fault_retry_rounds": fa.retry_rounds,
                         "fault_retry_bytes": retry_bytes})
    return ledgers, (jnp.asarray(active, jnp.int32) if any_drop
                     else None), diags


def _seed_sessions_setup(keys, splits, extractors, ssl_cfgs,
                         cfg: IterativeConfig, make_schedule,
                         clients_per_seed=None, servers=None):
    """The per-seed setup every ``*_seeds`` runner shares — ONE
    implementation of the single-seed key discipline (``key, kc, ks =
    split(keys[s], 3)``, clients from ``kc``, server init from ``ks``,
    ``seed0`` drawn from ``key``) that the parity tests pin.
    ``make_schedule(seed0, n)`` builds the runner's minibatch schedule.
    Returns ``(clients_all, servers_all, schedules, carries)``."""
    num_seeds = len(keys)
    clients_all, servers_all, schedules, carries = [], [], [], []
    for s in range(num_seeds):
        key, kc, ks = jax.random.split(keys[s], 3)
        given = clients_per_seed[s] if clients_per_seed is not None else None
        clients = (given if given is not None else
                   _build_clients(kc, splits[s], extractors[s], ssl_cfgs[s]))
        server = servers[s] if servers is not None else None
        if server is None or server.params is None:
            server = VFLServer(num_classes=splits[s].num_classes)
            reps0 = [c.extract(x[:2])
                     for c, x in zip(clients, splits[s].aligned)]
            server = _init_server(ks, server, reps0)
        seed0 = int(jax.random.randint(key, (), 0, 2**31 - 1))
        schedules.append(make_schedule(seed0, splits[s].labels.shape[0]))
        carries.append(_session_carry(clients, server, cfg))
        clients_all.append(clients)
        servers_all.append(server)
    return clients_all, servers_all, schedules, carries


def _finish_seed_results(cfg: IterativeConfig, ledger: CommLedger,
                         clients_all, servers, splits, carries, losses,
                         extra_diags=None, ledgers=None,
                         per_seed_diags=None, faults=None
                         ) -> List[VFLResult]:
    """Shared tail of every seed-batched baseline: install the trained
    carries, evaluate per seed, and attach the (shared) ledger — callers
    copy it per seed when S > 1 (``run_seeds`` does). Faulted folds pass
    per-entry ``ledgers`` / ``per_seed_diags`` / ``faults`` instead: a
    dropped party's test reps are zero-imputed at eval (no estimator in
    the iterative methods) and the degraded metric is recorded."""
    num_seeds = len(carries)
    results = []
    for s in range(num_seeds):
        cp, sp = carries[s][0], carries[s][1]
        clients = [replace(c, params=ClientParams(*p))
                   for c, p in zip(clients_all[s], cp)]
        servers[s].params = sp
        fa = faults[s] if faults is not None else None
        name, metric = _evaluate(
            servers[s], clients, splits[s],
            fault=fa if fa is not None and fa.kind == "dropout" else None)
        diag = {"seed_fold": num_seeds,
                "device_fold": parallel.device_fold(
                    parallel.resolve_mesh(cfg.mesh)),
                "final_loss": (float(losses[s][-1]) if losses.shape[1]
                               else None)}
        if extra_diags is not None:
            diag.update(extra_diags)
        if per_seed_diags is not None:
            diag.update(per_seed_diags[s])
            diag["degraded_metric"] = float(metric)
        results.append(VFLResult(name, metric,
                                 ledgers[s] if ledgers is not None
                                 else ledger,
                                 clients, servers[s], diag))
    return results


def run_vanilla_seeds(
    keys: Sequence[jax.Array],
    splits: Sequence,
    extractors: Sequence[Sequence[Model]],
    ssl_cfgs: Sequence[Sequence[SSLConfig]],
    cfg: Optional[IterativeConfig] = None,
    clients_per_seed: Optional[Sequence[Optional[List[VFLClient]]]] = None,
    servers: Optional[Sequence[Optional[VFLServer]]] = None,
    ledger: Optional[CommLedger] = None,
    faults: Optional[Sequence] = None,
) -> List[VFLResult]:
    """Vanilla SplitNN VFL over S seeds at once (DESIGN.md §11): every
    seed's whole-session ``lax.scan`` carry stacks on a leading seed axis
    and the fold trains as one program. Per-seed PRNG/schedule discipline
    matches the historical single-seed runner exactly — S = 1 *is*
    ``run_vanilla``. All results share ``ledger`` (bytes are a function of
    shapes, seed-invariant); multi-seed callers copy it per result.

    ``clients_per_seed`` / ``servers`` admit pre-trained per-seed state —
    the chained few-shot + finetune fold threads the folded few-shot
    output carry straight into this folded finetune session.

    ``faults`` (one Optional[FaultSpec] per entry, §16) switches to
    per-entry ledgers: a dropout truncates the entry's committed round
    loop (``active_steps`` — fault mask as data, same compiled session)
    and charges the retry/timeout rounds; other fault kinds are not
    modeled by the synchronous loop (``fault_modeled: False``)."""
    cfg = cfg if cfg is not None else IterativeConfig()
    faulted = faults is not None and any(fa is not None for fa in faults)
    ledger = ledger if ledger is not None else CommLedger()
    clients_all, servers_all, schedules, carries = _seed_sessions_setup(
        keys, splits, extractors, ssl_cfgs, cfg,
        lambda seed0, n: iterative.build_iteration_schedule(
            seed0, n, cfg.batch_size, cfg.iterations),
        clients_per_seed=clients_per_seed, servers=servers)
    bs = min(cfg.batch_size, splits[0].labels.shape[0])
    fault_ledgers = active = fault_diags = None
    if faulted:
        fault_ledgers, active, fault_diags = _iterative_fault_plan(
            faults, clients_all[0], cfg.iterations, bs)
    carries, losses = batched.splitnn_sessions_seeds(
        [[c.extractor for c in cl] for cl in clients_all],
        [srv.classifier for srv in servers_all], cfg.iter_hparams(),
        carries, [sp.aligned for sp in splits],
        [sp.labels for sp in splits], schedules, mesh=cfg.mesh,
        active_steps=active)

    if not faulted:
        _log_iterative_rounds(ledger, clients_all[0], cfg.iterations, bs)
    return _finish_seed_results(cfg, ledger, clients_all, servers_all,
                                splits, carries, losses,
                                {"iterations": cfg.iterations},
                                ledgers=fault_ledgers,
                                per_seed_diags=fault_diags, faults=faults)


def run_vanilla(
    key: jax.Array,
    split,
    extractors: Sequence[Model],
    ssl_cfgs: Sequence[SSLConfig],
    cfg: Optional[IterativeConfig] = None,
    clients: Optional[List[VFLClient]] = None,
    server: Optional[VFLServer] = None,
    ledger: Optional[CommLedger] = None,
    fault=None,
) -> VFLResult:
    return run_vanilla_seeds([key], [split], [extractors], [ssl_cfgs], cfg,
                             clients_per_seed=[clients], servers=[server],
                             ledger=ledger,
                             faults=None if fault is None else [fault])[0]


def _fedbcd_schedule(seed0: int, n: int, batch_size: int,
                     rounds: int) -> jnp.ndarray:
    """(rounds, bs) minibatch indices replicating the historical FedBCD
    loop exactly: each shuffled epoch is seeded ``seed0 + rounds_done`` at
    its *entry* (not ``seed0 + epoch`` — the historical loop reseeded on
    the round counter), drop-remainder, truncated to ``rounds`` rows."""
    bs = min(batch_size, n)
    if rounds <= 0:
        return jnp.zeros((0, bs), jnp.int32)
    rows: List[np.ndarray] = []
    while len(rows) < rounds:
        for b in epoch_batches(n, bs, seed0 + len(rows)):
            rows.append(b)
            if len(rows) == rounds:
                break
    return jnp.asarray(np.stack(rows), jnp.int32)


def run_fedbcd_seeds(
    keys: Sequence[jax.Array],
    splits: Sequence,
    extractors: Sequence[Sequence[Model]],
    ssl_cfgs: Sequence[Sequence[SSLConfig]],
    cfg: Optional[IterativeConfig] = None,
    faults: Optional[Sequence] = None,
) -> List[VFLResult]:
    """FedBCD-p over S seeds at once: per round, one rep exchange then Q
    parallel local updates on the stale partial gradients (clients) / stale
    reps (server) — the whole multi-seed session one folded scan program
    (DESIGN.md §11), where it used to re-``jax.jit`` an ad-hoc round step
    per call. ``faults``: see :func:`run_vanilla_seeds` — the dropout
    horizon counts communication ROUNDS (the scan axis), not local
    updates."""
    cfg = cfg if cfg is not None else IterativeConfig()
    faulted = faults is not None and any(fa is not None for fa in faults)
    ledger = CommLedger()
    rounds = cfg.iterations // cfg.fedbcd_q
    clients_all, servers_all, schedules, carries = _seed_sessions_setup(
        keys, splits, extractors, ssl_cfgs, cfg,
        lambda seed0, n: _fedbcd_schedule(seed0, n, cfg.batch_size, rounds))
    bs = min(cfg.batch_size, splits[0].labels.shape[0])
    fault_ledgers = active = fault_diags = None
    if faulted:
        fault_ledgers, active, fault_diags = _iterative_fault_plan(
            faults, clients_all[0], rounds, bs)
    carries, losses = batched.fedbcd_sessions_seeds(
        [[c.extractor for c in cl] for cl in clients_all],
        [srv.classifier for srv in servers_all], cfg.iter_hparams(),
        cfg.fedbcd_q, carries, [sp.aligned for sp in splits],
        [sp.labels for sp in splits], schedules, mesh=cfg.mesh,
        active_steps=active)

    if not faulted:
        _log_iterative_rounds(ledger, clients_all[0], rounds, bs)
    return _finish_seed_results(cfg, ledger, clients_all, servers_all,
                                splits, carries, losses,
                                {"rounds": rounds, "Q": cfg.fedbcd_q},
                                ledgers=fault_ledgers,
                                per_seed_diags=fault_diags, faults=faults)


def run_fedbcd(
    key: jax.Array,
    split,
    extractors: Sequence[Model],
    ssl_cfgs: Sequence[SSLConfig],
    cfg: Optional[IterativeConfig] = None,
    fault=None,
) -> VFLResult:
    return run_fedbcd_seeds([key], [split], [extractors], [ssl_cfgs], cfg,
                            faults=None if fault is None else [fault])[0]


def run_fedcvt_seeds(
    keys: Sequence[jax.Array],
    splits: Sequence,
    extractors: Sequence[Sequence[Model]],
    ssl_cfgs: Sequence[Sequence[SSLConfig]],
    cfg: Optional[IterativeConfig] = None,
    faults: Optional[Sequence] = None,
) -> List[VFLResult]:
    """FedCVT-style semi-supervised baseline over S seeds at once: vanilla
    iterative VFL + per-iteration cross-view training-set expansion. Each
    round, missing reps of a sampled unaligned batch are attention-
    estimated from the overlap batch and samples whose classifier
    confidence exceeds the threshold train with their pseudo labels. The
    whole multi-seed session is one folded scan program
    (``engine.batched.fedcvt_sessions_seeds``, DESIGN.md §11).
    ``faults``: see :func:`run_vanilla_seeds` (retry payloads carry the
    same 2× factor as the normal rounds)."""
    cfg = cfg if cfg is not None else IterativeConfig()
    faulted = faults is not None and any(fa is not None for fa in faults)
    ledger = CommLedger()
    clients_all, servers_all, schedules, carries = _seed_sessions_setup(
        keys, splits, extractors, ssl_cfgs, cfg,
        lambda seed0, n: iterative.build_iteration_schedule(
            seed0, n, cfg.batch_size, cfg.iterations))
    # the unaligned draws are key-free (historically seeded literally 0):
    # only pool sizes and the batch width enter
    u_schedules = [iterative.build_unaligned_schedule(
        0, [x.shape[0] for x in sp.unaligned],
        min(cfg.batch_size, sp.labels.shape[0]), cfg.iterations)
        for sp in splits]
    bs = min(cfg.batch_size, splits[0].labels.shape[0])
    fault_ledgers = active = fault_diags = None
    if faulted:
        fault_ledgers, active, fault_diags = _iterative_fault_plan(
            faults, clients_all[0], cfg.iterations, bs, payload_factor=2)
    carries, losses = batched.fedcvt_sessions_seeds(
        [[c.extractor for c in cl] for cl in clients_all],
        [srv.classifier for srv in servers_all], cfg.iter_hparams(),
        carries, [sp.aligned for sp in splits],
        [sp.labels for sp in splits], schedules,
        [sp.unaligned for sp in splits], u_schedules, mesh=cfg.mesh,
        active_steps=active)

    # overlap reps + unaligned reps up; both gradients down
    if not faulted:
        _log_iterative_rounds(ledger, clients_all[0], cfg.iterations, bs,
                              payload_factor=2)
    return _finish_seed_results(cfg, ledger, clients_all, servers_all,
                                splits, carries, losses,
                                {"iterations": cfg.iterations},
                                ledgers=fault_ledgers,
                                per_seed_diags=fault_diags, faults=faults)


def run_fedcvt(
    key: jax.Array,
    split,
    extractors: Sequence[Model],
    ssl_cfgs: Sequence[SSLConfig],
    cfg: Optional[IterativeConfig] = None,
    fault=None,
) -> VFLResult:
    return run_fedcvt_seeds([key], [split], [extractors], [ssl_cfgs], cfg,
                            faults=None if fault is None else [fault])[0]
