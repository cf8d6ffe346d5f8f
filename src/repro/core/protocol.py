"""One-shot and few-shot VFL protocol orchestration (Alg. 1 + Alg. 2).

``run_one_shot`` / ``run_few_shot`` are THIN orchestrators: they do the
ledger-tracked client↔server exchanges (every transfer goes through the
CommLedger so Tab. 1's communication columns are produced by the training
code path itself) and delegate all client-side computation to the VFL
engine layer (``repro.engine``): gradient-clustering pseudo-labels, SDPA
estimation, and the local-SSL sessions — one vmapped, scanned program per
party signature, a single one when the party zoo is homogeneous
(including few-shot's masked fixed-shape phase ⑤', at any ragged
per-party gate counts — DESIGN.md §2, §9).

Both protocols are implemented once, *seed-batched* (DESIGN.md §10): the
internal ``_one_shot_seeds`` / ``_few_shot_seeds`` drive S seeds of one
scenario point through the exchanges together, folding the heavy compute
(S·K local-SSL sessions, S·K k-means runs, S server fits) into stacked
compiled programs while reproducing each seed's exact single-seed PRNG
stream host-side. The public single-seed runners are the S = 1 case of the
same code; ``run_seeds`` is the multi-seed entry point, and
``run_scenarios_seeds`` extends the very same fold along the *scenario*
axis (DESIGN.md §12): a group of shape-homogeneous scenarios flattens
scenario-major into the identical ``*_seeds`` impls, so C scenarios × S
seeds train as one stacked program under unchanged session-cache keys.
Communication is a function of shapes only, so the ledger is produced
host-side once and asserted byte-identical across seeds.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro import engine, obs
from repro.core import clustering
from repro.core.client import VFLClient, make_client, ssl_task_for
from repro.core.comm import CommLedger, nbytes
from repro.core.metrics import accuracy, binary_auc
from repro.core.server import (VFLServer, fit_aux_classifiers_seeds,
                               train_classifier_seeds)
from repro.core.ssl import SSLConfig
from repro.data.vertical import VerticalSplit
from repro.models.extractors import Model
from repro.scenarios.faults import (POINT_EVAL, POINT_ROUND2, POINT_SSL,
                                    POINT_UPLOAD1, POINT_UPLOAD2, FaultSpec)


@dataclass(frozen=True)
class ProtocolConfig:
    """Frozen (use ``dataclasses.replace`` to derive variants — runner
    signatures default to None and construct a fresh instance, so no call
    ever observes another caller's mutations)."""
    client_epochs: int = 20          # E_c
    server_epochs: int = 50          # E_s
    batch_size: int = 32             # B   (paper: 32)
    client_lr: float = 0.01          # η_c (paper: 0.01)
    server_lr: float = 0.01          # η_s (paper: 0.01)
    fewshot_threshold: float = 0.9   # t in Eq. (9)
    fewshot_stochastic_gate: bool = False   # Bernoulli(p̂) sample instead of
                                     # the paper's keep-all-gated (Eq. 9)
    fewshot_relabel_overlap: bool = False   # legacy phase-⑤' behavior: re-
                                     # predict the overlap rows with the
                                     # local head instead of reusing the
                                     # step-③ cluster pseudo-labels Ŷ_o^k
    grad_dp_sigma: float = 0.0       # Gaussian noise on partial grads (label-DP
                                     # style defense — paper §6 compatibility)
    kmeans_iters: int = 25
    unlabeled_ratio: int = 2
    use_kernels: bool = False        # one switch: Pallas k-means + SDPA kernels
    mesh: object = None              # device mesh for the stacked engine axis
                                     # (DESIGN.md §14): None | device count |
                                     # jax.sharding.Mesh; None consults the
                                     # REPRO_DEVICE_COUNT env knob
    rep_dtype: jnp.dtype = jnp.float32

    def ssl_hparams(self) -> engine.SSLHParams:
        return engine.SSLHParams(epochs=self.client_epochs,
                                 batch_size=self.batch_size,
                                 learning_rate=self.client_lr,
                                 unlabeled_ratio=self.unlabeled_ratio)


@dataclass
class VFLResult:
    metric_name: str
    metric: float
    ledger: CommLedger
    clients: List[VFLClient]
    server: VFLServer
    diagnostics: dict = field(default_factory=dict)

    def summary_row(self) -> dict:
        """JSON-ready summary of the paper's three columns (metric, comm
        bytes, comm times) — built by the ONE typed row builder every
        benchmark surface shares (``repro.core.rows``, DESIGN.md §13)."""
        from repro.core import rows
        return rows.training_row(self)

    def to_artifact(self, scenario_spec, cfg=None, split=None):
        """Export this result as a deployable
        :class:`~repro.checkpoint.artifact.TrainedVFLModel` — per-party
        extractor params + apply identity, the fitted joint head, and
        provenance (DESIGN.md §13). Pass ``split`` to also bake the final
        overlap representations H_o (what serving-time missing-party
        estimation attends over, Eq. 10)."""
        from repro.checkpoint import artifact
        return artifact.from_state(self.clients, self.server, scenario_spec,
                                   cfg=cfg, metric_name=self.metric_name,
                                   metric=self.metric, split=split)


# --------------------------------------------------------------------------
def _build_clients(key, split: VerticalSplit, extractors: Sequence[Model],
                   ssl_cfgs: Sequence[SSLConfig]) -> List[VFLClient]:
    clients = []
    for k_idx, (ext, cfg) in enumerate(zip(extractors, ssl_cfgs)):
        key, kc = jax.random.split(key)
        # x̄ for the tabular augmentations (Eq. 5-6) comes from the party's
        # local rows: the private pool, or — for a full-overlap party whose
        # pool is empty — its aligned feature block (also party-local data)
        local_pool = split.unaligned[k_idx]
        if local_pool.ndim == 2 and local_pool.shape[0] == 0:
            local_pool = split.aligned[k_idx]
        clients.append(make_client(
            kc, k_idx, ext, split.num_classes,
            sample_input=split.aligned[k_idx][:2],
            ssl_cfg=cfg,
            local_data_for_mean=local_pool if local_pool.ndim == 2 else None))
    return clients


def _evaluate(server: VFLServer, clients: Sequence[VFLClient],
              split: VerticalSplit, fault: Optional[FaultSpec] = None,
              h_o_final: Optional[Sequence[jnp.ndarray]] = None,
              fkey: Optional[jax.Array] = None,
              use_kernels: bool = False) -> tuple:
    test_reps = [c.extract(x) for c, x in zip(clients, split.test_aligned)]
    if fault is not None:
        test_reps = _faulted_test_reps(test_reps, fault, h_o_final, fkey,
                                       use_kernels)
    logits = server.predict_logits(test_reps)
    if split.num_classes == 2:
        scores = jax.nn.softmax(logits, axis=-1)[:, 1]
        return "auc", binary_auc(scores, split.test_labels)
    return "accuracy", accuracy(logits, split.test_labels)


def _safe_mean(x) -> float:
    """Host-side mean that treats an empty array (e.g. a full-overlap
    party's zero-row pool) as rate 0 instead of NaN."""
    return float(jnp.mean(x)) if x.size else 0.0


def _log_seeds(ledger: CommLedger, party: int, direction: str, tag: str,
               payloads: Sequence, round: int) -> None:
    """Log ONE event for S per-seed payloads of one transfer: communication
    is a function of shapes, so the seeds must agree byte-for-byte — the
    seed-batched runs assert it at every exchange."""
    sizes = {nbytes(p) for p in payloads}
    if len(sizes) != 1:
        raise ValueError(
            f"seed-batched run broke ledger byte-identity for {tag!r}: "
            f"per-seed payload bytes {sorted(sizes)}")
    ledger.log_bytes(party, direction, tag, sizes.pop(), round=round)


# -------------------------------------------------------- fault injection
# the fault-injection PRNG stream is folded off the entry's ORIGINAL key
# with a fixed prime, disjoint from every key the protocol splits itself
_FAULT_STREAM = 15485863


def _phase_round(ledger: CommLedger, entry_ledgers) -> object:
    """Advance the round counter for one protocol phase: the shared
    prototype ledger on the fault-free path, every per-entry ledger on a
    faulted fold (healthy entries keep the prototype round sequence)."""
    if entry_ledgers is None:
        return ledger.next_round()
    return [led.next_round() for led in entry_ledgers]


def _log_phase(ledger: CommLedger, entry_ledgers, party: int,
               direction: str, tag: str, payloads: Sequence, rounds,
               skip=None) -> None:
    """Log one transfer of ``party`` across the S stacked entries.
    Fault-free folds share one prototype ledger (``_log_seeds``, with the
    byte-identity assertion); faulted folds carry one ledger PER entry so
    a dropped party's missing upload (``skip[s]``) stays entry-local
    while healthy entries' ledgers remain content-identical."""
    if entry_ledgers is None:
        _log_seeds(ledger, party, direction, tag, payloads, rounds)
        return
    for s, led in enumerate(entry_ledgers):
        if skip is not None and skip[s]:
            continue
        led.log_bytes(party, direction, tag, nbytes(payloads[s]),
                      round=rounds[s])


def _drop_skip(faults, k: int, point: int, num_seeds: int):
    """Per-entry skip flags for party k's transfer at a protocol point."""
    if faults is None:
        return None
    return [faults[s] is not None and faults[s].drops(k, point)
            for s in range(num_seeds)]


def _dp_noised(fkey: jax.Array, phase: int, party: int,
               fault: Optional[FaultSpec], arr: jnp.ndarray) -> jnp.ndarray:
    """``dp_upload`` fault: σ·std(arr) Gaussian noise on the faulted
    party's payload at the given protocol phase index. Bytes on the wire
    are unchanged — privacy costs accuracy, not communication."""
    if (fault is None or fault.kind != "dp_upload"
            or fault.party != party or fault.dp_sigma <= 0):
        return arr
    k = jax.random.fold_in(fkey, phase)
    scale = fault.dp_sigma * jnp.std(arr)
    return arr + scale * jax.random.normal(k, arr.shape).astype(arr.dtype)


def _reconstruct_dropped(reps_all, stale_all, faults, point: int,
                         use_kernels: bool) -> None:
    """Server-side Eq. 10 recovery of dropped parties' missing uploads:
    Ĥ^k = softmax(H_a H̄_aᵀ/√d) H̄_k with a the lowest-index surviving
    party, H_a its fresh upload and H̄ the last payloads the server still
    holds (DESIGN.md §16). Entries sharing (dropped, anchor) fold into ONE
    batched SDPA program (§15). A party that never uploaded (stale zeros)
    reconstructs to zeros — the same code path, degrading gracefully."""
    from repro.core import estimator
    groups: dict = {}
    for s, fa in enumerate(faults):
        if fa is None or fa.kind != "dropout":
            continue
        num_parties = len(reps_all[s])
        alive = [k for k in range(num_parties) if not fa.drops(k, point)]
        for k in range(num_parties):
            if fa.drops(k, point):
                groups.setdefault((k, alive[0]), []).append(s)
    for (k, anchor), entries in sorted(groups.items()):
        est = estimator.sdpa_transform_batched(
            jnp.stack([reps_all[s][anchor] for s in entries]),
            jnp.stack([stale_all[s][anchor] for s in entries]),
            jnp.stack([stale_all[s][k] for s in entries]),
            use_kernel=use_kernels)
        for i, s in enumerate(entries):
            reps_all[s][k] = est[i].astype(reps_all[s][k].dtype)


def _fault_step_valid(fault: Optional[FaultSpec], party: int,
                      n_labeled: int, hp, skip_all: bool) -> jnp.ndarray:
    """(n_steps,) per-step commit mask for one party's SSL session in a
    faulted fold (§16): all-zeros for a dropped / representation-only
    party, the leading ⌊fraction·epochs⌋ whole epochs for a straggler,
    all-ones otherwise. EVERY party gets a mask when the fold carries any
    fault, so the stacked session keeps one shape — the mask is data,
    never compile-time structure."""
    n_steps = engine.schedule_steps(n_labeled, hp)
    if skip_all:
        return jnp.zeros((n_steps,), jnp.float32)
    if (fault is not None and fault.kind == "straggler"
            and fault.party == party):
        steps_per_epoch = n_steps // max(hp.epochs, 1)
        active = int(hp.epochs * fault.epoch_fraction) * steps_per_epoch
        return (jnp.arange(n_steps) < active).astype(jnp.float32)
    return jnp.ones((n_steps,), jnp.float32)


def _faulted_test_reps(test_reps, fault: FaultSpec, h_o_final, fkey,
                       use_kernels: bool):
    """Degraded-serving view of the test forward (§16): a dropped party's
    test representations are Eq. 10-reconstructed from the final overlap
    reps (zero-imputed when no estimator memory exists — the iterative
    baselines), and a dp_upload party's payload carries the same σ·std
    noise as its training uploads."""
    from repro.core import estimator
    reps = list(test_reps)
    num_parties = len(reps)
    if fault.kind == "dp_upload":
        if fkey is not None and fault.party < num_parties:
            reps[fault.party] = _dp_noised(fkey, 5, fault.party, fault,
                                           reps[fault.party])
        return reps
    if fault.kind != "dropout":
        return reps
    alive = [j for j in range(num_parties)
             if not fault.drops(j, POINT_EVAL)]
    for k in range(num_parties):
        if fault.drops(k, POINT_EVAL):
            if h_o_final is None:
                reps[k] = jnp.zeros_like(reps[k])
            else:
                reps[k] = estimator.sdpa_transform(
                    reps[alive[0]], h_o_final[alive[0]], h_o_final[k],
                    use_kernel=use_kernels).astype(reps[k].dtype)
    return reps


def _fault_diags(fault: Optional[FaultSpec], num_parties: int,
                 metric: float) -> dict:
    """Per-entry fault diagnostics every faulted row reports (rows.py)."""
    d = {"fault_kind": fault.kind if fault is not None else "none",
         "parties_survived": (fault.parties_survived(num_parties)
                              if fault is not None else num_parties),
         "degraded_metric": float(metric)}
    if fault is not None and fault.kind == "dropout":
        d["fault_stage"] = fault.stage
    return d


def _commit_ssl(out, diags, clients_all, mesh) -> None:
    """Record one ``engine.train_clients_ssl_seeds`` result (steps ④ and
    ⑤'): each seed's SSL metrics and fold width, and its clients' new
    params. ``out`` is indexed rather than unpacked, so a stand-in that
    returns extra trailing values still serves."""
    for s, (params, metrics) in enumerate(zip(out[0], out[1])):
        diags[s]["device_fold"] = engine.device_fold(mesh)
        diags[s].setdefault("ssl_metrics", []).extend(metrics)
        clients_all[s] = [replace(c, params=p)
                          for c, p in zip(clients_all[s], params)]


def fewshot_phase5_labels(client: VFLClient, x_o: jnp.ndarray,
                          x_u: jnp.ndarray, pseudo_overlap: jnp.ndarray,
                          relabel_overlap: bool = False) -> jnp.ndarray:
    """Labels of the padded phase-⑤' labeled set ``x_o ∘ x_u`` (Alg. 2
    l.11-19): the overlap rows reuse the step-③ gradient-cluster
    pseudo-labels Ŷ_o^k — the local head may drift off them during SSL, so
    re-predicting is NOT guaranteed to agree — and the pool rows take the
    local model's predictions (their contribution is masked by the Eq. 9
    gate). ``relabel_overlap`` restores the legacy re-prediction of the
    overlap rows for ablations."""
    y_o = (client.predict(x_o) if relabel_overlap
           else pseudo_overlap.astype(jnp.int32))
    return jnp.concatenate([y_o, client.predict(x_u)], axis=0)


# ------------------------------------------------------------- one-shot VFL
def _one_shot_seeds(
    keys: Sequence[jax.Array],
    splits: Sequence[VerticalSplit],
    extractors: Sequence[Sequence[Model]],
    ssl_cfgs: Sequence[Sequence[SSLConfig]],
    cfg: Optional[ProtocolConfig] = None,
    ledger: Optional[CommLedger] = None,
    clients_per_seed: Optional[Sequence[Optional[List[VFLClient]]]] = None,
    final_reps_out: Optional[list] = None,
    faults: Optional[Sequence[Optional[FaultSpec]]] = None,
    ledgers: Optional[Sequence[CommLedger]] = None,
) -> List[VFLResult]:
    """Alg. 1 over S seeds at once. Per-seed PRNG streams are split exactly
    like the historical single-seed runner's (S = 1 *is* the single-seed
    runner); the heavy stages — step-③ k-means, step-④ local SSL, step-⑥
    classifier fit — execute seed-batched (DESIGN.md §10). All results
    share ``ledger``; multi-seed callers copy it per result.
    ``final_reps_out`` (if given) receives the step-⑤ refreshed overlap
    reps per seed, so few-shot's ①' needn't re-extract them.

    ``faults`` (one optional :class:`FaultSpec` per entry, DESIGN.md §16)
    switches the fold to per-entry ``ledgers``: a dropped party's missing
    uploads are skipped entry-locally and its H_o^k reconstructed by the
    Eq. 10 estimator, stragglers/representation-only parties ride the
    §9 mask machinery as ``step_valid`` data, dp_upload entries noise
    their payloads — shapes never change, so the faulted fold runs the
    SAME stacked programs under unchanged session-cache keys."""
    cfg = cfg if cfg is not None else ProtocolConfig()
    ledger = ledger if ledger is not None else CommLedger()
    num_seeds = len(keys)
    num_parties = len(splits[0].aligned)
    mesh = engine.resolve_mesh(cfg.mesh)
    if faults is not None and len(faults) != num_seeds:
        raise ValueError("faults needs one entry (FaultSpec or None) per "
                         "stacked seed/scenario entry")
    faulted = faults is not None
    if not faulted:
        faults = [None] * num_seeds
    entry_ledgers = fkeys = None
    if faulted:
        entry_ledgers = (list(ledgers) if ledgers is not None
                         else [CommLedger() for _ in range(num_seeds)])
        fkeys = [jax.random.fold_in(keys[s], _FAULT_STREAM)
                 for s in range(num_seeds)]

    with obs.span("init"):
        st_keys, k_srvs, clients_all, servers = [], [], [], []
        for s in range(num_seeds):
            key, k_clients, k_srv = jax.random.split(keys[s], 3)
            given = (clients_per_seed[s] if clients_per_seed is not None
                     else None)
            clients = (given if given is not None else
                       _build_clients(k_clients, splits[s], extractors[s],
                                      ssl_cfgs[s]))
            st_keys.append(key)
            k_srvs.append(k_srv)
            clients_all.append(clients)
            servers.append(VFLServer(num_classes=splits[s].num_classes))

    with obs.span("p1.extract"):
        # ① clients upload overlap representations. A party dropped before
        # this point never shows up: the server zero-imputes its H_o^k slot
        # (fixed shapes — the fold never re-compiles) and no event is logged.
        reps_all = [[c.extract(x_o).astype(cfg.rep_dtype)
                     for c, x_o in zip(clients_all[s], splits[s].aligned)]
                    for s in range(num_seeds)]
        if faulted:
            for s, fa in enumerate(faults):
                if fa is None:
                    continue
                for k in range(num_parties):
                    if fa.drops(k, POINT_UPLOAD1):
                        reps_all[s][k] = jnp.zeros_like(reps_all[s][k])
                    else:
                        reps_all[s][k] = _dp_noised(fkeys[s], 1, k, fa,
                                                    reps_all[s][k])
        r1 = _phase_round(ledger, entry_ledgers)
        for k in range(num_parties):
            _log_phase(ledger, entry_ledgers, k, "up", "reps_overlap",
                       [reps_all[s][k] for s in range(num_seeds)], r1,
                       skip=_drop_skip(faults if faulted else None, k,
                                       POINT_UPLOAD1, num_seeds))
        # the server's last-seen view of every party, AFTER imputation/noise
        # — what Eq. 10 reconstruction attends over at step ⑤
        stale_reps = ([list(reps) for reps in reps_all] if faulted else None)

    with obs.span("p2.grads"):
        # ② server computes and sends partial gradients (+ class count C);
        # optional label-DP-style Gaussian noise (the paper's §6 notes such
        # defenses compose with the protocol — grad_dp_sigma exercises that)
        grads_all = []
        for s in range(num_seeds):
            st_keys[s], kg = jax.random.split(st_keys[s])
            grads = servers[s].partial_gradients(kg, reps_all[s],
                                                 splits[s].labels)
            if cfg.grad_dp_sigma > 0:
                noised = []
                for g in grads:
                    st_keys[s], kn = jax.random.split(st_keys[s])
                    scale = cfg.grad_dp_sigma * jnp.std(g)
                    noised.append(g + scale * jax.random.normal(kn, g.shape))
                grads = noised
            grads_all.append(grads)
        r2 = _phase_round(ledger, entry_ledgers)
        for k in range(num_parties):
            _log_phase(ledger, entry_ledgers, k, "down", "partial_grads",
                       [grads_all[s][k] for s in range(num_seeds)], r2,
                       skip=_drop_skip(faults if faulted else None, k,
                                       POINT_SSL, num_seeds))

    # ③ gradient clustering → pseudo labels;  ④ local SSL — both engine-
    # side and seed-batched: the S·K gradient matrices cluster in one
    # vmapped k-means, the S·K SSL sessions fold into one stacked program
    with obs.span("p3.kmeans"):
        diags = [{"kmeans_purity": [], "ssl_metrics": [],
                  "seed_fold": num_seeds} for _ in range(num_seeds)]
        kss = []
        flat_kmeans_keys, flat_grads = [], []
        for s in range(num_seeds):
            st_keys[s], kk, ks = jax.random.split(st_keys[s], 3)
            kss.append(ks)
            flat_kmeans_keys.extend(jax.random.fold_in(kk, c.index)
                                    for c in clients_all[s])
            flat_grads.extend(grads_all[s])
        km_info: dict = {}
        flat_pseudo = engine.pseudo_labels_seeds(
            flat_kmeans_keys, flat_grads, splits[0].num_classes,
            cfg.kmeans_iters, use_kernels=cfg.use_kernels, mesh=mesh,
            info=km_info)
        pseudo_all = engine.unflatten_seed_results(flat_pseudo, num_seeds,
                                                   num_parties)
        for s in range(num_seeds):
            # the k-means fold width actually run (S·K on the folded path, 1
            # on the ragged-shape fallback) — kernel and jnp routes alike
            diags[s]["kernel_fold"] = km_info.get("fold", 1)
            if "fallback" in km_info:
                diags[s]["kernel_fallback"] = km_info["fallback"]

    with obs.span("p4.ssl"):
        tasks_per_seed = []
        hp = cfg.ssl_hparams()
        for s in range(num_seeds):
            tasks = []
            fa = faults[s]
            for c, pseudo, x_o, x_u in zip(clients_all[s], pseudo_all[s],
                                           splits[s].aligned,
                                           splits[s].unaligned):
                diags[s]["kmeans_purity"].append(clustering.cluster_purity(
                    pseudo, splits[s].labels, splits[s].num_classes))
                # faulted folds give EVERY party a per-step commit mask (§16):
                # all-ones healthy, truncated straggler, all-zero dropped /
                # representation-only — mask as data, one stacked shape
                sv = (_fault_step_valid(fa, c.index, x_o.shape[0], hp,
                                        skip_all=(fa is not None
                                                  and fa.skips_ssl(c.index)))
                      if faulted else None)
                # equal-shape overlap variants pad x_o to a fixed capacity; the
                # split's validity mask zeroes the padded rows out of the loss
                tasks.append(ssl_task_for(c, x_o, pseudo, x_u,
                                          labeled_mask=splits[s].aligned_mask,
                                          step_valid=sv))
            diags[s]["pseudo_labels"] = pseudo_all[s]  # Ŷ_o^k — few-shot
            tasks_per_seed.append(tasks)           # ⑤' reuses them (Alg. 2)
        _commit_ssl(engine.train_clients_ssl_seeds(
            kss, tasks_per_seed, cfg.ssl_hparams(), mesh=mesh),
            diags, clients_all, mesh)

    # ⑤ upload refreshed reps;  ⑥ server trains classifier (seed-batched).
    # Parties dropped by now upload nothing: the server reconstructs their
    # slot via Eq. 10 attention from the lowest-index survivor's refreshed
    # upload over the stale step-① payloads it still holds (§16).
    with obs.span("p5.extract"):
        reps_all = [[c.extract(x_o).astype(cfg.rep_dtype)
                     for c, x_o in zip(clients_all[s], splits[s].aligned)]
                    for s in range(num_seeds)]
        if faulted:
            for s, fa in enumerate(faults):
                if fa is None:
                    continue
                for k in range(num_parties):
                    reps_all[s][k] = _dp_noised(fkeys[s], 2, k, fa,
                                                reps_all[s][k])
            _reconstruct_dropped(reps_all, stale_reps, faults, POINT_UPLOAD2,
                                 cfg.use_kernels)
        r3 = _phase_round(ledger, entry_ledgers)
        for k in range(num_parties):
            _log_phase(ledger, entry_ledgers, k, "up",
                       "reps_overlap_refreshed",
                       [reps_all[s][k] for s in range(num_seeds)], r3,
                       skip=_drop_skip(faults if faulted else None, k,
                                       POINT_UPLOAD2, num_seeds))

    with obs.span("p6.fit"):
        train_classifier_seeds(k_srvs, servers, reps_all,
                               [sp.labels for sp in splits],
                               epochs=cfg.server_epochs,
                               batch_size=cfg.batch_size,
                               learning_rate=cfg.server_lr, mesh=mesh)
        if final_reps_out is not None:
            final_reps_out.extend(reps_all)

    with obs.span("eval"):
        results = []
        for s in range(num_seeds):
            name, metric = _evaluate(
                servers[s], clients_all[s], splits[s], fault=faults[s],
                h_o_final=reps_all[s] if faulted else None,
                fkey=fkeys[s] if faulted else None,
                use_kernels=cfg.use_kernels)
            if faulted:
                diags[s].update(_fault_diags(faults[s], num_parties, metric))
            results.append(VFLResult(name, metric,
                                     entry_ledgers[s] if faulted else ledger,
                                     clients_all[s], servers[s], diags[s]))
    return results


def run_one_shot(
    key: jax.Array,
    split: VerticalSplit,
    extractors: Sequence[Model],
    ssl_cfgs: Sequence[SSLConfig],
    cfg: Optional[ProtocolConfig] = None,
    ledger: Optional[CommLedger] = None,
    clients: Optional[List[VFLClient]] = None,
    fault: Optional[FaultSpec] = None,
) -> VFLResult:
    return _one_shot_seeds([key], [split], [extractors], [ssl_cfgs], cfg,
                           ledger=ledger, clients_per_seed=[clients],
                           faults=None if fault is None else [fault])[0]


def _few_shot_finetune_seeds(
    keys: Sequence[jax.Array],
    splits: Sequence[VerticalSplit],
    extractors: Sequence[Sequence[Model]],
    ssl_cfgs: Sequence[Sequence[SSLConfig]],
    cfg: Optional[ProtocolConfig] = None,
    finetune_iterations: int = 200,
    faults: Optional[Sequence[Optional[FaultSpec]]] = None,
) -> List[VFLResult]:
    """Tab. 1's last row over S seeds at once: the seed-batched few-shot
    pass hands its per-seed output state (trained clients + fitted server)
    straight to the seed-batched vanilla finetune — the folded few-shot
    carry chains into the folded finetune session with no per-seed loop in
    between, and the shared ledger accumulates both stages' transfers."""
    from repro.core import baselines

    if faults is not None and any(fa is not None for fa in faults):
        raise ValueError(
            "few_shot_finetune does not support fault injection: the "
            "chained finetune stage is the iterative round loop — model "
            "its dropout cost with run_vanilla_seeds(faults=...) instead")
    cfg = cfg if cfg is not None else ProtocolConfig()
    k1s, k2s = [], []
    for s in range(len(keys)):
        key, k1, k2 = jax.random.split(keys[s], 3)
        k1s.append(k1)
        k2s.append(k2)
    fews = _few_shot_seeds(k1s, splits, extractors, ssl_cfgs, cfg)
    it_cfg = baselines.IterativeConfig(iterations=finetune_iterations,
                                       batch_size=cfg.batch_size,
                                       client_lr=cfg.client_lr / 10,
                                       server_lr=cfg.server_lr / 10,
                                       mesh=cfg.mesh)
    results = baselines.run_vanilla_seeds(
        k2s, splits, extractors, ssl_cfgs, it_cfg,
        clients_per_seed=[f.clients for f in fews],
        servers=[f.server for f in fews],
        ledger=fews[0].ledger)       # one shared ledger spans both stages
    for res, few in zip(results, fews):
        res.diagnostics.update(few.diagnostics)
        res.diagnostics["fewshot_metric"] = few.metric
    return results


def run_few_shot_finetune(
    key: jax.Array,
    split: VerticalSplit,
    extractors: Sequence[Model],
    ssl_cfgs: Sequence[SSLConfig],
    cfg: Optional[ProtocolConfig] = None,
    finetune_iterations: int = 200,
) -> VFLResult:
    """Tab. 1's last row: few-shot VFL as pre-training, then end-to-end
    vanilla-VFL finetuning of the whole stack (extractors + classifier),
    sharing one ledger so the combined communication cost is visible."""
    return _few_shot_finetune_seeds(
        [key], [split], [extractors], [ssl_cfgs], cfg,
        finetune_iterations=finetune_iterations)[0]


# ------------------------------------------------------------- few-shot VFL
def _few_shot_seeds(
    keys: Sequence[jax.Array],
    splits: Sequence[VerticalSplit],
    extractors: Sequence[Sequence[Model]],
    ssl_cfgs: Sequence[Sequence[SSLConfig]],
    cfg: Optional[ProtocolConfig] = None,
    ledger: Optional[CommLedger] = None,
    faults: Optional[Sequence[Optional[FaultSpec]]] = None,
) -> List[VFLResult]:
    """Alg. 2 over S seeds at once, continuing from the seed-batched
    one-shot pass: the aux-classifier fits, the ③' SDPA estimation +
    Eq. 8-9 gating (``engine.fewshot_probs_seeds`` — one batched program
    per party over the stacked seed axis, DESIGN.md §15), the masked
    phase-⑤' SSL sessions, and the final classifier re-fit all execute
    seed-batched with the exact single-seed key discipline.

    ``faults`` (DESIGN.md §16) threads straight through the one-shot pass
    (per-entry ledgers, same objects) and then governs round 2: a dropped
    party skips every round-2 event — its final upload is Eq. 10-
    reconstructed from the surviving anchor over the ⑤-era overlap view —
    while stragglers/representation-only parties re-enter ⑤' as
    ``step_valid`` masks on the SAME stacked session shapes."""
    cfg = cfg if cfg is not None else ProtocolConfig()
    ledger = ledger if ledger is not None else CommLedger()
    num_seeds = len(keys)
    num_parties = len(splits[0].aligned)
    mesh = engine.resolve_mesh(cfg.mesh)
    if faults is not None and len(faults) != num_seeds:
        raise ValueError("faults needs one entry (FaultSpec or None) per "
                         "stacked seed/scenario entry")
    faulted = faults is not None
    if not faulted:
        faults = [None] * num_seeds
    entry_ledgers = fkeys = None
    if faulted:
        entry_ledgers = [CommLedger() for _ in range(num_seeds)]
        fkeys = [jax.random.fold_in(keys[s], _FAULT_STREAM)
                 for s in range(num_seeds)]

    st_keys, k_ones = [], []
    for s in range(num_seeds):
        key, k_one = jax.random.split(keys[s])
        st_keys.append(key)
        k_ones.append(k_one)
    h_o_all: list = []
    ones = _one_shot_seeds(k_ones, splits, extractors, ssl_cfgs, cfg,
                           ledger=ledger, final_reps_out=h_o_all,
                           faults=faults if faulted else None,
                           ledgers=entry_ledgers)
    clients_all = [r.clients for r in ones]
    servers = [r.server for r in ones]
    diags = [dict(r.diagnostics) for r in ones]

    # ①' clients upload unaligned reps alongside the refreshed overlap reps
    # (h_o_all IS the step-⑤ upload — same params, same dtype — and shares
    # its round: the ledger tags the unaligned payload separately but the
    # event count matches the paper's 5 comm-times; see comm.py)
    with obs.span("f1.extract"):
        h_u_all = [[c.extract(x).astype(cfg.rep_dtype)
                    for c, x in zip(clients_all[s], splits[s].unaligned)]
                   for s in range(num_seeds)]
        if faulted:
            for s, fa in enumerate(faults):
                if fa is None:
                    continue
                for k in range(num_parties):
                    h_u_all[s][k] = _dp_noised(fkeys[s], 3, k, fa,
                                               h_u_all[s][k])
        if entry_ledgers is None:   # bundled with the ⑤ upload
            r3 = max(e.round for e in ledger.events)
        else:
            r3 = [max(e.round for e in led.events) for led in entry_ledgers]
        for k in range(num_parties):
            _log_phase(ledger, entry_ledgers, k, "up", "reps_unaligned",
                       [h_u_all[s][k] for s in range(num_seeds)], r3,
                       skip=_drop_skip(faults if faulted else None, k,
                                       POINT_ROUND2, num_seeds))

    # ②' server fits aux classifiers f_c^k (seed-batched) and reuses the
    # joint f_c
    with obs.span("f2.aux"):
        kas = []
        for s in range(num_seeds):
            st_keys[s], ka = jax.random.split(st_keys[s])
            kas.append(ka)
        fit_aux_classifiers_seeds(kas, servers, h_o_all,
                                  [sp.labels for sp in splits],
                                  epochs=cfg.server_epochs,
                                  batch_size=cfg.batch_size,
                                  learning_rate=cfg.server_lr, mesh=mesh)

    # ③' SDPA estimation + Eq. 8-9 gating;  ④' download p̂ — seed-batched
    # (DESIGN.md §15): per party, the S estimations + gates fold over the
    # stacked seed axis (one batched SDPA program per missing party — ONE
    # Pallas grid launch under cfg.use_kernels — and one vmapped gate
    # session); the single-seed path is the width-1 case of the same code
    # under the same session-cache keys.
    with obs.span("f3.sdpa"):
        probs_all = [[] for _ in range(num_seeds)]
        for s in range(num_seeds):
            diags[s]["fewshot_gate_rate"] = []
            diags[s]["sdpa_fold"] = num_seeds
        h_o_stacks = [jnp.stack([h_o_all[s][j] for s in range(num_seeds)])
                      for j in range(num_parties)]
        for k_idx in range(num_parties):
            h_u_stack = jnp.stack([h_u_all[s][k_idx]
                                   for s in range(num_seeds)])
            probs_stack = engine.fewshot_probs_seeds(
                servers, k_idx, h_u_stack, h_o_stacks, cfg.fewshot_threshold,
                use_kernels=cfg.use_kernels, mesh=mesh)
            for s in range(num_seeds):
                probs_all[s].append(probs_stack[s])
                diags[s]["fewshot_gate_rate"].append(
                    _safe_mean(probs_stack[s] > 0))

    with obs.span("f4.probs"):
        r4 = _phase_round(ledger, entry_ledgers)
        for k_idx in range(num_parties):
            _log_phase(ledger, entry_ledgers, k_idx, "down",
                       "pseudo_label_probs",
                       [probs_all[s][k_idx] for s in range(num_seeds)], r4,
                       skip=_drop_skip(faults if faulted else None, k_idx,
                                       POINT_ROUND2, num_seeds))

    # ⑤' clients expand the labeled set and re-run SSL (Alg. 2 l.11-19) as
    # masked fixed-shape sessions (DESIGN.md §9): every party's labeled set
    # is the full (x_o ∘ x_u) at the static capacity N_o + N_u with a
    # validity mask [1…1 ∘ gate], and the unlabeled set stays the full
    # private pool with the complementary mask — so ragged per-party gate
    # counts share one stacked shape and one scanned session, and an
    # all-gated pool is simply a zero-valid unlabeled
    # mask (no row ever sits in both sets). The paper keeps *every* sample
    # passing the Eq. 9 gate (p̂ > 0); fewshot_stochastic_gate restores the
    # legacy Bernoulli(p̂) subsampling for ablations. Overlap rows keep the
    # step-③ cluster pseudo-labels Ŷ_o^k (``fewshot_phase5_labels``).
    with obs.span("f5.ssl"):
        kss = []
        for s in range(num_seeds):
            st_keys[s], ks = jax.random.split(st_keys[s])
            kss.append(ks)
        tasks_per_seed = []
        hp = cfg.ssl_hparams()
        for s in range(num_seeds):
            tasks = []
            fa = faults[s]
            for c, probs, pseudo, x_o, x_u in zip(
                    clients_all[s], probs_all[s], diags[s]["pseudo_labels"],
                    splits[s].aligned, splits[s].unaligned):
                if cfg.fewshot_stochastic_gate:
                    st_keys[s], kb = jax.random.split(st_keys[s])
                    take = jax.random.bernoulli(
                        kb, jnp.clip(probs, 0.0, 1.0)).astype(jnp.float32)
                else:
                    take = (probs > 0).astype(jnp.float32)
                # a party absent from round 2 never received p̂: nothing gates
                # in, and its ⑤' session commits zero steps (step_valid below)
                skip_r2 = (fa is not None
                           and (fa.skips_ssl(c.index)
                                or fa.drops(c.index, POINT_ROUND2)))
                if skip_r2:
                    take = jnp.zeros_like(take)
                x_lab = jnp.concatenate([x_o, x_u], axis=0)
                y_lab = fewshot_phase5_labels(c, x_o, x_u, pseudo,
                                              cfg.fewshot_relabel_overlap)
                # an equal-shape overlap variant's padded x_o rows stay invalid
                # in phase ⑤' too: the overlap part of the mask is the split's
                # validity mask instead of all-ones
                o_mask = (jnp.ones(x_o.shape[0], jnp.float32)
                          if splits[s].aligned_mask is None
                          else splits[s].aligned_mask.astype(jnp.float32))
                lab_mask = jnp.concatenate([o_mask, take])
                sv = (_fault_step_valid(fa, c.index, x_lab.shape[0], hp,
                                        skip_all=skip_r2)
                      if faulted else None)
                tasks.append(ssl_task_for(c, x_lab, y_lab, x_u,
                                          labeled_mask=lab_mask,
                                          unlabeled_mask=1.0 - take,
                                          step_valid=sv))
                diags[s].setdefault("fewshot_take_rate", []).append(
                    _safe_mean(take))
            tasks_per_seed.append(tasks)
        _commit_ssl(engine.train_clients_ssl_seeds(
            kss, tasks_per_seed, cfg.ssl_hparams(), mesh=mesh),
            diags, clients_all, mesh)

    # ⑥' final upload + classifier re-fit (seed-batched). Round-2-dropped
    # parties upload nothing; their slot is Eq. 10-reconstructed from the
    # anchor's final upload over the ⑤-era overlap view (h_o_all).
    with obs.span("f6.fit"):
        reps_all = [[c.extract(x_o).astype(cfg.rep_dtype)
                     for c, x_o in zip(clients_all[s], splits[s].aligned)]
                    for s in range(num_seeds)]
        if faulted:
            for s, fa in enumerate(faults):
                if fa is None:
                    continue
                for k in range(num_parties):
                    reps_all[s][k] = _dp_noised(fkeys[s], 4, k, fa,
                                                reps_all[s][k])
            _reconstruct_dropped(reps_all, h_o_all, faults, POINT_ROUND2,
                                 cfg.use_kernels)
        r5 = _phase_round(ledger, entry_ledgers)
        for k in range(num_parties):
            _log_phase(ledger, entry_ledgers, k, "up", "reps_overlap_final",
                       [reps_all[s][k] for s in range(num_seeds)], r5,
                       skip=_drop_skip(faults if faulted else None, k,
                                       POINT_ROUND2, num_seeds))
        kfs = []
        for s in range(num_seeds):
            st_keys[s], kf = jax.random.split(st_keys[s])
            kfs.append(kf)
        train_classifier_seeds(kfs, servers, reps_all,
                               [sp.labels for sp in splits],
                               epochs=cfg.server_epochs,
                               batch_size=cfg.batch_size,
                               learning_rate=cfg.server_lr, mesh=mesh)

    with obs.span("eval"):
        results = []
        for s in range(num_seeds):
            name, metric = _evaluate(
                servers[s], clients_all[s], splits[s], fault=faults[s],
                h_o_final=reps_all[s] if faulted else None,
                fkey=fkeys[s] if faulted else None,
                use_kernels=cfg.use_kernels)
            if faulted:
                diags[s].update(_fault_diags(faults[s], num_parties, metric))
            results.append(VFLResult(name, metric,
                                     entry_ledgers[s] if faulted else ledger,
                                     clients_all[s], servers[s], diags[s]))
    return results


def run_few_shot(
    key: jax.Array,
    split: VerticalSplit,
    extractors: Sequence[Model],
    ssl_cfgs: Sequence[SSLConfig],
    cfg: Optional[ProtocolConfig] = None,
    fault: Optional[FaultSpec] = None,
) -> VFLResult:
    return _few_shot_seeds([key], [split], [extractors], [ssl_cfgs], cfg,
                           faults=None if fault is None else [fault])[0]


# ---------------------------------------------------- multi-seed orchestrator
def _splits_are_homogeneous(splits: Sequence[VerticalSplit]) -> bool:
    """True when every seed's split shares all shapes and the class count —
    the precondition of seed-batched execution (one scenario point's seeds
    satisfy it by construction; communication is then seed-invariant)."""
    s0 = splits[0]

    def sig(sp):
        mask = getattr(sp, "aligned_mask", None)
        return (tuple(x.shape for x in sp.aligned),
                tuple(x.shape for x in sp.unaligned),
                tuple(x.shape for x in sp.test_aligned),
                sp.labels.shape, sp.test_labels.shape, sp.num_classes,
                None if mask is None else tuple(mask.shape))

    return all(sig(sp) == sig(s0) for sp in splits[1:])


def _copy_ledger(ledger: CommLedger) -> CommLedger:
    return CommLedger(events=list(ledger.events),
                      _round_counter=ledger._round_counter)


def _assert_ledgers_identical(ledgers: Sequence[CommLedger]) -> None:
    l0 = ledgers[0]
    for i, led in enumerate(ledgers[1:], start=1):
        if (led.total_bytes() != l0.total_bytes()
                or led.comm_times() != l0.comm_times()
                or led.by_tag() != l0.by_tag()):
            raise ValueError(
                f"seed {i} produced a different communication ledger than "
                f"seed 0 — multi-seed runs of one scenario point must be "
                f"byte-identical ({led.total_bytes()} vs {l0.total_bytes()} "
                f"bytes)")


def _run_one_scenario_seeds(runner, impl, keys, splits, extractors, ssl_cfgs,
                            cfg, faults=None, **runner_kwargs
                            ) -> List[VFLResult]:
    """One scenario's S seeds when the cross-scenario fold doesn't apply:
    seed-batched when the runner has a registered ``*_seeds`` impl and the
    seeds share one shape, else a per-seed loop over the runner's cached
    sessions (with the ledger byte-identity asserted post hoc)."""
    num_seeds = len(keys)
    if impl is not None and _splits_are_homogeneous(splits):
        kw = dict(runner_kwargs)
        if faults is not None:
            kw["faults"] = list(faults)
        results = impl(list(keys), list(splits), list(extractors),
                       list(ssl_cfgs), cfg, **kw)
        if num_seeds > 1:       # the shared prototype ledger → per-seed copies
            for res in results:
                res.ledger = _copy_ledger(res.ledger)
    else:
        results = [runner(k, sp, ex, sc, cfg,
                          **(runner_kwargs if faults is None
                             else {**runner_kwargs, "fault": faults[i]}))
                   for i, (k, sp, ex, sc) in enumerate(zip(
                       keys, splits, extractors, ssl_cfgs))]
        _assert_ledgers_identical([r.ledger for r in results])
    for res in results:
        res.diagnostics.setdefault("scenario_fold", 1)
        res.diagnostics.setdefault("device_fold", 1)
    return results


def run_scenarios_seeds(
    runner,
    keys: Sequence[Sequence[jax.Array]],
    splits: Sequence[Sequence[VerticalSplit]],
    extractors: Sequence[Sequence[Sequence[Model]]],
    ssl_cfgs: Sequence[Sequence[Sequence[SSLConfig]]],
    cfg=None,
    **runner_kwargs,
) -> List[List[VFLResult]]:
    """Run C grouped scenarios × S seeds as ONE folded sweep (DESIGN.md
    §12). Arguments are rectangular C×S grids (``keys[c][s]`` …); returns
    the results on the same grid.

    The batch axis of every seed-batched runner is *anonymous* — nothing
    in the stacked programs distinguishes "seed s" from "scenario c, seed
    s" — so a group of scenarios whose splits share one shape signature
    flattens scenario-major into the registered ``*_seeds`` impl exactly
    like extra seeds: one vmapped S·C·K local-SSL session, one folded
    step-③ k-means, seed×scenario-batched server fits (or, for the
    iterative baselines, one ``vmap``-of-scan over S·C stacked carries).
    Session-cache keys never contain the batch width, so a C ≥ 2 fold
    against a warm single-scenario cache adds ZERO fresh session builds
    (tests/test_scenario_batched.py pins this, along with fold ≡
    per-scenario-loop parity at 1e-5).

    Each result's ``diagnostics["seed_fold"]`` / ``["scenario_fold"]``
    record the fold actually run (S and C on the folded path). Grids whose
    flat splits are NOT shape-homogeneous — or unregistered runners — fall
    back to the per-scenario path (``scenario_fold`` 1), which itself
    seed-batches where it can; :func:`run_seeds` is precisely the C = 1
    case. Ledgers are per-(scenario, seed) copies; byte-identity across
    the whole flat batch is asserted at every exchange on the folded path.
    Per-seed *state* kwargs are rejected exactly as in :func:`run_seeds`.
    """
    from repro.core import runners as runner_registry  # deferred: registry
                                                       # imports this module
    num_scenarios = len(keys)
    if not (len(splits) == len(extractors) == len(ssl_cfgs)
            == num_scenarios):
        raise ValueError("run_scenarios_seeds needs one per-seed list of "
                         "keys / splits / extractor stacks / ssl-cfg lists "
                         "per scenario")
    if num_scenarios == 0:
        return []
    num_seeds = len(keys[0])
    for c in range(num_scenarios):
        if not (len(keys[c]) == len(splits[c]) == len(extractors[c])
                == len(ssl_cfgs[c]) == num_seeds):
            raise ValueError(
                "run_scenarios_seeds needs a rectangular C×S grid: every "
                "scenario must carry the same per-seed list lengths")
    entry = runner_registry.resolve(runner)
    runner_registry.reject_stateful_kwargs("run_scenarios_seeds",
                                           runner_kwargs, entry)
    impl = entry.seeds_impl if entry is not None else None
    # faults is a C×S grid of Optional[FaultSpec] mirroring the data grids
    # (DESIGN.md §16); it flattens scenario-major with them, as per-entry
    # DATA — fold signatures and session-cache keys never see it
    faults = runner_kwargs.pop("faults", None)
    if faults is not None:
        if (len(faults) != num_scenarios
                or any(len(row) != num_seeds for row in faults)):
            raise ValueError("faults must mirror the C×S grid: one entry "
                             "(FaultSpec or None) per scenario per seed")
        if not any(fa is not None for row in faults for fa in row):
            faults = None
    name = (entry.name if entry is not None
            else getattr(runner, "__name__", type(runner).__name__))
    with obs.span("run", runner=name, S=num_seeds, C=num_scenarios,
                  K=len(splits[0][0].aligned)):
        return _run_grid(runner, impl, keys, splits, extractors, ssl_cfgs,
                         cfg, faults, runner_kwargs)


def _run_grid(runner, impl, keys, splits, extractors, ssl_cfgs, cfg, faults,
              runner_kwargs) -> List[List[VFLResult]]:
    """The C×S grid of :func:`run_scenarios_seeds`, validated: one folded
    sweep when the flat splits share one shape, else per scenario."""
    num_scenarios, num_seeds = len(keys), len(keys[0])
    flat_splits = [sp for row in splits for sp in row]
    if impl is not None and num_scenarios > 1 \
            and _splits_are_homogeneous(flat_splits):
        flat_keys = [k for row in keys for k in row]
        flat_ext = [e for row in extractors for e in row]
        flat_ssl = [s for row in ssl_cfgs for s in row]
        kw = dict(runner_kwargs)
        if faults is not None:
            kw["faults"] = [fa for row in faults for fa in row]
        results = impl(flat_keys, flat_splits, flat_ext, flat_ssl, cfg,
                       **kw)
        if len(results) > 1:    # the shared prototype ledger → per-entry copies
            for res in results:
                res.ledger = _copy_ledger(res.ledger)
        for res in results:
            # the impl counted the flat width as its seed fold; report the
            # grid's true factorization instead
            res.diagnostics["seed_fold"] = num_seeds
            res.diagnostics["scenario_fold"] = num_scenarios
        return [results[c * num_seeds:(c + 1) * num_seeds]
                for c in range(num_scenarios)]
    return [_run_one_scenario_seeds(runner, impl, list(keys[c]),
                                    list(splits[c]), list(extractors[c]),
                                    list(ssl_cfgs[c]), cfg,
                                    faults=(None if faults is None
                                            else list(faults[c])),
                                    **runner_kwargs)
            for c in range(num_scenarios)]


def run_seeds(
    runner,
    keys: Sequence[jax.Array],
    splits: Sequence[VerticalSplit],
    extractors: Sequence[Sequence[Model]],
    ssl_cfgs: Sequence[Sequence[SSLConfig]],
    cfg=None,
    **runner_kwargs,
) -> List[VFLResult]:
    """Run one scenario point over S seeds (DESIGN.md §10-11) — the C = 1
    case of :func:`run_scenarios_seeds`, under the same session-cache keys.

    EVERY registered runner executes seed-BATCHED: the protocol runners
    (``run_one_shot`` / ``run_few_shot`` / ``run_few_shot_finetune``) fold
    S·K local-SSL sessions into one stacked vmapped program with the
    k-means and server fits vmapped over the seed axis, and the iterative
    baselines (``run_vanilla`` / ``run_fedcvt`` / ``run_fedbcd``) stack
    their whole-session scan carries on a leading seed axis and train as
    one ``vmap``-of-scan program. The communication ledger is produced
    host-side ONCE and asserted byte-identical across seeds (each result
    carries its own copy). Every per-seed PRNG stream matches the
    corresponding single-seed run's exactly, so ``run_seeds`` agrees with
    a Python loop of single-seed runs at atol 1e-5
    (tests/test_seed_batched.py pins it, along with the
    zero-fresh-compiles contract for seeds ≥ 2).

    Unregistered runners — or seed sets whose splits don't share one
    shape — loop per seed over the runner's cached sessions, with the
    same ledger byte-identity assertion.

    Args mirror the runners', one entry per seed: ``keys[s]`` /
    ``splits[s]`` / ``extractors[s]`` / ``ssl_cfgs[s]``; ``cfg`` and
    ``runner_kwargs`` are shared. Per-seed *state* kwargs (``clients``,
    ``server``, ``ledger``) are rejected: one object cannot serve S seeds
    (a shared ledger would accumulate every seed's events and a shared
    client/server stack would be trained S times over) — call the runner
    directly for stateful single-seed composition. Returns one
    ``VFLResult`` per seed.
    """
    num_seeds = len(keys)
    if not (len(splits) == len(extractors) == len(ssl_cfgs) == num_seeds):
        raise ValueError("run_seeds needs one split / extractor stack / "
                         "ssl-cfg list per seed")
    from repro.core import runners as runner_registry
    runner_registry.reject_stateful_kwargs(
        "run_seeds", runner_kwargs, runner_registry.resolve(runner))
    faults = runner_kwargs.pop("faults", None)   # per-seed list → C = 1 grid
    if faults is not None:
        runner_kwargs["faults"] = [list(faults)]
    return run_scenarios_seeds(runner, [list(keys)], [list(splits)],
                               [list(extractors)], [list(ssl_cfgs)], cfg,
                               **runner_kwargs)[0]
