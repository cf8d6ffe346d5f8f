"""Window loop of the protocol cells: whole calls of
``repro.core.protocol.run_scenarios_seeds`` with the registry runner on the
kernel route, evaluation included.

Set-up builds the cell's ``seeds_per_call`` splits from ``--seed`` and runs
one untimed call, which compiles (or loads from the cache) every program the
window uses. Each call in the window draws fresh PRNG keys from
(``--seed``, call index) over the same data. Probes on the program's entry
points keep, for every call, what the timed path itself produced:

* step 3: the partial gradients and the pseudo-labels clustered from them;
* step 4, the SSL sessions: each party's key, task (data, pseudo-labels and
  parameters before) and parameters and last-step loss after;
* evaluation: the test reps and the joint head's logits.

After the window, a call drawn from the seed is compared with
``bench/reference`` (see ``numbers``).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, harness
from bench.reference import ssl as ref_ssl
from bench.reference import vfl as ref

#: calls compared with the reference after the window
CHECKED_CALLS = 1
_COMM_TIMES = 3


class Probes:
    """Wrappers on the program's entry points that keep their inputs and
    outputs for the call in progress. They change no argument and no
    result."""

    def __init__(self) -> None:
        self.record = None
        self._undo = []

    def _wrap(self, owner, attr, keep):
        orig = getattr(owner, attr)

        def wrapped(*args, **kwargs):
            out = orig(*args, **kwargs)
            if self.record is not None:
                keep(self.record, args, kwargs, out)
            return out

        setattr(owner, attr, wrapped)
        self._undo.append((owner, attr, orig))

    def install(self) -> None:
        from repro import engine
        from repro.core import server

        def kmeans(rec, args, kw, out):
            rec.setdefault("kmeans", []).append((list(args[1]), list(out)))

        def ssl(rec, args, kw, out):
            rec.setdefault("ssl", []).append(dict(
                keys=list(args[0]), tasks=[list(t) for t in args[1]],
                params=out[0], metrics=out[1]))

        def evaluate(rec, args, kw, out):
            rec.setdefault("eval", []).append((list(args[1]), out))

        self._wrap(engine, "pseudo_labels_seeds", kmeans)
        self._wrap(engine, "train_clients_ssl_seeds", ssl)
        self._wrap(server.VFLServer, "predict_logits", evaluate)

    def uninstall(self) -> None:
        for owner, attr, orig in reversed(self._undo):
            setattr(owner, attr, orig)
        self._undo = []


@dataclass
class State:
    cell: dict
    config: dict
    seed: int
    method: str
    bundles: list
    runner: Any
    cfg: Any
    key: Any
    probes: Probes
    calls: List[dict] = field(default_factory=list)
    #: reference sessions by (call, seed entry, party, dtype, half)
    sessions: Dict[tuple, Any] = field(default_factory=dict)


def setup(cell: dict, config: dict, seed: int) -> State:
    from repro import scenarios
    from repro.core import runners
    from repro.core.protocol import ProtocolConfig

    params = cell["params"]
    spec = harness.scenario_spec(config)
    bundles = [scenarios.build(spec, seed=harness.derive_seed(seed, s))
               for s in range(int(params["seeds_per_call"]))]
    chips = int(cell["chips"])
    cfg = ProtocolConfig(**config["protocol"],
                         mesh=chips if chips > 1 else None)
    state = State(cell=cell, config=config, seed=seed,
                  method=params["method"], bundles=bundles,
                  runner=runners.get(params["method"]).runner, cfg=cfg,
                  key=jax.random.PRNGKey(harness.derive_seed(seed, 1 << 20)),
                  probes=Probes())
    state.probes.install()
    call(state, 0, stream=1)        # warm-up: every program the window runs
    return state


def call(state: State, index: int, stream: int = 0):
    """One protocol call with keys from (seed, ``stream``, ``index``); waits
    for it. The window's calls are stream 0, the warm-up stream 1."""
    from repro.core.protocol import run_scenarios_seeds

    n = len(state.bundles)
    base = jax.random.fold_in(state.key, stream)
    keys = [jax.random.fold_in(base, index * n + s) for s in range(n)]
    grid = run_scenarios_seeds(
        state.runner, [keys], [[b.split for b in state.bundles]],
        [[b.extractors for b in state.bundles]],
        [[b.ssl_cfgs for b in state.bundles]], state.cfg)
    results = grid[0]
    jax.block_until_ready([(r.clients[0].params, r.server.params)
                           for r in results])
    return results


def window(state: State, seconds: float, tracer) -> harness.Window:
    trace_calls = int(state.cell["params"].get("trace_calls", 1))
    t0 = time.perf_counter()
    tracer.start()
    i = 0
    while True:
        if tracer.active and i >= trace_calls:
            tracer.stop()
        state.probes.record = rec = {}
        with harness.annotate("call"):
            rec["results"] = call(state, i)
        state.probes.record = None
        state.calls.append(rec)
        i += 1
        if time.perf_counter() - t0 >= seconds:
            break
    elapsed = time.perf_counter() - t0
    n = len(state.bundles)
    counters = {
        "kind": "protocol", "calls": i, "entries_per_call": n,
        "model_flops_per_call": flops.protocol_call(state.config,
                                                    state.method, n),
        "kernels": kernel_work(state.config, n),
    }
    return harness.Window(attempted=i, failed=0,
                          end_to_end={"protocol_s": (elapsed / i, "s")},
                          counters=counters)


def kernel_work(config: dict, entries: int) -> dict:
    """(ops, bytes) per call of each Pallas kernel the call launches."""
    sizes = flops.split_sizes(config)
    rep = int(config["scenario"]["rep_dim"])
    c = int(config["scenario"]["gen_params"].get("num_classes", 2))
    return {"kmeans": flops.kmeans_assign(entries * sizes["parties"],
                                          sizes["overlap"], c, rep)}


# ------------------------------------------------------------- checking


def expected_ledger(config: dict) -> list:
    """The exchanges of the paper's one-shot protocol, from shapes alone:
    (party, direction, tag, bytes, round), float32 payloads."""
    sizes = flops.split_sizes(config)
    k, n_o = sizes["parties"], sizes["overlap"]
    rep = 4 * int(config["scenario"]["rep_dim"])
    ev = [(p, "up", "reps_overlap", n_o * rep, 1) for p in range(k)]
    ev += [(p, "down", "partial_grads", n_o * rep, 2) for p in range(k)]
    ev += [(p, "up", "reps_overlap_refreshed", n_o * rep, 3)
           for p in range(k)]
    return ev


def checked_calls(state: State) -> List[int]:
    rng = np.random.default_rng(harness.derive_seed(state.seed, 7))
    n = len(state.calls)
    return sorted(rng.choice(n, size=min(n, CHECKED_CALLS), replace=False))


def reference_eval(state: State, entry: int, client_params, server_params,
                   precision: str = "highest"):
    """Reference test reps and joint-head logits of one entry's trained
    parameters."""
    split = state.bundles[entry].split
    ext = harness.extractor_kind(state.config)
    reps = [ref.extractor_forward(ext["kind"], p, x, ext["widths"],
                                  ext["blocks"], precision)
            for p, x in zip(client_params, split.test_aligned)]
    logits = ref.dense_forward(server_params, jnp.concatenate(reps, -1),
                               precision)
    return reps, logits


def reference_session(state: State, ci: int, rec: dict, s: int, p: int,
                      dtype=jnp.float32, half: bool = False):
    """The reference session of seed entry ``s``, party ``p`` of checked
    call ``ci``, from the parameters the party started with."""
    cache = (ci, s, p, jnp.dtype(dtype).name, half)
    if cache not in state.sessions:
        ssl = rec["ssl"][0]
        task = ssl["tasks"][s][p]
        key = jax.random.split(ssl["keys"][s], len(ssl["tasks"][s]))[p]
        ext = harness.extractor_kind(state.config)
        state.sessions[cache] = ref_ssl.session(
            {"extractor": task.params.extractor, "head": task.params.head},
            task.x_labeled, task.y_pseudo, task.x_unlabeled, key,
            ref_ssl.hyper(state.config), ext["widths"], ext["blocks"],
            dtype=dtype, half=half,
            precision=state.config["precision"]["ssl_sessions"])
    return state.sessions[cache]


def _norm(a) -> float:
    return float(jnp.linalg.norm(jnp.ravel(a)))


def session_gaps(before: dict, got: dict, got_loss: float,
                 want: ref_ssl.Session) -> dict:
    """One party's session against the reference's: ``change`` is the worst
    leaf's gap between the norms of the two sides' changes, over the
    reference's change of that leaf or of the median leaf, whichever is
    larger (leaves whose first reference gradient is under a thousandth of
    the median leaf's are left out); ``change_global`` is the gap between
    the norms of the whole changes over the reference's; ``loss`` is the
    last-step loss gap over the reference's last-step or median loss,
    whichever is larger. ``change_median`` (the median leaf's gap) is kept
    for the readings."""
    leaves = lambda t: jax.tree_util.tree_leaves(t)
    g1 = [_norm(g) for g in leaves(want.first_grad)]
    keep = [g >= 1e-3 * float(np.median(g1)) for g in g1]
    d_got, d_want = [], []
    for b, a, w, k in zip(leaves(before), leaves(got), leaves(want.params),
                          keep):
        if k:
            d_got.append(_norm(a - b))
            d_want.append(_norm(w - b))
    d_got, d_want = np.asarray(d_got), np.asarray(d_want)
    typical = float(np.median(d_want))
    gaps = np.abs(d_got - d_want) / np.maximum(np.maximum(d_want, typical),
                                               1e-30)
    whole = float(np.sqrt(np.sum(d_want ** 2)))
    losses = np.asarray(want.losses)
    loss = abs(got_loss - float(losses[-1])) / max(
        abs(float(losses[-1])), float(np.median(np.abs(losses))), 1e-30)
    return {"change": float(np.max(gaps)), "loss": loss,
            "change_median": float(np.median(gaps)),
            "change_global": abs(float(np.sqrt(np.sum(d_got ** 2))) - whole)
            / max(whole, 1e-30),
            "left_out": len(keep) - int(sum(keep))}


def numbers(state: State, outputs=None, stand_in: Optional[dict] = None,
            detail: bool = False) -> Dict[str, float]:
    """The numbers over the checked calls (worst entry of each). A cell
    compares those it gives a limit; the others are readings:

    * ``ledger_mismatches`` — exchanges differing from the paper's protocol
      (party, direction, tag, bytes, round) plus comm-time differences;
    * ``kmeans_moved_share`` — pseudo-labels that one reference Lloyd step
      from the program's own partition moves;
    * ``kmeans_inertia_excess`` — the program partition's inertia over the
      reference k-means's, minus 1 (0 when at least as tight);
    * ``ssl_unmoved_leaves`` — party leaves an SSL session left unchanged;
    * ``ssl_change_gap``, ``ssl_change_median``, ``ssl_change_global`` and
      ``ssl_loss_gap`` — each session against the reference session from
      the same start (``session_gaps``: the worst leaf, the median leaf,
      the whole change, the last step's loss);
    * ``ext_rel_gap`` — test reps against the reference extractor forward of
      the trained parameters (worst row's gap over the typical row's norm);
    * ``head_rel_gap`` — evaluation logits against the reference head over
      the reference reps.

    ``outputs`` replaces the program's outputs (faults planted in them); by
    default they are what the probes kept. ``stand_in`` (``dtype``,
    ``half``) puts a reference session of that kind in the program's place.
    ``detail`` adds the sessions' other readings (``session_gaps``) under
    ``ssl.<name>``, the worst party's.
    """
    cfg = state.config
    c = int(cfg["scenario"]["gen_params"].get("num_classes", 2))
    k = int(cfg["scenario"].get("num_parties", 2))
    want = expected_ledger(cfg)
    out = {"ledger_mismatches": 0.0, "kmeans_moved_share": 0.0,
           "kmeans_inertia_excess": 0.0, "ssl_unmoved_leaves": 0.0,
           "ssl_change_gap": 0.0, "ssl_change_median": 0.0,
           "ssl_change_global": 0.0, "ssl_loss_gap": 0.0,
           "ext_rel_gap": 0.0, "head_rel_gap": 0.0}
    n = len(state.bundles)
    for ci in checked_calls(state):
        rec = state.calls[ci] if outputs is None else outputs[ci]
        results = rec["results"]
        for r in results:
            got = [(e.party, e.direction, e.tag, e.bytes, e.round)
                   for e in r.ledger.events]
            bad = sum(a != b for a, b in zip(got, want)) + abs(len(got)
                                                               - len(want))
            bad += sum(abs(r.ledger.comm_times(p) - _COMM_TIMES)
                       for p in range(k))
            out["ledger_mismatches"] += bad
        grads, labels = rec["kmeans"][0]
        for i, (g, lab) in enumerate(zip(grads, labels)):
            moved = ref.kmeans_refit(g, lab, c)
            out["kmeans_moved_share"] = max(out["kmeans_moved_share"], float(
                jnp.mean(moved != lab)))
            key = jax.random.PRNGKey(harness.derive_seed(state.seed, 100 + i))
            _, best = ref.kmeans_fit(key, g, c)
            mine = ref.kmeans_inertia(g, lab, c)
            out["kmeans_inertia_excess"] = max(
                out["kmeans_inertia_excess"],
                float(mine / jnp.maximum(best, 1e-30)) - 1.0)
        ssl = rec["ssl"][0]
        for s, tasks in enumerate(ssl["tasks"]):
            for p, task in enumerate(tasks):
                before = {"extractor": task.params.extractor,
                          "head": task.params.head}
                if stand_in is None:
                    after = ssl["params"][s][p]
                    after = {"extractor": after.extractor, "head": after.head}
                    loss = float(ssl["metrics"][s][p].get("loss", np.nan))
                else:
                    other = reference_session(state, ci, rec, s, p,
                                              **stand_in)
                    after, loss = other.params, float(other.losses[-1])
                for lb, la in zip(jax.tree_util.tree_leaves(before),
                                  jax.tree_util.tree_leaves(after)):
                    if _norm(la - lb) <= 1e-6 * _norm(lb) + 1e-30:
                        out["ssl_unmoved_leaves"] += 1
                gaps = session_gaps(before, after, loss,
                                    reference_session(state, ci, rec, s, p))
                out["ssl_change_gap"] = max(out["ssl_change_gap"],
                                            gaps.pop("change"))
                out["ssl_change_median"] = max(out["ssl_change_median"],
                                               gaps.pop("change_median"))
                out["ssl_change_global"] = max(out["ssl_change_global"],
                                               gaps.pop("change_global"))
                out["ssl_loss_gap"] = max(out["ssl_loss_gap"],
                                          gaps.pop("loss"))
                if detail:
                    for name, value in gaps.items():
                        out[f"ssl.{name}"] = max(out.get(f"ssl.{name}", 0),
                                                 value)
        evals = rec["eval"][-n:]
        for s, (reps, logits) in enumerate(evals):
            r = results[s]
            want_reps, want_logits = reference_eval(
                state, s, [cl.params.extractor for cl in r.clients],
                r.server.params)
            out["ext_rel_gap"] = max(out["ext_rel_gap"], max(
                harness.row_gap(a, b) for a, b in zip(reps, want_reps)))
            out["head_rel_gap"] = max(out["head_rel_gap"],
                                      harness.row_gap(logits, want_logits))
    return out


def check(state: State, win: harness.Window) -> Dict[str, float]:
    state.probes.uninstall()
    return numbers(state)


def control_numbers(state: State, detail: bool = False) -> Dict[str, float]:
    """The control: the reference in the program's place, one precision
    below what the configuration states — the SSL sessions in bfloat16
    (parameters, optimizer state and activations; the configuration states
    float32), forwards and heads with float8 inputs (the program runs their
    matmuls at one bf16 pass), the step-3 assignment at three bf16 passes
    (the program runs it at HIGHEST) — judged by the same comparisons."""
    cfg = state.config
    c = int(cfg["scenario"]["gen_params"].get("num_classes", 2))
    session = numbers(state, stand_in={"dtype": jnp.bfloat16}, detail=detail)
    out = {k: v for k, v in session.items() if k.startswith("ssl")}
    out.update(kmeans_moved_share=0.0, ext_rel_gap=0.0, head_rel_gap=0.0)
    for ci in checked_calls(state):
        rec = state.calls[ci]
        grads, labels = rec["kmeans"][0]
        for g, lab in zip(grads, labels):
            low = ref.kmeans_refit(g, lab, c, precision="high")
            out["kmeans_moved_share"] = max(out["kmeans_moved_share"], float(
                jnp.mean(low != ref.kmeans_refit(g, lab, c))))
        for s, r in enumerate(rec["results"]):
            params = [cl.params.extractor for cl in r.clients]
            hi_reps, hi_logits = reference_eval(state, s, params,
                                                r.server.params)
            lo_reps, lo_logits = reference_eval(state, s, params,
                                                r.server.params, "fp8")
            out["ext_rel_gap"] = max(out["ext_rel_gap"], max(
                harness.row_gap(a, b) for a, b in zip(lo_reps, hi_reps)))
            out["head_rel_gap"] = max(out["head_rel_gap"],
                                      harness.row_gap(lo_logits, hi_logits))
    return out
