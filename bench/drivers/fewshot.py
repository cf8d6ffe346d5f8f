"""Window loop of the few-shot cells: whole calls of
``repro.core.protocol.run_scenarios_seeds`` with the registry ``few_shot``
runner on the kernel route, evaluation included.

Everything the protocol cells do is reused from ``bench/drivers/protocol.py``
(set-up, calls, window, the reference evaluation and session gaps). This
driver adds:

* the group plan: set-up reads how the engine partitions each call's S·K
  SSL tasks (``repro.engine.ssl_task_groups``; a program without it fails
  here, before any data is built) and the window reports it in its counters;
* probes on step 3': per party, its private reps, the overlap reps, the
  server's auxiliary and joint heads, the Eq. 10 estimates and p̂;
* the few-shot ledger (5 exchanges per client) and the tabular sessions
  of steps 4 and 5', each against ``bench/reference/ssl_tabular.py``;
* the SDPA kernel's work (``bench/flops.py::sdpa``) for its roofline.

After the window, a call drawn from the seed is compared with
``bench/reference`` (see ``numbers``).
"""
from __future__ import annotations

import sys
from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, harness
from bench.drivers import protocol as base
from bench.reference import fewshot as ref_fs
from bench.reference import ssl_tabular as ref_tab
from bench.reference import vfl as ref

_COMM_TIMES = 5
#: ``protocol.session_gaps``'s names → the numbers they are read under
_GAPS = {"change": "ssl_change_gap", "change_median": "ssl_change_median",
         "change_global": "ssl_change_global", "loss": "ssl_loss_gap"}
#: the exchanges only few-shot adds (Alg. 2's second round)
ROUND2_TAGS = ("reps_unaligned", "pseudo_label_probs", "reps_overlap_final")


class Probes(base.Probes):
    """The protocol cells' probes, and those of step 3' and of the SSL
    group plan."""

    def install(self) -> None:
        from repro import engine
        from repro.engine import dispatch, local_ssl

        super().install()

        def estimates(rec, args, kw, out):
            rec.setdefault("estimates", []).append(list(out))

        def gate(rec, args, kw, out):
            servers, k = args[0], args[1]
            rec.setdefault("fewshot", []).append(dict(
                k=k, h_u=args[2], h_o=list(args[3]), threshold=args[4],
                aux=[srv.aux_params[k] for srv in servers],
                joint=[srv.params for srv in servers],
                estimates=rec["estimates"].pop(), probs=out))

        def groups(rec, args, kw, out):
            rec.setdefault("groups", []).append([list(g) for g in out])

        self._wrap(dispatch, "estimate_missing_batched", estimates)
        self._wrap(engine, "fewshot_probs_seeds", gate)
        self._wrap(local_ssl, "ssl_task_groups", groups)


@dataclass
class State(base.State):
    #: group sizes of each SSL call's sessions, as set-up read them
    plan: List[List[int]] = field(default_factory=list)


def setup(cell: dict, config: dict, seed: int) -> State:
    from repro.engine import ssl_task_groups  # noqa: F401  (the group fold)
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
    state.probes.record = warm = {}
    base.call(state, 0, stream=1)    # warm-up: every program the window runs
    state.probes.record = None
    state.plan = [[len(g) for g in plan] for plan in warm.get("groups", [])]
    print(f"ssl sessions per call (members each): {state.plan}",
          file=sys.stderr)
    return state


def window(state: State, seconds: float, tracer) -> harness.Window:
    win = base.window(state, seconds, tracer)
    win.counters["kernels"].update(kernel_work(state.config,
                                               len(state.bundles)))
    win.counters["ssl_groups"] = state.plan
    return win


def kernel_work(config: dict, entries: int) -> dict:
    """(ops, bytes) per call of the SDPA kernel: each party's private rows
    attend over the overlap to estimate every other party's reps."""
    sizes = flops.split_sizes(config)
    k, rep = sizes["parties"], int(config["scenario"]["rep_dim"])
    return {"sdpa": flops.sdpa(entries * k * (k - 1), sizes["pool"],
                               sizes["overlap"], rep, rep)}


# ------------------------------------------------------------- checking
def expected_ledger(config: dict) -> list:
    """The exchanges of the paper's few-shot protocol, from shapes alone:
    the one-shot exchanges, then the private reps (bundled with the
    refreshed upload), p̂ and the final overlap upload, float32 payloads."""
    sizes = flops.split_sizes(config)
    k, n_o, n_u = sizes["parties"], sizes["overlap"], sizes["pool"]
    rep = 4 * int(config["scenario"]["rep_dim"])
    ev = base.expected_ledger(config)
    ev += [(p, "up", "reps_unaligned", n_u * rep, 3) for p in range(k)]
    ev += [(p, "down", "pseudo_label_probs", n_u * 4, 4) for p in range(k)]
    ev += [(p, "up", "reps_overlap_final", n_o * rep, 5) for p in range(k)]
    return ev


def reference_session(state: State, ci: int, rec: dict, phase: int, s: int,
                      p: int, dtype=jnp.float32, half: bool = False):
    """The reference session of SSL call ``phase`` (0: step 4, 1: step 5'),
    seed entry ``s``, party ``p`` of checked call ``ci``, from the
    parameters the party started with."""
    cache = (ci, phase, s, p, jnp.dtype(dtype).name, half)
    if cache not in state.sessions:
        ssl = rec["ssl"][phase]
        task = ssl["tasks"][s][p]
        key = jax.random.split(ssl["keys"][s], len(ssl["tasks"][s]))[p]
        state.sessions[cache] = ref_tab.session(
            {"extractor": task.params.extractor, "head": task.params.head},
            task.x_labeled, task.y_pseudo, task.x_unlabeled, key,
            ref_tab.hyper(state.config), task.labeled_mask,
            task.unlabeled_mask, dtype=dtype, half=half,
            precision=state.config["precision"]["ssl_sessions"])
    return state.sessions[cache]


def _ledger_mismatches(config: dict, results) -> float:
    k = int(config["scenario"].get("num_parties", 2))
    want = expected_ledger(config)
    bad = 0
    for r in results:
        got = [(e.party, e.direction, e.tag, e.bytes, e.round)
               for e in r.ledger.events]
        bad += sum(a != b for a, b in zip(got, want)) + abs(len(got)
                                                            - len(want))
        bad += sum(abs(r.ledger.comm_times(p) - _COMM_TIMES)
                   for p in range(k))
    return float(bad)


def _sessions(state: State, ci: int, rec: dict, out: dict,
              stand_in: Optional[dict], detail: bool) -> None:
    """Unmoved leaves and the gaps to the reference of every session of
    steps 4 and 5', the worst of each."""
    for phase, ssl in enumerate(rec["ssl"]):
        for s, tasks in enumerate(ssl["tasks"]):
            for p, task in enumerate(tasks):
                before = {"extractor": task.params.extractor,
                          "head": task.params.head}
                if stand_in is None:
                    after = ssl["params"][s][p]
                    after = {"extractor": after.extractor, "head": after.head}
                    loss = float(ssl["metrics"][s][p].get("loss", np.nan))
                else:
                    other = reference_session(state, ci, rec, phase, s, p,
                                              **stand_in)
                    after, loss = other.params, float(other.losses[-1])
                for lb, la in zip(jax.tree_util.tree_leaves(before),
                                  jax.tree_util.tree_leaves(after)):
                    if base._norm(la - lb) <= 1e-6 * base._norm(lb) + 1e-30:
                        out["ssl_unmoved_leaves"] += 1
                gaps = base.session_gaps(
                    before, after, loss,
                    reference_session(state, ci, rec, phase, s, p))
                for name, key in _GAPS.items():
                    out[key] = max(out[key], gaps.pop(name))
                if detail:
                    for name, value in gaps.items():
                        out[f"ssl.{name}"] = max(out.get(f"ssl.{name}", 0),
                                                 value)


def _gate_numbers(f: dict, s: int, got=None) -> dict:
    """Entry ``s`` of one party's step 3' against the reference: the
    estimates' gap (``harness.row_gap``) and, over the program's heads,
    the share of rows whose p̂ > 0 decision differs and the largest p̂ gap
    among the rows that agree. ``got`` (estimates, p̂) replaces the
    program's; by default they are what the probes kept."""
    k = f["k"]
    h_u = f["h_u"][s]
    h_o = [h[s] for h in f["h_o"]]
    est, probs = got if got is not None else (
        [e[s] for e in f["estimates"]], f["probs"][s])
    want = ref_fs.estimates(h_u, h_o, k)
    p_ref = ref_fs.gate(f["aux"][s], f["joint"][s], h_u, want, k,
                        float(f["threshold"]))
    flips = (probs > 0) != (p_ref > 0)
    return {"sdpa_rel_gap": max(harness.row_gap(a, b)
                                for a, b in zip(est, want)),
            "gate_flip_share": float(jnp.mean(flips)),
            "gate_p_gap": float(jnp.max(jnp.where(flips, 0.0,
                                                  jnp.abs(probs - p_ref))))}


def numbers(state: State, outputs=None, stand_in: Optional[dict] = None,
            detail: bool = False) -> Dict[str, float]:
    """The numbers over the checked calls (worst entry of each). A cell
    compares those it gives a limit; the others are readings:

    * ``ledger_mismatches`` — exchanges differing from the paper's few-shot
      protocol (party, direction, tag, bytes, round) plus comm-time
      differences (5 per client);
    * ``kmeans_moved_share``, ``kmeans_inertia_excess``, ``ext_rel_gap``
      and ``head_rel_gap`` — as the protocol cells read them (the heads
      over the final evaluation);
    * ``ssl_unmoved_leaves`` and the session gaps ``ssl_change_gap``,
      ``ssl_change_median``, ``ssl_change_global``, ``ssl_loss_gap`` —
      over every session of steps 4 and 5', each against the reference
      tabular session from the same start;
    * ``sdpa_rel_gap`` — the step-3' Eq. 10 estimates against the
      reference's (HIGHEST) on the same reps;
    * ``gate_flip_share`` — the share of a party's private rows whose
      p̂ > 0 decision differs from the reference gate's over the program's
      heads;
    * ``gate_p_gap`` — the largest p̂ gap among the rows that agree.

    ``outputs`` and ``stand_in`` (a reference session of that ``dtype`` or
    ``half`` in the program's place) as in ``protocol.numbers``.
    """
    cfg = state.config
    # the protocol cells' numbers, with the sessions left out (there they
    # are compared with the image reference)
    recs = [dict(rec, ssl=[dict(rec["ssl"][0], tasks=[])])
            for rec in (state.calls if outputs is None else outputs)]
    out = base.numbers(state, recs)
    out.update(ledger_mismatches=0.0, sdpa_rel_gap=0.0, gate_flip_share=0.0,
               gate_p_gap=0.0)
    for ci in base.checked_calls(state):
        rec = state.calls[ci] if outputs is None else outputs[ci]
        out["ledger_mismatches"] += _ledger_mismatches(cfg, rec["results"])
        _sessions(state, ci, rec, out, stand_in, detail)
        for f in rec["fewshot"]:
            for s in range(len(state.bundles)):
                for name, value in _gate_numbers(f, s).items():
                    out[name] = max(out[name], value)
    return out


def check(state: State, win: harness.Window) -> Dict[str, float]:
    state.probes.uninstall()
    return numbers(state)


def control_numbers(state: State, detail: bool = False) -> Dict[str, float]:
    """The control: the reference in the program's place, one precision
    below what the configuration states — the SSL sessions in bfloat16
    (stated: float32), forwards and heads with float8 inputs (stated: one
    bf16 pass), the step-3 assignment and Eq. 10 at three bf16 passes
    (stated: HIGHEST), the gate's heads with float8 inputs — judged by the
    same comparisons."""
    cfg = state.config
    c = int(cfg["scenario"]["gen_params"].get("num_classes", 2))
    session = numbers(state, stand_in={"dtype": jnp.bfloat16}, detail=detail)
    out = {k: v for k, v in session.items() if k.startswith("ssl")}
    out.update(kmeans_moved_share=0.0, ext_rel_gap=0.0, head_rel_gap=0.0,
               sdpa_rel_gap=0.0, gate_flip_share=0.0, gate_p_gap=0.0)
    for ci in base.checked_calls(state):
        rec = state.calls[ci]
        grads, labels = rec["kmeans"][0]
        for g, lab in zip(grads, labels):
            low = ref.kmeans_refit(g, lab, c, precision="high")
            out["kmeans_moved_share"] = max(out["kmeans_moved_share"], float(
                jnp.mean(low != ref.kmeans_refit(g, lab, c))))
        for s, r in enumerate(rec["results"]):
            params = [cl.params.extractor for cl in r.clients]
            hi_reps, hi_logits = base.reference_eval(state, s, params,
                                                     r.server.params)
            lo_reps, lo_logits = base.reference_eval(state, s, params,
                                                     r.server.params, "fp8")
            out["ext_rel_gap"] = max(out["ext_rel_gap"], max(
                harness.row_gap(a, b) for a, b in zip(lo_reps, hi_reps)))
            out["head_rel_gap"] = max(out["head_rel_gap"],
                                      harness.row_gap(lo_logits, hi_logits))
        for f in rec["fewshot"]:
            for s in range(len(state.bundles)):
                h_o = [h[s] for h in f["h_o"]]
                est = ref_fs.estimates(f["h_u"][s], h_o, f["k"], "high")
                probs = ref_fs.gate(f["aux"][s], f["joint"][s], f["h_u"][s],
                                    est, f["k"], float(f["threshold"]), "fp8")
                for name, value in _gate_numbers(f, s,
                                                 got=(est, probs)).items():
                    out[name] = max(out[name], value)
    return out


def fault_numbers(state: State) -> Dict[str, Dict[str, float]]:
    """Faults planted in what step 3' produced and in the ledger: the gate
    inverted (rows that passed dropped, rows that failed kept at p̂ 1), the
    estimates rolled by one row, round 2 dropped from the ledger."""

    def alter(fn):
        recs = []
        for rec in state.calls:
            rec = dict(rec)
            fn(rec)
            recs.append(rec)
        return numbers(state, recs)

    def inverted(f):
        return dict(f, probs=jnp.where(f["probs"] > 0, 0.0, 1.0))

    def rolled(f):
        return dict(f, estimates=[jnp.roll(e, 1, axis=1)
                                  for e in f["estimates"]])

    def one_round(r):
        from repro.core.comm import CommLedger

        events = [e for e in r.ledger.events if e.tag not in ROUND2_TAGS]
        return replace(r, ledger=CommLedger(events=events))

    return {
        "gate_inverted": alter(lambda r: r.update(
            fewshot=[inverted(f) for f in r["fewshot"]])),
        "estimates_rolled": alter(lambda r: r.update(
            fewshot=[rolled(f) for f in r["fewshot"]])),
        "round2_dropped": alter(lambda r: r.update(
            results=[one_round(x) for x in r["results"]])),
    }
