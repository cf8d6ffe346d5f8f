"""Online VFL serving: a loaded artifact behind one fused jitted forward.

The deployment side of the paper's claim (DESIGN.md §13): after ~1-2
communication rounds the parties hold a *joint* model, and this module is
what answers queries with it. A :class:`ServingEngine` wraps a
:class:`~repro.checkpoint.artifact.TrainedVFLModel` in ONE jitted forward —
party extractors and the server head fused into a single program, vmapped
over the party axis when ``parties_are_homogeneous`` (equal specs ⇒ one
stacked extractor call, the serving analogue of the engine's training-time
fast path) and Python-composed inside the same jit otherwise — and drives
continuous traffic through the fixed-shape masked batcher of
``launch/batching.py``: requests pad to the engine's capacity, validity
masks neutralize the padding, and input buffers are donated, so
changing traffic never recompiles and steady-state serving allocates no
fresh forward buffers.

The fused program is built through the engine-wide session cache
(``engine/sessions.py``, domain ``"serving"``) under the artifact's model
identity — a key that never encodes batch width — so serving adds exactly
ONE fresh session build per deployed model: every later batch shape, every
re-instantiated engine over the same artifact, re-serves it
(tests/test_serving.py pins the zero-fresh-misses contract).

Kernel routing is roofline-informed (:class:`KernelRouter`): the SDPA
missing-party estimation of Eq. 10 — the serveable Pallas hot-spot, used
when a querying party lacks the other parties' features — routes to the
flash-style blocked kernel only where ``roofline/`` analysis says it beats
XLA (score-matrix working sets past VMEM scale, never under CPU interpret
mode); the zoo-serving thresholds for ``rmsnorm`` (rows·d ≳ a few MB,
kernels/rmsnorm/ops.py) and ``decode_attention`` (S ≳ 8k,
kernels/decode_attention/ops.py) live on the same router.

CLI::

    PYTHONPATH=src python -m repro.launch.vfl_serve \
        --artifact artifacts/hard32 --capacity 64 --requests 32
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.checkpoint.artifact import TrainedVFLModel, load_artifact
from repro.engine.dispatch import estimate_missing_fused
from repro.engine.sessions import cached_session, model_key
from repro.kernels import interpret_mode
from repro.launch import batching
from repro.launch.compile_cache import enable_compile_cache

SERVING_DOMAIN = "serving"


@dataclasses.dataclass(frozen=True)
class KernelRouter:
    """Roofline-informed Pallas-vs-XLA routing for the serving hot paths.

    One rule per kernel, each citing the crossover its ops.py derives; on
    CPU (interpret mode) Pallas never wins — interpretation is strictly
    overhead — so everything routes to XLA.
    """

    backend: str
    interpret: bool

    @staticmethod
    def default() -> "KernelRouter":
        return KernelRouter(backend=jax.default_backend(),
                            interpret=interpret_mode())

    @property
    def pallas_viable(self) -> bool:
        return not self.interpret and self.backend == "tpu"

    def use_sdpa(self, n_u: int, n_o: int, d: int, batch: int = 1) -> bool:
        """Eq. 10 estimation: the flash-style blocked kernel wins when the
        score matrices no longer fit VMEM-resident tiles — i.e. when
        materializing softmax(H_u H_oᵀ) costs an extra HBM round-trip
        (kernels/sdpa_estimator). Below that XLA fuses the chain fine.
        ``batch`` is the batched-grid width (a served partial-party query
        runs all K−1 estimates as ONE ``(K−1, …)`` grid launch, so the
        roofline sees the whole B·N_u·N_o score volume, not one slice)."""
        return self.pallas_viable and batch * n_u * n_o * 4 >= 4 << 20

    def use_rmsnorm(self, rows: int, d: int) -> bool:
        """Fused RMSNorm wins on large activations (rows·d ≳ a few MB)
        where XLA's unfused upcast/variance round-trips dominate the
        1R+1W memory floor (kernels/rmsnorm/ops.py)."""
        return self.pallas_viable and rows * d * 4 >= 4 << 20

    def use_decode_attention(self, seq_len: int) -> bool:
        """Flash-decode pays past S ≳ 8k context
        (kernels/decode_attention/ops.py)."""
        return self.pallas_viable and seq_len >= 8192


def _serving_key(art: TrainedVFLModel) -> tuple:
    """The fused forward's session-cache key: the artifact's model identity
    (per-party apply identity + head identity + fusion strategy). No batch
    width, no capacity — one cached program per deployed model."""
    exts = art.extractors()
    clf = art.classifier()
    return (tuple(model_key(e) for e in exts), model_key(clf),
            art.parties_are_homogeneous)


def _build_fused_forward(art: TrainedVFLModel):
    """ONE jitted program: K extractors + joint head. Parameters travel as
    arguments (the session-cache contract), the per-party inputs are donated
    (they are per-request scratch), and the validity mask zeroes padding
    logits."""
    exts = art.extractors()
    clf = art.classifier()

    if art.parties_are_homogeneous:
        apply0 = exts[0].apply

        def raw(client_ext_params, server_params, xs, mask):
            stacked = jnp.stack(xs)                       # (K, capacity, ...)
            reps = jax.vmap(apply0)(client_ext_params, stacked)  # (K, B, r)
            # party-major flatten — identical layout to training-time
            # concat_reps, so the head sees exactly the trained geometry
            flat = jnp.transpose(reps, (1, 0, 2)).reshape(reps.shape[1], -1)
            logits = clf.apply(server_params, flat)
            return jnp.where(mask[:, None], logits, 0.0)
    else:

        def raw(client_ext_params, server_params, xs, mask):
            reps = [e.apply(p, x)
                    for e, p, x in zip(exts, client_ext_params, xs)]
            logits = clf.apply(server_params, jnp.concatenate(reps, axis=-1))
            return jnp.where(mask[:, None], logits, 0.0)

    # donating params would free them after the first call; only the
    # per-request inputs (xs, mask) are scratch
    return jax.jit(raw, donate_argnums=(2, 3))


class ServingEngine:
    """Continuous batched inference over one deployed VFL model."""

    def __init__(self, art: TrainedVFLModel, capacity: int = 64,
                 router: Optional[KernelRouter] = None) -> None:
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.art = art
        self.capacity = int(capacity)
        self.router = router or KernelRouter.default()
        if art.parties_are_homogeneous:
            self._ext_params = jax.tree_util.tree_map(
                lambda *ps: jnp.stack(ps),
                *[p.extractor for p in art.client_params])
        else:
            self._ext_params = [p.extractor for p in art.client_params]

    # ------------------------------------------------------------ forward
    def _fused(self):
        """The session-cached jitted forward (hits/misses visible under
        ``session_cache_stats("serving")``)."""
        return cached_session(SERVING_DOMAIN, _serving_key(self.art),
                              lambda: _build_fused_forward(self.art))

    def step(self, batch: batching.MaskedBatch) -> jnp.ndarray:
        """One fixed-shape forward over a padded batch → (capacity, C)
        logits (padding rows zeroed). The raw unit ``batching.drive``
        times."""
        return self._fused()(self._ext_params, self.art.server_params,
                             batch.xs, batch.mask)

    def predict_logits(self, xs: Sequence[jnp.ndarray]) -> jnp.ndarray:
        """Logits for an arbitrary-size request: chunk to capacity, pad,
        run the fused forward, keep the valid rows. Matches the artifact's
        unbatched reference oracle at 1e-5."""
        parts = []
        for chunk in batching.chunk_requests(xs, self.capacity):
            batch = batching.pad_to_capacity(chunk, self.capacity)
            parts.append(self.step(batch)[:batch.n])
        return (jnp.concatenate(parts, axis=0) if len(parts) > 1
                else parts[0])

    def predict(self, xs: Sequence[jnp.ndarray]) -> jnp.ndarray:
        """Class predictions (argmax over the fused logits)."""
        return jnp.argmax(self.predict_logits(xs), axis=-1)

    # ------------------------------------------- partial-party queries
    def predict_logits_partial(self, x_k: jnp.ndarray,
                               k: int) -> jnp.ndarray:
        """Serve a query where ONLY party ``k``'s features are present:
        estimate every other party's representation from the artifact's
        stored overlap reps via Eq. 10 (the few-shot SDPA estimator,
        kernel-routed by the roofline rules), then run the joint head."""
        art = self.art
        if art.overlap_reps is None:
            raise ValueError(
                "artifact carries no overlap_reps — re-export it with "
                "to_artifact(..., split=split) to serve partial-party "
                "queries")
        if not 0 <= k < art.num_parties:
            raise ValueError(f"party index {k} out of range "
                             f"[0, {art.num_parties})")
        ext = art.extractors()[k]
        h_u_k = ext.apply(art.client_params[k].extractor, x_k)
        n_o = int(art.overlap_reps[0].shape[0])
        use_kernels = self.router.use_sdpa(int(h_u_k.shape[0]), n_o,
                                           int(h_u_k.shape[-1]),
                                           batch=art.num_parties - 1)
        # all K−1 missing-party estimates as ONE batched grid launch when
        # the other parties' rep dims agree (DESIGN.md §15)
        estimates = estimate_missing_fused(h_u_k, art.overlap_reps, k,
                                           use_kernels=use_kernels)
        est = iter(estimates)
        reps = [h_u_k if j == k else next(est)
                for j in range(art.num_parties)]
        return art.classifier().apply(art.server_params,
                                      jnp.concatenate(reps, axis=-1))


# ------------------------------------------------------------------- CLI
def synthetic_requests(art: TrainedVFLModel, num_requests: int,
                       batch_size: int, seed: int = 0) -> List[tuple]:
    """Per-party Gaussian feature blocks matching the artifact's declared
    shapes — traffic for demos and latency benchmarks."""
    key = jax.random.PRNGKey(seed)
    reqs = []
    for _ in range(num_requests):
        xs = []
        for shape in art.feature_shapes:
            key, sub = jax.random.split(key)
            xs.append(jax.random.normal(sub, (batch_size,) + tuple(shape)))
        reqs.append(tuple(xs))
    return reqs


def serve_traffic(engine: ServingEngine,
                  requests: Sequence[Sequence[jnp.ndarray]],
                  warmup: int = 1):
    """Drive a request stream through the engine's fused step via the
    shared batcher; returns (outputs, LatencyRecorder)."""
    return batching.drive(engine.step, requests, engine.capacity,
                          warmup=warmup)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--artifact", required=True,
                    help="directory written by save_artifact")
    ap.add_argument("--capacity", type=int, default=64,
                    help="fixed batch capacity (ONE compiled shape)")
    ap.add_argument("--requests", type=int, default=32,
                    help="number of synthetic requests to serve")
    ap.add_argument("--batch-size", type=int, default=None,
                    help="rows per request (default: capacity)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    t0 = time.time()
    art = load_artifact(args.artifact)
    engine = ServingEngine(art, capacity=args.capacity)
    print(f"loaded {args.artifact}: scenario={art.scenario} "
          f"K={art.num_parties} classes={art.num_classes} "
          f"homogeneous={art.parties_are_homogeneous} "
          f"({time.time() - t0:.2f}s)")

    bs = args.batch_size or args.capacity
    reqs = synthetic_requests(art, args.requests, bs, seed=args.seed)
    outs, rec = serve_traffic(engine, reqs)
    s = rec.summary()
    print(f"served {s['rows']} rows in {s['batches']} batches "
          f"(capacity {engine.capacity}): p50={s['p50_ms']:.2f}ms "
          f"p99={s['p99_ms']:.2f}ms throughput={s['rows_per_s']:.0f} rows/s")
    preds = jnp.argmax(outs[0], axis=-1)
    print(f"sample predictions: {preds[:8].tolist()}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
