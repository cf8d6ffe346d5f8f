"""VFL client: representation extractor + local classification head + SSL.

The client never sees true labels. Its local model is (extractor f_k → local
head), trained by semi-supervised learning on gradient-clustering
pseudo-labels (one-shot, Alg. 1 l.28-34) optionally expanded with the
server-gated pseudo-labeled unaligned samples (few-shot, Alg. 2 l.11-19).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.ssl import SSLConfig
from repro.engine import sessions
from repro.engine.local_ssl import PartyParams, PartyTask
from repro.models.extractors import Model, make_classifier

# The (extractor, head) parameter pair is defined by the engine layer so the
# protocol path and the multi-pod schedule train the same structure.
ClientParams = PartyParams


@dataclass
class VFLClient:
    index: int
    extractor: Model
    head: Model
    params: ClientParams
    ssl_cfg: SSLConfig
    feature_mean: Optional[jnp.ndarray]   # x̄ for FixMatch-tab

    # ------------------------------------------------------------------ api
    def extract(self, x: jnp.ndarray) -> jnp.ndarray:
        return extract_program(self.extractor)(self.params.extractor, x)

    def local_logits(self, x: jnp.ndarray) -> jnp.ndarray:
        return self.head.apply(self.params.head, self.extract(x))

    def predict(self, x: jnp.ndarray) -> jnp.ndarray:
        return jnp.argmax(self.local_logits(x), axis=-1)


# Rows per block of the compiled forward. Each row's reps depend on that row
# alone, so blocking changes no math; a block's activations stay small. On a
# TPU v5e, WRN-28-2 over 12,000 half-image rows took 92.6 ms as one pass and
# 34.2 ms as 24 blocks of 500 (PERF.md §6).
_BLOCK_ROWS = 512


def extract_program(extractor: Model):
    """The extractor's forward as one compiled program, from the session
    cache (domain ``"extract"``, DESIGN.md §9). Parameters and rows are
    arguments, so one program per architecture serves every party, seed
    and call; ``jax.jit`` specialises it once per row count. More than
    ``_BLOCK_ROWS`` rows run as ``lax.map`` over equal blocks of at most
    that many, the last padded. A model whose closure ``model_key`` cannot
    digest is keyed on the ``Model`` itself: it misses once per object, not
    once per call."""
    key = sessions.model_key(extractor)
    if not sessions.is_digested(key):
        key = ("model", extractor)

    def build():
        def extract_forward(params, x):
            n = x.shape[0]
            blocks = -(-n // _BLOCK_ROWS)
            if blocks <= 1:
                return extractor.apply(params, x)
            rows = -(-n // blocks)
            pad = [(0, blocks * rows - n)] + [(0, 0)] * (x.ndim - 1)
            xb = jnp.pad(x, pad).reshape((blocks, rows) + x.shape[1:])
            out = jax.lax.map(lambda b: extractor.apply(params, b), xb)
            return out.reshape((blocks * rows,) + out.shape[2:])[:n]
        return jax.jit(extract_forward)

    return sessions.cached_session("extract", key, build)


def make_client(key: jax.Array, index: int, extractor: Model, num_classes: int,
                sample_input: jnp.ndarray, ssl_cfg: SSLConfig,
                local_data_for_mean: Optional[jnp.ndarray] = None) -> VFLClient:
    k_e, k_h = jax.random.split(key)
    e_params = extractor.init(k_e, sample_input)
    head = make_classifier(num_classes)
    reps = extractor.apply(e_params, sample_input[:1])
    h_params = head.init(k_h, reps)
    fm = None
    if (local_data_for_mean is not None and local_data_for_mean.ndim == 2
            and local_data_for_mean.shape[0] > 0):   # empty pool ⇒ NaN mean
        fm = jnp.mean(local_data_for_mean, axis=0)
    return VFLClient(index=index, extractor=extractor, head=head,
                     params=ClientParams(e_params, h_params),
                     ssl_cfg=ssl_cfg, feature_mean=fm)


# ----------------------------------------------------------------- SSL loop
def ssl_task_for(client: VFLClient, x_labeled: jnp.ndarray,
                 y_pseudo: jnp.ndarray, x_unlabeled: jnp.ndarray,
                 labeled_mask: Optional[jnp.ndarray] = None,
                 unlabeled_mask: Optional[jnp.ndarray] = None,
                 step_valid: Optional[jnp.ndarray] = None) -> PartyTask:
    """Package this client's local-SSL problem for the engine layer.

    Pass ``labeled_mask`` / ``unlabeled_mask`` for the masked fixed-shape
    sessions of few-shot phase ⑤' (data padded to a static capacity; masked
    rows contribute zero loss — DESIGN.md §9), ``step_valid`` for faulted
    sessions (per-step commit mask — stragglers, dropped or
    representation-only parties; DESIGN.md §16)."""
    return PartyTask(extractor=client.extractor, head=client.head,
                     params=PartyParams(*client.params),
                     ssl_cfg=client.ssl_cfg,
                     x_labeled=x_labeled, y_pseudo=y_pseudo,
                     x_unlabeled=x_unlabeled,
                     feature_mean=client.feature_mean,
                     labeled_mask=labeled_mask,
                     unlabeled_mask=unlabeled_mask,
                     step_valid=step_valid)
