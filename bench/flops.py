"""Operations and bytes computed from shapes: the yardstick's arithmetic for
MFU and roofline shares. Only multiply-adds of matmuls and convolutions
count (2 operations each); normalization, activations and softmax
exponentials are left out, as model FLOPs conventionally leave them.

A backward pass counts twice its forward (gradients of the activations and
of the weights). FixMatch's weak-view forward runs under stop_gradient and
counts once.
"""
from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; an unknown device raises."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


# ---------------------------------------------------------- extractors
def _taps(n: int, k: int, stride: int) -> int:
    """(output position, kernel tap) pairs of a SAME convolution along one
    axis of length ``n`` that read a real input element, not padding."""
    out = -(-n // stride)
    lo = max((out - 1) * stride + k - n, 0) // 2
    return sum(0 <= o * stride + d - lo < n
               for o in range(out) for d in range(k))


def conv(h: int, w: int, k: int, c_in: int, c_out: int, stride: int = 1
         ) -> int:
    """Operations of one SAME ``k``x``k`` convolution over an ``h``x``w``
    input; taps that fall on the zero padding do no work."""
    return 2 * _taps(h, k, stride) * _taps(w, k, stride) * c_in * c_out


def cnn_forward(h: int, w: int, c_in: int, widths, blocks: int,
                rep_dim: int) -> int:
    """Operations of one row through the WideResNet-style extractor."""
    ops = conv(h, w, 3, c_in, widths[0])                       # stem
    prev = widths[0]
    for s, width in enumerate(widths):
        for b in range(blocks):
            stride = 2 if (b == 0 and s > 0) else 1
            ops += conv(h, w, 3, prev, width, stride)          # conv1
            if prev != width:
                ops += conv(h, w, 1, prev, width, stride)      # 1x1 proj
            h, w = -(-h // stride), -(-w // stride)
            ops += conv(h, w, 3, width, width)                 # conv2
            prev = width
    return ops + 2 * prev * rep_dim                            # dense head


def dense_forward(dims) -> int:
    """Operations of one row through a dense stack of widths ``dims``."""
    return sum(2 * a * b for a, b in zip(dims[:-1], dims[1:]))


def extractor_forward(config: dict, party: int) -> int:
    """One row through party ``party``'s extractor of a benchmark config."""
    sc = config["scenario"]
    if sc["modality"] == "image":
        size = sc["gen_params"].get("image_size", 32)
        channels = sc["gen_params"].get("channels", 3)
        k = sc["num_parties"]
        w = size // k + (size - (size // k) * k if party == k - 1 else 0)
        return cnn_forward(size, w, channels, sc["widths"],
                           sc["blocks_per_stage"], sc["rep_dim"])
    d_in = sc["feature_sizes"][party]
    return dense_forward([d_in] + list(sc["hidden"]) + [sc["rep_dim"]])


# ------------------------------------------------------------- kernels
def kmeans_assign(batch: int, n: int, c: int, d: int) -> tuple:
    """(ops, bytes) of the batched assignment kernel: distances of n rows to
    c centers in d dimensions; reads rows and centers, writes int32 labels."""
    ops = batch * 2 * n * c * d
    nbytes = batch * 4 * (n * d + c * d + n)
    return ops, nbytes


def sdpa(batch: int, n_u: int, n_o: int, d: int, d_b: int) -> tuple:
    """(ops, bytes) of Eq. 10 for ``batch`` entries: scores (n_u x n_o x d)
    and the weighted sum (n_u x n_o x d_b); reads queries, keys, values,
    writes the estimates."""
    ops = batch * 2 * n_u * n_o * (d + d_b)
    nbytes = batch * 4 * (n_u * d + n_o * d + n_o * d_b + n_u * d_b)
    return ops, nbytes


def kmeans_search(batch: int, n: int, c: int, d: int, iters: int,
                  restarts: int) -> int:
    """Operations of the k-means++ seeding and Lloyd iterations that precede
    the kernel's assignment: per restart, c seeding distance passes and, per
    iteration, a distance pass and a center update, plus the inertia pass."""
    per_restart = (c + iters * 2 + 1) * 2 * n * c * d
    return batch * restarts * per_restart


# ------------------------------------------------------ protocol calls
def split_sizes(config: dict) -> dict:
    """Row counts of the vertical split the program draws for a config: a
    ``test_fraction`` test split, N_o aligned rows, the rest in equal
    private pools."""
    sc = config["scenario"]
    n = int(sc["num_samples"])
    n_test = int(n * config.get("test_fraction", 0.2))
    n_o = int(sc["overlap"])
    k = int(sc.get("num_parties", 2))
    return {"test": n_test, "overlap": n_o,
            "pool": (n - n_test - n_o) // k, "parties": k}


def protocol_call(config: dict, method: str, entries: int) -> dict:
    """Model operations of one protocol call over ``entries`` stacked
    seed entries, by phase. ``method`` is ``one_shot`` or ``few_shot``."""
    sc, pc = config["scenario"], config["protocol"]
    sizes = split_sizes(config)
    k, n_o, n_u, n_test = (sizes["parties"], sizes["overlap"], sizes["pool"],
                           sizes["test"])
    c = int(sc["gen_params"].get("num_classes", 2))
    rep = int(sc["rep_dim"])
    bs = int(pc.get("batch_size", 32))
    mu = int(pc.get("unlabeled_ratio", 2))
    e_c, e_s = int(pc["client_epochs"]), int(pc["server_epochs"])
    f = [extractor_forward(config, p) for p in range(k)]
    local_head = 2 * rep * c
    joint_head = 2 * k * rep * c

    def ssl(n_lab):
        b = min(bs, n_lab)
        steps = e_c * (n_lab // b)
        return sum(steps * (3 * b + mu * b + 3 * mu * b) * (fk + local_head)
                   for fk in f)

    def fit(head, rows):
        b = min(bs, rows)
        return e_s * (rows // b) * 3 * head * b

    phases = {
        "extract": 2 * sum(f) * n_o,
        "partial_grads": 3 * joint_head * n_o,
        "kmeans": (kmeans_search(k, n_o, c, rep, pc.get("kmeans_iters", 25),
                                 4) + kmeans_assign(k, n_o, c, rep)[0]),
        "ssl": ssl(n_o),
        "server_fit": fit(joint_head, n_o),
        "eval": (sum(f) + joint_head) * n_test,
    }
    if method == "few_shot":
        phases["extract"] += sum(f) * (n_u + n_o)
        phases["server_fit"] += fit(joint_head, n_o) + k * fit(local_head,
                                                               n_o)
        phases["sdpa"] = k * (k - 1) * sdpa(1, n_u, n_o, rep, rep)[0]
        phases["gate"] = k * (local_head + joint_head) * n_u
        phases["ssl"] += ssl(n_o + n_u)
        phases["eval"] *= 2          # the one-shot pass inside evaluates too
    return {name: ops * entries for name, ops in phases.items()}
