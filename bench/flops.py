"""The work each piece needs: operations and bytes, from shapes.

These count what the algorithm needs, not what a given kernel moves: the
paged attention kernels need each lane's K/V over its own length read once,
plus the queries in and the outputs out.  A kernel that re-reads the pool
or copies it around is slower against the same count, so its roofline
share falls; a rewrite that stops doing so raises it without the count
going stale.

``dims`` is one model of an arm (the whole model, or one SEMANTIC branch)
as ``reference.dense.branch_dims`` gives it; an arm runs ``branches`` of
them side by side.
"""
from __future__ import annotations

import json
from pathlib import Path

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def peaks(device_kind: str) -> dict:
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"bench/peaks.json has {sorted(table)}")
    return table[device_kind]


def matmul_params(dims: dict) -> int:
    """Weights one token multiplies through in one model of the arm: the
    attention projections and MLP of every layer, and the head (the token
    table is a gather, not a matmul)."""
    d, h, kv, hd, ff = (dims[k] for k in ("d", "h", "kv", "hd", "ff"))
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    mlp = (3 if dims["mlp"] == "swiglu" else 2) * d * ff
    return dims["n_layers"] * (attn + mlp) + d * dims["vocab"]


def token_flops(dims: dict, context: float) -> float:
    """Model FLOPs of one token at ``context`` positions (itself included)
    through the whole arm: 2 per matmul weight plus QK^T and PV."""
    attn = 4 * dims["h"] * dims["hd"] * context * dims["n_layers"]
    return dims["branches"] * (2 * matmul_params(dims) + attn)


def decode_attn_cost(dims: dict, context: float):
    """(flops, bytes) of the paged decode attention for one lane and one
    token at ``context`` cached positions, over every layer and branch."""
    isz = ITEMSIZE[dims["dtype"]]
    h, kv, hd = dims["h"], dims["kv"], dims["hd"]
    per_layer_bytes = context * kv * hd * 2 * isz + 2 * h * hd * isz
    per_layer_flops = 4 * h * hd * context
    n = dims["n_layers"] * dims["branches"]
    return n * per_layer_flops, n * per_layer_bytes


def prefill_attn_cost(dims: dict, start: int, n_tok: int):
    """(flops, bytes) of the paged chunked-prefill attention for one lane
    committing ``n_tok`` tokens at positions ``start..start+n_tok-1``: the
    K/V of the whole prefix read once, the chunk's queries in and outputs
    out, causal scores over the prefix and the in-chunk triangle."""
    isz = ITEMSIZE[dims["dtype"]]
    h, kv, hd = dims["h"], dims["kv"], dims["hd"]
    end = start + n_tok
    per_layer_bytes = end * kv * hd * 2 * isz + 2 * n_tok * h * hd * isz
    # sum over query positions p of (p + 1) keys
    keys = n_tok * start + n_tok * (n_tok + 1) // 2
    per_layer_flops = 4 * h * hd * keys
    n = dims["n_layers"] * dims["branches"]
    return n * per_layer_flops, n * per_layer_bytes


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict):
    """Least time the chip could take for the work, over the time it took,
    in percent, and which bound sets it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
