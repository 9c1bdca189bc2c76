"""Decide ``correct``: the served tokens against the plain reference.

Once the window has closed and the served model is freed, a sample of the
finished requests is drawn from the seed: on each arm the longest request
(prompt + output) and others at random.  The reference runs once over each
prompt followed by its served tokens, and at each served position reads the
gap by which the served token's logit lies below the reference's best.  The
widest gap of each arm is compared with that arm's limit in
``bench/limits/<cell>.json``.  Decoding is greedy, so a sound run serves
tokens whose reference logit is the best up to rounding.

The control (``control_gaps``) puts the reference computed in float8 in the
program's place: at the same positions it reads the gap of the token the
float8 forward puts first.
"""
from __future__ import annotations

import json
from typing import Dict, List

import numpy as np

ARM_NAMES = {0: "layer", 1: "semantic"}


def load_limits(cell) -> dict:
    path = cell.bench_dir / "limits" / f"{cell.name}.json"
    return json.loads(path.read_text())


def draw_sample(rec, per_arm: int, seed: int) -> Dict[int, List[dict]]:
    """Finished requests per arm: the longest, then others drawn from the
    seed, ``per_arm`` in all (fewer if fewer finished)."""
    from gen.common import seed_rng
    from measure import served
    rng = seed_rng(seed, 3)
    out = {}
    for arm in (0, 1):
        done = sorted((r for r in rec.requests.values()
                       if r["arm"] == arm and served(r)),
                      key=lambda r: r["rid"])
        if not done:
            out[arm] = []
            continue
        longest = max(done, key=lambda r: (len(r["prompt"]) + len(r["out"]),
                                           -r["rid"]))
        rest = [r for r in done if r is not longest]
        pick = rng.permutation(len(rest))[:per_arm - 1]
        out[arm] = [longest] + [rest[i] for i in sorted(pick)]
    return out


def _rows(sample: List[dict], rows: int, seq: int, width: int):
    """Token rows (prompt + served tokens but the last), the positions whose
    logits predict each served token, and the served tokens (-1 = pad)."""
    toks = np.zeros((rows, seq), np.int32)
    pos = np.zeros((rows, width), np.int32)
    want = np.full((rows, width), -1, np.int32)
    for i, r in enumerate(sample):
        p, o = np.asarray(r["prompt"]), np.asarray(r["out"])
        full = np.concatenate([p, o[:-1]])
        toks[i, :len(full)] = full
        pos[i, :len(o)] = len(p) - 1 + np.arange(len(o))
        want[i, :len(o)] = o
    return toks, pos, want


def _gaps(logits, pick):
    """Per position: best reference logit minus the reference logit of
    ``pick`` (device arrays), as numpy."""
    import jax.numpy as jnp
    best = jnp.max(logits, axis=-1)
    safe = jnp.maximum(pick, 0)
    at = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    return np.asarray(best - at), np.asarray(jnp.argmax(logits, axis=-1))


def served_gaps(cell, weight_seed: int, sample: Dict[int, List[dict]],
                *, control: bool = False) -> Dict[str, dict]:
    """Widest gap per arm of the served tokens (or, with ``control``, of
    the float8 reference's first choices) below the f32 reference's best."""
    import jax.numpy as jnp
    ref = cell.reference()
    cfg = cell.config
    rows = cell.traffic["sample_per_arm"]
    seq = cfg["serving"]["cache_len"]
    width = max(cell.traffic["output_len"]["max"], 1)
    out = {}
    for arm, reqs in sample.items():
        if not reqs:
            continue
        toks, pos, want = _rows(reqs, rows, seq, width)
        logits = ref.logits_at(cfg, arm, weight_seed, toks, pos)
        if control:
            low = ref.logits_at(cfg, arm, weight_seed, toks, pos, fp8=True)
            pick = jnp.argmax(low, axis=-1)
            del low
        else:
            pick = jnp.asarray(want)
        gaps, argmax = _gaps(logits, pick)
        del logits
        mask = want >= 0
        out[ARM_NAMES[arm]] = {
            "gap_max": float(gaps[mask].max()),
            "tokens": int(mask.sum()),
            "agree": float(np.mean((np.asarray(pick) == argmax)[mask])),
            "in_vocab": bool(np.all((want[mask] >= 0)
                                    & (want[mask] < cfg["vocab_size"]))),
        }
    return out


def checks(rec, sample, gaps: Dict[str, dict],
           limits: dict) -> Dict[str, dict]:
    """Every number compared, with its limit."""
    out = {}
    short = sum(len(r["out"]) != r["max_new"]
                for reqs in sample.values() for r in reqs)
    out["short_outputs"] = {"value": short, "limit": 0}
    failed = sum(1 for r in rec.requests.values()
                 if "finish" in r and r["failed"])
    out["failed_requests"] = {"value": failed, "limit": 0}
    for arm, g in gaps.items():
        out[f"gap_{arm}"] = {"value": g["gap_max"],
                             "limit": limits[f"gap_{arm}"]}
        out[f"vocab_{arm}"] = {"value": int(not g["in_vocab"]), "limit": 0}
    return out


def is_correct(chk: Dict[str, dict], sample) -> bool:
    return all(sample.values()) and all(
        c["value"] <= c["limit"] for c in chk.values())
