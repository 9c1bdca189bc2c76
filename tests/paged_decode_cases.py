"""Cases for the paged decode kernel's parity tests (test_decode, test_quant).

A case is a stacked pool ``[N, P, bs, K, hd]`` in the kernel's view, a
query and a new K/V row per lane, block tables over shuffled pages (the
first ``alias`` blocks shared by every lane, as a prefix hit makes them),
and write slots: an active lane writes the slot after its last token, an
inactive one the null block.  ``check`` holds a result to the contract:
the attention equals a dense reference over the updated pool, and the pool
differs from its input exactly at the active lanes' write slots (the null
block of the layer is the inactive lanes' scratch).
"""
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.decode import quantize_kv
from repro.kernels import ref
from repro.decode.paged_model import pages_view
from repro.kernels.paged_decode_attention import paged_decode_attention


@dataclass
class Case:
    q: jax.Array
    new: dict
    pool: dict
    layer: int
    tables: np.ndarray
    lengths: np.ndarray
    active: np.ndarray
    wb: np.ndarray
    wo: np.ndarray
    branches: int = 0

    @property
    def args(self):
        return (self.q, self.new, self.pool, jnp.int32(self.layer),
                jnp.asarray(self.tables), jnp.asarray(self.lengths),
                jnp.asarray(self.wb), jnp.asarray(self.wo))


def make_case(rng, h, kh, hd, bs, nb, lengths, *, active=None,
              dtype=jnp.float32, int8=False, alias=0, n_layers=3, layer=1,
              branches=0):
    """``lengths``: tokens per lane, the new one included for an active
    lane; ``active`` defaults to every lane of nonzero length."""
    b = len(lengths)
    p = 1 + b * nb
    lead = (branches,) if branches else ()
    normal = lambda *s: jnp.asarray(rng.normal(size=lead + s), jnp.float32)
    kv = {"k": normal(n_layers, p, bs, kh, hd),
          "v": normal(n_layers, p, bs, kh, hd)}
    rows = {"k": normal(b, kh, hd), "v": normal(b, kh, hd)}
    if int8:
        pool, new = {}, {}
        for key in ("k", "v"):
            pool[key], pool[key + "_scale"] = quantize_kv(kv[key])
            new[key], new[key + "_scale"] = quantize_kv(rows[key])
    else:
        pool = {key: x.astype(dtype) for key, x in kv.items()}
        new = {key: x.astype(dtype) for key, x in rows.items()}
    tables = rng.permutation(np.arange(1, p))[:b * nb].reshape(b, nb)
    tables[:, :alias] = tables[0, :alias]
    lengths = np.asarray(lengths, np.int32)
    active = lengths > 0 if active is None else np.asarray(active)
    last = np.maximum(lengths - 1, 0)
    wb = np.where(active, tables[np.arange(b), last // bs], 0)
    wo = np.where(active, last % bs, 0)
    # as in serving, a lane never writes a block it shares
    assert not np.isin(wb[active], tables[:, :alias]).any()
    return Case(q=normal(b, h, hd).astype(dtype), new=new,
                pool=pages_view(pool), layer=layer,
                tables=tables.astype(np.int32), lengths=lengths,
                active=active, wb=wb.astype(np.int32),
                wo=wo.astype(np.int32), branches=branches)


def run_kernel(case):
    """The Pallas kernel in interpret mode, jitted, vmapped over the branch
    axis when the case has one."""
    q, new, pool, *rest = case.args
    call = lambda q, new, pool: paged_decode_attention(
        q, new, pool, *rest, interpret=True)
    if case.branches:
        call = jax.vmap(call)
    return jax.jit(call)(q, new, pool)


def run_ref(case):
    """``ref.paged_decode_attention_ref`` on the same case."""
    q, new, pool, *rest = case.args
    call = lambda q, new, pool: ref.paged_decode_attention_ref(
        q, new, pool, *rest)
    return (jax.vmap(call) if case.branches else call)(q, new, pool)


def expected_pool(case):
    """The input pool with each active lane's row at its write slot."""
    out = {}
    for key, leaf in case.pool.items():
        exp = np.array(leaf)
        lead = (slice(None),) if case.branches else ()
        rows = np.asarray(case.new[key]).astype(exp.dtype)
        slot = exp.shape[len(lead) + 3:]
        rows = rows.reshape(rows.shape[:len(lead) + 1] + slot)
        for lane in np.nonzero(case.active)[0]:
            exp[lead + (case.layer, case.wb[lane], case.wo[lane])] = \
                rows[lead + (lane,)]
        out[key] = exp
    return out


def dense_attention(case, pool):
    """q against each lane's pages of ``case.layer`` gathered into a dense
    cache (``decode_attention_ref``); zeros for a lane of length 0."""
    hd = case.q.shape[-1]
    outs = []
    for c in range(case.branches or 1):
        pick = (lambda x: x[c]) if case.branches else (lambda x: x)
        kv = []
        for key in ("k", "v"):
            x = pick(pool[key])[case.layer]
            x = jnp.asarray(x).reshape(x.shape[:2] + (-1, hd))
            if key + "_scale" in pool:
                x = x.astype(jnp.float32) * pick(
                    pool[key + "_scale"])[case.layer][..., None]
            x = x[case.tables]                    # [B, NB, bs, K, hd]
            kv.append(x.reshape(x.shape[0], -1, *x.shape[3:]))
        out = ref.decode_attention_ref(
            pick(case.q).astype(jnp.float32), *kv,
            jnp.asarray(case.lengths))
        outs.append(jnp.where(case.lengths[:, None, None] > 0, out, 0))
    return np.asarray(jnp.stack(outs) if case.branches else outs[0])


def check(case, out, pool, atol=1e-3, rtol=None):
    """``(out, pool)`` from the kernel, held to the contract (``rtol``
    defaults to ``atol``)."""
    exp_pool = expected_pool(case)
    n_layers, p = exp_pool["k"].shape[(1 if case.branches else 0):][:2]
    keep = np.ones((n_layers, p), bool)
    keep[case.layer, 0] = False                   # inactive lanes' scratch
    at = (slice(None),) if case.branches else ()
    for key, exp in exp_pool.items():
        np.testing.assert_array_equal(np.asarray(pool[key])[at + (keep,)],
                                      exp[at + (keep,)], err_msg=key)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               dense_attention(case, exp_pool), atol=atol,
                               rtol=atol if rtol is None else rtol)
