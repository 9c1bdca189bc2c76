"""Plain reference of starcoder2-15b as served: the dense decoder of
``reference/dense.py`` with LayerNorm and a GELU MLP (tanh form, the
published ``gelu_pytorch_tanh``), gelu(x Wu) Wd, and grouped-query
attention with 48 query heads over 4 kv heads."""
from reference import dense

MLP = "gelu"


def logits_at(cfg, arm, seed, tokens, pos, *, fp8=False):
    if (cfg["mlp_type"], cfg["norm_type"]) != (MLP, "layernorm"):
        raise ValueError("not this configuration's layer equations")
    return dense.logits_at(cfg, arm, seed, tokens, pos, fp8=fp8)
