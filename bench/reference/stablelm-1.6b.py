"""Plain reference of stablelm-1.6b as served: the dense decoder of
``reference/dense.py`` with LayerNorm and a SwiGLU MLP,
silu(x Wg) * (x Wu) Wd."""
from reference import dense

MLP = "swiglu"


def logits_at(cfg, arm, seed, tokens, pos, *, fp8=False):
    if (cfg["mlp_type"], cfg["norm_type"]) != (MLP, "layernorm"):
        raise ValueError("not this configuration's layer equations")
    return dense.logits_at(cfg, arm, seed, tokens, pos, fp8=fp8)
