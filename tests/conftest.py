import os

# Tests that need a multi-device mesh live in test_dist.py, which re-execs
# with forced host devices.  Everything else sees the single real CPU device
# (per the dry-run contract: only dryrun.py forces 512).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def tiny_cfg():
    """Shrunken stablelm for fast in-process dist/serving tests."""
    from repro.configs.base import get_config
    return get_config("stablelm-1.6b").reduced().replace(
        d_model=64, n_heads=2, n_kv_heads=2, head_dim=32, d_ff=128,
        vocab_size=128)


@pytest.fixture(scope="session")
def tiny_mesh():
    """1x1 mesh on the single CPU device."""
    import jax
    return jax.make_mesh((1, 1), ("data", "model"))
